"""The one generator of traffic.  A traffic mix is a data file under
``perfbench/traffic/``; this module turns its parameters and ``--seed``
into requests and arrival times.  A new mix is a new data file.

Every seed gets the same work in another order: the set of request sizes
(and of arrival gaps) is fixed by the file -- ``POPULATION`` quantiles of
the stated distribution, so the set has the distribution's shape exactly
-- and the seed permutes it and draws the token ids.  A run cycles through
the permuted set, so two seeds that complete the same number of requests
have done the same work.

A mix with a ``cycle`` fixes the order too: ``cycle`` arrivals, each a gap
with its sizes, in one sequence that the file's numbers decide, and the
seed chooses where in it the run starts (and draws the token ids).  A
queue's tail is made by the few moments at which short gaps meet long
requests; permuted anew by every seed, those moments differ from seed to
seed, and the share of arrivals with a first token inside the mix's limit
(``ttft_ok_share``, the open loop's end-to-end metric) differs with them
(PERF.md, PR 27).  A window of ``cycle / rate_per_s`` seconds (the
benchmark's ``run_seconds``; the mixes' test holds the file to it) sees
each arrival of the sequence exactly once, whatever the seed; a shorter
``--seconds`` sees a consecutive part of it, a longer one some arrivals
twice.
"""
import itertools
import math
import statistics
import threading

import numpy as np

_STD_NORMAL = statistics.NormalDist()
# how many request sizes (and arrival gaps) a mix's set holds, and the seed
# of what is drawn once for every run: the pairing of prompt and output
# lengths, and the gaps
POPULATION = 96
_POPULATION_SEED = 0


def _seed32(seed):
    return int(seed) % (2 ** 32)


def length_population(dist, count=POPULATION):
    """``count`` integer lengths at the stratified quantiles
    ``(i + 0.5) / count`` of a clipped log-normal: ``{"median", "sigma",
    "min", "max"}``."""
    out = []
    for i in range(count):
        z = _STD_NORMAL.inv_cdf((i + 0.5) / count)
        n = int(round(dist["median"] * math.exp(dist["sigma"] * z)))
        out.append(min(max(n, int(dist["min"])), int(dist["max"])))
    return out


def gap_population(rate_per_s, count=POPULATION):
    """``count`` inter-arrival gaps in seconds of a Poisson process: one
    fixed exponential draw, scaled so that the set's mean is exactly
    ``1 / rate_per_s``."""
    rng = np.random.RandomState(_POPULATION_SEED)
    gaps = rng.exponential(1.0, size=count)
    return list(gaps * (count / float(rate_per_s) / gaps.sum()))


class Request:
    __slots__ = ("index", "prompt", "max_new")

    def __init__(self, index, prompt, max_new):
        self.index, self.prompt, self.max_new = index, prompt, max_new


class ServeTraffic:
    """Requests (and, in an open loop, arrival gaps) of one serving mix
    under one seed.  ``next_request`` may be called from many client
    threads."""

    def __init__(self, mix, vocab_size, seed):
        count = int(mix.get("cycle", POPULATION))
        prompts = length_population(mix["prompt_len"], count)
        outputs = length_population(mix["output_len"], count)
        # pair the two independently
        fixed = np.random.RandomState(_POPULATION_SEED)
        fixed.shuffle(outputs)
        rng = np.random.RandomState(_seed32(seed))
        if "cycle" in mix:
            fixed.shuffle(prompts)
            start = int(rng.randint(count))
            order = gap_order = [(start + i) % count for i in range(count)]
        else:
            order = rng.permutation(count)
            gap_order = rng.permutation(count) if "rate_per_s" in mix \
                else None
        self.sizes = [(prompts[i], outputs[i]) for i in order]
        self._vocab = int(vocab_size)
        self._rng = rng
        self._lock = threading.Lock()
        self._index = itertools.count()
        self.gaps = None
        if "rate_per_s" in mix:
            gaps = gap_population(mix["rate_per_s"], count)
            self.gaps = [gaps[i] for i in gap_order]

    def next_request(self):
        with self._lock:
            i = next(self._index)
            n_prompt, n_out = self.sizes[i % len(self.sizes)]
            prompt = self._rng.randint(0, self._vocab, n_prompt).tolist()
        return Request(i, prompt, n_out)

    def arrival_offsets(self):
        """Seconds after the start at which request 0, 1, 2, ... is due,
        without end."""
        t = 0.0
        for gap in itertools.cycle(self.gaps):
            t += gap
            yield t
