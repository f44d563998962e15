"""Measure a serving cell: requests through the program's generative
serving path, in a closed loop (each client sends its next request when
its stream ends) or an open loop (arrivals on a schedule, whatever the
system does), with every time taken on the client's side of the stream.

Set-up deploys the model, sends a request through every prefill bucket and
enough streams through every decode bucket, decides ``correct`` on a
seeded sample of streams, and lets the load run for the mix's
``preroll_s`` before the window opens, so that every client's first
request is admitted outside it.  Whether the window then holds a steady
state depends on how long a request lasts against it (the mix's file says
what holds today).

``attempted`` counts every request in flight at some moment of the window,
whenever it was due; ``failed`` those of them that were shed, raised an
error, ended short of their length, or stopped getting tokens.  All but a
shed request also make the run incorrect.
"""
import threading
import time

import numpy as np

from . import stats, xplane
from .spec import SpecError
from .traffic import ServeTraffic

MODEL_NAME = "perfbench_model"
DRAIN_TIMEOUT_S = 60.0


class StreamRecord:
    """One request as its client saw it."""

    def __init__(self, request, t_due):
        self.index = request.index
        self.prompt_len = len(request.prompt)
        self.max_new = request.max_new
        self.t_due = t_due
        self.t_submit = None
        self.token_times = []
        self.tokens = []
        self.t_end = None           # when the client stopped reading
        self.failed = None  # "shed" | "error: ..." | "short" | "stalled"
        self.stream = None
        self.done = threading.Event()


class Load:
    """Sends requests and reads their streams, one reader thread to a
    stream in flight: the served interface is a blocking iterator."""

    def __init__(self, registry, traffic, span):
        self._registry = registry
        self._traffic = traffic
        self._span = span           # run.span: a host span in a traced run
        self.records = []
        self._lock = threading.Lock()
        self.stop = threading.Event()
        self._threads = []

    def submit(self, request, t_due):
        """Hand one request (timed from ``t_due``) to the served interface;
        the record holds its stream, or ``failed = "shed"``."""
        from mxnet_tpu.serving.batcher import ServingQueueFull
        rec = StreamRecord(request, t_due)
        with self._lock:
            self.records.append(rec)
        rec.t_submit = time.perf_counter()
        try:
            with self._span("perfbench.submit"):
                rec.stream = self._registry.generate(
                    MODEL_NAME, request.prompt, request.max_new)
        except ServingQueueFull:
            rec.failed = "shed"
            rec.t_end = time.perf_counter()
            rec.done.set()
        return rec

    def read(self, rec):
        """Read a submitted request's stream to its end in the calling
        thread."""
        if rec.stream is None:
            return rec
        try:
            for token in rec.stream:
                rec.token_times.append(time.perf_counter())
                rec.tokens.append(token)
        except Exception as e:          # the stream re-raises engine errors
            rec.failed = "error: %r" % (e,)
        if rec.failed is None and not rec.stream.cancelled \
                and len(rec.tokens) != rec.max_new:
            rec.failed = "short"
        rec.t_end = time.perf_counter()
        rec.done.set()
        return rec

    def send(self, request, t_due):
        """Submit one request and read its stream to the end in the
        calling thread."""
        return self.read(self.submit(request, t_due))

    def _spawn(self, target, *args):
        t = threading.Thread(target=target, args=args, daemon=True)
        self._threads.append(t)
        t.start()

    def start_closed(self, clients):
        def client():
            while not self.stop.is_set():
                self.send(self._traffic.next_request(), time.perf_counter())
        for _ in range(clients):
            self._spawn(client)

    def start_open(self, t_begin):
        """Arrivals from ``t_begin`` on; returns nothing, runs until
        ``stop``.  The dispatcher submits each request itself as it falls
        due (a submit does not block: it reserves the cache and queues) and
        starts a thread only to read the stream, so that no thread's start
        stands between the due time and the submit: with the submit inside
        the new thread the generator ran 0.9 ms late at the median, 5% of
        a time to first token of 18 ms (PERF.md, PR 27).  Lateness is
        ``t_submit - t_due`` of each record."""
        def dispatcher():
            for offset in self._traffic.arrival_offsets():
                t_due = t_begin + offset
                wait = t_due - time.perf_counter()
                if wait > 0 and self.stop.wait(wait):
                    return
                if self.stop.is_set():
                    return
                rec = self.submit(self._traffic.next_request(), t_due)
                self._spawn(self.read, rec)
        self._spawn(dispatcher)

    def wait_for_a_token(self, since, timeout=10.0):
        """Return once some stream has a token read at or after ``since``
        (or nothing is in flight, or ``timeout`` passed)."""
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            with self._lock:
                records = list(self.records)
            live = [r for r in records if not r.done.is_set()]
            if not live or any(r.token_times and r.token_times[-1] >= since
                               for r in records):
                return
            time.sleep(0.002)

    def finish(self, t_close):
        """Stop sending.  Every request due before ``t_close`` is waited
        for until it has a token read at or after ``t_close`` (or has
        ended): one waiting for a slot shows its time to the first token,
        and one that had tokens and gets no more within the drain time is
        "stalled" -- the engine dropped it.  Then cancel what is in flight
        and wait for the readers."""
        self.stop.set()
        deadline = time.perf_counter() + DRAIN_TIMEOUT_S
        with self._lock:
            records = list(self.records)
        for rec in records:
            while rec.t_due < t_close and not rec.done.is_set() \
                    and not (rec.token_times
                             and rec.token_times[-1] >= t_close):
                if time.perf_counter() >= deadline:
                    if rec.token_times:
                        rec.failed = "stalled"
                    break
                time.sleep(0.005)
        for rec in records:
            if rec.stream is not None and not rec.done.is_set():
                rec.stream.cancel()
        for t in self._threads:
            t.join(max(0.0, deadline + 5.0 - time.perf_counter()))
        return [t for t in self._threads if t.is_alive()]


class TelemetrySink:
    """Keeps the ``sample`` records of the program's telemetry timers in
    memory (the timers themselves keep only a coarse histogram)."""

    def __init__(self):
        self.samples = {}

    def write(self, record):
        if record.get("kind") == "sample":
            self.samples.setdefault(record["name"], []).append(
                (record["t"], record["value"]))


def _warm_buckets(load, cfg, traffic_vocab, rng):
    """One request through each prefill bucket, then for each decode
    bucket as many concurrent streams as it holds, half of them one token
    longer, so that the batch steps once at the bucket's size and once at
    half of it.  (The engine admits every pending request before it steps,
    and a prefill takes longer than the submits, so the streams of a burst
    decode together.)  ``DecodeEngine.warmup()`` compiles ahead of time but
    executes nothing."""
    from .traffic import Request
    dep = cfg["deployment"]

    def burst(sizes):
        threads = []
        for n_prompt, n_out in sizes:
            req = Request(-1, rng.randint(0, traffic_vocab,
                                          n_prompt).tolist(), n_out)
            t = threading.Thread(target=load.send,
                                 args=(req, time.perf_counter()))
            t.start()
            threads.append(t)
        for t in threads:
            t.join()

    burst([(b, 2) for b in dep["prefill_buckets"]])
    n_prompt = max(1, min(dep["prefill_buckets"]) // 2)
    for slots in dep["decode_buckets"]:
        burst([(n_prompt, 2 + i % 2) for i in range(slots)])


def check_streams(run, model, params, load, traffic):
    """``correct``: a seeded sample of streams through the engine, every
    generated token judged against the benchmark's float32 GPT-2 forward
    over the stream's own tokens (teacher forced) by
    ``chip_smoke.py``'s margin rule; every stream reaches its length.
    The configuration's ``check.references`` lists the arithmetic of each
    reference forward and the tolerance held against it."""
    import jax
    import jax.numpy as jnp
    fam, cfg = run.family, run.cfg
    chk = cfg["check"]
    reqs = [traffic.next_request() for _ in range(chk["streams"])]
    width = chk["width"]
    for r in reqs:
        r.max_new = min(r.max_new, chk["max_new"])
        if len(r.prompt) + r.max_new > width:
            # cut to ``width`` it would be judged on empty slices and pass
            raise SpecError(
                "check stream %d is %d tokens long (prompt %d + %d new), "
                "the configuration's check.width is %d"
                % (r.index, len(r.prompt) + r.max_new, len(r.prompt),
                   r.max_new, width))
    threads = [threading.Thread(target=load.send,
                                args=(r, time.perf_counter()))
               for r in reqs]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # warm-up requests carry index -1, the traffic's own count from 0
    recs = {r.index: r for r in load.records if r.index >= 0}
    ref_params = fam.reference_params(params, cfg)
    references = [(ref, fam.make_reference(cfg, ref["precision"]),
                   {"gap": 0.0, "margin": 0.0, "exact": 0})
                  for ref in chk["references"]]
    system_forward = jax.jit(model.full_logits)
    judge = jax.jit(_judge)
    judged = 0
    for i in range(0, len(reqs), chk["chunk"]):
        part = reqs[i:i + chk["chunk"]]
        tokens = np.zeros((len(part), width), np.int32)
        for j, r in enumerate(part):
            rec = recs[r.index]
            if rec.failed or len(rec.tokens) != r.max_new:
                run.incorrect("check stream %d ended after %d of %d tokens "
                              "(%s)" % (r.index, len(rec.tokens), r.max_new,
                                        rec.failed))
            seq = r.prompt + rec.tokens
            tokens[j, :len(seq)] = seq
        tokens = jnp.asarray(tokens)
        sys_logits = system_forward(params, tokens)
        judged += sum(len(recs[r.index].tokens) for r in part)
        for _ref, reference_forward, worst in references:
            margin, equal, gap = (np.asarray(a) for a in judge(
                reference_forward(ref_params, tokens), sys_logits, tokens))
            for j, r in enumerate(part):
                n, m = len(r.prompt), len(recs[r.index].tokens)
                # position n-1+k of the forward predicts generated token k
                worst["margin"] = max([worst["margin"]]
                                      + margin[j, n - 1:n - 1 + m].tolist())
                worst["gap"] = max([worst["gap"]] + gap[j, :n + m].tolist())
                worst["exact"] += int(equal[j, n - 1:n - 1 + m].sum())
    for ref, _forward, worst in references:
        tol = ref["logit_tolerance"]
        run.log.line(event="reference_check", streams=len(reqs),
                     reference_precision=ref["precision"],
                     tokens_judged=judged,
                     tokens_equal_reference_argmax=worst["exact"],
                     worst_margin_below_reference_max=worst["margin"],
                     logit_gap_system_vs_reference=worst["gap"],
                     logit_tolerance=tol)
        run.compare("logit_gap." + ref["precision"], worst["gap"], tol,
                    "the program's logits against the %s reference"
                    % ref["precision"])
        # an argmax over logits within tol of the reference lies within
        # 2*tol of the reference's maximum
        run.compare("token_margin." + ref["precision"], worst["margin"],
                    2 * tol, "a generated token's logit below the %s "
                    "reference's maximum" % ref["precision"])


def _judge(ref_logits, sys_logits, tokens):
    """On the device, so that no (batch, seq, vocab) array crosses to the
    host: per position, how far the reference's logit of the token that
    follows lies below the reference's maximum, whether that token is the
    reference's argmax, and the largest gap between the two forwards."""
    import jax.numpy as jnp
    nxt = tokens[:, 1:]
    head = ref_logits[:, :-1]
    chosen = jnp.take_along_axis(head, nxt[..., None], axis=-1)[..., 0]
    return (head.max(axis=-1) - chosen, head.argmax(axis=-1) == nxt,
            jnp.abs(sys_logits - ref_logits).max(axis=-1))


def _window_metrics(run, records, t0, t1):
    """End-to-end numbers of the window [t0, t1) from the client-side
    records.  The rate is taken between two token arrivals, the first at
    or after ``t0`` and the first at or after ``t1``: tokens come in steps
    (one per live stream at once), and a window that cuts a step short
    at a fixed time counts a step more or less by chance -- 2.6% of a
    30 s window while a step takes 0.78 s."""
    in_window = [r for r in records if t0 <= r.t_due < t1]
    # in flight at some moment of the window, whenever it was due
    live = [r for r in records if r.t_due < t1
            and (r.t_end is None or r.t_end >= t0)]
    times = sorted(t for r in records for t in r.token_times if t >= t0)
    t_first = times[0] if times else t0
    t_last = next((t for t in times if t >= t1), t1)
    tokens = sum(1 for t in times if t_first <= t < t_last)
    gaps = [b - a for r in records
            for a, b in zip(r.token_times, r.token_times[1:])
            if t0 <= b < t1]
    # the longest stretch of the window in which no stream got a token: a
    # run that reads a tenth under its neighbours at the same gap between
    # tokens has a hole of seconds somewhere (PERF.md, PR 26 and 27)
    edges = [t0] + [t for t in times if t < t1] + [t1]
    silence, silence_at = max((b - a, a - t0)
                              for a, b in zip(edges, edges[1:]))
    ttft = [r.token_times[0] - r.t_due for r in in_window if r.token_times]
    late = [r.t_submit - r.t_due for r in in_window
            if r.t_submit is not None]
    failed = [r for r in live if r.failed]
    for r in failed:
        if r.failed != "shed":
            run.incorrect("request %d (%d of %d tokens): %s"
                          % (r.index, len(r.tokens), r.max_new, r.failed))
    mid = (t0 + t1) / 2.0
    halves = [[r.token_times[0] - r.t_due for r in in_window
               if r.token_times and lo <= r.t_due < hi]
              for lo, hi in ((t0, mid), (mid, t1))]
    waiting_at_end = sum(1 for r in in_window if not r.failed
                         and (not r.token_times or r.token_times[0] >= t1))
    run.attempted, run.failed = len(live), len(failed)
    run.window_s = t_last - t_first
    e2e = run.end_to_end
    e2e["serve_tokens_per_s"] = tokens / run.window_s
    if ttft:
        e2e["ttft_p95_ms"] = 1e3 * stats.percentile(ttft, 95)
    # the share of the window's arrivals whose first token came within the
    # mix's limit of when they were due; a shed or failed request misses
    limit_ms = run.mix.get("ttft_limit_ms")
    if limit_ms is not None and in_window:
        e2e["ttft_ok_share"] = 100.0 * sum(
            1 for t in ttft if 1e3 * t <= limit_ms) / len(in_window)
    if gaps:
        e2e["itl_p95_ms"] = 1e3 * stats.percentile(gaps, 95)
    e2e["setup_s"] = run.setup_seconds(t0)
    # context tokens the decode steps of the window attended over: the
    # k-th token of a stream (k >= 1; token 0 comes from the prefill) is
    # produced by a step whose context is the prompt and k earlier tokens
    context = sum(r.prompt_len + k for r in records
                  for k, t in enumerate(r.token_times)
                  if k >= 1 and t0 <= t < t1)
    busy = _in_flight_intervals(records, t0, t1)
    # what the rate's tokens cost the model: the decode tokens with their
    # contexts and the prompts whose first token is among them
    served = [(r.prompt_len, k) for r in records
              for k, t in enumerate(r.token_times) if t_first <= t < t_last]
    run.counters.update(
        itl_p95_ms=e2e.get("itl_p95_ms"), itl_samples=len(gaps),
        ttft_p50_ms=_ms(ttft, 50), ttft_samples=len(ttft),
        tokens_in_window=tokens,
        served_decode_tokens=sum(1 for _n, k in served if k >= 1),
        served_context_tokens=sum(n + k for n, k in served if k >= 1),
        served_prompt_lens=[n for n, k in served if k == 0],
        decode_context_tokens=context, in_flight_intervals=busy)
    run.log.measurement(
        "window", kind="serve", seconds=run.window_s,
        requests_in_flight=len(live), requests_due=len(in_window),
        requests_ended=sum(1 for r in live if r.t_end is not None
                           and r.t_end < t1),
        failed=len(failed),
        failures=sorted({r.failed for r in failed}),
        tokens=tokens, serve_tokens_per_s=e2e["serve_tokens_per_s"],
        ttft_samples=len(ttft), ttft_p50_ms=_ms(ttft, 50),
        ttft_p95_ms=_ms(ttft, 95),
        ttft_highest_supported_percentile=stats.highest_supported(len(ttft)),
        ttft_limit_ms=limit_ms, ttft_ok_share=e2e.get("ttft_ok_share"),
        ttft_slowest_ms=[round(1e3 * t, 1)
                         for t in sorted(ttft, reverse=True)[:16]],
        # a backlog that grows shows as a second half slower than the first
        ttft_p50_ms_first_half=_ms(halves[0], 50),
        ttft_p50_ms_second_half=_ms(halves[1], 50),
        without_first_token_at_window_end=waiting_at_end,
        itl_samples=len(gaps), itl_p50_ms=_ms(gaps, 50),
        itl_p95_ms=_ms(gaps, 95), itl_p99_ms=_ms(gaps, 99),
        itl_max_ms=1e3 * max(gaps) if gaps else None,
        longest_silence_ms=1e3 * silence, longest_silence_at_s=silence_at,
        generator_lateness_p50_ms=_ms(late, 50),
        generator_lateness_max_ms=1e3 * max(late) if late else None,
        setup_s=e2e["setup_s"])


def _ms(values, q):
    v = stats.percentile(values, q)
    return None if v is None else 1e3 * v


def _in_flight_intervals(records, t0, t1):
    """[(start, end)] inside the window during which at least one request
    was in flight (submitted, last token not yet read), by the host clock."""
    spans = []
    for r in records:
        if r.t_submit is None:
            continue
        end = r.token_times[-1] if r.done.is_set() and r.token_times \
            else (r.t_submit if r.done.is_set() else t1)
        s, e = max(r.t_submit, t0), min(end, t1)
        if e > s:
            spans.append((s, e))
    return xplane.merge_intervals(spans)


def run_cell(run, compile_log):
    import mxnet_tpu as mx

    fam, cfg, mix = run.family, run.cfg, run.mix
    if mix["kind"] not in ("closed_loop", "open_loop"):
        raise ValueError("a serving configuration takes a closed_loop or "
                         "open_loop mix, not %r" % mix["kind"])
    sink = None
    if run.tracing:
        # the program's own timers, read in the traced run only
        mx.telemetry.enable()
        sink = mx.telemetry.registry().attach(TelemetrySink())
    model, params = fam.build_model(cfg, run.args.seed)
    registry = mx.serving.ModelRegistry()
    leftover = []
    try:
        fam.deploy(registry, MODEL_NAME, model, params, cfg)
        traffic = ServeTraffic(mix, cfg["vocab_size"], run.args.seed)
        warm = Load(registry, traffic, run.span)
        _warm_buckets(warm, cfg, cfg["vocab_size"],
                      np.random.RandomState(run.args.seed % (2 ** 32)))
        check_streams(run, model, params, warm, traffic)
        bad = [r for r in warm.records if r.failed]
        if bad:
            run.incorrect("%d warm-up request(s) failed: %s"
                          % (len(bad), bad[0].failed))

        load = Load(registry, traffic, run.span)
        t_begin, wall_begin = time.perf_counter(), time.time()
        if mix["kind"] == "closed_loop":
            load.start_closed(int(mix["clients"]))
        else:
            load.start_open(t_begin)
        time.sleep(float(mix["preroll_s"]))
        setup = compile_log.snapshot()
        secs = run.window_seconds()
        with run.traced_window():
            t0, wall0 = run.t_window_open, time.time()
            time.sleep(max(0.0, t0 + secs - time.perf_counter()))
            t1, wall1 = time.perf_counter(), time.time()
            load.wait_for_a_token(since=t1)     # the rate's closing edge
            if sink is not None:
                # how much of the reserved pool the traffic holds, by the
                # program's own gauge
                in_use = mx.telemetry.registry().gauge(
                    "kvcache.blocks_in_use").value
                run.log.line(event="kv_pool", blocks_in_use_at_close=in_use,
                             num_blocks=cfg["deployment"]["num_blocks"])
            # nothing new is sent while the trace is stopped and read: an
            # open loop would go on arriving for those tens of seconds, on
            # a host busy with the trace, and fill the queue
            load.stop.set()
        after = compile_log.snapshot()
        leftover = load.finish(t1)
    finally:
        registry.shutdown(drain=True)
        if sink is not None:
            mx.telemetry.registry().detach(sink)
            mx.telemetry.disable()
    if leftover:
        run.incorrect("%d client thread(s) did not end" % len(leftover))
    _window_metrics(run, load.records, t0, t1)
    in_window = after["requests"] - setup["requests"]
    run.compare("compiles_in_window", in_window, 0,
                "compiles inside the measured window")
    run.counters.update(
        compile_requests_setup=setup["requests"],
        cache_hits_setup=setup["cache_hits"],
        compiles_in_window=in_window)
    if sink is not None:
        for name, pairs in sink.samples.items():
            run.samples[name] = [v for t, v in pairs if wall0 <= t < wall1]
            # the pre-roll's requests are the mix's own too
            run.samples[name + "@load"] = [v for t, v in pairs
                                           if wall_begin <= t < wall1]
    run.log.line(event="setup", compile_requests=setup["requests"],
                 cache_hits=setup["cache_hits"],
                 compiles_in_window=in_window,
                 telemetry_samples={k: len(v)
                                    for k, v in run.samples.items()})


# idle time that neither a span of the program nor one of the benchmark
# covers: the engine's own thread between two of its spans
IDLE_DEFAULT = "engine host loop"


def extra_spans(run):
    """Moments of the window with no request in flight, as host intervals
    on the trace's clock: there the engine waits for arrivals."""
    tr = run.trace
    t0 = run.t_window_open
    out, at = [], t0
    for s, e in list(run.counters["in_flight_intervals"]) \
            + [(t0 + run.window_s, None)]:
        if s > at:
            a, b = tr.host_ns(at, t0), tr.host_ns(s, t0)
            out.append(xplane.Event(xplane.HOST_PLANE, "benchmark",
                                    "no request in flight", a, b - a, ""))
        at = max(at, e) if e is not None else at
    return out
