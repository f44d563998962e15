"""From a ``jax.profiler`` trace (``.xplane.pb``) to numbers.

The reader turns the file into plain ``Event`` tuples; every reduction
below works on such tuples, so each is tested on hand-built ones
(``tests/perfbench``).  Times are nanoseconds on the profiler's clock,
which the host planes and the device planes share.

A device plane is named ``/device:TPU:<n>``.  Its line ``XLA Ops`` holds
one event per executed HLO instruction (a fusion, a custom call, a
collective), named by the instruction's whole text
(``%flash_attention_fwd_pallas.13 = (bf16[384,512,64]...) custom-call(...``);
the reader keeps the instruction's own name as ``name`` and the start of
the rest as ``detail``, so that a pattern finds a kernel by its name and
not by an operand that mentions it.  A Pallas kernel is named after the
jitted function that calls it.  The line ``XLA Modules`` holds one event
per execution of a compiled program, named after the jitted function
(``jit_step_fn(...)``).
A host plane (``/host:CPU``) holds one line per thread with the
``jax.profiler.TraceAnnotation`` spans the benchmark puts around its calls
into each layer.
"""
import collections
import glob
import os
import re

import numpy as np

Event = collections.namedtuple(
    "Event", "plane line name start_ns dur_ns detail")

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_OPS_LINE = "Async XLA Ops"
HOST_PLANE = "/host:CPU"
_HLO_TEXT = re.compile(r"^%(\S+) = (.*)$", re.DOTALL)
_DETAIL_CHARS = 160


def find_xplane(trace_dir):
    """The newest ``.xplane.pb`` under a ``jax.profiler`` trace directory."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return max(paths, key=os.path.getmtime)


def read_events(profile, host_prefix="perfbench."):
    """The events of a ``jax.profiler.ProfileData`` that a reduction can
    use, as ``Event`` tuples: every event of the device planes, and of the
    host plane the spans whose name starts with ``host_prefix`` (the
    benchmark's own)."""
    out = []
    for plane in profile.planes:
        on_device = bool(DEVICE_PLANE.match(plane.name))
        if not on_device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                name, detail = ev.name, ""
                if on_device:
                    name, detail = split_hlo_text(name)
                elif not name.startswith(host_prefix):
                    continue
                out.append(Event(plane.name, line.name, name,
                                 float(ev.start_ns), float(ev.duration_ns),
                                 detail))
    return out


def split_hlo_text(text):
    """(instruction name, start of its type and operation) of an ops-line
    event's name; a name that is no HLO text is kept whole."""
    m = _HLO_TEXT.match(text)
    if not m:
        return text, ""
    return m.group(1), m.group(2)[:_DETAIL_CHARS]


def load(trace_dir):
    from jax.profiler import ProfileData
    return read_events(ProfileData.from_file(find_xplane(trace_dir)))


def device_ids(events):
    ids = {int(DEVICE_PLANE.match(e.plane).group(1)) for e in events
           if DEVICE_PLANE.match(e.plane)}
    return sorted(ids)


def on_device(events, device, line):
    plane = "/device:TPU:%d" % device
    return [e for e in events if e.plane == plane and e.line == line]


def clip(events, window):
    """The parts of ``events`` inside ``window`` = (start_ns, end_ns)."""
    if window is None:
        return list(events)
    t0, t1 = window
    out = []
    for e in events:
        s, t = max(e.start_ns, t0), min(e.start_ns + e.dur_ns, t1)
        if t > s:
            out.append(e._replace(start_ns=s, dur_ns=t - s))
    return out


def merge_intervals(spans):
    """Merged, sorted ``[(start, end)]`` of ``(start, end)`` pairs: what
    overlaps or nests counts once."""
    merged = []
    for s, t in sorted(spans):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def union_intervals(events):
    """The merged intervals of the events."""
    return merge_intervals((e.start_ns, e.start_ns + e.dur_ns)
                           for e in events if e.dur_ns > 0)


def busy_ns(events):
    """Nanoseconds in which at least one of ``events`` ran."""
    return sum(t - s for s, t in union_intervals(events))


def matching(events, pattern):
    """Events whose name matches the regular expression."""
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e.name)]


def time_by_name(events):
    """``{name: summed ns}``.  A parent instruction (``while``,
    ``conditional``) and its children are both on the ops line, so sums
    of different names may overlap; the busy union does not."""
    total = collections.Counter()
    for e in events:
        total[e.name] += e.dur_ns
    return total


def top_ops(events, n=10):
    """The ``n`` device operations that took most time, each under its
    name and the start of what the trace prints for it:
    ``[[name, seconds], ...]``."""
    detail = {e.name: e.detail for e in events}
    return [[(name + " = " + detail[name][:70]) if detail[name] else name,
             ns / 1e9]
            for name, ns in time_by_name(events).most_common(n)]


def module_runs(events, device, pattern):
    """Executions of the compiled program(s) whose name matches
    ``pattern`` on one device, in time order."""
    runs = matching(on_device(events, device, MODULES_LINE), pattern)
    return sorted(runs, key=lambda e: e.start_ns)


def per_run_ns(ops, runs):
    """Summed duration of ``ops`` inside each of ``runs`` (one number per
    execution of the program), by the op's start time."""
    out = []
    for r in runs:
        t0, t1 = r.start_ns, r.start_ns + r.dur_ns
        out.append(sum(e.dur_ns for e in ops if t0 <= e.start_ns < t1))
    return out


def host_spans(events, prefix):
    """Host ``TraceAnnotation`` spans whose name starts with ``prefix``."""
    return [e for e in events
            if e.plane == HOST_PLANE and e.name.startswith(prefix)]


def window_of(events, name):
    """(start_ns, end_ns) of the one host span called ``name``, or None."""
    found = [e for e in events if e.plane == HOST_PLANE and e.name == name]
    if len(found) != 1:
        return None
    return (found[0].start_ns, found[0].start_ns + found[0].dur_ns)


def idle_gaps(device_ops, window, spans, default, n=10):
    """The device's idle time inside ``window`` by what the host was doing:
    ``[[name, seconds], ...]``, longest first, at most ``n``.  Each gap
    between device operations is split among the host spans that overlap
    it (the innermost wins where spans nest: the shortest span covering a
    moment); what no span covers goes to ``default``."""
    t0, t1 = window
    busy = union_intervals(clip(device_ops, window))
    gaps, at = [], t0
    for s, t in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, t)
    if t1 > at:
        gaps.append((at, t1))
    by_name = collections.Counter()
    spans = sorted(spans, key=lambda e: e.dur_ns)      # innermost first
    starts = np.array([e.start_ns for e in spans])
    ends = np.array([e.start_ns + e.dur_ns for e in spans])
    for g0, g1 in gaps:
        # a device trace has a gap between any two operations: look only
        # at the few spans that overlap this one
        near = [spans[i] for i in
                np.nonzero((starts < g1) & (ends > g0))[0]] \
            if spans else []
        cuts = sorted({g0, g1} | {p for e in near
                                  for p in (e.start_ns, e.start_ns + e.dur_ns)
                                  if g0 < p < g1})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2.0
            owner = next((e.name for e in near
                          if e.start_ns <= mid < e.start_ns + e.dur_ns),
                         default)
            by_name[owner] += b - a
    return [[name, ns / 1e9] for name, ns in by_name.most_common(n)]
