"""What one run of one cell knows: the lines it prints, its clocks, the
trace it took, and the record that the per-layer readers read."""
import contextlib
import json
import os
import shutil
import time

from . import xplane

WINDOW_SPAN = "perfbench.window"


class Log:
    """Every line a run prints before the last is one JSON object that
    names the platform, the device kind and the device count."""

    def __init__(self, stamp, rehearse=False):
        self._stamp = {"platform": stamp["platform"],
                       "device_kind": stamp["kind"],
                       "device_count": stamp["count"]}
        self._rehearse = rehearse

    def line(self, **fields):
        print(json.dumps({**self._stamp, **fields}, default=float),
              flush=True)

    def measurement(self, event, **fields):
        """A line of times, rates or shares.  A rehearsal prints which
        fields it would carry and none of their values: a number from a CPU
        run never stands under the name of a device metric."""
        if self._rehearse:
            self.line(event=event, rehearsal="values withheld",
                      fields=sorted(fields))
        else:
            self.line(event=event, **fields)


class TraceView:
    """The events of the traced window and how to clip them to it."""

    def __init__(self, events, chips):
        self.events = events
        self._ops = {}
        self.window = xplane.window_of(events, WINDOW_SPAN)
        if self.window is None:
            raise RuntimeError("the trace holds no %r span: nothing says "
                               "where the window lies" % WINDOW_SPAN)
        self.devices = xplane.device_ids(events)[:chips]

    def ops(self, device, clipped=True):
        """The device's ops-line events, clipped to the window or whole."""
        key = (device, clipped)
        if key not in self._ops:
            evs = xplane.on_device(self.events, device, xplane.OPS_LINE)
            self._ops[key] = xplane.clip(evs, self.window) if clipped \
                else evs
        return self._ops[key]

    @property
    def window_s(self):
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self, device):
        return xplane.busy_ns(self.ops(device)) / 1e9

    def host_ns(self, t_perf, t_window_open):
        """A ``time.perf_counter()`` reading on the trace's clock, given
        the reading taken as the window span opened."""
        return self.window[0] + (t_perf - t_window_open) * 1e9


class Run:
    """The record a per-layer reader reads.  ``counters`` are the
    program's own counters and the benchmark's client-side tallies,
    ``samples`` lists of readings in the window by name (a serving cell
    also keeps ``<name>@load``: the readings since its load started, the
    pre-roll included), ``trace`` the ``TraceView`` of a
    ``--trace 1`` run (None otherwise, and None in a rehearsal without
    device planes)."""

    def __init__(self, cell, cfg, mix, family, stamp, args, root, t_start,
                 log):
        self.cell, self.cfg, self.mix, self.family = cell, cfg, mix, family
        self.stamp, self.args, self.root, self.log = stamp, args, root, log
        self.t_process_start = t_start
        self.chips = cell.chips
        self.tracing = bool(args.trace)
        self.window_s = None
        self.t_window_open = None
        self.counters = {}
        self.samples = {}
        self.end_to_end = {}
        self.trace = None
        self.correct = True
        self.compared = {}      # name -> (number, its limit): ``compare``
        self.attempted = 0
        self.failed = 0
        self.memory_peak_bytes = None

    # -- clocks ----------------------------------------------------------
    def setup_seconds(self, now):
        return now - self.t_process_start

    def window_seconds(self):
        """How long to measure: ``--seconds``, or in a traced run at most
        the mix's ``trace_seconds`` (a trace of the whole window would be
        larger than what it adds)."""
        secs = float(self.args.seconds)
        if self.tracing:
            secs = min(secs, float(self.mix.get("trace_seconds", secs)))
        return secs

    def incorrect(self, why):
        self.correct = False
        self.log.line(event="incorrect", why=why)

    def compare(self, name, value, limit, why):
        """Hold ``value`` to ``limit`` (at most) and keep the pair for the
        result's ``compared``; ``why`` says what it means to exceed it."""
        self.compared[name] = (float(value), float(limit))
        if not value <= limit:
            self.incorrect("%s: %.4g against a limit of %.4g" % (why, value,
                                                                limit))

    # -- tracing ---------------------------------------------------------
    def span(self, name):
        """A host span on the profiler's clock in a traced run, nothing
        otherwise."""
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def traced_window(self):
        """Trace what runs inside (``--trace 1``), under one span that
        marks the window on the profiler's clock."""
        if not self.tracing:
            self.t_window_open = time.perf_counter()
            yield
            return
        import jax
        trace_dir = os.path.join(self.root, ".perfbench_out", "trace",
                                 self.cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        # no Python call tracing: it is millions of host events in a few
        # seconds and slows the loop it traces.  TraceAnnotation spans are
        # host-tracer events and stay.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            self.t_window_open = time.perf_counter()
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                yield
        finally:
            jax.profiler.stop_trace()
        t0 = time.perf_counter()
        self.trace = TraceView(xplane.load(trace_dir), self.chips)
        self.log.line(event="trace_read",
                      seconds=round(time.perf_counter() - t0, 2),
                      events=len(self.trace.events),
                      devices=self.trace.devices)
        if not self.trace.devices:
            self.trace = None
