"""The last line of a run: one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics`` and ``device`` (and ``breakdown`` in a traced
run), and last ``compared``: each number that decided ``correct`` beside
its limit.  Values are printed as measured, with all their digits."""
import json
import sys

from . import program_trace, xplane


class MissingMetric(Exception):
    """The cell is to report a metric that this run did not produce."""


def end_to_end_metrics(run):
    out = {}
    for m in run.cell.end_to_end:
        if m["name"] not in run.end_to_end:
            raise MissingMetric("the run produced no %r" % m["name"])
        out[m["name"]] = {"value": float(run.end_to_end[m["name"]]),
                          "unit": m["unit"]}
    return out


def per_layer_metrics(run):
    """Each per-layer metric of the cell through its own reader.  A reader
    that finds nothing to read returns None and the metric is left out of
    the result, as the benchmark's contract has it -- but never silently:
    a line names what the cell declares and this run did not produce."""
    out, missing = {}, []
    for m in run.cell.per_layer:
        value = run.cell.layer_reader(m["name"])(run)
        if value is None:
            missing.append(m["name"])
        else:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    run.log.line(event="per_layer", produced=sorted(out), missing=missing)
    return out


def breakdown(run, idle_default, extra_spans=()):
    """The traced run's ``breakdown``: the ten device operations that took
    most time, under the names the trace prints, and the device's idle
    time by what the host was doing: under the program's own ``mx.`` spans
    and the benchmark's ``perfbench.`` ones, the innermost winning where
    they nest.  ``extra_spans`` are host intervals the driver knows from
    its own records, on the trace's clock."""
    tr = run.trace
    ops = tr.ops(tr.devices[0])
    spans = [e for e in xplane.host_spans(tr.events, "perfbench.")
             if e.name != "perfbench.window"] + list(extra_spans)
    view = program_trace.load(run)
    if view is not None:
        spans += program_trace.as_events(view.spans)
    return {"device_ops": xplane.top_ops(ops, 10),
            "idle_gaps": xplane.idle_gaps(ops, tr.window, spans,
                                          idle_default, 10)}


def result(run, metrics, breakdown=None):
    device = dict(run.stamp)
    device["memory_peak_bytes"] = run.memory_peak_bytes
    if run.trace is not None:
        busy = [run.trace.busy_s(d) for d in run.trace.devices]
        device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = run.trace.window_s
    out = {"correct": bool(run.correct), "attempted": int(run.attempted),
           "failed": int(run.failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = {name: {"value": value, "limit": limit}
                       for name, (value, limit) in run.compared.items()}
    return out


def print_result(obj):
    """The compared numbers as the last lines of standard error, the
    result as the last line of standard output."""
    for name, pair in obj["compared"].items():
        print("compared %s %r limit %r" % (name, pair["value"],
                                           pair["limit"]), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(obj), flush=True)
