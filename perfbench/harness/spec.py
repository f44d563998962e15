"""Find a cell and everything that belongs to it by name.

``BENCHMARK.json`` names the cell, its configuration and its traffic mix;
each of those, and each per-layer metric, is a file of its own under
``perfbench/``.  Nothing here lists them: a new one is a new file.
"""
import importlib
import importlib.util
import json
import os


class SpecError(Exception):
    """The benchmark's files do not describe the cell that was asked for."""


def _load_json(path):
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError("missing file: %s" % path) from None


def _one(entries, name, what):
    found = [e for e in entries if e["name"] == name]
    if len(found) != 1:
        raise SpecError("BENCHMARK.json has %d %s named %r (has: %s)" % (
            len(found), what, name, ", ".join(e["name"] for e in entries)))
    return found[0]


class Cell:
    """One entry of ``workloads`` with its configuration, traffic mix and
    the metrics it reports."""

    def __init__(self, root, workload):
        self.root = root
        bench = _load_json(os.path.join(root, "BENCHMARK.json"))
        self.entry = _one(bench["workloads"], workload, "workload")
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfg_entry = _one(bench["configs"], self.entry["config"], "config")
        self.config = _load_json(os.path.join(root, cfg_entry["file"]))
        self.config["name"] = cfg_entry["name"]
        self.traffic = _load_json(os.path.join(
            root, "perfbench", "traffic", self.entry["traffic"] + ".json"))
        self.traffic["name"] = self.entry["traffic"]
        self.end_to_end = [m for m in bench["end_to_end"]
                           if self._reports(m)]
        self.per_layer = [m for m in bench["per_layer"] if self._reports(m)]

    def _reports(self, metric):
        return "workloads" not in metric or self.name in metric["workloads"]

    def family(self):
        """The module that builds this configuration's model through the
        program's entry points and holds its plain reference and its
        shape functions."""
        return importlib.import_module(
            "perfbench.families." + self.config["family"])

    def layer_reader(self, metric_name):
        """``read(run)`` of ``perfbench/layer_metrics/<name>.py``."""
        path = os.path.join(self.root, "perfbench", "layer_metrics",
                            metric_name + ".py")
        if not os.path.exists(path):
            raise SpecError("per-layer metric %r has no reader at %s"
                            % (metric_name, path))
        spec = importlib.util.spec_from_file_location(
            "perfbench_layer_metric_" + metric_name.replace(".", "_"), path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read


def sized(mapping, rehearse):
    """``mapping`` with its ``rehearse`` block laid over it when the run is
    a CPU rehearsal: the tiny sizes live beside the real ones."""
    out = {k: v for k, v in mapping.items() if k != "rehearse"}
    if rehearse:
        out.update(mapping.get("rehearse", {}))
    return out
