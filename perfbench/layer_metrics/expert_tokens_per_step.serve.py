"""How many tokens a held expert is given in a decode step: the count of
token-to-expert assignments that fell on experts this chip holds, which
the decode program returns beside its tokens and the engine puts on the
``mx.decode.step`` span (``moe_assignments_held``, the same number it adds
to the counter ``decode.moe.assignments_held``), summed over the steps
that lie whole inside the traced window, over those steps and over the
expert-layer-experts held (expert layers x experts held a layer).  In the
deployment the configuration stands for, each expert's load is ``chips
sharing a layer`` times this chip's; how near this reads to that says how
far the grouped matmul here is from the deployment's regime."""
from perfbench.harness import program_trace


def read(run):
    view = program_trace.load(run)
    if view is None:
        return None
    held = [float(s.attrs["moe_assignments_held"])
            for s, ns in view.in_window("mx.decode.step")
            if ns == s.dur_ns and "moe_assignments_held" in s.attrs]
    cfg = run.cfg
    experts = (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]) \
        * cfg["n_routed_experts"]
    return sum(held) / len(held) / experts if held and experts else None
