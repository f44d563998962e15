"""Device microseconds a prefill spends on one (padded) token's attention:
self time of the ops inside the whole ``jit_mx_prefill_b<bucket>`` executions
of the traced window whose scope path has the component ``attention``,
``attention_full`` or ``attention_window`` (the layer's own scope around
``blocks.causal_attention`` or a model's plain attention), over the summed
``bucket`` of those executions.  What a prefill attention kernel would move.
A program without those scopes, or one that does not name its serving
programs, has nothing to read."""
from perfbench.harness import serve_programs


def read(run):
    found = serve_programs.load(run)
    if found is None:
        return None
    return found.prefill_us_per_token(keep=serve_programs.is_attention)
