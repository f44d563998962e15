"""Device microseconds a prefill spends on one token: the summed duration of
the ``jit_mx_prefill_b<bucket>`` executions that lie whole inside the traced
window over the summed ``bucket`` of those executions.  The token is a PADDED
one: the device computes the bucket whatever the prompt's length (the line
``prefill_programs`` gives ``padded_share`` and each bucket's own reading).
Unlike ``prefill_ms.serve``, a host timer that holds what was left of the
step in flight and moves with the prompts a window catches, this moves with
the program."""
from perfbench.harness import serve_programs


def read(run):
    found = serve_programs.load(run)
    return None if found is None else found.prefill_us_per_token()
