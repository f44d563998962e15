"""Device time a decode step spends writing the new token's K and V into
the paged cache: self time of the ``XLA Ops`` events scoped under
``h<i>/kv_write`` (the two ``.at[i, blk, off].set`` of
``TinyGPT.decode_logits``), summed over the layers, mean over the decode
steps that lie whole inside the traced window.  Copies of a slab that
the compiler adds around the scatter count only if XLA gave them the
scatter's ``op_name``; otherwise they are ``unscoped`` on the
``device_by_scope`` line."""
from perfbench.harness import program_trace


def read(run):
    view = program_trace.load(run)
    return None if view is None else view.scoped_ms(r"(^|/)kv_write(/|$)")
