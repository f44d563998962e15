"""Device time a decode step spends attending in its WINDOW layers: self
time of the ``XLA Ops`` events scoped under ``h<i>/attention_window`` (the
``paged_attention`` call of a layer that reads its last ``sliding_window``
positions through a ring table, in ``WindowMoEDecoder.decode_logits``),
summed over those layers, mean over the decode steps that lie whole inside
the traced window.  It reads its scope as ``kv_write_ms.serve`` reads its
own and inherits that reader's known under-read since PR 31 (a serving
step is taken to be a ``.call`` span; PERF.md section 7).  A program
without such a scope has nothing to read."""
from perfbench.harness import program_trace


def read(run):
    view = program_trace.load(run)
    return None if view is None \
        else view.scoped_ms(r"(^|/)attention_window(/|$)")
