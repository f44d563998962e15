"""``mxnet_tpu.serving.decode`` -- the generative serving tier.

Autoregressive decoding is a different serving problem from the
fixed-shape forwards the PR-8 tier batches: each request is a LOOP
whose cost is unknown upfront (EOS-dependent), whose KV cache grows
every step, and whose latency contract is per-token (TTFT + inter-token),
not per-request.  This package is that tier:

- :class:`~.kvcache.PagedKVCache` -- fixed-size blocks carved from
  preallocated per-layer slabs; a per-request block table maps token
  position -> (block, offset), so sequences grow without contiguous
  reallocation and memory fragments at worst one partial block per
  sequence (``kvcache.*`` telemetry).
- :class:`~.engine.DecodeEngine` -- prefill and decode as SEPARATELY
  bucketed AOT executables (prompt-length vs slot-count) with
  continuous batching: requests join the running batch at step
  boundaries, finished sequences vacate immediately, admission sheds
  (``ServingQueueFull``) when the cache cannot cover a request's whole
  ``prompt + max_new`` budget -- never mid-generation.
- :class:`~.engine.GenerativeServable` /
  :meth:`ModelRegistry.register_generative` /
  :meth:`ModelRegistry.generate` -- the multi-tenant surface:
  token-streaming iterators, mid-decode hot swap with drain-to-
  completion on the old executables, ``/statusz`` + ``/healthz``
  integration.
- :class:`~.model.TinyGPT` -- a GPT-style decoder in pure-function
  form (prefill + paged decode step + full-forward oracle), the
  CI/bench workload.
- :class:`~.latent_moe.LatentMoEDecoder` -- a DeepSeek-V3-style decoder
  (latent attention over ONE cache row a token, rotary positions, RMS
  norm, routed experts told which experts they hold) in the same form.
- :class:`~.window_moe.WindowMoEDecoder` -- grouped-query attention in
  sliding-window and full layers (a bounded ring of cache blocks a
  sequence in the window layers, a whole table in the full ones, side by
  side in the one cache), two rotary tables by layer type, a softmax
  top-k router over experts that are all held here.  What the two
  share (RMS norm, rotary, SwiGLU, blocked prefill attention, the routed
  FFN with its counts) is :mod:`~.blocks`.
- :class:`~.looped.LoopedDecoder` -- a dense decoder (four RMS norms a
  layer, rotary attention, SwiGLU, from :mod:`~.blocks` too) whose stack
  of layers runs ``total_ut_steps`` times a token over ONE set of
  weights, the passes a loop of the compiled program, with a learned
  exit gate that picks which pass's hidden state the head reads.  Each
  pass of each layer keeps its own K and V: the cache has
  ``cache_passes x num_layers`` layers over ``num_layers`` layers of
  weights.
- :class:`~.linear_moe.LinearLatentMoEDecoder` -- Kimi Linear's block:
  KDA linear-attention layers (a gated delta rule over a float32 state
  a sequence, a short convolution; a chunked scan in prefill, one step
  of the ``kda_decode`` kernel in decode) beside latent-attention layers
  without positions, over the routed experts of ``LatentMoEDecoder``,
  whose latent attention, FFN, embedding and head it reuses.  Its KDA
  layers keep a STATE a sequence, not rows a token.

A model declares what a token keeps in the cache (``cache_rows()``),
where it has them which layers are window or state layers
(``cache_layers()``), what a sequence keeps in a state layer
(``cache_states()``) and how many cache layers a layer of weights keeps
(``cache_passes``, 1 where it is not declared), and the engine builds the one
``PagedKVCache`` from that: the cache's layers are the model's
declaration, not its ``num_layers``.  The decode-step
attention itself is a kernel-registry citizen
(``kernels.paged_attention`` for per-head K and V,
``kernels.mla_paged_attention`` for latent rows): a Pallas
online-softmax walk over the slot's block table on TPU (interpret mode
on CPU where the caller passes ``use_pallas=True``), an XLA
gather+masked-softmax fallback everywhere else.  docs/serving.md covers tuning.
"""
from .engine import (DecodeEngine, GenerationStream, GenerativeServable,
                     GenerativeWatcher)
from .kvcache import BlockTable, KVCacheExhausted, PagedKVCache
from .latent_moe import LatentMoEDecoder
from .linear_moe import LinearLatentMoEDecoder
from .looped import LoopedDecoder
from .model import TinyGPT, tiny_gpt
from .window_moe import WindowMoEDecoder

__all__ = ["BlockTable", "DecodeEngine", "GenerationStream",
           "GenerativeServable", "GenerativeWatcher",
           "KVCacheExhausted", "LatentMoEDecoder",
           "LinearLatentMoEDecoder", "LoopedDecoder",
           "PagedKVCache",
           "TinyGPT", "WindowMoEDecoder", "tiny_gpt"]
