"""The autoregressive decode engine: separately-bucketed prefill/decode
AOT executables, continuous batching, token streaming.

The PR-8 engine serves fixed-shape forwards; generation is a *loop*
whose batch membership changes every step.  This module is the loop:

- **executor split**: prefill (whole prompt -> cache blocks + first
  token) compiles once per PROMPT-LENGTH bucket at batch 1; the decode
  step (one token per slot over the paged cache) compiles once per
  SLOT-COUNT bucket.  Both go through the same lower -> fingerprint ->
  :class:`~mxnet_tpu.serving.cache.CompileCache` path as
  ``BucketExecutorPool`` and warm at registration, so no request pays a
  first-compile.
- **continuous batching**: one worker thread runs an admit-then-step
  loop.  Pending requests join the RUNNING batch at a step boundary
  (one prefill call each), finished sequences vacate their slot the
  step they finish, and the live slots pad up to the smallest decode
  bucket -- no bucket flush, no drain-the-batch barrier (Orca's
  iteration-level scheduling).
- **one step in flight**: the loop dispatches step *n+1* before it
  fetches step *n*'s tokens, so the device runs one step while the
  host emits the last one and builds the next; a prefill is dispatched
  behind the step in flight and, before its first token is waited for,
  the running streams' next step behind IT.  A slot of step *n+1*
  takes its token from step *n*'s output ON THE DEVICE (the decode
  program is handed that output and, per slot, which of its entries
  to take); position, block table and the length limit the host counts
  ahead from the tokens it has DISPATCHED.  What the host cannot count
  ahead -- EOS, ``cancel()`` -- it finds one step late: the token that
  step *n+1* computed for the ended stream is discarded
  (``decode.tokens_discarded``), never pushed.  An error that the
  fetch of step *n* raises arrives with step *n+1* dispatched: every
  live stream ends with it and the step in flight is dropped.
- **admission backpressure**: the whole ``prompt + max_new`` KV budget
  allocates at submit; an exhausted
  :class:`~.kvcache.PagedKVCache` (or a full pending queue) sheds with
  the standard :class:`~mxnet_tpu.serving.batcher.ServingQueueFull` --
  a running sequence can never die for cache space.
- **token streaming**: :meth:`DecodeEngine.submit` returns a
  :class:`GenerationStream` iterator; every token lands there as it is
  decoded, so TTFT and inter-token latency are product-layer
  measurements (``decode.ttft`` / ``decode.inter_token`` timers).
- **spans**: the worker's time is named through ``obs.span`` --
  ``mx.decode.idle``, ``.admit`` (``.queue_wait``), ``.prefill`` and
  ``.step``, the last two split into ``.build`` / ``.call`` / ``.emit``.
  ONE ``mx.decode.step`` a step (``n``, ``bucket``, ``max_slots``,
  ``overlapped``) whose ring record links the ``serving.request`` root
  of every sequence it served (docs/observability.md).

Hot swap (the PR-12 contract extended mid-decode): re-registering a
:class:`GenerativeServable` installs the replacement for NEW requests
while the old engine's ``close(drain=True)`` keeps stepping its
half-generated sequences to completion -- zero dropped sequences,
counted under ``chaos.survived.serving.decode_swap``.
"""
from __future__ import annotations

import collections
import queue as _queue_mod
import threading
import time

import numpy as np

from ... import chaos as _chaos
from ... import obs as _obs
from ... import sync as _sync
from ... import telemetry as _telemetry
from ...base import MXNetError, scopes_in_cache_key
from ..batcher import RequestTimeout, ServableClosed, ServingQueueFull
from ..cache import compile_through, named, stablehlo_fingerprint
from ..loop import RegistryWatcher as _RegistryWatcher
from .kvcache import (FULL, SCRATCH_BLOCK, WINDOW, KVCacheExhausted,
                      PagedKVCache, write_prompt)

__all__ = ["DecodeEngine", "GenerationStream", "GenerativeServable",
           "GenerativeWatcher"]

_IDLE_WAIT_S = 0.05
_DONE = object()


def _env_buckets(var):
    from ... import env as _env
    spec = _env.get(var)
    try:
        return tuple(sorted({int(tok) for tok in str(spec).split(",")
                             if tok}))
    except ValueError as e:
        raise MXNetError("%s=%r is not a comma-separated int list"
                         % (var, spec)) from e


class GenerationStream:
    """Iterator over one request's generated token ids.

    Tokens arrive as the engine decodes them; iteration blocks until
    the next token, ``StopIteration`` lands after EOS / ``max_new`` /
    cancel / drain, and an engine-side failure re-raises here.
    ``cancel()`` asks the engine to drop the sequence at the next step
    boundary (its cache blocks are freed there)."""

    def __init__(self, model, prompt_len, max_new):
        self.model = model
        self.prompt_len = int(prompt_len)
        self.max_new = int(max_new)
        self._q = _queue_mod.Queue()
        self._error = None
        self._finished = False
        self.finish_reason = None       # eos | length | cancel | closed
        self.cancelled = False
        self.t_submit = time.perf_counter()
        self.t_first_token = None

    # -- engine side ----------------------------------------------------
    def _push(self, token, now):
        if self.t_first_token is None:
            self.t_first_token = now
        self._q.put(int(token))

    def _finish(self, reason, error=None):
        self.finish_reason = reason
        self._error = error
        self._q.put(_DONE)

    # -- client side ----------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        if self._finished:
            raise StopIteration
        item = self._q.get()
        if item is _DONE:
            self._finished = True
            if self._error is not None:
                raise self._error
            raise StopIteration
        return item

    def cancel(self):
        """Drop the sequence at the next step boundary (idempotent)."""
        self.cancelled = True

    def tokens(self):
        """Drain the stream to completion and return every token."""
        return list(self)

    @property
    def ttft_s(self):
        """Submit -> first token, or None before the first token."""
        if self.t_first_token is None:
            return None
        return self.t_first_token - self.t_submit


class _GenRequest:
    __slots__ = ("prompt", "max_new", "eos_id", "table", "stream",
                 "deadline", "tctx", "generated", "dispatched", "slot",
                 "done", "last_token", "t_last_emit", "t_submit")

    def __init__(self, prompt, max_new, eos_id, table, stream, timeout):
        self.prompt = prompt
        self.max_new = int(max_new)
        self.eos_id = eos_id
        self.table = table
        self.stream = stream
        self.t_submit = stream.t_submit
        self.deadline = (self.t_submit + timeout) if timeout else None
        self.tctx = None
        # tokens pushed to the stream, and tokens whose call has been
        # dispatched: the second runs at most one step ahead
        self.generated = 0
        self.dispatched = 0
        self.slot = None            # its slot in the step last dispatched
        self.done = False           # ended: a token still in flight is dropped
        self.last_token = None
        self.t_last_emit = None

    @property
    def position(self):
        """Cache position the NEXT decode step dispatched writes (the
        last dispatched token's index in the full sequence)."""
        return len(self.prompt) + self.dispatched - 1


class _Flight:
    """One decode step dispatched and not yet fetched: the requests it
    carries in slot order, its outputs on the device (the tokens at the
    engine's fixed width, the program's counts), when it was dispatched,
    whether the step before it was still unfetched then, and whether it
    runs behind a prefill: its tokens then arrive a prefill late."""

    __slots__ = ("batch", "bucket", "out", "t_dispatch", "overlapped",
                 "after_prefill")

    def __init__(self, batch, bucket, out, t_dispatch, overlapped):
        self.batch = batch
        self.bucket = bucket
        self.out = out
        self.t_dispatch = t_dispatch
        self.overlapped = overlapped
        self.after_prefill = False


def _request_links(reqs):
    """Span ids of the requests' ``serving.request`` roots: what a step,
    a prefill or a queue wait serves without being its child.  Empty
    unless tracing was armed when the requests were submitted."""
    return [r.tctx.span_id for r in reqs if r.tctx is not None]


def _memory_of(compiled):
    """{"aliased_bytes", "temp_bytes"} of a compiled executable, or
    None where the backend's ``memory_analysis()`` reports nothing."""
    try:
        stats = compiled.memory_analysis()
        return {"aliased_bytes": int(stats.alias_size_in_bytes),
                "temp_bytes": int(stats.temp_size_in_bytes)}
    except Exception:
        return None


class _AotPrograms:
    """lower -> fingerprint -> CompileCache -> compile, per static
    shape key (the BucketExecutorPool discipline generalized to
    multi-argument decode/prefill signatures).

    ``donate_argnums`` of :meth:`build` reach BOTH jits: the one that
    is lowered and fingerprinted, and the ``jax.export`` wrapper that
    ``compile_through`` compiles when a ``CompileCache`` is present
    (the path a registry's servable runs).  A call of the built
    program consumes the donated arguments: they are deleted when it
    returns and the outputs that alias them are the arrays to keep.
    ``memory[key]`` holds what the compiled executable's
    ``memory_analysis()`` says of that: ``aliased_bytes`` (outputs
    written into donated arguments) and ``temp_bytes``; None where the
    backend reports nothing."""

    def __init__(self, cache=None, label="decode"):
        self._cache = cache
        self._label = label
        self._programs = {}
        self.fingerprints = {}
        self.memory = {}

    def build(self, key, fn, specs, donate_argnums=()):
        import jax
        if key in self._programs:
            return self._programs[key]
        # its kind and bucket are the compiled module's name on both
        # routes of ``compile_through``: a device trace reads an
        # execution as ``jit_mx_prefill_b2048(<id>)``
        name = "mx_%s_b%s" % tuple(key)
        jfn = jax.jit(named(fn, name), donate_argnums=donate_argnums)
        lowered = jfn.lower(*specs)
        # the lowering drops an argument that the program does not read,
        # the exported artifact's calling convention keeps it: a key of
        # the text alone would hand a caller of six arguments the
        # artifact of a caller of five (and, the text being keyed without
        # its names, this name an artifact made under another)
        fp = stablehlo_fingerprint(
            lowered.as_text() + "\n// %s called with %s"
            % (name, jax.tree_util.tree_structure(specs)))
        # compiled here, never at the first request: warmup() promises
        # that no request pays a compile; with its scopes in the XLA
        # cache's key, so that a trace reads this version's names
        with scopes_in_cache_key():
            call = compile_through(self._cache, fp, jfn, lowered, specs,
                                   donate_argnums=donate_argnums,
                                   name=name)
        self._programs[key] = call
        self.fingerprints[key] = fp
        self.memory[key] = _memory_of(call)
        aliased = [m["aliased_bytes"] for m in self.memory.values() if m]
        if aliased and _telemetry._ENABLED:
            # the least of the programs built: one that copies shows
            _telemetry.hooks.decode_kv_aliased(self._label, min(aliased))
        # for a reader of a device trace: instruction name -> scope
        _obs.note_program("%s:%s:%s" % ((self._label,) + tuple(key)),
                          call.as_text)
        return call

    def get(self, key):
        return self._programs[key]


class DecodeEngine:
    """Continuous-batching autoregressive decode over a paged KV cache.

    Parameters
    ----------
    model : :class:`~.model.TinyGPT`-shaped spec (``prefill_kv`` or
        ``prefill_cache`` / ``decode_logits`` / geometry attributes)
    params : flat name -> device-array dict
    prefill_buckets : prompt-length buckets (each compiles one prefill
        executable at batch 1)
    decode_buckets : slot-count buckets (each compiles one decode-step
        executable); the largest is the concurrent-sequence bound
    block_size / num_blocks : :class:`~.kvcache.PagedKVCache` geometry
    window_blocks : blocks in a window layer's slab, for a model that
        declares such layers (``cache_layers()``, ``sliding_window``)
    max_queue : pending-request bound past which submits shed
    cache : :class:`~mxnet_tpu.serving.cache.CompileCache` or None
    """

    def __init__(self, model, params, prefill_buckets=None,
                 decode_buckets=None, block_size=None, num_blocks=None,
                 max_queue=None, cache=None, label="generative",
                 kv_dtype="float32", window_blocks=None):
        from ... import env as _env
        self.model = model
        self.params = params
        self._label = label
        if prefill_buckets is None:
            prefill_buckets = _env_buckets(
                "MXNET_TPU_SERVING_PREFILL_BUCKETS")
        if decode_buckets is None:
            decode_buckets = _env_buckets(
                "MXNET_TPU_SERVING_DECODE_BUCKETS")
        self.prefill_buckets = tuple(sorted(set(
            int(b) for b in prefill_buckets)))
        self.decode_buckets = tuple(sorted(set(
            int(b) for b in decode_buckets)))
        if not self.prefill_buckets or self.prefill_buckets[0] < 1 \
                or not self.decode_buckets \
                or self.decode_buckets[0] < 1:
            raise MXNetError("decode engine: buckets must be positive "
                             "ints, got prefill=%r decode=%r"
                             % (prefill_buckets, decode_buckets))
        # buckets past the model's context are uncompilable dead
        # weight (the default env list serves models of any size):
        # keep those that fit, plus one capped at max_seq so the
        # longest admissible prompt stays servable
        if self.prefill_buckets[-1] > model.max_seq:
            kept = tuple(b for b in self.prefill_buckets
                         if b < model.max_seq)
            self.prefill_buckets = kept + (int(model.max_seq),)
        block_size = int(block_size if block_size is not None
                         else _env.get("MXNET_TPU_SERVING_KV_BLOCK"))
        num_blocks = int(num_blocks if num_blocks is not None
                         else _env.get("MXNET_TPU_SERVING_KV_BLOCKS"))
        self.max_slots = self.decode_buckets[-1]
        # the model declares what a token keeps in a layer, (a model
        # with window or state layers) which layer is of which kind and
        # what a sequence keeps in a state layer, and (a model that runs
        # its layers several times) how many cache layers a layer of
        # weights keeps: the cache's layers are not the model's
        kinds = model.cache_layers() \
            if hasattr(model, "cache_layers") else None
        states = model.cache_states() \
            if hasattr(model, "cache_states") else None
        self.cache = PagedKVCache(
            model.num_layers, model.cache_rows(), block_size, num_blocks,
            dtype=kv_dtype, kinds=kinds,
            window=getattr(model, "sliding_window", None),
            window_blocks=window_blocks,
            fold_heads=getattr(model, "cache_fold_heads", False),
            passes=getattr(model, "cache_passes", 1), states=states,
            # a state row a slot (a sequence holds one from admission to
            # its end) and row 0 the scratch row of padded slots
            state_rows=self.max_slots + 1 if states else None)
        # fixed compiled block-table widths: a full layer's enough for
        # the longest sequence the model can hold, a window layer's the
        # ring, a state layer's the one row
        self._table_widths = self.cache.blocks_needed(model.max_seq)
        self.max_blocks_per_seq = self._table_widths[FULL]
        self.max_queue = int(max_queue if max_queue is not None
                             else _env.get("MXNET_TPU_SERVING_QUEUE"))
        self._programs = _AotPrograms(cache=cache, label=label)
        self._cond = _sync.Condition(name="serving.decode")
        self._pending = collections.deque()
        self._active = []
        # the decode step dispatched and not yet fetched, and when the
        # last step's tokens reached the host
        self._flight = None
        self._t_fetched = 0.0
        # what a step with no step before it is handed as that step's
        # tokens: every slot of such a step holds a token of the host's
        import jax.numpy as jnp
        self._no_tokens = jnp.zeros((self.max_slots,), jnp.int32)
        self._closed = False
        self._drain = True
        self._drained_live = 0      # sequences in flight at close()
        self._thread = None

    # -- AOT build ------------------------------------------------------
    # argument number of (params, slabs, ...) that every prefill and
    # decode program donates: the cache's whole pytree.  Never
    # ``params``, which every call and every engine of a swap share.
    _DONATED = (1,)

    def _prefill_impl(self, params, slabs, tokens, table, true_len):
        import jax
        import jax.numpy as jnp
        bs = self.cache.block_size
        if hasattr(self.model, "prefill_cache"):
            # a model that runs its layers several times writes each
            # pass's rows itself, inside its loop over the passes; one
            # with state layers its rows and its sequence's states
            logits, slabs, stats = self.model.prefill_cache(
                params, slabs, tokens, true_len - 1, table, bs)
        else:
            logits, rows, stats = self.model.prefill_kv(params, tokens,
                                                        true_len - 1)
            with jax.named_scope("mx.kv_scatter"):
                # each layer's prompt rows into that layer's own slab,
                # through the table of the layer's kind; a window layer
                # keeps what its ring holds at the prompt's end
                tables = table if isinstance(table, dict) \
                    else {FULL: table}
                slabs = {name: tuple(
                    write_prompt(slab, r, tables[kind], true_len, bs,
                                 ring=kind == WINDOW)
                    for slab, r, kind in zip(layers, rows[name],
                                             self.cache.table_kinds))
                    for name, layers in slabs.items()}
        with jax.named_scope("mx.lm_head"):
            first_token = jnp.argmax(logits).astype(jnp.int32)
        return (first_token, stats), slabs

    def _decode_impl(self, params, slabs, prev, tokens, positions, tables,
                     live):
        import jax
        import jax.numpy as jnp
        with jax.named_scope("mx.step_tokens"):
            # a slot's token is an id the host holds (a prefill's first
            # token), or, written ``-1 - j``, slot j of the step
            # before, whose output ``prev`` never left the device
            tokens = jnp.where(tokens < 0,
                               jnp.take(prev, jnp.maximum(-1 - tokens, 0)),
                               tokens)
        next_token, _logits, slabs, stats = self.model.decode_logits(
            params, slabs, tokens, positions, tables,
            self.cache.block_size, live)
        # at ONE width whatever the bucket, so that every bucket's
        # program can take any bucket's output
        next_token = jnp.pad(next_token,
                             (0, self.max_slots - next_token.shape[0]))
        return (next_token, stats), slabs

    def _specs(self):
        import jax
        i32 = np.int32
        kv = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            self.cache.slabs)
        pspec = {n: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for n, v in self.params.items()}

        def tables(rows=None):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                self._tables((), rows))

        prefill = {
            b: (pspec, kv,
                jax.ShapeDtypeStruct((1, b), i32),
                tables(),
                jax.ShapeDtypeStruct((), i32))
            for b in self.prefill_buckets}
        decode = {
            s: (pspec, kv,
                jax.ShapeDtypeStruct((self.max_slots,), i32),
                jax.ShapeDtypeStruct((s,), i32),
                jax.ShapeDtypeStruct((s,), i32),
                tables(s),
                jax.ShapeDtypeStruct((s,), np.bool_))
            for s in self.decode_buckets}
        return prefill, decode

    def _tables(self, reqs, rows=None):
        """The block tables of ``reqs`` at the compiled widths, padded
        with the scratch block to ``rows`` rows (None: ONE request's
        table, without the slot axis): an int32 array, or for a model
        with window or state layers one a kind of layer, ``{"full": ...,
        "window": ...}`` (the ring) or ``{"full": ..., "state": ...}``
        (the sequence's state row, a table one entry wide; a padded
        slot's is the scratch row)."""
        out = {}
        for kind, width in self._table_widths.items():
            arr = np.full((1 if rows is None else rows, width),
                          SCRATCH_BLOCK, np.int32)
            for i, req in enumerate(reqs):
                arr[i] = self.cache.padded_table(req.table, width, kind)
            out[kind] = arr[0] if rows is None else arr
        return out if len(out) > 1 else out[FULL]

    def warmup(self):
        """Compile every prefill and decode bucket (compile-cache
        checked first), each with the cache's slabs donated; returns total
        warm-up seconds.  After this no request can trigger a
        compile."""
        t0 = time.perf_counter()
        prefill, decode = self._specs()
        for b, specs in prefill.items():
            self._programs.build(("prefill", b), self._prefill_impl,
                                 specs, donate_argnums=self._DONATED)
        for s, specs in decode.items():
            self._programs.build(("decode", s), self._decode_impl,
                                 specs, donate_argnums=self._DONATED)
        dt = time.perf_counter() - t0
        if _telemetry._ENABLED:
            _telemetry.hooks.serving_warmup(
                self._label, dt,
                len(self.prefill_buckets) + len(self.decode_buckets))
        return dt

    def _bucket(self, buckets, n, what):
        for b in buckets:
            if b >= n:
                return b
        raise MXNetError("decode engine: %s of %d exceeds the largest "
                         "%s bucket %d" % (what, n, what, buckets[-1]))

    # -- intake ---------------------------------------------------------
    def submit(self, prompt, max_new_tokens, eos_id=None, timeout=None):
        """Admit one generation request; returns a
        :class:`GenerationStream`.

        The FULL ``prompt + max_new_tokens`` cache budget allocates
        here -- :class:`ServingQueueFull` is raised when the pending
        queue is at capacity or the KV cache cannot cover the budget
        (``decode.shed`` + ``kvcache.alloc_failures``), so an accepted
        request can never fail for cache space mid-generation."""
        prompt = [int(t) for t in prompt]
        max_new = int(max_new_tokens)
        if not prompt or max_new < 1:
            raise MXNetError("generate needs a non-empty prompt and "
                             "max_new_tokens >= 1")
        if len(prompt) > self.prefill_buckets[-1]:
            raise MXNetError(
                "prompt of %d tokens exceeds the largest prefill "
                "bucket %d" % (len(prompt), self.prefill_buckets[-1]))
        total = len(prompt) + max_new
        if total > self.model.max_seq:
            raise MXNetError(
                "prompt + max_new_tokens = %d exceeds model max_seq %d"
                % (total, self.model.max_seq))
        with self._cond:
            if self._closed:
                raise ServableClosed("generative servable %r is closed"
                                     % self._label)
            if len(self._pending) >= self.max_queue:
                if _telemetry._ENABLED:
                    _telemetry.hooks.decode_shed(self._label, "queue")
                raise ServingQueueFull(
                    "generative servable %r pending queue full (%d)"
                    % (self._label, self.max_queue))
            try:
                table = self.cache.allocate(total)
            except KVCacheExhausted as e:
                if _telemetry._ENABLED:
                    _telemetry.hooks.decode_shed(self._label,
                                                 "kvcache")
                raise ServingQueueFull(
                    "generative servable %r shed at admission: %s"
                    % (self._label, e)) from e
            stream = GenerationStream(self._label, len(prompt),
                                      max_new)
            req = _GenRequest(prompt, max_new, eos_id, table, stream,
                              timeout)
            if _obs._TRACE_ENABLED:
                req.tctx = _obs.trace.fresh_context()
            self._pending.append(req)
            depth = len(self._pending)
            self._cond.notify()
        if _telemetry._ENABLED:
            _telemetry.hooks.decode_request(self._label, depth)
        return stream

    # -- the loop -------------------------------------------------------
    def start(self):
        if self._thread is not None:
            raise MXNetError("DecodeEngine already started")
        self._thread = threading.Thread(
            target=self._worker, daemon=True,
            name="mxtpu-decode-%s" % self._label)
        self._thread.start()

    def _worker(self):
        while True:
            with self._cond:
                if not self._busy() and not self._closed:
                    with _obs.span("mx.decode.idle"):
                        while not self._busy() and not self._closed:
                            self._cond.wait(_IDLE_WAIT_S)
                if self._closed:
                    if not self._drain:
                        self._abort_locked()
                        return
                    if not self._busy():
                        return
            if self._pending:
                with _obs.span("mx.decode.admit"):
                    self._admit()
            if self._turn_due():
                self._step()

    def _busy(self):
        return bool(self._pending or self._active
                    or self._flight is not None)

    def _turn_due(self):
        """Whether :meth:`_step` has work: a step to fetch or a stream
        to step."""
        return self._flight is not None or bool(self._live())

    def _live(self):
        """The streams with a token still to dispatch, in slot order.
        (In ``_active`` and in no step: one whose first token is not
        out yet, and one whose last token is in flight.)"""
        return [r for r in self._active if 0 < r.dispatched < r.max_new]

    def _abort_locked(self):
        """close(drain=False): resolve everything as closed, free every
        table -- still zero *lost* streams, they all end explicitly.
        The step in flight is dropped with them."""
        err = ServableClosed("generative servable %r closed without "
                             "drain" % self._label)
        for req in list(self._pending) + self._active:
            self.cache.free(req.table)
            req.done = True
            req.stream._finish("closed", error=err)
        self._pending.clear()
        del self._active[:]
        self._flight = None

    def _admit(self):
        """Step-boundary admission: pending requests take free slots in
        the RUNNING batch (one prefill each, dispatched behind the step
        in flight).  Expired/cancelled requests resolve here and never
        occupy a slot."""
        while True:
            with self._cond:
                if not self._pending \
                        or len(self._live()) >= self.max_slots:
                    return
                req = self._pending.popleft()
            with _obs.span("mx.decode.queue_wait", since=req.t_submit,
                           links=_request_links((req,))):
                pass
            now = time.perf_counter()
            if req.stream.cancelled:
                self._finish(req, "cancel")
                continue
            if req.deadline is not None and now > req.deadline:
                self.cache.free(req.table)
                req.stream._finish("timeout", error=RequestTimeout(
                    "generation waited %.1fms > timeout while queued"
                    % (1e3 * (now - req.t_submit))))
                if _telemetry._ENABLED:
                    _telemetry.hooks.serving_timeout(self._label)
                continue
            self._prefill(req)

    def _prefill(self, req):
        bucket = self._bucket(self.prefill_buckets, len(req.prompt),
                              "prefill")
        # whom it holds up: the streams with a token still to dispatch,
        # and whether it queues behind a decode step in flight
        span = _obs.span("mx.decode.prefill", bucket=bucket,
                         prompt=len(req.prompt), live=len(self._live()),
                         behind=int(self._flight is not None),
                         links=_request_links((req,)))
        with span:
            self._prefill_spanned(req, bucket, span)

    def _prefill_spanned(self, req, bucket, span):
        import jax
        with _obs.span("mx.decode.prefill.build"):
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :len(req.prompt)] = req.prompt
            table = self._tables((req,))
        t0 = time.perf_counter()
        call = self._programs.get(("prefill", bucket))
        cache = self.cache
        dispatched = False
        try:
            with _obs.span("mx.decode.prefill.call"):
                _chaos.fail_point("serving.decode.prefill",
                                  model=self._label, bucket=bucket)
                # the slabs that go in are donated: dead once the call
                # returns, so the outputs are bound before anything
                # else can read the cache.  The call runs behind the
                # decode step in flight
                out, cache.slabs = call(
                    self.params, cache.slabs, tokens, table,
                    np.int32(len(req.prompt)))
                dispatched = True
                # it holds a cache from here on; it steps once its
                # first token is out
                self._active.append(req)
                stepping = self._turn_due()
                if not stepping:
                    # the token and the program's counts in one fetch
                    first, stats = jax.device_get(out)
            if stepping:
                # the running streams take their next turn BEHIND the
                # prefill before its token is waited for: the device
                # then has a step to run when the prefill ends, where
                # the token's way to the host and a dispatch's way back
                # would leave it idle.  This request joins the step
                # after that one
                self._step(after_prefill=True)
                if req.done:            # that turn lost the cache
                    return
                with _obs.span("mx.decode.prefill.call"):
                    first, stats = jax.device_get(out)
            first = int(first)
        except Exception as e:
            self._call_failed(e, [req], dispatched)
            return
        with _obs.span("mx.decode.prefill.emit"):
            self.cache.note_tokens(req.table, len(req.prompt) + 1)
            now = time.perf_counter()
            if _telemetry._ENABLED:
                _telemetry.hooks.decode_prefill(self._label, bucket,
                                                len(req.prompt), now - t0)
                _telemetry.hooks.decode_ttft(now - req.t_submit)
            self._note_stats(span, stats)
            req.dispatched = 1          # the prefill was its dispatch
            self._emit(req, first, now)
            if self._maybe_finish(req):
                self._active.remove(req)

    def _step(self, after_prefill=False):
        """One turn of the loop under ONE ``mx.decode.step`` span: the
        step in flight is fetched and emitted with its successor
        already dispatched behind it.  Where nothing is in flight (the
        first step after an idle moment) the span's step is dispatched
        here first.  ``after_prefill``: the turn is a prefill's own, so
        the first step it dispatches runs behind that prefill, and the
        span that delivers that step's tokens says so."""
        flight, self._flight = self._flight, None
        batch = flight.batch if flight is not None else self._live()
        span = _obs.span("mx.decode.step", n=len(batch),
                         bucket=self._bucket(self.decode_buckets,
                                             len(batch), "decode"),
                         max_slots=self.max_slots,
                         overlapped=int(flight is not None
                                        and flight.overlapped),
                         after_prefill=int(
                             flight.after_prefill if flight is not None
                             else after_prefill),
                         links=_request_links(batch))
        with span:
            self._step_spanned(flight, batch, span, after_prefill)

    def _step_spanned(self, flight, batch, span, after_prefill):
        import jax
        with _obs.span("mx.decode.step.build"):
            if flight is None:
                try:
                    flight = self._dispatch(batch, self._build(batch))
                    flight.after_prefill, after_prefill = after_prefill, False
                except Exception as e:
                    self._call_failed(e, batch, dispatched=False)
                    return
            # who steps on behind it: known without its tokens
            after = self._live()
            args = self._build(after) if after else None
        with _obs.span("mx.decode.step.call"):
            if after:
                try:
                    self._flight = self._dispatch(after, args, flight)
                    self._flight.after_prefill = after_prefill
                except Exception as e:
                    if self._call_failed(e, after, dispatched=False):
                        return          # the slabs went with it
            try:
                out, stats = jax.device_get(flight.out)
            except Exception as e:
                self._call_failed(e, flight.batch, dispatched=True)
                return
        with _obs.span("mx.decode.step.emit"):
            now = time.perf_counter()
            self._note_stats(span, stats)
            discarded = 0
            for i, req in enumerate(flight.batch):
                if req.done:
                    # it ended (EOS, cancel, an error) with this step
                    # already dispatched: the token is nobody's
                    discarded += 1
                    continue
                self._emit(req, int(out[i]), now)
                self.cache.note_tokens(req.table,
                                       len(req.prompt) + req.generated)
                self._maybe_finish(req)
            # finished sequences vacate their slot IMMEDIATELY: the
            # next step dispatched packs the survivors into a smaller
            # bucket
            self._active = [r for r in self._active if not r.done]
            if _telemetry._ENABLED:
                # the loop's period: from the tokens of the step before
                # (or this step's dispatch, where none was in flight)
                # to this step's tokens
                _telemetry.hooks.decode_step(
                    self._label, len(flight.batch), flight.bucket,
                    now - max(self._t_fetched, flight.t_dispatch),
                    flight.overlapped, discarded)
            self._t_fetched = now

    def _build(self, batch):
        """The host's arguments of one decode step for ``batch``: each
        slot's token (or where on the device it is), position and block
        table, padded to the bucket."""
        bucket = self._bucket(self.decode_buckets, len(batch), "decode")
        tokens = np.zeros((bucket,), np.int32)
        positions = np.zeros((bucket,), np.int32)
        tables = self._tables(batch, bucket)
        # which slots of the bucket hold a sequence: the rest is
        # padding, and a model that counts its tokens leaves it out
        live = np.arange(bucket) < len(batch)
        for i, req in enumerate(batch):
            # a token still in flight is taken from that step's output
            tokens[i] = req.last_token if req.dispatched == req.generated \
                else -1 - req.slot
            positions[i] = req.position
        return bucket, (tokens, positions, tables, live)

    def _dispatch(self, batch, built, behind=None):
        """Dispatch one decode step for ``batch`` behind the step in
        flight (None: nothing is), whose tokens it takes on the device,
        and return it in flight."""
        bucket, args = built
        prev = behind.out[0] if behind is not None else self._no_tokens
        _chaos.fail_point("serving.decode.step", model=self._label,
                          occupancy=len(batch), bucket=bucket)
        call = self._programs.get(("decode", bucket))
        cache = self.cache
        t0 = time.perf_counter()
        # donated slabs in, the same memory out: rebind at once
        out, cache.slabs = call(self.params, cache.slabs, prev, *args)
        for i, req in enumerate(batch):
            req.slot = i
            req.dispatched += 1
        return _Flight(batch, bucket, out, t0, behind is not None)

    def _note_stats(self, span, stats):
        """The counts a program returned beside its token (a model with
        routed experts: ``moe_assignments``, ``moe_assignments_held``,
        ``moe_expert_tokens_max``; one with window layers: ``kv_rows_full``,
        ``kv_rows_window``; one that runs its layers several times:
        ``ut_passes``, ``exit_step_sum``, ``exit_early``, ``kv_rows``;
        one with state layers: ``state_rows`` (a decode step) or
        ``scan_tokens`` (a prefill); one with latent-attention layers:
        ``latent_grid_steps`` (a decode step); empty for a dense one of
        full layers run once): onto the step's or the prefill's span and
        the ``decode.moe.*`` / ``decode.kv.*`` / ``decode.ut.*`` /
        ``decode.linear.*`` / ``decode.latent.*`` counters."""
        if not stats:
            return
        stats = {k: int(v) for k, v in stats.items()}
        span.set(**stats)
        if _telemetry._ENABLED:
            if "moe_assignments" in stats:
                _telemetry.hooks.decode_moe(self._label, stats)
            if "kv_rows_full" in stats:
                _telemetry.hooks.decode_kv_rows(self._label, stats)
            if "ut_passes" in stats:
                _telemetry.hooks.decode_ut_passes(self._label, stats)
            if "state_rows" in stats or "scan_tokens" in stats:
                _telemetry.hooks.decode_linear(self._label, stats)
            if "latent_grid_steps" in stats:
                _telemetry.hooks.decode_latent(self._label, stats)

    def _call_failed(self, error, served, dispatched):
        """A prefill or decode call raised.  Before the call took its
        arguments (the chaos fail points, a bad argument) the cache is
        whole and only the requests the call ``served`` end with the
        error; a step in flight that carries them discards their
        tokens.  After it -- the slabs are deleted, or are the outputs
        of a program that failed -- no live stream has a cache left:
        every one ends with the error, the step in flight is dropped,
        and fresh zeroed slabs serve the next request.  Returns whether
        the cache was lost."""
        if _telemetry._ENABLED:
            _telemetry.hooks.serving_error(self._label)
        lost = dispatched or self.cache.slabs_deleted()
        failed = list(served)
        if lost:
            failed += [r for r in self._active if r not in failed]
            flight, self._flight = self._flight, None
            if flight is not None:
                # it holds the old slabs until it ends, and two sets
                # may not fit the device
                import jax
                try:
                    jax.block_until_ready(flight.out)
                except Exception:       # whatever failed the call
                    pass
            # before a stream hears of it: its client may ask again
            self.cache.reset_slabs()
        for req in failed:
            if not req.done:
                req.done = True
                self.cache.free(req.table)
                req.stream._finish("error", error=error)
        self._active = [r for r in self._active if not r.done]
        return lost

    def _emit(self, req, token, now):
        req.generated += 1
        req.last_token = token
        if _telemetry._ENABLED and req.t_last_emit is not None:
            _telemetry.hooks.decode_inter_token(now - req.t_last_emit)
        req.t_last_emit = now
        req.stream._push(token, now)

    def _maybe_finish(self, req):
        if req.stream.cancelled:
            self._finish(req, "cancel")
            return True
        if req.eos_id is not None and req.last_token == req.eos_id:
            self._finish(req, "eos")
            return True
        if req.generated >= req.max_new:
            self._finish(req, "length")
            return True
        return False

    def _finish(self, req, reason):
        # a step in flight may still write this request's next row: into
        # a block of its own budget, and the device runs calls in the
        # order they were dispatched, so a prefill that reuses the
        # block runs after that write
        req.done = True
        self.cache.free(req.table)
        now = time.perf_counter()
        if req.tctx is not None:        # tracing was armed at submit
            _obs.record_span(
                "serving.request", req.tctx, t0=req.t_submit,
                dur=now - req.t_submit,
                attrs={"model": self._label, "generative": True,
                       "tokens": req.generated, "reason": reason})
        if _telemetry._ENABLED:
            _telemetry.hooks.decode_finish(self._label, reason,
                                           req.generated)
            _telemetry.hooks.serving_latency(now - req.t_submit)
        req.stream._finish(reason)

    # -- introspection --------------------------------------------------
    def queue_depth(self):
        with self._cond:
            return len(self._pending)

    def active_sequences(self):
        with self._cond:
            return len(self._active)

    def live_sequences(self):
        with self._cond:
            return len(self._pending) + len(self._active)

    def fingerprint(self, kind, bucket):
        return self._programs.fingerprints.get((kind, bucket))

    def program_memory(self, kind, bucket):
        """``{"aliased_bytes", "temp_bytes"}`` of one compiled program
        (``kind`` "prefill" or "decode"), from its executable's
        ``memory_analysis()``; None where the backend reports nothing.
        In place means ``aliased_bytes == cache.slab_bytes()``."""
        return self._programs.memory.get((kind, bucket))

    # -- lifecycle ------------------------------------------------------
    def close(self, drain=True):
        """Stop intake and shut the loop down.  ``drain=True`` keeps
        STEPPING until every admitted sequence runs to completion (the
        mid-decode hot-swap path rides this); ``drain=False`` resolves
        everything as closed.  Returns the number of sequences that
        were in flight when close was called."""
        with self._cond:
            if self._closed:
                return 0
            self._closed = True
            self._drain = drain
            live = len(self._pending) + len(self._active)
            self._drained_live = live
            self._cond.notify_all()
        t = self._thread
        if t is not None:
            t.join()
            self._thread = None
        return live

    @property
    def closed(self):
        return self._closed


class GenerativeServable:
    """One deployed generative model: a :class:`DecodeEngine` behind
    the registry's servable surface (lookup / drain / introspection
    compatible with :class:`~mxnet_tpu.serving.registry.Servable`)."""

    source = "generative"

    def __init__(self, name, engine):
        self.name = name
        self._engine = engine

    # -- client surface -------------------------------------------------
    def generate(self, prompt, max_new_tokens, eos_id=None,
                 timeout=None):
        """Stream greedy-decoded tokens for ``prompt``; returns a
        :class:`GenerationStream`."""
        return self._engine.submit(prompt, max_new_tokens,
                                   eos_id=eos_id, timeout=timeout)

    # -- introspection --------------------------------------------------
    @property
    def engine(self):
        return self._engine

    @property
    def buckets(self):
        return self._engine.decode_buckets

    @property
    def prefill_buckets(self):
        return self._engine.prefill_buckets

    def queue_depth(self):
        return self._engine.queue_depth()

    @property
    def queue_capacity(self):
        return self._engine.max_queue

    def kvcache_stats(self):
        return self._engine.cache.stats()

    @property
    def closed(self):
        return self._engine.closed

    def close(self, drain=True):
        return self._engine.close(drain=drain)

    def __repr__(self):
        return ("GenerativeServable(%r, prefill=%r, decode=%r, kv=%s)"
                % (self.name, self._engine.prefill_buckets,
                   self._engine.decode_buckets,
                   self._engine.cache.stats()))


class GenerativeWatcher(_RegistryWatcher):
    """The :class:`~mxnet_tpu.serving.loop.RegistryWatcher` contract
    for generative servables: same verified-step discovery, same
    retry/backoff/failure-budget state machine (it IS a
    RegistryWatcher), but a swap re-registers through
    ``register_generative`` -- params restored from the checkpoint's
    ``params`` item -- and the old engine drains its half-generated
    sequences to completion (zero dropped, counted under
    ``chaos.survived.serving.decode_swap``)."""

    def __init__(self, registry, name, checkpoint, model, **kwargs):
        # block/input_shape/dtype are fixed-shape-servable concepts;
        # the base class only threads them into register(), which
        # _register_step replaces wholesale
        super().__init__(registry, name, checkpoint, block=None,
                         input_shape=(), **kwargs)
        self.model = model

    def _register_step(self, step):
        self.registry.register_generative(
            self.name, model=self.model, checkpoint=self.manager,
            step=step, **self._register_kwargs)
