"""Block/paged KV cache manager (the vLLM idea on bucketing.py's slab
discipline).

The generative engine never allocates per-request device memory: at
construction it preallocates, PER LAYER, one slab for each kind of row
the MODEL declares a token to hold there (``rows``: name -> shape of
one token's row).  A GPT-style decoder declares ``k`` and ``v`` of
``(heads, head_dim)``, so ``slabs["k"][i]`` and ``slabs["v"][i]`` are
``(num_blocks, block_size, heads, lanes)``; a latent-attention (MLA)
decoder declares ONE row, ``latent`` of ``(kv_lora_rank +
qk_rope_head_dim,)``, so ``slabs["latent"][i]`` is ``(num_blocks,
block_size, lanes)``.  In both the first ``shape[-1]`` lanes are in
use.  The slabs are carved into fixed-size blocks, and a request is
admitted by handing it a **block table** (the ordered list of block
ids its tokens map onto; one table names the same blocks in every
layer's slabs).  Token position ``p`` of a request lives at
``(table[p // block_size], p % block_size)``; the decode-step attention
kernel gathers its rows through the table, so sequences share the
slabs without ever being contiguous.  Allocation, the scratch block,
donation and the lane padding are the same code whatever the rows are.

The slabs are **donated**: ``slabs`` is one pytree (``{name: (layer 0's
array, layer 1's, ...)}``) that the engine's compiled prefill and
decode programs take as one argument and write in place
(``DecodeEngine`` compiles them with ``donate_argnums``; each layer's
scatter and its attention kernel work on that layer's own array, so no
program ever copies a slab).  After a call the arrays that went in are
deleted and the engine rebinds ``slabs`` to the call's outputs.  So
nobody but the engine's loop may hold on to a slab across a call: read
``cache.slabs[name][i]`` afresh, and copy (``np.asarray``) what must
outlive the next step.

In place also needs the slabs to LIE in memory the way the programs
work on them, and that is why a slab's last dimension is the row's own
rounded up to whole 128-lane tiles (``lanes_for``), with the lanes past
the row's width never read.  A TPU tiles the two minor dimensions of an
array in (8, 128) tiles, so a 64-wide head occupies 128 lanes however
it is declared; but for an array DECLARED 64 wide the device's default
layout is a compact one with ``num_blocks`` minor, while the scatter
and the Mosaic kernel work row-major -- every layer then converts its
slab on the way in and on the way out, two slab copies a layer whatever
is donated.  Declared in whole tiles, the default layout IS the
row-major one, ``slab[..., :head_dim]`` is a view of the same tiles (the
compiler makes it a bitcast), and nothing is copied.  (Asking for a
row-major layout of the 64-wide array through ``jax.experimental.
layout`` compiles to the same program, but an executable read back
from JAX's persistent compilation cache hands its outputs back tagged
with the default layout and the next call refuses them; my chip runs,
PR 26.)  On a backend without tiles the padding is real memory.

**Two kinds of layer** may live side by side in ONE manager.  The model
says which layer is of which kind (``cache_layers()``: ``"full"`` or
``"window"`` a layer; a model without the method has full layers only).
A full layer's table covers the request's whole budget, as above.  A
WINDOW layer attends over its last ``window`` positions only, so a
sequence keeps a **bounded ring** there: ``ring = ceil(window /
block_size) + 1`` blocks whatever its length (fewer for a request
shorter than that), position ``p`` living in ring entry ``(p //
block_size) % ring`` -- the entry that held position ``p - ring *
block_size``, which no query can see any more.  The ring's blocks come
from a pool of their own (``window_blocks`` blocks in every window
layer's slabs, block 0 the scratch block there too): one
:class:`BlockTable` holds both lists, and :meth:`allocate` takes from
both pools or from neither.  A reader of a window layer's slab may
assume nothing of a ring entry but this: after the write of position
``p`` the entry ``(p // block_size) % ring`` holds positions ``(p //
block_size) * block_size .. p`` at their offsets, and the ``ring - 1``
entries before it (cyclically) the ``ring - 1`` blocks before that one;
rows past ``p`` in its entry are stale.

**Passes**: a model that runs its stack of layers several times a
token over ONE set of weights keeps a token's rows once a pass a layer
(``model.cache_passes``): a **cache layer** is then one (pass, layer)
pair, and there are ``cache_layers = passes * layers`` of them over
``layers`` layers of weights.  A layer of weights still owns ONE array a
row, which holds the blocks of all its passes: ``passes * num_blocks``
blocks, pass ``t``'s copy of block ``b`` at ``t * num_blocks + b``.  The
allocator, the block tables and the gauges know nothing of passes -- a
sequence holds the same block ids in every pass -- and a program reaches
pass ``t`` by adding ``t * num_blocks`` to the table it hands
:func:`write_tokens`, :func:`write_prompt` and the attention kernel,
whose block index is data: no slab is ever sliced by pass.  Block ``t *
num_blocks`` is the scratch block of pass ``t``.  :meth:`slab_bytes` and
``stats()["kv_bytes_per_token"]`` count every cache layer.

**State layers**: a layer whose kind is ``"state"`` (a linear-attention
layer: a recurrent state of fixed size a SEQUENCE, not a row a token)
owns no slab of rows and reads no block table.  The model declares what
ONE sequence keeps in ONE such layer (``cache_states()``: name ->
``(shape, dtype)``, e.g. a float32 ``(heads, key, value)`` state and the
short convolution's last inputs), and the cache keeps, a state layer,
one array a state name, ``(state_rows, *shape)``: ``slabs[name][j]`` is
the ``j``-th state layer's, beside ``slabs[row][i]`` for the ``i``-th
TABLE layer (full or window).  A sequence is handed ONE row of them,
the same in every state layer, from a pool of ``state_rows - 1`` (row 0
the scratch row, as block 0 is the scratch block): :meth:`allocate`
takes it with the sequence's blocks, all or nothing, the table holds it
as ``table.of("state") == [row]``, :meth:`free` returns it, and
:meth:`blocks_needed` counts it as a table one entry wide, so a program
is handed each slot's state row as it is handed its block table.  A
prefill writes the sequence's whole state (nothing of an earlier
sequence in that row survives it); padded slots read and write the
scratch row.  The state rows are donated with the slabs and counted in
:meth:`slab_bytes`; ``stats()["state_bytes"]`` is their share.

**Folded heads**: a ``(heads, width)`` row lies ``(num_blocks,
block_size, heads, lanes)`` by default.  A bfloat16 array's tiles are
(16, 128), so 4 K/V heads in the second-minor dimension would be padded
to 16 on the chip; with ``fold_heads`` the slab is declared
``(num_blocks, block_size * heads, lanes)`` -- the same memory order,
(token, head) rows, and whole tiles.  :func:`write_tokens` and
:func:`write_prompt` write either layout.

Admission-time sizing is the backpressure contract: a request's whole
budget -- ``prompt_len + max_new_tokens`` -- is allocated **at
admission** and the allocator raises :class:`KVCacheExhausted` when the
free list cannot cover it, so a running sequence can NEVER fail
mid-generation for cache space (the engine maps the exhaustion to the
standard :class:`~mxnet_tpu.serving.batcher.ServingQueueFull` shed).
EOS, max-token completion, cancel and timeout all return blocks through
:meth:`free` -- ``kvcache.blocks_in_use`` returning to zero after a
drain is the leak-proof gate CI holds.

Block 0 is reserved as the **scratch block**: padded decode slots and
padded prefill positions route their writes there (a compiled program
always writes *somewhere*), so it is never handed to a request and its
contents are garbage by design.

Telemetry: ``kvcache.blocks_in_use`` (all pools of blocks; with two
kinds of table layer also ``kvcache.blocks_in_use.full`` / ``.window``;
with state layers ``kvcache.state_rows_in_use``) /
``kvcache.fragmentation`` gauges, ``kvcache.allocs`` / ``kvcache.frees`` /
``kvcache.alloc_failures`` counters.
"""
from __future__ import annotations

from ... import sync as _sync
from ... import telemetry as _telemetry
from ...base import MXNetError

__all__ = ["PagedKVCache", "BlockTable", "KVCacheExhausted",
           "SCRATCH_BLOCK", "FULL", "WINDOW", "STATE", "slab_rows",
           "lanes_for",
           "write_tokens", "write_prompt"]

# the kinds of layer a model may declare (``cache_layers()``): two read a
# block table of rows, one keeps a state a sequence
FULL, WINDOW, STATE = "full", "window", "state"

# a TPU's lane count: the minor dimension of its (8, 128) memory tiles
LANE_TILE = 128

# block id 0 is the write sink for padded slots/positions; never
# allocated to a request (see module doc)
SCRATCH_BLOCK = 0


def lanes_for(width):
    """``width`` rounded up to whole 128-lane tiles: the last dimension
    of a slab whose rows are ``width`` wide (module doc)."""
    return -(-int(width) // LANE_TILE) * LANE_TILE


def slab_rows(rows, slab):
    """``rows`` (..., width) as whole rows of ``slab`` (num_blocks,
    block_size, ..., lanes >= width): the slab's dtype, zeros in the
    lanes past the rows' own width.  What a program scatters
    into a slab: a whole-row update is one scatter, a window of some
    lanes a loop of slot-sized updates on the TPU."""
    import jax.numpy as jnp
    pad = slab.shape[-1] - rows.shape[-1]
    rows = rows.astype(slab.dtype)
    if pad:
        rows = jnp.pad(rows, [(0, 0)] * (rows.ndim - 1) + [(0, pad)])
    return rows


def _entries(table, blocks):
    """The table's entry for each logical block: a table wide enough
    for every block is a ring that never wraps."""
    import jax.numpy as jnp
    return jnp.take(table, blocks % table.shape[-1], axis=-1)


def write_tokens(slab, rows, table, positions, block_size):
    """One new row a slot into ``slab``: ``rows`` (slots, ..., width) at
    ``positions`` (slots,) through ``table`` (slots, width of the
    table), read as a ring (module doc).  ``slab`` (num_blocks,
    block_size, ..., lanes) or, heads folded, (num_blocks, block_size *
    heads, lanes).  The padded slots of a bucket all write (scratch
    block, offset 0): the scatter's indices are not unique and nothing
    is promised to XLA about them."""
    import jax.numpy as jnp
    blk = jnp.take_along_axis(
        table, ((positions // block_size) % table.shape[1])[:, None],
        axis=1)[:, 0]
    off = positions % block_size
    rows = slab_rows(rows, slab)
    if slab.ndim == rows.ndim + 1:
        return slab.at[blk, off].set(rows)
    heads = rows.shape[1]                           # folded
    return slab.at[blk[:, None], off[:, None] * heads
                   + jnp.arange(heads, dtype=off.dtype)].set(rows)


def write_prompt(slab, rows, table, true_len, block_size, ring=False):
    """A prompt's rows into ``slab``: ``rows`` (t, ..., width), of which
    the first ``true_len`` are the prompt's, through ``table`` (width of
    the table,).  ``ring``: only the rows the ring still holds at the
    prompt's end are written (the last ``table width`` blocks; a window
    layer's).  Padding goes to the scratch block."""
    import jax
    import jax.numpy as jnp
    t = rows.shape[0]
    rows = slab_rows(rows, slab)
    if slab.ndim == rows.ndim + 1:
        pos = jnp.arange(t, dtype=jnp.int32)
        kept = pos < true_len
        if ring:
            kept &= pos // block_size \
                > (true_len - 1) // block_size - table.shape[0]
        blocks = pos // block_size
        blk = jnp.where(kept, _entries(table, blocks) if ring
                        else jnp.take(table, blocks), SCRATCH_BLOCK)
        return slab.at[blk, pos % block_size].set(rows)
    # folded heads: whole blocks of (token, head) rows, one update each
    n_blocks = -(-t // block_size)
    rows = jnp.pad(rows, [(0, n_blocks * block_size - t)]
                   + [(0, 0)] * (rows.ndim - 1))
    rows = rows.reshape((n_blocks, block_size * rows.shape[1])
                        + rows.shape[2:])
    last = (true_len - 1) // block_size
    n = min(n_blocks, table.shape[0]) if ring else n_blocks
    # the n blocks that end with the prompt's last (all of them: n_blocks)
    first = jnp.clip(last + 1 - n, 0, n_blocks - n)
    rows = jax.lax.dynamic_slice_in_dim(rows, first, n, axis=0)
    blocks = first + jnp.arange(n, dtype=jnp.int32)
    blk = jnp.where((blocks <= last) & (blocks > last - table.shape[0]),
                    _entries(table, blocks), SCRATCH_BLOCK)
    return slab.at[blk].set(rows)


class KVCacheExhausted(MXNetError):
    """Admission-time allocation failed: the free list cannot cover the
    request's ``prompt + max_new`` block budget.  The engine sheds the
    request (ServingQueueFull) -- it is never raised mid-generation."""


class BlockTable:
    """One request's ordered block ids plus its token-capacity bound:
    ``blocks`` in the full layers' slabs and, where the cache has window
    layers, ``ring`` in theirs; where it has state layers, ``state``
    the one row the sequence keeps in them."""

    __slots__ = ("blocks", "ring", "state", "capacity", "freed")

    def __init__(self, blocks, capacity, ring=(), state=()):
        self.blocks = list(blocks)
        self.ring = list(ring)
        self.state = list(state)
        self.capacity = int(capacity)   # tokens the table can hold
        self.freed = False

    def of(self, kind):
        return self.ring if kind == WINDOW else \
            self.state if kind == STATE else self.blocks

    def __len__(self):
        return len(self.blocks)

    def __repr__(self):
        return "BlockTable(blocks=%r%s%s, capacity=%d%s)" % (
            self.blocks, ", ring=%r" % (self.ring,) if self.ring else "",
            ", state=%r" % (self.state,) if self.state else "",
            self.capacity, ", freed" if self.freed else "")


class _Pool:
    """The blocks of one kind of layer: ``num_blocks`` in each of its
    slabs, block 0 the scratch block, the rest a free list."""

    def __init__(self, num_blocks):
        self.num_blocks = int(num_blocks)
        self.free = list(range(1, self.num_blocks))     # 0 = scratch

    @property
    def total(self):
        return self.num_blocks - 1

    @property
    def in_use(self):
        return self.total - len(self.free)


class PagedKVCache:
    """Fixed-size block allocator over preallocated per-layer slabs.

    Parameters
    ----------
    layers : how many layers own slabs (the model's layers of weights)
    rows : ``{name: shape}``, what ONE token holds in ONE layer, as the
        model declares it (``model.cache_rows()``): ``{"k": (heads,
        head_dim), "v": (heads, head_dim)}`` or ``{"latent": (576,)}``
    block_size : tokens per block
    num_blocks : total blocks in a full layer's slab (block 0 is
        scratch, so the allocatable pool is ``num_blocks - 1``)
    dtype : cache dtype
    kinds : the kind of each layer, ``"full"``, ``"window"`` or
        ``"state"`` (``model.cache_layers()``); None: every layer full
    window : positions a window layer attends over; sizes the ring
    window_blocks : total blocks in a window layer's slab
    fold_heads : lay a ``(heads, width)`` row's heads into the block's
        rows (module doc)
    passes : cache layers a layer of weights keeps
        (``model.cache_passes``; module doc, "Passes")
    states : ``{name: (shape, dtype)}``, what ONE sequence keeps in ONE
        state layer (``model.cache_states()``; module doc, "State
        layers")
    state_rows : rows of each state array (row 0 is scratch, so
        ``state_rows - 1`` sequences can hold one at a time)
    """

    def __init__(self, layers, rows, block_size, num_blocks,
                 dtype="float32", kinds=None, window=None,
                 window_blocks=None, fold_heads=False, passes=1,
                 states=None, state_rows=None):
        import numpy as np
        if block_size < 1 or num_blocks < 2:
            raise MXNetError(
                "PagedKVCache needs block_size >= 1 and num_blocks >= 2 "
                "(block 0 is the reserved scratch block), got "
                "block_size=%r num_blocks=%r" % (block_size, num_blocks))
        if not rows or any(not shape or min(shape) < 1
                           for shape in rows.values()):
            raise MXNetError(
                "PagedKVCache needs the rows a token holds, {name: "
                "shape} with positive sizes, got %r" % (rows,))
        if passes < 1:
            raise MXNetError("PagedKVCache needs passes >= 1, got %r"
                             % (passes,))
        self.layers = int(layers)
        self.passes = int(passes)
        self.kinds = tuple(kinds) if kinds is not None \
            else (FULL,) * self.layers
        if len(self.kinds) != self.layers \
                or set(self.kinds) - {FULL, WINDOW, STATE}:
            raise MXNetError(
                "PagedKVCache needs one kind a layer, %r, %r or %r: %d "
                "layers, kinds %r" % (FULL, WINDOW, STATE, self.layers,
                                      kinds))
        # the layers that keep rows through a block table, and those
        # that keep a state a sequence: each list owns its own arrays
        self.table_kinds = tuple(k for k in self.kinds if k != STATE)
        self.state_layers = self.layers - len(self.table_kinds)
        self.cache_layers = len(self.table_kinds) * self.passes
        self.states = {str(name): (tuple(int(n) for n in shape),
                                   np.dtype(dt))
                       for name, (shape, dt) in (states or {}).items()}
        if bool(self.state_layers) != bool(self.states) \
                or (self.states and (self.passes != 1 or not state_rows
                                     or state_rows < 2)):
            raise MXNetError(
                "a cache with state layers needs the states a sequence "
                "keeps, state_rows >= 2 and one pass, and a cache without "
                "them no states: %d state layers, states %r, state_rows "
                "%r, passes %d" % (self.state_layers, states, state_rows,
                                   self.passes))
        self.rows = {str(name): tuple(int(n) for n in shape)
                     for name, shape in rows.items()}
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        self.dtype = np.dtype(dtype)
        self.fold_heads = bool(fold_heads)
        self.window = self.ring = None
        self._pools = {FULL: _Pool(num_blocks)}
        if WINDOW in self.kinds:
            if not window or window < 1 or not window_blocks \
                    or window_blocks < 2:
                raise MXNetError(
                    "a cache with window layers needs window >= 1 and "
                    "window_blocks >= 2, got window=%r window_blocks=%r"
                    % (window, window_blocks))
            self.window = int(window)
            # the blocks that hold a window's positions, wherever in a
            # block it starts
            self.ring = -(-self.window // self.block_size) + 1
            self._pools[WINDOW] = _Pool(window_blocks)
        self.state_rows = None
        if self.states:
            # a state row is a "block" of its own pool: one a sequence
            self.state_rows = int(state_rows)
            self._pools[STATE] = _Pool(self.state_rows)
        # whole 128-lane tiles, so that the device's default layout is
        # the row-major one the programs work in (module doc)
        self.slab_shapes = {name: self._slab_shape(name, FULL)
                            for name in self.rows}
        self.reset_slabs()
        self._lock = _sync.Lock(name="serving.kvcache")
        self._used_tokens = {}          # id(table) -> tokens written

    def _slab_shape(self, name, kind):
        shape = self.rows[name]
        head = (self.passes * self._pools[kind].num_blocks,
                self.block_size)
        if self.fold_heads and len(shape) == 2:
            head, shape = (head[0], head[1] * shape[0]), shape[1:]
        return head + shape[:-1] + (lanes_for(shape[-1]),)

    # -- the slabs ------------------------------------------------------
    def reset_slabs(self):
        """Allocate fresh zeroed slabs: ``slabs[name][i]`` for every
        declared row and layer, a window layer's with the window pool's
        blocks.  The engine's programs are compiled with
        the whole pytree donated (``DecodeEngine.warmup``), so a call
        consumes the arrays it is given and writes the new rows in
        place; the engine binds the call's outputs here again.  Also the
        way back after a call that failed with its arguments already
        consumed."""
        import jax.numpy as jnp
        # let go of the old slabs first: two sets may not fit the device
        self.slabs = {}
        self.slabs = {
            name: tuple(jnp.zeros(self._slab_shape(name, kind), self.dtype)
                        for kind in self.table_kinds)
            for name in self.rows}
        for name, (shape, dt) in self.states.items():
            self.slabs[name] = tuple(
                jnp.zeros((self.state_rows,) + shape, dt)
                for _ in range(self.state_layers))

    def _arrays(self):
        return [a for layers in self.slabs.values() for a in layers]

    def slab_bytes(self):
        """Bytes the slabs of all layers hold on their device (unused
        lanes included), state arrays with them."""
        return sum(a.on_device_size_in_bytes() for a in self._arrays())

    def state_bytes(self):
        """Bytes the state layers' arrays hold on their device."""
        return sum(a.on_device_size_in_bytes() for name in self.states
                   for a in self.slabs[name])

    def slabs_deleted(self):
        """Whether a call consumed the slabs without handing new ones
        back (a donating call that raised after it took them)."""
        return any(a.is_deleted() for a in self._arrays())

    def kv_bytes_per_token(self):
        """Bytes the rows of ONE token take over all cache layers, at
        the rows' own width (lanes a slab pads them to not counted)."""
        import math
        return self.cache_layers * self.dtype.itemsize * sum(
            math.prod(shape) for shape in self.rows.values())

    # -- sizing ---------------------------------------------------------
    def blocks_for(self, n_tokens):
        """Blocks a full layer needs to hold ``n_tokens`` (ceil)."""
        return max(1, -(-int(n_tokens) // self.block_size))

    def blocks_needed(self, n_tokens):
        """{kind: blocks} a sequence of ``n_tokens`` holds in a layer of
        each kind the cache has: its whole length in a full layer, the
        ring at the most in a window one, one row in a state one.  What
        :meth:`allocate` takes for a request, and (of the longest
        sequence) how wide the fixed-width tables of a compiled program
        are."""
        need = self.blocks_for(n_tokens)
        return {kind: min(need, self.ring) if kind == WINDOW
                else 1 if kind == STATE else need
                for kind in self._pools}

    @property
    def total_blocks(self):
        """Allocatable pool size of the full layers (scratch excluded)."""
        return self.num_blocks - 1

    def free_blocks(self, kind=FULL):
        with self._lock:
            return len(self._pools[kind].free)

    def blocks_in_use(self, kind=None):
        """Blocks requests hold: of one kind's pool, or (None) of all
        the pools of blocks (state rows are not blocks)."""
        with self._lock:
            return self._in_use_locked(kind)

    def _in_use_locked(self, kind=None):
        if kind is not None:
            return self._pools[kind].in_use
        return sum(pool.in_use for kind, pool in self._pools.items()
                   if kind != STATE)

    def can_admit(self, n_tokens):
        """Whether :meth:`allocate` for ``n_tokens`` would succeed now
        (admission pre-check; racing admitters still handle the
        exception path)."""
        with self._lock:
            return all(n <= len(self._pools[kind].free)
                       for kind, n in self.blocks_needed(n_tokens).items())

    # -- allocate / free ------------------------------------------------
    def allocate(self, n_tokens):
        """Carve a :class:`BlockTable` holding ``n_tokens`` from the
        free lists -- every pool's share or none of any -- or raise
        :class:`KVCacheExhausted` (counted as
        ``kvcache.alloc_failures``) without partial allocation."""
        need = self.blocks_needed(n_tokens)
        with self._lock:
            short = [(kind, n, len(self._pools[kind].free))
                     for kind, n in need.items()
                     if n > len(self._pools[kind].free)]
            if not short:
                got = {kind: [self._pools[kind].free.pop()
                              for _ in range(n)]
                       for kind, n in need.items()}
                table = BlockTable(
                    got[FULL], capacity=need[FULL] * self.block_size,
                    ring=got.get(WINDOW, ()), state=got.get(STATE, ()))
                self._used_tokens[id(table)] = int(n_tokens)
                gauges = self._gauges_locked()
        if short:
            if _telemetry._ENABLED:
                _telemetry.hooks.kvcache_alloc_failure()
            kind, n, free = short[0]
            raise KVCacheExhausted(
                "kv cache exhausted: need %d %s blocks for %d tokens, "
                "%d free (of %d)" % (n, kind, n_tokens, free,
                                     self._pools[kind].total))
        if _telemetry._ENABLED:
            _telemetry.hooks.kvcache_alloc(*gauges)
        return table

    def free(self, table):
        """Return a table's blocks to the free lists.  Idempotent -- the
        EOS/timeout/cancel paths may race a drain, and double-freeing a
        block would corrupt a live sequence."""
        with self._lock:
            if table.freed:
                return
            table.freed = True
            for kind, pool in self._pools.items():
                pool.free.extend(table.of(kind))
            self._used_tokens.pop(id(table), None)
            gauges = self._gauges_locked()
        if _telemetry._ENABLED:
            _telemetry.hooks.kvcache_free(*gauges)

    def _gauges_locked(self):
        """(blocks in use over all pools of blocks, fragmentation,
        {kind: blocks in use} where there is more than one kind of
        table, state rows in use where there are state layers)."""
        tables = {kind: pool.in_use for kind, pool in self._pools.items()
                  if kind != STATE}
        gauges = (self._in_use_locked(), self._fragmentation_locked(),
                  tables if len(tables) > 1 else None)
        if STATE in self._pools:
            gauges += (self._pools[STATE].in_use,)
        return gauges

    # -- introspection --------------------------------------------------
    def _fragmentation_locked(self):
        """Internal fragmentation of the full layers' pool: share of
        allocated token slots not (yet) holding a token --
        admission-time whole-budget allocation makes this the honest
        cost of the shed-never-mid-generation contract."""
        in_use = self._pools[FULL].in_use
        if in_use == 0:
            return 0.0
        used = sum(self._used_tokens.values())
        return max(0.0, 1.0 - used / float(in_use * self.block_size))

    def note_tokens(self, table, n_tokens):
        """Update the written-token count for ``table`` (fragmentation
        accounting only; capacity is fixed at admission)."""
        with self._lock:
            if not table.freed:
                self._used_tokens[id(table)] = int(n_tokens)

    def stats(self):
        with self._lock:
            full = self._pools[FULL]
            out = {
                "block_size": self.block_size,
                "total_blocks": full.total,
                "blocks_in_use": full.in_use,
                "free_blocks": len(full.free),
                "fragmentation": round(self._fragmentation_locked(), 4),
                "cache_layers": self.cache_layers,
                "kv_bytes_per_token": self.kv_bytes_per_token(),
            }
            if WINDOW in self._pools:
                ring = self._pools[WINDOW]
                out.update(window=self.window, ring=self.ring,
                           window_total_blocks=ring.total,
                           window_blocks_in_use=ring.in_use,
                           window_free_blocks=len(ring.free))
            if STATE in self._pools:
                rows = self._pools[STATE]
                out.update(state_layers=self.state_layers,
                           state_rows_total=rows.total,
                           state_rows_in_use=rows.in_use,
                           state_bytes=self.state_bytes())
            return out

    def padded_table(self, table, width, kind=FULL):
        """The table as a fixed-width int32 row for a compiled program:
        real ids first, scratch-block padding after (padded positions
        write into scratch, reads are masked by context length)."""
        import numpy as np
        blocks = table.of(kind)
        if len(blocks) > width:
            raise MXNetError(
                "block table %d wider than compiled width %d"
                % (len(blocks), width))
        row = np.full((width,), SCRATCH_BLOCK, np.int32)
        row[:len(blocks)] = blocks
        return row

    def __repr__(self):
        return "PagedKVCache(%s)" % (self.stats(),)
