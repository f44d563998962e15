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

Telemetry: ``kvcache.blocks_in_use`` / ``kvcache.fragmentation``
gauges, ``kvcache.allocs`` / ``kvcache.frees`` /
``kvcache.alloc_failures`` counters.
"""
from __future__ import annotations

from ... import sync as _sync
from ... import telemetry as _telemetry
from ...base import MXNetError

__all__ = ["PagedKVCache", "BlockTable", "KVCacheExhausted",
           "SCRATCH_BLOCK", "slab_rows", "lanes_for"]

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


class KVCacheExhausted(MXNetError):
    """Admission-time allocation failed: the free list cannot cover the
    request's ``prompt + max_new`` block budget.  The engine sheds the
    request (ServingQueueFull) -- it is never raised mid-generation."""


class BlockTable:
    """One request's ordered block ids plus its token-capacity bound."""

    __slots__ = ("blocks", "capacity", "freed")

    def __init__(self, blocks, capacity):
        self.blocks = list(blocks)
        self.capacity = int(capacity)   # tokens the table can hold
        self.freed = False

    def __len__(self):
        return len(self.blocks)

    def __repr__(self):
        return "BlockTable(blocks=%r, capacity=%d%s)" % (
            self.blocks, self.capacity, ", freed" if self.freed else "")


class PagedKVCache:
    """Fixed-size block allocator over preallocated per-layer slabs.

    Parameters
    ----------
    layers : how many layers keep rows
    rows : ``{name: shape}``, what ONE token holds in ONE layer, as the
        model declares it (``model.cache_rows()``): ``{"k": (heads,
        head_dim), "v": (heads, head_dim)}`` or ``{"latent": (576,)}``
    block_size : tokens per block
    num_blocks : total blocks in a slab (block 0 is scratch, so the
        allocatable pool is ``num_blocks - 1``)
    dtype : cache dtype
    """

    def __init__(self, layers, rows, block_size, num_blocks,
                 dtype="float32"):
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
        self.layers = int(layers)
        self.rows = {str(name): tuple(int(n) for n in shape)
                     for name, shape in rows.items()}
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        self.dtype = np.dtype(dtype)
        # whole 128-lane tiles, so that the device's default layout is
        # the row-major one the programs work in (module doc)
        self.slab_shapes = {
            name: (self.num_blocks, self.block_size) + shape[:-1]
            + (lanes_for(shape[-1]),)
            for name, shape in self.rows.items()}
        self.reset_slabs()
        self._lock = _sync.Lock(name="serving.kvcache")
        self._free = list(range(1, self.num_blocks))  # 0 = scratch
        self._used_tokens = {}          # id(table) -> tokens written

    # -- the slabs ------------------------------------------------------
    def reset_slabs(self):
        """Allocate fresh zeroed slabs: ``slabs[name][i]`` for every
        declared row and layer.  The engine's programs are compiled with
        the whole pytree donated (``DecodeEngine.warmup``), so a call
        consumes the arrays it is given and writes the new rows in
        place; the engine binds the call's outputs here again.  Also the
        way back after a call that failed with its arguments already
        consumed."""
        import jax.numpy as jnp
        # let go of the old slabs first: two sets may not fit the device
        self.slabs = {}
        self.slabs = {
            name: tuple(jnp.zeros(shape, self.dtype)
                        for _ in range(self.layers))
            for name, shape in self.slab_shapes.items()}

    def _arrays(self):
        return [a for layers in self.slabs.values() for a in layers]

    def slab_bytes(self):
        """Bytes the slabs of all layers hold on their device (unused
        lanes included)."""
        return sum(a.on_device_size_in_bytes() for a in self._arrays())

    def slabs_deleted(self):
        """Whether a call consumed the slabs without handing new ones
        back (a donating call that raised after it took them)."""
        return any(a.is_deleted() for a in self._arrays())

    # -- sizing ---------------------------------------------------------
    def blocks_for(self, n_tokens):
        """Blocks needed to hold ``n_tokens`` (ceil)."""
        return max(1, -(-int(n_tokens) // self.block_size))

    @property
    def total_blocks(self):
        """Allocatable pool size (scratch excluded)."""
        return self.num_blocks - 1

    def free_blocks(self):
        with self._lock:
            return len(self._free)

    def blocks_in_use(self):
        with self._lock:
            return self.total_blocks - len(self._free)

    def can_admit(self, n_tokens):
        """Whether :meth:`allocate` for ``n_tokens`` would succeed now
        (admission pre-check; racing admitters still handle the
        exception path)."""
        with self._lock:
            return self.blocks_for(n_tokens) <= len(self._free)

    # -- allocate / free ------------------------------------------------
    def allocate(self, n_tokens):
        """Carve a :class:`BlockTable` holding ``n_tokens`` from the
        free list, or raise :class:`KVCacheExhausted` (counted as
        ``kvcache.alloc_failures``) without partial allocation."""
        need = self.blocks_for(n_tokens)
        with self._lock:
            if need > len(self._free):
                shortfall = (need, len(self._free))
            else:
                blocks = [self._free.pop() for _ in range(need)]
                table = BlockTable(blocks,
                                   capacity=need * self.block_size)
                self._used_tokens[id(table)] = int(n_tokens)
                in_use = self.total_blocks - len(self._free)
                frag = self._fragmentation_locked()
                shortfall = None
        if shortfall is not None:
            if _telemetry._ENABLED:
                _telemetry.hooks.kvcache_alloc_failure()
            raise KVCacheExhausted(
                "kv cache exhausted: need %d blocks for %d tokens, "
                "%d free (of %d)" % (shortfall[0], n_tokens,
                                     shortfall[1], self.total_blocks))
        if _telemetry._ENABLED:
            _telemetry.hooks.kvcache_alloc(in_use, frag)
        return table

    def free(self, table):
        """Return a table's blocks to the free list.  Idempotent -- the
        EOS/timeout/cancel paths may race a drain, and double-freeing a
        block would corrupt a live sequence."""
        with self._lock:
            if table.freed:
                return
            table.freed = True
            self._free.extend(table.blocks)
            self._used_tokens.pop(id(table), None)
            in_use = self.total_blocks - len(self._free)
            frag = self._fragmentation_locked()
        if _telemetry._ENABLED:
            _telemetry.hooks.kvcache_free(in_use, frag)

    # -- introspection --------------------------------------------------
    def _fragmentation_locked(self):
        """Internal fragmentation: share of allocated token slots not
        (yet) holding a token -- admission-time whole-budget allocation
        makes this the honest cost of the shed-never-mid-generation
        contract."""
        in_use = self.total_blocks - len(self._free)
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
            in_use = self.total_blocks - len(self._free)
            return {
                "block_size": self.block_size,
                "total_blocks": self.total_blocks,
                "blocks_in_use": in_use,
                "free_blocks": len(self._free),
                "fragmentation": round(self._fragmentation_locked(), 4),
            }

    def padded_table(self, table, width):
        """The table as a fixed-width int32 row for a compiled program:
        real ids first, scratch-block padding after (padded positions
        write into scratch, reads are masked by context length)."""
        import numpy as np
        if len(table.blocks) > width:
            raise MXNetError(
                "block table %d wider than compiled width %d"
                % (len(table.blocks), width))
        row = np.full((width,), SCRATCH_BLOCK, np.int32)
        row[:len(table.blocks)] = table.blocks
        return row

    def __repr__(self):
        return "PagedKVCache(%s)" % (self.stats(),)
