"""Generative serving tier tests (ISSUE 18): paged KV cache block
lifecycle, decode-step paged attention numerics (Pallas interpret vs
XLA reference), prefill+decode vs the full-forward oracle, continuous
batching (join mid-batch bit-identical, occupancy > 1), admission
backpressure, token streaming with per-token trace spans, mid-decode
hot swap under chaos, and the GenerativeWatcher."""
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import chaos, obs, serving, telemetry
from mxnet_tpu.serving import (RequestTimeout, ServableClosed,
                               ServingQueueFull)
from mxnet_tpu.serving.decode import (DecodeEngine, GenerativeWatcher,
                                      KVCacheExhausted, PagedKVCache,
                                      tiny_gpt)
from mxnet_tpu.serving.decode.kvcache import SCRATCH_BLOCK

MODEL = tiny_gpt(vocab_size=32, units=16, num_layers=2, num_heads=2,
                 max_seq=32)
ENGINE_KW = dict(prefill_buckets=(8, 16), decode_buckets=(1, 2, 4),
                 block_size=4, num_blocks=64, max_queue=16)


@pytest.fixture(scope="module")
def params():
    return MODEL.init_params(0)


@pytest.fixture(scope="module")
def ccache(tmp_path_factory):
    # shared on-disk compile cache: the first engine pays the AOT
    # compiles, every later engine warms from disk
    return serving.CompileCache(str(tmp_path_factory.mktemp("cc")))


@pytest.fixture()
def make_engine(params, ccache):
    engines = []

    def _make(weights=None, **overrides):
        kw = dict(ENGINE_KW, cache=ccache, **overrides)
        eng = DecodeEngine(MODEL, params if weights is None else weights,
                           **kw)
        eng.warmup()
        eng.start()
        engines.append(eng)
        return eng

    yield _make
    for eng in engines:
        eng.close(drain=False)


@pytest.fixture()
def registry(ccache, tmp_path):
    reg = serving.ModelRegistry(cache_dir=str(tmp_path / "reg_cc"))
    reg._cache = ccache
    yield reg
    reg.shutdown(drain=True)


@pytest.fixture()
def counters():
    telemetry.enable()
    for prefix in ("decode.", "kvcache.", "serving.", "chaos."):
        telemetry.reset(prefix)
    yield telemetry
    for prefix in ("decode.", "kvcache.", "serving.", "chaos."):
        telemetry.reset(prefix)
    telemetry.disable()


def _kv_rows(heads, head_dim):
    """What a GPT-style decoder declares a token to keep in a layer."""
    return {"k": (heads, head_dim), "v": (heads, head_dim)}


def _slabs(cache):
    """Every slab of every layer, as one tuple."""
    return tuple(a for layers in cache.slabs.values() for a in layers)


def _reference(params, prompt, max_new, eos_id=None):
    return MODEL.reference_decode(params, prompt, max_new, eos_id=eos_id)


# ---------------------------------------------------------------------
# paged KV cache
# ---------------------------------------------------------------------

def test_kvcache_alloc_free_cycle():
    c = PagedKVCache(2, _kv_rows(2, 8), block_size=4, num_blocks=16)
    assert c.total_blocks == 15          # block 0 reserved as scratch
    t = c.allocate(10)                   # ceil(10/4) = 3 blocks
    assert len(t.blocks) == 3
    assert SCRATCH_BLOCK not in t.blocks
    assert c.blocks_in_use() == 3
    assert c.free_blocks() == 12
    c.free(t)
    assert c.blocks_in_use() == 0
    c.free(t)                            # idempotent
    assert c.blocks_in_use() == 0


def test_kvcache_exhaustion_and_can_admit():
    c = PagedKVCache(1, _kv_rows(1, 4), block_size=4,
                     num_blocks=5)       # 4 usable
    t = c.allocate(12)                   # 3 of 4
    assert c.can_admit(4) and not c.can_admit(5)
    with pytest.raises(KVCacheExhausted):
        c.allocate(8)
    assert c.blocks_in_use() == 3        # failed alloc left no debris
    c.free(t)
    c.allocate(16)                       # the whole cache fits again


def test_kvcache_fragmentation_and_padded_table():
    c = PagedKVCache(1, _kv_rows(1, 4), block_size=4, num_blocks=16)
    t = c.allocate(6)                    # 2 blocks for 6 tokens
    c.note_tokens(t, 5)                  # 5 live of 8 allocated slots
    assert c.stats()["fragmentation"] == pytest.approx(3 / 8)
    padded = c.padded_table(t, 6)
    assert padded.shape == (6,) and padded.dtype == np.int32
    assert list(padded[:2]) == list(t.blocks)
    assert all(b == SCRATCH_BLOCK for b in padded[2:])
    c.free(t)


# ---------------------------------------------------------------------
# paged attention kernel
# ---------------------------------------------------------------------

def test_paged_attention_pallas_matches_reference():
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.paged_attention import (
        paged_attention_pallas, paged_attention_reference)
    rng = np.random.default_rng(0)
    nb, bs, h, d = 8, 4, 2, 8
    k = jnp.asarray(rng.normal(size=(nb, bs, h, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(nb, bs, h, d)).astype(np.float32))
    q = jnp.asarray(rng.normal(size=(3, h, d)).astype(np.float32))
    bt = jnp.asarray(np.array([[1, 2, 3, 0], [4, 5, 0, 0],
                               [6, 7, 1, 2]], np.int32))
    ctx = jnp.asarray(np.array([[10], [5], [16]], np.int32))
    ref = paged_attention_reference(q, k, v, bt, ctx, scale=0.35)
    pal = paged_attention_pallas(q, k, v, bt, ctx, scale=0.35,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               atol=1e-5)


def _decode_shaped(rng, mb, heads, d=64, bs=16):
    """q, slabs and a table shaped as ``decode_logits`` hands them over:
    128-lane slabs seen through their ``[..., :d]`` view, two slots whose
    tables share no block, block 0 the scratch block."""
    import jax.numpy as jnp
    nb = 2 * mb + 1
    k, v = (rng.normal(size=(nb, bs, heads, 128)).astype(np.float32)
            for _ in range(2))
    q = jnp.asarray(rng.normal(size=(2, heads, d)).astype(np.float32))
    bt = (1 + rng.permutation(nb - 1)).reshape(2, mb).astype(np.int32)
    return q, k, v, bt


# table width -> the pages a grid step walks (the largest of 8, 4, 2, 1
# that divides it)
_PAGES = {64: 8, 12: 4, 6: 2, 5: 1}


@pytest.mark.parametrize("heads", [16, 12])
@pytest.mark.parametrize("context", ["inside_a_group", "on_a_group_edge",
                                     "one_token", "full_table"])
@pytest.mark.parametrize("mb", sorted(_PAGES))
def test_paged_attention_pallas_walks_pages_like_the_reference(
        mb, context, heads):
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.paged_attention import (
        PAGES, paged_attention_pallas, paged_attention_reference)
    d, bs = 64, 16
    pages = next(p for p in PAGES if mb % p == 0)
    assert pages == _PAGES[mb]
    span = pages * bs
    q, k, v, bt = _decode_shaped(np.random.default_rng(mb + heads), mb,
                                 heads, d, bs)
    ctx0 = {"inside_a_group": span + (bs if pages > 1 else 0) + bs // 2 + 1,
            "on_a_group_edge": span, "one_token": 1,
            "full_table": mb * bs}[context]
    if context == "one_token":
        bt[0] = 0                # a bucket's padded slot: all scratch
    # the second slot ends mid-page in the table's last group, so the
    # step from one slot to the next crosses live and dead pages
    ctx = jnp.asarray(np.array([[ctx0], [mb * bs - bs // 2 - 1]], np.int32))
    args = (q, jnp.asarray(k)[..., :d], jnp.asarray(v)[..., :d],
            jnp.asarray(bt), ctx)
    ref = paged_attention_reference(*args, scale=0.125)
    pal = paged_attention_pallas(*args, scale=0.125, interpret=True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               atol=1e-5)


def test_paged_attention_pallas_masks_dead_pages():
    # the reference's poison test for the kernel: a table 8 wide is ONE
    # page group, the context ends in its second page; the pages after
    # it, the scratch block and the dead rows of the last live block
    # must not contribute
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.paged_attention import (
        paged_attention_pallas)
    rng = np.random.default_rng(2)
    k = rng.normal(size=(10, 4, 2, 8)).astype(np.float32)
    v = rng.normal(size=(10, 4, 2, 8)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(2, 2, 8)).astype(np.float32))
    bt = jnp.asarray(np.array([[1, 2, 3, 4, 5, 6, 7, 8],
                               [9, 0, 0, 0, 0, 0, 0, 0]], np.int32))
    ctx = jnp.asarray(np.array([[5], [3]], np.int32))

    def run():
        return np.asarray(paged_attention_pallas(
            q, jnp.asarray(k), jnp.asarray(v), bt, ctx, scale=0.35,
            interpret=True))
    base = run()
    for slab in (k, v):
        slab[2, 1:] = 1e6        # positions 5..7 of the last live block
        slab[3:9] = 1e6          # pages 2..7 of the live group
        slab[9, 3:] = 1e6        # the second slot's dead row
        slab[0] = 1e6            # the scratch block
    np.testing.assert_allclose(run(), base, atol=1e-6)


def test_paged_attention_grid_is_the_live_page_groups():
    # one step a page group a context reaches, no other; every page of
    # every step names a LIVE block, so nothing else is ever fetched
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.paged_attention import _live_steps
    bs, pages, mb = 4, 2, 8
    bt = np.arange(1, 33, dtype=np.int32).reshape(4, mb)
    bt[3] = 0                            # a padded slot: all scratch
    ctx = np.array([17, 32, 3, 1], np.int32)
    steps, slot, group, blocks = _live_steps(
        jnp.asarray(bt), jnp.asarray(ctx), bs, pages)
    steps = int(steps)
    assert steps == 3 + 4 + 1 + 1
    assert list(np.asarray(slot)[:steps]) == [0] * 3 + [1] * 4 + [2, 3]
    assert list(np.asarray(group)[:steps]) == [0, 1, 2, 0, 1, 2, 3, 0, 0]
    blocks = np.asarray(blocks)[:, :steps]
    assert blocks.shape == (pages, steps)
    live = {int(b) for s in range(4)
            for b in bt[s, :-(-int(ctx[s]) // bs)]}
    assert set(blocks.ravel().tolist()) == live
    # slot 0 ends in the first page of its third group: the second
    # page keeps block 4, what it held a step before
    assert blocks[:, 2].tolist() == [5, 4]


def test_paged_attention_reference_masks_dead_context():
    # tokens past context_lens must not contribute: poison them
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.paged_attention import (
        paged_attention_reference)
    rng = np.random.default_rng(1)
    k = rng.normal(size=(4, 4, 1, 4)).astype(np.float32)
    v = rng.normal(size=(4, 4, 1, 4)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(1, 1, 4)).astype(np.float32))
    bt = jnp.asarray(np.array([[1, 2]], np.int32))
    ctx = jnp.asarray(np.array([[5]], np.int32))
    base = paged_attention_reference(q, jnp.asarray(k), jnp.asarray(v),
                                     bt, ctx)
    k[2, 1:], v[2, 1:] = 1e6, 1e6        # positions 5..7: dead
    poisoned = paged_attention_reference(q, jnp.asarray(k),
                                         jnp.asarray(v), bt, ctx)
    np.testing.assert_allclose(np.asarray(poisoned), np.asarray(base),
                               atol=1e-6)


def test_paged_attention_is_registered():
    from mxnet_tpu import kernels
    assert "paged_attention" in kernels.list_kernels()
    ch = kernels.choose("paged_attention", heads=2, head_dim=8,
                        block_size=4)
    assert isinstance(ch.use_pallas, bool)


# ---------------------------------------------------------------------
# engine: numerics + streaming
# ---------------------------------------------------------------------

def test_engine_matches_full_forward_oracle(make_engine, params):
    eng = make_engine()
    for prompt in ([3, 7, 1, 9, 2], [5, 5, 6], [1]):
        stream = eng.submit(prompt, 8)
        assert stream.tokens() == _reference(params, prompt, 8)
    assert eng.cache.blocks_in_use() == 0


def test_engine_streams_incrementally(make_engine):
    eng = make_engine()
    stream = eng.submit([3, 7, 1], 6)
    seen = []
    for tok in stream:
        seen.append(tok)
        assert stream.ttft_s is not None and stream.ttft_s >= 0
    assert len(seen) == 6
    assert stream.finish_reason == "length"


def test_engine_eos_stops_and_frees(make_engine, params):
    eng = make_engine()
    ref = _reference(params, [5, 5, 6], 10)
    eos = ref[2]                         # an id the model will emit
    stream = eng.submit([5, 5, 6], 10, eos_id=eos)
    toks = stream.tokens()
    assert toks == _reference(params, [5, 5, 6], 10, eos_id=eos)
    assert toks[-1] == eos and len(toks) <= 10
    assert stream.finish_reason == "eos"
    assert eng.cache.blocks_in_use() == 0


def test_engine_rejects_over_budget_prompts(make_engine):
    eng = make_engine()
    with pytest.raises(mx.MXNetError):
        eng.submit(list(range(17)), 4)   # > largest prefill bucket
    with pytest.raises(mx.MXNetError):
        eng.submit([1, 2, 3], 30)        # 33 > max_seq 32
    with pytest.raises(mx.MXNetError):
        eng.submit([], 4)


# ---------------------------------------------------------------------
# continuous batching
# ---------------------------------------------------------------------

def test_join_mid_batch_is_bit_identical(make_engine, params, counters):
    eng = make_engine()
    prompts = [[3, 7, 1, 9, 2], [5, 5, 6], [1, 2, 3, 4], [9, 8, 7]]
    solo = [_reference(params, p, 10) for p in prompts]
    results = {}

    def run(i, delay):
        time.sleep(delay)
        results[i] = eng.submit(prompts[i], 10).tokens()

    # throttled steps pin the stagger inside the running batch (a fast
    # machine must not finish stream 0 before stream 1 arrives)
    with chaos.scenario(seed=0):
        chaos.on("serving.decode.step",
                 action=lambda ctx: time.sleep(0.02))
        threads = [threading.Thread(target=run, args=(i, 0.01 * i))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    for i in range(len(prompts)):
        assert results[i] == solo[i], "slot %d diverged" % i
    # occupancy > 1 at some step <=> more tokens than iterations
    assert counters.counter("decode.tokens").value \
        > counters.counter("decode.steps").value
    assert eng.cache.blocks_in_use() == 0


def test_finished_sequences_vacate_immediately(make_engine, params):
    eng = make_engine()
    short = eng.submit([5, 5, 6], 2)
    long = eng.submit([3, 7, 1, 9, 2], 12)
    assert short.tokens() == _reference(params, [5, 5, 6], 2)
    # the long request keeps generating after the short one vacated
    assert long.tokens() == _reference(params, [3, 7, 1, 9, 2], 12)
    assert eng.cache.blocks_in_use() == 0


# ---------------------------------------------------------------------
# admission backpressure + lifecycle
# ---------------------------------------------------------------------

def test_admission_sheds_on_kv_exhaustion_never_midflight(
        make_engine, params, counters):
    # 9 usable blocks of 4 = 36 token-slots; one request budgets
    # 5 + 12 = 17 -> 5 blocks, so a second identical one must shed
    eng = make_engine(num_blocks=10)
    with chaos.scenario(seed=0):
        chaos.on("serving.decode.step",
                 action=lambda ctx: time.sleep(0.02))
        first = eng.submit([3, 7, 1, 9, 2], 12)
        time.sleep(0.05)                 # first is mid-generation now
        with pytest.raises(ServingQueueFull):
            eng.submit([3, 7, 1, 9, 2], 12)
        # the in-flight sequence is untouched by the shed
        assert first.tokens() == _reference(params, [3, 7, 1, 9, 2], 12)
    assert counters.counter("decode.shed").value == 1
    assert counters.counter("decode.shed.kvcache").value == 1
    assert counters.counter("kvcache.alloc_failures").value == 1
    assert eng.cache.blocks_in_use() == 0
    eng.submit([1], 2).tokens()          # sheds recover


def test_admission_sheds_on_queue_full(make_engine, counters):
    eng = make_engine(max_queue=1)
    with chaos.scenario(seed=0):
        chaos.on("serving.decode.step",
                 action=lambda ctx: time.sleep(0.05))
        streams, shed = [], 0
        for _ in range(12):              # 4 slots + 1 pending max
            try:
                streams.append(eng.submit([1], 8))
            except ServingQueueFull:
                shed += 1
        assert shed >= 1
        for s in streams:
            assert len(s.tokens()) == 8  # accepted work still completes
    assert counters.counter("decode.shed.queue").value >= 1


def test_cancel_frees_blocks(make_engine, counters):
    eng = make_engine()
    with chaos.scenario(seed=0):
        chaos.on("serving.decode.step",
                 action=lambda ctx: time.sleep(0.02))
        stream = eng.submit([3, 7, 1], 20)
        first = next(stream)
        stream.cancel()
        tail = list(stream)
    assert stream.finish_reason == "cancel"
    assert 1 + len(tail) < 20
    assert isinstance(first, int)
    assert eng.cache.blocks_in_use() == 0


def test_timeout_while_pending_frees_blocks(make_engine, counters):
    eng = make_engine(decode_buckets=(1,), max_queue=8)
    with chaos.scenario(seed=0):
        chaos.on("serving.decode.step",
                 action=lambda ctx: time.sleep(0.03))
        blocker = eng.submit([1], 10)    # owns the single slot
        time.sleep(0.02)
        late = eng.submit([2], 4, timeout=0.01)
        with pytest.raises(RequestTimeout):
            late.tokens()
        assert blocker.tokens()          # the running one is unharmed
    assert late.finish_reason == "timeout"
    assert counters.counter("serving.timeouts").value == 1
    assert eng.cache.blocks_in_use() == 0


def test_close_without_drain_resolves_streams(make_engine):
    eng = make_engine()
    with chaos.scenario(seed=0):
        chaos.on("serving.decode.step",
                 action=lambda ctx: time.sleep(0.02))
        stream = eng.submit([3, 7, 1], 20)
        next(stream)
        eng.close(drain=False)
        with pytest.raises(ServableClosed):
            list(stream)
    assert stream.finish_reason == "closed"
    assert eng.cache.blocks_in_use() == 0


# ---------------------------------------------------------------------
# the cache is written in place: per-layer slabs, donated (ISSUE 26)
# ---------------------------------------------------------------------

def test_kvcache_holds_one_slab_per_layer_and_resets_to_zeros():
    c = PagedKVCache(3, _kv_rows(2, 8), block_size=4, num_blocks=16)
    assert sorted(c.slabs) == ["k", "v"]
    assert len(c.slabs["k"]) == len(c.slabs["v"]) == 3
    # head_dim 8 in whole 128-lane tiles: the device's default layout
    # is then the row-major one the programs work in
    assert c.rows["k"] == (2, 8) and c.slab_shapes["k"] == (16, 4, 2, 128)
    for slab in _slabs(c):
        assert slab.shape == (16, 4, 2, 128) and slab.dtype == np.float32
    assert c.slab_bytes() == 2 * 3 * 16 * 4 * 2 * 128 * 4
    for width in (256, 130):
        wide = PagedKVCache(1, _kv_rows(2, width), block_size=4,
                            num_blocks=4)
        assert wide.slab_shapes["v"][-1] == 256
    old = c.slabs["k"]
    t = c.allocate(6)
    c.reset_slabs()                      # the allocator is untouched
    assert c.blocks_in_use() == len(t.blocks)
    assert all(new is not o for new, o in zip(c.slabs["k"], old))
    assert not c.slabs_deleted()
    assert all(not np.asarray(a).any() for a in _slabs(c))
    old[0].delete()
    c.slabs = dict(c.slabs, k=old)
    assert c.slabs_deleted()


def _slab_args(text, slab_type):
    """The ``@main`` arguments of a lowered program that have the slab's
    tensor type, each with whether it is marked as donated."""
    sig = text[text.index("@main("):]
    sig = sig[:sig.index(") -> ")]
    args = [a for a in sig.split("%arg")[1:] if slab_type in a]
    return [("tf.aliasing_output" in a or "jax.buffer_donor" in a)
            for a in args], sig


@pytest.mark.parametrize("with_compile_cache", [False, True],
                         ids=["plain", "compile_cache"])
def test_every_program_writes_both_slabs_in_place(params, ccache,
                                                  with_compile_cache):
    """Aliased bytes = the bytes of both slabs in every prefill and
    decode program, on the plain path and on the ``jax.export`` wrapper
    a CompileCache compiles (the path a registry's servable runs)."""
    eng = DecodeEngine(MODEL, params, **dict(
        ENGINE_KW, cache=ccache if with_compile_cache else None))
    eng.warmup()
    both = eng.cache.slab_bytes()
    assert both == 2 * MODEL.num_layers * 64 * 4 * 2 * 128 * 4
    keys = [("prefill", b) for b in eng.prefill_buckets] \
        + [("decode", b) for b in eng.decode_buckets]
    slabs = 2 * MODEL.num_layers
    for kind, bucket in keys:
        mem = eng.program_memory(kind, bucket)
        if mem is not None:              # the backend reports it
            assert mem["aliased_bytes"] == both, (kind, bucket, mem)
            assert mem["temp_bytes"] < both
        # the compiled module aliases one output to each slab argument
        head = eng._programs.get((kind, bucket)).as_text().split("\n")[0]
        assert head.count("-alias)") == slabs, (kind, bucket, head)
    # and the lowering marks the cache arguments, and only them
    prefill, decode = eng._specs()
    for impl, specs in ((eng._prefill_impl, prefill[8]),
                        (eng._decode_impl, decode[2])):
        import jax
        text = jax.jit(impl, donate_argnums=eng._DONATED).lower(
            *specs).as_text()
        marked, sig = _slab_args(text, "tensor<64x4x2x128xf32>")
        assert marked == [True] * slabs
        assert sig.count("tf.aliasing_output") \
            + sig.count("jax.buffer_donor") == slabs


def test_a_dense_models_decode_program_does_not_take_the_live_mask():
    """The engine hands every model the bucket's live slots; ``TinyGPT``
    does not read them, so its compiled step keeps the arguments it had
    (params, slabs, tokens, positions, tables) and not one more."""
    import jax
    import re
    eng = DecodeEngine(MODEL, MODEL.init_params(0), **ENGINE_KW)
    _prefill, decode = eng._specs()
    specs = decode[2]
    assert specs[-1].shape == (2,) and specs[-1].dtype == np.bool_
    text = jax.jit(eng._decode_impl, donate_argnums=eng._DONATED).lower(
        *specs).compile().as_text()
    entry = text.split("ENTRY", 1)[1]
    taken = len(set(re.findall(r" parameter\((\d+)\)", entry)))
    assert taken == len(jax.tree.leaves(specs)) - 1


def test_an_unread_argument_is_part_of_a_cached_programs_key(ccache):
    """A lowering drops an argument that the program does not read, the
    exported artifact's calling convention keeps it: two programs that
    lower to the same text and are called with two and with three
    arguments are two entries of the cache."""
    import jax
    from mxnet_tpu.serving.decode.engine import _AotPrograms
    spec = jax.ShapeDtypeStruct((2,), np.float32)
    two = _AotPrograms(cache=ccache)
    three = _AotPrograms(cache=ccache)
    f2 = two.build(("p", 2), lambda a, b: a + 1, (spec, spec))
    f3 = three.build(("p", 3), lambda a, b, c: a + 1, (spec, spec, spec))
    assert two.fingerprints["p", 2] != three.fingerprints["p", 3]
    x = np.ones(2, np.float32)
    assert float(f2(x, x)[0]) == 2.0 and float(f3(x, x, x)[0]) == 2.0


def test_compile_through_donates_only_when_told(ccache):
    """``BucketExecutorPool`` shares ``compile_through`` and keeps its
    behaviour: no donation unless the caller names the arguments, and
    then on the export wrapper too."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.serving.cache import (compile_through,
                                         stablehlo_fingerprint)

    def bump(state, x):
        return state.at[0].add(x), x * 2

    specs = (jax.ShapeDtypeStruct((8, 4), jnp.float32),
             jax.ShapeDtypeStruct((4,), jnp.float32))
    for donate in ((), (0,)):
        jfn = jax.jit(bump, donate_argnums=donate)
        lowered = jfn.lower(*specs)
        key = stablehlo_fingerprint(lowered.as_text())
        for cache in (None, ccache):
            kw = {"donate_argnums": donate} if donate else {}
            call = compile_through(cache, key, jfn, lowered, specs, **kw)
            state = jnp.zeros((8, 4), jnp.float32)
            new, _ = call(state, jnp.ones((4,), jnp.float32))
            assert state.is_deleted() == bool(donate)
            assert float(new[0, 0]) == 1.0
        assert key in ccache


def test_slabs_are_rebound_and_the_old_ones_deleted(make_engine, params):
    eng = make_engine()
    before = _slabs(eng.cache)
    # one token comes from the prefill alone: no decode step runs
    assert eng.submit([3, 7, 1], 1).tokens() \
        == _reference(params, [3, 7, 1], 1)
    after_prefill = _slabs(eng.cache)
    assert all(a.is_deleted() for a in before)
    assert not any(a.is_deleted() for a in after_prefill)
    seen = []
    with chaos.scenario(seed=0):
        # fires before each step's call: what the step is about to take
        chaos.on("serving.decode.step", action=lambda ctx: seen.append(
            _slabs(eng.cache)))
        assert eng.submit([5, 5, 6], 3).tokens() \
            == _reference(params, [5, 5, 6], 3)
    assert len(seen) == 2                # two steps made tokens 2 and 3
    for taken in seen:
        assert all(a.is_deleted() for a in taken)
    assert all(a.is_deleted() for a in after_prefill)
    assert not eng.cache.slabs_deleted()
    assert len(eng.cache.slabs["k"]) == MODEL.num_layers


class _ConsumesThenFails:
    """A compiled program that, from its ``from_call``-th call on, takes
    its donated slabs and then fails: what a device error after dispatch
    looks like to the engine (from the second call on: with the step
    before it in flight)."""

    def __init__(self, real, from_call=1):
        self.real = real
        self.from_call = from_call
        self.calls = 0

    def __call__(self, *args):
        self.calls += 1
        out = self.real(*args)
        if self.calls >= self.from_call:
            raise RuntimeError("device lost after the slabs were taken")
        return out


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_a_call_that_fails_with_the_slabs_taken_ends_every_stream(
        make_engine, params, kind, counters):
    eng = make_engine()
    programs = eng._programs._programs
    real = dict(programs)
    # worked out before the stream starts: it must still be decoding
    # when the programs are swapped
    want_first = _reference(params, [3, 7, 1, 9, 2], 1)[0]
    with chaos.scenario(seed=0):
        chaos.on("serving.decode.step",
                 action=lambda ctx: time.sleep(0.02))
        running = eng.submit([3, 7, 1, 9, 2], 20)
        first = next(running)            # decoding from here on
        assert first == want_first
        for key in real:
            if key[0] == kind:
                programs[key] = _ConsumesThenFails(real[key])
        joiner = eng.submit([5, 5, 6], 10)
        for stream in (running, joiner):
            # (the joiner's prefill still works where the decode
            # programs fail, but the step that runs behind it takes the
            # cache before its first token is out)
            with pytest.raises(RuntimeError, match="device lost"):
                list(stream)
            assert stream.finish_reason == "error"
    assert sum(p.calls for p in programs.values()
               if isinstance(p, _ConsumesThenFails)) >= 1
    assert eng.cache.blocks_in_use() == 0
    assert counters.gauge("kvcache.blocks_in_use").value == 0
    assert eng.active_sequences() == 0
    # fresh slabs, and the engine serves on
    programs.update(real)
    assert not eng.cache.slabs_deleted()
    for prompt in ([3, 7, 1, 9, 2], [1]):
        assert eng.submit(prompt, 8).tokens() \
            == _reference(params, prompt, 8)
    assert eng.cache.blocks_in_use() == 0


def test_a_prefill_fail_point_fails_only_that_request(make_engine,
                                                      params):
    """The chaos fail points fire BEFORE the call takes the slabs: the
    cache is whole, the running stream never notices."""
    eng = make_engine()
    with chaos.scenario(seed=0):
        chaos.on("serving.decode.step",
                 action=lambda ctx: time.sleep(0.02))
        running = eng.submit([3, 7, 1, 9, 2], 12)
        first = next(running)
        chaos.on("serving.decode.prefill", action=chaos.RAISE, times=1)
        doomed = eng.submit([5, 5, 6], 4)
        with pytest.raises(chaos.ChaosInjected):
            doomed.tokens()
        assert [first] + list(running) \
            == _reference(params, [3, 7, 1, 9, 2], 12)
        assert eng.submit([5, 5, 6], 4).tokens() \
            == _reference(params, [5, 5, 6], 4)
    assert eng.cache.blocks_in_use() == 0


def test_a_step_fail_point_leaves_the_slabs_alone(make_engine, params):
    eng = make_engine()
    with chaos.scenario(seed=0):
        chaos.on("serving.decode.step", action=chaos.RAISE, times=1)
        stream = eng.submit([3, 7, 1], 6)
        slabs = None
        with pytest.raises(chaos.ChaosInjected):
            next(stream)                 # the prefill's token
            slabs = eng.cache.slabs
            list(stream)
    assert stream.finish_reason == "error"
    assert eng.cache.blocks_in_use() == 0
    assert slabs is not None and eng.cache.slabs is slabs  # not reset
    assert eng.submit([3, 7, 1], 6).tokens() \
        == _reference(params, [3, 7, 1], 6)


@pytest.mark.parametrize("lanes", [MODEL.head_dim, 128],
                         ids=["plain", "whole_tiles"])
def test_padded_slots_write_the_scratch_block_only(params, lanes):
    """A bucket's padded slots all write (block 0, offset 0): indices
    that are not unique.  Every other block of every layer keeps what
    it held, and the live slot's row lands where its table says --
    in a slab as wide as a head and in one padded to whole tiles."""
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    shape = (8, 4, MODEL.num_heads, lanes)
    keys = tuple(jnp.asarray(rng.normal(size=shape).astype(np.float32))
                 for _ in range(MODEL.num_layers))
    values = tuple(jnp.asarray(rng.normal(size=shape).astype(np.float32))
                   for _ in range(MODEL.num_layers))
    tables = np.full((4, 8), SCRATCH_BLOCK, np.int32)
    tables[0, :2] = [5, 3]
    tokens = np.array([7, 0, 0, 0], np.int32)
    positions = np.array([6, 0, 0, 0], np.int32)     # block 3, offset 2
    _next, _logits, new, stats = MODEL.decode_logits(
        params, {"k": keys, "v": values}, jnp.asarray(tokens),
        jnp.asarray(positions), jnp.asarray(tables), 4)
    new_k, new_v = new["k"], new["v"]
    assert stats == {}
    assert isinstance(new_k, tuple) and len(new_k) == MODEL.num_layers
    for old, new in list(zip(keys, new_k)) + list(zip(values, new_v)):
        old, new = np.asarray(old), np.asarray(new)
        changed = {(b, o) for b in range(8) for o in range(4)
                   if not np.array_equal(old[b, o], new[b, o])}
        assert changed == {(SCRATCH_BLOCK, 0), (3, 2)}
        assert not new[3, 2, :, MODEL.head_dim:].any()    # dead lanes


def test_the_padded_lanes_never_reach_the_tokens(params):
    """Garbage in the lanes past head_dim changes no token: the
    programs read the first head_dim lanes only."""
    import jax.numpy as jnp
    eng = DecodeEngine(MODEL, params, **ENGINE_KW)
    eng.warmup()
    d = MODEL.head_dim
    junk = np.zeros(eng.cache.slab_shapes["k"], np.float32)
    junk[..., d:] = 1e9
    eng.cache.slabs = {name: tuple(jnp.asarray(junk) for _ in layers)
                       for name, layers in eng.cache.slabs.items()}
    eng.start()
    try:
        assert eng.submit([3, 7, 1, 9, 2], 8).tokens() \
            == _reference(params, [3, 7, 1, 9, 2], 8)
    finally:
        eng.close(drain=False)


def test_three_streams_in_a_bucket_of_four_match_the_oracle(make_engine,
                                                            params):
    eng = make_engine()
    prompts = [[3, 7, 1, 9, 2], [5, 5, 6], [1, 2, 3, 4]]
    with chaos.scenario(seed=0):
        chaos.on("serving.decode.step",
                 action=lambda ctx: time.sleep(0.01))
        streams = [eng.submit(p, 9) for p in prompts]
        got = [s.tokens() for s in streams]
    assert got == [_reference(params, p, 9) for p in prompts]
    assert eng.cache.blocks_in_use() == 0


def test_the_aliased_bytes_gauge_is_catalogued_and_set(params, counters):
    from mxnet_tpu.telemetry import hooks
    assert "decode.kv_aliased_bytes" in {i.name for i in hooks.INSTRUMENTS}
    eng = DecodeEngine(MODEL, params, **ENGINE_KW)
    eng.warmup()
    assert counters.gauge("decode.kv_aliased_bytes").value \
        == eng.cache.slab_bytes()


# ---------------------------------------------------------------------
# one decode step in flight (ISSUE 31)
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def lively():
    """Weights under which a stream's tokens change from step to step
    and differ between streams (``init_params(0)`` repeats one id), so
    that a token taken from the wrong slot or the wrong step shows."""
    return {name: a * 10 if name == "pos_embed"
            else a * 6 if a.ndim == 2 and name != "embed" else a
            for name, a in MODEL.init_params(2).items()}


def _greedy(params, prompt, max_new, eos_id=None):
    """The reference loop: ONE request, one full forward a token, no
    cache, no batch, nothing in flight."""
    import jax.numpy as jnp
    seq, out = list(prompt), []
    while len(out) < max_new:
        logits = MODEL.full_logits(params, jnp.asarray([seq], jnp.int32))
        out.append(int(jnp.argmax(logits[0, -1])))
        seq.append(out[-1])
        if out[-1] == eos_id:
            break
    return out


def _serve_on_schedule(eng, first, plan):
    """Submit ``first`` now and ``plan[k]`` (a list of (prompt, max_new,
    eos_id)) from the engine's own thread at the fail point of its k-th
    decode dispatch -- with the step before that one in flight, so the
    admission arrives while a step runs.  Returns the streams in the
    order submitted and the occupancy of every step dispatched."""
    streams, occupancy = [], []

    def at_dispatch(ctx):
        occupancy.append(ctx["occupancy"])
        for prompt, max_new, eos_id in plan.get(len(occupancy), ()):
            streams.append(eng.submit(prompt, max_new, eos_id=eos_id))

    with chaos.scenario(seed=0):
        chaos.on("serving.decode.step", action=at_dispatch)
        streams.append(eng.submit(*first[:2], eos_id=first[2]))
        got = []
        i = 0
        while i < len(streams):          # the list grows as the plan runs
            got.append(streams[i].tokens())
            i += 1
    return streams, got, occupancy


_A, _B, _C, _D = [3, 7, 1, 9, 2], [5, 5, 6], [1, 2, 3, 4], [9, 8, 7]
_SCHEDULES = {
    # B joins while A's first step is in flight and ends after three
    # tokens (2 -> 1); C and D join later (1 -> 2 -> 3)
    "shrinks_to_one_and_grows_again": (
        (_A, 14, None), {1: [(_B, 3, None)], 6: [(_C, 6, None)],
                         7: [(_D, 5, None)]}),
    # two arrive at one boundary, one of them for a single token (it
    # never takes a slot of a step)
    "two_at_one_boundary": (
        (_B, 9, None), {2: [(_A, 1, None), (_C, 7, None)]}),
    # the last request arrives at the running streams' last step: the
    # batch runs empty and the loop starts again from nothing in flight
    "runs_empty_and_starts_again": (
        (_C, 8, None), {1: [(_D, 5, None)], 7: [(_A, 6, None)]}),
    # more streams than the largest bucket: the fifth waits for a slot
    "a_full_house": (
        (_A, 10, None), {1: [(_B, 4, None), (_C, 8, None), (_D, 12, None),
                             ([2, 4, 6, 8], 5, None)]}),
}


@pytest.mark.parametrize("schedule", sorted(_SCHEDULES))
def test_tokens_are_the_reference_loops_under_any_schedule(
        make_engine, lively, counters, schedule):
    params = lively
    first, plan = _SCHEDULES[schedule]
    eng = make_engine(weights=params)
    streams, got, occupancy = _serve_on_schedule(eng, first, plan)
    asked = [first] + [r for k in sorted(plan) for r in plan[k]]
    assert len(streams) == len(asked)
    for (prompt, max_new, eos_id), tokens in zip(asked, got):
        assert tokens == _greedy(params, prompt, max_new, eos_id)
        assert len(set(tokens)) > 1 or max_new == 1      # lively
    assert {s.finish_reason for s in streams} == {"length"}
    assert 1 < max(occupancy) <= eng.max_slots
    if schedule == "shrinks_to_one_and_grows_again":
        assert [n for i, n in enumerate(occupancy)
                if i == 0 or n != occupancy[i - 1]][:5] == [1, 2, 1, 2, 3]
    # every step but the first after an empty moment was dispatched
    # behind its predecessor, and greedy traffic wastes no slot-step
    steps = counters.counter("decode.steps").value
    assert steps == len(occupancy)
    assert counters.counter("decode.steps_overlapped").value \
        >= steps - 3
    assert counters.counter("decode.tokens_discarded").value == 0
    assert counters.counter("decode.tokens").value == sum(occupancy)
    assert eng.cache.blocks_in_use() == 0


@pytest.mark.parametrize("how", ["eos", "cancel"])
def test_a_stream_that_ends_early_drops_the_token_in_flight(
        make_engine, lively, counters, how):
    """EOS and cancel are found one step late, with the next step
    already dispatched: that one token is discarded, the stream holds
    exactly the tokens up to its end, the others never notice."""
    params = lively
    eng = make_engine(weights=params)
    want = _greedy(params, _A, 12)
    ref_b = _greedy(params, _B, 10)
    # B ends in the middle: at the first id it has not emitted before
    k = next(i for i in range(2, 8) if ref_b[i] not in ref_b[:i])
    eos = ref_b[k] if how == "eos" else None
    seen = []
    with chaos.scenario(seed=0):
        chaos.on("serving.decode.step",
                 action=lambda ctx: time.sleep(0.01))
        a = eng.submit(_A, 12)
        b = eng.submit(_B, 10, eos_id=eos)
        for tok in b:
            seen.append(tok)
            if how == "cancel" and len(seen) == k + 1:
                b.cancel()
        rest = a.tokens()
    assert rest == want
    assert b.finish_reason == how
    if how == "eos":
        assert seen == ref_b[:k + 1] and seen[-1] == eos
    else:
        # the cancel lands at the next emit: a token or two more, never
        # one that is not the reference's
        assert k + 1 <= len(seen) <= k + 3 and seen == ref_b[:len(seen)]
    assert counters.counter("decode.tokens_discarded").value == 1
    assert counters.counter("decode.tokens").value \
        == len(want) - 1 + len(seen) - 1
    assert eng.cache.blocks_in_use() == 0


def test_a_freed_table_is_reused_under_the_step_in_flight(make_engine,
                                                          lively):
    """A stream that ends by EOS frees its blocks while the step in
    flight still writes its next row into one of them; the next
    admission takes those very blocks (the pool has no others) and
    reads none of that row."""
    # 8 usable blocks of 4: A budgets 5 + 12 -> 5 blocks, B 3 + 9 -> 3
    params = lively
    eng = make_engine(weights=params, num_blocks=9)
    ref_b = _greedy(params, _B, 9)
    k = next(i for i in range(2, 8) if ref_b[i] not in ref_b[:i])
    eos = ref_b[k]
    taken = []
    real_allocate = eng.cache.allocate

    def allocate(n_tokens):
        taken.append(real_allocate(n_tokens))
        return taken[-1]

    eng.cache.allocate = allocate
    with chaos.scenario(seed=0):
        chaos.on("serving.decode.step",
                 action=lambda ctx: time.sleep(0.01))
        a = eng.submit(_A, 12)
        b = eng.submit(_B, 9, eos_id=eos)
        assert b.tokens() == ref_b[:k + 1]
        c = eng.submit(_D, 9)            # fits only where B was
        assert c.tokens() == _greedy(params, _D, 9)
        assert a.tokens() == _greedy(params, _A, 12)
    assert set(taken[2].blocks) == set(taken[1].blocks)
    assert eng.cache.blocks_in_use() == 0


@pytest.mark.parametrize("drain", [True, False])
def test_close_with_a_step_in_flight(make_engine, lively, drain):
    params = lively
    eng = make_engine(weights=params)
    with chaos.scenario(seed=0):
        chaos.on("serving.decode.step",
                 action=lambda ctx: time.sleep(0.01))
        streams = [eng.submit(_A, 12), eng.submit(_B, 7)]
        firsts = [next(s) for s in streams]
        assert eng.close(drain=drain) == 2
        if drain:
            # every token of every step, the one in flight included
            assert [[f] + list(s) for f, s in zip(firsts, streams)] \
                == [_greedy(params, _A, 12), _greedy(params, _B, 7)]
            assert {s.finish_reason for s in streams} == {"length"}
        else:
            for s in streams:
                with pytest.raises(ServableClosed):
                    list(s)
                assert s.finish_reason == "closed"
    assert eng.cache.blocks_in_use() == 0
    assert eng._flight is None


@pytest.mark.parametrize("where", ["second_call", "fetch", "fail_point"])
def test_a_failure_with_a_step_in_flight_loses_nothing_silently(
        make_engine, lively, counters, monkeypatch, where):
    """The error of step n arrives when step n+1 is dispatched.  With
    the slabs taken (a call that raises, a fetch that raises) every live
    stream ends with it, the step in flight is dropped and fresh slabs
    serve the next request; a fail point fires before the call takes
    anything, so the cache stays and only that step's streams end."""
    import jax
    params = lively
    eng = make_engine(weights=params)
    programs = eng._programs._programs
    real = dict(programs)
    resets = []
    real_reset = eng.cache.reset_slabs
    eng.cache.reset_slabs = lambda: (resets.append(1), real_reset())
    with chaos.scenario(seed=0):
        streams = [eng.submit(_A, 12), eng.submit(_B, 12)]
        if where == "second_call":
            for key in real:
                if key[0] == "decode":
                    programs[key] = _ConsumesThenFails(real[key],
                                                       from_call=2)
            error = RuntimeError
        elif where == "fetch":
            real_get, gets = jax.device_get, []

            def device_get(x):
                # prefills fetch a scalar token, steps a vector
                if np.ndim(x[0]) == 1:
                    gets.append(x)
                    if len(gets) == 2:
                        raise RuntimeError("device lost at the fetch")
                return real_get(x)

            monkeypatch.setattr(jax, "device_get", device_get)
            error = RuntimeError
        else:
            # the third dispatch: its predecessor is in flight
            chaos.on("serving.decode.step", action=chaos.RAISE, nth=3)
            error = chaos.ChaosInjected
        for s in streams:
            with pytest.raises(error):
                list(s)
            assert s.finish_reason == "error"
    monkeypatch.undo()
    programs.update(real)
    assert eng.cache.blocks_in_use() == 0
    assert eng.active_sequences() == 0 and eng._flight is None
    assert not eng.cache.slabs_deleted()
    assert counters.counter("serving.errors").value >= 1
    for prompt in (_A, [1]):
        assert eng.submit(prompt, 8).tokens() == _greedy(params, prompt, 8)
    # a fail point leaves the cache it found: the next request ran on it
    assert len(resets) == (0 if where == "fail_point" else 1)
    assert eng.cache.blocks_in_use() == 0


def test_the_engine_compiles_one_program_a_bucket(lively):
    params = lively
    eng = DecodeEngine(MODEL, params, **ENGINE_KW)
    eng.warmup()
    built = set(eng._programs._programs)
    assert built == {("prefill", b) for b in eng.prefill_buckets} \
        | {("decode", b) for b in eng.decode_buckets}
    eng.start()
    try:
        streams = [eng.submit(p, 6) for p in (_A, _B, _C)]
        assert [s.tokens() for s in streams] \
            == [_greedy(params, p, 6) for p in (_A, _B, _C)]
    finally:
        eng.close(drain=False)
    # every bucket's step takes any bucket's tokens: nothing was built
    # for a pair of buckets
    assert set(eng._programs._programs) == built


# ---------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------

def test_one_step_span_a_step_links_the_request_root(make_engine):
    obs.enable_tracing()
    try:
        eng = make_engine()
        eng.submit([3, 7, 1], 5).tokens()
        spans = obs.spans()
    finally:
        obs.disable_tracing()
    roots = [s for s in spans if s["name"] == "serving.request"
             and s["attrs"].get("generative")]
    assert len(roots) == 1
    assert roots[0]["attrs"]["tokens"] == 5
    # one prefill and four decode steps made the five tokens: ONE span
    # each, none per token, every one linking the request it served
    by = {}
    for s in spans:
        by.setdefault(s["name"], []).append(s)
    assert "serving.decode_step" not in by
    assert len(by["mx.decode.prefill"]) == 1
    steps = by["mx.decode.step"]
    assert len(steps) == 4
    for s in steps + by["mx.decode.prefill"] + by["mx.decode.queue_wait"]:
        assert s["links"] == [roots[0]["span"]]
    assert {(s["attrs"]["n"], s["attrs"]["bucket"]) for s in steps} \
        == {(1, eng.decode_buckets[0])}
    assert steps[0]["attrs"]["max_slots"] == eng.max_slots
    for part in ("build", "call", "emit"):
        kids = by["mx.decode.step." + part]
        assert len(kids) == 4
        assert {k["parent"] for k in kids} == {s["span"] for s in steps}
    assert by["mx.decode.queue_wait"][0]["parent"] \
        == by["mx.decode.admit"][0]["span"]
    assert by["mx.decode.prefill"][0]["parent"] \
        == by["mx.decode.admit"][0]["span"]


# ---------------------------------------------------------------------
# registry surface + hot swap
# ---------------------------------------------------------------------

def test_registry_generate_and_statusz_surface(registry, params):
    sv = registry.register_generative("gpt", MODEL, params=params,
                                      **ENGINE_KW)
    assert "gpt" in registry
    assert sv.queue_depth() == 0 and sv.queue_capacity == 16
    assert sv.kvcache_stats()["blocks_in_use"] == 0
    toks = registry.generate("gpt", [3, 7, 1], 5).tokens()
    assert toks == _reference(params, [3, 7, 1], 5)


def test_registry_generate_rejects_non_generative(registry):
    from mxnet_tpu import gluon
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(4))
    net.initialize(force_reinit=True)
    net.hybridize()
    net(mx.nd.array(np.zeros((1, 8), np.float32)))
    registry.register("mlp", block=net, input_shape=(8,),
                      buckets=(1, 2))
    with pytest.raises(mx.MXNetError, match="not generative"):
        registry.generate("mlp", [1, 2], 4)
    with pytest.raises(mx.MXNetError):
        registry.register_generative("both", MODEL)      # no source
    with pytest.raises(mx.MXNetError):
        registry.register_generative("both", MODEL, params={},
                                     checkpoint="/nope")  # two sources


def test_mid_decode_swap_drains_old_zero_dropped(registry, params,
                                                counters):
    p1 = MODEL.init_params(1)
    registry.register_generative("gpt", MODEL, params=params,
                                 **ENGINE_KW)
    old = registry._servables["gpt"]
    with chaos.scenario(seed=0):
        # gate every decode step until the REPLACEMENT servable has
        # installed: the swap then provably lands mid-generation
        # (install precedes old.close(drain=True) in the registry), and
        # the drain -- which only starts after install -- releases the
        # gate.  The first token comes from prefill, so next(stream)
        # never blocks on this.
        def _hold_until_swapped(ctx, deadline=None):
            deadline = deadline or time.monotonic() + 10.0
            while (registry._servables.get("gpt") is old
                   and time.monotonic() < deadline):
                time.sleep(0.002)
        chaos.on("serving.decode.step", action=_hold_until_swapped)
        stream = registry.generate("gpt", [3, 7, 1, 9, 2], 20)
        first = next(stream)             # mid-generation from here on
        registry.register_generative("gpt", MODEL, params=p1,
                                     **ENGINE_KW)
        drained = [first] + list(stream)
        # the half-generated sequence finished on the OLD weights
        assert drained == _reference(params, [3, 7, 1, 9, 2], 20)
        assert stream.finish_reason == "length"
        assert chaos.stats()["survived"].get("serving.decode_swap") == 1
        # new requests land on the new weights
        assert registry.generate("gpt", [3, 7, 1], 5).tokens() \
            == _reference(p1, [3, 7, 1], 5)
    assert counters.counter(
        "chaos.survived.serving.decode_swap").value == 1


def test_swap_abort_leaves_old_serving(registry, params):
    registry.register_generative("gpt", MODEL, params=params,
                                 **ENGINE_KW)
    with chaos.scenario(seed=0):
        chaos.on("serving.swap", action=chaos.RAISE, times=1)
        with pytest.raises(chaos.ChaosInjected):
            registry.register_generative("gpt", MODEL,
                                         params=MODEL.init_params(1),
                                         **ENGINE_KW)
    toks = registry.generate("gpt", [3, 7, 1], 5).tokens()
    assert toks == _reference(params, [3, 7, 1], 5)


def test_generative_watcher_swaps_on_new_step(registry, params,
                                              tmp_path):
    from mxnet_tpu.checkpoint import CheckpointManager
    p1 = MODEL.init_params(1)
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    mgr.save(1, {"params": params})
    w = GenerativeWatcher(registry, "gpt", mgr, MODEL, **ENGINE_KW)
    assert w.poll_once() == 1
    assert registry.generate("gpt", [3, 7, 1], 5).tokens() \
        == _reference(params, [3, 7, 1], 5)
    assert w.poll_once() is None         # nothing new
    mgr.save(2, {"params": p1})
    assert w.poll_once() == 2
    assert registry.generate("gpt", [3, 7, 1], 5).tokens() \
        == _reference(p1, [3, 7, 1], 5)
    w.close()
