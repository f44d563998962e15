"""The DeepSeek-V3-style decoder on the serving path, at a small size on
the CPU in float32: rotary tables against hand numbers, the absorbed
decode form against the expanded one, the latent paged-attention kernel
body against its XLA reference, routed experts that are told which
experts they hold, and the engine serving it through the ONE paged cache
that also serves ``TinyGPT``."""
import numpy as np
import pytest

from mxnet_tpu import kernels, obs, telemetry
from mxnet_tpu.parallel.moe import route_top_k, routed_experts
from mxnet_tpu.serving.decode import (DecodeEngine, LatentMoEDecoder,
                                      PagedKVCache, TinyGPT)
from mxnet_tpu.serving.decode.kvcache import SCRATCH_BLOCK, slab_rows
from mxnet_tpu.serving.decode.latent_moe import (attention_scale,
                                                 yarn_inv_freq)

KIMI_ROPE = {"beta_fast": 1, "beta_slow": 1, "factor": 32, "mscale": 1,
             "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
             "type": "yarn"}
TINY = dict(vocab_size=128, hidden_size=64, num_hidden_layers=3,
            num_attention_heads=4, q_lora_rank=16, kv_lora_rank=8,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
            intermediate_size=96, moe_intermediate_size=32,
            n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
            first_k_dense_replace=1, routed_scaling_factor=2.827,
            rope_theta=50000,
            rope_scaling=dict(KIMI_ROPE,
                              original_max_position_embeddings=16),
            first_expert=2, n_held=2, max_seq=64, dtype="float32")
MODEL = LatentMoEDecoder(**TINY)
ENGINE_KW = dict(prefill_buckets=(8, 16, 32), decode_buckets=(2, 4),
                 block_size=4, num_blocks=65)


@pytest.fixture(scope="module")
def params():
    return MODEL.init_params(3)


def _greedy(model, params, prompt, max_new):
    """Greedy decode by one FULL forward a token: the oracle."""
    import jax.numpy as jnp
    tokens, out = list(prompt), []
    for _ in range(max_new):
        logits = model.full_logits(params, jnp.asarray([tokens], jnp.int32))
        out.append(int(jnp.argmax(logits[0, -1])))
        tokens.append(out[-1])
    return out


# ---------------------------------------------------------------------
# rotary tables and the softmax scale: the numbers of ISSUE 28
# ---------------------------------------------------------------------

def test_yarn_inv_freq_and_scale_are_kimi_k2s():
    inv = yarn_inv_freq(64, 50000, KIMI_ROPE)
    plain = 50000.0 ** (-2.0 * np.arange(32) / 64)
    corr = 64 * np.log(4096 / (2 * np.pi)) / (2 * np.log(50000))
    assert int(np.floor(corr)) == 19 and int(np.ceil(corr)) == 20
    # pairs 0..19 turn often enough to be kept, 20..31 are interpolated
    np.testing.assert_allclose(inv[:20], plain[:20], rtol=1e-12)
    np.testing.assert_allclose(inv[20:], plain[20:] / 32, rtol=1e-12)
    np.testing.assert_allclose(yarn_inv_freq(64, 50000), plain)
    m = 0.1 * np.log(32) + 1
    assert m == pytest.approx(1.34657, abs=1e-5)
    assert attention_scale(192, KIMI_ROPE) == pytest.approx(0.13086,
                                                            abs=1e-5)
    assert attention_scale(192) == pytest.approx(192 ** -0.5)


def test_rotation_turns_adjacent_pairs_and_keeps_relative_position():
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(5, 4)).astype(np.float32))
    pos = jnp.arange(5, dtype=jnp.int32)
    got = np.asarray(MODEL._rotate(x, pos))
    for t in range(5):
        for j in range(2):
            a = t * MODEL.inv_freq[j]
            c, s = np.cos(a), np.sin(a)
            e, o = float(x[t, 2 * j]), float(x[t, 2 * j + 1])
            np.testing.assert_allclose(
                got[t, 2 * j:2 * j + 2], [e * c - o * s, o * c + e * s],
                rtol=1e-5, atol=1e-6)
    # a score depends on the distance only
    q = jnp.asarray(rng.normal(size=(1, 4)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 4)).astype(np.float32))
    scores = [float(jnp.sum(MODEL._rotate(q, jnp.asarray([a]))
                            * MODEL._rotate(k, jnp.asarray([a - 3]))))
              for a in (3, 10, 40)]
    assert scores[0] == pytest.approx(scores[1], abs=1e-4)
    assert scores[0] == pytest.approx(scores[2], abs=1e-4)


# ---------------------------------------------------------------------
# the latent paged-attention kernel
# ---------------------------------------------------------------------

def _kernel_case(rng, dtype, slots, ctx, nb=24, bs=4, mb=8, heads=4,
                 width=12, v_width=8, lanes=128):
    import jax.numpy as jnp
    q = np.zeros((slots, heads, lanes), np.float32)
    q[..., :width] = rng.normal(size=(slots, heads, width))
    cache = np.zeros((nb, bs, lanes), np.float32)
    cache[..., :width] = rng.normal(size=(nb, bs, width))
    bt = rng.integers(1, nb, (slots, mb)).astype(np.int32)
    return (jnp.asarray(q, dtype), jnp.asarray(cache, dtype),
            jnp.asarray(bt), jnp.asarray(np.asarray(ctx, np.int32)
                                         .reshape(slots, 1)), v_width)


@pytest.mark.parametrize("dtype,atol", [("float32", 1e-5),
                                        ("bfloat16", 3e-2)])
@pytest.mark.parametrize("mb", [8, 6, 3], ids=["pages8", "pages2",
                                                "pages1"])
def test_mla_pallas_body_equals_the_xla_reference_at_ragged_contexts(
        dtype, atol, mb):
    from mxnet_tpu.ops.pallas.mla_paged_attention import (
        mla_paged_attention_pallas, mla_paged_attention_reference)
    rng = np.random.default_rng(0)
    # one token, a block boundary, inside a block, the whole table
    ctx = [1, 4, 4 * mb - 3, 4 * mb]
    q, cache, bt, ctx, v = _kernel_case(rng, dtype, 4, ctx, mb=mb)
    ref = mla_paged_attention_reference(q, cache, bt, ctx, v_width=v,
                                        scale=0.3)
    pal = mla_paged_attention_pallas(q, cache, bt, ctx, v_width=v,
                                     scale=0.3, interpret=True)
    assert pal.shape == (4, 4, v) and pal.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(pal, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


def test_mla_attention_never_reads_past_the_context():
    from mxnet_tpu.ops.pallas.mla_paged_attention import (
        mla_paged_attention_pallas, mla_paged_attention_reference)
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    q, cache, _bt, _ctx, v = _kernel_case(rng, "float32", 3, [6, 10, 5])
    # slot 0 ends inside block 2, slot 1 inside block 3, slot 2 inside
    # block 5; blocks 6, 7 and 8 are named by a table past its slot's
    # context.  Read 8 pages a step, slot 1's and slot 2's dead pages
    # hold what an earlier slot's held; read 2, slot 1 takes two steps
    # and the second one's dead page holds block 10
    bt = np.array([[1, 2, 6, 6, 6, 6, 6, 6],
                   [9, 10, 3, 8, 8, 8, 8, 8],
                   [4, 5, 7, 7, 7, 7, 7, 7]], np.int32)
    ctx = jnp.asarray(np.array([[6], [10], [5]], np.int32))
    poisoned = np.asarray(cache).copy()
    poisoned[2, 2:] = 1e6       # positions 6, 7 of block 2: dead to slot 0
    poisoned[3, 2:] = 1e6       # positions 10, 11 of block 3
    poisoned[5, 1:] = 1e6       # positions 5..7 of block 5
    poisoned[6:9] = 1e6         # every block past a context
    for width in (8, 6):
        table = jnp.asarray(bt[:, :width])
        for fn, kw in ((mla_paged_attention_reference, {}),
                       (mla_paged_attention_pallas, {"interpret": True})):
            base = fn(q, cache, table, ctx, v_width=v, scale=0.5, **kw)
            got = fn(q, jnp.asarray(poisoned), table, ctx, v_width=v,
                     scale=0.5, **kw)
            np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                                       atol=1e-6)


@pytest.mark.parametrize("mb", [8, 6, 3], ids=["pages8", "pages2",
                                                "pages1"])
def test_mla_grid_is_the_live_page_groups(mb):
    """One grid step a page group a slot's context reaches (one for a
    slot of one token), slot by slot and group by group; a live page
    names its table entry and a dead one what it held a step before."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas import mla_paged_attention as mla
    from mxnet_tpu.ops.pallas.paged_attention import _live_steps
    assert mla._live_steps is _live_steps       # the GPT-2 kernel's own
    bs, pages = 4, {8: 8, 6: 2, 3: 1}[mb]
    span = bs * pages
    # one token, a block boundary, inside a block, the whole table
    ctx = np.array([1, 4, 4 * mb - 3, 4 * mb], np.int32)
    bt = np.arange(1, 4 * mb + 1, dtype=np.int32).reshape(4, mb)
    steps = int(mla.grid_steps(jnp.asarray(bt), jnp.asarray(ctx[:, None]),
                               bs))
    want = [(s, g) for s in range(4)
            for g in range(max(1, -(-int(ctx[s]) // span)))]
    assert steps == len(want)
    assert steps < 4 * mb // pages or mb == 8    # the whole table's grid
    n, slot, group, blocks = (np.asarray(a) for a in _live_steps(
        jnp.asarray(bt), jnp.asarray(ctx), bs, pages))
    assert int(n) == steps
    assert list(zip(slot[:steps], group[:steps])) == want
    for i, (s, g) in enumerate(want):
        for k in range(pages):
            if (g * pages + k) * bs < ctx[s]:
                assert blocks[k, i] == bt[s, g * pages + k]
            elif i:
                assert blocks[k, i] == blocks[k, i - 1]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("mb", [8, 6, 3], ids=["pages8", "pages2",
                                                "pages1"])
def test_the_live_grid_is_the_whole_table_kernel_bit_for_bit(dtype, mb):
    """Today's kernel against the one whose grid walked every slot's
    whole table (``tests/_mla_paged_attention_whole_table.py``): the
    same live groups in the same order over the same rows, a dead
    page's scores exact zeros, so the same bits."""
    import importlib.util
    import os
    from mxnet_tpu.ops.pallas.mla_paged_attention import (
        mla_paged_attention_pallas)
    spec = importlib.util.spec_from_file_location(
        "mla_paged_attention_whole_table", os.path.join(
            os.path.dirname(__file__), "_mla_paged_attention_whole_table.py"))
    before = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(before)
    rng = np.random.default_rng(5)
    ctx = [1, 4, 9, 4 * mb - 3, 4 * mb, 2]
    q, cache, bt, ctx, v = _kernel_case(rng, dtype, 6, ctx, mb=mb)
    for scale in (0.3, 1.0):
        np.testing.assert_array_equal(
            np.asarray(mla_paged_attention_pallas(
                q, cache, bt, ctx, v_width=v, scale=scale, interpret=True),
                np.float32),
            np.asarray(before.mla_paged_attention_pallas(
                q, cache, bt, ctx, v_width=v, scale=scale, interpret=True),
                np.float32))


def test_mla_paged_attention_is_a_registry_entry():
    from mxnet_tpu.kernels.mla_paged_attention import mla_paged_attention
    assert "mla_paged_attention" in kernels.list_kernels()
    shape = dict(heads=4, lanes=128, v_width=8, block_size=4)
    assert not kernels.choose("mla_paged_attention", force=False, **shape)
    forced = kernels.choose("mla_paged_attention", force=True, **shape)
    assert forced.use_pallas and forced.interpret       # CPU: interpret
    bad = kernels.choose("mla_paged_attention", force=True,
                         **dict(shape, v_width=256))
    assert not bad.use_pallas and "v_width" in bad.reason
    rng = np.random.default_rng(2)
    q, cache, bt, ctx, v = _kernel_case(rng, "float32", 2, [5, 17])
    xla = mla_paged_attention(q, cache, bt, ctx, v, scale=0.2,
                              use_pallas=False)
    pal = mla_paged_attention(q, cache, bt, ctx, v, scale=0.2,
                              use_pallas=True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(xla), atol=1e-5)


# ---------------------------------------------------------------------
# routed experts that are told which experts they hold
# ---------------------------------------------------------------------

def _expert_layer(rng, tokens=37, d=16, f=24, experts=8):
    x = rng.normal(size=(tokens, d)).astype(np.float32)
    gate_w = (rng.normal(size=(d, experts)) * 0.3).astype(np.float32)
    bias = (rng.normal(size=(experts,)) * 0.1).astype(np.float32)
    w = [(rng.normal(size=s) * 0.2).astype(np.float32)
         for s in ((experts, d, f), (experts, d, f), (experts, f, d))]
    return x, gate_w, bias, w


def _dense_experts(x, chosen, weights, w, first, n):
    """Token by token, expert by expert, in numpy."""
    y = np.zeros_like(x)
    for t in range(x.shape[0]):
        for e, wt in zip(chosen[t], weights[t]):
            if first <= e < first + n:
                h = x[t] @ w[0][e]
                h = h / (1 + np.exp(-h)) * (x[t] @ w[1][e])
                y[t] += wt * (h @ w[2][e])
    return y


def test_the_router_scores_sigmoid_adds_the_bias_for_the_choice_only():
    rng = np.random.default_rng(0)
    x, gate_w, bias, _w = _expert_layer(rng)
    chosen, weights = (np.asarray(a) for a in route_top_k(
        x, gate_w, bias, 2, scale=2.5))
    scores = 1 / (1 + np.exp(-(x @ gate_w)))
    want = np.argsort(-(scores + bias), axis=1)[:, :2]
    assert (np.sort(chosen, 1) == np.sort(want, 1)).all()
    picked = np.take_along_axis(scores, chosen, 1)      # without the bias
    np.testing.assert_allclose(
        weights, picked / picked.sum(1, keepdims=True) * 2.5, rtol=1e-5)
    # the bias moves a choice and no weight's formula
    moved, _ = route_top_k(x, gate_w, bias + np.eye(8)[5] * 10, 2)
    assert (np.asarray(moved) == 5).any(axis=1).all()


@pytest.mark.parametrize("first,n,chunk", [(0, 8, 2048), (2, 2, 2048),
                                           (0, 8, 16), (4, 4, 5)])
def test_routed_experts_equal_a_dense_pass_over_the_held_experts(
        first, n, chunk):
    rng = np.random.default_rng(1)
    x, gate_w, bias, w = _expert_layer(rng)
    chosen, weights = route_top_k(x, gate_w, bias, 2, scale=2.5)
    y, counts = routed_experts(
        x, chosen, weights, *(a[first:first + n] for a in w),
        first_expert=first, chunk_rows=chunk)
    want = _dense_experts(x, np.asarray(chosen), np.asarray(weights), w,
                          first, n)
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5)
    assert list(np.asarray(counts)) == [
        int((np.asarray(chosen) == first + j).sum()) for j in range(n)]


def test_four_ranks_of_two_experts_add_up_to_the_whole_layer():
    rng = np.random.default_rng(2)
    x, gate_w, bias, w = _expert_layer(rng)
    chosen, weights = route_top_k(x, gate_w, bias, 2, scale=2.5)
    parts = [routed_experts(x, chosen, weights,
                            *(a[2 * r:2 * r + 2] for a in w),
                            first_expert=2 * r) for r in range(4)]
    whole = _dense_experts(x, np.asarray(chosen), np.asarray(weights), w,
                           0, 8)
    np.testing.assert_allclose(sum(np.asarray(y) for y, _c in parts), whole,
                               atol=1e-5)
    assert sum(int(np.asarray(c).sum()) for _y, c in parts) == 37 * 2


def test_every_token_sent_to_one_held_expert_drops_none():
    """No capacity: an expert that every token chose computes every
    token, over as many chunks as that takes."""
    import jax.numpy as jnp
    rng = np.random.default_rng(3)
    x, _gate_w, _bias, w = _expert_layer(rng, tokens=50)
    chosen = jnp.asarray(np.stack([np.full(50, 5), np.arange(50) % 4], 1)
                         .astype(np.int32))
    weights = jnp.asarray(rng.uniform(0.2, 1.0, (50, 2)).astype(np.float32))
    y, counts = routed_experts(x, chosen, weights, *(a[4:6] for a in w),
                               first_expert=4, chunk_rows=8)
    assert list(np.asarray(counts)) == [0, 50]
    np.testing.assert_allclose(
        np.asarray(y), _dense_experts(x, np.asarray(chosen),
                                      np.asarray(weights), w, 4, 2),
        atol=1e-5)
    # padding tokens count in no expert's load and get nothing
    live = jnp.asarray(np.arange(50) < 30)
    y, counts = routed_experts(x, chosen, weights, *(a[4:6] for a in w),
                               first_expert=4, live=live, chunk_rows=8)
    assert list(np.asarray(counts)) == [0, 30]
    assert not np.asarray(y)[30:].any()


# ---------------------------------------------------------------------
# two attention forms over one set of weights, one cache for both models
# ---------------------------------------------------------------------

def test_each_model_declares_its_cache_rows():
    assert MODEL.cache_rows() == {"latent": (8 + 4,)}
    gpt = TinyGPT(units=32, num_heads=2)
    assert gpt.cache_rows() == {"k": (2, 16), "v": (2, 16)}
    cache = PagedKVCache(3, MODEL.cache_rows(), block_size=4, num_blocks=9)
    assert cache.slab_shapes == {"latent": (9, 4, 128)}
    assert len(cache.slabs["latent"]) == 3
    wide = PagedKVCache(1, {"latent": (576,)}, 64, 3, dtype="bfloat16")
    assert wide.slab_shapes["latent"] == (3, 64, 640)     # whole tiles
    assert wide.slab_bytes() == 3 * 64 * 640 * 2


def test_absorbed_decode_equals_the_expanded_form_on_logits(params):
    """Prefill 11 tokens, then decode 9 through the latent cache, two
    streams and two padded slots in one batch: every step's logits are
    the full (expanded) forward's at that position."""
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    seqs = rng.randint(0, 128, (2, 20)).astype(np.int32)
    full = np.asarray(MODEL.full_logits(params, jnp.asarray(seqs)))
    cache = PagedKVCache(MODEL.num_layers, MODEL.cache_rows(), 4, 20)
    tables = [cache.allocate(20), cache.allocate(20)]
    slabs, n = cache.slabs, 11
    for i, table in enumerate(tables):
        padded = np.zeros((1, 16), np.int32)
        padded[0, :n] = seqs[i, :n]
        logits, rows, stats = jax.jit(MODEL.prefill_kv)(params, padded,
                                                        n - 1)
        np.testing.assert_allclose(np.asarray(logits), full[i, n - 1],
                                   atol=2e-5)
        assert int(stats["moe_assignments"]) == n * 2 * 2
        pos = np.arange(16)
        blk = np.where(pos < n, np.asarray(table.blocks)[pos // 4],
                       SCRATCH_BLOCK)
        slabs = {"latent": tuple(
            s.at[blk, pos % 4].set(slab_rows(r, s))
            for s, r in zip(slabs["latent"], rows["latent"]))}
    step = jax.jit(MODEL.decode_logits, static_argnums=(5,))
    bt = np.full((4, 8), SCRATCH_BLOCK, np.int32)
    for i, table in enumerate(tables):
        bt[i] = cache.padded_table(table, 8)
    for t in range(n, 20):
        tokens = np.array([seqs[0, t], seqs[1, t], 0, 0], np.int32)
        positions = np.array([t, t, 0, 0], np.int32)
        live = np.array([True, True, False, False])
        _next, logits, slabs, stats = step(params, slabs, tokens,
                                           positions, bt, 4, live)
        np.testing.assert_allclose(np.asarray(logits)[:2], full[:, t],
                                   atol=2e-5)
        # two live slots, two experts a token, two expert layers
        assert int(stats["moe_assignments"]) == 2 * 2 * 2
        assert 0 <= int(stats["moe_expert_tokens_max"]) <= 2


# ---------------------------------------------------------------------
# through the engine
# ---------------------------------------------------------------------

@pytest.fixture()
def engine(params):
    eng = DecodeEngine(MODEL, params, **ENGINE_KW)
    eng.warmup()
    eng.start()
    yield eng
    eng.close(drain=False)


def test_engine_tokens_match_the_oracle_solo_and_joined_mid_batch(
        engine, params):
    prompts = [[3, 14, 15, 92, 65, 35], [27, 18, 28], [1, 2, 3, 5, 8, 13,
                                                        21, 34, 55]]
    want = [_greedy(MODEL, params, p, 7) for p in prompts]
    assert engine.submit(prompts[0], 7).tokens() == want[0]      # solo
    first = engine.submit(prompts[0], 7)
    assert next(first) == want[0][0]     # it is decoding: the rest join
    others = [engine.submit(p, 7) for p in prompts[1:]]
    assert [next(first)] + list(first) == want[0][1:]
    assert [s.tokens() for s in others] == want[1:]
    assert engine.cache.blocks_in_use() == 0


@pytest.mark.parametrize("model", [
    MODEL, TinyGPT(vocab_size=128, units=32, num_layers=2, num_heads=2,
                   max_seq=64)], ids=["latent_rows", "k_and_v"])
def test_blocks_in_use_returns_to_zero_after_a_drain(model):
    """The allocator is ONE code path whatever rows the model declares."""
    eng = DecodeEngine(model, model.init_params(0), **ENGINE_KW)
    eng.warmup()
    eng.start()
    streams = [eng.submit([5, 6, 7, 8, 9][:2 + i], 4 + i) for i in range(5)]
    assert eng.cache.blocks_in_use() > 0
    eng.close(drain=True)
    assert [len(s.tokens()) for s in streams] == [4, 5, 6, 7, 8]
    assert eng.cache.blocks_in_use() == 0
    assert eng.cache.stats()["free_blocks"] == 64
    rows = model.cache_rows()
    assert sorted(eng.cache.slabs) == sorted(rows)
    for name, shape in rows.items():
        assert eng.cache.slab_shapes[name] == (65, 4) + shape[:-1] + (128,)


def test_the_latent_cache_is_written_in_place(params):
    eng = DecodeEngine(MODEL, params, **ENGINE_KW)
    eng.warmup()
    slab_bytes = eng.cache.slab_bytes()
    assert slab_bytes == MODEL.num_layers * 65 * 4 * 128 * 4
    for kind, buckets in (("prefill", eng.prefill_buckets),
                          ("decode", eng.decode_buckets)):
        for b in buckets:
            mem = eng.program_memory(kind, b)
            if mem is not None:
                assert mem["aliased_bytes"] == slab_bytes, (kind, b, mem)
            head = eng._programs.get((kind, b)).as_text().split("\n")[0]
            assert head.count("-alias)") == MODEL.num_layers


def test_the_engine_counts_expert_assignments(engine, params):
    telemetry.enable()
    telemetry.reset("decode.")
    obs.trace.clear()
    obs.enable_tracing()
    try:
        assert len(engine.submit([9, 8, 7, 6, 5], 4).tokens()) == 4
        reg = telemetry.registry()
        # 5 prompt tokens and 3 decode steps of one token, two experts
        # a token, two expert layers
        assert reg.counter("decode.moe.assignments").value \
            == (5 + 3) * 2 * 2
        held = reg.counter("decode.moe.assignments_held").value
        assert 0 <= held <= (5 + 3) * 2 * 2
        assert reg.counter("decode.moe.expert_tokens_max").value <= held
        spans = {name: [s for s in obs.spans() if s["name"] == name]
                 for name in ("mx.decode.prefill", "mx.decode.step")}
        assert len(spans["mx.decode.prefill"]) == 1
        assert len(spans["mx.decode.step"]) == 3
        assert spans["mx.decode.prefill"][0]["attrs"]["moe_assignments"] \
            == 5 * 2 * 2
        for s in spans["mx.decode.step"]:
            assert s["attrs"]["moe_assignments"] == 2 * 2
            assert {"moe_assignments_held", "moe_expert_tokens_max",
                    "n", "bucket"} <= set(s["attrs"])
        assert sum(s["attrs"]["moe_assignments_held"]
                   for ss in spans.values() for s in ss) == held
    finally:
        obs.disable_tracing()
        telemetry.reset("decode.")
        telemetry.disable()


def test_a_decode_step_counts_the_latent_kernels_grid_steps(engine,
                                                           params):
    """``latent_grid_steps`` is the schedule's step count times the MLA
    layers, on the step's span and summed by ``decode.latent.grid_steps``:
    a table of 16 blocks of 4 is read 8 pages (32 tokens) a step, so a
    30-token prompt's decode steps reach 31, 32 and 33 tokens, one group,
    one and two, beside the padded slot's one."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.mla_paged_attention import grid_steps
    bt = np.full((4, 16), SCRATCH_BLOCK, np.int32)
    bt[:, :12] = np.arange(1, 49).reshape(4, 12)
    positions = np.array([40, 3, 31, 0], np.int32)
    slabs = {"latent": tuple(jnp.zeros((49, 4, 128), jnp.float32)
                             for _ in range(MODEL.num_layers))}
    _next, _logits, _slabs, stats = jax.jit(
        MODEL.decode_logits, static_argnums=(5,))(
        params, slabs, np.zeros(4, np.int32), positions, bt, 4,
        np.array([True, True, True, False]))
    assert int(grid_steps(jnp.asarray(bt), jnp.asarray(positions + 1)
                          .reshape(4, 1), 4)) == 2 + 1 + 1 + 1
    assert int(stats["latent_grid_steps"]) == 5 * MODEL.num_layers
    telemetry.enable()
    telemetry.reset("decode.")
    obs.trace.clear()
    obs.enable_tracing()
    try:
        assert len(engine.submit(list(range(1, 31)), 4).tokens()) == 4
        steps = [s["attrs"]["latent_grid_steps"] for s in obs.spans()
                 if s["name"] == "mx.decode.step"]
        assert steps == [2 * 3, 2 * 3, 3 * 3]
        assert telemetry.registry().counter(
            "decode.latent.grid_steps").value == sum(steps)
        assert not any("latent_grid_steps" in s["attrs"] for s in obs.spans()
                       if s["name"] == "mx.decode.prefill")
    finally:
        obs.disable_tracing()
        telemetry.reset("decode.")
        telemetry.disable()


def test_an_eos_in_the_middle_of_a_batch_ends_that_stream_alone(
        engine, params):
    """The second model through the loop that keeps a step in flight:
    the stream that hits its EOS holds exactly the tokens up to it, the
    token the next step computed for it is discarded, and each step's
    expert counts are on ONE span and in the counters once."""
    prompts = [[3, 14, 15, 92, 65, 35], [27, 18, 28]]
    want = [_greedy(MODEL, params, p, 9) for p in prompts]
    k = next(i for i in range(1, 8) if want[1][i] not in want[1][:i])
    telemetry.enable()
    telemetry.reset("decode.")
    obs.trace.clear()
    obs.enable_tracing()
    try:
        first = engine.submit(prompts[0], 9)
        other = engine.submit(prompts[1], 9, eos_id=want[1][k])
        assert other.tokens() == want[1][:k + 1]
        assert other.finish_reason == "eos"
        assert first.tokens() == want[0]
        reg = telemetry.registry()
        assert reg.counter("decode.tokens_discarded").value == 1
        steps = [s for s in obs.spans() if s["name"] == "mx.decode.step"]
        assert reg.counter("decode.steps").value == len(steps)
        # two experts a token, two expert layers: a step's count is its
        # live slots' alone (the discarded token's slot was live in it)
        for s in steps:
            assert s["attrs"]["moe_assignments"] == s["attrs"]["n"] * 2 * 2
        prefills = [s for s in obs.spans()
                    if s["name"] == "mx.decode.prefill"]
        assert reg.counter("decode.moe.assignments").value == sum(
            s["attrs"]["moe_assignments"] for s in steps + prefills)
    finally:
        obs.disable_tracing()
        telemetry.reset("decode.")
        telemetry.disable()
    assert engine.cache.blocks_in_use() == 0


def test_a_dense_model_returns_no_counts(params):
    import jax.numpy as jnp
    gpt = TinyGPT(vocab_size=64, units=32, num_layers=1, num_heads=2,
                  max_seq=32)
    _logits, _rows, stats = gpt.prefill_kv(
        gpt.init_params(0), jnp.zeros((1, 8), jnp.int32), 3)
    assert stats == {}
    dense_only = LatentMoEDecoder(**dict(TINY, num_hidden_layers=1))
    assert dense_only._new_stats() == {}


def test_a_share_outside_the_experts_is_refused():
    from mxnet_tpu.base import MXNetError
    with pytest.raises(MXNetError, match="held"):
        LatentMoEDecoder(**dict(TINY, first_expert=7, n_held=2))
    with pytest.raises(MXNetError, match="rotary"):
        LatentMoEDecoder(**dict(TINY, qk_rope_head_dim=3))
