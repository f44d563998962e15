"""The decoder with window and full layers on the serving path, at a small
size on the CPU in float32: the rotary tables against hand numbers, the
generalised paged-attention kernel (grouped heads, a window over a ring
table) against its XLA reference and against the kernel it was, the
softmax router, ONE cache with two pools, and the engine serving the
model through it across several wraps of the ring."""
import importlib.util
import os

import numpy as np
import pytest

from mxnet_tpu import obs, telemetry
from mxnet_tpu.kernels import registry
from mxnet_tpu.kernels.paged_attention import paged_attention
from mxnet_tpu.parallel.moe import route_top_k, routed_experts
from mxnet_tpu.serving.batcher import ServingQueueFull
from mxnet_tpu.serving.decode import (DecodeEngine, KVCacheExhausted,
                                      LatentMoEDecoder, PagedKVCache,
                                      TinyGPT, WindowMoEDecoder, blocks)
from mxnet_tpu.serving.decode.kvcache import (FULL, SCRATCH_BLOCK, WINDOW,
                                              write_prompt, write_tokens)

YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
PLAIN = {"rope_type": "default", "rope_theta": 500000}
SLIDING, WHOLE = "sliding_attention", "full_attention"
TINY = dict(vocab_size=128, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16,
            layer_types=[SLIDING, SLIDING, SLIDING, WHOLE],
            sliding_window=8,
            rope_parameters={
                WHOLE: dict(YARN, original_max_position_embeddings=16),
                SLIDING: PLAIN},
            moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
            max_seq=72, dtype="float32")
MODEL = WindowMoEDecoder(**TINY)
# ring = ceil(8 / 4) + 1 = 3 blocks a sequence in a window layer
ENGINE_KW = dict(prefill_buckets=(8, 16, 32), decode_buckets=(2, 4),
                 block_size=4, num_blocks=73, window_blocks=13,
                 kv_dtype="float32")


@pytest.fixture(scope="module")
def params():
    return MODEL.init_params(3)


@pytest.fixture()
def engine(params):
    eng = DecodeEngine(MODEL, params, **ENGINE_KW)
    eng.warmup()
    eng.start()
    yield eng
    eng.close(drain=False)


# ---------------------------------------------------------------------
# rotary tables: the numbers of ISSUE 32
# ---------------------------------------------------------------------

def test_the_yarn_table_is_mellum2s_by_hand():
    inv = blocks.yarn_inv_freq(128, 500000, YARN)
    plain = 500000.0 ** (-2.0 * np.arange(64) / 128)

    def corr(b):
        return 128 * np.log(8192 / (2 * np.pi * b)) / (2 * np.log(500000))
    assert corr(32) == pytest.approx(18.08, abs=0.01)
    assert corr(1) == pytest.approx(34.98, abs=0.01)
    low, high = int(np.floor(corr(32))), int(np.ceil(corr(1)))
    assert (low, high) == (18, 35)
    # pairs up to 18 turn often enough to be kept, from 35 on they are
    # interpolated, a ramp of 17 steps between
    np.testing.assert_allclose(inv[:19], plain[:19], rtol=1e-12)
    np.testing.assert_allclose(inv[35:], plain[35:] / 16, rtol=1e-12)
    ramp = (26 - 18) / 17
    assert inv[26] == pytest.approx(
        plain[26] / 16 * ramp + plain[26] * (1 - ramp), rel=1e-12)
    assert inv[26] == pytest.approx(500000 ** (-52 / 128)
                                    * (1 - ramp * 15 / 16), rel=1e-12)
    assert YARN["attention_factor"] == pytest.approx(0.1 * np.log(16) + 1)
    np.testing.assert_allclose(blocks.yarn_inv_freq(128, 500000), plain)
    big = WindowMoEDecoder(**dict(
        TINY, head_dim=128, rope_parameters={WHOLE: YARN, SLIDING: PLAIN}))
    np.testing.assert_allclose(big.rope[WHOLE][0], inv, rtol=1e-6)
    assert big.rope[WHOLE][1] == YARN["attention_factor"]
    np.testing.assert_allclose(big.rope[SLIDING][0], plain, rtol=1e-6)
    assert big.rope[SLIDING][1] == 1.0


def test_rotation_turns_the_halves_and_a_full_layers_score_is_squared():
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(5, 16)).astype(np.float32))
    pos = jnp.arange(5, dtype=jnp.int32)
    inv, factor = MODEL.rope[WHOLE]
    got = np.asarray(blocks.rotate(x, pos, inv, halves=True, factor=factor))
    for t in range(5):
        for j in range(8):
            c, s = np.cos(t * inv[j]) * factor, np.sin(t * inv[j]) * factor
            a, b = float(x[t, j]), float(x[t, j + 8])
            np.testing.assert_allclose(
                [got[t, j], got[t, j + 8]], [a * c - b * s, b * c + a * s],
                rtol=1e-5, atol=1e-6)
    # position 0 is the vector times the factor: q . k carries its square
    np.testing.assert_allclose(got[0], np.asarray(x[0]) * factor, rtol=1e-6)
    q = jnp.asarray(rng.normal(size=(1, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 16)).astype(np.float32))
    scores = [float(jnp.sum(
        blocks.rotate(q, jnp.asarray([a]), inv, halves=True)
        * blocks.rotate(k, jnp.asarray([a - 3]), inv, halves=True)))
        for a in (3, 10, 40)]
    assert scores[0] == pytest.approx(scores[1], abs=1e-4)
    assert scores[0] == pytest.approx(scores[2], abs=1e-4)


# ---------------------------------------------------------------------
# the kernel: grouped heads, a window over a ring
# ---------------------------------------------------------------------

def _ring_case(rng, heads, kv_heads, d, bs, ring, ctxs, fold):
    slots = len(ctxs)
    nb = 1 + slots * ring
    k = rng.normal(size=(nb, bs, kv_heads, d)).astype(np.float32)
    v = rng.normal(size=(nb, bs, kv_heads, d)).astype(np.float32)
    q = rng.normal(size=(slots, heads, d)).astype(np.float32)
    tables = np.stack([1 + i * ring + rng.permutation(ring)
                       for i in range(slots)]).astype(np.int32)
    ctx = np.asarray(ctxs, np.int32).reshape(slots, 1)
    if fold:
        k, v = (a.reshape(nb, bs * kv_heads, d) for a in (k, v))
    return q, k, v, tables, ctx


def _dense(q, k, v, tables, ctx, bs, window, scale):
    """The oracle: every position looked up through the ring."""
    slots, heads, d = q.shape
    k = k.reshape(k.shape[0], bs, -1, d)
    v = v.reshape(v.shape[0], bs, -1, d)
    group = heads // k.shape[2]
    out = np.zeros_like(q)
    for i in range(slots):
        c = int(ctx[i, 0])
        pos = np.arange(max(0, c - window) if window else 0, c)
        blk, off = tables[i, (pos // bs) % tables.shape[1]], pos % bs
        for h in range(heads):
            s = k[blk, off, h // group] @ q[i, h] * scale
            p = np.exp(s - s.max())
            out[i, h] = (p / p.sum()) @ v[blk, off, h // group]
    return out


@pytest.mark.parametrize("use_pallas", [True, False], ids=["pallas", "xla"])
@pytest.mark.parametrize("fold", [True, False], ids=["folded", "4d"])
@pytest.mark.parametrize("group,window", [(1, None), (2, None), (1, 8),
                                          (2, 8)])
def test_grouped_heads_and_a_window_at_ragged_contexts(group, window, fold,
                                                       use_pallas):
    """Contexts on both sides of the window (8) and of the ring's reach
    (3 blocks of 4); without a window the table covers the context."""
    import jax.numpy as jnp
    rng = np.random.default_rng(group * 10 + (window or 0))
    ctxs = [1, 5, 8, 9, 12, 13, 30, 41] if window else [1, 7, 33, 64]
    ring = 3 if window else 16
    q, k, v, tables, ctx = _ring_case(rng, 4, 4 // group, 16, 4, ring, ctxs,
                                      fold)
    got = paged_attention(*(jnp.asarray(a) for a in (q, k, v, tables, ctx)),
                          scale=0.3, use_pallas=use_pallas, window=window,
                          block_size=4)
    np.testing.assert_allclose(
        np.asarray(got), _dense(q, k, v, tables, ctx, 4, window, 0.3),
        atol=2e-6)


def test_mellum2s_own_shapes_in_interpret_mode():
    """32 query over 4 K/V heads of 128, blocks of 64, a ring of 17 read
    8 pages a step (the page group does not divide the ring)."""
    import jax.numpy as jnp
    rng = np.random.default_rng(5)
    q, k, v, tables, ctx = _ring_case(rng, 32, 4, 128, 64, 17,
                                      [100, 1024, 1025, 1100, 5000], True)
    got = paged_attention(*(jnp.asarray(a) for a in (q, k, v, tables, ctx)),
                          scale=128 ** -0.5, use_pallas=True, window=1024,
                          block_size=64)
    np.testing.assert_allclose(
        np.asarray(got), _dense(q, k, v, tables, ctx, 64, 1024, 128 ** -0.5),
        atol=2e-5)


def test_a_window_call_makes_grid_steps_for_its_windows_pages_alone():
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas.paged_attention import _live_steps
    tables = jnp.asarray(1 + np.arange(2 * 17).reshape(2, 17), jnp.int32)
    ctx = jnp.asarray([5000, 700], jnp.int32)
    steps, slot, group, pages = _live_steps(tables, ctx, 64, 8, window=1024)
    # positions 3976..4999 are blocks 62..78, page groups 7, 8, 9; the
    # short one's 700 positions are groups 0, 1
    assert int(steps) == 5
    assert slot[:5].tolist() == [0, 0, 0, 1, 1]
    assert group[:5].tolist() == [7, 8, 9, 0, 1]
    named = np.asarray(pages)[:, :5]
    # group 7 holds blocks 56..63 of which 62, 63 are live: ring entries
    # 62 % 17 = 11 and 12 of slot 0's table
    assert named[6, 0] == tables[0, 11] and named[7, 0] == tables[0, 12]
    assert named[0, 1] == tables[0, 64 % 17]
    # without a window the same slot walks all ten of its groups
    whole = jnp.asarray(np.arange(2 * 80).reshape(2, 80), jnp.int32)
    assert int(_live_steps(whole, ctx, 64, 8)[0]) == 10 + 2


def test_the_ungrouped_unwindowed_call_is_the_kernel_it_was():
    """Bit for bit against the kernel and the reference as they stood
    before grouped heads and windows
    (``tests/_paged_attention_before_pr32.py``, a verbatim copy)."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas import paged_attention as now
    spec = importlib.util.spec_from_file_location(
        "paged_attention_before_pr32", os.path.join(
            os.path.dirname(__file__), "_paged_attention_before_pr32.py"))
    before = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(before)
    rng = np.random.default_rng(7)
    for table_width in (8, 6, 3):               # 8, 2 and 1 pages a step
        q, k, v, tables, ctx = (jnp.asarray(a) for a in _ring_case(
            rng, 4, 4, 16, 4, table_width,
            [1, 4, 9, 4 * table_width - 3, 4 * table_width], False))
        np.testing.assert_array_equal(
            np.asarray(now.paged_attention_pallas(
                q, k, v, tables, ctx, scale=0.25, interpret=True)),
            np.asarray(before.paged_attention_pallas(
                q, k, v, tables, ctx, scale=0.25, interpret=True)))
        np.testing.assert_array_equal(
            np.asarray(now.paged_attention_reference(
                q, k, v, tables, ctx, scale=0.25)),
            np.asarray(before.paged_attention_reference(
                q, k, v, tables, ctx, scale=0.25)))
        steps = [np.asarray(a) for a in now._live_steps(
            tables, ctx.reshape(-1), 4, 1)]
        for a, b in zip(steps, before._live_steps(tables, ctx.reshape(-1),
                                                  4, 1)):
            np.testing.assert_array_equal(a, np.asarray(b))


def test_choose_sees_the_k_v_heads_and_the_window():
    ok = registry.choose("paged_attention", force=True, heads=32,
                         head_dim=128, block_size=64, kv_heads=4,
                         window=1024)
    assert ok.use_pallas and ok.interpret
    for bad in (dict(kv_heads=5), dict(kv_heads=0), dict(window=0)):
        choice = registry.choose("paged_attention", force=True, heads=32,
                                 head_dim=128, block_size=64,
                                 **dict(dict(kv_heads=4), **bad))
        assert not choice.use_pallas and "paged attention needs" \
            in choice.reason


# ---------------------------------------------------------------------
# the router and the experts
# ---------------------------------------------------------------------

def test_softmax_top_k_weights_sum_to_one_and_carry_no_bias():
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(9, 16)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))
    chosen, weights = route_top_k(x, w, None, 3, scoring="softmax")
    prob = np.asarray(jax.nn.softmax(
        jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST), -1))
    np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1),
                                  np.sort(np.argsort(-prob, -1)[:, :3], -1))
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, atol=1e-6)
    picked = np.take_along_axis(prob, np.asarray(chosen), -1)
    np.testing.assert_allclose(np.asarray(weights),
                               picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)
    _c, raw = route_top_k(x, w, None, 3, scoring="softmax", normalize=False)
    np.testing.assert_allclose(np.asarray(raw), picked, rtol=1e-5)
    with pytest.raises(Exception, match="scoring"):
        route_top_k(x, w, None, 3, scoring="tanh")


def test_every_token_sent_to_one_expert_drops_none():
    import jax.numpy as jnp
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(40, 16)).astype(np.float32))
    gate, up = (jnp.asarray(rng.normal(size=(8, 16, 12)).astype(np.float32))
                for _ in range(2))
    down = jnp.asarray(rng.normal(size=(8, 12, 16)).astype(np.float32))
    chosen = jnp.full((40, 1), 5, jnp.int32)
    y, counts = routed_experts(x, chosen, jnp.ones((40, 1), jnp.float32),
                               gate, up, down, 0, chunk_rows=16)
    assert counts.tolist() == [0, 0, 0, 0, 0, 40, 0, 0]
    want = blocks.swiglu(x, gate[5], up[5], down[5])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("tokens,experts,top_k,first,held,dense", [
    (32, 64, 8, 0, 64, True),       # Mellum2's decode step: 4 an expert
    (16, 64, 8, 0, 64, True),
    (32, 384, 8, 0, 12, False),     # Kimi-K2's: 0.7 an expert, 12 held
    (16, 8, 2, 2, 4, True),         # a share of the experts, padding
    (33, 8, 2, 0, 8, False),        # a token past what the chip has run
    (200, 8, 2, 0, 8, False)],      # too many tokens for a step
    ids=["mellum2_32", "mellum2_16", "kimi_k2_32", "a_share", "past_32",
         "many"])
def test_a_decode_step_over_busy_experts_runs_every_expert_over_every_token(
        tokens, experts, top_k, first, held, dense):
    """The route is the caller's to allow (``num_experts``: the tokens
    are a decode step's) and is then chosen from the call's shapes; a
    call that does not say so is the grouped matmul, in as many chunks as
    the assignments fill, and both give the same sum and the same
    counts."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(tokens + experts)
    x = jnp.asarray(rng.normal(size=(tokens, 16)).astype(np.float32))
    gate, up = (jnp.asarray(rng.normal(size=(held, 16, 12))
                            .astype(np.float32)) for _ in range(2))
    down = jnp.asarray(rng.normal(size=(held, 12, 16)).astype(np.float32))
    router = jnp.asarray(rng.normal(size=(16, experts)).astype(np.float32))
    chosen, weights = route_top_k(x, router, None, top_k, scoring="softmax")
    live = jnp.arange(tokens) < tokens - 3

    def route(**kw):
        return lambda x: routed_experts(
            x, chosen, weights, gate, up, down, first, live=live, **kw)
    step = route(num_experts=experts, decode_step=True)
    assert ("ragged_dot" not in str(jax.make_jaxpr(step)(x))) == dense
    y, counts = step(x)
    # 24 sorted rows a chunk: every case has several chunks to loop over
    grouped = route(chunk_rows=24)
    assert tokens * top_k > 24
    assert "ragged_dot" in str(jax.make_jaxpr(grouped)(x))
    want, want_counts = grouped(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-4)
    assert counts.tolist() == want_counts.tolist()
    assert int(counts.sum()) == int(jnp.sum(
        live[:, None] & (chosen >= first) & (chosen < first + held)))


def test_a_prefill_is_the_grouped_matmul_and_a_busy_decode_step_is_not(
        params):
    """What the chip runs at Mellum2's sizes, at this size: a prompt's
    tokens are sorted by expert whatever their number, a step of 4 slots
    (8 assignments over 8 experts) runs every expert over every token,
    and a step of 2 slots, which cannot keep 8 experts busy, is grouped."""
    import jax
    eng = DecodeEngine(MODEL, params, **ENGINE_KW)
    prefill, decode = eng._specs()
    for bucket in eng.prefill_buckets:
        assert "ragged_dot" in str(jax.make_jaxpr(eng._prefill_impl)(
            *prefill[bucket]))
    assert "ragged_dot" in str(jax.make_jaxpr(eng._decode_impl)(*decode[2]))
    assert "ragged_dot" not in str(
        jax.make_jaxpr(eng._decode_impl)(*decode[4]))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("rows,sizes", [
    (40, [7, 0, 20, 1, 9]),             # an empty group, rows past the last
    (128, [128, 0, 0]),                 # one group owns the one tile
    (300, [100, 100, 100]),             # groups share a tile, rows pad to one
    (1100, [3, 509, 0, 1, 511, 60]),    # three row tiles, groups across them
    (256, [0, 0, 0, 0])],               # nothing to do
    ids=["ragged", "one_group", "shared_tile", "three_tiles", "empty"])
def test_the_grouped_matmul_kernel_equals_ragged_dot(rows, sizes, dtype):
    """``kernels.grouped_matmul``: the Pallas body (interpret mode here)
    against its XLA reference, zeros in the rows past the last group."""
    import jax.numpy as jnp
    from mxnet_tpu.kernels.grouped_matmul import grouped_matmul
    rng = np.random.default_rng(rows)
    lhs = jnp.asarray(rng.normal(size=(rows, 32)), dtype)
    rhs = jnp.asarray(rng.normal(size=(len(sizes), 32, 16)), dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    want = grouped_matmul(lhs, rhs, sizes, use_pallas=False)
    got = grouped_matmul(lhs, rhs, sizes, use_pallas=True)
    assert got.shape == want.shape == (rows, 16) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    assert not np.asarray(got)[int(sizes.sum()):].any()


@pytest.mark.parametrize("first,held", [(0, 8), (2, 4)],
                         ids=["all_held", "a_share"])
@pytest.mark.parametrize("chunk_rows", [16, 40, 2048])
def test_routed_experts_through_the_kernel_equal_those_through_ragged_dot(
        chunk_rows, first, held):
    """A prefill's route with the Pallas grouped matmul asked for: the
    same sum and the same counts as with ``ragged_dot``, in one chunk
    and in several, with padding tokens and experts held elsewhere."""
    import jax.numpy as jnp
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(50, 16)), jnp.bfloat16)
    gate, up = (jnp.asarray(rng.normal(size=(held, 16, 12)) * 0.3,
                            jnp.bfloat16) for _ in range(2))
    down = jnp.asarray(rng.normal(size=(held, 12, 16)) * 0.3, jnp.bfloat16)
    router = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))
    chosen, weights = route_top_k(x, router, None, 2, scoring="softmax")
    live = jnp.arange(50) < 45

    def route(use_pallas):
        return routed_experts(x, chosen, weights, gate, up, down, first,
                              live=live, chunk_rows=chunk_rows,
                              use_pallas=use_pallas)
    (want, want_counts), (got, counts) = route(False), route(True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert counts.tolist() == want_counts.tolist()


@pytest.fixture()
def combine_counts():
    """The counters of ``routed_experts``' two combines, read as
    (gather, scatter) from zero."""
    was = telemetry.enabled()
    telemetry.enable()
    telemetry.reset("moe.")

    def read():
        return (telemetry.counter("moe.combine_gather").value,
                telemetry.counter("moe.combine_scatter").value)
    yield read
    telemetry.reset("moe.")
    if not was:
        telemetry.disable()


def _every_chosen_expert(x, chosen, weights, gate, up, down, live):
    """The routed sum in float64, token by token: the reference neither
    route shares any code with."""
    x, gate, up, down = (np.asarray(a, np.float64)
                         for a in (x, gate, up, down))
    y = np.zeros(x.shape)
    for t in np.flatnonzero(np.asarray(live)):
        for e, w in zip(np.asarray(chosen)[t], np.asarray(weights)[t]):
            h = x[t] @ gate[e]
            y[t] += w * ((h / (1 + np.exp(-h)) * (x[t] @ up[e])) @ down[e])
    return y


@pytest.mark.parametrize("tokens,experts,top_k,padding,chunk_rows,block", [
    (50, 8, 2, 0, 24, 4096),        # 100 rows: not a multiple of the chunk
    (40, 8, 2, 7, 16, 4096),        # padding masked by live
    (50, 8, 2, 5, 24, 16),          # four token blocks, the last padded
    (40, 64, 8, 3, 64, 16)],        # top-8 of 64 experts, tiny widths
    ids=["ragged_chunk", "padding", "token_blocks", "top8_of_64"])
def test_a_layer_holding_every_expert_combines_by_a_gather(
        tokens, experts, top_k, padding, chunk_rows, block, combine_counts,
        monkeypatch):
    """A layer that holds every expert the router scores (``num_experts``
    its width, ``first_expert`` 0) takes the gather through the inverse
    of the sort; a call that does not give the width keeps the
    scatter-add.  Both equal the token-by-token sum to float32 rounding
    and count the same tokens."""
    import jax.numpy as jnp
    from mxnet_tpu.parallel import moe
    monkeypatch.setattr(moe, "BLOCK_TOKENS", block)
    rng = np.random.default_rng(tokens + experts + block)
    x = jnp.asarray(rng.normal(size=(tokens, 16)).astype(np.float32))
    gate, up = (jnp.asarray(rng.normal(0, 0.3, (experts, 16, 12))
                            .astype(np.float32)) for _ in range(2))
    down = jnp.asarray(rng.normal(0, 0.3, (experts, 12, 16))
                       .astype(np.float32))
    router = jnp.asarray(rng.normal(size=(16, experts)).astype(np.float32))
    chosen, weights = route_top_k(x, router, None, top_k, scoring="softmax")
    live = jnp.arange(tokens) < tokens - padding

    def route(**kw):
        return routed_experts(x, chosen, weights, gate, up, down, 0,
                              live=live, chunk_rows=chunk_rows, **kw)
    y, counts = route(num_experts=experts)
    assert combine_counts() == (1, 0)
    want, want_counts = route()
    assert combine_counts() == (1, 1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(y), _every_chosen_expert(x, chosen, weights, gate, up,
                                            down, live), atol=1e-4)
    assert counts.tolist() == want_counts.tolist()
    assert int(counts.sum()) == (tokens - padding) * top_k
    assert not np.asarray(y)[tokens - padding:].any()


def test_a_layer_holding_a_share_of_the_experts_keeps_the_scatter(
        combine_counts):
    """Kimi-K2's case, 4 of 16 experts held from ``first_expert`` 4: told
    the router's width or not, the call is the scatter-add it was, bit
    for bit, and counts as ``moe.combine_scatter``."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(50, 16)), jnp.bfloat16)
    gate, up = (jnp.asarray(rng.normal(0, 0.3, (4, 16, 12)), jnp.bfloat16)
                for _ in range(2))
    down = jnp.asarray(rng.normal(0, 0.3, (4, 12, 16)), jnp.bfloat16)
    router = jnp.asarray(rng.normal(size=(16, 16)).astype(np.float32))
    chosen, weights = route_top_k(x, router, None, 8, scoring="softmax")
    live = jnp.arange(50) < 45

    def route(**kw):
        return jax.jit(lambda x: routed_experts(
            x, chosen, weights, gate, up, down, 4, live=live,
            chunk_rows=24, **kw))(x)
    (y, counts), (was, was_counts) = route(num_experts=16), route()
    assert combine_counts() == (0, 2)
    assert np.array_equal(np.asarray(y), np.asarray(was))
    assert np.array_equal(np.asarray(counts), np.asarray(was_counts))
    assert 0 < int(counts.sum()) < 45 * 8


def test_a_prefill_holding_every_expert_is_counted_a_gather_a_layer(
        params, combine_counts):
    """The engine's programs through ``blocks.routed_ffn``: every prefill
    program traces its four expert layers through the gather, and a
    decode step of 2 slots (grouped: too few assignments to keep 8
    experts busy) too; a step of 4 slots takes every expert over every
    token and no combine is counted."""
    eng = DecodeEngine(MODEL, params, **ENGINE_KW)
    eng.warmup()
    layers = MODEL.num_layers
    assert combine_counts() == (layers * (len(eng.prefill_buckets) + 1), 0)


# ---------------------------------------------------------------------
# one cache, two pools
# ---------------------------------------------------------------------

def _two_pool_cache(**kw):
    return PagedKVCache(**dict(dict(
        layers=4, rows=MODEL.cache_rows(), block_size=4, num_blocks=21,
        kinds=MODEL.cache_layers(), window=8, window_blocks=7,
        fold_heads=True), **kw))


def test_the_model_declares_its_layers_and_the_cache_lays_them_out():
    assert MODEL.cache_rows() == {"k": (2, 16), "v": (2, 16)}
    assert MODEL.cache_layers() == (WINDOW, WINDOW, WINDOW, FULL)
    cache = _two_pool_cache()
    assert cache.ring == 3 and cache.window == 8
    # heads folded into the block's rows; a window layer's slab has the
    # window pool's blocks
    assert [a.shape for a in cache.slabs["k"]] \
        == [(7, 8, 128)] * 3 + [(21, 8, 128)]
    assert cache.slab_shapes["k"] == (21, 8, 128)
    assert cache.slab_bytes() == 2 * (3 * 7 + 21) * 8 * 128 * 4
    assert cache.blocks_needed(72) == {FULL: 18, WINDOW: 3}
    plain = PagedKVCache(2, MODEL.cache_rows(), 4, 21)
    assert plain.kinds == (FULL, FULL) and plain.ring is None
    assert plain.slab_shapes["k"] == (21, 4, 2, 128)
    assert plain.blocks_needed(72) == {FULL: 18}
    with pytest.raises(Exception, match="window_blocks"):
        PagedKVCache(4, MODEL.cache_rows(), 4, 21,
                     kinds=MODEL.cache_layers(), window=8)
    with pytest.raises(Exception, match="one kind a layer"):
        PagedKVCache(4, MODEL.cache_rows(), 4, 21, kinds=(FULL, "ring"))
    # the other specs declare no kinds: full layers, one table
    for model in (TinyGPT(), LatentMoEDecoder.__new__(LatentMoEDecoder)):
        assert not hasattr(model, "cache_layers")


@pytest.mark.parametrize("tokens,ring_blocks", [(3, 1), (12, 3), (13, 3),
                                                (40, 3), (72, 3)])
def test_a_window_layers_blocks_do_not_grow_with_the_request(tokens,
                                                             ring_blocks):
    cache = _two_pool_cache()
    table = cache.allocate(tokens)
    assert len(table.blocks) == -(-tokens // 4)
    assert len(table.ring) == ring_blocks
    assert not set(table.blocks) & {SCRATCH_BLOCK}
    assert not set(table.ring) & {SCRATCH_BLOCK}
    assert cache.blocks_in_use(FULL) == len(table.blocks)
    assert cache.blocks_in_use(WINDOW) == ring_blocks
    assert cache.blocks_in_use() == len(table.blocks) + ring_blocks
    assert cache.padded_table(table, 3, WINDOW).tolist()[:ring_blocks] \
        == table.ring
    cache.free(table)
    cache.free(table)                                   # idempotent
    assert cache.blocks_in_use() == 0
    assert cache.stats()["window_free_blocks"] == 6


@pytest.mark.parametrize("short", [FULL, WINDOW])
def test_an_admission_one_pool_cannot_cover_takes_nothing_from_the_other(
        short):
    # 20 full blocks and 6 ring blocks; requests of 20 tokens take 5 + 3
    cache = _two_pool_cache(num_blocks=21 if short == WINDOW else 8)
    held = [cache.allocate(20)]
    if short == WINDOW:
        held.append(cache.allocate(20))         # the window pool is empty
    before = (cache.blocks_in_use(FULL), cache.blocks_in_use(WINDOW))
    assert not cache.can_admit(20)
    with pytest.raises(KVCacheExhausted, match=short):
        cache.allocate(20)
    assert (cache.blocks_in_use(FULL), cache.blocks_in_use(WINDOW)) == before
    assert cache.can_admit(4) == (short == FULL)
    for table in held:
        cache.free(table)
    assert cache.blocks_in_use() == 0 and cache.can_admit(20)


def test_the_gauges_report_each_pool():
    telemetry.enable()
    telemetry.reset("kvcache.")
    try:
        cache = _two_pool_cache()
        table = cache.allocate(40)
        reg = telemetry.registry()
        assert reg.gauge("kvcache.blocks_in_use").value == 13
        assert reg.gauge("kvcache.blocks_in_use.full").value == 10
        assert reg.gauge("kvcache.blocks_in_use.window").value == 3
        cache.free(table)
        assert reg.gauge("kvcache.blocks_in_use.window").value == 0
        assert reg.gauge("kvcache.blocks_in_use").value == 0
    finally:
        telemetry.reset("kvcache.")
        telemetry.disable()


@pytest.mark.parametrize("fold", [True, False], ids=["folded", "4d"])
def test_a_prompt_longer_than_the_ring_leaves_its_last_blocks_in_it(fold):
    """30 prompt rows through a ring of 3 blocks of 4: blocks 5, 6, 7
    (positions 20..29) are what the ring holds, at entries 5 % 3, 6 % 3,
    7 % 3; the whole table of a full layer holds all of them."""
    import jax.numpy as jnp
    rows = jnp.asarray(np.arange(32 * 2 * 16, dtype=np.float32)
                       .reshape(32, 2, 16) + 1.0)
    shape = (9, 8, 128) if fold else (9, 4, 2, 128)
    ring_table = jnp.asarray([3, 5, 7], jnp.int32)
    slab = write_prompt(jnp.zeros(shape), rows, ring_table, 30, 4, ring=True)
    slab = np.asarray(slab).reshape(9, 4, 2, 128)
    for block, entry in ((5, 7), (6, 3), (7, 5)):
        n = 4 if block < 7 else 2
        np.testing.assert_array_equal(
            slab[entry, :n, :, :16], np.asarray(rows[block * 4:block * 4 + n]))
    assert not slab[[1, 2, 4, 6, 8]].any()      # nobody else's blocks
    whole = jnp.asarray([8, 7, 6, 5, 4, 3, 2, 1], jnp.int32)
    full = np.asarray(write_prompt(jnp.zeros(shape), rows, whole, 30, 4)
                      ).reshape(9, 4, 2, 128)
    for block in range(8):
        n = 4 if block < 7 else 2
        np.testing.assert_array_equal(
            full[8 - block, :n, :, :16],
            np.asarray(rows[block * 4:block * 4 + n]))
    # a decode step's row lands where the ring says: position 33 is block
    # 8, entry 8 % 3 = 2
    one = write_tokens(jnp.zeros(shape), rows[:1], ring_table[None],
                       jnp.asarray([33], jnp.int32), 4)
    one = np.asarray(one).reshape(9, 4, 2, 128)
    np.testing.assert_array_equal(one[7, 1, :, :16], np.asarray(rows[0]))
    assert np.count_nonzero(one) == 2 * 16


# ---------------------------------------------------------------------
# through the engine
# ---------------------------------------------------------------------

def _engine_logits(params, prompts, max_new, **kw):
    """Prefill then decode through the engine's own programs and both
    pools, by hand (the engine's loop keeps no logits): per request the
    logits of every generated position."""
    import jax
    import jax.numpy as jnp
    eng = DecodeEngine(MODEL, params, **dict(ENGINE_KW, **kw))
    prefill = jax.jit(eng._prefill_impl)
    decode = jax.jit(lambda p, s, t, pos, tb, live: MODEL.decode_logits(
        p, s, t, pos, tb, 4, live))
    slabs, reqs = eng.cache.slabs, []

    class Req:
        pass
    for prompt in prompts:
        r = Req()
        r.table = eng.cache.allocate(len(prompt) + max_new)
        r.seq = list(prompt)
        bucket = next(b for b in eng.prefill_buckets if b >= len(prompt))
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :len(prompt)] = prompt
        (first, _stats), slabs = prefill(params, slabs, tokens,
                                         eng._tables((r,)),
                                         np.int32(len(prompt)))
        r.seq.append(int(first))
        r.logits = []
        reqs.append(r)
    for _ in range(max_new - 1):
        n = len(reqs)
        tokens = np.zeros((4,), np.int32)
        positions = np.zeros((4,), np.int32)
        for i, r in enumerate(reqs):
            tokens[i], positions[i] = r.seq[-1], len(r.seq) - 1
        nxt, logits, slabs, stats = decode(
            params, slabs, tokens, positions, eng._tables(reqs, 4),
            np.arange(4) < n)
        assert int(stats["kv_rows_full"]) == sum(len(r.seq) for r in reqs)
        assert int(stats["kv_rows_window"]) == sum(min(len(r.seq), 8)
                                                   for r in reqs)
        for i, r in enumerate(reqs):
            r.logits.append(np.asarray(logits[i]))
            r.seq.append(int(nxt[i]))
    return reqs


def test_prefill_then_decode_equals_the_full_forward_on_logits_across_wraps(
        params):
    """A prompt under the window and one of 30 tokens (the ring holds 12),
    then 26 steps each: the ring wraps more than twice, and every step's
    logits are the full forward's at that position."""
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 128, n).tolist() for n in (5, 30)]
    for r in _engine_logits(params, prompts, 27):
        full = np.asarray(MODEL.full_logits(
            params, jnp.asarray([r.seq], jnp.int32)))[0]
        start = len(r.seq) - 27
        assert (len(r.seq) - start) // 12 >= 2
        for k, logits in enumerate(r.logits):
            np.testing.assert_allclose(logits, full[start + k], atol=3e-5)


def test_engine_tokens_match_the_oracle_solo_and_joined_mid_batch(engine,
                                                                  params):
    """Greedy tokens are the full forward's argmax at every position of
    the sequence they make (one forward a stream, teacher forced: a
    forward a token would compile a program a length)."""
    import jax.numpy as jnp
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 128, n).tolist() for n in (13, 3, 30)]
    solo = engine.submit(prompts[0], 26).tokens()
    first = engine.submit(prompts[0], 26)
    head = [next(first)]                 # it is decoding: the rest join
    others = [engine.submit(p, 26) for p in prompts[1:]]
    joined = [head + list(first)] + [s.tokens() for s in others]
    assert joined[0] == solo
    for prompt, out in zip(prompts, joined):
        assert len(out) == 26
        seq = np.zeros((1, 64), np.int32)
        seq[0, :len(prompt) + 26] = prompt + out
        logits = np.asarray(MODEL.full_logits(params, jnp.asarray(seq)))[0]
        want = logits[len(prompt) - 1:len(prompt) + 25].argmax(-1)
        assert out == want.tolist()
    assert engine.cache.blocks_in_use(FULL) == 0
    assert engine.cache.blocks_in_use(WINDOW) == 0


def test_both_pools_return_to_zero_after_a_drain_a_cancel_and_a_shed(params):
    eng = DecodeEngine(MODEL, params, **dict(ENGINE_KW, window_blocks=10))
    eng.warmup()
    eng.start()
    streams = [eng.submit([5, 6, 7, 8, 9, 10, 11][:3 + i], 20 + i)
               for i in range(3)]
    # three rings of three hold the window pool's nine blocks: the fourth
    # request is shed, and takes no block of the full pool with it
    in_full = eng.cache.blocks_in_use(FULL)
    assert eng.cache.blocks_in_use(WINDOW) == 9
    with pytest.raises(ServingQueueFull, match="window"):
        eng.submit([1, 2, 3], 20)
    assert eng.cache.blocks_in_use(FULL) == in_full
    streams[1].cancel()
    eng.close(drain=True)
    assert [len(s.tokens()) for s in (streams[0], streams[2])] == [20, 22]
    assert streams[1].finish_reason == "cancel"
    assert eng.cache.blocks_in_use(FULL) == 0
    assert eng.cache.blocks_in_use(WINDOW) == 0
    assert eng.cache.stats()["window_free_blocks"] == 9


def test_the_cache_is_written_in_place_in_both_pools(params):
    eng = DecodeEngine(MODEL, params, **ENGINE_KW)
    eng.warmup()
    slab_bytes = eng.cache.slab_bytes()
    assert slab_bytes == 2 * (3 * 13 + 73) * 8 * 128 * 4
    for kind, buckets in (("prefill", eng.prefill_buckets),
                          ("decode", eng.decode_buckets)):
        for b in buckets:
            mem = eng.program_memory(kind, b)
            if mem is not None:
                assert mem["aliased_bytes"] == slab_bytes, (kind, b, mem)
            head = eng._programs.get((kind, b)).as_text().split("\n")[0]
            assert head.count("-alias)") == 2 * MODEL.num_layers


def test_the_engine_counts_cache_rows_and_expert_assignments(engine):
    telemetry.enable()
    telemetry.reset("decode.")
    obs.trace.clear()
    obs.enable_tracing()
    try:
        assert len(engine.submit(list(range(1, 11)), 4).tokens()) == 4
        reg = telemetry.registry()
        # a prompt of 10 and three decode steps at contexts 11, 12, 13;
        # two experts a token, four layers
        assert reg.counter("decode.moe.assignments").value \
            == (10 + 3) * 2 * 4
        assert reg.counter("decode.moe.assignments_held").value \
            == (10 + 3) * 2 * 4                 # every expert is held
        assert reg.counter("decode.kv.rows_full").value == 10 + 11 + 12 + 13
        assert reg.counter("decode.kv.rows_window").value == 8 * 4
        spans = {name: [s for s in obs.spans() if s["name"] == name]
                 for name in ("mx.decode.prefill", "mx.decode.step")}
        assert [s["attrs"]["kv_rows_full"]
                for s in spans["mx.decode.prefill"]] == [10]
        assert sorted(s["attrs"]["kv_rows_full"]
                      for s in spans["mx.decode.step"]) == [11, 12, 13]
        for s in spans["mx.decode.step"]:
            assert s["attrs"]["kv_rows_window"] == 8
            assert s["attrs"]["moe_assignments"] == 2 * 4
            assert {"moe_assignments_held", "moe_expert_tokens_max", "n",
                    "bucket"} <= set(s["attrs"])
    finally:
        obs.disable_tracing()
        obs.trace.clear()       # the ring is the process's: leave none
        telemetry.reset("decode.")
        telemetry.disable()


def test_the_scopes_name_each_layers_kind(params):
    import jax
    eng = DecodeEngine(MODEL, params, **ENGINE_KW)
    _prefill, decode = eng._specs()
    text = jax.jit(eng._decode_impl).lower(*decode[2]).as_text(
        debug_info=True)
    for scope in ("h0/qkv", "h0/rope", "h0/kv_write", "h0/attention_window",
                  "h3/attention_full", "h3/proj", "h3/router", "h3/experts",
                  "mx.embed", "mx.lm_head", "mx.step_tokens"):
        assert scope in text, scope
    assert "h3/attention_window" not in text
    assert "h0/attention_full" not in text
    text = jax.jit(eng._prefill_impl).lower(
        *eng._specs()[0][8]).as_text(debug_info=True)
    assert "h1/attention_window" in text and "mx.kv_scatter" in text


def test_a_prefills_routed_experts_bear_a_scope_for_each_part(params):
    """The COMPILED prefill program (what a device trace's instruction
    names are looked up in) has instructions under ``h<i>/experts/sort``
    and, inside the chunk loop, ``gather``, ``matmul`` and ``combine``;
    and ``causal_attention``'s loops lie under the layer's own attention
    scope, which is why it has none of its own."""
    eng = DecodeEngine(MODEL, params, label="parts_test", **ENGINE_KW)
    eng.warmup()
    ops = obs.program_scopes()["parts_test:prefill:32"]["scopes"].values()
    for part in ("/h3/experts/sort/", "/h3/experts/while/body/gather/",
                 "/h3/experts/while/body/matmul/",
                 "/h3/experts/while/body/combine/"):
        assert any(part in op for op in ops), part
    # the attention's block loops: every instruction inside one bears
    # the scope of its layer's kind
    looped = [op for op in ops if "/while/body/" in op
              and "/experts/" not in op]
    assert looped and all("/attention_full/" in op
                          or "/attention_window/" in op for op in looped)
    # a decode step of few tokens takes the grouped matmul too (one chunk)
    ops = obs.program_scopes()["parts_test:decode:2"]["scopes"].values()
    assert any("/h3/experts/while/body/matmul/" in op for op in ops)


@pytest.mark.parametrize("chunk_rows", [16, 2048])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_scopes_inside_routed_experts_change_no_bit(dtype, chunk_rows,
                                                        monkeypatch):
    """A ``jax.named_scope`` is metadata: the same call with every scope
    taken out (the program as it was before the parts had names) gives
    the same bits."""
    import contextlib
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(0, 1, (40, 16)), dtype)
    gate, up = (jnp.asarray(rng.normal(0, 0.3, (8, 16, 24)), dtype)
                for _ in range(2))
    down = jnp.asarray(rng.normal(0, 0.3, (8, 24, 16)), dtype)
    chosen, weights = route_top_k(
        x, jnp.asarray(rng.normal(0, 1, (16, 8)), jnp.float32), None, 2,
        scoring="softmax")

    def run():
        return jax.jit(lambda x: routed_experts(
            x, chosen, weights, gate, up, down, 0,
            chunk_rows=chunk_rows))(x)

    y, counts = run()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare, bare_counts = run()
    assert np.array_equal(np.asarray(y), np.asarray(bare))
    assert np.array_equal(np.asarray(counts), np.asarray(bare_counts))
    assert np.asarray(counts).sum() == 80
