"""Kimi Linear's decoder on the serving path, at a small size on the CPU in
float32: the chunked scan against the token recurrence, the KDA decode
kernel body against its XLA reference, a cache that keeps a STATE a
sequence beside latent rows a token in one manager, and the engine
serving it: prefill then decode against the full forward, a reused
state row, padded slots, counts, scopes and the cache written in place."""
import numpy as np
import pytest

from mxnet_tpu import kernels, obs, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops.pallas.kda_decode import (kda_decode_pallas,
                                             kda_decode_reference)
from mxnet_tpu.serving.decode import (DecodeEngine, LatentMoEDecoder,
                                      LinearLatentMoEDecoder, PagedKVCache)
from mxnet_tpu.serving.decode.kvcache import SCRATCH_BLOCK, STATE
from mxnet_tpu.serving.decode.linear_moe import chunked_delta_rule

LINEAR = {"kda_layers": [1, 2, 3, 5], "full_attn_layers": [4],
          "num_heads": 2, "head_dim": 8, "short_conv_kernel_size": 4}
TINY = dict(vocab_size=128, hidden_size=32, num_hidden_layers=5,
            num_attention_heads=2, kv_lora_rank=8, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, intermediate_size=48,
            moe_intermediate_size=16, n_routed_experts=8,
            num_experts_per_tok=2, n_shared_experts=1,
            first_k_dense_replace=1, routed_scaling_factor=2.446,
            linear_attn_config=LINEAR, first_expert=2, n_held=4,
            max_seq=64, dtype="float32", chunk=8)
MODEL = LinearLatentMoEDecoder(**TINY)
ENGINE_KW = dict(prefill_buckets=(8, 16, 32), decode_buckets=(2, 4),
                 block_size=4, num_blocks=65)


@pytest.fixture(scope="module")
def params():
    return MODEL.init_params(5)


def _greedy(model, params, prompt, max_new):
    """Greedy decode by one FULL forward a token: the oracle."""
    import jax.numpy as jnp
    tokens, out = list(prompt), []
    for _ in range(max_new):
        logits = model.full_logits(params, jnp.asarray([tokens], jnp.int32))
        out.append(int(jnp.argmax(logits[0, -1])))
        tokens.append(out[-1])
    return out


def _recurrence(q, k, v, g, beta, state):
    """The gated delta rule a token at a time, plainly."""
    q, k, v, g, beta, state = (np.asarray(a, np.float64)
                               for a in (q, k, v, g, beta, state))
    out = np.zeros(v.shape)
    for t in range(q.shape[1]):
        state = np.exp(g[:, t])[..., None] * state
        pred = np.einsum("bhkv,bhk->bhv", state, k[:, t])
        state = state + k[:, t, ..., None] \
            * (beta[:, t, :, None] * (v[:, t] - pred))[..., None, :]
        out[:, t] = np.einsum("bhkv,bhk->bhv", state, q[:, t])
    return out, state


def _unit(a):
    return a / np.linalg.norm(a, axis=-1, keepdims=True)


# ---------------------------------------------------------------------
# the chunked scan
# ---------------------------------------------------------------------

@pytest.mark.parametrize("decay", [0.01, 1.0, 500.0])
@pytest.mark.parametrize("t,chunk", [(16, 8), (37, 8), (5, 16)])
def test_the_chunked_scan_is_the_token_recurrence(decay, t, chunk):
    """Across chunk edges, in a chunk cut short, and under a decay of 500
    a token (where ``exp(-b)`` would overflow float32 within a chunk)."""
    import jax.numpy as jnp
    rng = np.random.default_rng(int(decay * 10) + t)
    b, h, dk, dv = 2, 3, 8, 4
    q = _unit(rng.normal(size=(b, t, h, dk)))
    k = _unit(rng.normal(size=(b, t, h, dk)))
    v = rng.normal(size=(b, t, h, dv))
    g = -decay * rng.uniform(size=(b, t, h, dk))
    beta = rng.uniform(size=(b, t, h))
    s0 = rng.normal(size=(b, h, dk, dv))
    want_o, want_s = _recurrence(q, k, v, g, beta, s0)
    got_o, got_s = chunked_delta_rule(
        *(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta, s0)),
        chunk=chunk)
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(np.asarray(got_o), want_o, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_s), want_s, atol=2e-5)


def test_padding_with_no_input_and_no_decay_leaves_the_state():
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    b, t, h, d = 1, 11, 2, 8
    args = [_unit(rng.normal(size=(b, t, h, d))),
            _unit(rng.normal(size=(b, t, h, d))),
            rng.normal(size=(b, t, h, d)),
            -rng.uniform(size=(b, t, h, d)), rng.uniform(size=(b, t, h))]
    _o, short = chunked_delta_rule(
        *(jnp.asarray(a[:, :7], jnp.float32) for a in args),
        jnp.zeros((b, h, d, d)), chunk=4)
    args[3][:, 7:] = 0.0                    # g
    args[4][:, 7:] = 0.0                    # beta
    _o, padded = chunked_delta_rule(
        *(jnp.asarray(a, jnp.float32) for a in args),
        jnp.zeros((b, h, d, d)), chunk=4)
    np.testing.assert_allclose(np.asarray(padded), np.asarray(short),
                               atol=1e-6)


# ---------------------------------------------------------------------
# the decode kernel
# ---------------------------------------------------------------------

def _kernel_args(rng, slots=4, heads=3, dk=128, dv=16, rows=6,
                 dtype=np.float32):
    import jax.numpy as jnp
    q, k = (jnp.asarray(_unit(rng.normal(size=(slots, heads, dk))),
                        jnp.float32) for _ in range(2))
    beta = rng.uniform(size=(slots, heads, 1))
    v = jnp.asarray(rng.normal(size=(slots, heads, dv)), jnp.float32)
    g = jnp.asarray(-rng.uniform(size=(slots, heads, dk)), jnp.float32)
    state = jnp.asarray(rng.normal(size=(rows, heads, dv, dk)), dtype)
    return q, k, jnp.asarray(beta, jnp.float32) * k, g, v, state


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_kda_kernel_body_equals_its_xla_reference(dtype):
    """Two live slots in rows 4 and 1, two padded slots on the scratch
    row: the live rows turn as the reference turns them, the rows no slot
    names are untouched."""
    import jax.numpy as jnp
    q, k, kb, g, v, state = _kernel_args(np.random.default_rng(0),
                                         dtype=dtype)
    rows = jnp.asarray([4, 1, 0, 0], jnp.int32)
    want_o, want_s = kda_decode_reference(q, k, kb, g, v, state, rows)
    got_o, got_s = kda_decode_pallas(q, k, kb, g, v, state, rows,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(got_o)[:2],
                               np.asarray(want_o)[:2], atol=1e-5)
    for r in (1, 4):
        np.testing.assert_allclose(np.asarray(got_s[r], np.float32),
                                   np.asarray(want_s[r], np.float32),
                                   atol=1e-5)
    for r in (2, 3, 5):
        assert np.array_equal(np.asarray(got_s[r]), np.asarray(state[r]))
    # the plain rule for slot 0, head 0, in float64
    t = np.asarray(state[4, 0], np.float64) \
        * np.exp(np.asarray(g[0, 0], np.float64))
    u = np.asarray(v[0, 0]) - t @ np.asarray(k[0, 0])
    t = t + np.outer(u, np.asarray(kb[0, 0]))
    np.testing.assert_allclose(np.asarray(want_o[0, 0]),
                               t @ np.asarray(q[0, 0]), atol=1e-4)


def test_kda_decode_is_a_registry_entry():
    from mxnet_tpu.kernels import registry
    from mxnet_tpu.kernels.kda_decode import kda_decode
    assert "kda_decode" in kernels.list_kernels()
    assert not registry.choose("kda_decode", heads=32, key=128, value=128)
    forced = registry.choose("kda_decode", force=True, heads=32, key=128,
                             value=128)
    assert forced.use_pallas and forced.interpret
    assert not registry.choose("kda_decode", force=True, heads=2, key=8,
                               value=8)
    q, k, kb, g, v, state = _kernel_args(np.random.default_rng(2))
    import jax.numpy as jnp
    rows = jnp.asarray([3, 2, 5, 0], jnp.int32)
    a = kda_decode(q, k, kb, g, v, state, rows, use_pallas=True)
    b = kda_decode(q, k, kb, g, v, state, rows, use_pallas=False)
    np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]),
                               atol=1e-5)


# ---------------------------------------------------------------------
# the cache: state layers beside table layers, one manager
# ---------------------------------------------------------------------

def test_the_model_declares_state_layers_and_the_cache_lays_them_out(
        params):
    assert MODEL.cache_layers() == ["state"] * 3 + ["full", "state"]
    assert MODEL.cache_states() == {"kda_state": ((2, 8, 8), "float32"),
                                    "kda_conv": ((2, 128), "float32")}
    eng = DecodeEngine(MODEL, params, **ENGINE_KW)
    cache = eng.cache
    # only the one MLA layer keeps latent rows
    assert [a.shape for a in cache.slabs["latent"]] == [(65, 4, 128)]
    assert [a.shape for a in cache.slabs["kda_state"]] == [(5, 2, 8, 8)] * 4
    # 3 inputs x 3 parts x 16 channels = 144 values in rows of 128 lanes
    assert [a.shape for a in cache.slabs["kda_conv"]] == [(5, 2, 128)] * 4
    assert eng._table_widths == {"full": 16, STATE: 1}
    stats = cache.stats()
    assert stats["cache_layers"] == 1 and stats["state_layers"] == 4
    assert stats["state_rows_total"] == 4      # slots; row 0 is scratch
    assert stats["state_bytes"] == 4 * 5 * (2 * 8 * 8 + 256) * 4
    assert cache.kv_bytes_per_token() == 12 * 4


def test_a_state_row_goes_and_comes_back_with_its_blocks():
    cache = PagedKVCache(2, {"latent": (12,)}, 4, 9, kinds=["state", "full"],
                         states={"s": ((2, 2), "float32")}, state_rows=3)
    telemetry.enable()
    telemetry.reset("kvcache.")
    try:
        a = cache.allocate(10)
        b = cache.allocate(3)
        assert len(a.blocks) == 3 and len(a.state) == 1
        assert {a.state[0], b.state[0]} == {1, 2}
        gauge = telemetry.registry().gauge
        assert gauge("kvcache.state_rows_in_use").value == 2
        assert gauge("kvcache.blocks_in_use").value == 4   # blocks only
        free = cache.free_blocks()
        with pytest.raises(MXNetError, match="state"):
            cache.allocate(4)           # blocks enough, no state row
        assert cache.free_blocks() == free          # nothing taken
        assert not cache.can_admit(4)
        cache.free(a)
        c = cache.allocate(4)
        assert c.state == a.state
        assert list(cache.padded_table(c, 1, STATE)) == c.state
        assert gauge("kvcache.state_rows_in_use").value == 2
    finally:
        telemetry.reset("kvcache.")
        telemetry.disable()


def test_a_cache_without_state_layers_has_no_state_pool():
    cache = PagedKVCache(2, {"latent": (12,)}, 4, 9)
    assert cache.state_layers == 0 and cache.state_rows is None
    assert cache.blocks_needed(5) == {"full": 2}
    assert "state_bytes" not in cache.stats()
    assert cache.allocate(5).state == []
    with pytest.raises(MXNetError, match="state"):
        PagedKVCache(2, {"latent": (12,)}, 4, 9, kinds=["state", "full"])


# ---------------------------------------------------------------------
# the spec: latent attention without positions, prefill then decode
# ---------------------------------------------------------------------

def test_latent_attention_without_positions_rotates_nothing():
    import jax.numpy as jnp
    assert MODEL.inv_freq is None and MODEL.q_rank is None
    x = jnp.arange(12.0).reshape(3, 4)
    assert MODEL._rotate(x, jnp.arange(3)) is x
    shapes = MODEL.param_shapes()
    assert shapes["h3_wq"][0] == (32, 2 * 12)           # one projection
    assert "h3_wqa" not in shapes and "h0_wkva" not in shapes
    assert shapes["h0_wq"][0] == (32, 16) and shapes["h0_A_log"][0] == (2,)
    with pytest.raises(MXNetError, match="every one"):
        LinearLatentMoEDecoder(**dict(TINY, linear_attn_config=dict(
            LINEAR, full_attn_layers=[4, 5])))


def test_the_kda_gates_are_drawn_as_published(params):
    a = np.exp(np.asarray(params["h0_A_log"]))
    assert ((1 <= a) & (a < 16)).all()
    dt = np.log1p(np.exp(np.asarray(params["h0_dt_bias"])))    # softplus
    assert ((0.001 <= dt * 1.0001) & (dt < 0.1 * 1.0001)).all()


def test_prefill_then_decode_equals_the_full_forward_on_logits(params):
    """The prompt through ``prefill_cache`` (the chunked scan writes each
    KDA layer's final state, the MLA layer its latent rows), then decode
    steps through the cache: the logits of every position are the full
    forward's.  Slot 1 is padding in every step."""
    import jax.numpy as jnp
    eng = DecodeEngine(MODEL, params, **ENGINE_KW)
    seq = [5, 9, 2, 77, 31, 4, 8, 60, 3, 11, 17, 90, 42]
    n = 6
    req = type("R", (), {})()
    req.table = eng.cache.allocate(len(seq))
    table = eng._tables((req,))
    tokens = np.zeros((1, 16), np.int32)
    tokens[0, :n] = seq[:n]
    slabs = eng.cache.slabs
    logits, slabs, stats = MODEL.prefill_cache(
        params, slabs, jnp.asarray(tokens), n - 1, table, 4)
    full = np.asarray(MODEL.full_logits(params, jnp.asarray([seq])))[0]
    np.testing.assert_allclose(np.asarray(logits), full[n - 1], atol=2e-4)
    assert int(stats["scan_tokens"]) == n * 4
    tables = eng._tables((req,), 2)
    for p in range(n, len(seq)):
        _next, step_logits, slabs, stats = MODEL.decode_logits(
            params, slabs, jnp.asarray([seq[p], 0]), jnp.asarray([p, 0]),
            tables, 4, jnp.asarray([True, False]))
        np.testing.assert_allclose(np.asarray(step_logits[0]), full[p],
                                   atol=2e-4)
        assert int(stats["state_rows"]) == 4
    # the decode steps wrote every KDA layer's row and the padded slot
    # the scratch row only
    row = req.table.state[0]
    for arr in slabs["kda_state"]:
        assert np.abs(np.asarray(arr[row])).sum() > 0
        assert not np.asarray(arr)[[r for r in range(1, 5)
                                    if r != row]].any()


# ---------------------------------------------------------------------
# the engine
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
    prompts = [[3, 14, 15, 92, 65, 35], [27, 18, 28],
               [1, 2, 3, 5, 8, 13, 21, 34, 55, 89, 14, 33]]
    want = [_greedy(MODEL, params, p, 7) for p in prompts]
    assert engine.submit(prompts[0], 7).tokens() == want[0]      # solo
    first = engine.submit(prompts[0], 7)
    assert next(first) == want[0][0]     # it is decoding: the rest join
    others = [engine.submit(p, 7) for p in prompts[1:]]
    assert [next(first)] + list(first) == want[0][1:]
    assert [s.tokens() for s in others] == want[1:]
    assert engine.cache.blocks_in_use() == 0
    assert engine.cache.stats()["state_rows_in_use"] == 0


def test_a_reused_state_row_reads_like_a_fresh_engine(params):
    """ONE state row for every sequence in turn: what a sequence left in
    the row (its state and its convolution's inputs) is gone once the
    next one's prefill has written it."""
    eng = DecodeEngine(MODEL, params,
                       **dict(ENGINE_KW, decode_buckets=(1,)))
    eng.warmup()
    eng.start()
    try:
        for prompt in ([40, 41, 42, 43, 44, 45, 46, 47, 48], [7, 3]):
            stream = eng.submit(prompt, 6)
            assert eng.cache.stats()["state_rows_in_use"] == 1
            assert stream.tokens() == _greedy(MODEL, params, prompt, 6)
    finally:
        eng.close(drain=True)
    # a row a slot, and the scratch row
    assert eng.cache.stats()["state_rows_total"] == 1
    assert eng.cache.state_rows == 2


def test_the_cache_is_written_in_place(params):
    eng = DecodeEngine(MODEL, params, **ENGINE_KW)
    eng.warmup()
    slab_bytes = eng.cache.slab_bytes()
    assert slab_bytes == 65 * 4 * 128 * 4 + eng.cache.state_bytes()
    for kind, buckets in (("prefill", eng.prefill_buckets),
                          ("decode", eng.decode_buckets)):
        for b in buckets:
            mem = eng.program_memory(kind, b)
            if mem is not None:
                assert mem["aliased_bytes"] == slab_bytes, (kind, b, mem)


def test_the_engine_counts_state_rows_and_scan_tokens(engine):
    telemetry.enable()
    telemetry.reset("decode.")
    obs.trace.clear()
    obs.enable_tracing()
    try:
        assert len(engine.submit([9, 8, 7, 6, 5], 4).tokens()) == 4
        reg = telemetry.registry()
        # 5 prompt tokens through 4 KDA layers, 3 decode steps of 1 slot
        assert reg.counter("decode.linear.scan_tokens").value == 5 * 4
        assert reg.counter("decode.linear.state_rows").value == 3 * 4
        spans = {name: [s for s in obs.spans() if s["name"] == name]
                 for name in ("mx.decode.prefill", "mx.decode.step")}
        assert spans["mx.decode.prefill"][0]["attrs"]["scan_tokens"] == 20
        assert [s["attrs"]["state_rows"] for s in spans["mx.decode.step"]] \
            == [4, 4, 4]
        # one MLA layer; the stream's 6..8 tokens and the padded slot are
        # one page group each
        assert [s["attrs"]["latent_grid_steps"]
                for s in spans["mx.decode.step"]] == [2, 2, 2]
        assert reg.counter("decode.latent.grid_steps").value == 3 * 2
        assert "moe_assignments" in spans["mx.decode.step"][0]["attrs"]
    finally:
        obs.disable_tracing()
        telemetry.reset("decode.")
        telemetry.disable()


def test_the_scopes_name_the_linear_attention_parts(params):
    import jax
    eng = DecodeEngine(MODEL, params, **ENGINE_KW)
    prefill, decode = eng._specs()
    for fn, specs in ((eng._decode_impl, decode[2]),
                      (eng._prefill_impl, prefill[8])):
        text = jax.jit(fn).lower(*specs).as_text(debug_info=True)
        for part in ("proj", "conv", "gate", "recurrence", "norm"):
            assert "h0/linear_attention/" + part in text, part
        assert "h3/kv_latent" in text and "h3/attention" in text
        assert "h3/linear_attention" not in text
        assert "h1/experts" in text and "h0/mlp" in text


def test_a_latent_decoder_keeps_its_rotation_and_low_rank_query():
    """The accepted latent decoder is untouched by the options it gained:
    rotated, with the low-rank query pair."""
    spec = LatentMoEDecoder(
        vocab_size=64, hidden_size=32, num_hidden_layers=1,
        num_attention_heads=2, q_lora_rank=8, kv_lora_rank=8,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        intermediate_size=48, moe_intermediate_size=16, n_routed_experts=4,
        num_experts_per_tok=2, n_shared_experts=1, first_k_dense_replace=1,
        routed_scaling_factor=1.0, rope_theta=10000, max_seq=32)
    assert spec.inv_freq is not None and spec.q_rank == 8
    assert {"h0_wqa", "h0_q_norm", "h0_wqb"} <= set(spec.param_shapes())
    assert "h0_wq" not in spec.param_shapes()
    assert not hasattr(spec, "cache_states")
    assert SCRATCH_BLOCK == 0
