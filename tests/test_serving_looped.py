"""The decoder whose layers run several times over one set of weights, on
the serving path at a small size on the CPU in float32: its full forward
against the benchmark's plain reference (logits, the pass each position
exits at, the exit distribution), prefill then decode through the engine
and the paged cache against the full forward, the cache's layout by (pass,
layer) and its accounting, a planted fault in which every pass reads one
pass's rows, the decode program's size whatever the number of passes, and
the cache the three other specs still build."""
import numpy as np
import pytest

from mxnet_tpu import obs, telemetry
from mxnet_tpu.kernels import paged_attention as paged_attention_entry
from mxnet_tpu.serving.decode import (DecodeEngine, LatentMoEDecoder,
                                      LoopedDecoder, PagedKVCache, TinyGPT,
                                      WindowMoEDecoder)
from mxnet_tpu.serving.decode.looped import PASS_LOOP
from perfbench.families import ouro

LAYERS, PASSES, BS, BLOCKS = 3, 4, 4, 33
TINY = dict(vocab_size=128, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=4, head_dim=16, intermediate_size=96,
            num_hidden_layers=LAYERS, total_ut_steps=PASSES,
            early_exit_threshold=1.0, rope_theta=1e6, max_seq=64,
            dtype="float32")
MODEL = LoopedDecoder(**TINY)
ENGINE_KW = dict(prefill_buckets=(8, 16, 32), decode_buckets=(2, 4),
                 block_size=BS, num_blocks=BLOCKS, kv_dtype="float32")


def _cfg(**over):
    """The model's settings under the published config's keys, for the
    reference."""
    cfg = {k: v for k, v in TINY.items() if k not in ("max_seq", "dtype")}
    cfg.update(rms_norm_eps=1e-6, max_position_embeddings=64,
               serving_dtype="float32", **over)
    return cfg


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
# the full forward against the plain reference
# ---------------------------------------------------------------------

@pytest.mark.parametrize("threshold", [1.0, 0.5])
@pytest.mark.parametrize("passes", [1, 2, 4])
def test_the_full_forward_is_the_references(passes, threshold):
    """Logits, the pass each position exits at and the exit distribution,
    from weights the two sides share."""
    import jax.numpy as jnp
    cfg = _cfg(total_ut_steps=passes, early_exit_threshold=threshold)
    model, params = ouro.build_model(cfg, 11)
    tokens = np.random.RandomState(passes).randint(0, 128, (2, 24))
    logits, tau, p = model.full_logits(params, jnp.asarray(tokens, jnp.int32),
                                       with_exit=True)
    reference = ouro.make_exit_reference(cfg)
    ref_params = ouro.reference_params(params, cfg)
    seen = set()
    for row in range(2):
        want, want_tau, want_p = reference(ref_params,
                                           jnp.asarray(tokens[row]))
        np.testing.assert_allclose(logits[row], want, atol=3e-5)
        np.testing.assert_array_equal(tau[row], want_tau)
        np.testing.assert_allclose(p[row], want_p, atol=1e-6)
        np.testing.assert_allclose(np.asarray(p[row]).sum(-1), 1.0,
                                   atol=1e-6)
        seen |= set(np.asarray(tau[row]).tolist())
    if threshold == 1.0 or passes == 1:
        assert seen == {passes}         # no gate saturates: the last pass
    else:
        assert len(seen) > 1 and min(seen) < passes


def test_one_pass_is_a_plain_decoder_whatever_its_gate_says():
    import jax.numpy as jnp
    model = LoopedDecoder(**dict(TINY, total_ut_steps=1,
                                 early_exit_threshold=0.0))
    params = model.init_params(5)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 128, (1, 16)),
                         jnp.int32)
    logits, tau, p = model.full_logits(params, tokens, with_exit=True)
    other = dict(params, gate_w=-params["gate_w"],
                 gate_b=params["gate_b"] + 3.0)
    np.testing.assert_array_equal(logits, model.full_logits(other, tokens))
    assert np.asarray(tau).tolist() == [[1] * 16]
    np.testing.assert_array_equal(p, np.ones((1, 16, 1), np.float32))
    assert model.cache_passes == 1


def test_the_reference_sees_a_pass_and_a_gate():
    """One pass fewer is another model; so is another threshold."""
    import jax.numpy as jnp
    cfg = _cfg()
    model, params = ouro.build_model(cfg, 11)
    ref_params = ouro.reference_params(params, cfg)
    tokens = jnp.asarray(np.random.RandomState(4).randint(0, 128, 24))
    logits = ouro.make_exit_reference(cfg)(ref_params, tokens)[0]
    fewer, tau, p = ouro.make_exit_reference(
        cfg, ouro.CONTROL_ONE_PASS_FEWER)(ref_params, tokens)
    assert np.asarray(tau).tolist() == [PASSES - 1] * 24
    assert p.shape == (24, PASSES - 1)
    assert float(jnp.abs(fewer - logits).max()) > 0.05
    gated = ouro.make_exit_reference(dict(cfg, early_exit_threshold=0.5))(
        ref_params, tokens)[0]
    assert float(jnp.abs(gated - logits).max()) > 0.05


def test_the_passes_do_not_magnify_a_rounding():
    """In bfloat16 at 48 layers the program stays near the float32
    reference through four passes, because ``init_params`` draws the
    second norm of each sandwich about ``(2 L) ** -0.5``; the same
    weights with those norms about 1 lie several times further off, and
    the reference with one pass fewer further still."""
    import jax
    import jax.numpy as jnp
    layers = 48
    cfg = dict(vocab_size=512, hidden_size=128, num_attention_heads=2,
               num_key_value_heads=2, head_dim=64, intermediate_size=352,
               num_hidden_layers=layers, total_ut_steps=4,
               early_exit_threshold=1.0, rope_theta=1e6, rms_norm_eps=1e-6,
               max_position_embeddings=64, serving_dtype="bfloat16")
    model, params = ouro.build_model(cfg, 7)
    drawn = np.asarray(params["h5_ffn_out_norm"], np.float32)
    assert abs(drawn.mean() - (2 * layers) ** -0.5) < 0.01
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 512, (1, 32)),
                         jnp.int32)
    forward = jax.jit(model.full_logits)

    def gap(weights, precision="highest"):
        want = ouro.make_reference(cfg, precision)(
            ouro.reference_params(weights, cfg), tokens)
        return float(jnp.abs(forward(weights, tokens) - want).max())

    about_one = {k: v * (2 * layers) ** 0.5 if k.endswith("_out_norm")
                 else v for k, v in params.items()}
    sound = gap(params)
    assert sound < 0.4
    assert gap(about_one) > 4 * sound
    assert gap(params, ouro.CONTROL_ONE_PASS_FEWER) > 4 * sound


# ---------------------------------------------------------------------
# the cache: layers that are not the model's
# ---------------------------------------------------------------------

def test_the_model_declares_its_cache_layers_and_the_engine_builds_them(
        params):
    eng = DecodeEngine(MODEL, params, **ENGINE_KW)
    cache = eng.cache
    assert MODEL.num_layers == LAYERS and MODEL.cache_passes == PASSES
    assert (cache.layers, cache.passes) == (LAYERS, PASSES)
    assert cache.cache_layers == PASSES * LAYERS
    # a layer of weights owns one array a row, with every pass's blocks
    assert [a.shape for a in cache.slabs["k"]] \
        == [(PASSES * BLOCKS, BS, 4, 128)] * LAYERS
    # T x L x blocks x a block's bytes (16-wide heads in 128-lane tiles)
    assert cache.slab_bytes() \
        == PASSES * LAYERS * BLOCKS * 2 * BS * 4 * 128 * 4
    stats = cache.stats()
    assert stats["cache_layers"] == PASSES * LAYERS
    assert stats["kv_bytes_per_token"] == cache.kv_bytes_per_token() \
        == PASSES * LAYERS * 2 * 4 * 16 * 4
    assert stats["total_blocks"] == BLOCKS - 1
    # the tables know nothing of passes: max_seq / block_size wide
    assert eng._table_widths == {"full": 16}


def test_the_allocator_does_not_learn_about_passes(params):
    """Admission, free and the gauges count a sequence's blocks once,
    whatever the number of passes."""
    used = []
    for passes in (1, PASSES):
        model = LoopedDecoder(**dict(TINY, total_ut_steps=passes))
        cache = DecodeEngine(model, params, **ENGINE_KW).cache
        tables = [cache.allocate(n) for n in (5, 17, 30)]
        after_admission = cache.blocks_in_use()
        assert cache.can_admit(4 * (cache.free_blocks()))
        assert not cache.can_admit(4 * (cache.free_blocks() + 1))
        cache.free(tables[1])
        used.append((after_admission, cache.blocks_in_use(),
                     [t.blocks for t in tables], cache.free_blocks()))
        for t in tables:
            cache.free(t)
        assert cache.blocks_in_use() == 0
    assert used[0] == used[1]
    assert used[0][:2] == (2 + 5 + 8, 2 + 8)


def test_passes_are_at_least_one():
    from mxnet_tpu.base import MXNetError
    with pytest.raises(MXNetError, match="passes"):
        PagedKVCache(2, {"k": (2, 8)}, 4, 9, passes=0)


_SPECS = {
    "gpt": (lambda: TinyGPT(vocab_size=64, units=32, num_layers=2,
                            num_heads=2, max_seq=32), {}),
    "latent_moe": (lambda: LatentMoEDecoder(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, q_lora_rank=16, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        intermediate_size=48, moe_intermediate_size=16,
        n_routed_experts=4, num_experts_per_tok=2, n_shared_experts=1,
        first_k_dense_replace=1, routed_scaling_factor=1.0,
        rope_theta=10000, first_expert=0, n_held=4, max_seq=32,
        dtype="float32"), {}),
    "window_moe": (lambda: WindowMoEDecoder(
        vocab_size=64, hidden_size=32, num_attention_heads=4,
        num_key_value_heads=2, head_dim=8,
        layer_types=["sliding_attention", "full_attention"],
        sliding_window=8, rope_parameters={
            "full_attention": {"rope_type": "default", "rope_theta": 1e4},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 1e4}},
        moe_intermediate_size=16, num_experts=4, num_experts_per_tok=2,
        max_seq=32, dtype="float32"), {"window_blocks": 7}),
}


@pytest.mark.parametrize("spec", sorted(_SPECS))
def test_a_spec_without_the_declaration_builds_the_cache_it_built(spec):
    """``num_layers`` cache layers, ``num_blocks`` blocks a slab: the
    three specs that run their layers once."""
    import jax
    make, extra = _SPECS[spec]
    model = make()
    assert not hasattr(model, "cache_passes")
    params = jax.eval_shape(model.init_params, 0) \
        if spec == "gpt" else model.init_params(0)
    eng = DecodeEngine(model, params, prefill_buckets=(8,),
                       decode_buckets=(2,), block_size=4, num_blocks=9,
                       kv_dtype="float32", **extra)
    cache = eng.cache
    assert cache.passes == 1 and cache.cache_layers == model.num_layers
    for name in model.cache_rows():
        assert len(cache.slabs[name]) == model.num_layers
        assert {a.shape[0] for a in cache.slabs[name]} \
            <= {9, extra.get("window_blocks", 9)}
    assert cache.stats()["cache_layers"] == model.num_layers


# ---------------------------------------------------------------------
# through the engine
# ---------------------------------------------------------------------

def _engine_logits(params, prompts, max_new, model=MODEL):
    """Prefill then decode through the engine's own programs and the
    paged cache, by hand (the engine's loop keeps no logits): per request
    the logits of every generated position."""
    import jax
    eng = DecodeEngine(model, params, **ENGINE_KW)
    prefill = jax.jit(eng._prefill_impl)
    decode = jax.jit(lambda p, s, t, pos, tb, live: model.decode_logits(
        p, s, t, pos, tb, BS, live))
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
        (first, stats), slabs = prefill(params, slabs, tokens,
                                        eng._tables((r,)),
                                        np.int32(len(prompt)))
        assert int(stats["kv_rows"]) == len(prompt)
        assert int(stats["ut_passes"]) == model.passes
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
        assert int(stats["kv_rows"]) == sum(len(r.seq) for r in reqs)
        assert int(stats["ut_passes"]) == model.passes * n
        assert int(stats["exit_step_sum"]) == model.passes * n
        assert int(stats["exit_early"]) == 0
        for i, r in enumerate(reqs):
            r.logits.append(np.asarray(logits[i]))
            r.seq.append(int(nxt[i]))
    return reqs, slabs, eng


def _gap_to_the_reference(params, reqs, max_new):
    import jax.numpy as jnp
    cfg = _cfg()
    reference = ouro.make_exit_reference(cfg)
    ref_params = ouro.reference_params(params, cfg)
    worst = 0.0
    for r in reqs:
        full = np.asarray(reference(ref_params, jnp.asarray(r.seq))[0])
        start = len(r.seq) - max_new
        for k, logits in enumerate(r.logits):
            worst = max(worst, float(np.abs(logits - full[start + k]).max()))
    return worst


PROMPTS = (5, 13, 30)


def test_prefill_then_decode_equals_the_references_full_forward(params):
    """Three prompts over several blocks, 20 steps each: every step's
    logits are the plain reference's at that position."""
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 128, n).tolist() for n in PROMPTS]
    reqs, _slabs, _eng = _engine_logits(params, prompts, 21)
    assert _gap_to_the_reference(params, reqs, 21) < 5e-5


@pytest.mark.parametrize("fault", ["last_pass", "one_entry"])
def test_a_cache_that_is_not_per_pass_fails_the_comparison(
        params, monkeypatch, fault):
    """The planted fault: every pass's query reads the LAST pass's rows
    (``last_pass``), or every pass writes and reads pass 0's entry
    (``one_entry``).  Either is far outside the comparison's tolerance."""
    from mxnet_tpu.serving.decode import looped
    real = paged_attention_entry.paged_attention
    to_pass = (PASSES - 1) * BLOCKS if fault == "last_pass" else 0

    def read_one_pass(q, k_cache, v_cache, tables, ctx, **kw):
        return real(q, k_cache, v_cache, tables % BLOCKS + to_pass, ctx,
                    **kw)

    monkeypatch.setattr(paged_attention_entry, "paged_attention",
                        read_one_pass)
    if fault == "one_entry":
        for name in ("write_tokens", "write_prompt"):
            write = getattr(looped, name)
            monkeypatch.setattr(
                looped, name,
                lambda slab, rows, table, *a, _write=write:
                _write(slab, rows, table % BLOCKS, *a))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 128, n).tolist() for n in PROMPTS]
    reqs, _slabs, _eng = _engine_logits(params, prompts, 21)
    assert _gap_to_the_reference(params, reqs, 21) > 100 * 5e-5


def test_pass_t_of_layer_l_lives_at_t_times_num_blocks(params):
    """What the prefill wrote into the cache is the plain reference's K
    and V, cache layer ``t * L + l`` in layer ``l``'s slab at block ``t *
    num_blocks + table[p // bs]``; the other passes' blocks of that table
    hold other rows."""
    import jax.numpy as jnp
    prompt = np.random.RandomState(2).randint(0, 128, 13).tolist()
    reqs, slabs, eng = _engine_logits(params, [prompt], 1)
    cfg, rows = _cfg(), []
    ouro.make_exit_reference(cfg)(ouro.reference_params(params, cfg),
                                  jnp.asarray(prompt), rows)
    assert len(rows) == PASSES * LAYERS
    table = reqs[0].table.blocks
    for which, name in enumerate(("k", "v")):
        for t in range(PASSES):
            for layer in range(LAYERS):
                slab = np.asarray(slabs[name][layer])
                want = np.asarray(rows[t * LAYERS + layer][which])
                for pos in range(13):
                    got = slab[t * BLOCKS + table[pos // BS], pos % BS,
                               :, :16]
                    np.testing.assert_allclose(got, want[pos], atol=1e-5)
        first = [np.asarray(slabs[name][0])[t * BLOCKS + table[0], 0, :, :16]
                 for t in range(PASSES)]
        assert all(np.abs(first[0] - other).max() > 1e-3
                   for other in first[1:])
    assert eng.cache.blocks_in_use() == 4       # 14 tokens, once


def test_engine_tokens_match_the_oracle_solo_and_joined_mid_batch(engine,
                                                                  params):
    """Greedy tokens are the full forward's argmax at every position of
    the sequence they make, and a stream's tokens do not depend on who
    joins its batch (the engine's bit-identity property)."""
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
    assert engine.cache.blocks_in_use() == 0


def test_an_early_exit_is_counted_and_skips_no_pass(params):
    """At a threshold the gates reach, ``tau`` is under the last pass for
    some slots; every pass is run all the same."""
    import jax.numpy as jnp
    model = LoopedDecoder(**dict(TINY, early_exit_threshold=0.5))
    eng = DecodeEngine(model, params, **ENGINE_KW)
    eng.warmup()
    eng.start()
    telemetry.enable()
    telemetry.reset("decode.")
    try:
        prompt = list(range(1, 11))
        out = eng.submit(prompt, 8).tokens()
        seq = jnp.asarray([prompt + out], jnp.int32)
        logits, tau, _p = model.full_logits(params, seq, with_exit=True)
        assert out == np.asarray(logits)[0, 9:17].argmax(-1).tolist()
        tau = np.asarray(tau)[0, 9:17]
        reg = telemetry.registry()
        assert reg.counter("decode.ut.passes").value == PASSES * 8
        assert reg.counter("decode.ut.exit_step_sum").value == tau.sum()
        assert reg.counter("decode.ut.exit_early").value \
            == (tau < PASSES).sum() > 0
    finally:
        telemetry.reset("decode.")
        telemetry.disable()
        eng.close(drain=False)


def test_the_engine_puts_the_pass_counts_on_its_spans(engine):
    telemetry.enable()
    telemetry.reset("decode.")
    obs.trace.clear()
    obs.enable_tracing()
    try:
        assert len(engine.submit(list(range(1, 11)), 4).tokens()) == 4
        reg = telemetry.registry()
        # a prompt of 10 and three decode steps at contexts 11, 12, 13
        assert reg.counter("decode.ut.passes").value == PASSES * 4
        assert reg.counter("decode.ut.exit_step_sum").value == PASSES * 4
        assert reg.counter("decode.ut.exit_early").value == 0
        assert reg.counter("decode.kv.rows").value == 10 + 11 + 12 + 13
        spans = {name: [s for s in obs.spans() if s["name"] == name]
                 for name in ("mx.decode.prefill", "mx.decode.step")}
        assert [s["attrs"]["kv_rows"]
                for s in spans["mx.decode.prefill"]] == [10]
        assert sorted(s["attrs"]["kv_rows"]
                      for s in spans["mx.decode.step"]) == [11, 12, 13]
        for s in spans["mx.decode.step"]:
            assert s["attrs"]["ut_passes"] == PASSES * s["attrs"]["n"]
            assert s["attrs"]["exit_step_sum"] == PASSES
            assert s["attrs"]["exit_early"] == 0
    finally:
        obs.disable_tracing()
        obs.trace.clear()       # the ring is the process's: leave none
        telemetry.reset("decode.")
        telemetry.disable()


# ---------------------------------------------------------------------
# the programs
# ---------------------------------------------------------------------

def _compiled_decode(passes, params):
    import jax
    model = LoopedDecoder(**dict(TINY, total_ut_steps=passes))
    eng = DecodeEngine(model, params, **ENGINE_KW)
    _prefill, decode = eng._specs()
    compiled = jax.jit(eng._decode_impl,
                       donate_argnums=eng._DONATED).lower(
                           *decode[4]).compile()
    return eng, compiled


# a loop's plumbing, which computes nothing: its carry is a tuple that the
# body takes apart and puts together again
_PLUMBING = ("parameter", "get-tuple-element", "tuple", "constant", "bitcast")


def _instructions(compiled):
    """Instructions of the optimized HLO that compute something."""
    import re
    ops = re.findall(r"^\s*(?:ROOT )?%\S+ = \S+ ([\w\-]+)\(",
                     compiled.as_text(), re.M)
    return sum(1 for op in ops if op not in _PLUMBING)


def test_the_passes_are_a_loop_of_the_program(params):
    """The decode program at four passes is the program at one: a loop,
    not four copies; it keeps no slab-sized temporary and writes every
    byte of the slabs in place."""
    one, four = (_compiled_decode(t, params) for t in (1, PASSES))
    assert _instructions(four[1]) <= 1.25 * _instructions(one[1])
    eng, compiled = four
    slab = PASSES * BLOCKS * BS * 4 * 128 * 4
    assert eng.cache.slab_bytes() == 2 * LAYERS * slab
    stats = compiled.memory_analysis()
    if stats is not None:
        assert stats.alias_size_in_bytes == eng.cache.slab_bytes()
        assert stats.temp_size_in_bytes < slab
    assert " while(" in compiled.as_text()


def test_the_engines_programs_alias_the_whole_cache(params):
    eng = DecodeEngine(MODEL, params, **ENGINE_KW)
    eng.warmup()
    for kind, buckets in (("prefill", eng.prefill_buckets),
                          ("decode", eng.decode_buckets)):
        for b in buckets:
            mem = eng.program_memory(kind, b)
            if mem is not None:
                assert mem["aliased_bytes"] == eng.cache.slab_bytes(), \
                    (kind, b, mem)
            head = eng._programs.get((kind, b)).as_text().split("\n")[0]
            assert head.count("-alias)") == 2 * LAYERS


def test_the_scopes_name_the_loop_and_each_layers_parts(params):
    import jax
    eng = DecodeEngine(MODEL, params, **ENGINE_KW)
    prefill, decode = eng._specs()
    text = jax.jit(eng._decode_impl).lower(*decode[2]).as_text(
        debug_info=True)
    for scope in (PASS_LOOP, "h0/qkv", "h0/rope", "h0/kv_write",
                  "h0/attention", "h2/proj", "h2/mlp", "mx.final_norm",
                  "mx.exit_gate", "mx.embed", "mx.lm_head",
                  "mx.step_tokens"):
        assert scope in text, scope
    assert "h%d/" % LAYERS not in text      # one stack of layers, once
    text = jax.jit(eng._prefill_impl).lower(*prefill[8]).as_text(
        debug_info=True)
    assert PASS_LOOP in text and "h1/kv_write" in text
    assert "mx.kv_scatter" not in text      # written inside the loop
