"""``kimi_k2_serve_closed32``: the configuration's file against the
published config, the family's plain reference against the program at a
tiny size on the CPU in float32 (logits, full forward and prefill-then-
decode through the engine's own programs), the shares of an
expert-parallel deployment adding up to the whole layer, the shape
functions by hand, and the readers (its own two and the accepted roofline
reader) on hand-built runs."""
import json
import os
import threading
import types

import numpy as np
import pytest

import _entries
from perfbench.families import kimi_k2
from perfbench.harness import program_trace, xplane
from perfbench.harness.spec import Cell, SpecError, sized
from perfbench.harness.traffic import length_population

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "kimi_k2_serve_closed32"
# https://huggingface.co/moonshotai/Kimi-K2-Instruct/blob/main/config.json
PUBLISHED = {
    "first_k_dense_replace": 1, "hidden_size": 7168,
    "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 384,
    "n_shared_experts": 1, "num_attention_heads": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 61,
    "num_key_value_heads": 64, "num_nextn_predict_layers": 0,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 50000,
    "routed_scaling_factor": 2.827, "topk_group": 1, "v_head_dim": 128,
    "vocab_size": 163840,
    "rope_scaling": {"beta_fast": 1, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"}}


def _config(rehearse=False):
    with open(os.path.join(REPO, "perfbench", "configs",
                           "kimi-k2-instruct-ep32.json")) as f:
        return sized(json.load(f), rehearse)


def _tiny(**over):
    """The rehearsal's size; ``uncut=True`` gives one chip every expert."""
    cfg = _config(rehearse=True)
    if over.pop("uncut", False):
        cfg["n_routed_experts"] = cfg["published"]["n_routed_experts"]
        cfg["deployment"] = dict(cfg["deployment"], expert_rank=0)
    if "rank" in over:
        cfg["deployment"] = dict(cfg["deployment"],
                                 expert_rank=over.pop("rank"))
    cfg.update(over)
    return cfg


# ---------------------------------------------------------------------
# the configuration's file, the mix and the cell
# ---------------------------------------------------------------------

def test_every_published_number_is_in_the_file_and_the_cut_is_named():
    cfg = _config()
    reduced = {"num_hidden_layers": 6, "n_routed_experts": 12,
               "vocab_size": 20480, "max_position_embeddings": 16384}
    assert cfg["reduced"] == list(reduced)
    for key, value in PUBLISHED.items():
        assert cfg[key] == reduced.get(key, value), key
    assert cfg["published"] == {k: PUBLISHED[k] for k in reduced}
    # the floors of a cut: four expert layers after the dense ones, eight
    # experts, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] * cfg["n_routed_experts"] \
        == PUBLISHED["n_routed_experts"]
    assert set(cfg["assumed"]) >= {"arithmetic", "weights", "decoding",
                                   "e_score_correction_bias"}
    # the accepted reader of the decode kernel finds this one by the file
    assert cfg["trace"]["paged_attention"] == "^mla_paged_attention_pallas"


def test_the_mix_fits_the_deployment_letter_for_letter():
    cfg = _config()
    dep = cfg["deployment"]
    with open(os.path.join(REPO, "perfbench", "traffic",
                           "closed_loop_p2k.json")) as f:
        mix = json.load(f)
    assert mix["kind"] == "closed_loop" and mix["clients"] == 32 \
        == dep["slots"] == max(dep["decode_buckets"])
    assert mix["prompt_len"] == {"median": 2048, "sigma": 0.7, "min": 256,
                                 "max": 8192}
    assert mix["output_len"] == {"median": 128, "sigma": 0.5, "min": 32,
                                 "max": 384}
    assert mix["preroll_s"] == 8 and mix["trace_seconds"] == 4
    prompts = length_population(mix["prompt_len"])
    outputs = length_population(mix["output_len"])
    assert np.mean(prompts) == pytest.approx(2600, rel=0.05)
    assert np.mean(outputs) == pytest.approx(145, rel=0.05)
    longest = max(prompts) + max(outputs)
    assert max(prompts) <= max(dep["prefill_buckets"]) == 8192
    assert longest <= cfg["max_position_embeddings"]
    # every slot can hold the longest request
    per_slot = -(-longest // dep["block_size"])
    assert dep["num_blocks"] == dep["slots"] * per_slot + 1 == 4289
    chk = cfg["check"]
    assert chk["width"] >= max(prompts) + chk["max_new"]
    # the rehearsal's prompts fit its buckets
    small, tiny = sized(mix, True), _config(rehearse=True)
    assert small["prompt_len"]["max"] \
        <= max(tiny["deployment"]["prefill_buckets"])
    assert small["prompt_len"]["max"] + small["output_len"]["max"] \
        <= tiny["max_position_embeddings"]


def entries(bench):
    """What the benchmark holds of the cell, whatever later cells were
    appended after it."""
    closed = {m["name"] for m in bench["per_layer"]
              if "gpt2m_serve_open_r80" in m.get("workloads", ())}
    mine = _entries.reported(bench, CELL)
    assert closed - mine == {"queue_wait_ms.serve", "ttft_p50_ms.open"}
    assert mine - closed == {"moe_experts_ms.serve",
                             "expert_tokens_per_step.serve",
                             "cache_hit_share.setup"}
    assert _entries.reported(bench, CELL, "end_to_end") \
        == {"serve_tokens_per_s", "setup_s"}
    # appended: cell 4, configuration 3, its two metrics straight after the
    # open loop's, and in each list behind the cells the benchmark had
    _entries.entry_at(bench, "workloads", CELL, 4)
    _entries.entry_at(bench, "configs", "kimi-k2-instruct-ep32", 3)
    new = ["moe_experts_ms.serve", "expert_tokens_per_step.serve"]
    _entries.metrics_in_order(bench, ["ttft_p50_ms.open"] + new)
    _entries.first_of_its_own(bench, CELL, new)
    _entries.after_earlier_cells(bench, CELL,
                                 _entries.names(bench["workloads"][:4]))
    # the decode kernel's roofline is the accepted metric, read by the
    # accepted reader: the cell is appended to its list
    roofline = next(m for m in bench["per_layer"]
                    if m["name"] == "paged_attention_roofline.serve")
    assert CELL in roofline["workloads"]


def test_the_cell_reports_every_serving_metric_but_the_open_loops_two():
    cell = Cell(REPO, CELL)
    assert cell.chips == 1 and cell.family() is kimi_k2
    entries(json.load(open(os.path.join(REPO, "BENCHMARK.json"))))


# ---------------------------------------------------------------------
# the plain reference against the program
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def built():
    cfg = _tiny()
    model, params = kimi_k2.build_model(cfg, seed=5)
    return cfg, model, params


def test_reference_constants_are_kimi_k2s():
    cfg = _config()
    inv = kimi_k2.yarn_inv_freq(cfg)
    plain = 50000.0 ** (-2.0 * np.arange(32) / 64)
    np.testing.assert_allclose(inv[:20], plain[:20])
    np.testing.assert_allclose(inv[20:], plain[20:] / 32)
    assert kimi_k2.softmax_scale(cfg) == pytest.approx(0.13086, abs=1e-5)
    # the program's tables, made by other code, are the same numbers
    from mxnet_tpu.serving.decode.latent_moe import (attention_scale,
                                                     yarn_inv_freq)
    np.testing.assert_allclose(
        yarn_inv_freq(64, 50000, cfg["rope_scaling"]), inv, rtol=1e-12)
    assert attention_scale(192, cfg["rope_scaling"]) \
        == pytest.approx(kimi_k2.softmax_scale(cfg), rel=1e-12)


def test_reference_agrees_with_the_program_on_the_full_forward(built):
    cfg, model, params = built
    assert model.first_expert == 2 and model.n_held == 2    # rank 1 of 4
    tokens = np.random.RandomState(0).randint(
        0, cfg["vocab_size"], (2, 24)).astype(np.int32)
    ref_params = kimi_k2.reference_params(params, cfg)
    # the served arrays themselves, under the published names
    assert ref_params["model.layers.1.self_attn.q_a_proj.weight"] \
        is params["h1_wqa"]
    assert ref_params["model.layers.2.mlp.experts.up_proj.weight"] \
        is params["h2_experts_up"]
    assert "model.layers.0.mlp.gate_proj.weight" in ref_params
    assert "model.layers.0.mlp.gate.weight" not in ref_params
    got = np.asarray(model.full_logits(params, tokens))
    want = np.asarray(kimi_k2.make_reference(cfg)(ref_params, tokens))
    assert want.shape == (2, 24, cfg["vocab_size"])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("name,change", [
    ("model.layers.1.self_attn.kv_b_proj.weight", lambda w: w * 1.5),
    ("model.layers.2.mlp.experts.down_proj.weight", lambda w: w * 1.5),
    ("model.layers.1.mlp.gate.e_score_correction_bias",
     lambda b: b + np.linspace(-3, 3, b.shape[0], dtype=np.float32)),
    ("model.layers.0.input_layernorm.weight", lambda w: w * 0 + 1)],
    ids=["kv_b_proj", "experts", "selection_bias", "norm"])
def test_reference_sees_a_changed_weight(built, name, change):
    cfg, _model, params = built
    tokens = np.random.RandomState(1).randint(
        0, cfg["vocab_size"], (1, 24)).astype(np.int32)
    ref_params = kimi_k2.reference_params(params, cfg)
    reference = kimi_k2.make_reference(cfg)
    base = np.asarray(reference(ref_params, tokens))
    moved = np.asarray(reference(dict(
        ref_params, **{name: change(np.asarray(ref_params[name]))}), tokens))
    assert np.abs(moved - base).max() > 1e-3


class _Spy:
    """The program's model with every prefill's and decode step's logits
    copied out of the compiled programs the engine runs."""

    def __init__(self, model):
        self._model, self.seen = model, []
        self._lock = threading.Lock()

    def __getattr__(self, name):
        return getattr(self._model, name)

    def _note(self, kind, positions, logits):
        with self._lock:
            self.seen.append((kind, np.asarray(positions),
                              np.asarray(logits)))

    def prefill_kv(self, params, tokens, last):
        import jax
        out = self._model.prefill_kv(params, tokens, last)
        jax.debug.callback(lambda at, lg: self._note("prefill", at, lg),
                           last, out[0])
        return out

    def decode_logits(self, params, slabs, token_ids, positions, tables,
                      block_size, live=None):
        import jax
        out = self._model.decode_logits(params, slabs, token_ids,
                                        positions, tables, block_size, live)
        jax.debug.callback(lambda at, lg: self._note("decode", at, lg),
                           positions, out[1])
        return out


def test_prefill_then_decode_through_the_engine_agrees_on_logits(built):
    """Solo, and joined mid-batch: the logits of every prefill and every
    decode step that the engine's own programs computed over the latent
    cache are the reference's full forward at that position."""
    import jax
    from mxnet_tpu.serving.decode import DecodeEngine
    cfg, model, params = built
    dep = cfg["deployment"]
    spy = _Spy(model)
    eng = DecodeEngine(spy, params, prefill_buckets=dep["prefill_buckets"],
                       decode_buckets=dep["decode_buckets"],
                       block_size=dep["block_size"],
                       num_blocks=dep["num_blocks"],
                       kv_dtype=dep["kv_dtype"])
    eng.warmup()
    eng.start()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, cfg["vocab_size"], n).tolist()
               for n in (9, 5, 14)]
    try:
        solo = eng.submit(prompts[0], 6).tokens()
        first = eng.submit(prompts[0], 6)
        head = [next(first)]             # decoding: the others join it
        others = [eng.submit(p, 6) for p in prompts[1:]]
        joined = [head + list(first)] + [s.tokens() for s in others]
        jax.effects_barrier()
    finally:
        eng.close(drain=False)
    assert joined[0] == solo and eng.cache.blocks_in_use() == 0
    reference = kimi_k2.make_reference(cfg)
    ref_params = kimi_k2.reference_params(params, cfg)
    want = {}                           # (prompt index, position) -> logits
    for i, (prompt, out) in enumerate(zip(prompts, joined)):
        seq = np.asarray([prompt + out], np.int32)
        logits = np.asarray(reference(ref_params, seq))[0]
        for pos in range(len(prompt) - 1, len(seq[0]) - 1):
            want[i, pos] = logits[pos]
            # greedy: the engine's token is the reference's argmax
            assert int(logits[pos].argmax()) == seq[0, pos + 1]
    checked = 0
    for kind, positions, logits in spy.seen:
        rows = [(int(positions), logits)] if kind == "prefill" else [
            (int(p), lg) for p, lg in zip(positions, logits) if p > 0]
        for pos, got in rows:
            # which stream it was: the one whose reference logits it has
            gaps = [np.abs(got - ref).max() for (i, at), ref in want.items()
                    if at == (pos if kind == "prefill" else pos)]
            assert gaps and min(gaps) < 1e-3, (kind, pos, min(gaps))
            checked += 1
    # the solo run's and the joined run's prefills and steps
    assert checked >= 2 * (1 + 5) + 2 * (1 + 5)


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight experts over four ranks of two: the four ranks' routed parts,
    with what every chip computes alike (attention, the shared expert)
    counted once, equal what the uncut reference gives for the whole
    layer; and each rank's routed part is what the program's
    ``routed_experts`` computes for that rank."""
    import jax.numpy as jnp
    from mxnet_tpu.parallel.moe import route_top_k, routed_experts
    base = dict(num_hidden_layers=1, first_k_dense_replace=0)
    uncut = _tiny(uncut=True, **base)
    model, params = kimi_k2.build_model(uncut, seed=9)
    assert model.n_held == 8 and model.is_expert_layer(0)
    x = jnp.asarray(np.random.RandomState(3).normal(
        size=(21, uncut["hidden_size"])).astype(np.float32))

    def layer_of(cfg, first, count, **changed):
        """The reference's block 0 given experts first .. first + count."""
        share = dict(params, **{
            "h0_experts_" + n: params["h0_experts_" + n][first:
                                                         first + count]
            for n in ("gate", "up", "down")})
        return np.asarray(kimi_k2.reference_layer(cfg)(
            dict(kimi_k2.reference_params(share, cfg), **changed), 0, x))

    whole = layer_of(uncut, 0, 8)
    # no expert held: the residual, attention and the shared expert
    none = _tiny(n_routed_experts=0, rank=0, **base)
    alike = layer_of(none, 0, 0)
    parts = [layer_of(_tiny(rank=r, **base), 2 * r, 2) - alike
             for r in range(4)]
    assert all(np.abs(part).max() > 1e-3 for part in parts)
    np.testing.assert_allclose(alike + sum(parts), whole, atol=2e-5)
    # the program's expert layer, rank by rank, on the same input: the
    # residual after attention is the block without any expert at all
    p = {k[len("h0_"):]: v for k, v in params.items() if k.startswith("h0_")}
    att = layer_of(none, 0, 0, **{
        "model.layers.0.mlp.shared_experts.down_proj.weight":
        jnp.zeros_like(p["shared_down"])})
    h = model._rms(jnp.asarray(att), p["ffn_norm"])
    chosen, weights = route_top_k(h, p["router"], p["router_bias"],
                                  uncut["num_experts_per_tok"],
                                  uncut["routed_scaling_factor"])
    loads = []
    for rank in range(4):
        own = [p["experts_" + n][2 * rank:2 * rank + 2]
               for n in ("gate", "up", "down")]
        y, counts = routed_experts(h, chosen, weights, *own,
                                   first_expert=2 * rank)
        np.testing.assert_allclose(np.asarray(y), parts[rank], atol=2e-5)
        loads += list(np.asarray(counts))
    assert sum(loads) == 21 * uncut["num_experts_per_tok"]


# ---------------------------------------------------------------------
# shape functions, by hand
# ---------------------------------------------------------------------

def test_a_token_holds_1152_bytes_a_layer_of_latent_row():
    cfg = _config()
    assert kimi_k2.kv_bytes_per_token(cfg) == 6 * 1152 == 6 * 576 * 2
    flops, nbytes = kimi_k2.paged_attention_cost(cfg, 1000)
    assert nbytes == 6 * 1152 * 1000
    assert flops == 6 * 2 * 64 * (576 + 512) * 1000
    # per-head K and V of the expanded form would be 40,960 B a layer
    assert 64 * (192 + 128) * 2 == 40960


def test_matmul_weights_are_the_issues_table():
    n = kimi_k2.matmul_params(_config())
    assert n["attention"] == 11010048 + 18874368 + 4128768 + 8388608 \
        + 58720256
    assert n["attention"] / 1e6 == pytest.approx(101.12, abs=0.01)
    assert n["expert"] / 1e6 == pytest.approx(44.04, abs=0.01)
    assert n["router"] / 1e6 == pytest.approx(2.75, abs=0.01)
    assert n["dense_ffn"] / 1e6 == pytest.approx(396.36, abs=0.01)


def test_served_flops_by_hand():
    cfg = _config()
    n = kimi_k2.matmul_params(cfg)
    expert_layer = n["attention"] + n["router"] \
        + n["expert"] * (1 + 8 * 12 / 384)
    token = 2 * (5 * expert_layer + n["attention"] + n["dense_ffn"])
    head = 2 * 7168 * 20480
    assert kimi_k2.served_flops(cfg, 0, 0, []) == 0
    assert kimi_k2.served_flops(cfg, 1, 300, []) == pytest.approx(
        token + head + 6 * 2 * 64 * (576 + 512) * 300)
    assert kimi_k2.served_flops(cfg, 0, 0, [100]) == pytest.approx(
        100 * token + head + 6 * 2 * 64 * (192 + 128) * (100 * 101 // 2))


# ---------------------------------------------------------------------
# the readers, on hand-built runs
# ---------------------------------------------------------------------

def _fake_run(spans=(), ops=(), steps=(), trace=None, counters=None):
    cell = Cell(REPO, CELL)
    lines = []
    run = types.SimpleNamespace(
        cell=cell, cfg=_config(), family=kimi_k2, trace=trace,
        counters=counters or {}, tracing=True,
        stamp={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        log=types.SimpleNamespace(
            line=lambda **kw: lines.append(kw),
            measurement=lambda event, **kw: lines.append(
                dict(kw, event=event))))
    view = program_trace.ProgramTrace(list(spans), list(ops), (0.0, 1e9))
    view.steps = list(steps)
    run._program_trace = view
    return run, lines


def test_expert_tokens_per_step_reads_the_step_spans_counts():
    read = Cell(REPO, CELL).layer_reader("expert_tokens_per_step.serve")
    span = program_trace.Span
    spans = [span("mx.decode.step", "engine", 100.0, 50.0,
                  {"n": "32", "moe_assignments_held": "30"}),
             span("mx.decode.step", "engine", 200.0, 50.0,
                  {"n": "32", "moe_assignments_held": "90"}),
             # not whole inside the window: left out
             span("mx.decode.step", "engine", 1e9 - 10, 50.0,
                  {"moe_assignments_held": "1000"}),
             span("mx.decode.prefill", "engine", 300.0, 50.0,
                  {"moe_assignments_held": "5000"})]
    run, _ = _fake_run(spans=spans)
    # 60 a step over 5 expert layers x 12 experts held
    assert read(run) == pytest.approx(60 / 60)
    # a program that counts nothing (its parent): nothing to read
    plain = [span("mx.decode.step", "engine", 100.0, 50.0, {"n": "4"})]
    assert read(_fake_run(spans=plain)[0]) is None
    assert read(_fake_run()[0]) is None


def test_moe_experts_ms_sums_router_experts_and_shared_expert():
    read = Cell(REPO, CELL).layer_reader("moe_experts_ms.serve")
    op = program_trace.Op
    ops = [op("fusion.1", 0e7, 4e6, ("h1", "router")),
           op("custom-call.2", 1e7, 6e6, ("h1", "experts")),
           op("fusion.3", 2e7, 2e6, ("h5", "shared_expert")),
           op("fusion.4", 3e7, 9e6, ("h0", "mlp")),
           op("fusion.5", 4e7, 9e6, ("h1", "attention")),
           op("fusion.6", 5e7, 9e6, ()),
           # the compiler's own name for the grouped matmul: no scope
           op("ragged-dot-none.14", 6e7, 3e6, ())]
    run, _ = _fake_run(ops=ops, steps=[(0.0, 3e7), (3e7, 7e7)])
    assert read(run) == pytest.approx((4 + 6 + 2 + 3) / 2)
    run, _ = _fake_run(ops=ops[3:6], steps=[(3e7, 6e7)])
    assert read(run) is None


def test_the_accepted_roofline_reader_reads_the_latent_kernel():
    """``paged_attention_roofline.serve`` works from data: the
    configuration's ``trace.paged_attention`` names the kernel's events and
    the family's ``paged_attention_cost`` what they have to move."""
    read = Cell(REPO, CELL).layer_reader("paged_attention_roofline.serve")
    d0 = "/device:TPU:0"
    ev = xplane.Event
    events = [ev(xplane.HOST_PLANE, "main", "perfbench.window", 0.0, 1e9,
                 ""),
              ev(d0, xplane.OPS_LINE, "mla_paged_attention_pallas.7",
                 100.0, 3e6, ""),
              ev(d0, xplane.OPS_LINE, "mla_paged_attention_pallas.9",
                 5e6, 1e6, ""),
              ev(d0, xplane.OPS_LINE, "paged_attention_pallas.3", 7e6, 9e6,
                 ""),
              ev(d0, xplane.OPS_LINE, "fusion.1", 2e7, 5e6, "")]
    from perfbench.harness.runctx import TraceView
    run, lines = _fake_run(trace=TraceView(events, chips=1),
                           counters={"decode_context_tokens": 100000})
    # 100,000 context tokens x 6 x 1,152 B at 819 GB/s over 4 ms
    least = 100000 * 6 * 1152 / 819e9
    assert read(run) == pytest.approx(100.0 * least / 4e-3)
    assert lines[-1]["bound"] == "memory" and lines[-1]["events"] == 2
    assert read(_fake_run(trace=TraceView(events, chips=1))[0]) is None
    assert read(_fake_run(counters={"decode_context_tokens": 5})[0]) is None


def test_a_program_without_the_model_fails_the_cell_cleanly(monkeypatch):
    """The parent of the PR that brought the model: ``build_model`` is a
    ``SpecError`` (exit 2, at once), not a traceback or a hang."""
    import mxnet_tpu.serving.decode as decode
    monkeypatch.delattr(decode, "LatentMoEDecoder")
    with pytest.raises(SpecError, match="cannot run the configuration"):
        kimi_k2.build_model(_tiny(), seed=0)


def test_the_control_reference_rounds_its_weights_through_float8(built):
    """The check's control: the reference at the precision next below the
    configuration's lies far from the program, so a tolerance between the
    two readings fails it."""
    cfg, model, params = built
    tokens = np.random.RandomState(4).randint(
        0, cfg["vocab_size"], (1, 24)).astype(np.int32)
    ref_params = kimi_k2.reference_params(params, cfg)
    got = np.asarray(model.full_logits(params, tokens))
    plain = np.asarray(kimi_k2.make_reference(cfg)(ref_params, tokens))
    control = np.asarray(kimi_k2.make_reference(
        cfg, kimi_k2.CONTROL_PRECISION)(ref_params, tokens))
    assert np.abs(got - plain).max() < 1e-3
    assert np.abs(got - control).max() > 30 * np.abs(got - plain).max()
    assert np.abs(got - control).max() > 0.05


def _ties(err):
    """The ``router_ties`` lines of standard error, one dict a line."""
    out = []
    for line in err.splitlines():
        if line.startswith("router_ties "):
            words = line.split()[2:]
            out.append(dict(zip(words[0::2], map(float, words[1::2]))))
    return out


def test_a_served_ties_reference_follows_the_program_in_near_ties_only(
        built, capfd):
    """The reference's router stays its own: an expert the program chose
    counts where the reference's own biased score puts it within
    ``check.tie_eps`` of its own top k, and nowhere else."""
    import jax
    cfg, model, params = built
    tokens = np.random.RandomState(6).randint(
        0, cfg["vocab_size"], (2, 24)).astype(np.int32)
    ref_params = kimi_k2.reference_params(params, cfg)
    plain = kimi_k2.make_reference(cfg)
    served = kimi_k2.make_reference(cfg, "served_ties.highest")
    want = np.asarray(plain(ref_params, tokens))
    # it follows the forward that was judged last, and says so if that was
    # over other tokens: it runs no program itself
    kimi_k2._JUDGED.clear()
    with pytest.raises(ValueError, match="judged last"):
        served(ref_params, tokens)
    _logits, routing = model.spec.full_logits(params, tokens,
                                              with_routing=True)
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    assert len(routing) == layers and all(
        r.shape == (2, 24, cfg["num_experts_per_tok"]) for r in routing)
    jax.block_until_ready(jax.jit(model.full_logits)(params, tokens))
    jax.effects_barrier()
    assert all((k == np.asarray(r)).all()
               for k, r in zip(kimi_k2._JUDGED["routing"], routing))
    # float32 at this size: the two routers choose alike, nothing moves
    capfd.readouterr()
    np.testing.assert_allclose(np.asarray(served(ref_params, tokens)), want,
                               atol=1e-5)
    ties = _ties(capfd.readouterr().err)
    assert len(ties) == 2 * layers and all(
        t["moved"] == 0 and t["not_followed"] == 0 and t["tokens"] == 24
        for t in ties)
    # a served choice that lies far down the reference's own scores is NOT
    # followed: the reference stays where it was, and counts the tokens
    experts = cfg["published"]["n_routed_experts"]
    kept = kimi_k2._JUDGED["routing"]
    kimi_k2._JUDGED["routing"] = [
        np.stack([k[0], (k[1] + 1) % experts]) for k in kept]
    strict = dict(cfg, check=dict(cfg["check"], tie_eps=1e-9))
    np.testing.assert_allclose(
        np.asarray(kimi_k2.make_reference(strict, "served_ties.highest")(
            ref_params, tokens)), want, atol=1e-5)
    ties = _ties(capfd.readouterr().err)
    assert all(t["moved"] == 0 for t in ties)
    assert all(t["not_followed"] == 0 for t in ties[:layers])
    assert all(t["not_followed"] > 0 and t["shortfall"] > 1e-9
               for t in ties[layers:])
    # with every choice counted a near-tie, that same choice moves it
    loose = dict(cfg, check=dict(cfg["check"], tie_eps=10.0))
    off = np.asarray(kimi_k2.make_reference(loose, "served_ties.highest")(
        ref_params, tokens))
    assert np.abs(off[0] - want[0]).max() < 1e-5
    assert np.abs(off[1] - want[1]).max() > 1e-3
    ties = _ties(capfd.readouterr().err)
    assert all(t["not_followed"] == 0 for t in ties)
    assert all(t["moved"] > 0 for t in ties[layers:])
    kimi_k2._JUDGED.clear()


@pytest.mark.parametrize("fault", ["selection_bias", "router", "top_k"])
def test_a_program_whose_router_is_wrong_is_not_followed(built, fault):
    """What the reference is for: a program that chooses with another
    selection bias, another gate or another k lies a swap away from it,
    although the reference is handed that program's choices."""
    import jax
    cfg, model, params = built
    tokens = np.random.RandomState(8).randint(
        0, cfg["vocab_size"], (1, 24)).astype(np.int32)
    ref_params = kimi_k2.reference_params(params, cfg)
    served = kimi_k2.make_reference(cfg, "served_ties.highest")
    good = np.asarray(jax.jit(model.full_logits)(params, tokens))
    assert np.abs(good - np.asarray(served(ref_params, tokens))).max() < 1e-3
    if fault == "top_k":
        program, _ = kimi_k2.build_model(dict(cfg, num_experts_per_tok=1),
                                         seed=5)
        wrong = params
    else:
        program = model
        name = {"selection_bias": "h1_router_bias", "router": "h2_router"}[
            fault]
        wrong = dict(params, **{name: jax.numpy.flip(params[name], -1)})
    got = np.asarray(jax.jit(program.full_logits)(wrong, tokens))
    assert np.abs(got - good).max() > 1e-2
    # the reference is handed the RIGHT weights and the wrong program's
    # choices, and does not go where that program went
    want = np.asarray(served(ref_params, tokens))
    assert np.abs(got - want).max() > 1e-2
    kimi_k2._JUDGED.clear()


def test_the_control_stays_far_off_under_the_programs_choices(built):
    import jax
    cfg, model, params = built
    tokens = np.random.RandomState(6).randint(
        0, cfg["vocab_size"], (2, 24)).astype(np.int32)
    ref_params = kimi_k2.reference_params(params, cfg)
    got = np.asarray(jax.jit(model.full_logits)(params, tokens))
    control = kimi_k2.make_reference(
        cfg, "served_ties." + kimi_k2.CONTROL_PRECISION)
    assert np.abs(np.asarray(control(ref_params, tokens)) - got).max() \
        > 0.05
    kimi_k2._JUDGED.clear()


def test_the_cell_held_to_the_control_reference_is_not_correct(tmp_path):
    """The whole command, rehearsed from a copy whose configuration lists
    the float8 control in place of the reference: the run is
    incorrect and names that reference (on the chip the control reads
    1.06-1.11 against the cell's 0.35; PERF.md, PR 28)."""
    from test_perfbench_command import (_copy_of_the_benchmark, _records,
                                        _run)
    root = _copy_of_the_benchmark(tmp_path)
    path = root / "perfbench/configs/kimi-k2-instruct-ep32.json"
    cfg = json.load(open(path))
    control = "served_ties." + kimi_k2.CONTROL_PRECISION
    refs = cfg["rehearse"]["check"]["references"]
    assert [r["precision"] for r in refs] == ["served_ties.highest"]
    refs[0]["precision"] = control
    with open(path, "w") as f:
        json.dump(cfg, f)
    out = _run(["--workload", CELL, "--seed", "7", "--seconds", "1",
                "--trace", "0", "--rehearse"], root=str(root),
               pythonpath=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    records = _records(out)
    why = [r["why"] for r in records if r["event"] == "incorrect"]
    assert why and all(control in w for w in why), why
    assert {r["event"]: r for r in records}["rehearsed"]["correct"] is False
