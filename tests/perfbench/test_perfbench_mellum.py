"""``mellum2_serve_closed32``: the configuration's file against the
published config, the family's plain reference against the program at a
tiny size on the CPU in float32 (logits, full forward and prefill-then-
decode through the engine's own programs and both pools of the cache,
across wraps of the ring), what the reference sees (a weight, the window,
the router), the shape functions by hand, and the three readers on
hand-built runs."""
import json
import os
import threading
import types

import numpy as np
import pytest

import _entries
from perfbench.families import mellum
from perfbench.harness import program_trace, xplane
from perfbench.harness.spec import Cell, SpecError, sized
from perfbench.harness.traffic import length_population

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "mellum2_serve_closed32"
CONFIG = "mellum2-12b-a2.5b-instruct-l8"
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct, config.json
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": PERIOD * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}


def _config(rehearse=False):
    with open(os.path.join(REPO, "perfbench", "configs",
                           CONFIG + ".json")) as f:
        return sized(json.load(f), rehearse)


def _tiny(**over):
    cfg = _config(rehearse=True)
    cfg.update(over)
    return cfg


# ---------------------------------------------------------------------
# the configuration's file, the mix and the cell
# ---------------------------------------------------------------------

def test_every_published_key_is_in_the_file_and_the_cut_is_named():
    cfg = _config()
    reduced = {"num_hidden_layers": 8, "vocab_size": 24576,
               "max_position_embeddings": 16768}
    assert cfg["reduced"] == list(reduced)
    for key, value in PUBLISHED.items():
        assert cfg[key] == reduced.get(key, value), key
    assert cfg["published"] == {k: PUBLISHED[k] for k in reduced}
    # the floors of a cut: a whole period (here two) of the layer pattern,
    # at least 8 routed experts, an eighth of the vocabulary
    assert mellum.layer_types(cfg) == PERIOD * 2
    assert cfg["num_experts"] == 64 and cfg["num_experts_per_tok"] == 8
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    # no width is cut, and the two names the accepted reader of
    # expert_tokens_per_step.serve reads stand beside the published one
    assert cfg["n_routed_experts"] == cfg["num_experts"]
    assert cfg["first_k_dense_replace"] == 0
    dep = cfg["deployment"]
    assert dep["chips"] == 1 and dep["chips_sharing_a_layer"] == 1
    assert set(cfg["assumed"]) >= {"arithmetic", "qk_norm", "window",
                                   "rotary", "router", "mtp_head",
                                   "weights", "decoding"}
    assert cfg["trace"]["paged_attention"] == "^paged_attention_pallas"
    assert dep["peak_hbm_measured_bytes"] >= 0.25 * 16.9e9
    assert len(cfg["check"]["why"]) > 500


def test_the_mix_fits_the_deployment_letter_for_letter():
    cfg = _config()
    dep = cfg["deployment"]
    with open(os.path.join(REPO, "perfbench", "traffic",
                           "closed_loop_p16k.json")) as f:
        mix = json.load(f)
    assert mix["kind"] == "closed_loop" and mix["clients"] == 32 \
        == dep["slots"] == max(dep["decode_buckets"])
    assert mix["prompt_len"] == {"median": 2048, "sigma": 0.9, "min": 128,
                                 "max": 16384}
    assert mix["output_len"] == {"median": 128, "sigma": 0.5, "min": 32,
                                 "max": 384}
    assert mix["preroll_s"] == 8 and mix["trace_seconds"] == 4
    prompts = length_population(mix["prompt_len"])
    outputs = length_population(mix["output_len"])
    assert np.mean(prompts) == pytest.approx(2996, abs=1)
    assert np.mean(outputs) == pytest.approx(145, rel=0.02)
    window = cfg["sliding_window"]
    assert sum(p < window for p in prompts) == 21
    assert sum(p > 4096 for p in prompts) == 21
    assert (min(prompts), max(prompts)) == (204, 16384)
    assert dep["prefill_buckets"] == [256, 512, 1024, 2048, 4096, 8192,
                                      16384]
    longest = max(prompts) + max(outputs)
    assert longest == cfg["max_position_embeddings"] == 16768
    # every slot can hold the longest request in a full layer, and a ring
    # in a window layer whatever the request
    bs = dep["block_size"]
    assert dep["num_blocks"] == dep["slots"] * -(-longest // bs) + 1 == 8385
    assert dep["ring"] == -(-window // bs) + 1 == 17
    assert dep["window_blocks"] == dep["slots"] * dep["ring"] + 1 == 545
    chk = cfg["check"]
    assert chk["width"] == 16768 >= max(prompts) + chk["max_new"]
    assert chk["width"] % bs == 0
    # the warm-up sends each prefill bucket's length and two tokens
    assert max(dep["prefill_buckets"]) + 2 <= cfg["max_position_embeddings"]
    small, tiny = sized(mix, True), _config(rehearse=True)
    assert small["clients"] == 4
    assert (small["prompt_len"]["min"], small["prompt_len"]["max"]) == (2, 40)
    assert (small["output_len"]["min"], small["output_len"]["max"]) == (2, 12)
    assert small["prompt_len"]["max"] \
        <= max(tiny["deployment"]["prefill_buckets"])
    assert max(tiny["deployment"]["prefill_buckets"]) + 2 \
        <= tiny["max_position_embeddings"]


def test_the_slabs_bytes_are_the_issues_arithmetic():
    cfg = _config()
    dep = cfg["deployment"]
    block = dep["block_size"] * mellum.kv_bytes_per_token(cfg)
    assert block == 131072
    full, window = mellum._layers_by_kind(cfg)
    assert (full, window) == (2, 6)
    assert dep["num_blocks"] * block * full / 1e9 \
        == pytest.approx(2.198, abs=0.001)
    assert dep["window_blocks"] * block * window / 1e9 \
        == pytest.approx(0.429, abs=0.001)
    # without the ring every layer would hold the full table
    assert dep["num_blocks"] * block * 8 / 1e9 == pytest.approx(8.79,
                                                                abs=0.01)


def entries(bench):
    """What the benchmark holds of the cell, whatever later cells were
    appended after it."""
    kimi = _entries.reported(bench, "kimi_k2_serve_closed32")
    mine = _entries.reported(bench, CELL)
    new = ["window_attention_roofline.serve", "attention_full_ms.serve",
           "attention_window_ms.serve"]
    # every serving metric the Kimi cell reports but the one roofline that
    # a sum of contexts cannot feed where a window bounds what is read
    assert kimi - mine == {"paged_attention_roofline.serve"}
    assert mine - kimi == set(new)
    assert _entries.reported(bench, CELL, "end_to_end") \
        == {"serve_tokens_per_s", "setup_s"}
    # appended: after everything the benchmark had at PR 31, whatever a
    # later PR appends after them in turn
    assert _entries.entry_at(bench, "workloads", CELL, 5)["traffic"] \
        == "closed_loop_p16k"
    assert _entries.entry_at(bench, "configs", CONFIG, 4)["reduced"] \
        == _config()["reduced"]
    for m in _entries.metrics_in_order(
            bench, ["expert_tokens_per_step.serve"] + new)[1:]:
        assert m["moves"] == "serve_tokens_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    _entries.first_of_its_own(bench, CELL, new)
    for m in bench["per_layer"] + bench["end_to_end"]:
        lists = m.get("workloads", ())
        if CELL in lists and "kimi_k2_serve_closed32" in lists:
            assert lists.index(CELL) \
                == lists.index("kimi_k2_serve_closed32") + 1
    # one cell of the benchmark took four chips when it was appended
    _entries.among_four_chip_cells(bench, "bert_train_dp4")


def test_the_cell_and_its_three_metrics_are_appended_entries():
    cell = Cell(REPO, CELL)
    assert cell.chips == 1 and cell.family() is mellum
    assert not hasattr(mellum, "paged_attention_cost")
    entries(json.load(open(os.path.join(REPO, "BENCHMARK.json"))))


def test_what_the_kimi_cells_own_test_held_of_it_still_holds():
    """The Kimi-K2 cell's entries, as its own file holds them, seen from
    the file of the cell appended after it."""
    import test_perfbench_kimi_k2
    cell = Cell(REPO, "kimi_k2_serve_closed32")
    assert cell.chips == 1 and cell.family() is test_perfbench_kimi_k2.kimi_k2
    test_perfbench_kimi_k2.entries(
        json.load(open(os.path.join(REPO, "BENCHMARK.json"))))


# ---------------------------------------------------------------------
# the plain reference against the program
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def built():
    cfg = _tiny()
    model, params = mellum.build_model(cfg, seed=5)
    return cfg, model, params


def test_reference_constants_are_mellum2s():
    cfg = _config()
    assert mellum.yarn_parameters(cfg) == (18, 35)
    inv, factor = mellum.rope_table(cfg, "full_attention")
    plain, one = mellum.rope_table(cfg, "sliding_attention")
    np.testing.assert_allclose(plain,
                               500000.0 ** (-2.0 * np.arange(64) / 128))
    assert one == 1.0 and factor == pytest.approx(0.1 * np.log(16) + 1)
    np.testing.assert_allclose(inv[:19], plain[:19])
    np.testing.assert_allclose(inv[35:], plain[35:] / 16)
    ramp = (30 - 18) / 17
    assert inv[30] == pytest.approx(plain[30] * (1 - ramp * 15 / 16))
    # the program's tables, made by other code, are the same numbers
    from mxnet_tpu.serving.decode import blocks
    np.testing.assert_allclose(
        blocks.yarn_inv_freq(128, 500000,
                             cfg["rope_parameters"]["full_attention"]),
        inv, rtol=1e-12)


def test_reference_agrees_with_the_program_on_the_full_forward(built):
    cfg, model, params = built
    assert model.cache_layers() == ("window",) * 3 + ("full",)
    tokens = np.random.RandomState(0).randint(
        0, cfg["vocab_size"], (2, 40)).astype(np.int32)
    ref_params = mellum.reference_params(params, cfg)
    # the served arrays themselves, under the published names
    assert ref_params["model.layers.1.self_attn.q_proj.weight"] \
        is params["h1_wq"]
    assert ref_params["model.layers.2.mlp.experts.up_proj.weight"] \
        is params["h2_experts_up"]
    assert ref_params["model.layers.3.mlp.gate.weight"] \
        is params["h3_router"]
    assert len(ref_params) == 3 + 4 * 10 == len(params)
    got = np.asarray(model.full_logits(params, tokens))
    want = np.asarray(mellum.make_reference(cfg)(ref_params, tokens))
    assert want.shape == (2, 40, cfg["vocab_size"])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_the_references_gather_and_its_dense_pass_agree(built, monkeypatch):
    """An expert reads the tokens that chose it through a gather of a
    quarter of the sequence; a layer in which one expert was chosen by
    more takes the dense pass: both are the same sum."""
    cfg, _model, params = built
    tokens = np.random.RandomState(3).randint(
        0, cfg["vocab_size"], (1, 64)).astype(np.int32)
    ref_params = mellum.reference_params(params, cfg)
    gathered = np.asarray(mellum.make_reference(cfg)(ref_params, tokens))
    monkeypatch.setattr(mellum, "_REF_SHARE", 10 ** 6)      # never fits
    dense = np.asarray(mellum.make_reference(cfg)(ref_params, tokens))
    np.testing.assert_allclose(gathered, dense, atol=2e-5)


@pytest.mark.parametrize("name,change", [
    ("model.layers.1.self_attn.k_proj.weight", lambda w: w * 1.5),
    ("model.layers.2.mlp.experts.down_proj.weight", lambda w: w * 1.5),
    ("model.layers.3.mlp.gate.weight", lambda w: w[:, ::-1]),
    ("model.layers.0.input_layernorm.weight", lambda w: w * 0 + 1)],
    ids=["k_proj", "experts", "router", "norm"])
def test_reference_sees_a_changed_weight(built, name, change):
    cfg, _model, params = built
    tokens = np.random.RandomState(1).randint(
        0, cfg["vocab_size"], (1, 24)).astype(np.int32)
    ref_params = mellum.reference_params(params, cfg)
    reference = mellum.make_reference(cfg)
    base = np.asarray(reference(ref_params, tokens))
    moved = np.asarray(reference(dict(
        ref_params, **{name: change(np.asarray(ref_params[name]))}), tokens))
    assert np.abs(moved - base).max() > 1e-3


@pytest.mark.parametrize("how", ["another_window", "no_window"])
def test_reference_sees_the_window(built, how):
    """Positions under the window read the same with any window; past it a
    window of another length, or none (the check's second control), reads
    otherwise."""
    cfg, _model, params = built
    tokens = np.random.RandomState(1).randint(
        0, cfg["vocab_size"], (1, 24)).astype(np.int32)
    ref_params = mellum.reference_params(params, cfg)
    base = np.asarray(mellum.make_reference(cfg)(ref_params, tokens))[0]
    other = mellum.make_reference(dict(cfg, sliding_window=12)) \
        if how == "another_window" \
        else mellum.make_reference(cfg, mellum.CONTROL_NO_WINDOW)
    moved = np.asarray(other(ref_params, tokens))[0]
    window = cfg["sliding_window"]
    np.testing.assert_allclose(moved[:window], base[:window], atol=1e-5)
    assert np.abs(moved[window:] - base[window:]).max() > 1e-3


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
    """Solo, and joined mid-batch, a prompt longer than the window (8) and
    than the ring (12 rows), 26 decode steps each so that the ring wraps
    more than twice: the logits of every prefill and every decode step
    that the engine's own programs computed over both pools of the cache
    are the reference's full forward at that position."""
    import jax
    from mxnet_tpu.serving.decode import DecodeEngine
    cfg, model, params = built
    dep = cfg["deployment"]
    spy = _Spy(model.spec)
    eng = DecodeEngine(spy, params, prefill_buckets=dep["prefill_buckets"],
                       decode_buckets=dep["decode_buckets"],
                       block_size=dep["block_size"],
                       num_blocks=dep["num_blocks"],
                       window_blocks=dep["window_blocks"],
                       kv_dtype=dep["kv_dtype"])
    assert eng.cache.ring == dep["ring"] == 3
    eng.warmup()
    eng.start()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, cfg["vocab_size"], n).tolist()
               for n in (19, 5, 30)]
    new = 26
    try:
        solo = eng.submit(prompts[0], new).tokens()
        first = eng.submit(prompts[0], new)
        head = [next(first)]             # decoding: the others join it
        others = [eng.submit(p, new) for p in prompts[1:]]
        joined = [head + list(first)] + [s.tokens() for s in others]
        jax.effects_barrier()
    finally:
        eng.close(drain=False)
    assert joined[0] == solo
    assert eng.cache.blocks_in_use("full") == 0
    assert eng.cache.blocks_in_use("window") == 0
    assert new // (dep["ring"] * dep["block_size"]) >= 2
    reference = mellum.make_reference(cfg)
    ref_params = mellum.reference_params(params, cfg)
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
                    if at == pos]
            assert gaps and min(gaps) < 1e-3, (kind, pos, min(gaps))
            checked += 1
    # the solo run's and the joined run's prefills and steps
    assert checked >= 4 * (1 + new - 1)


def test_a_program_without_the_model_fails_the_cell_cleanly(monkeypatch):
    """The parent of the PR that brought the model: ``build_model`` is a
    ``SpecError`` (exit 2, at once), not a traceback or a hang."""
    import mxnet_tpu.serving.decode as decode
    monkeypatch.delattr(decode, "WindowMoEDecoder")
    with pytest.raises(SpecError, match="cannot run the configuration"):
        mellum.build_model(_tiny(), seed=0)


def test_the_control_reference_rounds_its_weights_through_float8(built):
    cfg, model, params = built
    tokens = np.random.RandomState(4).randint(
        0, cfg["vocab_size"], (1, 24)).astype(np.int32)
    ref_params = mellum.reference_params(params, cfg)
    got = np.asarray(model.full_logits(params, tokens))
    plain = np.asarray(mellum.make_reference(cfg)(ref_params, tokens))
    control = np.asarray(mellum.make_reference(
        cfg, mellum.CONTROL_PRECISION)(ref_params, tokens))
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
    import jax
    cfg, model, params = built
    tokens = np.random.RandomState(6).randint(
        0, cfg["vocab_size"], (2, 24)).astype(np.int32)
    ref_params = mellum.reference_params(params, cfg)
    served = mellum.make_reference(cfg, "served_ties.highest")
    want = np.asarray(mellum.make_reference(cfg)(ref_params, tokens))
    mellum._JUDGED.clear()
    with pytest.raises(ValueError, match="judged last"):
        served(ref_params, tokens)
    layers = cfg["num_hidden_layers"]
    jax.block_until_ready(jax.jit(model.full_logits)(params, tokens))
    jax.effects_barrier()
    assert len(mellum._JUDGED["routing"]) == layers and all(
        r.shape == (2, 24, cfg["num_experts_per_tok"])
        for r in mellum._JUDGED["routing"])
    capfd.readouterr()
    np.testing.assert_allclose(np.asarray(served(ref_params, tokens)), want,
                               atol=1e-5)
    ties = _ties(capfd.readouterr().err)
    assert len(ties) == 2 * layers and all(
        t["moved"] == 0 and t["not_followed"] == 0 and t["tokens"] == 24
        for t in ties)
    # a served choice that lies far down the reference's own probabilities
    # is NOT followed: the reference stays where it was, and counts it
    kept = mellum._JUDGED["routing"]
    mellum._JUDGED["routing"] = [
        np.stack([k[0], (k[1] + 1) % cfg["num_experts"]]) for k in kept]
    strict = dict(cfg, check=dict(cfg["check"], tie_eps=1e-9))
    np.testing.assert_allclose(
        np.asarray(mellum.make_reference(strict, "served_ties.highest")(
            ref_params, tokens)), want, atol=1e-5)
    ties = _ties(capfd.readouterr().err)
    assert all(t["moved"] == 0 for t in ties)
    assert all(t["not_followed"] == 0 for t in ties[:layers])
    assert all(t["not_followed"] > 0 and t["shortfall"] > 1e-9
               for t in ties[layers:])
    # with every choice counted a near-tie, that same choice moves it
    loose = dict(cfg, check=dict(cfg["check"], tie_eps=10.0))
    off = np.asarray(mellum.make_reference(loose, "served_ties.highest")(
        ref_params, tokens))
    assert np.abs(off[0] - want[0]).max() < 1e-5
    assert np.abs(off[1] - want[1]).max() > 1e-3
    mellum._JUDGED.clear()


@pytest.mark.parametrize("fault", ["router", "top_k"])
def test_a_program_whose_router_is_wrong_is_not_followed(built, fault):
    import jax
    cfg, model, params = built
    tokens = np.random.RandomState(8).randint(
        0, cfg["vocab_size"], (1, 24)).astype(np.int32)
    ref_params = mellum.reference_params(params, cfg)
    served = mellum.make_reference(cfg, "served_ties.highest")
    good = np.asarray(jax.jit(model.full_logits)(params, tokens))
    assert np.abs(good - np.asarray(served(ref_params, tokens))).max() < 1e-3
    if fault == "top_k":
        program, _ = mellum.build_model(dict(cfg, num_experts_per_tok=1),
                                        seed=5)
        wrong = params
    else:
        program = model
        wrong = dict(params,
                     h2_router=jax.numpy.flip(params["h2_router"], -1))
    got = np.asarray(jax.jit(program.full_logits)(wrong, tokens))
    assert np.abs(got - good).max() > 1e-2
    want = np.asarray(served(ref_params, tokens))
    assert np.abs(got - want).max() > 1e-2
    mellum._JUDGED.clear()


@pytest.mark.parametrize("control", [mellum.CONTROL_PRECISION,
                                     mellum.CONTROL_NO_WINDOW])
def test_the_cell_held_to_a_control_reference_is_not_correct(tmp_path,
                                                             control):
    """The whole command, rehearsed from a copy whose configuration lists
    a control in place of the reference: weights through float8, or the
    window taken off (the rehearsal's streams run past its window of 8)."""
    from test_perfbench_command import (_copy_of_the_benchmark, _records,
                                        _run)
    root = _copy_of_the_benchmark(tmp_path)
    path = root / "perfbench/configs" / (CONFIG + ".json")
    cfg = json.load(open(path))
    name = "served_ties." + control
    refs = cfg["rehearse"]["check"]["references"]
    assert [r["precision"] for r in refs] == ["served_ties.highest"]
    refs[0]["precision"] = name
    with open(path, "w") as f:
        json.dump(cfg, f)
    out = _run(["--workload", CELL, "--seed", "7", "--seconds", "1",
                "--trace", "0", "--rehearse"], root=str(root),
               pythonpath=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    records = _records(out)
    why = [r["why"] for r in records if r["event"] == "incorrect"]
    assert why and all(name in w for w in why), why
    assert {r["event"]: r for r in records}["rehearsed"]["correct"] is False


# ---------------------------------------------------------------------
# shape functions, by hand
# ---------------------------------------------------------------------

def test_a_row_is_2048_bytes_a_layer_and_costs_16384_flops():
    cfg = _config()
    assert mellum.kv_bytes_per_token(cfg) == 2 * 4 * 128 * 2 == 2048
    flops, nbytes = mellum.attention_cost(cfg, 1000, 300)
    assert nbytes == 2048 * (2 * 1000 + 6 * 300)
    assert flops == 2 * 32 * (128 + 128) * (2 * 1000 + 6 * 300)
    assert mellum.attention_cost(cfg, 0, 0) == (0, 0)


def test_matmul_weights_are_the_issues_table():
    n = mellum.matmul_params(_config())
    assert n["attention"] == 2 * 2304 * 4096 + 2 * 2304 * 512
    assert n["attention"] / 1e6 == pytest.approx(21.23, abs=0.01)
    assert n["router"] / 1e6 == pytest.approx(0.15, abs=0.01)
    assert n["expert"] / 1e6 == pytest.approx(6.19, abs=0.01)
    layer = n["attention"] + n["router"] + 64 * n["expert"] + 2 * 2304
    assert layer / 1e6 == pytest.approx(417.75, abs=0.02)
    total = 8 * layer + 2 * 24576 * 2304 + 2304
    assert total / 1e6 == pytest.approx(3455, abs=1)
    assert 2 * total / 1e9 == pytest.approx(6.91, abs=0.01)


def test_served_flops_by_hand():
    cfg = _config()
    n = mellum.matmul_params(cfg)
    token = 2 * 8 * (n["attention"] + n["router"] + 8 * n["expert"])
    assert token / 8 / 1e6 == pytest.approx(141.9, abs=0.1)
    head = 2 * 2304 * 24576
    pair = 2 * 32 * 256
    assert mellum.served_flops(cfg, 0, 0, []) == 0
    # a decode token over a context under the window: 8 layers read it
    assert mellum.served_flops(cfg, 1, 300, []) == token + head \
        + pair * 8 * 300
    # past it the 6 window layers read 1,024 rows
    assert mellum.served_flops(cfg, 1, 5000, []) == token + head \
        + pair * (2 * 5000 + 6 * 1024)
    assert mellum.window_pairs(100, 1024) == 100 * 101 // 2
    assert mellum.window_pairs(3000, 1024) == sum(
        min(i + 1, 1024) for i in range(3000))
    assert mellum.served_flops(cfg, 0, 0, [3000]) == 3000 * token + head \
        + pair * (2 * (3000 * 3001 // 2)
                  + 6 * mellum.window_pairs(3000, 1024))
    # the longest prompt: 134 M pairs in a full layer, 16-17 M in a window
    assert 16384 * 16385 // 2 == pytest.approx(134e6, rel=0.01)
    assert mellum.window_pairs(16384, 1024) == pytest.approx(16.3e6,
                                                             rel=0.01)


# ---------------------------------------------------------------------
# the readers, on hand-built runs
# ---------------------------------------------------------------------

def _fake_run(spans=(), ops=(), steps=(), trace=None, family=mellum):
    cell = Cell(REPO, CELL)
    lines = []
    run = types.SimpleNamespace(
        cell=cell, cfg=_config(), family=family, trace=trace, counters={},
        tracing=True,
        stamp={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        log=types.SimpleNamespace(
            line=lambda **kw: lines.append(kw),
            measurement=lambda event, **kw: lines.append(
                dict(kw, event=event))))
    view = program_trace.ProgramTrace(list(spans), list(ops), (0.0, 1e9))
    view.steps = list(steps)
    run._program_trace = view
    return run, lines


@pytest.mark.parametrize("metric,want", [
    ("attention_full_ms.serve", (4 + 2) / 2),
    ("attention_window_ms.serve", (6 + 1) / 2)])
def test_the_attention_readers_read_their_own_kind_of_layer(metric, want):
    read = Cell(REPO, CELL).layer_reader(metric)
    op = program_trace.Op
    ops = [op("custom-call.1", 0e7, 4e6, ("h3", "attention_full")),
           op("custom-call.2", 1e7, 6e6, ("h0", "attention_window")),
           op("fusion.3", 2e7, 2e6, ("h7", "attention_full")),
           op("fusion.4", 3e7, 1e6, ("h5", "attention_window")),
           op("fusion.5", 4e7, 9e6, ("h1", "experts")),
           op("fusion.6", 5e7, 9e6, ("h2", "attention")),
           op("fusion.7", 6e7, 9e6, ())]
    run, _ = _fake_run(ops=ops, steps=[(0.0, 3e7), (3e7, 7e7)])
    assert read(run) == pytest.approx(want)
    # a program without such scopes (its parent, another model): nothing
    run, _ = _fake_run(ops=ops[4:], steps=[(3e7, 7e7)])
    assert read(run) is None
    assert read(_fake_run()[0]) is None


def test_the_window_roofline_reads_the_step_spans_rows_and_the_kernels_time():
    from perfbench.harness.runctx import TraceView
    read = Cell(REPO, CELL).layer_reader("window_attention_roofline.serve")
    d0 = "/device:TPU:0"
    ev, span = xplane.Event, program_trace.Span
    events = [ev(xplane.HOST_PLANE, "main", "perfbench.window", 0.0, 1e9,
                 ""),
              ev(d0, xplane.OPS_LINE, "paged_attention_pallas.7", 100.0,
                 3e6, ""),
              ev(d0, xplane.OPS_LINE, "paged_attention_pallas.9", 5e6, 1e6,
                 ""),
              ev(d0, xplane.OPS_LINE, "mla_paged_attention_pallas.3", 7e6,
                 9e6, ""),
              ev(d0, xplane.OPS_LINE, "fusion.1", 2e7, 5e6, "")]
    spans = [span("mx.decode.step", "engine", 100.0, 50.0,
                  {"n": "32", "kv_rows_full": "100000",
                   "kv_rows_window": "30000"}),
             span("mx.decode.step", "engine", 200.0, 50.0,
                  {"n": "32", "kv_rows_full": "100032",
                   "kv_rows_window": "30010"}),
             # not whole inside the window, and a prefill: left out
             span("mx.decode.step", "engine", 1e9 - 10, 50.0,
                  {"kv_rows_full": "9999999", "kv_rows_window": "9"}),
             span("mx.decode.prefill", "engine", 300.0, 50.0,
                  {"kv_rows_full": "9999999", "kv_rows_window": "9"})]
    run, lines = _fake_run(spans=spans, trace=TraceView(events, chips=1))
    rows = 2 * 200032 + 6 * 60010
    assert read(run) == pytest.approx(100.0 * rows * 2048 / 819e9 / 4e-3)
    assert lines[-1]["bound"] == "memory" and lines[-1]["events"] == 2
    assert lines[-1]["steps"] == 2 and lines[-1]["rows_window"] == 60010
    # a program that counts no rows (its parent), a run without a trace, a
    # family without the cost function: nothing to read, nothing raised
    plain = [span("mx.decode.step", "engine", 100.0, 50.0, {"n": "4"})]
    assert read(_fake_run(spans=plain,
                          trace=TraceView(events, chips=1))[0]) is None
    assert read(_fake_run(spans=spans)[0]) is None
    assert read(_fake_run(spans=spans, trace=TraceView(events, chips=1),
                          family=types.SimpleNamespace())[0]) is None
    assert read(_fake_run(spans=spans,
                          trace=TraceView(events[:1] + events[3:],
                                          chips=1))[0]) is None
