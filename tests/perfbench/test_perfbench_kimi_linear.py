"""``kimi_linear_serve_closed128``: the configuration's file against the
published config key by key, the cell, the mix and the two metrics held by
index, the family's plain reference (KDA token by token) against the
program at a tiny size on the CPU in float32 (the full forward, and prefill
then decode through the engine's own programs over the states and latent
rows), the shares of the expert-parallel deployment adding up to the whole
layer, a rehearsal of the cell's command (and of the command held to the
float8 control), the shape functions by hand, and the two readers on
hand-built runs."""
import json
import os
import threading
import types

import numpy as np
import pytest

import _entries
from perfbench.families import kimi_linear
from perfbench.harness import program_trace, xplane
from perfbench.harness.spec import Cell, SpecError, sized
from perfbench.harness.traffic import length_population

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "kimi_linear_serve_closed128"
CONFIG = "kimi-linear-48b-a3b-instruct-ep8"
MIX = "closed_loop_reason2k"
# https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct, config.json
# (the catalog's row)
LINEAR = {"full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
          "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                         19, 21, 22, 23, 25, 26],
          "num_heads": 32, "short_conv_kernel_size": 4}
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": LINEAR, "mla_use_nope": True,
    "model_max_length": 1048576, "model_type": "kimi_linear",
    "moe_intermediate_size": 1024, "moe_layer_freq": 1,
    "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
    "num_attention_heads": 32, "num_expert_group": 1, "num_experts": 256,
    "num_experts_per_token": 8, "num_hidden_layers": 27,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
    "num_shared_experts": 1, "q_lora_rank": None, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "routed_scaling_factor": 2.446,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True,
    "v_head_dim": 128, "vocab_size": 163840}
CUT = {"num_hidden_layers": 8, "num_experts": 32, "vocab_size": 20480,
       "model_max_length": 16384,
       "linear_attn_config": dict(LINEAR, full_attn_layers=[4, 8],
                                  kda_layers=[1, 2, 3, 5, 6, 7])}
GB = 1e9


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _config(rehearse=False):
    with open(os.path.join(REPO, "perfbench", "configs",
                           CONFIG + ".json")) as f:
        return sized(json.load(f), rehearse)


def _mix():
    with open(os.path.join(REPO, "perfbench", "traffic",
                           MIX + ".json")) as f:
        return json.load(f)


def _tiny(**over):
    """The rehearsal's size; ``uncut=True`` gives one chip every expert."""
    cfg = _config(rehearse=True)
    if over.pop("uncut", False):
        cfg["n_routed_experts"] = cfg["published"]["num_experts"]
        cfg["deployment"] = dict(cfg["deployment"], expert_rank=0)
    if "rank" in over:
        cfg["deployment"] = dict(cfg["deployment"],
                                 expert_rank=over.pop("rank"))
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def built():
    cfg = _tiny()
    model, params = kimi_linear.build_model(cfg, seed=11)
    return cfg, model, params


# ---------------------------------------------------------------------
# the configuration's file, the mix and the cell
# ---------------------------------------------------------------------

def test_every_published_number_is_in_the_file_and_the_cut_is_named():
    cfg = _config()
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size", "model_max_length",
                              "linear_attn_config"]
    for key, value in PUBLISHED.items():
        assert cfg[key] == CUT.get(key, value), key
        if key in CUT:
            assert cfg["published"][key] == value, key
    assert set(cfg["published"]) == set(CUT)
    # the DeepSeek-V3 names the readers read, for the held share
    assert (cfg["n_routed_experts"], cfg["num_experts_per_tok"],
            cfg["n_shared_experts"]) == (32, 8, 1)
    assert kimi_linear.kda_layers(cfg) == [0, 1, 2, 4, 5, 6]
    assert kimi_linear.mla_layers(cfg) == [3, 7]
    # the floors: a whole period twice, 7 expert layers, 8+ experts, 1/8
    # of the vocabulary
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    for key in ("moe_names", "arithmetic", "weights", "kda", "decoding",
                "replicated_parts", "layout", "model_max_length",
                "e_score_correction_bias"):
        assert cfg["assumed"][key].strip(), key
    assert "float32" in cfg["assumed"]["arithmetic"]
    assert cfg["family"] == "kimi_linear"
    assert cfg["serving_dtype"] == "bfloat16"
    entry = _bench()["configs"][6]
    assert entry["name"] == CONFIG and entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["source"].startswith(
        "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
        "blob/main/config.json")
    assert "model_type kimi_linear" in entry["source"]


def test_the_deployment_holds_the_mixs_longest_request_in_every_slot():
    cfg, mix = _config(), _mix()
    dep = cfg["deployment"]
    assert (dep["chips"], dep["chips_sharing_a_layer"],
            dep["expert_rank"]) == (1, 8, 0)
    assert dep["slots"] == mix["clients"] == max(dep["decode_buckets"])
    assert dep["decode_buckets"] == [64, 128] and dep["slots"] == 128
    assert dep["prefill_buckets"] == [256, 512, 1024, 2048, 4096, 8192]
    assert max(dep["prefill_buckets"]) == mix["prompt_len"]["max"]
    assert dep["block_size"] == 64 and dep["kv_dtype"] == "bfloat16"
    longest = mix["prompt_len"]["max"] + mix["output_len"]["max"]
    assert longest == 16384 == cfg["model_max_length"]
    assert dep["num_blocks"] == 128 * longest // 64 + 1 == 32769
    # the latent rows of the 2 MLA layers in 640 lanes, and the states:
    # the engine keeps a row a slot and the scratch row
    latent = dep["num_blocks"] * 64 * 640 * 2 * 2
    assert latent / GB == pytest.approx(5.37, abs=0.005)
    states = 129 * kimi_linear.state_bytes_per_sequence(cfg)
    assert states / GB == pytest.approx(1.68, abs=0.005)
    assert "state_rows" not in dep and "state_dtype" not in dep
    for key in ("what", "cache_rule"):
        assert dep[key].strip(), key
    # the check's streams fit its width, a multiple of 256 past the
    # longest prompt and the new tokens
    chk = cfg["check"]
    assert chk["width"] == 8448 and chk["width"] % 256 == 0
    assert mix["prompt_len"]["max"] + chk["max_new"] <= chk["width"]
    assert chk["why"].strip()
    assert [r["precision"] for r in chk["references"]] \
        == [kimi_linear.SERVED_TIES + "highest"]
    assert cfg["trace"] == {"paged_attention": "^mla_paged_attention_pallas"}


def test_the_mix_is_the_issues_letter_for_letter():
    mix = _mix()
    assert mix["kind"] == "closed_loop" and mix["clients"] == 128
    assert mix["prompt_len"] == {"median": 1024, "sigma": 1.0, "min": 256,
                                 "max": 8192}
    assert mix["output_len"] == {"median": 2048, "sigma": 0.6, "min": 512,
                                 "max": 8192}
    assert mix["preroll_s"] == 16 and mix["trace_seconds"] == 4
    assert "rehearse" in mix and mix["what"].strip()
    prompts = length_population(mix["prompt_len"])
    outputs = length_population(mix["output_len"])
    assert (len(prompts), min(prompts), max(prompts)) == (96, 256, 8192)
    assert (min(outputs), max(outputs)) == (512, 8192)
    assert sum(prompts) / 96 == pytest.approx(1612, abs=1)
    assert sum(outputs) / 96 == pytest.approx(2431, abs=1)


def entries(bench):
    """What the benchmark holds of the cell, whatever later cells were
    appended after it."""
    entry = _entries.entry_at(bench, "workloads", CELL, 7)
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (CONFIG, MIX, 1)
    assert len(entry["why"]) <= 200
    _entries.entry_at(bench, "configs", CONFIG, 6)
    assert _entries.reported(bench, CELL, "end_to_end") \
        == {"serve_tokens_per_s", "setup_s"}
    new = ["linear_attention_roofline.serve", "linear_attention_ms.serve"]
    mine = _entries.metrics_in_order(bench, new)
    want = [("%", "higher", "kernel tier (kernels/, ops/pallas/)"),
            ("ms", "lower", "serving engine (serving/decode/engine.py)")]
    at = _entries.names(bench["per_layer"]).index(new[0])
    names = {m["layer"] for m in bench["per_layer"][:at]}
    for m, (unit, better, name) in zip(mine, want):
        assert (m["unit"], m["better"], m["source"], m["layer"]) \
            == (unit, better, "device_trace", name)
        assert name in names        # one of the layers the file had
        assert m["moves"] == "serve_tokens_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    _entries.first_of_its_own(bench, CELL, new)
    # one of the four device-clock metrics of harness/serve_programs.py:
    # the fourth's reader keeps the components attention* only, 2 of the
    # cell's 8 layers, and the second and third read the prefills, of
    # which the traced window at 16-20 s holds 1 to 4 and on some seeds
    # none, so they follow the arrivals and not the program
    assert _entries.reported(bench, CELL) == set(new) | {
        "mfu.serve", "paged_attention_roofline.serve",
        "decode_step_ms.serve", "prefill_ms.serve",
        "device_idle_share.serve", "peak_hbm_gb.serve", "itl_p95_ms.closed",
        "kv_write_ms.serve", "host_loop_ms.serve", "slot_occupancy.serve",
        "moe_experts_ms.serve", "expert_tokens_per_step.serve",
        "cache_hit_share.setup", "decode_device_ms.serve"}
    # appended to each list: behind every cell the benchmark had
    _entries.after_earlier_cells(bench, CELL,
                                 _entries.names(bench["workloads"][:7]))
    _entries.among_four_chip_cells(bench, "bert_train_dp4")


def test_the_cell_and_its_two_metrics_are_appended_entries():
    cell = Cell(REPO, CELL)
    assert cell.chips == 1 and cell.family() is kimi_linear
    entries(_bench())
    for reader in ("linear_attention_roofline.serve",
                   "linear_attention_ms.serve"):
        assert callable(cell.layer_reader(reader))


# ---------------------------------------------------------------------
# the reference against the program
# ---------------------------------------------------------------------

def test_reference_agrees_with_the_program_on_the_full_forward(built):
    cfg, model, params = built
    assert model.first_expert == 2 and model.n_held == 2    # rank 1 of 4
    tokens = np.random.RandomState(0).randint(
        0, cfg["vocab_size"], (2, 27)).astype(np.int32)
    ref_params = kimi_linear.reference_params(params, cfg)
    # the served arrays themselves, under the published names
    assert ref_params["model.layers.0.self_attn.A_log"] is params["h0_A_log"]
    assert ref_params["model.layers.3.self_attn.q_proj.weight"] \
        is params["h3_wq"]
    assert "model.layers.3.self_attn.A_log" not in ref_params
    assert "model.layers.0.mlp.gate_proj.weight" in ref_params
    got = np.asarray(model.full_logits(params, tokens))
    want = np.asarray(kimi_linear.make_reference(cfg)(ref_params, tokens))
    assert want.shape == (2, 27, cfg["vocab_size"])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("name,change", [
    ("model.layers.0.self_attn.A_log", lambda a: a + 2.0),
    ("model.layers.1.self_attn.q_conv1d.weight", lambda w: w * 1.5),
    ("model.layers.2.self_attn.b_proj.weight", lambda w: w * 3.0),
    ("model.layers.3.self_attn.kv_b_proj.weight", lambda w: w * 1.5),
    ("model.layers.4.mlp.experts.down_proj.weight", lambda w: w * 1.5)],
    ids=["A_log", "conv", "beta", "kv_b_proj", "experts"])
def test_reference_sees_a_changed_weight(built, name, change):
    cfg, _model, params = built
    tokens = np.random.RandomState(1).randint(
        0, cfg["vocab_size"], (1, 20)).astype(np.int32)
    ref_params = kimi_linear.reference_params(params, cfg)
    reference = kimi_linear.make_reference(cfg)
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

    def prefill_cache(self, params, slabs, tokens, last, table, block_size):
        import jax
        out = self._model.prefill_cache(params, slabs, tokens, last, table,
                                        block_size)
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
    """Solo, and joined mid-batch: the logits of every prefill (the
    chunked scan) and every decode step (the kernel's recurrence over the
    state rows, the latent kernel over the rows) that the engine's own
    programs computed are the reference's full forward at that
    position."""
    import jax
    from mxnet_tpu.serving.decode import DecodeEngine
    cfg, model, params = built
    dep = cfg["deployment"]
    spy = _Spy(model.spec)
    eng = DecodeEngine(spy, params, prefill_buckets=dep["prefill_buckets"],
                       decode_buckets=dep["decode_buckets"],
                       block_size=dep["block_size"],
                       num_blocks=dep["num_blocks"],
                       kv_dtype=dep["kv_dtype"])
    eng.warmup()
    eng.start()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, cfg["vocab_size"], n).tolist()
               for n in (11, 5, 19)]
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
    assert eng.cache.stats()["state_rows_in_use"] == 0
    reference = kimi_linear.make_reference(cfg)
    ref_params = kimi_linear.reference_params(params, cfg)
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
            gaps = [np.abs(got - ref).max() for (_i, at), ref in want.items()
                    if at == pos]
            assert gaps and min(gaps) < 1e-3, (kind, pos, min(gaps))
            checked += 1
    assert checked >= 2 * (1 + 5) + 2 * (1 + 5)


def _check_stream(cfg, prompt=20, seed=5):
    """A stream as the check hands it: the prompt and ``check.max_new``
    generated tokens, padded with 0 to ``check.width``."""
    chk = cfg["check"]
    tokens = np.zeros((1, chk["width"]), np.int32)
    n = prompt + chk["max_new"]
    tokens[0, :n] = np.random.RandomState(seed).randint(
        1, cfg["vocab_size"], n)
    return tokens, prompt, n


def test_the_judged_forward_is_the_served_prefill_and_decode(
        built, monkeypatch, capsys):
    """The forward the check judges takes the prompt through the spec's
    prefill and the generated tokens through its decode steps over a
    cache (the reference's full forward at every position, and the
    decode steps' logits are what the reference's ``decoded_gap`` line
    compares); a fault planted in the decode kernel moves exactly the
    generated tokens' logits."""
    import jax
    import mxnet_tpu.kernels.kda_decode as kda
    cfg, model, params = built
    tokens, prompt, n = _check_stream(cfg)
    got = np.asarray(jax.jit(model.full_logits)(params, tokens))[0]
    ref_params = kimi_linear.reference_params(params, cfg)
    want = np.asarray(kimi_linear.make_reference(
        cfg, kimi_linear.SERVED_TIES + "highest")(ref_params, tokens))[0]
    np.testing.assert_allclose(got[:n], want[:n], rtol=1e-4, atol=1e-4)
    line = [ln for ln in capsys.readouterr().err.splitlines()
            if ln.startswith("decoded_gap")]
    assert len(line) == 1, line
    words = line[0].split()
    assert words[words.index("decode_from") + 1] == str(prompt)
    assert words[words.index("positions") + 1] == str(n - prompt)
    assert float(words[words.index("gap") + 1]) < 1e-4

    real = kda.kda_decode
    monkeypatch.setattr(kda, "kda_decode", lambda q, *a, **kw: (
        lambda o, state: (o * 1.5, state))(*real(q, *a, **kw)))
    planted = np.asarray(jax.jit(model.full_logits)(params, tokens))[0]
    moved = np.abs(planted - got).max(axis=-1)
    assert moved[:prompt].max() == 0.0
    assert moved[prompt:n].min() > 1e-3


def test_the_judged_forward_sees_a_bfloat16_state(built, monkeypatch):
    """The state stored between steps in bfloat16 moves the decode
    steps' logits and no prefill position's; a program that declares it
    is refused."""
    import jax
    from mxnet_tpu.serving.decode import linear_moe
    cfg, model, params = built
    tokens, prompt, n = _check_stream(cfg, seed=6)
    f32 = np.asarray(jax.jit(model.full_logits)(params, tokens))[0]
    monkeypatch.setattr(linear_moe, "STATE_DTYPE", "bfloat16")
    bf16_model = kimi_linear._Served(model.spec, cfg)
    assert model.cache_states()["kda_state"][1] == "bfloat16"
    bf16 = np.asarray(jax.jit(bf16_model.full_logits)(params, tokens))[0]
    moved = np.abs(bf16 - f32).max(axis=-1)
    assert moved[:prompt].max() == 0.0
    assert moved[prompt + 1:n].max() > 1e-3
    # at the cell's width it reads within the float32 state's gaps (PERF.md,
    # PR 38), so the family refuses the program outright
    with pytest.raises(SpecError, match="stores the KDA state in bfloat16"):
        kimi_linear.build_model(cfg, seed=11)


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight experts over four ranks of two, in a KDA layer with experts:
    the four ranks' routed parts, with what every chip computes alike
    (the KDA attention, the shared expert) counted once, equal what the
    uncut reference gives for the whole layer; and each rank's routed
    part is what the program's ``routed_experts`` computes for that
    rank."""
    import jax.numpy as jnp
    from mxnet_tpu.parallel.moe import route_top_k, routed_experts
    uncut = _tiny(uncut=True)
    model, params = kimi_linear.build_model(uncut, seed=9)
    assert model.n_held == 8 and model.is_expert_layer(1)
    assert model.layer_kind(1) == "state"
    x = jnp.asarray(np.random.RandomState(3).normal(
        size=(21, uncut["hidden_size"])).astype(np.float32))

    def layer_of(cfg, first, count, **changed):
        """The reference's block 1 given experts first .. first + count."""
        share = dict(params, **{
            "h1_experts_" + n: params["h1_experts_" + n][first:
                                                         first + count]
            for n in ("gate", "up", "down")})
        return np.asarray(kimi_linear.reference_layer(cfg)(
            dict(kimi_linear.reference_params(share, cfg), **changed), 1, x))

    whole = layer_of(uncut, 0, 8)
    # no expert held: the residual, the KDA attention, the shared expert
    none = _tiny(n_routed_experts=0, rank=0)
    alike = layer_of(none, 0, 0)
    parts = [layer_of(_tiny(rank=r), 2 * r, 2) - alike for r in range(4)]
    assert all(np.abs(part).max() > 1e-3 for part in parts)
    np.testing.assert_allclose(alike + sum(parts), whole, atol=2e-5)
    # the program's expert layer, rank by rank, on the same input
    p = {k[len("h1_"):]: v for k, v in params.items() if k.startswith("h1_")}
    att = layer_of(none, 0, 0, **{
        "model.layers.1.mlp.shared_experts.down_proj.weight":
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


def test_the_float8_control_rounds_its_weights_and_stays_far_off(built):
    import jax.numpy as jnp
    cfg, model, params = built
    tokens = np.random.RandomState(4).randint(
        0, cfg["vocab_size"], (1, 24)).astype(np.int32)
    ref_params = kimi_linear.reference_params(params, cfg)
    # every matmul weight; the embedding is a lookup and the short
    # convolutions no matmul
    rounded = {k: (v.astype(jnp.float8_e4m3fn).astype(v.dtype)
                   if v.ndim >= 2 and "embed" not in k and "conv1d" not in k
                   else v)
               for k, v in ref_params.items()}
    control = np.asarray(kimi_linear.make_reference(
        cfg, kimi_linear.CONTROL_PRECISION)(ref_params, tokens))
    plain = kimi_linear.make_reference(cfg)
    np.testing.assert_allclose(control, np.asarray(plain(rounded, tokens)),
                               atol=1e-4)
    assert np.abs(control - np.asarray(model.full_logits(
        params, tokens))).max() > 0.05


# ---------------------------------------------------------------------
# the command, rehearsed
# ---------------------------------------------------------------------

def test_a_program_without_the_model_fails_the_cell_cleanly(monkeypatch):
    import mxnet_tpu.serving.decode as decode
    monkeypatch.delattr(decode, "LinearLatentMoEDecoder")
    with pytest.raises(SpecError, match="this program cannot run the "
                                        "configuration"):
        kimi_linear.build_model(_tiny(), 0)


def test_the_cells_command_rehearses_and_counts_state_rows(tmp_path):
    from test_perfbench_command import (_copy_of_the_benchmark, _records,
                                        _run)
    root = str(_copy_of_the_benchmark(tmp_path))
    out = _run(["--workload", CELL, "--seed", "3000038007", "--seconds",
                "1", "--trace", "1", "--rehearse"], root=root,
               pythonpath=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    by = {r["event"]: r for r in _records(out)}
    assert by["rehearsed"]["correct"] is True
    assert by["rehearsed"]["failed"] == 0
    check = by["reference_check"]
    assert check["reference_precision"] == "served_ties.highest"
    assert check["tokens_judged"] == check["tokens_equal_reference_argmax"]
    assert check["logit_gap_system_vs_reference"] < 1e-3
    # a CPU trace has no device ops under the program's names
    missing = by["per_layer"]["missing"]
    assert "linear_attention_ms.serve" in missing
    assert "linear_attention_roofline.serve" in missing


def test_the_cell_held_to_the_control_reference_is_not_correct(tmp_path):
    from test_perfbench_command import (_copy_of_the_benchmark, _records,
                                        _run)
    root = _copy_of_the_benchmark(tmp_path)
    path = root / "perfbench/configs" / (CONFIG + ".json")
    cfg = json.load(open(path))
    refs = cfg["rehearse"]["check"]["references"]
    assert [r["precision"] for r in refs] == ["served_ties.highest"]
    refs[0]["precision"] = kimi_linear.SERVED_TIES \
        + kimi_linear.CONTROL_PRECISION
    with open(path, "w") as f:
        json.dump(cfg, f)
    out = _run(["--workload", CELL, "--seed", "7", "--seconds", "1",
                "--trace", "0", "--rehearse"], root=str(root),
               pythonpath=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    records = _records(out)
    why = [r["why"] for r in records if r["event"] == "incorrect"]
    assert why and all(kimi_linear.CONTROL_PRECISION in w for w in why), why
    assert {r["event"]: r for r in records}["rehearsed"]["correct"] is False


# ---------------------------------------------------------------------
# shape functions, by hand
# ---------------------------------------------------------------------

def test_a_token_holds_2304_bytes_and_a_sequence_13_mb_of_state():
    cfg = _config()
    # a latent row of 512 + 64 bf16 values in each of the 2 MLA layers
    assert kimi_linear.kv_bytes_per_token(cfg) == 2 * 576 * 2 == 2304
    # 6 KDA layers x (32 x 128 x 128 float32 + 3 x 12,288 bf16 inputs)
    assert kimi_linear.state_bytes_per_sequence(cfg) \
        == 6 * (2097152 + 73728) == 13025280
    flops, nbytes = kimi_linear.paged_attention_cost(cfg, 1000)
    assert nbytes == 2304 * 1000
    assert flops == 2 * 2 * 32 * (576 + 512) * 1000


def test_matmul_weights_are_the_issues_table():
    n = kimi_linear.matmul_params(_config())
    # q 2304 x 6144, kv_a 2304 x 576, kv_b 512 x 8192, o 4096 x 2304
    assert n["mla"] == 14155776 + 1327104 + 4194304 + 9437184 == 29114368
    # q, k, v, o 2304 x 4096 each; f and g 2304 x 128 x 4096; beta
    assert n["kda"] == 4 * 9437184 + 2 * (294912 + 524288) + 73728 \
        == 39460864
    assert n["expert"] == 3 * 2304 * 1024 == 7077888
    assert n["dense_ffn"] == 3 * 2304 * 9216
    assert n["router"] == 2304 * 256
    # with the convolution, A_log, dt_bias and the norms: the issue's
    # 39.52 M a KDA layer's attention
    assert (n["kda"] + 4 * 12288 + 4096 + 32 + 128 + 2304) / 1e6 \
        == pytest.approx(39.52, abs=0.005)


def test_recurrence_cost_reads_and_writes_each_state_once():
    cfg = _config()
    flops, nbytes = kimi_linear.recurrence_cost(cfg, 1)
    assert flops == 7 * 32 * 128 * 128
    # the state read and written in float32, q, k, v, g, beta and o
    assert nbytes == 2 * 32 * 128 * 128 * 4 + 4 * (4 * 4096 + 32 + 4096)
    assert kimi_linear.recurrence_cost(cfg, 768) \
        == (768 * flops, 768 * nbytes)
    # 128 slots x 6 layers: the issue's 3.3 GB a step, 4.1 ms at 819 GB/s
    assert 768 * 2 * 32 * 128 * 128 * 4 / GB == pytest.approx(3.22, abs=0.01)


def test_served_flops_by_hand():
    cfg = _config()
    n = kimi_linear.matmul_params(cfg)
    share = 8 * 32 / 256
    matmuls = 2 * (6 * n["kda"] + 2 * n["mla"] + n["dense_ffn"]
                   + 7 * (n["router"] + n["expert"] * (1 + share)))
    scan = 6 * (2 * 4 * 3 * 4096 + 7 * 32 * 128 * 128)
    leave = 2 * 2304 * 20480
    pair = 2 * 32 * 2 * (128 + 64 + 128)
    latent = 2 * 2 * 32 * (576 + 512)
    assert kimi_linear.served_flops(cfg, 1, 100, []) \
        == pytest.approx(matmuls + scan + leave + latent * 100, rel=1e-12)
    assert kimi_linear.served_flops(cfg, 0, 0, [10]) == pytest.approx(
        10 * (matmuls + scan) + leave + pair * 55, rel=1e-12)
    assert kimi_linear.served_flops(cfg, 3, 700, [10, 20]) \
        == pytest.approx(33 * (matmuls + scan) + 5 * leave + latent * 700
                         + pair * (55 + 210), rel=1e-12)


# ---------------------------------------------------------------------
# the readers, on hand-built runs
# ---------------------------------------------------------------------

D0 = "/device:TPU:0"
WINDOW = (0.0, 1000e6)


def _fake_run(modules=(), spans=(), ops=(), family=kimi_linear):
    from perfbench.harness.runctx import TraceView
    lines = []
    events = [xplane.Event(xplane.HOST_PLANE, "main", "perfbench.window",
                           WINDOW[0], WINDOW[1] - WINDOW[0], "")]
    events += [xplane.Event(D0, xplane.MODULES_LINE, name, start, dur, "")
               for name, start, dur in modules]
    run = types.SimpleNamespace(
        cell=Cell(REPO, CELL), cfg=_config(), family=family,
        trace=TraceView(events, chips=1) if modules else None,
        counters={}, tracing=True,
        stamp={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        log=types.SimpleNamespace(
            line=lambda **kw: lines.append(kw),
            measurement=lambda event, **kw: lines.append(
                dict(kw, event=event))))
    run._program_trace = program_trace.ProgramTrace(
        list(spans), list(ops), WINDOW, None)
    return run, lines


def _two_steps_and_a_prefill():
    """Two decode executions of 30 ms (in each, KDA layers 0 and 4: 4 ms
    of recurrence, 2 of projections, 1 of the rest; an MLA layer's 3 ms
    that must not count), a 256-token prefill with 2 ms of scan, and a
    decode step cut by the window's end."""
    op = program_trace.Op
    modules = [("jit_mx_decode_b128(1)", 100e6, 30e6),
               ("jit_mx_prefill_b256(2)", 200e6, 40e6),
               ("jit_mx_decode_b64(3)", 300e6, 30e6),
               ("jit_mx_decode_b128(1)", 990e6, 30e6)]
    ops = []
    for start in (100e6, 300e6, 990e6):
        at = start + 1e6
        for name, dur, scope in (
                ("kda_decode_pallas.1", 2e6,
                 ("h0", "linear_attention", "recurrence")),
                ("fusion.2", 1e6, ("h0", "linear_attention", "proj")),
                ("fusion.3", 0.5e6, ("h0", "linear_attention", "gate")),
                ("kda_decode_pallas.1", 2e6,
                 ("h4", "linear_attention", "recurrence")),
                ("fusion.4", 1e6, ("h4", "linear_attention", "proj")),
                ("fusion.5", 0.5e6, ("h4", "linear_attention", "norm")),
                ("mla_paged_attention_pallas.6", 3e6, ("h3", "attention"))):
            ops.append(op(name, at, dur, scope))
            at += dur
    ops.append(op("fusion.7", 205e6, 2e6,
                  ("h0", "linear_attention", "recurrence")))
    ops.append(op("fusion.8", 210e6, 1e6, ("h0", "linear_attention", "conv")))
    span = program_trace.Span
    spans = [span("mx.decode.step", "engine", 99e6, 33e6,
                  {"n": "128", "state_rows": "768"}),
             span("mx.decode.step", "engine", 299e6, 33e6,
                  {"n": "60", "state_rows": 360}),
             # not whole inside the window: left out
             span("mx.decode.step", "engine", 989e6, 33e6,
                  {"n": "128", "state_rows": "768"})]
    return modules, ops, spans


def test_linear_attention_ms_is_a_decode_executions_kda_time():
    read = Cell(REPO, CELL).layer_reader("linear_attention_ms.serve")
    modules, ops, spans = _two_steps_and_a_prefill()
    run, lines = _fake_run(modules, spans, ops)
    assert read(run) == pytest.approx(7.0)
    line = next(ln for ln in lines if ln.get("event") == "linear_attention")
    assert line["found"] is True and line["decode_executions"] == 2
    assert line["decode_ms_a_step"] == pytest.approx(
        {"recurrence": 4.0, "proj": 2.0, "gate": 0.5, "norm": 0.5})
    # the prefill's parts a padded token, in microseconds
    assert line["prefill_us_a_padded_token"] == pytest.approx(
        {"recurrence": 2e3 / 256, "conv": 1e3 / 256})
    assert line["state_rows_a_step"] == pytest.approx((768 + 360) / 2)


def test_linear_attention_roofline_is_the_state_traffic_over_its_time():
    read = Cell(REPO, CELL).layer_reader("linear_attention_roofline.serve")
    modules, ops, spans = _two_steps_and_a_prefill()
    run, lines = _fake_run(modules, spans, ops)
    rows = (768 + 360) / 2
    flops, nbytes = kimi_linear.recurrence_cost(_config(), rows)
    least_ms = 1e3 * max(flops / 197e12, nbytes / 819e9)
    assert read(run) == pytest.approx(100.0 * least_ms / 4.0)
    line = next(ln for ln in lines if ln.get("event") == "roofline")
    # the line names the layer the scope names, whatever family runs it
    assert line["bound"] == "memory" \
        and line["kernel"] == "linear_attention"
    assert line["kernel_ms"] == pytest.approx(4.0)


def test_a_program_without_the_scopes_gives_nothing_and_raises_nothing():
    modules, ops, spans = _two_steps_and_a_prefill()
    bare = [o for o in ops if "linear_attention" not in o.scope]
    for name in ("linear_attention_ms.serve",
                 "linear_attention_roofline.serve"):
        read = Cell(REPO, CELL).layer_reader(name)
        run, lines = _fake_run(modules, spans, bare)
        assert read(run) is None
        assert lines[-1]["event"] == "linear_attention"
        assert lines[-1]["found"] is False
        # no trace at all; a parent whose programs are all ``jit_call``
        assert read(_fake_run(spans=spans, ops=ops)[0]) is None
        parent = [("jit_call(%d)" % i, s, d)
                  for i, (_n, s, d) in enumerate(modules)]
        assert read(_fake_run(parent, spans, ops)[0]) is None
    # steps that carry no count: no roofline, the time still reads
    plain = [program_trace.Span(s.name, s.line, s.start_ns, s.dur_ns,
                                {"n": "4"}) for s in spans]
    run = _fake_run(modules, plain, ops)[0]
    assert Cell(REPO, CELL).layer_reader(
        "linear_attention_roofline.serve")(run) is None
    assert Cell(REPO, CELL).layer_reader(
        "linear_attention_ms.serve")(run) == pytest.approx(7.0)
