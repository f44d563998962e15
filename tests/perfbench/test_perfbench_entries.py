"""Each family's entry check holds on ``BENCHMARK.json`` with one more cell
appended, as the next configuration of a new family would append it: a
serving configuration, a serving cell in every list that holds a serving
cell (the accepted readers of the linear-attention layers, of Ouro's loop
and of the device clock among them), a per-layer metric of its own at the
end of ``per_layer``, and a second four-chip cell, which ten cells
allow."""
import copy
import json
import os

import pytest

import _entries
import test_perfbench_kimi_k2
import test_perfbench_kimi_linear
import test_perfbench_mellum
import test_perfbench_ouro
import test_perfbench_serve_programs
import test_perfbench_units

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SERVE = "synthetic_serve_closed8"
FOUR = "synthetic_train_dp4"


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _appended(bench):
    out = copy.deepcopy(bench)
    out["configs"].append({
        "name": "synthetic-serve", "source": "https://example.org/config",
        "file": "perfbench/configs/synthetic-serve.json", "reduced": []})
    out["workloads"] += [
        {"name": SERVE, "config": "synthetic-serve",
         "traffic": "closed_loop", "chips": 1, "why": "a serving cell"},
        {"name": FOUR, "config": "bert-base-mlm-s512-dp4",
         "traffic": "synthetic_stream", "chips": 4,
         "why": "a second four-chip cell"}]
    serving = set(next(m for m in out["end_to_end"]
                       if m["name"] == "serve_tokens_per_s")["workloads"])
    for m in out["end_to_end"] + out["per_layer"]:
        lists = m.get("workloads")
        if lists and serving & set(lists):
            lists.append(SERVE)
        if lists and "bert_train_dp4" in lists:
            lists.append(FOUR)
    out["per_layer"].append({
        "name": "synthetic_ms.serve", "unit": "ms", "better": "lower",
        "source": "device_trace",
        "layer": "serving engine (serving/decode/engine.py)",
        "moves": "serve_tokens_per_s", "workloads": [SERVE]})
    return out


def test_the_copy_is_a_benchmark_the_contract_allows():
    bench, out = _bench(), _appended(_bench())
    test_perfbench_units.contract(out)
    assert len(out["workloads"]) == len(bench["workloads"]) + 2
    # appended after every accepted entry, which keep their places
    for kind in ("configs", "workloads", "per_layer"):
        was = _entries.names(bench[kind])
        assert _entries.names(out[kind])[:len(was)] == was
    for m, was in zip(out["end_to_end"] + out["per_layer"],
                      bench["end_to_end"] + bench["per_layer"]):
        was = was.get("workloads", [])
        assert m.get("workloads", [])[:len(was)] == was, m["name"]
    for name in ("serve_tokens_per_s", "linear_attention_roofline.serve",
                 "linear_attention_ms.serve", "loop_pass_ms.serve",
                 "decode_device_ms.serve", "prefill_device_share.serve",
                 "prefill_us_per_token.serve",
                 "prefill_attention_us_per_token.serve"):
        assert SERVE in next(m for m in out["end_to_end"] + out["per_layer"]
                             if m["name"] == name)["workloads"], name


@pytest.mark.parametrize("check", [
    test_perfbench_kimi_k2.entries,
    test_perfbench_mellum.entries,
    test_perfbench_ouro.entries,
    test_perfbench_kimi_linear.entries,
    test_perfbench_serve_programs.entries,
], ids=["kimi_k2", "mellum", "ouro", "kimi_linear", "serve_programs"])
def test_an_appended_cell_breaks_no_accepted_entry_test(check):
    check(_bench())
    check(_appended(_bench()))
