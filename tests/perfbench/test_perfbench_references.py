"""The benchmark's two plain references against the program, at a tiny
size on the CPU in float32: the yardstick and the system compute the same
function."""
import json
import os

import numpy as np
import pytest

from perfbench.harness.spec import sized

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _tiny(name):
    with open(os.path.join(REPO, "perfbench", "configs", name + ".json")) as f:
        return sized(json.load(f), rehearse=True)


def test_bert_reference_agrees_with_the_model_zoo_in_float32():
    import mxnet_tpu as mx
    from perfbench.families import bert_mlm
    cfg = _tiny("bert-base-mlm-s512")
    built = bert_mlm.build_model(cfg, seed=11, platform="cpu")
    ids, labels = bert_mlm.make_batches(cfg, 3, seed=12, count=1)[0]
    got = built.loss_fn(built.net(mx.nd.array(ids)),
                        mx.nd.array(labels)).asnumpy().reshape(-1)
    want = bert_mlm.reference_token_losses(
        bert_mlm.reference_params(built.net), ids.astype(np.int64),
        labels.astype(np.int64), cfg)
    assert got.shape == want.shape == (3 * cfg["seq_len"],)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # and under the trainer's AMP policy it stays within bf16's reach
    amp_got = bert_mlm.system_token_losses(built, ids, labels)
    assert abs(amp_got.mean() - want.mean()) < 0.01
    assert np.abs(amp_got - want).max() < 0.1


def test_bert_reference_sees_a_changed_weight():
    from perfbench.families import bert_mlm
    cfg = _tiny("bert-base-mlm-s512")
    built = bert_mlm.build_model(cfg, seed=11, platform="cpu")
    ids, labels = bert_mlm.make_batches(cfg, 2, seed=12, count=1)[0]
    bert_mlm.system_token_losses(built, ids, labels)   # shapes deferred till now
    params = bert_mlm.reference_params(built.net)
    assert len(params) == 15 + 12 * cfg["num_hidden_layers"]
    base = bert_mlm.reference_token_losses(
        params, ids.astype(np.int64), labels.astype(np.int64), cfg)
    params["l0_ffn1_w"] = params["l0_ffn1_w"] * 1.5
    moved = bert_mlm.reference_token_losses(
        params, ids.astype(np.int64), labels.astype(np.int64), cfg)
    assert np.abs(moved - base).max() > 1e-3


def test_gpt2_reference_agrees_with_tinygpt_and_names_the_zero_biases():
    from perfbench.families import gpt2
    cfg = _tiny("gpt2-medium")
    model, params = gpt2.build_model(cfg, seed=5)
    ref_params = gpt2.reference_params(params, cfg)
    tokens = np.random.RandomState(0).randint(
        0, cfg["vocab_size"], (2, 24)).astype(np.int32)
    got = np.asarray(model.full_logits(params, tokens))
    want = np.asarray(gpt2.make_reference(cfg)(ref_params, tokens))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    for i in range(cfg["n_layer"]):
        for bias in ("h%d.attn.c_attn.b" % i, "h%d.attn.c_proj.b" % i):
            assert not np.asarray(ref_params[bias]).any()
    # the reference really has those biases: a non-zero one moves it
    # (not a constant one: every later layer norm would take that out)
    ref_params["h0.attn.c_proj.b"] = ref_params["h0.attn.c_proj.b"] \
        + np.linspace(-0.5, 0.5, cfg["n_embd"], dtype=np.float32)
    moved = np.asarray(gpt2.make_reference(cfg)(ref_params, tokens))
    assert np.abs(moved - want).max() > 1e-3


def test_gpt2_greedy_tokens_are_the_references_argmax():
    from perfbench.families import gpt2
    cfg = _tiny("gpt2-medium")
    model, params = gpt2.build_model(cfg, seed=5)
    prompt = [3, 14, 15, 92, 65]
    out = model.reference_decode(params, prompt, 6)
    tokens = np.asarray([prompt + out], np.int32)
    logits = np.asarray(gpt2.make_reference(cfg)(
        gpt2.reference_params(params, cfg), tokens))
    n = len(prompt)
    assert [int(logits[0, n - 1 + k].argmax()) for k in range(6)] == out


def test_gpt2_family_refuses_a_width_tinygpt_cannot_have():
    from perfbench.families import gpt2
    cfg = dict(_tiny("gpt2-medium"), n_inner=100)
    with pytest.raises(ValueError):
        gpt2.build_model(cfg, seed=0)
