"""Cross-lower every Pallas kernel for TPU from the CPU.

``jax.export`` with ``platforms=["tpu"]`` runs the Pallas->Mosaic
lowering without a chip, so a block shape the TPU lowering refuses
(second-to-last dim neither a multiple of 8 nor the whole extent, last
dim likewise for 128) fails here, in CPU CI, at the shapes
``chip_smoke.py``'s census runs on the chip.  Whether Mosaic then
compiles the kernel within VMEM only the chip can say.
"""
import jax
import jax.numpy as jnp
import pytest
from jax import export

from mxnet_tpu.ops.pallas.flash_attention import (
    flash_attention_bwd_pallas, flash_attention_fwd_pallas)
from mxnet_tpu.ops.pallas.grouped_matmul import grouped_matmul_pallas
from mxnet_tpu.ops.pallas.kda_decode import kda_decode_pallas
from mxnet_tpu.ops.pallas.mla_paged_attention import (
    mla_paged_attention_pallas)
from mxnet_tpu.ops.pallas.paged_attention import paged_attention_pallas

S = jax.ShapeDtypeStruct
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


def _lowers_to_mosaic(fn, *specs):
    text = export.export(jax.jit(fn), platforms=["tpu"])(*specs) \
        .mlir_module()
    assert "tpu_custom_call" in text


# BERT-base, batch 32 x seq 512: (batch*heads, seq, head_dim)
_QKV = S((32 * 12, 512, 64), BF16)
_LSE = S((32 * 12, 512), F32)
_MASK = S((32, 512, 512), F32)


def test_flash_attention_forward():
    _lowers_to_mosaic(
        lambda q, k, v: flash_attention_fwd_pallas(q, k, v, scale=0.125),
        _QKV, _QKV, _QKV)
    _lowers_to_mosaic(
        lambda q, k, v, m: flash_attention_fwd_pallas(
            q, k, v, m, scale=0.125, heads=12),
        _QKV, _QKV, _QKV, _MASK)


def test_flash_attention_backward():
    _lowers_to_mosaic(
        lambda q, k, v, lse, do, delta: flash_attention_bwd_pallas(
            q, k, v, lse, do, delta, scale=0.125),
        _QKV, _QKV, _QKV, _LSE, _QKV, _LSE)
    _lowers_to_mosaic(
        lambda q, k, v, lse, do, delta, m: flash_attention_bwd_pallas(
            q, k, v, lse, do, delta, m, scale=0.125, heads=12),
        _QKV, _QKV, _QKV, _LSE, _QKV, _LSE, _MASK)


@pytest.mark.parametrize("slots", [1, 2, 8])
def test_paged_attention_every_decode_bucket(slots):
    # the serve phase's cache: 512 blocks of 16 tokens, BERT-base heads,
    # tables wide enough for max_seq 512
    slab = S((512, 16, 12, 64), F32)
    _lowers_to_mosaic(
        lambda q, k, v, bt, cl: paged_attention_pallas(
            q, k, v, bt, cl, scale=0.125),
        S((slots, 12, 64), F32), slab, slab,
        S((slots, 32), I32), S((slots, 1), I32))


@pytest.mark.parametrize("slots", [8, 16])
def test_paged_attention_gpt2_medium_decode_buckets(slots):
    # the GPT-2 cells as ``decode_logits`` calls the kernel: 16 heads of
    # 64 seen through the [..., :64] view of a 128-lane slab of 1,025
    # blocks, a table 64 wide (8 pages a grid step).  Bucket 16 is
    # gpt2m_serve_closed16's every step; gpt2m_serve_open_r80 runs 8 and 16
    slab = S((1025, 16, 16, 128), F32)
    _lowers_to_mosaic(
        lambda q, k, v, bt, cl: paged_attention_pallas(
            q, k[..., :64], v[..., :64], bt, cl, scale=0.125),
        S((slots, 16, 64), F32), slab, slab,
        S((slots, 64), I32), S((slots, 1), I32))


@pytest.mark.parametrize("slots", [16, 32])
def test_mla_paged_attention_kimi_k2_decode_buckets(slots):
    # Kimi-K2's latent row (512 + 64 values in 640 lanes, bf16), 64 heads,
    # blocks of 64 tokens, tables wide enough for 16,384 tokens
    _lowers_to_mosaic(
        lambda q, c, bt, cl: mla_paged_attention_pallas(
            q, c, bt, cl, v_width=512, scale=0.13),
        S((slots, 64, 640), BF16), S((4289, 64, 640), BF16),
        S((slots, 256), I32), S((slots, 1), I32))


@pytest.mark.parametrize("slots", [64, 128])
def test_mla_paged_attention_kimi_linear_decode_buckets(slots):
    # Kimi-Linear's NoPE latent row (512 + 64 values in 640 lanes, bf16),
    # 32 heads, a slab of 32,769 blocks of 64 tokens and tables 256 wide:
    # at 128 slots the largest schedule the kernel takes, 4,096 steps of
    # 8 pages scalar-prefetched beside the context lengths
    _lowers_to_mosaic(
        lambda q, c, bt, cl: mla_paged_attention_pallas(
            q, c, bt, cl, v_width=512, scale=0.07),
        S((slots, 32, 640), BF16), S((32769, 64, 640), BF16),
        S((slots, 256), I32), S((slots, 1), I32))


@pytest.mark.parametrize("window,blocks,table", [(None, 8385, 262),
                                                 (1024, 545, 17)],
                         ids=["full", "window"])
@pytest.mark.parametrize("slots", [16, 32])
def test_paged_attention_mellum2_decode_buckets(slots, window, blocks,
                                                table):
    # mellum2_serve_closed32: 32 query heads over 4 K/V heads of 128, the
    # heads folded into a block's rows (64 tokens x 4 heads), bf16; a full
    # layer's table covers 16,768 tokens (2 pages a step: 262 = 2 x 131),
    # a window layer's is a ring of 17 read 8 pages a step
    slab = S((blocks, 64 * 4, 128), BF16)
    _lowers_to_mosaic(
        lambda q, k, v, bt, cl: paged_attention_pallas(
            q, k, v, bt, cl, scale=128 ** -0.5, window=window,
            block_size=64),
        S((slots, 32, 128), BF16), slab, slab,
        S((slots, table), I32), S((slots, 1), I32))


@pytest.mark.parametrize("slots", [8, 16])
def test_paged_attention_ouro_decode_buckets(slots):
    # ouro_serve_closed16 as ``LoopedDecoder.decode_logits`` calls the
    # kernel: 16 heads of 128 in bfloat16 (a (16, 128) tile a token, so
    # the heads stay a dimension of their own), a layer's slab of 4
    # passes x 81 blocks of 64 tokens, a table 8 wide: one page group a
    # slot, 8 pages a grid step, the pass chosen by the table's entries
    slab = S((324, 64, 16, 128), BF16)
    _lowers_to_mosaic(
        lambda q, k, v, bt, cl: paged_attention_pallas(
            q, k, v, bt + 3 * 81, cl, scale=128 ** -0.5),
        S((slots, 16, 128), BF16), slab, slab,
        S((slots, 8), I32), S((slots, 1), I32))


@pytest.mark.parametrize("slots", [64, 128])
def test_kda_decode_kimi_linear_decode_buckets(slots):
    # kimi_linear_serve_closed128: 32 heads of 128, each slot's whole
    # float32 state row (2 MiB) a grid step, 128 slots + the scratch row
    vec = S((slots, 32, 128), F32)
    _lowers_to_mosaic(kda_decode_pallas, vec, vec, vec, vec, vec,
                      S((129, 32, 128, 128), F32), S((slots,), I32))


_GROUPED = {"gate_up": (2304, 896), "down": (896, 2304)}


@pytest.mark.parametrize("which", sorted(_GROUPED))
def test_grouped_matmul_mellum2_prefill_chunk(which):
    # mellum2_serve_closed32's prefill: 2,048 sorted rows a chunk over 64
    # whole experts of 2,304 x 896 (gate, up) and 896 x 2,304 (down), bf16
    k, n = _GROUPED[which]
    _lowers_to_mosaic(grouped_matmul_pallas, S((2048, k), BF16),
                      S((64, k, n), BF16), S((64,), I32))


# ----------------------------------------------------------------------
# compiled for a described chip: the decode step writes its K/V in place
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    """A v5e chip that is described, not attached (the TPU compiler is
    installed; nothing runs).  Described inside a fixture: only the
    worker that is given this file loads the TPU's library."""
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    # a compile for a described chip is written to the persistent cache
    # and cannot be read back without one: keep it out
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_the_decode_programs_write_the_cache_in_place_on_the_chip(
        one_chip, no_compile_cache, monkeypatch, kind):
    """GPT-2-medium's heads (16 x 64) and block size, two layers: every
    byte of both slabs is aliased, no temporary is slab-sized and no
    instruction copies a slab -- the layout a 64-wide slab would have by
    default is why the slabs are declared in whole 128-lane tiles."""
    import re

    from mxnet_tpu.kernels import registry
    from mxnet_tpu.serving.decode import DecodeEngine, TinyGPT
    monkeypatch.setattr(registry, "_backend", lambda: "tpu")
    model = TinyGPT(vocab_size=512, units=1024, num_layers=2,
                    num_heads=16, max_seq=1024)
    params = jax.eval_shape(model.init_params, 0)
    eng = DecodeEngine(model, params, prefill_buckets=(128,),
                       decode_buckets=(16,), block_size=16, num_blocks=513)
    prefill, decode = eng._specs()
    impl, specs = (eng._decode_impl, decode[16]) if kind == "decode" \
        else (eng._prefill_impl, prefill[128])
    specs = jax.tree.map(
        lambda s: S(s.shape, s.dtype, sharding=one_chip), specs)
    compiled = jax.jit(impl, donate_argnums=eng._DONATED).lower(
        *specs).compile()
    slab = 513 * 16 * 16 * 128 * 4
    assert eng.cache.slab_shapes == {"k": (513, 16, 16, 128),
                                     "v": (513, 16, 16, 128)}
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes == 2 * model.num_layers * slab
    assert stats.temp_size_in_bytes < slab // 2
    text = compiled.as_text()
    assert text.count("tpu_custom_call") \
        == (model.num_layers if kind == "decode" else 0)
    moved = re.findall(r"= f32\[513,16,16,\d+\]\S* (?:copy|slice)\(", text)
    assert not moved, moved[:3]
    assert "remat_" not in text


def test_the_latent_decode_step_writes_its_rows_in_place_on_the_chip(
        one_chip, no_compile_cache, monkeypatch):
    """Kimi-K2's widths, one dense and one expert layer holding two of
    384 experts: the decode program aliases every byte of the latent
    slabs, keeps no slab-sized temporary, runs the latent kernel once a
    layer and the experts as a grouped matmul, and copies no slab."""
    import re

    from mxnet_tpu.kernels import registry
    from mxnet_tpu.serving.decode import DecodeEngine, LatentMoEDecoder
    monkeypatch.setattr(registry, "_backend", lambda: "tpu")
    model = LatentMoEDecoder(
        vocab_size=2048, hidden_size=7168, num_hidden_layers=2,
        num_attention_heads=64, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        intermediate_size=18432, moe_intermediate_size=2048,
        n_routed_experts=384, num_experts_per_tok=8, n_shared_experts=1,
        first_k_dense_replace=1, routed_scaling_factor=2.827,
        rope_theta=50000, rope_scaling={
            "beta_fast": 1, "beta_slow": 1, "factor": 32, "mscale": 1,
            "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
            "type": "yarn"},
        first_expert=0, n_held=2, max_seq=16384)
    params = {name: S(shape, F32 if kind == "bias" else BF16)
              for name, (shape, kind) in model.param_shapes().items()}
    eng = DecodeEngine(model, params, prefill_buckets=(512,),
                       decode_buckets=(16,), block_size=64, num_blocks=2049,
                       kv_dtype="bfloat16")
    assert eng.cache.slab_shapes == {"latent": (2049, 64, 640)}
    assert eng.max_blocks_per_seq == 256
    _prefill, decode = eng._specs()
    specs = jax.tree.map(
        lambda s: S(s.shape, s.dtype, sharding=one_chip), decode[16])
    compiled = jax.jit(eng._decode_impl, donate_argnums=eng._DONATED).lower(
        *specs).compile()
    slab = 2049 * 64 * 640 * 2
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes == model.num_layers * slab
    assert stats.temp_size_in_bytes < slab // 2
    text = compiled.as_text()
    assert len(re.findall(r"mla_paged_attention_pallas\S* = ", text)) \
        == model.num_layers
    assert "ragged" in text              # the grouped matmul, not a loop
    moved = re.findall(r"= bf16\[2049,64,\d+\]\S* (?:copy|slice)\(", text)
    assert not moved, moved[:3]


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_window_and_full_layers_write_both_pools_in_place_on_the_chip(
        one_chip, no_compile_cache, monkeypatch, kind):
    """Mellum2's widths, a window layer and a full one with all 64
    experts: the programs alias every byte of both pools' slabs (declared
    in whole tiles: their bytes are the arithmetic), keep no slab-sized
    temporary, run the kernel once a layer in decode and copy no slab."""
    import re

    from mxnet_tpu.kernels import registry
    from mxnet_tpu.serving.decode import DecodeEngine, WindowMoEDecoder
    monkeypatch.setattr(registry, "_backend", lambda: "tpu")
    model = WindowMoEDecoder(
        vocab_size=2048, hidden_size=2304, num_attention_heads=32,
        num_key_value_heads=4, head_dim=128,
        layer_types=["sliding_attention", "full_attention"],
        sliding_window=1024, rope_parameters={
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}},
        moe_intermediate_size=896, num_experts=64, num_experts_per_tok=8,
        max_seq=16768)
    params = {name: S(shape, BF16)
              for name, (shape, _kind) in model.param_shapes().items()}
    eng = DecodeEngine(model, params, prefill_buckets=(2048,),
                       decode_buckets=(32,), block_size=64, num_blocks=2097,
                       window_blocks=137, kv_dtype="bfloat16")
    assert [a.shape for a in eng.cache.slabs["k"]] \
        == [(137, 256, 128), (2097, 256, 128)]
    # one block is 64 tokens x 2,048 B, K and V, no padding
    slabs = (137 + 2097) * 64 * 2048
    assert eng.cache.slab_bytes() == slabs
    assert eng._table_widths == {"full": 262, "window": 17}
    prefill, decode = eng._specs()
    fn, specs = (eng._decode_impl, decode[32]) if kind == "decode" \
        else (eng._prefill_impl, prefill[2048])
    specs = jax.tree.map(
        lambda s: S(s.shape, s.dtype, sharding=one_chip), specs)
    compiled = jax.jit(fn, donate_argnums=eng._DONATED).lower(
        *specs).compile()
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes == slabs
    # a step keeps less than half a slab; a prefill what one chunk of
    # 8,192 sorted rows takes through an expert layer (the gathered
    # inputs, gate and up in float32, their product, the output)
    assert stats.temp_size_in_bytes < (
        2097 * 64 * 1024 // 2 if kind == "decode"
        else 8192 * (2304 * 2 + 2 * 896 * 4 + 896 * 2 + 2304 * 4))
    text = compiled.as_text()
    assert len(re.findall(r"paged_attention_pallas\S* = ", text)) \
        == (model.num_layers if kind == "decode" else 0)
    # a prompt's tokens go through the grouped-matmul kernel, gate, up
    # and down a layer, where XLA's ragged-dot stood; a step's 32, four
    # to an expert, run every expert over every token (parallel/moe.py).
    # The instructions' names: the text also lists the names of the
    # functions that were on the stack, this process's tests among them
    assert len(re.findall(r"gmm\S* = \S+ custom-call\(", text)) \
        == (3 * model.num_layers if kind == "prefill" else 0)
    assert "ragged-dot" not in text
    moved = re.findall(r"= bf16\[(?:137|2097),256,128\]\S* (?:copy|slice)\(",
                       text)
    assert not moved, moved[:3]


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_a_looped_decoders_passes_write_one_cache_in_place_on_the_chip(
        one_chip, no_compile_cache, monkeypatch, kind):
    """Ouro-2.6B's widths, two layers run four times: the programs alias
    every byte of the slabs (each holds the blocks of all four passes),
    keep no slab-sized temporary, copy and slice no slab, and hold ONE
    kernel call a layer -- the passes are a loop, not four copies."""
    import re

    from mxnet_tpu.kernels import registry
    from mxnet_tpu.serving.decode import DecodeEngine, LoopedDecoder
    monkeypatch.setattr(registry, "_backend", lambda: "tpu")
    model = LoopedDecoder(
        vocab_size=2048, hidden_size=2048, num_attention_heads=16,
        num_key_value_heads=16, head_dim=128, intermediate_size=5632,
        num_hidden_layers=2, total_ut_steps=4, early_exit_threshold=1,
        rope_theta=1000000, max_seq=512)
    params = {name: S(shape, F32 if kind_ == "bias" else BF16)
              for name, (shape, kind_) in model.param_shapes().items()}
    eng = DecodeEngine(model, params, prefill_buckets=(128,),
                       decode_buckets=(16,), block_size=64, num_blocks=81,
                       kv_dtype="bfloat16")
    assert [a.shape for a in eng.cache.slabs["k"]] \
        == [(4 * 81, 64, 16, 128)] * 2
    # 192 x 2 x 16 x 128 x 2 B a token at the whole depth, no padding
    slab = 4 * 81 * 64 * 16 * 128 * 2
    assert eng.cache.slab_bytes() == 2 * 2 * slab
    assert eng.cache.kv_bytes_per_token() * 48 // 2 == 1572864
    assert eng._table_widths == {"full": 8}
    prefill, decode = eng._specs()
    fn, specs = (eng._decode_impl, decode[16]) if kind == "decode" \
        else (eng._prefill_impl, prefill[128])
    specs = jax.tree.map(
        lambda s: S(s.shape, s.dtype, sharding=one_chip), specs)
    compiled = jax.jit(fn, donate_argnums=eng._DONATED).lower(
        *specs).compile()
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes == eng.cache.slab_bytes()
    # a step keeps next to nothing; a prefill the compiler's re-laid
    # copies of two 2,048 x 2,048 weights a layer, hoisted out of the loop
    assert stats.temp_size_in_bytes < (slab // 2 if kind == "decode"
                                       else slab)
    text = compiled.as_text()
    assert len(re.findall(r"paged_attention_pallas\S* = ", text)) \
        == (model.num_layers if kind == "decode" else 0)
    assert len(re.findall(r" while\(", text)) >= 1
    moved = re.findall(
        r"= bf16\[324,64,16,128\]\S* (?:copy|slice|dynamic-slice)\(", text)
    assert not moved, moved[:3]


@pytest.mark.parametrize("which", sorted(_GROUPED))
def test_the_grouped_matmuls_tiles_fit_the_chips_fast_memory(
        one_chip, no_compile_cache, which):
    """The tiles ``ops/pallas/grouped_matmul.py`` chooses at Mellum2's
    widths compile for a v5e: two buffers of each operand and the
    accumulator stay inside what a kernel may use."""
    k, n = _GROUPED[which]

    def on_chip(*shape_dtype):
        return S(*shape_dtype, sharding=one_chip)
    jax.jit(grouped_matmul_pallas).lower(
        on_chip((2048, k), BF16), on_chip((64, k, n), BF16),
        on_chip((64,), I32)).compile()


# ----------------------------------------------------------------------
# compiled for a described chip: the MLM head's loss writes no float32
# array over the vocabulary
# ----------------------------------------------------------------------

@pytest.mark.parametrize("loss,writes_float32", [("sparse_softmax_ce", False),
                                                 ("log_softmax_pick", True)])
def test_the_loss_over_the_vocabulary_keeps_only_the_bf16_logits(
        one_chip, no_compile_cache, loss, writes_float32):
    """BERT-base's MLM decoder (768 -> 30,522, every position of 4 x 512)
    and the loss under bf16 AMP, forward and backward, as ``MLMLoss``
    reshapes them: with the one op the program's temporaries are the bf16
    logits and no instruction of the entry computation writes a float32
    array of rows x vocabulary; ``log_softmax`` + ``pick`` spelled out is
    the control that does (the AMP cast's copy and the softmax's
    backward: 10.7 ms of a 132 ms step on the chip, PERF.md, PR 35)."""
    import re

    import mxnet_tpu as mx
    from mxnet_tpu import amp, gluon
    from mxnet_tpu.ndarray import NDArray
    batch, seq, units, vocab = 4, 512, 768, 30522
    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    def spelled_out(pred, label):
        return -mx.nd.pick(mx.nd.log_softmax(pred), label, axis=-1,
                           keepdims=True)

    loss_fn = ce if loss == "sparse_softmax_ce" else spelled_out

    def head(hidden, weight, bias, labels):
        def summed(hidden, weight, bias):
            with amp.scope("bfloat16"):
                logits = mx.nd.FullyConnected(
                    NDArray(hidden), NDArray(weight), NDArray(bias),
                    num_hidden=vocab, flatten=False)
                rows = loss_fn(logits.reshape((-1, vocab)),
                               NDArray(labels).reshape((-1,)))
            return jnp.sum(rows._data)
        return jax.value_and_grad(summed, argnums=(0, 1, 2))(
            hidden, weight, bias)

    def on_chip(*shape):
        return S(shape, F32, sharding=one_chip)
    compiled = jax.jit(head).lower(
        on_chip(batch, seq, units), on_chip(vocab, units), on_chip(vocab),
        on_chip(batch, seq)).compile()
    logits_bytes = batch * seq * vocab * 2
    entry = compiled.as_text().split("\nENTRY ", 1)[1]
    wide = re.findall(r"= f32\[(?:%d,%d|%d),%d\]\S* (\S+?)\("
                      % (batch, seq, batch * seq, vocab), entry)
    temp = compiled.memory_analysis().temp_size_in_bytes
    if writes_float32:
        assert wide and temp > 3 * logits_bytes, (wide, temp)
    else:
        assert not wide, wide
        assert temp < 1.25 * logits_bytes, temp


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_a_hybrids_states_and_latent_rows_are_written_in_place_on_the_chip(
        one_chip, no_compile_cache, monkeypatch, kind):
    """Kimi Linear's widths, a KDA layer with the dense FFN and a NoPE
    latent layer holding two of 256 experts: both programs alias every
    byte of the latent slab, the state array and the convolution's
    inputs, copy no state array, and a decode step turns the states with
    the KDA kernel once a KDA layer."""
    import re

    from mxnet_tpu.kernels import registry
    from mxnet_tpu.serving.decode import DecodeEngine, LinearLatentMoEDecoder
    monkeypatch.setattr(registry, "_backend", lambda: "tpu")
    model = LinearLatentMoEDecoder(
        vocab_size=2048, hidden_size=2304, num_hidden_layers=2,
        num_attention_heads=32, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, intermediate_size=9216,
        moe_intermediate_size=1024, n_routed_experts=256,
        num_experts_per_tok=8, n_shared_experts=1, first_k_dense_replace=1,
        routed_scaling_factor=2.446, linear_attn_config={
            "kda_layers": [1], "full_attn_layers": [2], "num_heads": 32,
            "head_dim": 128, "short_conv_kernel_size": 4},
        first_expert=0, n_held=2, max_seq=16384)
    params = {name: S(shape, BF16 if kind_ == "norm" or isinstance(
                  kind_, int) else F32)
              for name, (shape, kind_) in model.param_shapes().items()}
    eng = DecodeEngine(model, params, prefill_buckets=(256,),
                       decode_buckets=(16,), block_size=64, num_blocks=257,
                       kv_dtype="bfloat16")
    assert [a.shape for a in eng.cache.slabs["kda_state"]] \
        == [(17, 32, 128, 128)]
    # the convolution's 3 x 3 x 4,096 inputs a row in rows of 128 lanes
    assert [a.shape for a in eng.cache.slabs["kda_conv"]] == [(17, 288, 128)]
    slabs = 257 * 64 * 640 * 2 + 17 * 32 * 128 * 128 * 4 \
        + 17 * 3 * 3 * 4096 * 2
    assert eng.cache.slab_bytes() == slabs
    prefill, decode = eng._specs()
    fn, specs = (eng._decode_impl, decode[16]) if kind == "decode" \
        else (eng._prefill_impl, prefill[256])
    specs = jax.tree.map(
        lambda s: S(s.shape, s.dtype, sharding=one_chip), specs)
    compiled = jax.jit(fn, donate_argnums=eng._DONATED).lower(
        *specs).compile()
    assert compiled.memory_analysis().alias_size_in_bytes == slabs
    text = compiled.as_text()
    assert len(re.findall(r"kda_decode_pallas\S* = ", text)) \
        == (1 if kind == "decode" else 0)
    assert len(re.findall(r"mla_paged_attention_pallas\S* = ", text)) \
        == (1 if kind == "decode" else 0)
    moved = re.findall(r"= f32\[17,32,128,128\]\S* (?:copy|slice)\(", text)
    assert not moved, moved[:3]
    # the convolution's inputs are written as whole rows (one scatter),
    # not by a loop of one-row updates
    assert not re.findall(r"dynamic-update-slice\S* = bf16\[17,", text) \
        or kind == "prefill"
