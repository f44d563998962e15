"""AMP cast lists over the op registry.

Reference: ``python/mxnet/contrib/amp/lists/symbol_fp16.py :: FP16_FUNCS,
FP32_FUNCS, WIDEST_TYPE_CASTS``.  The reference enumerates every generated
op; here the lists name registry ops and everything unlisted runs in
whatever dtype its inputs already have (cast-through), which matches the
reference's FP16_FP32_FUNCS behavior.

TPU note: the target dtype is bfloat16 by default -- the MXU's native
input type -- and the FP32 list keeps reductions/normalizations/losses in
fp32 for range safety (bf16 has fp32's exponent, so this list is shorter
than the reference's fp16 one; it is kept for fp16 mode and for
reduction accuracy).
"""

# Ops whose FLOPs dominate and map onto the MXU: run in the target dtype.
TARGET_DTYPE_OPS = [
    "FullyConnected",
    "Convolution",
    "Deconvolution",
    "dot",
    "batch_dot",
    "matmul",
    "einsum",
    "tensordot",
    "RNN",
]

# Ops kept in float32 for accumulation range/precision (reference
# FP32_FUNCS core; softmax/losses).  BatchNorm/LayerNorm are NOT here:
# their kernels accumulate stats in fp32 internally while activations
# stay in the compute dtype (ops/nn.py), which saves two full-tensor
# casts per normalization.  sparse_softmax_ce (the op under
# gluon.loss.SoftmaxCrossEntropyLoss for integer labels over the last
# axis) is NOT here for the same reason: it takes the logits in the
# compute dtype, keeps the maximum, the exponent, the sum and the loss in
# fp32 inside, and its own backward writes the gradient once in the
# logits' dtype -- listed here, the cast's transpose would write that
# gradient in fp32 over the whole vocabulary and copy it back.
FP32_OPS = [
    "L2Normalization",
    "softmax",
    "log_softmax",
    "SoftmaxActivation",
    "SoftmaxOutput",
    "norm",
    "mean",
    "sum",
    "prod",
    "_np_var",
    "_np_std",
    "exp",
    "log",
    "log2",
    "log10",
    "log1p",
    "expm1",
    "erf",
    "erfinv",
    "gamma",
    "gammaln",
    "smooth_l1",
    "MakeLoss",
    "LinearRegressionOutput",
    "LogisticRegressionOutput",
    "MAERegressionOutput",
]

# Elementwise multi-input ops: cast all inputs to the widest dtype present
# (reference WIDEST_TYPE_CASTS).
WIDEST_TYPE_CASTS = [
    "elemwise_add",
    "elemwise_sub",
    "elemwise_mul",
    "elemwise_div",
    "broadcast_add",
    "broadcast_sub",
    "broadcast_mul",
    "broadcast_div",
    "broadcast_mod",
    "broadcast_power",
    "broadcast_maximum",
    "broadcast_minimum",
    "broadcast_hypot",
    "Concat",
    "concat",
    "stack",
    "where",
    "maximum",
    "minimum",
    "add_n",
]
