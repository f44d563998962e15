"""Neural-network operators.

TPU-native re-design of the reference's ``src/operator/nn/`` tree
(``convolution.cc``, ``fully_connected.cc``, ``batch_norm.cc``,
``pooling.cc``, ``activation.cc``, ``softmax.cc``, ``layer_norm.cc``,
``dropout.cc``, ``deconvolution.cc``, ``upsampling.cc``) and the cuDNN
variants under ``src/operator/nn/cudnn/``.  On TPU the "cuDNN fast path" is
XLA itself: convs and matmuls lower to MXU ops, normalization/activation
chains fuse into them.  Stateful-looking ops are functional here:

- BatchNorm *returns* updated running stats (``num_diff_outputs=1``); the
  Gluon layer rebinds its aux parameters (the reference mutates aux states
  in-place via the engine's mutable vars).
- Dropout and random samplers are ``stateful_rng``: the dispatcher injects
  a PRNG key as the first argument (the reference draws from the per-device
  ResourceManager RNG, ``src/resource.cc``).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .registry import register


def _pair(v, n=2):
    if isinstance(v, (tuple, list)):
        return tuple(int(x) for x in v)
    return (int(v),) * n


# ----------------------------------------------------------------------
# Dense / conv / pooling
# ----------------------------------------------------------------------

@register("FullyConnected", args=("data", "weight", "bias"))
def _fully_connected(data, weight, bias, num_hidden=0, no_bias=False, flatten=True):
    """Dense layer (reference: ``src/operator/nn/fully_connected.cc``).

    weight has shape (num_hidden, in_units) as in the reference; the matmul
    contracts data's trailing axis with weight's trailing axis (MXU-friendly
    single dot_general).
    """
    if flatten and data.ndim > 2:
        data = data.reshape(data.shape[0], -1)
    out = lax.dot_general(data, weight, (((data.ndim - 1,), (1,)), ((), ())))
    if not no_bias and bias is not None:
        out = out + bias
    return out


_DEFAULT_LAYOUTS = {3: "NCH", 4: "NCHW", 5: "NCDHW"}


def _conv_dnums(ndim, layout=None):
    """Dimension-number strings for a data layout.

    The weight layout follows the reference's convention: the data layout
    string with N->O and C->I (NCHW -> OIHW, NHWC -> OHWI, ...).
    """
    if not layout:
        layout = _DEFAULT_LAYOUTS.get(ndim)
    if layout is None or len(layout) != ndim:
        raise MXNetError("Convolution: unsupported input rank %d / layout %r"
                         % (ndim, layout))
    rhs = layout.replace("N", "O").replace("C", "I")
    return (layout, rhs, layout)


def _bias_bshape(ndim, layout):
    c_axis = layout.index("C") if layout else 1
    shape = [1] * ndim
    shape[c_axis] = -1
    return tuple(shape)


@register("Convolution", args=("data", "weight", "bias"))
def _convolution(data, weight, bias, kernel=(), stride=(), dilate=(), pad=(),
                 num_filter=0, num_group=1, no_bias=False, layout="NCHW"):
    """N-D convolution (reference: ``src/operator/nn/convolution.cc``).

    Lowers to one ``lax.conv_general_dilated`` -- XLA tiles it onto the MXU
    (the reference dispatches to cuDNN ``cudnn_convolution-inl.h``).

    ``layout`` follows the reference's semantics: it names the data (and
    derived weight) layout, e.g. NCHW (weight OIHW) or NHWC (weight OHWI).
    On TPU channels-last is the fast path -- the channel dim lands in the
    128-wide lane dimension of the (8, 128) vector tiles, so 56x56
    activations don't pad the 128-lane minor dim the way W=56 does in
    NCHW.
    """
    nsp = data.ndim - 2
    stride = _pair(stride, nsp) if stride else (1,) * nsp
    dilate = _pair(dilate, nsp) if dilate else (1,) * nsp
    pad = _pair(pad, nsp) if pad else (0,) * nsp
    if layout and len(layout) != data.ndim:
        layout = _DEFAULT_LAYOUTS.get(data.ndim)
    if not jnp.issubdtype(data.dtype, jnp.floating) and \
            jnp.issubdtype(weight.dtype, jnp.floating):
        # uint8 image batches convolve in the weight dtype (the pipeline
        # ships uint8 to the device and casts there -- 4x less transfer)
        data = data.astype(weight.dtype)
    dn = lax.conv_dimension_numbers(data.shape, weight.shape,
                                    _conv_dnums(data.ndim, layout))
    out = lax.conv_general_dilated(
        data, weight, window_strides=stride,
        padding=[(p, p) for p in pad], rhs_dilation=dilate,
        dimension_numbers=dn, feature_group_count=num_group)
    if not no_bias and bias is not None:
        out = out + bias.reshape(_bias_bshape(data.ndim, layout))
    return out


@register("Deconvolution", args=("data", "weight", "bias"))
def _deconvolution(data, weight, bias, kernel=(), stride=(), dilate=(), pad=(),
                   adj=(), num_filter=0, num_group=1, no_bias=True, layout="NCHW"):
    """Transposed convolution (reference: ``deconvolution.cc``).

    Implemented as the gradient of Convolution (lhs-dilated conv), matching
    the reference's definition.  Weight shape (in_c, out_c/groups, *k).
    """
    nsp = data.ndim - 2
    if layout and len(layout) == data.ndim \
            and layout.index("C") == data.ndim - 1:
        # channels-last: run the channels-first path on transposed operands
        # (deconv is never the hot op; correctness over layout tuning)
        perm = (0, data.ndim - 1) + tuple(range(1, data.ndim - 1))
        inv = (0,) + tuple(range(2, data.ndim)) + (1,)
        wperm = (0, weight.ndim - 1) + tuple(range(1, weight.ndim - 1))
        out = _deconv_channels_first(
            jnp.transpose(data, perm), jnp.transpose(weight, wperm), bias,
            stride=stride, dilate=dilate, pad=pad, adj=adj,
            num_group=num_group, no_bias=no_bias)
        return jnp.transpose(out, inv)
    return _deconv_channels_first(data, weight, bias, stride=stride,
                                  dilate=dilate, pad=pad, adj=adj,
                                  num_group=num_group, no_bias=no_bias)


def _deconv_channels_first(data, weight, bias, stride=(), dilate=(), pad=(),
                           adj=(), num_group=1, no_bias=True):
    nsp = data.ndim - 2
    stride = _pair(stride, nsp) if stride else (1,) * nsp
    dilate = _pair(dilate, nsp) if dilate else (1,) * nsp
    pad = _pair(pad, nsp) if pad else (0,) * nsp
    adj = _pair(adj, nsp) if adj else (0,) * nsp
    k = weight.shape[2:]
    # effective kernel extent
    keff = [d * (kk - 1) + 1 for kk, d in zip(k, dilate)]
    padding = [(keff[i] - 1 - pad[i], keff[i] - 1 - pad[i] + adj[i])
               for i in range(nsp)]
    # flip spatial dims, swap I/O channels
    w = jnp.flip(weight, axis=tuple(range(2, weight.ndim)))
    if num_group > 1:
        ic = weight.shape[0]
        w = w.reshape((num_group, ic // num_group) + w.shape[1:])
        w = jnp.swapaxes(w, 1, 2)
        w = w.reshape((num_group * w.shape[1], ic // num_group) + w.shape[3:])
    else:
        w = jnp.swapaxes(w, 0, 1)
    dn = lax.conv_dimension_numbers(data.shape, w.shape, _conv_dnums(data.ndim))
    out = lax.conv_general_dilated(
        data, w, window_strides=(1,) * nsp, padding=padding,
        lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=num_group)
    if not no_bias and bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nsp)
    return out


@register("Pooling", args=("data",))
def _pooling(data, kernel=(), pool_type="max", stride=(), pad=(),
             global_pool=False, count_include_pad=True,
             pooling_convention="valid", layout="NCHW"):
    """Max/avg/sum/lp pooling (reference: ``src/operator/nn/pooling.cc``).

    ``layout`` names the data layout (NCHW/NHWC/...); the pooling window
    spans its spatial dims.
    """
    nsp = data.ndim - 2
    if not layout or len(layout) != data.ndim:
        layout = _DEFAULT_LAYOUTS.get(data.ndim, "NCHW")
    sp_axes = [i for i, c in enumerate(layout) if c not in ("N", "C")]
    sp_sizes = [data.shape[i] for i in sp_axes]
    if global_pool:
        kernel = tuple(sp_sizes)
        stride = (1,) * nsp
        pad = (0,) * nsp
    else:
        kernel = _pair(kernel, nsp)
        stride = _pair(stride, nsp) if stride else (1,) * nsp
        pad = _pair(pad, nsp) if pad else (0,) * nsp
    window = [1] * data.ndim
    strides = [1] * data.ndim
    padding = [(0, 0)] * data.ndim
    for j, ax in enumerate(sp_axes):
        window[ax] = kernel[j]
        strides[ax] = stride[j]
        padding[ax] = (pad[j], pad[j])
    if pooling_convention == "full":
        # ceil-mode: extend right/bottom padding so ragged edges are kept
        for j, ax in enumerate(sp_axes):
            size = sp_sizes[j] + 2 * pad[j] - kernel[j]
            rem = size % stride[j]
            extra = stride[j] - rem if rem else 0
            padding[ax] = (pad[j], pad[j] + extra)
    window = tuple(window)
    strides = tuple(strides)
    padding = tuple(padding)
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).min
        return lax.reduce_window(data, init, lax.max, window, strides, padding)
    if pool_type in ("avg", "sum"):
        summed = lax.reduce_window(data, 0.0, lax.add, window, strides, padding)
        if pool_type == "sum":
            return summed
        if count_include_pad:
            return summed / float(np.prod(kernel))
        ones = jnp.ones_like(data)
        counts = lax.reduce_window(ones, 0.0, lax.add, window, strides, padding)
        return summed / counts
    if pool_type == "lp":
        p = 2.0
        s = lax.reduce_window(jnp.abs(data) ** p, 0.0, lax.add, window, strides, padding)
        return s ** (1.0 / p)
    raise MXNetError("Pooling: bad pool_type %r" % pool_type)


@register("UpSampling", args=("data",), variadic=True)
def _upsampling(*data, scale=1, sample_type="nearest", num_args=1):
    """Reference: ``src/operator/upsampling.cc`` (nearest mode)."""
    x = data[0]
    if sample_type == "nearest":
        out = jnp.repeat(jnp.repeat(x, scale, axis=2), scale, axis=3)
        return out
    return jax.image.resize(
        x, x.shape[:2] + (x.shape[2] * scale, x.shape[3] * scale), "bilinear")


@register("BilinearResize2D", args=("data",))
def _bilinear_resize(data, height=0, width=0, scale_height=None, scale_width=None):
    """Reference: ``contrib/bilinear_resize.cc``."""
    h = int(data.shape[2] * scale_height) if scale_height else height
    w = int(data.shape[3] * scale_width) if scale_width else width
    return jax.image.resize(data, data.shape[:2] + (h, w), "bilinear")


# ----------------------------------------------------------------------
# Normalization
# ----------------------------------------------------------------------

@register("BatchNorm", args=("data", "gamma", "beta", "moving_mean", "moving_var"),
          num_diff_outputs=1)
def _batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-5,
                momentum=0.9, fix_gamma=True, use_global_stats=False,
                axis=1, output_mean_var=False, training=False):
    """Batch normalization (reference: ``src/operator/nn/batch_norm.cc``).

    Functional form: returns ``(out, new_moving_mean, new_moving_var)``.
    The reference mutates the moving stats through the engine's mutable
    aux vars; here the Gluon BatchNorm layer rebinds its aux Parameters
    with the returned values (and the hybridize tracer threads them as
    loop-carried state).
    """
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    reduce_axes = tuple(i for i in range(data.ndim) if i != axis)
    bshape = [1] * data.ndim
    bshape[axis] = data.shape[axis]
    # Stats accumulate in fp32 even for bf16 activations (AMP): the
    # upcast fuses into the reduction, so activations stay bf16 in HBM
    # while the mean/var math is exact enough.
    xf = data.astype(jnp.float32)
    if training and not use_global_stats:
        # Shifted one-pass moments: E[(x-c)^2] - E[x-c]^2 with the
        # per-channel shift c = moving_mean.  The two reductions are
        # independent, so XLA fuses them into ONE read pass over the
        # activation (jnp.var's (x - mean)^2 form depends on the mean
        # and forces a second pass); the shift bounds the catastrophic
        # cancellation of the naive E[x^2]-E[x]^2 form when |mean| >>
        # std (large-offset inputs), since moving_mean tracks the batch
        # mean and |E[x-c]| stays near zero in steady state.
        c = lax.stop_gradient(moving_mean.astype(jnp.float32)) \
            .reshape(bshape)
        y = xf - c
        mean_y = jnp.mean(y, axis=reduce_axes)
        m2 = jnp.mean(y * y, axis=reduce_axes)
        var = jnp.maximum(m2 - mean_y * mean_y, 0.0)
        mean = mean_y + c.reshape(mean_y.shape)
        # EMA blended in fp32, stored back at the aux dtype: with bf16
        # running stats the weak-typed ``momentum * moving_mean``
        # product would round at bf16 (8 mantissa bits) every step,
        # and (1 - momentum) = 0.1-ish deltas drop below the store's
        # resolution after a few hundred steps.
        new_mean = (momentum * moving_mean.astype(jnp.float32)
                    + (1 - momentum) * mean).astype(moving_mean.dtype)
        new_var = (momentum * moving_var.astype(jnp.float32)
                   + (1 - momentum) * var).astype(moving_var.dtype)
    else:
        # eval path: upcast BEFORE the eps add -- in bf16,
        # var + 1e-5 == var exactly, and rsqrt would run at 8 mantissa
        # bits
        mean = moving_mean.astype(jnp.float32)
        var = moving_var.astype(jnp.float32)
        new_mean, new_var = moving_mean, moving_var
    inv = (lax.rsqrt(var + eps) * g).astype(jnp.float32)
    out = (xf - mean.reshape(bshape).astype(jnp.float32)) \
        * inv.reshape(bshape) + beta.reshape(bshape).astype(jnp.float32)
    return (out.astype(data.dtype), lax.stop_gradient(new_mean),
            lax.stop_gradient(new_var))


@register("LayerNorm", args=("data", "gamma", "beta"))
def _layer_norm(data, gamma, beta, axis=-1, eps=1e-5):
    """Layer normalization (reference: ``src/operator/nn/layer_norm.cc``).

    Written so XLA fuses the whole thing into one elementwise pass.
    Stats accumulate in fp32 for bf16 activations.
    """
    xf = data.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axis, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=axis, keepdims=True)
    out = (xf - mean) * lax.rsqrt(var + eps)
    bshape = [1] * data.ndim
    bshape[axis] = data.shape[axis]
    out = out * gamma.reshape(bshape).astype(jnp.float32) \
        + beta.reshape(bshape).astype(jnp.float32)
    return out.astype(data.dtype)


@register("InstanceNorm", args=("data", "gamma", "beta"))
def _instance_norm(data, gamma, beta, eps=1e-3):
    """Reference: ``src/operator/instance_norm.cc``."""
    axes = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=axes, keepdims=True)
    var = jnp.var(data, axis=axes, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    return out * gamma.reshape(bshape) + beta.reshape(bshape)


@register("GroupNorm", args=("data", "gamma", "beta"))
def _group_norm(data, gamma, beta, num_groups=1, eps=1e-5):
    """Reference: ``contrib/group_norm (?v1.6)``; NCHW layout."""
    n, c = data.shape[:2]
    x = data.reshape((n, num_groups, c // num_groups) + data.shape[2:])
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    x = (x - mean) * lax.rsqrt(var + eps)
    x = x.reshape(data.shape)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    return x * gamma.reshape(bshape) + beta.reshape(bshape)


# ----------------------------------------------------------------------
# Activations / softmax
# ----------------------------------------------------------------------

@register("Activation", args=("data",))
def _activation(data, act_type="relu"):
    """Reference: ``src/operator/nn/activation.cc``."""
    if act_type == "relu":
        return jax.nn.relu(data)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return jax.nn.soft_sign(data)
    if act_type == "log_sigmoid":
        return jax.nn.log_sigmoid(data)
    if act_type == "mish":
        return data * jnp.tanh(jax.nn.softplus(data))
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "gelu_tanh":
        return jax.nn.gelu(data, approximate=True)
    raise MXNetError("Activation: bad act_type %r" % act_type)


@register("LeakyReLU", args=("data",))
def _leaky_relu(data, act_type="leaky", slope=0.25, lower_bound=0.125, upper_bound=0.334):
    """Reference: ``src/operator/leaky_relu.cc`` (prelu is ``_prelu``)."""
    if act_type == "leaky":
        return jnp.where(data > 0, data, slope * data)
    if act_type == "elu":
        return jnp.where(data > 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        return jax.nn.selu(data)
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    raise MXNetError("LeakyReLU: bad act_type %r" % act_type)


@register("_prelu", args=("data", "gamma"))
def _prelu(data, gamma):
    bshape = [1] * data.ndim
    if data.ndim > 1:
        bshape[1] = -1
    else:
        bshape[0] = -1
    return jnp.where(data > 0, data, gamma.reshape(bshape) * data)


@register("softmax", args=("data",), aliases=("SoftmaxActivation",))
def _softmax(data, axis=-1, temperature=None):
    """Reference: ``src/operator/nn/softmax.cc``."""
    if temperature is not None and temperature != 1.0:
        data = data / temperature
    return jax.nn.softmax(data, axis=axis)


@register("log_softmax", args=("data",))
def _log_softmax(data, axis=-1, temperature=None):
    if temperature is not None and temperature != 1.0:
        data = data / temperature
    return jax.nn.log_softmax(data, axis=axis)


@register("softmin", args=("data",))
def _softmin(data, axis=-1):
    return jax.nn.softmax(-data, axis=axis)


def _softmax_output_fwd(data, label, grad_scale, ignore_label, use_ignore,
                        multi_output, normalization):
    if multi_output:
        prob = jax.nn.softmax(data, axis=1)
    else:
        prob = jax.nn.softmax(data, axis=-1)
    return prob


import functools as _functools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _softmax_output_core(data, label, grad_scale, ignore_label, use_ignore,
                         multi_output, normalization_code):
    # flags are static (nondiff_argnums): they steer Python control flow
    # and must not be abstracted by custom_vjp tracing
    return _softmax_output_fwd(data, label, grad_scale, ignore_label,
                               use_ignore, multi_output, normalization_code)


def _softmax_output_core_fwd(data, label, grad_scale, ignore_label,
                             use_ignore, multi_output, normalization_code):
    prob = _softmax_output_fwd(data, label, grad_scale, ignore_label,
                               use_ignore, multi_output, normalization_code)
    return prob, (prob, label)


def _softmax_output_core_bwd(grad_scale, ignore_label, use_ignore,
                             multi_output, norm_code, res, g):
    prob, label = res
    # The defining property of SoftmaxOutput (reference:
    # src/operator/softmax_output.cc): backward ignores the incoming
    # cotangent and emits (prob - one_hot(label)) * grad_scale.
    axis = 1 if multi_output else -1
    nclass = prob.shape[axis]
    onehot = jax.nn.one_hot(label.astype(jnp.int32), nclass, dtype=prob.dtype)
    if multi_output:
        onehot = jnp.moveaxis(onehot, -1, 1)
    grad = (prob - onehot)
    if use_ignore:
        mask = (label != ignore_label).astype(prob.dtype)
        mask = jnp.expand_dims(mask, axis=axis)
        grad = grad * mask
    if norm_code == 1:  # batch
        grad = grad / prob.shape[0]
    elif norm_code == 2:  # valid
        if use_ignore:
            valid = jnp.maximum(jnp.sum(label != ignore_label), 1)
        else:
            valid = label.size
        grad = grad / valid
    return (grad * grad_scale, jnp.zeros_like(label))


_softmax_output_core.defvjp(_softmax_output_core_fwd, _softmax_output_core_bwd)


@register("SoftmaxOutput", args=("data", "label"))
def _softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                    use_ignore=False, multi_output=False, normalization="null"):
    """Softmax with built-in cross-entropy gradient (reference:
    ``src/operator/softmax_output.cc``): forward = softmax(data); backward
    writes ``(p - onehot(label)) * grad_scale`` regardless of head grad.
    """
    norm_code = {"null": 0, "batch": 1, "valid": 2}[normalization]
    return _softmax_output_core(data, label, grad_scale, ignore_label,
                                use_ignore, multi_output, norm_code)


@register("softmax_cross_entropy", args=("data", "label"))
def _softmax_cross_entropy(data, label):
    """Reference: ``src/operator/loss_binary_op.cc``; summed CE over batch."""
    logp = jax.nn.log_softmax(data, axis=-1)
    picked = jnp.take_along_axis(logp, label.astype(jnp.int32)[:, None], axis=-1)
    return -jnp.sum(picked)


def _ce_label_hit(data, label):
    """Where each row's class is its label's: labels cast to int32 and
    clipped into the class range (the reference's pick mode="clip")."""
    nclass = data.shape[-1]
    idx = jnp.clip(label.astype(jnp.int32), 0, nclass - 1)
    classes = lax.broadcasted_iota(jnp.int32, data.shape, data.ndim - 1)
    return classes == idx[..., None]


def _sparse_softmax_ce_rows(data, label):
    """Per-row loss and log-sum-exp, both float32 whatever ``data`` is."""
    with jax.named_scope("softmax_ce"):
        x = data.astype(jnp.float32)
        m = jnp.max(x, axis=-1, keepdims=True)
        lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(x - m), axis=-1))
        # the label's logit as a masked sum, a reduction like the one
        # beside it: a gather makes XLA lay the logits out anew for it,
        # in float32
        hit = _ce_label_hit(data, label)
        return lse - jnp.sum(jnp.where(hit, x, 0.0), axis=-1), lse


@jax.custom_vjp
def _sparse_softmax_ce_core(data, label):
    return _sparse_softmax_ce_rows(data, label)[0]


def _sparse_softmax_ce_core_fwd(data, label):
    loss, lse = _sparse_softmax_ce_rows(data, label)
    # the residuals: the logits as they arrived, the labels and one
    # float32 a row -- never a float32 array over the classes
    return loss, (data, label, lse)


def _sparse_softmax_ce_core_bwd(res, g):
    data, label, lse = res
    # the transposed ops of a custom_vjp are traced here, outside the
    # forward's scope: name them, so a trace still files them under it
    with jax.named_scope("softmax_ce"):
        p = jnp.exp(data.astype(jnp.float32) - lse[..., None])
        grad = jnp.where(_ce_label_hit(data, label), p - 1.0, p)
        # ONE pass: float32 in registers, written once in data's dtype
        return (grad * g[..., None]).astype(data.dtype), None


_sparse_softmax_ce_core.defvjp(_sparse_softmax_ce_core_fwd,
                               _sparse_softmax_ce_core_bwd)


@register("sparse_softmax_ce", args=("data", "label"))
def _sparse_softmax_ce(data, label, keepdims=False):
    """Softmax cross-entropy of integer class labels over the LAST axis,
    one float32 loss a row: ``logsumexp(data) - data[label]``.

    ``data`` is taken in the dtype it arrives in (bf16/fp16 under AMP:
    the op is NOT in ``amp.lists.FP32_OPS``); the maximum, the exponent,
    the sum and the loss are float32 inside, as the norms keep their
    statistics.  Its own backward (``jax.custom_vjp``) keeps ``data``,
    ``label`` and the float32 log-sum-exp of each row, and writes
    ``(softmax(data) - onehot(label)) * g`` once, in ``data``'s dtype:
    no float32 array over the classes is stored or handed on, where
    ``log_softmax`` + ``pick`` under AMP write the gradient in float32
    and copy it to the compute dtype.  Labels are cast to int32 and
    clipped into the class range; ``label.shape == data.shape[:-1]``.
    """
    loss = _sparse_softmax_ce_core(data, label)
    return loss[..., None] if keepdims else loss


@register("smooth_l1", args=("data",))
def _smooth_l1(data, scalar=1.0):
    s2 = scalar * scalar
    return jnp.where(jnp.abs(data) < 1.0 / s2,
                     0.5 * s2 * jnp.square(data),
                     jnp.abs(data) - 0.5 / s2)


@register("LinearRegressionOutput", args=("data", "label"))
def _linear_regression_output(data, label, grad_scale=1.0):
    return _regression_core(data, label, grad_scale, 0)


@register("MAERegressionOutput", args=("data", "label"))
def _mae_regression_output(data, label, grad_scale=1.0):
    return _regression_core(data, label, grad_scale, 1)


@register("LogisticRegressionOutput", args=("data", "label"))
def _logistic_regression_output(data, label, grad_scale=1.0):
    return _regression_core(data, label, grad_scale, 2)


@jax.custom_vjp
def _regression_core(data, label, grad_scale, kind):
    if kind == 2:
        return jax.nn.sigmoid(data)
    return data


def _regression_core_fwd(data, label, grad_scale, kind):
    out = _regression_core(data, label, grad_scale, kind)
    return out, (out, label, grad_scale, kind)


def _regression_core_bwd(res, g):
    out, label, grad_scale, kind = res
    label = label.reshape(out.shape)
    if kind == 1:
        grad = jnp.sign(out - label)
    else:
        grad = out - label
    n = out.shape[0] if out.ndim else 1
    grad = grad * grad_scale / (out.size // max(n, 1))
    return (grad, jnp.zeros_like(label), None, None)


_regression_core.defvjp(_regression_core_fwd, _regression_core_bwd)


@register("MakeLoss", args=("data",), aliases=("make_loss",))
def _make_loss(data, grad_scale=1.0, normalization="null"):
    """Reference: ``src/operator/make_loss.cc``."""
    return _make_loss_core(data, grad_scale)


@jax.custom_vjp
def _make_loss_core(data, grad_scale):
    return data


def _make_loss_core_fwd(data, grad_scale):
    return data, (data.shape, data.dtype, grad_scale)


def _make_loss_core_bwd(res, g):
    shape, dtype, grad_scale = res
    return (jnp.full(shape, grad_scale, dtype=dtype), None)


_make_loss_core.defvjp(_make_loss_core_fwd, _make_loss_core_bwd)


# ----------------------------------------------------------------------
# Embedding / dropout
# ----------------------------------------------------------------------

@register("Embedding", args=("data", "weight"))
def _embedding(data, weight, input_dim=0, output_dim=0, dtype="float32",
               sparse_grad=False):
    """Reference: ``indexing_op.cc :: Embedding``; gather on MXU-adjacent
    VMEM; gradient is a scatter-add (XLA emits it from the vjp)."""
    return jnp.take(weight, data.astype(jnp.int32), axis=0)


@register("Dropout", args=("data",), stateful_rng=True)
def _dropout(key, data, p=0.5, mode="training", axes=(), cudnn_off=False,
             training=False):
    """Reference: ``src/operator/nn/dropout.cc``.

    ``key`` is injected by the dispatcher (stateful_rng).  ``mode='always'``
    applies dropout in inference too.
    """
    if p <= 0 or (not training and mode != "always"):
        return data
    shape = data.shape
    if axes:
        shape = tuple(1 if i in axes else s for i, s in enumerate(data.shape))
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, shape).astype(data.dtype)
    return data * mask / keep


# ----------------------------------------------------------------------
# Fused RNN (reference: src/operator/rnn.cc + cudnn_rnn-inl.h).
# ----------------------------------------------------------------------

def _gates_for(mode):
    return {"rnn_relu": 1, "rnn_tanh": 1, "gru": 3, "lstm": 4}[mode]


def rnn_param_size(mode, input_size, state_size, num_layers, bidirectional):
    """Total flat parameter count, matching the layout of ``_rnn_unpack``."""
    g = _gates_for(mode)
    dirs = 2 if bidirectional else 1
    total = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else state_size * dirs
        per_dir = g * state_size * in_sz + g * state_size * state_size \
            + 2 * g * state_size
        total += per_dir * dirs
    return total


def _rnn_unpack(params, mode, input_size, state_size, num_layers, bidirectional):
    """Slice the flat parameter vector into per-layer weight/bias arrays.

    Layout (documented contract of this framework, analogous to the cuDNN
    packed layout the reference uses): for each layer, for each direction:
    W_ih (G*H, in), W_hh (G*H, H), b_ih (G*H), b_hh (G*H).  LSTM gate order
    i, f, g, o; GRU gate order r, z, n.
    """
    g = _gates_for(mode)
    dirs = 2 if bidirectional else 1
    layers = []
    off = 0

    def take(n, shape):
        nonlocal off
        out = lax.dynamic_slice_in_dim(params, off, n).reshape(shape)
        off += n
        return out

    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else state_size * dirs
        per_dir = []
        for _ in range(dirs):
            w_ih = take(g * state_size * in_sz, (g * state_size, in_sz))
            w_hh = take(g * state_size * state_size, (g * state_size, state_size))
            b_ih = take(g * state_size, (g * state_size,))
            b_hh = take(g * state_size, (g * state_size,))
            per_dir.append((w_ih, w_hh, b_ih, b_hh))
        layers.append(per_dir)
    return layers


def _rnn_cell_step(mode, x, h, c, w_ih, w_hh, b_ih, b_hh, H):
    gates = x @ w_ih.T + b_ih + h @ w_hh.T + b_hh
    if mode == "lstm":
        i, f, gg, o = jnp.split(gates, 4, axis=-1)
        i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
        gg = jnp.tanh(gg)
        c_new = f * c + i * gg
        h_new = o * jnp.tanh(c_new)
        return h_new, c_new
    if mode == "gru":
        xg = x @ w_ih.T + b_ih
        hg = h @ w_hh.T + b_hh
        xr, xz, xn = jnp.split(xg, 3, axis=-1)
        hr, hz, hn = jnp.split(hg, 3, axis=-1)
        r = jax.nn.sigmoid(xr + hr)
        z = jax.nn.sigmoid(xz + hz)
        n = jnp.tanh(xn + r * hn)
        h_new = (1 - z) * n + z * h
        return h_new, c
    act = jax.nn.relu if mode == "rnn_relu" else jnp.tanh
    h_new = act(gates)
    return h_new, c


def _run_rnn_layer(mode, x, h0, c0, wts, reverse, H):
    """Scan one direction of one layer over time. x: (T, N, in)."""
    w_ih, w_hh, b_ih, b_hh = wts
    xs = jnp.flip(x, 0) if reverse else x

    def step(carry, xt):
        h, c = carry
        h2, c2 = _rnn_cell_step(mode, xt, h, c, w_ih, w_hh, b_ih, b_hh, H)
        return (h2, c2), h2

    (hT, cT), ys = lax.scan(step, (h0, c0), xs)
    if reverse:
        ys = jnp.flip(ys, 0)
    return ys, hT, cT


@register("RNN", args=("data", "parameters", "state", "state_cell"),
          num_diff_outputs=None, stateful_rng=True)
def _rnn(key, data, parameters, state, state_cell, state_size=0, num_layers=1,
         mode="lstm", bidirectional=False, p=0.0, state_outputs=True,
         training=False):
    """Fused multi-layer RNN (reference: ``src/operator/rnn.cc``; cuDNN path
    ``cudnn_rnn-inl.h``).  TPU-native: `lax.scan` over time per layer --
    XLA keeps the per-step matmuls on the MXU and pipelines layers.

    data: (T, N, input) time-major, as the reference.  state/state_cell:
    (num_layers*dirs, N, H).  Returns (out, hy[, cy]) -- for lstm, 3
    outputs; otherwise 2.
    """
    T, N, input_size = data.shape
    H = state_size
    dirs = 2 if bidirectional else 1
    layers = _rnn_unpack(parameters, mode, input_size, H, num_layers, bidirectional)
    x = data
    hys, cys = [], []
    for li, per_dir in enumerate(layers):
        outs = []
        for d in range(dirs):
            h0 = state[li * dirs + d]
            c0 = state_cell[li * dirs + d] if mode == "lstm" else jnp.zeros_like(h0)
            ys, hT, cT = _run_rnn_layer(mode, x, h0, c0, per_dir[d], d == 1, H)
            outs.append(ys)
            hys.append(hT)
            cys.append(cT)
        x = jnp.concatenate(outs, axis=-1) if dirs > 1 else outs[0]
        if p > 0 and training and li < len(layers) - 1:
            key, sub = jax.random.split(key)
            mask = jax.random.bernoulli(sub, 1 - p, x.shape).astype(x.dtype)
            x = x * mask / (1 - p)
    hy = jnp.stack(hys, axis=0)
    if mode == "lstm":
        cy = jnp.stack(cys, axis=0)
        return x, hy, cy
    return x, hy
