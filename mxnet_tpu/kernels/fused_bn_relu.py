"""Fused BatchNorm+ReLU (Pallas/TPU): NHWC-native, training forward
AND backward.

The PR-10 audits name transpose/layout traffic and unfused-elementwise
HLO as the top cost categories on the ResNet path; the BN->ReLU pair is
the hottest such site (one full activation read for the normalize, one
for the scale/shift, one for the relu when XLA declines to fuse across
the running-stat outputs).  This kernel is the remedy: the per-channel
batch statistics reduce in fp32 (XLA -- two independent reductions fuse
into one read pass, the same shifted one-pass moments as
``ops/nn._batch_norm``), then ONE Pallas VMEM pass applies
normalize + affine + relu, keeping activations bf16 in HBM with fp32
math in registers.  The custom-vjp backward mirrors it: the two
gradient reductions run in XLA (one read pass), then one Pallas VMEM
pass produces dx from the fused training-mode BN backward formula with
the relu mask folded in.

Channels-last only (NHWC-native): any other ``axis`` falls back to the
XLA reference path via the registry choice -- moving the channel axis
would pay exactly the transpose traffic the kernel exists to remove.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.pallas.tiling import row_block
from .registry import KernelSpec, choose, register_kernel

try:  # pallas import kept lazy-safe: CPU-only builds fall back to XLA
    from jax.experimental import pallas as pl
    _HAS_PALLAS = True
except Exception:  # pragma: no cover
    _HAS_PALLAS = False


BLOCK_ROWS = 256


# ----------------------------------------------------------------------
# forward apply: out = relu(x * scale + offset), one VMEM pass
# ----------------------------------------------------------------------

def _apply_fwd_kernel(x_ref, s_ref, o_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)          # (block_rows, C)
    y = x * s_ref[...] + o_ref[...]             # (1, C) broadcasts
    out_ref[...] = jnp.maximum(y, 0.0).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def bn_relu_apply_pallas(x2d, scale, offset, block_rows=BLOCK_ROWS,
                         interpret=False):
    """``relu(x2d * scale + offset)`` over (rows, C); ``scale``/
    ``offset`` are the folded per-channel (1, C) fp32 vectors
    ``gamma*rsqrt(var+eps)`` and ``beta - mean*gamma*rsqrt(var+eps)``."""
    rows, c = x2d.shape
    block_rows = row_block(rows, block_rows)
    vec = pl.BlockSpec((1, c), lambda i: (0, 0))
    return pl.pallas_call(
        _apply_fwd_kernel,
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
                  vec, vec],
        out_specs=pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
        interpret=interpret,
    )(x2d, scale, offset)


# ----------------------------------------------------------------------
# backward apply: dx from the fused BN(+relu-mask) training formula,
# one VMEM pass (the reductions c1/c2 arrive precomputed)
# ----------------------------------------------------------------------

def _apply_bwd_kernel(x_ref, dy_ref, y_ref, a_ref, m_ref, i_ref,
                      c1_ref, c2_ref, dx_ref):
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    y = y_ref[...].astype(jnp.float32)
    dyr = jnp.where(y > 0.0, dy, 0.0)           # relu mask folded in
    xhat = (x - m_ref[...]) * i_ref[...]
    dx = a_ref[...] * (dyr - c1_ref[...] - xhat * c2_ref[...])
    dx_ref[...] = dx.astype(dx_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def bn_relu_bwd_pallas(x2d, dy2d, y2d, a, mean, inv, c1, c2,
                       block_rows=BLOCK_ROWS, interpret=False):
    """dx of fused BN+ReLU over (rows, C).  Per-channel (1, C) fp32
    vectors: ``a = gamma*inv``; ``c1``/``c2`` the mean-reduced
    ``dyr`` / ``dyr*xhat`` (zeros in inference mode, where the batch
    statistics are constants)."""
    rows, c = x2d.shape
    block_rows = row_block(rows, block_rows)
    row_spec = pl.BlockSpec((block_rows, c), lambda i: (i, 0))
    vec = pl.BlockSpec((1, c), lambda i: (0, 0))
    return pl.pallas_call(
        _apply_bwd_kernel,
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        grid=(rows // block_rows,),
        in_specs=[row_spec, row_spec, row_spec, vec, vec, vec, vec, vec],
        out_specs=row_spec,
        interpret=interpret,
    )(x2d, dy2d, y2d, a, mean, inv, c1, c2)


# ----------------------------------------------------------------------
# custom-vjp apply stage (mean/var arrive stop_gradiented; the
# training-mode stats backward is folded into dx here)
# ----------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _bn_relu_apply(x2d, gamma_eff, beta, mean, var, eps, batch_stats,
                   use_pallas, interpret):
    inv = lax.rsqrt(var + eps)
    scale = (gamma_eff * inv)[None, :]
    offset = (beta.astype(jnp.float32) - mean * gamma_eff * inv)[None, :]
    if use_pallas:
        return bn_relu_apply_pallas(x2d, scale, offset,
                                    interpret=interpret)
    xf = x2d.astype(jnp.float32)
    return jnp.maximum(xf * scale + offset, 0.0).astype(x2d.dtype)


def _bn_relu_apply_fwd(x2d, gamma_eff, beta, mean, var, eps, batch_stats,
                       use_pallas, interpret):
    out = _bn_relu_apply(x2d, gamma_eff, beta, mean, var, eps,
                         batch_stats, use_pallas, interpret)
    return out, (x2d, out, gamma_eff, beta, mean, var)


def _bn_relu_apply_bwd(eps, batch_stats, use_pallas, interpret, res, dy):
    x2d, y2d, gamma_eff, beta, mean, var = res
    inv = lax.rsqrt(var + eps)
    xf = x2d.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    dyr = jnp.where(y2d.astype(jnp.float32) > 0.0, dyf, 0.0)
    xhat = (xf - mean[None, :]) * inv[None, :]
    # the two reductions fuse into ONE read pass over (x, dy, y)
    sum_dyr = jnp.sum(dyr, axis=0)
    sum_dyr_xhat = jnp.sum(dyr * xhat, axis=0)
    m = x2d.shape[0]
    if batch_stats:
        # training: mean/var were computed from THIS batch upstream
        # (fed in stop_gradiented), so their backward is folded here --
        # dx = a*(dyr - mean(dyr) - xhat*mean(dyr*xhat))
        c1 = sum_dyr / m
        c2 = sum_dyr_xhat / m
    else:
        c1 = jnp.zeros_like(sum_dyr)
        c2 = jnp.zeros_like(sum_dyr_xhat)
    a = gamma_eff * inv
    if use_pallas:
        dx = bn_relu_bwd_pallas(x2d, dy, y2d, a[None, :], mean[None, :],
                                inv[None, :], c1[None, :], c2[None, :],
                                interpret=interpret)
    else:
        dx = (a[None, :] * (dyr - c1[None, :] - xhat * c2[None, :])) \
            .astype(x2d.dtype)
    dgamma = sum_dyr_xhat.astype(gamma_eff.dtype)
    dbeta = sum_dyr.astype(beta.dtype)
    return (dx, dgamma, dbeta, jnp.zeros_like(mean), jnp.zeros_like(var))


_bn_relu_apply.defvjp(_bn_relu_apply_fwd, _bn_relu_apply_bwd)


# ----------------------------------------------------------------------
# full fused op (stats + apply); the ops-registry fcompute delegates here
# ----------------------------------------------------------------------

def xla_reference(data, gamma, beta, moving_mean, moving_var, eps=1e-5,
                  momentum=0.9, fix_gamma=True, use_global_stats=False,
                  axis=1, training=False):
    """The XLA fallback AND numerics oracle: relu over the registered
    BatchNorm op (identical statistics math)."""
    from ..ops.nn import _batch_norm
    out, nm, nv = _batch_norm.fcompute(
        data, gamma, beta, moving_mean, moving_var, eps=eps,
        momentum=momentum, fix_gamma=fix_gamma,
        use_global_stats=use_global_stats, axis=axis, training=training)
    return jax.nn.relu(out), nm, nv


def fused_bn_relu(data, gamma, beta, moving_mean, moving_var, eps=1e-5,
                  momentum=0.9, fix_gamma=True, use_global_stats=False,
                  axis=1, training=False):
    """Fused BatchNorm+ReLU: ``(out, new_moving_mean, new_moving_var)``
    with the same functional contract as the ``BatchNorm`` op plus the
    relu epilogue.  Kernel-vs-fallback is decided ONCE here through the
    registry (``choose('fused_bn_relu')``)."""
    ch = choose("fused_bn_relu", axis=axis, ndim=data.ndim,
                rows=data.size // max(data.shape[axis], 1))
    if not ch.use_pallas:
        return xla_reference(data, gamma, beta, moving_mean, moving_var,
                             eps=eps, momentum=momentum,
                             fix_gamma=fix_gamma,
                             use_global_stats=use_global_stats,
                             axis=axis, training=training)
    c = data.shape[-1]
    x2d = data.reshape(-1, c)
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    gf = g.astype(jnp.float32)
    batch_stats = bool(training) and not use_global_stats
    if batch_stats:
        # shifted one-pass moments, same math as ops/nn._batch_norm:
        # the two reductions are independent -> ONE read pass; the
        # moving-mean shift bounds catastrophic cancellation
        shift = lax.stop_gradient(moving_mean.astype(jnp.float32))
        y = x2d.astype(jnp.float32) - shift[None, :]
        mean_y = jnp.mean(y, axis=0)
        m2 = jnp.mean(y * y, axis=0)
        var = jnp.maximum(m2 - mean_y * mean_y, 0.0)
        mean = mean_y + shift
        # EMA blended in fp32, stored back at the aux dtype (same
        # discipline as ops/nn._batch_norm): a weak-typed
        # ``momentum * moving_mean`` would round at bf16 per step
        new_mean = (momentum * moving_mean.astype(jnp.float32)
                    + (1 - momentum) * mean).astype(moving_mean.dtype)
        new_var = (momentum * moving_var.astype(jnp.float32)
                   + (1 - momentum) * var).astype(moving_var.dtype)
    else:
        mean = moving_mean.astype(jnp.float32)
        var = moving_var.astype(jnp.float32)
        new_mean, new_var = moving_mean, moving_var
    out2d = _bn_relu_apply(x2d, gf, beta,
                           lax.stop_gradient(mean),
                           lax.stop_gradient(var),
                           float(eps), batch_stats, True, ch.interpret)
    return (out2d.reshape(data.shape), lax.stop_gradient(new_mean),
            lax.stop_gradient(new_var))


def _supports(axis=1, ndim=4, rows=None, **_kw):
    if axis not in (-1, ndim - 1):
        return False, ("fused_bn_relu is NHWC-native (channels-last); "
                       "axis=%d of a %d-d input falls back to XLA -- "
                       "moving the channel axis would pay the transpose "
                       "traffic the kernel removes" % (axis, ndim))
    if rows is not None and row_block(rows, BLOCK_ROWS) is None:
        return False, ("fused_bn_relu tiles N*H*W in row blocks that "
                       "are a multiple of 8; %d rows has no such "
                       "divisor <= %d" % (rows, BLOCK_ROWS))
    return True, ""


register_kernel(KernelSpec(
    name="fused_bn_relu",
    doc="NHWC-native fused BatchNorm+ReLU: fp32 batch statistics (one "
        "XLA read pass), one Pallas VMEM pass for normalize+affine+"
        "relu, custom-vjp backward with the relu mask and stats "
        "backward folded into one dx pass.  Wired into the gluon "
        "HybridSequential BatchNorm+Activation fusion sites behind "
        "MXNET_TPU_KERNELS=1.",
    categories=("elementwise_fusion", "transpose_layout"),
    remedies=("unfused-elementwise", "transpose-share"),
    supports=_supports,
    xla_ref=xla_reference,
))
