"""Pipeline parallelism over a ``pp`` mesh axis.

GPipe-style schedule, TPU-native: the S pipeline stages are ONE stacked
parameter tree with a leading stage axis sharded over ``pp`` (each
device holds its stage's weights); microbatches flow stage-to-stage via
``lax.ppermute`` over the ICI ring inside a single ``shard_map`` -- one
compiled program, no host round-trips between stages.  The reference has
no pipeline engine (its model parallelism was per-layer ctx_group
placement with engine-ordered copies); this is the compiler-era
re-design of that row.

Requirements: homogeneous stages (same ``stage_fn``, stacked params) --
the transformer-stack case pipelineing exists for.  Bubble fraction is
(S-1)/(M+S-1) as usual; raise the microbatch count M to amortize.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..base import MXNetError


def stack_stage_params(param_trees):
    """Stack S per-stage parameter trees into one tree with a leading
    stage axis (shard it over ``pp``)."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *param_trees)


def shard_stacked_params(stacked, mesh, axis="pp"):
    """Place a stacked param tree with its stage axis over ``pp``."""
    def put(leaf):
        spec = P(axis, *([None] * (leaf.ndim - 1)))
        return jax.device_put(leaf, NamedSharding(mesh, spec))
    return jax.tree_util.tree_map(put, stacked)


def pipeline_apply(stage_fn, stacked_params, microbatches, mesh,
                   axis="pp"):
    """Run ``microbatches`` (M, mb, ...) through S pipelined stages.

    ``stage_fn(stage_params, x) -> x`` applies one stage; stages =
    ``mesh.shape[axis]``; ``stacked_params`` leaves have leading dim S
    (use `stack_stage_params` + `shard_stacked_params`).  Returns the
    (M, mb, ...) outputs.  Differentiable end-to-end (ppermute
    transposes to the reverse rotation).
    """
    shard_map = functools.partial(jax.shard_map, check_vma=False)

    S = mesh.shape[axis]
    M = microbatches.shape[0]
    leaves = jax.tree_util.tree_leaves(stacked_params)
    if not leaves:
        raise MXNetError("stacked_params has no array leaves")
    lead = {leaf.shape[0] if leaf.ndim else None for leaf in leaves}
    if lead != {S}:
        raise MXNetError(
            "stacked params have leading stage dim(s) %s but the %r mesh "
            "axis has %d devices; stack exactly one stage per device "
            "(scalar leaves cannot be staged)"
            % (sorted(map(str, lead)), axis, S))
    perm = [(i, (i + 1) % S) for i in range(S)]

    def run(params, xs):
        # params: local (1, ...) slice of the stacked tree; xs: (M, ...)
        local = jax.tree_util.tree_map(lambda p: p[0], params)
        idx = jax.lax.axis_index(axis)
        state = jnp.zeros(xs.shape[1:], xs.dtype)
        outputs = jnp.zeros_like(xs)

        def step(t, carry):
            state, outputs = carry
            # stage 0 ingests microbatch t while t < M
            feed_t = jnp.clip(t, 0, M - 1)
            inp = jnp.where(idx == 0,
                            jnp.where(t < M, xs[feed_t],
                                      jnp.zeros_like(state)),
                            state)
            out = stage_fn(local, inp)
            # last stage emits microbatch t-(S-1)
            wt = t - (S - 1)
            wt_c = jnp.clip(wt, 0, M - 1)
            valid = jnp.logical_and(idx == S - 1,
                                    jnp.logical_and(wt >= 0, wt < M))
            outputs = outputs.at[wt_c].set(
                jnp.where(valid, out, outputs[wt_c]))
            state = jax.lax.ppermute(out, axis, perm)
            return state, outputs

        _, outputs = jax.lax.fori_loop(0, M + S - 1, step,
                                       (state, outputs))
        # only the last stage wrote outputs (others hold zeros):
        # psum replicates them everywhere
        return jax.lax.psum(outputs, axis)

    spec_p = jax.tree_util.tree_map(
        lambda leaf: P(axis, *([None] * (leaf.ndim - 1))), stacked_params)
    other_axes = [a for a in mesh.axis_names if a != axis]
    if any(mesh.shape[a] > 1 for a in other_axes):
        raise MXNetError("pipeline_apply uses every device of the mesh "
                         "for stages; pass a 1-D pp mesh")
    fn = shard_map(run, mesh=mesh, in_specs=(spec_p, P()),
                   out_specs=P())
    return fn(stacked_params, microbatches)
