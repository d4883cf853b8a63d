"""Pipeline-parallel stage runner (GPipe schedule) via shard_map +
collective_permute.

Intended for the coarse ``pod`` axis, where DCN-like latency favors few
large stages over per-layer collectives.  Layers are split into
``n_stages`` contiguous stages; microbatches stream through with the
classic (n_micro + n_stages − 1)-step schedule.  Activations hop stages
with a single ``collective_permute`` per step — the only inter-stage
communication.

This is a config option (``ParallelConfig.pipeline_stages > 1``) rather
than the default path; it is validated in tests on a small host-device
mesh.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS


def pipeline_apply(stage_fn: Callable, stage_params, x_micro, mesh, *,
                   stage_axis: str = "stage"):
    """Run ``stage_fn(params_local, x) -> x`` over ``n_stages`` stages.

    stage_params: pytree whose leaves have a leading stage dim
                  (n_stages, ...) — sharded 1-per-device over stage_axis.
    x_micro:      (n_micro, mb, ...) microbatched input, replicated.
    Returns (n_micro, mb, ...) outputs (valid on every device).
    """
    n_stages = mesh.shape[stage_axis]
    n_micro = x_micro.shape[0]
    T = n_micro + n_stages - 1
    fwd = [(i, i + 1) for i in range(n_stages - 1)]

    def fn(params_loc, xm):
        params_loc = jax.tree_util.tree_map(lambda p: p[0], params_loc)
        sid = jax.lax.axis_index(stage_axis)
        mb_shape = xm.shape[1:]
        carry_in = jnp.zeros(mb_shape, xm.dtype)
        outs = jnp.zeros_like(xm)

        def step(t, state):
            carry, outs = state
            # stage 0 ingests microbatch t (when valid)
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            inject = jax.lax.dynamic_index_in_dim(xm, mb_idx, 0,
                                                  keepdims=False)
            inp = jnp.where(sid == 0, inject, carry)
            out = stage_fn(params_loc, inp)
            # last stage emits microbatch t-(n_stages-1)
            emit_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            valid = (t >= n_stages - 1) & (sid == n_stages - 1)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, out.astype(o.dtype), emit_idx, 0),
                lambda o: o, outs)
            carry = jax.lax.ppermute(out, stage_axis, fwd)
            return carry, outs

        _, outs = jax.lax.fori_loop(0, T, step, (carry_in, outs))
        # every device returns the last stage's buffer
        outs = jax.lax.psum(
            jnp.where(sid == n_stages - 1, 1.0, 0.0) * outs, stage_axis)
        return outs

    pspec = jax.tree_util.tree_map(
        lambda _: PS(stage_axis), stage_params)
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=(pspec, PS()),
                         out_specs=PS(),
                         check_vma=False)(stage_params, x_micro)
