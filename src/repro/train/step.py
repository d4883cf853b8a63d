"""Train-step factory: microbatch gradient accumulation (scan), remat,
MoE SPMD wiring, optimizer update — one jit-able pure function.

State layout (plain value pytree, shardable with distrib.tree_shardings):
  {"params": …, "opt": {"m": …, "v": …} | {"f": …}, "count": i32}
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, ParallelConfig
from ..models import Model, unzip
from ..models.moe import MoESpmd
from . import optim


def make_moe_spmd(cfg: ModelConfig, par: ParallelConfig, mesh):
    if mesh is None or not cfg.moe.num_experts:
        return None
    if mesh.shape.get(par.tensor_axis, 1) <= 1:
        return None
    token_axes = tuple(a for a in (par.pod_axis, par.fsdp_axis)
                       if a and a in mesh.shape)
    return MoESpmd(mesh=mesh, token_axes=token_axes,
                   expert_axis=par.tensor_axis)


def init_state(model: Model, opt_cfg: optim.OptConfig, rng):
    """Returns (state value-tree, axes tree) — P-trees unzipped."""
    params_p = model.init(rng)
    if opt_cfg.name == "adafactor":
        opt_p = optim.adafactor_init(params_p)
    else:
        opt_p = optim.adamw_init(params_p)
        if opt_cfg.state_dtype != "float32":
            opt_p = optim.cast_state(opt_p, opt_cfg.state_dtype)
    state_p = {"params": params_p, "opt": opt_p}
    values, axes = unzip(state_p)
    return values, axes


def make_train_step(model: Model, opt_cfg: optim.OptConfig,
                    par: ParallelConfig, mesh=None,
                    impl: str = "auto") -> Callable:
    """Returns train_step(state, batch) -> (state, metrics)."""
    cfg = model.cfg
    spmd = make_moe_spmd(cfg, par, mesh)
    n_micro = max(par.microbatches, 1)

    def loss_of(params, mb):
        return model.loss_fn(params, mb, spmd=spmd, impl=impl,
                             remat=par.remat)

    grad_fn = jax.value_and_grad(loss_of, has_aux=True)

    def split_micro(batch):
        def r(x):
            return x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])
        return jax.tree_util.tree_map(r, batch)

    def train_step(state, batch):
        params = state["params"]

        if n_micro == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            micro = split_micro(batch)

            def acc_body(carry, mb):
                g_acc, l_acc = carry
                (l, m), g = grad_fn(params, mb)
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                return (g_acc, l_acc + l), m

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), ms = jax.lax.scan(
                acc_body, (g0, jnp.float32(0)), micro)
            grads = jax.tree_util.tree_map(lambda g: g / n_micro, grads)
            loss = loss / n_micro
            metrics = jax.tree_util.tree_map(lambda x: x[-1], ms)

        opt = state["opt"]
        if opt_cfg.name == "adafactor":
            new_params, f_new, count, stats = optim.adafactor_update(
                opt_cfg, params, grads, opt["f"], opt["count"])
            new_opt = {"f": f_new, "count": count}
        else:
            new_params, m_new, v_new, count, stats = optim.adamw_update(
                opt_cfg, params, grads, opt["m"], opt["v"], opt["count"])
            new_opt = {"m": m_new, "v": v_new, "count": count}

        metrics = dict(metrics)
        metrics.update(stats)
        metrics["loss"] = loss
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step
