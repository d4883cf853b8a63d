"""Mixture-of-Experts layer with *direct expert-parallel dispatch*.

Expert parallelism maps experts onto the ``model`` mesh axis.  Because TP
activations are replicated across ``model`` at block boundaries, every
model shard already holds its row's tokens — so instead of the classic
all-to-all dispatch, each shard (a) computes the router for its row's
tokens (tiny, redundant across shards), (b) sort-dispatches only the
assignments that route to *its* local experts into an (E_local, C, d)
capacity buffer, (c) runs its expert FFNs, (d) scatter-combines partial
outputs, and (e) all-reduces over ``model`` — the same psum a dense TP
MLP needs anyway.  Net effect: MoE costs one (T_local, d) all-reduce, no
all-to-all, no token-size-dependent resharding.  (Recorded in DESIGN.md
as a TPU adaptation; the classic a2a dispatch is what the GPU literature
uses.)

Token dropping: per-expert capacity C = ceil(T_local·k/E · cf); dropped
assignments fall out of the scatter (mode="drop") and contribute zero,
exactly like Switch-style capacity dispatch.

The same code runs without a mesh (``spmd=None``) for CPU smoke tests.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from ..configs.base import ModelConfig
from ..kernels import ops
from .common import P, dense_p, mlp_apply, mlp_params


@dataclass(frozen=True)
class MoESpmd:
    """How the MoE layer sees the mesh. ``expert_axis=None`` = experts
    replicated per device (flat-DP layout): dispatch still runs inside
    shard_map per token shard (a global-jnp sort/scatter would make GSPMD
    materialize global dispatch buffers), weights are gathered by the
    shard_map in_specs."""
    mesh: object                      # jax.sharding.Mesh
    token_axes: Tuple[str, ...]       # axes sharding the token dim ("pod","data")
    expert_axis: Optional[str] = "model"

    @property
    def n_expert_shards(self) -> int:
        if self.expert_axis is None:
            return 1
        return self.mesh.shape[self.expert_axis]


def padded_experts(cfg: ModelConfig, n_shards: int) -> int:
    e = cfg.moe.num_experts
    return int(math.ceil(e / n_shards) * n_shards)


def capacity(cfg: ModelConfig, tokens: int, capacity_factor: float,
             dropless: bool) -> int:
    """Rows per expert of the dispatch buffer for ``tokens`` local
    tokens.  Dropless: every expert can hold every assignment it could
    receive (each token contributes at most one assignment per expert) —
    used for serving, where per-step dropping would make decode diverge
    from prefill."""
    if dropless:
        return tokens
    return max(int(math.ceil(tokens * cfg.moe.top_k
                             / max(cfg.moe.num_experts, 1)
                             * capacity_factor)), 1)


def moe_params(cfg: ModelConfig, rng, path, e_pad: Optional[int] = None) -> dict:
    dt = jnp.dtype(cfg.param_dtype)
    d, f = cfg.d_model, cfg.d_ff
    E = e_pad or cfg.moe.num_experts
    p = {
        # router replicated (every shard needs global top-k); padded slots
        # are masked to -inf in apply.
        "router": dense_p(rng, path + ("router",), (d, E),
                          ("embed", "experts_unsharded"), dt),
        "wi_gate": dense_p(rng, path + ("wi_gate",), (E, d, f),
                           ("experts", "embed", "mlp"), dt, in_dim=d),
        "wi_up": dense_p(rng, path + ("wi_up",), (E, d, f),
                         ("experts", "embed", "mlp"), dt, in_dim=d),
        "wo": dense_p(rng, path + ("wo",), (E, f, d),
                      ("experts", "mlp", "embed"), dt, in_dim=f),
    }
    if cfg.moe.num_shared_experts:
        p["shared"] = mlp_params(
            cfg, rng, path + ("shared",),
            d_ff=cfg.moe.num_shared_experts * cfg.d_ff)
    return p


def _expert_ffn(cfg: ModelConfig, p: dict, buf):
    """buf: (E_l, C, d) -> (E_l, C, d), swiglu experts."""
    cdt = jnp.dtype(cfg.compute_dtype)
    x = buf.astype(cdt)
    gate = jnp.einsum("ecd,edf->ecf", x, p["wi_gate"].astype(cdt))
    up = jnp.einsum("ecd,edf->ecf", x, p["wi_up"].astype(cdt))
    if cfg.mlp in ("swiglu",):
        h = jax.nn.silu(gate) * up
    elif cfg.mlp == "geglu":
        h = jax.nn.gelu(gate, approximate=True) * up
    else:
        h = jax.nn.silu(gate) * up
    return jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(cdt))


def _moe_local(cfg: ModelConfig, params: dict, x2d, *, e_start, e_local,
               e_pad: int, capacity_factor: float, dropless: bool = False,
               router_impl: str = "auto"):
    """Dispatch + expert FFN for one shard. x2d: (T_l, d) local tokens;
    expert tensors hold [e_start, e_start+e_local). Returns partial y
    (contributions of local experts only) and local-sum aux stats."""
    T, d = x2d.shape
    E_real, k = cfg.moe.num_experts, cfg.moe.top_k
    cdt = jnp.dtype(cfg.compute_dtype)
    C = capacity(cfg, T, capacity_factor, dropless)

    with jax.named_scope("moe.route"):
        logits = x2d.astype(cdt) @ params["router"].astype(cdt)  # (T, E_pad)
        logits = logits.astype(jnp.float32)
        if e_pad > E_real:
            pad_mask = jnp.arange(e_pad) >= E_real
            logits = jnp.where(pad_mask[None], -1e30, logits)
        w, idx, probs = ops.router_topk(logits, k,
                                        impl=router_impl)       # (T,k)

        # aux stats (sums; caller normalizes / psums): load per expert,
        # mean prob per expert, router z
        assign_oh = jax.nn.one_hot(idx, e_pad, dtype=jnp.float32).sum(1)
        load_sum = assign_oh.sum(0)                               # (E,)
        prob_sum = probs.sum(0)                                   # (E,)
        z_sum = jnp.square(jax.nn.logsumexp(logits, axis=-1)).sum()

    with jax.named_scope("moe.dispatch"):
        flat_e = idx.reshape(-1)                                  # (T*k,)
        flat_w = w.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(T), k)
        if cfg.moe.dispatch == "cumsum":
            # Switch-style rank computation: position-in-expert = number
            # of prior assignments to the same expert, via a cumsum over
            # the (T·k, E) one-hot — no sort. Same (t, j)-order capacity
            # semantics as the stable sort, ~10x fewer HLO bytes (see
            # EXPERIMENTS.md §Perf).
            ohf = (flat_e[:, None] == jnp.arange(e_pad)[None, :]) \
                .astype(jnp.float32)                           # (T*k, E)
            prior = jnp.cumsum(ohf, axis=0) - ohf
            pos_in_e = jnp.sum(prior * ohf, axis=1).astype(jnp.int32)
            se, st, sw = flat_e, flat_t, flat_w
        else:
            order = jnp.argsort(flat_e, stable=True)
            se, st, sw = flat_e[order], flat_t[order], flat_w[order]
            seg_start = jnp.searchsorted(se, jnp.arange(e_pad))
            pos_in_e = jnp.arange(T * k) - seg_start[se]
        local_e = se - e_start                                # local expert id
        in_shard = (local_e >= 0) & (local_e < e_local)
        keep = (pos_in_e < C) & in_shard
        # out-of-shard / over-capacity rows scatter out of bounds -> dropped
        scat_e = jnp.where(keep, local_e, e_local)
        scat_c = jnp.where(keep, pos_in_e, C)

        buf = jnp.zeros((e_local, C, d), x2d.dtype)
        buf = buf.at[scat_e, scat_c].set(x2d[st], mode="drop")

    with jax.named_scope("moe.experts"):
        out_buf = _expert_ffn(cfg, params, buf)                   # (E_l,C,d)

    with jax.named_scope("moe.combine"):
        vals = out_buf.at[scat_e, scat_c].get(
            mode="fill", fill_value=0.0)                          # (T*k,d)
        vals = vals * jnp.where(keep, sw, 0.0)[:, None].astype(vals.dtype)
        y = jnp.zeros((T, d), vals.dtype).at[st].add(vals)
    return y, (load_sum, prob_sum, z_sum, jnp.float32(T))


def _aux_from_stats(cfg: ModelConfig, load_sum, prob_sum, z_sum, t_total):
    E_real = cfg.moe.num_experts
    k = cfg.moe.top_k
    frac_load = (load_sum / jnp.maximum(t_total * k, 1.0))[:E_real]
    frac_prob = (prob_sum / jnp.maximum(t_total, 1.0))[:E_real]
    lb = E_real * jnp.sum(frac_load * frac_prob)
    z = z_sum / jnp.maximum(t_total, 1.0)
    return {"moe_lb": lb * cfg.moe.aux_coef,
            "moe_z": z * cfg.moe.router_z_coef}


def moe_apply(cfg: ModelConfig, params: dict, x, *,
              spmd: Optional[MoESpmd] = None,
              capacity_factor: Optional[float] = None,
              dropless: bool = False,
              router_impl: str = "auto") -> Tuple[jax.Array, dict]:
    """MoE FFN over x: (B,S,d). Returns (y, aux_losses)."""
    B, S, d = x.shape
    x2d = x.reshape(B * S, d)
    cf = capacity_factor if capacity_factor is not None \
        else cfg.moe.capacity_factor

    if spmd is None:
        e_pad = params["wi_gate"].shape[0]
        y, (ls, ps, zs, t) = _moe_local(
            cfg, params, x2d, e_start=0, e_local=e_pad, e_pad=e_pad,
            capacity_factor=cf, dropless=dropless, router_impl=router_impl)
        if "shared" in params:
            y = y + mlp_apply(cfg, params["shared"], x2d)
        aux = _aux_from_stats(cfg, ls, ps, zs, t)
        return y.reshape(B, S, d), aux

    mesh = spmd.mesh
    tok = PS(spmd.token_axes)
    ex = spmd.expert_axis
    n_shards = spmd.n_expert_shards
    e_pad = params["wi_gate"].shape[0]
    e_local = e_pad // n_shards

    shared = params.get("shared")
    has_shared = shared is not None

    def fn(x_loc, router, wig, wiu, wo, *shared_w):
        my = (jax.lax.axis_index(ex) * e_local) if ex is not None else 0
        p_loc = {"router": router, "wi_gate": wig, "wi_up": wiu, "wo": wo}
        y, (ls, ps, zs, t) = _moe_local(
            cfg, p_loc, x_loc, e_start=my, e_local=e_local, e_pad=e_pad,
            capacity_factor=cf, dropless=dropless, router_impl=router_impl)
        if has_shared:
            sp = dict(zip(sorted(shared.keys()), shared_w))
            y = y + mlp_apply(cfg, sp, x_loc)      # mlp dim sharded -> partial
        if ex is not None:
            y = jax.lax.psum(y, ex)                # combine expert partials
        # Aux sums are identical on every expert shard (router is
        # replicated): psum over token shards only -> global sums.
        ls, ps, zs, t = (jax.lax.psum(v, spmd.token_axes)
                         for v in (ls, ps, zs, t))
        return y, ls, ps, zs, t

    shared_keys = sorted(shared.keys()) if has_shared else []
    shared_vals = [shared[k] for k in shared_keys]
    # shared-expert MLP is plain TP: wi_* shard the f dim, wo shards f too
    shared_specs = tuple(
        PS(None, ex) if k.startswith("wi") else PS(ex, None)
        for k in shared_keys)
    expert_spec = PS(ex, None, None)               # ex=None -> replicated

    y, ls, ps, zs, t = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(PS(spmd.token_axes, None),
                  PS(None, None),
                  expert_spec, expert_spec, expert_spec,
                  *shared_specs),
        out_specs=(PS(spmd.token_axes, None), PS(None), PS(None), PS(),
                   PS()),
        check_vma=False,
    )(x2d, params["router"], params["wi_gate"], params["wi_up"],
      params["wo"], *shared_vals)
    aux = _aux_from_stats(cfg, ls, ps, zs, t)
    return y.reshape(B, S, d), aux
