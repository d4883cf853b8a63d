"""Distributed-optimization collectives (beyond-paper features).

``compressed_allreduce`` — int8-quantized all-reduce with error feedback.
Per-tensor symmetric scale; the quantization residual is returned so the
caller can fold it into the next step's input (error feedback), which is
what preserves convergence.  Used by the train step when
``ParallelConfig.compress_grads`` is on; tested for convergence parity in
``tests/test_distrib.py``.

``sp_decode_attention`` — explicit 2-pass (max/sum) sequence-parallel
decode softmax over a sharded KV cache, as a ``shard_map`` alternative to
trusting GSPMD's partial-softmax rewrite.  Used in perf hillclimbing.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as PS


def quantize_int8(x) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8 quantization. Returns (q, scale)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


def compressed_psum(x, axis_name: str):
    """int8 all-reduce of x over ``axis_name`` (call inside shard_map).

    Quantizes locally, psums the int8 payload widened to int32 (the wire
    cost modeled is the int8 payload; XLA's all-reduce of int32 here is
    the CPU-side stand-in), and dequantizes with the max scale.
    Returns (mean-reduced value, local quantization error for feedback).
    """
    q, scale = quantize_int8(x)
    n = jax.lax.psum(1, axis_name)
    scale_max = jax.lax.pmax(scale, axis_name)
    # re-quantize against the shared scale so the sum is coherent
    q_shared = jnp.clip(jnp.round(x / scale_max), -127, 127).astype(jnp.int8)
    err = x - q_shared.astype(jnp.float32) * scale_max
    summed = jax.lax.psum(q_shared.astype(jnp.int32), axis_name)
    out = summed.astype(jnp.float32) * scale_max / n
    return out, err


def compressed_allreduce_tree(tree, err_tree, mesh, axis_name: str,
                              token_spec):
    """Apply compressed mean-all-reduce to every leaf of ``tree`` (with
    error feedback from / into ``err_tree``), via one shard_map."""
    flat, treedef = jax.tree_util.tree_flatten(tree)
    errs = jax.tree_util.tree_leaves(err_tree) if err_tree is not None \
        else [jnp.zeros_like(x) for x in flat]

    def fn(*args):
        half = len(args) // 2
        xs, es = args[:half], args[half:]
        outs, new_errs = [], []
        for x, e in zip(xs, es):
            o, ne = compressed_psum(x + e, axis_name)
            outs.append(o)
            new_errs.append(ne)
        return tuple(outs) + tuple(new_errs)

    specs = tuple(token_spec for _ in flat)
    res = jax.shard_map(fn, mesh=mesh, in_specs=specs + specs,
                        out_specs=specs + specs, check_vma=False)(*flat, *errs)
    out = jax.tree_util.tree_unflatten(treedef, res[:len(flat)])
    new_err = jax.tree_util.tree_unflatten(treedef, res[len(flat):])
    return out, new_err


def sp_decode_attention(q, k, v, mesh, *, seq_axis: str = "model",
                        softcap: float = 0.0):
    """Explicit 2-pass sequence-parallel decode attention.

    q: (B,1,H,D) replicated over ``seq_axis``; k, v: (B,T,Hkv,D) with T
    sharded over ``seq_axis``.  Each shard computes its local partial
    (max, exp-sum, weighted value); one psum pair combines them — the
    collective payload is O(B·H·D), independent of T.
    """
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    rep = Hq // Hkv

    def fn(q_l, k_l, v_l):
        qf = q_l.astype(jnp.float32).reshape(B, S, Hkv, rep, D)
        kf = k_l.astype(jnp.float32)
        vf = v_l.astype(jnp.float32)
        s = jnp.einsum("bsgrd,btgd->bsgrt", qf, kf) / np.sqrt(D)
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        m_loc = jnp.max(s, axis=-1)
        m = jax.lax.pmax(m_loc, seq_axis)
        p = jnp.exp(s - m[..., None])
        l_loc = p.sum(-1)
        acc_loc = jnp.einsum("bsgrt,btgd->bsgrd", p, vf)
        l = jax.lax.psum(l_loc, seq_axis)
        acc = jax.lax.psum(acc_loc, seq_axis)
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.reshape(B, S, Hq, D).astype(q_l.dtype)

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(PS(None, None, None, None),
                  PS(None, seq_axis, None, None),
                  PS(None, seq_axis, None, None)),
        out_specs=PS(None, None, None, None),
        check_vma=False)(q, k, v)
