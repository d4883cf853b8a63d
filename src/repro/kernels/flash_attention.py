"""Flash attention Pallas TPU kernel.

Grid (B, Hq, nq, nk); the kv index is the innermost (sequential on TPU)
dimension, so the online-softmax running state (m, l, acc) lives in VMEM
scratch and persists across kv steps — the canonical TPU flash pattern.
Out-of-band blocks (causal future / outside the sliding window) skip the
MXU work entirely with ``pl.when``.

Supports GQA (kv head = q head // rep via the k/v index maps), causal,
sliding window, tanh logit soft-capping, and prefix-LM bidirectional
prefixes (scalar prefix length in SMEM).

Block sizes default to 128 (MXU-aligned); f32 accumulators.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(prefix_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            causal, window, softcap, scale, nk, block_q, block_k,
            t_real, use_prefix):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    k_start = ik * block_k

    # block-level skip tests (python statics fold `causal`/`window`)
    live = k_start < t_real
    if causal:
        live = jnp.logical_and(live, k_start <= q_start + block_q - 1)
        if use_prefix:
            # prefix blocks are always live for every query row
            live = jnp.logical_or(live, k_start < prefix_ref[0])
    if window > 0 and not use_prefix:
        live = jnp.logical_and(
            live, k_start + block_k - 1 > q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < t_real
        if causal:
            cm = kpos <= qpos
            if use_prefix:
                cm = jnp.logical_or(cm, kpos < prefix_ref[0])
            mask = jnp.logical_and(mask, cm)
        if window > 0:
            wm = kpos > qpos - window
            if use_prefix:
                wm = jnp.logical_or(wm, kpos < prefix_ref[0])
            mask = jnp.logical_and(mask, wm)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _out():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, q_offset: int = 0,
                    prefix_len=None, interpret: bool = False,
                    block_q: int = 128, block_k: int = 128,
                    scale: Optional[float] = None):
    """q: (B,S,Hq,D); k,v: (B,T,Hkv,D) → (B,S,Hq,D).  ``scale``
    multiplies q·k (None: 1/sqrt(D)).

    ``q_offset`` must be 0 for the kernel path (decode uses the xla path).
    """
    if q_offset != 0:
        raise NotImplementedError("kernel path expects q_offset == 0")
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    bq = min(block_q, max(S, 8))
    bk = min(block_k, max(T, 8))

    qt = jnp.moveaxis(q, 2, 1)                      # (B,Hq,S,D)
    kt = jnp.moveaxis(k, 2, 1)                      # (B,Hkv,T,D)
    vt = jnp.moveaxis(v, 2, 1)
    pad_q = (-S) % bq
    pad_k = (-T) % bk
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Sp, Tp = S + pad_q, T + pad_k
    nq, nk = Sp // bq, Tp // bk

    use_prefix = prefix_len is not None
    prefix_arr = jnp.asarray(
        [prefix_len if use_prefix else 0], jnp.int32)

    kernel = functools.partial(
        _kernel, causal=causal, window=window, softcap=softcap,
        scale=1.0 / np.sqrt(D) if scale is None else scale, nk=nk, block_q=bq, block_k=bk,
        t_real=T, use_prefix=use_prefix)

    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, iq, ik, _rep=rep: (b, h // _rep, ik, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, iq, ik, _rep=rep: (b, h // _rep, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(prefix_arr, qt, kt, vt)
    out = out[:, :, :S]
    return jnp.moveaxis(out, 1, 2)
