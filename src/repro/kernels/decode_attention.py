"""Decode attention Pallas TPU kernel: one new query token per slot
against the slot cache, read where it lies.

The cache is stored lane-dense, ``(layers, slots, max_len, Hkv·D)`` with
the kv heads side by side in the minor dim, and the kernel takes the
whole stack in HBM with the layer index and each slot's position as
scalar prefetch: no layer is sliced out of the stack.  Grid (slots,);
each slot walks only its live kv blocks (at or below ``pos``, and above
``pos - window`` for sliding-window layers) with double-buffered DMAs,
keeping the f32 online-softmax state in the loop's carry.  The last
block of a slot starts the next slot's first fetch, so a slot's first
block is in flight before its turn.

Heads: the query enters as a block-diagonal ``(Hq, Hkv·D)`` matrix (row
h holds q_h in the columns of its kv head h // rep), so ``q·Kᵀ`` is one
matmul for every head and GQA needs no repeat of K or V.  ``p·V`` gives
each query head against every kv head's columns; the wrapper keeps each
head's own.
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
BLOCK_K = 128           # kv rows a DMA brings: one MXU tile of positions


def _live_blocks(pos, window, block_k, nk):
    """First and last kv block holding a position the query at ``pos``
    attends to."""
    hi = jnp.clip(pos // block_k, 0, nk - 1)
    if window <= 0:
        return 0, hi
    return jnp.minimum(jnp.maximum(pos - window + 1, 0) // block_k, hi), hi


def _kernel(layer_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf,
            sem, first_ref, *, window, softcap, scale, block_k, nk):
    b, nb = pl.program_id(0), pl.num_programs(0)
    layer = layer_ref[0]

    def fetch(slot, blk, buf):
        rows = pl.ds(pl.multiple_of(blk * block_k, block_k), block_k)
        return (pltpu.make_async_copy(k_hbm.at[layer, slot, rows],
                                      kbuf.at[buf], sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[layer, slot, rows],
                                      vbuf.at[buf], sem.at[1, buf]))

    def start(slot, blk, buf):
        for c in fetch(slot, blk, buf):
            c.start()

    pos = pos_ref[b]
    lo, hi = _live_blocks(pos, window, block_k, nk)

    # the first slot fetches its own first block; every later one finds
    # it in flight, started by the slot before in the buffer it names
    @pl.when(b == 0)
    def _first():
        first_ref[0] = 0
        start(b, lo, 0)

    base = first_ref[0]
    nxt = jnp.minimum(b + 1, nb - 1)
    nxt_lo, _ = _live_blocks(pos_ref[nxt], window, block_k, nk)
    q = q_ref[...].astype(kbuf.dtype)                    # (Hq, Hkv·D)

    def block(blk, carry):
        m_prev, l_prev, acc = carry
        buf = (base + blk - lo) % 2

        @pl.when(blk < hi)
        def _ahead():
            start(b, blk + 1, 1 - buf)

        @pl.when(jnp.logical_and(blk == hi, b + 1 < nb))
        def _next_slot():
            start(nxt, nxt_lo, 1 - buf)

        ck, cv = fetch(b, blk, buf)
        ck.wait()
        s = jax.lax.dot_general(q, kbuf[buf], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        kpos = blk * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos <= pos
        if window > 0:
            mask = jnp.logical_and(mask, kpos > pos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        cv.wait()
        v = vbuf[buf]
        acc = acc * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_prev * corr + p.sum(axis=1, keepdims=True), acc

    Hq, HD = q.shape
    m, l, acc = jax.lax.fori_loop(
        lo, hi + 1, block,
        (jnp.full((Hq, 1), NEG_INF, jnp.float32),
         jnp.zeros((Hq, 1), jnp.float32), jnp.zeros((Hq, HD), jnp.float32)))
    first_ref[0] = (base + hi + 1 - lo) % 2
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def decode_attention(q, k, v, pos, layer, *, window: int = 0,
                     softcap: float = 0.0,
                     scale: Optional[float] = None,
                     interpret: bool = False):
    """q: (B,Hq,D); k, v: (L,B,T,Hkv·D) as stored; pos: (B,) int32, each
    slot's query position (its own K/V already written there); layer: the
    index into the stack; ``scale`` multiplies q·k (None: 1/sqrt(D)) →
    (B,Hq,D).  A T that ``BLOCK_K`` does not divide is one block."""
    B, Hq, D = q.shape
    T, HD = k.shape[2], k.shape[3]
    Hkv = HD // D
    rep = Hq // Hkv
    bk = BLOCK_K if T % BLOCK_K == 0 else T
    nk = T // bk

    # (kv head of a query row, kv head of a column block)
    own = jnp.eye(Hkv, dtype=bool)[None, :, None, :, None]
    # block-diagonal query: row h carries q_h in its kv head's columns
    qbd = jnp.where(own, q.reshape(B, Hkv, rep, 1, D), 0).reshape(B, Hq, HD)

    kernel = functools.partial(_kernel, window=window, softcap=softcap,
                               scale=1.0 / np.sqrt(D) if scale is None
                               else scale, block_k=bk, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((None, Hq, HD), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, Hq, HD), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, bk, HD), k.dtype),
                pltpu.VMEM((2, bk, HD), v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, Hq, HD), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      pos.astype(jnp.int32), qbd, k, v)
    # each query head against its own kv head's columns
    out = jnp.where(own, out.reshape(B, Hkv, rep, Hkv, D), 0).sum(axis=3)
    return out.reshape(B, Hq, D)
