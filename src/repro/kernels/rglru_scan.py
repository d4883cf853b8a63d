"""RG-LRU Pallas TPU kernel: blocked linear recurrence.

Grid (B, n_channel_blocks, n_time_chunks); time chunks are the innermost
(sequential) dim, the hidden state (1, Wb) persists in VMEM scratch.
Each chunk walks its rows in sublane-aligned groups: a group of rows is
read through the refs, its gates/decays are computed as one vector tile,
the recurrence steps through the group's rows with static slices, and
the group's outputs are stored as one aligned tile.  The recurrence is
channel-parallel over the 128-lane block, which is exactly why the
per-channel gate simplification (see models/rglru_block.py) was chosen.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

RGLRU_C = 8.0


def _kernel(x_ref, r_ref, i_ref, ll_ref, h0_ref, o_ref, hf_ref, h_ref, *,
            nt, tc, rows, use_h0, s_real):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        if use_h0:
            h_ref[...] = h0_ref[0].astype(jnp.float32)
        else:
            h_ref[...] = jnp.zeros_like(h_ref)

    decay = -RGLRU_C * jax.nn.softplus(ll_ref[...].astype(jnp.float32))
    wb = h_ref.shape[-1]
    row_id = jax.lax.broadcasted_iota(jnp.int32, (rows, wb), 0)

    def group(g, h):
        start = pl.multiple_of(g * rows, rows)
        sl = pl.ds(start, rows)
        x = x_ref[0, sl, :].astype(jnp.float32)             # (rows, Wb)
        r = jax.nn.sigmoid(r_ref[0, sl, :].astype(jnp.float32))
        i = jax.nn.sigmoid(i_ref[0, sl, :].astype(jnp.float32))
        log_a = decay * r
        a = jnp.exp(log_a)
        beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
        b = i * x * beta
        # time-padding must be an identity step (a=1, b=0) or it decays
        # the carried state
        pad_row = it * tc + start + row_id >= s_real
        a = jnp.where(pad_row, 1.0, a)
        b = jnp.where(pad_row, 0.0, b)
        out = jnp.zeros((rows, wb), jnp.float32)
        for j in range(rows):
            h = a[j:j + 1] * h + b[j:j + 1]
            out = jnp.where(row_id == j, h, out)
        o_ref[0, sl, :] = out.astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, tc // rows, group, h_ref[...])
    h_ref[...] = h

    @pl.when(it == nt - 1)
    def _fin():
        hf_ref[0] = h.astype(hf_ref.dtype)


def rglru_pallas(x, r_gate, i_gate, log_lambda, h0=None, *,
                 interpret: bool = False, block_w: int = 128,
                 block_t: int = 256) -> Tuple[jax.Array, jax.Array]:
    """Shapes as in :func:`repro.kernels.ref.rglru_ref`."""
    Bb, S, W = x.shape
    # one sublane tile of rows per group: 8 for 32-bit, 16 for 16-bit
    rows = 8 * max(4 // x.dtype.itemsize, 1)
    wb = min(block_w, W)
    tc = min(block_t, S)
    tc += (-tc) % rows
    pad_w = (-W) % wb
    pad_t = (-S) % tc
    if pad_w or pad_t:
        pads = ((0, 0), (0, pad_t), (0, pad_w))
        x = jnp.pad(x, pads)
        r_gate = jnp.pad(r_gate, pads)
        i_gate = jnp.pad(i_gate, pads)
    if pad_w:
        log_lambda = jnp.pad(log_lambda, ((0, pad_w),))
    Wp, Sp = W + pad_w, S + pad_t
    nw, nt = Wp // wb, Sp // tc
    use_h0 = h0 is not None
    h0_in = h0 if use_h0 else jnp.zeros((Bb, W), jnp.float32)
    if pad_w:
        h0_in = jnp.pad(h0_in, ((0, 0), (0, pad_w)))
    # batch ahead of a unit dim, so every block's last two dims are legal
    # TPU tiles whatever the batch size
    h0_in = h0_in[:, None, :]                          # (B, 1, Wp)
    ll2 = log_lambda[None, :]                          # (1, Wp)

    kernel = functools.partial(_kernel, nt=nt, tc=tc, rows=rows,
                               use_h0=use_h0, s_real=S)
    hs, hf = pl.pallas_call(
        kernel,
        grid=(Bb, nw, nt),
        in_specs=[
            pl.BlockSpec((1, tc, wb), lambda b, w, t: (b, t, w)),
            pl.BlockSpec((1, tc, wb), lambda b, w, t: (b, t, w)),
            pl.BlockSpec((1, tc, wb), lambda b, w, t: (b, t, w)),
            pl.BlockSpec((1, wb), lambda b, w, t: (0, w)),
            pl.BlockSpec((1, 1, wb), lambda b, w, t: (b, 0, w)),
        ],
        out_specs=[
            pl.BlockSpec((1, tc, wb), lambda b, w, t: (b, t, w)),
            pl.BlockSpec((1, 1, wb), lambda b, w, t: (b, 0, w)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, Sp, Wp), x.dtype),
            jax.ShapeDtypeStruct((Bb, 1, Wp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, wb), jnp.float32)],
        interpret=interpret,
    )(x, r_gate, i_gate, ll2, h0_in)
    return hs[:, :S, :W], hf[:, 0, :W]
