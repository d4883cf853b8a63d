"""Mamba2 SSD Pallas TPU kernel (chunked state-space duality).

Grid (B, H, n_chunks); chunks are the innermost (sequential) dimension,
so the running inter-chunk state (P, N) lives in VMEM scratch.  Each
chunk does the quadratic intra-chunk part on the MXU ((Q,N)·(N,Q),
(Q,Q)·(Q,P)) plus the O(Q·P·N) state update — exactly the SSD
decomposition, with chunk length Q sized so the working set
(Q² scores + state) fits VMEM.

Layout is head-major: x as (B, H, S, P), B/C as (B, G, S, N) and dt as
(B, H, 1, S), so every block ends in a (Q, P) / (Q, N) / (1, Q) tile.
The within-chunk prefix sums of dt·A are triangular matmuls, giving the
row and column orientations without a transpose.

Padding trick: the sequence is padded with dt = 0 ⇒ decay 1, input
contribution 0, so padded tail rows never perturb the state.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NN = (((1,), (0,)), ((), ()))        # a @ b
_NT = (((1,), (1,)), ((), ()))        # a @ b.T
_TN = (((0,), (0,)), ((), ()))        # a.T @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _kernel(A_ref, D_ref, x_ref, dt_ref, B_ref, C_ref, h0_ref,
            y_ref, hf_ref, h_ref, *, nc, use_D, use_h0):
    h = pl.program_id(1)
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        if use_h0:
            h_ref[...] = h0_ref[0, 0].astype(jnp.float32)
        else:
            h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)               # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)             # (1, Q)
    Bm = B_ref[0, 0].astype(jnp.float32)              # (Q, N)
    Cm = C_ref[0, 0].astype(jnp.float32)              # (Q, N)
    Q = x.shape[0]

    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    lower = ii >= jj
    tri = lower.astype(jnp.float32)
    da = dt * A_ref[h]                                # (1, Q)
    # inclusive prefix sums in both orientations (no vector transpose)
    cum_row = _dot(da, tri, _NT)                      # (1, Q)
    cum_col = _dot(tri, da, _NT)                      # (Q, 1)
    total = jnp.sum(da)                               # scalar

    # intra-chunk quadratic part
    decay = jnp.exp(jnp.where(lower, cum_col - cum_row, -1e30))
    scores = _dot(Cm, Bm, _NT) * decay * dt
    y = _dot(scores, x, _NN)

    # inter-chunk: y += exp(cum) * C @ h^T   (h: (P,N))
    hs = h_ref[...]
    y = y + jnp.exp(cum_col) * _dot(Cm, hs, _NT)
    if use_D:
        y = y + D_ref[h] * x
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: h = exp(total) h + sum_j exp(total - cum_j) dt_j x_j ⊗ B_j
    dt_col = _dot((ii == jj).astype(jnp.float32), dt, _NT)   # (Q, 1)
    w = jnp.exp(total - cum_col) * dt_col             # (Q, 1)
    h_new = hs * jnp.exp(total) + _dot(x * w, Bm, _TN)
    h_ref[...] = h_new

    @pl.when(ic == nc - 1)
    def _fin():
        hf_ref[0, 0] = h_new


def ssd_pallas(x, dt, A, B, C, D=None, h0=None, *, chunk: int = 256,
               interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Shapes as in :func:`repro.kernels.ref.ssd_ref`."""
    Bb, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    rep = H // G
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    nc = Sp // Q

    use_D = D is not None
    use_h0 = h0 is not None
    D_in = D if use_D else jnp.zeros((H,), jnp.float32)
    h0_in = h0 if use_h0 else jnp.zeros((Bb, H, P, N), jnp.float32)

    xt = jnp.swapaxes(x, 1, 2)                         # (B, H, Sp, P)
    dtt = jnp.swapaxes(dt, 1, 2)[:, :, None, :]        # (B, H, 1, Sp)
    Bt = jnp.swapaxes(B, 1, 2)                         # (B, G, Sp, N)
    Ct = jnp.swapaxes(C, 1, 2)

    kernel = functools.partial(_kernel, nc=nc, use_D=use_D, use_h0=use_h0)
    y, hf = pl.pallas_call(
        kernel,
        grid=(Bb, H, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),     # A (H,)
            pl.BlockSpec(memory_space=pltpu.SMEM),     # D (H,)
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, 1, Q, N),
                         lambda b, h, c, _r=rep: (b, h // _r, c, 0)),
            pl.BlockSpec((1, 1, Q, N),
                         lambda b, h, c, _r=rep: (b, h // _r, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, H, Sp, P), x.dtype),
            jax.ShapeDtypeStruct((Bb, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(jnp.asarray(A, jnp.float32), jnp.asarray(D_in, jnp.float32),
      xt, dtt, Bt, Ct, h0_in)
    return jnp.swapaxes(y, 1, 2)[:, :S], hf
