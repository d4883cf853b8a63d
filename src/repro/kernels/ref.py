"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` of the spec).

These are written for *clarity and obvious correctness*, not speed: naive
full-materialization attention, step-by-step recurrences.  Kernel tests
sweep shapes/dtypes and ``assert_allclose`` the Pallas (interpret=True)
and the fast-XLA implementations in ``ops.py`` against these.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def attention_mask(q_len: int, kv_len: int, *, q_offset: int = 0,
                   causal: bool = True, window: int = 0,
                   prefix_len: Optional[jax.Array] = None) -> jax.Array:
    """(q_len, kv_len) boolean mask. ``q_offset`` is the absolute position
    of query row 0 (decode: kv_len-1).  ``window`` > 0 restricts keys to
    the last ``window`` positions (sliding-window / local attention).
    ``prefix_len`` (scalar) makes positions < prefix_len bidirectional
    (prefix-LM, paligemma)."""
    qpos = jnp.arange(q_len)[:, None] + q_offset
    kpos = jnp.arange(kv_len)[None, :]
    mask = jnp.ones((q_len, kv_len), dtype=bool)
    if causal:
        causal_m = kpos <= qpos
        if prefix_len is not None:
            causal_m = causal_m | (kpos < prefix_len)
        mask &= causal_m
    if window > 0:
        mask &= kpos > qpos - window
    return mask


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, window: int = 0,
                  softcap: float = 0.0, q_offset: int = 0,
                  prefix_len: Optional[jax.Array] = None,
                  scale: Optional[float] = None) -> jax.Array:
    """Naive attention oracle.

    q: (B, S, Hq, D); k, v: (B, T, Hkv, D) with Hq % Hkv == 0 (GQA).
    Returns (B, S, Hq, D) in q.dtype; math in f32.
    """
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    qf = q.astype(jnp.float32)
    kf = jnp.repeat(k.astype(jnp.float32), rep, axis=2)
    vf = jnp.repeat(v.astype(jnp.float32), rep, axis=2)
    s = scale if scale is not None else 1.0 / np.sqrt(D)
    logits = jnp.einsum("bshd,bthd->bhst", qf, kf) * s
    if softcap > 0.0:
        logits = jnp.tanh(logits / softcap) * softcap
    mask = attention_mask(S, T, q_offset=q_offset, causal=causal,
                          window=window, prefix_len=prefix_len)
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", p, vf)
    return out.astype(q.dtype)


def decode_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                         pos: jax.Array, *, window: int = 0,
                         softcap: float = 0.0,
                         scale: Optional[float] = None) -> jax.Array:
    """One query token per slot against its cache row, as stored.

    q: (B, Hq, D); k, v: (B, T, Hkv·D), kv heads side by side in the
    minor dim; pos: (B,) each slot's query position.  Returns (B, Hq, D).
    """
    B, Hq, D = q.shape
    T = k.shape[1]

    def one(qb, kb, vb, pb):
        kh = kb.reshape(1, T, -1, D)
        vh = vb.reshape(1, T, -1, D)
        return attention_ref(qb[None, None], kh, vh, causal=True,
                             window=window, softcap=softcap,
                             q_offset=pb, scale=scale)[0, 0]

    return jax.vmap(one)(q, k, v, pos)


# ---------------------------------------------------------------------------
# Mamba2 SSD (state-space duality)
# ---------------------------------------------------------------------------
def ssd_ref(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
            C: jax.Array, D: Optional[jax.Array] = None,
            h0: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """Sequential SSD recurrence oracle.

    x:  (batch, S, H, P)     per-head inputs
    dt: (batch, S, H)        positive step sizes (already softplus'ed)
    A:  (H,)                 negative decay rates
    B:  (batch, S, G, N)     input projections (G groups, H % G == 0)
    C:  (batch, S, G, N)     output projections
    D:  (H,) skip            optional
    h0: (batch, H, P, N)     initial state, optional
    Returns (y: (batch,S,H,P), h_final: (batch,H,P,N)); math in f32.

      h_t = exp(A dt_t) h_{t-1} + dt_t * x_t B_t^T
      y_t = h_t C_t + D x_t
    """
    Bb, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    rep = H // G
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Bf = jnp.repeat(B.astype(jnp.float32), rep, axis=2)  # (Bb,S,H,N)
    Cf = jnp.repeat(C.astype(jnp.float32), rep, axis=2)
    Af = A.astype(jnp.float32)
    h = jnp.zeros((Bb, H, P, N), jnp.float32) if h0 is None \
        else h0.astype(jnp.float32)

    def step(h, inp):
        x_t, dt_t, B_t, C_t = inp          # (Bb,H,P),(Bb,H),(Bb,H,N),(Bb,H,N)
        decay = jnp.exp(Af[None] * dt_t)   # (Bb,H)
        dBx = jnp.einsum("bhp,bhn->bhpn", x_t * dt_t[..., None], B_t)
        h = h * decay[..., None, None] + dBx
        y_t = jnp.einsum("bhpn,bhn->bhp", h, C_t)
        return h, y_t

    xs = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
          jnp.moveaxis(Bf, 1, 0), jnp.moveaxis(Cf, 1, 0))
    h, ys = jax.lax.scan(step, h, xs)
    y = jnp.moveaxis(ys, 0, 1)
    if D is not None:
        y = y + xf * D.astype(jnp.float32)[None, None, :, None]
    return y.astype(x.dtype), h


# ---------------------------------------------------------------------------
# RG-LRU (Griffin / recurrentgemma)
# ---------------------------------------------------------------------------
RGLRU_C = 8.0


def rglru_ref(x: jax.Array, r_gate: jax.Array, i_gate: jax.Array,
              log_lambda: jax.Array, h0: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """RG-LRU oracle (sequential).

    x, r_gate, i_gate: (B, S, W)   — gates pre-sigmoid
    log_lambda: (W,)               — Λ parameter; log a = -c·softplus(Λ)·r
    h_t = a_t ⊙ h_{t-1} + sqrt(1 - a_t²) ⊙ (i_t ⊙ x_t)
    Returns (h: (B,S,W) hidden sequence, h_final: (B,W)); math in f32.
    """
    Bb, S, W = x.shape
    xf = x.astype(jnp.float32)
    r = jax.nn.sigmoid(r_gate.astype(jnp.float32))
    i = jax.nn.sigmoid(i_gate.astype(jnp.float32))
    log_a = -RGLRU_C * jax.nn.softplus(log_lambda.astype(jnp.float32))[None, None] * r
    a = jnp.exp(log_a)
    # sqrt(1 - a^2) computed in log space for stability
    a2 = jnp.exp(2.0 * log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - a2, 1e-12))
    gated = i * xf * beta
    h = jnp.zeros((Bb, W), jnp.float32) if h0 is None else h0.astype(jnp.float32)

    def step(h, inp):
        a_t, g_t = inp
        h = a_t * h + g_t
        return h, h

    h, hs = jax.lax.scan(step, h, (jnp.moveaxis(a, 1, 0),
                                   jnp.moveaxis(gated, 1, 0)))
    return jnp.moveaxis(hs, 0, 1).astype(x.dtype), h


# ---------------------------------------------------------------------------
# MoE router
# ---------------------------------------------------------------------------
def router_topk_ref(logits: jax.Array, k: int, *,
                    renormalize: bool = True
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k softmax gating oracle.

    logits: (T, E). Returns (weights (T,k) f32, idx (T,k) i32,
    full_probs (T,E) f32 — for aux losses)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    if renormalize:
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return w, idx.astype(jnp.int32), probs


# ---------------------------------------------------------------------------
# grouped matmul (dropless MoE experts)
# ---------------------------------------------------------------------------
def grouped_matmul_ref(lhs: jax.Array, rhs: jax.Array,
                       group_sizes: jax.Array) -> jax.Array:
    """Grouped matmul oracle: each row of ``lhs`` times its own group's
    matrix, one row at a time.

    lhs: (M, K); rhs: (G, K, N); group_sizes: (G,), the groups' rows in
    order from row 0, summing to at most M.  Returns (M, N) f32; rows
    past the last group are 0.
    """
    M = lhs.shape[0]
    G = rhs.shape[0]
    ends = jnp.cumsum(group_sizes)
    g = jnp.searchsorted(ends, jnp.arange(M), side="right")   # (M,)
    w = rhs[jnp.minimum(g, G - 1)].astype(jnp.float32)          # (M,K,N)
    out = jnp.einsum("mk,mkn->mn", lhs.astype(jnp.float32), w)
    return jnp.where((g < G)[:, None], out, 0.0)


# ---------------------------------------------------------------------------
# Fletcher-64 checksum (bulk/checkpoint integrity — the RPC layer's hot loop)
# ---------------------------------------------------------------------------
FLETCHER_MOD = (1 << 32) - 1


def fletcher64_ref(words: np.ndarray) -> int:
    """Fletcher-64 over uint32 words (numpy oracle, exact integer math)."""
    s1, s2 = 0, 0
    for w in np.asarray(words, dtype=np.uint64):
        s1 = (s1 + int(w)) % FLETCHER_MOD
        s2 = (s2 + s1) % FLETCHER_MOD
    return (s2 << 32) | s1
