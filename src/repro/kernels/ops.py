"""Dispatching jit'd wrappers around the compute kernels.

Every op has up to four implementations, selected with ``impl=``:

  * ``"ref"``       — the naive oracle in :mod:`repro.kernels.ref`;
  * ``"xla"``       — a memory-efficient pure-jnp implementation (chunked
                      flash attention, blocked local attention, chunked
                      SSD, associative-scan RG-LRU); on CPU it is the
                      fastest;
  * ``"pallas"``    — the Pallas TPU kernel (``pl.pallas_call``), compiled
                      for the MXU/VMEM (TARGET hardware);
  * ``"interpret"`` — the same Pallas kernel in interpret mode (CPU
                      correctness validation of the TPU kernel body).

``impl="auto"`` resolves to ``pallas`` on TPU backends and ``xla``
elsewhere.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ref as ref_mod
from .ref import NEG_INF, RGLRU_C, FLETCHER_MOD


def resolve_impl(impl: str) -> str:
    if impl != "auto":
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "xla"


# ===========================================================================
# attention
# ===========================================================================
def attention(q, k, v, *, causal: bool = True, window: int = 0,
              softcap: float = 0.0, q_offset: int = 0,
              prefix_len=None, impl: str = "auto",
              kv_chunk: int = 512, q_block: int = 512,
              scale: Optional[float] = None):
    """Multi-head GQA attention. q: (B,S,Hq,D); k,v: (B,T,Hkv,D).
    ``scale`` multiplies q·k (None: 1/sqrt(D)).

    Shape-driven strategy for the xla path:
      * decode (S small, T large)          → masked full-logit matvec
      * sliding window with S == T large   → blocked local attention
      * otherwise                          → kv-chunked online-softmax
    """
    impl = resolve_impl(impl)
    if impl == "ref":
        return ref_mod.attention_ref(q, k, v, causal=causal, window=window,
                                     softcap=softcap, q_offset=q_offset,
                                     prefix_len=prefix_len, scale=scale)
    if impl in ("pallas", "interpret"):
        from .flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, q_offset=q_offset,
                               prefix_len=prefix_len, scale=scale,
                               interpret=(impl == "interpret"))
    # ---- xla path ----
    B, S, Hq, D = q.shape
    T = k.shape[1]
    if S <= 16 and T > 64:
        return _attention_decode(q, k, v, causal=causal, window=window,
                                 softcap=softcap, q_offset=q_offset,
                                 prefix_len=prefix_len, scale=scale)
    if (causal and window > 0 and S == T and prefix_len is None
            and S >= 2 * window and S % window == 0):
        return _attention_local_blocked(q, k, v, window=window,
                                        softcap=softcap, scale=scale)
    # naive path only when the full logits tensor is demonstrably small
    if B * Hq * S * T * 4 <= (64 << 20):
        return ref_mod.attention_ref(q, k, v, causal=causal, window=window,
                                     softcap=softcap, q_offset=q_offset,
                                     prefix_len=prefix_len, scale=scale)
    return _attention_chunked(q, k, v, causal=causal, window=window,
                              softcap=softcap, q_offset=q_offset,
                              prefix_len=prefix_len, kv_chunk=kv_chunk,
                              scale=scale)


def decode_attention(q, k, v, pos, layer, *, window: int = 0,
                     softcap: float = 0.0, scale: Optional[float] = None,
                     impl: str = "auto"):
    """One query token per slot against the cache as stored. q: (B,Hq,D);
    k, v: (L,B,T,Hkv·D), the stack of layers; pos: (B,) each slot's
    position (its own K/V already written); layer: index into the stack;
    ``scale`` multiplies q·k (None: 1/sqrt(D)).  Returns (B,Hq,D).
    Every impl but the kernel's takes the xla path (the oracle is
    ``ref.decode_attention_ref``)."""
    impl = resolve_impl(impl)
    if impl in ("pallas", "interpret"):
        from .decode_attention import decode_attention as kernel
        return kernel(q, k, v, pos, layer, window=window, softcap=softcap,
                      scale=scale, interpret=(impl == "interpret"))
    B, T, HD = k.shape[1:]
    D = q.shape[-1]
    kl = k[layer].reshape(B, T, HD // D, D)
    vl = v[layer].reshape(B, T, HD // D, D)
    return _attention_decode(q[:, None], kl, vl, causal=True, window=window,
                             softcap=softcap, q_offset=pos,
                             prefix_len=None, scale=scale)[:, 0]


def _softcap(logits, softcap):
    if softcap > 0.0:
        return jnp.tanh(logits / softcap) * softcap
    return logits


def _scaled(logits, D, scale):
    """q·k logits at ``scale`` (None: divided by sqrt(D))."""
    if scale is None:
        return logits / np.sqrt(D)
    return logits * scale


def _attention_decode(q, k, v, *, causal, window, softcap, q_offset,
                      prefix_len, scale=None):
    """Small-S (decode) attention: full logits over T, masked softmax.
    Written as plain jnp reductions over T so that GSPMD shards T (the KV
    sequence) and emits the 2-pass (max, sum) all-reduces itself.

    ``q_offset`` may be a scalar (all sequences at the same position) or a
    (B,) vector (continuous batching: per-slot positions)."""
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    qf = q.astype(jnp.float32).reshape(B, S, Hkv, rep, D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    logits = _scaled(jnp.einsum("bsgrd,btgd->bgrst", qf, kf), D, scale)
    logits = _softcap(logits, softcap)
    qoff = jnp.asarray(q_offset)
    if qoff.ndim == 0:
        qpos = (jnp.arange(S) + qoff)[None, :]              # (1,S)
    else:
        qpos = qoff[:, None] + jnp.arange(S)[None, :]       # (B,S)
    kpos = jnp.arange(T)
    mask = jnp.ones(qpos.shape + (T,), bool)
    if causal:
        cm = kpos[None, None, :] <= qpos[..., None]
        if prefix_len is not None:
            cm = cm | (kpos[None, None, :] < prefix_len)
        mask = mask & cm
    if window > 0:
        mask = mask & (kpos[None, None, :] > qpos[..., None] - window)
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgrst,btgd->bsgrd", p, vf)
    return out.reshape(B, S, Hq, D).astype(q.dtype)


def _attention_local_blocked(q, k, v, *, window, softcap, scale=None):
    """Exact sliding-window attention in O(S·2W): queries in blocks of W
    attend to their own and the previous key block."""
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    rep = Hq // Hkv
    W = window
    nb = S // W
    qf = q.astype(jnp.float32).reshape(B, nb, W, Hq, D)
    kf = jnp.repeat(k.astype(jnp.float32), rep, axis=2).reshape(B, nb, W, Hq, D)
    vf = jnp.repeat(v.astype(jnp.float32), rep, axis=2).reshape(B, nb, W, Hq, D)
    k_prev = jnp.pad(kf[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
    v_prev = jnp.pad(vf[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
    k2 = jnp.concatenate([k_prev, kf], axis=2)   # (B,nb,2W,H,D)
    v2 = jnp.concatenate([v_prev, vf], axis=2)
    logits = _scaled(jnp.einsum("bnqhd,bnkhd->bnhqk", qf, k2), D, scale)
    logits = _softcap(logits, softcap)
    qpos = jnp.arange(W)[:, None] + W                 # position within 2W frame
    kpos = jnp.arange(2 * W)[None, :]
    mask = (kpos <= qpos) & (kpos > qpos - W)
    blk0 = kpos >= W                                   # block 0 has no prev block
    m = jnp.where(jnp.arange(nb)[:, None, None] == 0, mask[None] & blk0[None],
                  mask[None])
    logits = jnp.where(m[None, :, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bnhqk,bnkhd->bnqhd", p, v2)
    return out.reshape(B, S, Hq, D).astype(q.dtype)


def _attention_chunked(q, k, v, *, causal, window, softcap, q_offset,
                       prefix_len, kv_chunk, scale=None):
    """Online-softmax flash attention as a lax.scan over KV chunks —
    O(S·Ck) live memory, exact."""
    B, S, Hq, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    rep = Hq // Hkv
    Ck = min(kv_chunk, T)
    pad = (-T) % Ck
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Tp = T + pad
    nc = Tp // Ck
    kf = jnp.moveaxis(k.astype(jnp.float32).reshape(B, nc, Ck, Hkv, D), 1, 0)
    vf = jnp.moveaxis(v.astype(jnp.float32).reshape(B, nc, Ck, Hkv, D), 1, 0)
    qf = q.astype(jnp.float32).reshape(B, S, Hkv, rep, D)
    qpos = jnp.arange(S)[:, None] + q_offset

    def chunk(carry, inp):
        m_prev, l_prev, acc = carry
        kc, vc, c_idx = inp
        kpos = jnp.arange(Ck)[None, :] + c_idx * Ck
        logits = _scaled(jnp.einsum("bsgrd,bkgd->bsgrk", qf, kc), D, scale)
        logits = _softcap(logits, softcap)
        mask = kpos < T
        if causal:
            cm = kpos <= qpos
            if prefix_len is not None:
                cm = cm | (kpos < prefix_len)
            mask = mask & cm
        if window > 0:
            mask = mask & (kpos > qpos - window)
        logits = jnp.where(mask[None, :, None, None, :], logits, NEG_INF)
        m_cur = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum("bsgrk,bkgd->bsgrd", p, vc)
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, S, Hkv, rep), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, S, Hkv, rep), jnp.float32)
    acc0 = jnp.zeros((B, S, Hkv, rep, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(chunk, (m0, l0, acc0),
                                  (kf, vf, jnp.arange(nc)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, S, Hq, D).astype(q.dtype)


# ===========================================================================
# Mamba2 SSD
# ===========================================================================
def ssd(x, dt, A, B, C, D=None, h0=None, *, chunk: int = 256,
        impl: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD. Shapes as in :func:`repro.kernels.ref.ssd_ref`."""
    impl = resolve_impl(impl)
    if impl == "ref":
        return ref_mod.ssd_ref(x, dt, A, B, C, D, h0)
    if impl in ("pallas", "interpret"):
        from .ssd import ssd_pallas
        return ssd_pallas(x, dt, A, B, C, D, h0, chunk=chunk,
                          interpret=(impl == "interpret"))
    return _ssd_chunked(x, dt, A, B, C, D, h0, chunk=chunk)


def _ssd_chunked(x, dt, A, B, C, D, h0, *, chunk):
    """Chunked SSD (the state-space-duality algorithm): quadratic within
    Q-length chunks, linear state recurrence across chunks."""
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
    xf = x.astype(jnp.float32).reshape(Bb, nc, Q, H, P)
    dtf = dt.astype(jnp.float32).reshape(Bb, nc, Q, H)
    Bf = jnp.repeat(B.astype(jnp.float32), rep, axis=2).reshape(Bb, nc, Q, H, N)
    Cf = jnp.repeat(C.astype(jnp.float32), rep, axis=2).reshape(Bb, nc, Q, H, N)
    Af = A.astype(jnp.float32)

    da = dtf * Af[None, None, None, :]              # (Bb,nc,Q,H) log-decay steps
    cum = jnp.cumsum(da, axis=2)                    # inclusive within-chunk
    total = cum[:, :, -1:, :]                       # (Bb,nc,1,H)

    # intra-chunk: y_i += sum_{j<=i} exp(cum_i - cum_j) dt_j (C_i·B_j) x_j
    # mask the exponent BEFORE exp: for i<j it is large-positive and the
    # overflowed inf would poison the backward of the where (0·inf = NaN)
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]            # (b,c,i,j,h)
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    diff = jnp.where(tri[None, None, :, :, None], diff, -1e30)
    decay = jnp.exp(diff)
    cb = jnp.einsum("bcihn,bcjhn->bcijh", Cf, Bf)
    scores = cb * decay * dtf[:, :, None, :, :]
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", scores, xf)

    # per-chunk end state: S_c = sum_j exp(total - cum_j) dt_j B_j ⊗ x_j
    w = jnp.exp(total - cum) * dtf                  # (Bb,nc,Q,H)
    chunk_state = jnp.einsum("bcjh,bcjhn,bcjhp->bchpn", w, Bf, xf)

    # inter-chunk recurrence over nc
    h_init = jnp.zeros((Bb, H, P, N), jnp.float32) if h0 is None \
        else h0.astype(jnp.float32)
    chunk_decay = jnp.exp(total[:, :, 0, :])        # (Bb,nc,H)

    def carry(h, inp):
        st, dec = inp
        h_out = h                                    # state *entering* the chunk
        h = h * dec[:, :, None, None] + st
        return h, h_out

    h_fin, h_prev = jax.lax.scan(
        carry, h_init, (jnp.moveaxis(chunk_state, 1, 0),
                        jnp.moveaxis(chunk_decay, 1, 0)))
    h_prev = jnp.moveaxis(h_prev, 0, 1)              # (Bb,nc,H,P,N)

    # inter-chunk contribution: y_i += exp(cum_i) C_i · h_prev
    y_inter = jnp.einsum("bcih,bcihn,bchpn->bcihp", jnp.exp(cum), Cf, h_prev)

    y = (y_intra + y_inter).reshape(Bb, Sp, H, P)[:, :S]
    if D is not None:
        y = y + x.astype(jnp.float32)[:, :S] * D.astype(jnp.float32)[None, None, :, None]
    return y.astype(x.dtype), h_fin


def ssd_decode_step(h, x_t, dt_t, A, B_t, C_t, D=None):
    """O(1) SSD decode: one token. h: (B,H,P,N); x_t: (B,H,P);
    dt_t: (B,H); B_t, C_t: (B,G,N). Returns (y_t, h_new)."""
    H = x_t.shape[1]
    G = B_t.shape[1]
    rep = H // G
    hf = h.astype(jnp.float32)
    xf = x_t.astype(jnp.float32)
    dtf = dt_t.astype(jnp.float32)
    Bf = jnp.repeat(B_t.astype(jnp.float32), rep, axis=1)
    Cf = jnp.repeat(C_t.astype(jnp.float32), rep, axis=1)
    decay = jnp.exp(A.astype(jnp.float32)[None] * dtf)
    h_new = hf * decay[..., None, None] + \
        jnp.einsum("bhp,bhn->bhpn", xf * dtf[..., None], Bf)
    y = jnp.einsum("bhpn,bhn->bhp", h_new, Cf)
    if D is not None:
        y = y + xf * D.astype(jnp.float32)[None, :, None]
    return y.astype(x_t.dtype), h_new


# ===========================================================================
# RG-LRU
# ===========================================================================
def rglru(x, r_gate, i_gate, log_lambda, h0=None, *, impl: str = "auto"):
    impl = resolve_impl(impl)
    if impl == "ref":
        return ref_mod.rglru_ref(x, r_gate, i_gate, log_lambda, h0)
    if impl in ("pallas", "interpret"):
        from .rglru_scan import rglru_pallas
        return rglru_pallas(x, r_gate, i_gate, log_lambda, h0,
                            interpret=(impl == "interpret"))
    return _rglru_assoc(x, r_gate, i_gate, log_lambda, h0)


def _rglru_assoc(x, r_gate, i_gate, log_lambda, h0):
    """RG-LRU via log(S)-depth associative scan (the XLA-friendly form)."""
    Bb, S, W = x.shape
    xf = x.astype(jnp.float32)
    r = jax.nn.sigmoid(r_gate.astype(jnp.float32))
    i = jax.nn.sigmoid(i_gate.astype(jnp.float32))
    log_a = -RGLRU_C * jax.nn.softplus(log_lambda.astype(jnp.float32))[None, None] * r
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    b = i * xf * beta
    if h0 is not None:
        b = b.at[:, 0].add(a[:, 0] * h0.astype(jnp.float32))

    def combine(lhs, rhs):
        a1, b1 = lhs
        a2, b2 = rhs
        return a1 * a2, a2 * b1 + b2

    a_s, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h.astype(x.dtype), h[:, -1]


def rglru_decode_step(h, x_t, r_gate_t, i_gate_t, log_lambda):
    """O(1) RG-LRU decode. h: (B,W); x_t/gates: (B,W)."""
    hf = h.astype(jnp.float32)
    r = jax.nn.sigmoid(r_gate_t.astype(jnp.float32))
    i = jax.nn.sigmoid(i_gate_t.astype(jnp.float32))
    log_a = -RGLRU_C * jax.nn.softplus(log_lambda.astype(jnp.float32))[None] * r
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    h_new = a * hf + beta * (i * x_t.astype(jnp.float32))
    return h_new.astype(x_t.dtype), h_new


# ===========================================================================
# MoE router
# ===========================================================================
def router_topk(logits, k: int, *, impl: str = "auto"):
    impl = resolve_impl(impl)
    if impl in ("pallas", "interpret"):
        from .moe_router import router_topk_pallas
        return router_topk_pallas(logits, k, interpret=(impl == "interpret"))
    return ref_mod.router_topk_ref(logits, k)


# ===========================================================================
# grouped matmul (dropless MoE experts)
# ===========================================================================
def grouped_matmul(lhs, rhs, group_sizes, *, layer=None,
                   impl: str = "auto"):
    """Each group of rows of ``lhs`` times its own matrix. lhs: (M, K);
    rhs: (G, K, N), or with ``layer`` a stack (L, G, K, N) of which
    layer ``layer``'s G matrices are used; group_sizes: (G,) int32, the
    groups' rows in order from row 0, summing to at most M.  Returns
    (M, N) float32; rows past the last group are 0.

    ``pallas`` is the megablox kernel (``gmm``): a grid over (row tile,
    group) pairs that visits only the tiles holding a group's rows, each
    group's whole (K, N) matrix one block, so a group's weights are read
    once however many tiles its rows span.  Given a stack, the kernel
    reads the layer's matrices where they lie: the stack is one (L·G)
    group axis and ``gmm``'s group offset, −layer·G, shifts each group's
    block index to ``group + layer·G``.  A per-layer slice would be an
    operand of its own, copied out of the stack before every call.
    ``xla`` is ``lax.ragged_dot``; ``ref`` the oracle."""
    impl = resolve_impl(impl)
    M = lhs.shape[0]
    if impl in ("pallas", "interpret"):
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        tm, m_pad = _gmm_tile(M)
        K, N = rhs.shape[-2:]
        offset = None
        if layer is not None:
            G = rhs.shape[1]
            rhs = rhs.reshape(-1, K, N)
            offset = (-G * jnp.asarray(layer)).astype(jnp.int32)
        out = gmm(jnp.pad(lhs, ((0, m_pad - M), (0, 0))), rhs,
                  group_sizes, preferred_element_type=jnp.float32,
                  tiling=(tm, K, N), group_offset=offset,
                  interpret=(impl == "interpret"))[:M]
    else:
        if layer is not None:
            rhs = rhs[layer]
        if impl == "ref":
            return ref_mod.grouped_matmul_ref(lhs, rhs, group_sizes)
        out = jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                 preferred_element_type=jnp.float32)
    rows = jnp.arange(M)[:, None]
    return jnp.where(rows < jnp.sum(group_sizes), out, 0.0)


def _gmm_tile(m: int) -> Tuple[int, int]:
    """The kernel's row tile for ``m`` rows, and the rows it runs over:
    ``m`` padded to 16 (a bfloat16 tile's rows), then the largest tile
    of at most 128 rows that divides that."""
    m_pad = -(-m // 16) * 16
    tm = next(t for t in (128, 64, 32, 16) if m_pad % t == 0)
    return tm, m_pad


def grouped_matmul_rows(m: int, groups: int, impl: str = "auto") -> int:
    """Rows :func:`grouped_matmul` multiplies for ``m`` rows of lhs in
    ``groups`` groups: the kernel's ``m`` padded to its row tile;
    ``lax.ragged_dot``'s ``m`` on TPU, and off it, where it lowers to a
    dense product of every group's matrix with every row, masked,
    ``groups × m``."""
    impl = resolve_impl(impl)
    if impl in ("pallas", "interpret"):
        return _gmm_tile(m)[1]
    if impl == "xla" and jax.default_backend() != "tpu":
        return groups * m
    return m


# ===========================================================================
# Fletcher-64
# ===========================================================================
def fletcher64(buf, *, impl: str = "auto", block: int = 1024) -> int:
    """Fletcher-64 checksum of a uint32 word array (numpy in, int out).

    Blockwise-combinable: for a block of length L with partial sums
    (s1_b, s2_b): s1 = s1_a + s1_b ; s2 = s2_a + s2_b + s1_a·L  (mod 2³²−1).
    """
    words = np.ascontiguousarray(buf).view(np.uint32).astype(np.uint64)
    impl = resolve_impl(impl)
    if impl in ("pallas", "interpret"):
        from .fletcher import fletcher64_pallas
        return fletcher64_pallas(words, interpret=(impl == "interpret"))
    if impl == "ref":
        return ref_mod.fletcher64_ref(words)
    # xla/numpy fast path: vectorized blockwise combine
    n = words.size
    s1 = np.uint64(0)
    s2 = np.uint64(0)
    M = np.uint64(FLETCHER_MOD)
    for off in range(0, n, block):
        w = words[off:off + block]
        L = np.uint64(w.size)
        b1 = np.uint64(int(w.sum()) % FLETCHER_MOD)
        coef = np.arange(w.size, 0, -1, dtype=np.uint64)
        b2 = np.uint64(int((coef * w % M).sum()) % FLETCHER_MOD)
        s2 = np.uint64((int(s2) + int(b2) + int(s1) * int(L)) % FLETCHER_MOD)
        s1 = np.uint64((int(s1) + int(b1)) % FLETCHER_MOD)
    return (int(s2) << 32) | int(s1)
