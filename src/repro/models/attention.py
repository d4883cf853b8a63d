"""GQA attention sub-layer: params, train/prefill apply, decode step.

Supports: GQA/MQA (kv repeat), RoPE (per-kind theta), sliding-window
("local" blocks), tanh logit soft-capping, qk RMS-norm, QKV biases,
prefix-LM bidirectional masks, and cross-attention (enc-dec).

KV caches are dicts ``{"k": (B,T,Hkv·D), "v": (B,T,Hkv·D)}``, lane-dense:
the kv heads lie side by side in the minor dim (a head_dim of 64 as the
minor dim would pad to the 128-lane tile), and the xla paths split the
heads inside their einsums.  Decode writes each slot's new row at its
``pos`` with one scatter and reads the cache where it lies (the
decode-attention kernel on TPU).  When the cache sequence dim is sharded
(sequence-parallel decode), the softmax reductions in
``kernels.ops._attention_decode`` are plain jnp reductions over T, so
GSPMD emits the 2-pass (max/sum) cross-shard reduction instead of
gathering the cache.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels import ops
from .common import P, dense_p, ones_p, zeros_p, apply_rope, rms_norm


def attn_params(cfg: ModelConfig, rng, path, cross: bool = False) -> dict:
    dt = jnp.dtype(cfg.param_dtype)
    d, H, Hkv, D = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    p = {
        "wq": dense_p(rng, path + ("wq",), (d, H, D), ("embed", "heads", "head_dim"), dt),
        "wk": dense_p(rng, path + ("wk",), (d, Hkv, D), ("embed", "kv_heads", "head_dim"), dt),
        "wv": dense_p(rng, path + ("wv",), (d, Hkv, D), ("embed", "kv_heads", "head_dim"), dt),
        "wo": dense_p(rng, path + ("wo",), (H, D, d), ("heads", "head_dim", "embed"), dt,
                      in_dim=H * D),
    }
    if cfg.qkv_bias:
        p["bq"] = zeros_p((H, D), ("heads", "head_dim"), dt)
        p["bk"] = zeros_p((Hkv, D), ("kv_heads", "head_dim"), dt)
        p["bv"] = zeros_p((Hkv, D), ("kv_heads", "head_dim"), dt)
    if cfg.qk_norm:
        p["q_norm"] = ones_p((D,), ("head_dim",), dt)
        p["k_norm"] = ones_p((D,), ("head_dim",), dt)
    return p


def _theta(cfg: ModelConfig, kind: str) -> float:
    if kind == "global" and cfg.rope_theta_global > 0:
        return cfg.rope_theta_global
    return cfg.rope_theta


def _project_q(cfg, p, x, positions, kind, use_rope=True):
    cdt = jnp.dtype(cfg.compute_dtype)
    q = jnp.einsum("bsd,dhk->bshk", x.astype(cdt), p["wq"].astype(cdt))
    if "bq" in p:
        q = q + p["bq"].astype(cdt)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    if use_rope:
        q = apply_rope(q, positions, _theta(cfg, kind))
    return q


def _project_kv(cfg, p, x, positions, kind, use_rope=True):
    cdt = jnp.dtype(cfg.compute_dtype)
    k = jnp.einsum("bsd,dhk->bshk", x.astype(cdt), p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dhk->bshk", x.astype(cdt), p["wv"].astype(cdt))
    if "bk" in p:
        k = k + p["bk"].astype(cdt)
        v = v + p["bv"].astype(cdt)
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if use_rope:
        k = apply_rope(k, positions, _theta(cfg, kind))
    return k, v


def _out(cfg, p, o):
    cdt = jnp.dtype(cfg.compute_dtype)
    return jnp.einsum("bshk,hkd->bsd", o.astype(cdt), p["wo"].astype(cdt))


def attn_apply(cfg: ModelConfig, p: dict, x, *, kind: str = "attn",
               causal: bool = True, prefix_len=None,
               impl: str = "auto") -> jax.Array:
    """Full-sequence self-attention (train / encoder)."""
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]
    q = _project_q(cfg, p, x, positions, kind)
    k, v = _project_kv(cfg, p, x, positions, kind)
    window = cfg.window if kind == "local" else 0
    o = ops.attention(q, k, v, causal=causal, window=window,
                      softcap=cfg.attn_softcap, scale=cfg.attn_scale,
                      prefix_len=prefix_len, impl=impl)
    return _out(cfg, p, o)


def attn_prefill(cfg: ModelConfig, p: dict, x, *, kind: str = "attn",
                 cache_len: int, prefix_len=None,
                 impl: str = "auto") -> Tuple[jax.Array, dict]:
    """Self-attention over the prompt; returns (out, cache)."""
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]
    q = _project_q(cfg, p, x, positions, kind)
    k, v = _project_kv(cfg, p, x, positions, kind)
    window = cfg.window if kind == "local" else 0
    o = ops.attention(q, k, v, causal=True, window=window,
                      softcap=cfg.attn_softcap, scale=cfg.attn_scale,
                      prefix_len=prefix_len, impl=impl)
    pad = ((0, 0), (0, cache_len - S), (0, 0))
    cache = {"k": jnp.pad(_lanes(k), pad), "v": jnp.pad(_lanes(v), pad)}
    return _out(cfg, p, o), cache


def attn_prefill_chunk(cfg: ModelConfig, p: dict, x, cache: dict, offset, *,
                       kind: str = "attn",
                       prefix_len=None) -> Tuple[jax.Array, dict]:
    """Prefill *continuation*: an S-token chunk at absolute positions
    ``offset .. offset+S`` attending causally against a full-length cache
    (earlier chunks / a resumed session's KV live below ``offset``; the
    chunk's own K/V are written at ``offset`` first).  This is the
    building block for micro-batched prefill and KV-session resume —
    ``attn_prefill`` with S == prompt length and ``offset == 0`` is the
    degenerate single-chunk case."""
    B, S, _ = x.shape
    off = jnp.asarray(offset, jnp.int32)
    positions = off + jnp.arange(S)[None, :]
    q = _project_q(cfg, p, x, positions, kind)
    k_new, v_new = _project_kv(cfg, p, x, positions, kind)
    k = jax.lax.dynamic_update_slice(
        cache["k"], _lanes(k_new).astype(cache["k"].dtype), (0, off, 0))
    v = jax.lax.dynamic_update_slice(
        cache["v"], _lanes(v_new).astype(cache["v"].dtype), (0, off, 0))
    window = cfg.window if kind == "local" else 0
    o = ops.attention(q, _heads(k, cfg.hd), _heads(v, cfg.hd), causal=True,
                      window=window, softcap=cfg.attn_softcap,
                      scale=cfg.attn_scale, q_offset=off,
                      prefix_len=prefix_len, impl="xla")
    return _out(cfg, p, o), {"k": k, "v": v}


def attn_decode(cfg: ModelConfig, p: dict, x, cache: dict, pos, *,
                kind: str = "attn", layer=None,
                impl: str = "auto") -> Tuple[jax.Array, dict]:
    """One-token decode against the KV cache, in place. x: (B,1,d); ``pos``
    (B,) is each slot's position.  ``cache`` holds one layer's k/v,
    (B,T,Hkv·D), or with ``layer`` the whole stack, (L,B,T,Hkv·D): each
    slot's new row is written at (layer, slot, pos), every slot included,
    and attention reads the stack where it lies.  Returns (out, the
    written cache)."""
    B = x.shape[0]
    q = _project_q(cfg, p, x, pos[:, None], kind)
    k_new, v_new = _project_kv(cfg, p, x, pos[:, None], kind)
    k, v = cache["k"], cache["v"]
    at = layer
    if layer is None:
        k, v, at = k[None], v[None], 0
    slots = jnp.arange(B)
    k = k.at[at, slots, pos].set(_lanes(k_new)[:, 0].astype(k.dtype))
    v = v.at[at, slots, pos].set(_lanes(v_new)[:, 0].astype(v.dtype))
    window = cfg.window if kind == "local" else 0
    o = ops.decode_attention(q[:, 0], k, v, pos, at, window=window,
                             softcap=cfg.attn_softcap, scale=cfg.attn_scale,
                             impl=impl)
    if layer is None:
        k, v = k[0], v[0]
    return _out(cfg, p, o[:, None]), {"k": k, "v": v}


def _lanes(kv):
    """(B,S,Hkv,D) → (B,S,Hkv·D), the cache's lane-dense layout."""
    return kv.reshape(kv.shape[:2] + (-1,))


def _heads(kv, head_dim: int):
    """(B,T,Hkv·D) → (B,T,Hkv,D)."""
    return kv.reshape(kv.shape[:2] + (-1, head_dim))


# ---------------------------------------------------------------------------
# cross-attention (enc-dec)
# ---------------------------------------------------------------------------
def cross_attn_apply(cfg: ModelConfig, p: dict, x, memory_kv: dict,
                     impl: str = "auto") -> jax.Array:
    """Decoder cross-attention: q from x, kv precomputed from encoder
    memory (no RoPE, bidirectional)."""
    B, S, _ = x.shape
    positions = jnp.zeros((1, S), jnp.int32)
    q = _project_q(cfg, p, x, positions, kind="attn", use_rope=False)
    o = ops.attention(q, memory_kv["k"], memory_kv["v"], causal=False,
                      softcap=cfg.attn_softcap, scale=cfg.attn_scale,
                      impl=impl)
    return _out(cfg, p, o)


def cross_kv(cfg: ModelConfig, p: dict, memory) -> dict:
    """Precompute cross-attention K/V from encoder output (prefill)."""
    B, F, _ = memory.shape
    positions = jnp.zeros((1, F), jnp.int32)
    k, v = _project_kv(cfg, p, memory, positions, kind="attn", use_rope=False)
    return {"k": k, "v": v}
