"""Model substrate: parameters with logical sharding axes, norms, RoPE,
MLPs, embeddings.

Parameters are plain pytrees whose leaves are :class:`P` — an array tagged
with a tuple of *logical axis names* (one per dim).  The distribution
layer (``distrib/sharding.py``) maps logical names to mesh axes, so model
code never mentions the mesh.  ``unzip(tree)`` splits a P-tree into
(arrays, axes) pytrees; ``jax.eval_shape`` over an ``init`` gives abstract
params for the dry-run without allocating.
"""
from __future__ import annotations

import hashlib
import math
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig


@jax.tree_util.register_pytree_node_class
class P:
    """An array leaf tagged with logical axis names (len == ndim)."""

    __slots__ = ("value", "axes")

    def __init__(self, value, axes: Tuple[str, ...]):
        self.value = value
        self.axes = tuple(axes)

    def tree_flatten(self):
        return (self.value,), self.axes

    @classmethod
    def tree_unflatten(cls, axes, children):
        return cls(children[0], axes)

    def __repr__(self):
        shape = getattr(self.value, "shape", None)
        return f"P{shape}{self.axes}"


def is_p(x) -> bool:
    return isinstance(x, P)


class Axes(tuple):
    """Logical-axis tuple. A *leaf* type (tuple subclass) so axes trees
    can be tree_map'd alongside value trees without ambiguity against
    tuple containers."""


def unzip(tree):
    """P-tree -> (value tree, axes tree)."""
    values = jax.tree_util.tree_map(lambda p: p.value, tree, is_leaf=is_p)
    axes = jax.tree_util.tree_map(lambda p: Axes(p.axes), tree, is_leaf=is_p)
    return values, axes


def zip_axes(values, axes):
    """(value tree, axes tree) -> P-tree."""
    return jax.tree_util.tree_map(P, values, axes)


def stack_p(trees):
    """Stack a list of same-structure P-trees along a new 'layers' axis."""
    def leaf(*ps):
        return P(jnp.stack([p.value for p in ps]), ("layers",) + ps[0].axes)
    return jax.tree_util.tree_map(leaf, *trees, is_leaf=is_p)


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------
def _key(rng, *path) -> jax.Array:
    """Per-parameter key: the path folded in through a stable digest
    (Python's ``hash`` of a str is salted per process, so it would give
    every process different parameters)."""
    k = rng
    for p in path:
        digest = hashlib.blake2b(repr(p).encode(), digest_size=4).digest()
        k = jax.random.fold_in(k, int.from_bytes(digest, "little") >> 1)
    return k


def dense_p(rng, path, shape, axes, dtype, in_dim: Optional[int] = None) -> P:
    """Truncated-normal fan-in init."""
    fan_in = in_dim if in_dim is not None else shape[0]
    std = 1.0 / math.sqrt(max(fan_in, 1))
    v = jax.random.truncated_normal(_key(rng, *path), -2.0, 2.0, shape,
                                    jnp.float32) * std
    return P(v.astype(dtype), axes)


def zeros_p(shape, axes, dtype) -> P:
    return P(jnp.zeros(shape, dtype), axes)


def ones_p(shape, axes, dtype) -> P:
    return P(jnp.ones(shape, dtype), axes)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def rms_norm(x, weight, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    D = x.shape[-1]
    freqs = rope_frequencies(D, theta)                        # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (...,S,D/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------
def mlp_params(cfg: ModelConfig, rng, path, d_ff: Optional[int] = None,
               dtype=None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dt = dtype or jnp.dtype(cfg.param_dtype)
    p = {}
    if cfg.mlp in ("swiglu", "geglu"):
        p["wi_gate"] = dense_p(rng, path + ("wi_gate",), (d, f),
                               ("embed", "mlp"), dt)
        p["wi_up"] = dense_p(rng, path + ("wi_up",), (d, f),
                             ("embed", "mlp"), dt)
    else:
        p["wi"] = dense_p(rng, path + ("wi",), (d, f), ("embed", "mlp"), dt)
    p["wo"] = dense_p(rng, path + ("wo",), (f, d), ("mlp", "embed"), dt,
                      in_dim=f)
    return p


def mlp_apply(cfg: ModelConfig, p: dict, x):
    cdt = jnp.dtype(cfg.compute_dtype)
    xc = x.astype(cdt)
    if cfg.mlp == "swiglu":
        h = jax.nn.silu(xc @ p["wi_gate"].astype(cdt)) * (xc @ p["wi_up"].astype(cdt))
    elif cfg.mlp == "geglu":
        h = jax.nn.gelu(xc @ p["wi_gate"].astype(cdt), approximate=True) \
            * (xc @ p["wi_up"].astype(cdt))
    elif cfg.mlp == "relu2":
        h = jnp.square(jax.nn.relu(xc @ p["wi"].astype(cdt)))
    else:  # gelu
        h = jax.nn.gelu(xc @ p["wi"].astype(cdt), approximate=True)
    return h @ p["wo"].astype(cdt)


# ---------------------------------------------------------------------------
# embedding / unembedding
# ---------------------------------------------------------------------------
def embed_params(cfg: ModelConfig, rng) -> dict:
    dt = jnp.dtype(cfg.param_dtype)
    p = {"embedding": dense_p(rng, ("embed_table",), (cfg.vocab, cfg.d_model),
                              ("vocab", "embed"), dt, in_dim=cfg.d_model)}
    if not cfg.tie_embeddings:
        p["head"] = dense_p(rng, ("head",), (cfg.d_model, cfg.vocab),
                            ("embed", "vocab"), dt)
    if cfg.frontend != "none" and cfg.frontend_dim:
        p["frontend_proj"] = dense_p(rng, ("frontend_proj",),
                                     (cfg.frontend_dim, cfg.d_model),
                                     ("frontend", "embed"), dt)
    return p


def embed_tokens(cfg: ModelConfig, p: dict, tokens):
    cdt = jnp.dtype(cfg.compute_dtype)
    h = jnp.take(p["embedding"], tokens, axis=0).astype(cdt)
    if cfg.embed_scale:
        h = h * jnp.asarray(math.sqrt(cfg.d_model), cdt)
    if cfg.embedding_multiplier != 1.0:
        h = h * jnp.asarray(cfg.embedding_multiplier, cdt)
    return h


def unembed(cfg: ModelConfig, p: dict, h):
    cdt = jnp.dtype(cfg.compute_dtype)
    w = p["embedding"].T if cfg.tie_embeddings else p["head"]
    logits = h.astype(cdt) @ w.astype(cdt)
    logits = logits.astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    if cfg.logit_softcap > 0.0:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits


# ---------------------------------------------------------------------------
# cross-entropy (chunked over sequence; vocab stays sharded)
# ---------------------------------------------------------------------------
def chunked_ce_loss(cfg: ModelConfig, p: dict, h, targets, *,
                    chunk: int = 512, z_coef: float = 1e-4,
                    ignore_id: int = -1, logits_sharding=None):
    """Softmax CE + z-loss without materializing (B,S,V) at once.

    h: (B,S,d) final hidden states; targets: (B,S) int32.
    Scans over S in chunks; within a chunk the (B,c,V) logits are formed,
    reduced, and discarded. Vocab reductions are plain jnp so GSPMD keeps
    V sharded and emits the cross-shard reductions.
    """
    B, S, d = h.shape
    c = min(chunk, S)
    pad = (-S) % c
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)),
                          constant_values=ignore_id)
    Sp = S + pad
    nc = Sp // c
    hs = jnp.moveaxis(h.reshape(B, nc, c, d), 1, 0)
    ts = jnp.moveaxis(targets.reshape(B, nc, c), 1, 0)

    def body(acc, inp):
        hc, tc = inp
        logits = unembed(cfg, p, hc)                      # (B,c,V) f32
        if logits_sharding is not None:
            logits = jax.lax.with_sharding_constraint(logits,
                                                      logits_sharding)
        lse = jax.nn.logsumexp(logits, axis=-1)           # (B,c)
        tgt = jnp.take_along_axis(
            logits, jnp.maximum(tc, 0)[..., None], axis=-1)[..., 0]
        valid = (tc != ignore_id)
        nll = jnp.where(valid, lse - tgt, 0.0)
        zl = jnp.where(valid, jnp.square(lse), 0.0)
        loss_sum, z_sum, n = acc
        return (loss_sum + nll.sum(), z_sum + zl.sum(),
                n + valid.sum()), None

    (loss_sum, z_sum, n), _ = jax.lax.scan(
        body, (jnp.float32(0), jnp.float32(0), jnp.int32(0)), (hs, ts))
    n = jnp.maximum(n, 1)
    ce = loss_sum / n
    z = z_sum / n
    return ce + z_coef * z, {"ce": ce, "z_loss": z, "tokens": n}
