"""Plain float32 reference of a decoder-only transformer with GQA, RoPE,
RMSNorm and a SwiGLU MLP or a top-k mixture of experts (Qwen1.5,
Granite-3.0 MoE as the program runs it).

It imports nothing of the program.  It reads the benchmark's weight tree
by name, runs the whole forward pass over one sequence in float32 at
``highest`` matmul precision, with no cache, no batching and no kernels,
and reduces each position's logits to what the comparison needs.

Departures from the published models are the configuration file's
``reduced`` keys; the reference follows the file.

The control is the same forward pass with every matmul operand rounded
to float8 e4m3 (per-tensor scale), the precision step below the
bfloat16 that the configuration computes in.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import numpy as np

_Q_BLOCK = 1024          # query rows per attention block
_V_BLOCK = 512           # positions per vocabulary-projection block
BUCKET = 512             # sequences are padded to this times a power of 2


def _fp8(x):
    import jax.numpy as jnp
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, low):
    import jax.numpy as jnp
    if low:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b)


def _rms(x, w, eps):
    import jax.numpy as jnp
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * w


def _rope(x, pos, theta):
    import jax.numpy as jnp
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * freq          # (T, d/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(c: Dict, x, lw, low: bool):
    """One block over the whole sequence x: (T, d)."""
    import jax
    import jax.numpy as jnp
    T = x.shape[0]
    H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    D = c["hidden_size"] // H
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    scale = c.get("attention_multiplier", 1.0 / math.sqrt(D))
    res = c.get("residual_multiplier", 1.0)
    pos = jnp.arange(T)
    a = lw["attn"]
    h = _rms(x, lw["norm1"], eps)
    q = _mm("td,dhk->thk", h, a["wq"], low)
    k = _mm("td,dhk->thk", h, a["wk"], low)
    v = _mm("td,dhk->thk", h, a["wv"], low)
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    rep = H // Hkv
    q = q.reshape(T, Hkv, rep, D)
    outs = []
    for s in range(0, T, _Q_BLOCK):
        qb = q[s:s + _Q_BLOCK]
        logits = _mm("qgrd,tgd->grqt", qb, k, low) * scale
        mask = (s + jnp.arange(qb.shape[0]))[:, None] >= pos[None, :]
        logits = jnp.where(mask, logits, -jnp.inf)
        p = jax.nn.softmax(logits, axis=-1)
        outs.append(_mm("grqt,tgd->qgrd", p, v, low))
    o = jnp.concatenate(outs, 0).reshape(T, H, D)
    x = x + res * _mm("thk,hkd->td", o, a["wo"], low)
    h = _rms(x, lw["norm2"], eps)
    if "moe" in lw:
        m = lw["moe"]
        k_top = c["num_experts_per_tok"]
        n_exp = c["num_local_experts"]
        r = _mm("td,de->te", h, m["router"][:, :n_exp], low)
        top, idx = jax.lax.top_k(r, k_top)
        gates = jax.nn.softmax(top, axis=-1)                 # over top-k
        w = jnp.zeros_like(r).at[jnp.arange(T)[:, None], idx].set(gates)
        g = _mm("td,edf->tef", h, m["wi_gate"][:n_exp], low)
        u = _mm("td,edf->tef", h, m["wi_up"][:n_exp], low)
        y = _mm("tef,efd->ted", jax.nn.silu(g) * u,
                m["wo"][:n_exp], low)
        y = jnp.einsum("te,ted->td", w, y)
    else:
        m = lw["mlp"]
        g = _mm("td,df->tf", h, m["wi_gate"], low)
        u = _mm("td,df->tf", h, m["wi_up"], low)
        y = _mm("tf,fd->td", jax.nn.silu(g) * u, m["wo"], low)
    return x + res * y


def _hidden(c: Dict, w, tokens, low: bool):
    import jax
    x = w["embed"]["embedding"][tokens] * c.get("embedding_multiplier", 1.0)
    stacked = w["periods"][0]

    def body(x, lw):
        return _layer(c, x, lw, low), None

    x, _ = jax.lax.scan(body, x, stacked)
    return _rms(x, w["final_norm"], c["rms_norm_eps"])


@functools.lru_cache(maxsize=None)
def _compiled(cfg_items: Tuple, control: bool):
    import jax
    import jax.numpy as jnp
    c = dict(cfg_items)

    def fn(w, tokens, targets):
        """Per position: the reference's best logit, and its logit of the
        target token (``control``: of the float8 pass's first token)."""
        emb = w["embed"]["embedding"]
        div = c.get("logits_scaling", 1.0)
        xr = _hidden(c, w, tokens, False)
        xl = _hidden(c, w, tokens, True) if control else None
        best, picked = [], []
        for s in range(0, xr.shape[0], _V_BLOCK):
            lr = jnp.einsum("td,vd->tv", xr[s:s + _V_BLOCK], emb) / div
            if control:
                ll = _mm("td,vd->tv", xl[s:s + _V_BLOCK], emb, True) / div
                pick = jnp.argmax(ll, -1)
            else:
                pick = targets[s:s + _V_BLOCK]
            best.append(jnp.max(lr, -1))
            picked.append(jnp.take_along_axis(lr, pick[:, None], -1)[:, 0])
        return jnp.concatenate(best), jnp.concatenate(picked)

    return jax.jit(fn)


def _cfg_items(c: Dict) -> Tuple:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "rms_norm_eps", "rope_theta", "num_local_experts",
            "num_experts_per_tok", "attention_multiplier",
            "embedding_multiplier", "residual_multiplier", "logits_scaling")
    return tuple((k, c[k]) for k in keys if k in c)


def gaps(c: Dict, w, prompt, served, *, control: bool = False
         ) -> np.ndarray:
    """For each served token, how far its reference logit lies below the
    reference's best logit at that position (0 where it is the best).

    ``control=True`` instead judges the token that the float8 forward
    pass would put first at each of those positions."""
    import jax
    prompt = list(map(int, prompt))
    served = list(map(int, served))
    seq = prompt + served[:-1]
    n = len(seq)
    tb = BUCKET
    while tb < n:
        tb *= 2
    tokens = np.zeros(tb, np.int32)
    tokens[:n] = seq
    targets = np.zeros(tb, np.int32)
    first = len(prompt) - 1
    targets[first:first + len(served)] = served
    fn = _compiled(_cfg_items(c), control)
    with jax.default_matmul_precision("highest"):
        best, got = fn(w, tokens, targets)
    best = np.asarray(best)[first:first + len(served)]
    got = np.asarray(got)[first:first + len(served)]
    return best - got
