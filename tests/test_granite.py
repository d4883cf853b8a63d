"""Granite-3.0 MoE's four multipliers on every serving path, against the
benchmark's plain float32 reference (``bench/reference/decoder.py``,
which imports nothing of the program) and the kernel oracles.

A tiny Granite (``configs.reduced``: 4 layers, d 64, 8 experts top-2)
keeps the published multipliers: embeddings × 12, attention scale 1/64,
residual branches × 0.22, logits ÷ 6.  Weights are random from a fixed
key, the embedding table divided by 12 so that the residual branches,
not the token's own embedding, decide the logits.  The program computes
in float32 here, so what it
may differ from the reference by is rounding of the order of
summation: each tolerance below is a few times what float32 gives, and
far below what dropping any one multiplier does.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.kernels import ops
from repro.kernels import ref as kref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.models import Model, unzip

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from bench.reference import decoder as reference  # noqa: E402

ARCH = "granite-moe-3b-a800m"
CFG = configs.reduced(ARCH).replace(compute_dtype="float32")
MULTIPLIERS = ("embedding_multiplier", "attention_multiplier",
               "residual_multiplier", "logits_scaling")
# logits of a float32 program against the float32 reference, as a share
# of the largest |logit|: float32 rounding gives ≤ 4e-7 on every path
# here, a dropped multiplier 0.36 or more; the limit lies between, with
# room for another machine's summation order
TOL = 1e-4


def _source_config(cfg):
    """The configuration as the source's keys give it (what the
    reference reads)."""
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "num_local_experts": cfg.moe.num_experts,
            "num_experts_per_tok": cfg.moe.top_k,
            **{k: getattr(CFG, k) for k in MULTIPLIERS}}


@pytest.fixture(scope="module")
def weights():
    params, _ = unzip(Model(CFG).init(jax.random.PRNGKey(3)))
    emb = params["embed"]["embedding"] / CFG.embedding_multiplier
    return dict(params, embed=dict(params["embed"], embedding=emb))


def _reference_logits(w, tokens):
    """Every position's logits from the reference's full forward pass."""
    c = _source_config(CFG)
    with jax.default_matmul_precision("highest"):
        h = reference._hidden(c, w, jnp.asarray(tokens, jnp.int32), False)
        return np.asarray(jnp.einsum("td,vd->tv", h,
                                     w["embed"]["embedding"])
                          / c["logits_scaling"])


def _rel_err(got, want):
    return float(np.max(np.abs(np.asarray(got) - want))
                 / np.max(np.abs(want)))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, CFG.vocab, n)


def test_preset_runs_the_published_multipliers():
    cfg = configs.get(ARCH)
    assert (cfg.embedding_multiplier, cfg.attention_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) == \
        (12.0, 0.015625, 0.22, 6.0)
    assert cfg.attn_scale == 0.015625
    assert cfg.param_dtype == "float32"        # served as bfloat16
    assert configs.get("qwen1.5-0.5b").attn_scale is None


def _prefill_last_logits(cfg, w, tokens, impl="xla"):
    model = Model(cfg)
    logits, _ = jax.jit(lambda p, b: model.prefill(p, b, impl=impl))(
        w, {"tokens": jnp.asarray(tokens)[None]})
    return np.asarray(logits[0])


def test_monolithic_prefill_logits_match_the_reference(weights):
    tokens = _tokens(40)
    got = _prefill_last_logits(CFG, weights, tokens)
    want = _reference_logits(weights, tokens)[-1]
    assert _rel_err(got, want) < TOL


def _chunked_then_decoded(cfg, w, tokens, n_prompt, chunk, impl):
    """Logits at every position: the prompt in chunks into a cache, then
    one decode step for each remaining token, each fed as it stands."""
    model = Model(cfg)
    cache, _ = unzip(model.cache_specs(1, 64, dtype=jnp.float32))
    chunk_fn = jax.jit(lambda p, c, t, o: model.prefill_chunk(
        p, c, t, o, impl=impl))
    step_fn = jax.jit(lambda p, c, t, pos: model.decode_step(
        p, c, t, pos, impl=impl))
    out = []
    for off in range(0, n_prompt, chunk):
        logits, cache = chunk_fn(w, cache,
                                 jnp.asarray(tokens[off:off + chunk])[None],
                                 jnp.int32(off))
        out.append(np.asarray(logits[0]))
    for pos in range(n_prompt, len(tokens)):
        logits, cache = step_fn(w, cache,
                                jnp.asarray(tokens[pos:pos + 1])[None],
                                jnp.full((1,), pos, jnp.int32))
        out.append(np.asarray(logits))
    return np.concatenate(out)


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_chunked_prefill_then_decode_match_the_reference(weights, impl):
    """Three 8-token chunks, then five cached decode steps, at every
    position; ``interpret`` runs the decode-attention kernel's body (and
    the router kernel's) on the CPU."""
    tokens = _tokens(29, seed=1)
    got = _chunked_then_decoded(CFG, weights, tokens, 24, 8, impl)
    want = _reference_logits(weights, tokens)
    assert got.shape == want.shape
    assert _rel_err(got, want) < TOL


@pytest.mark.parametrize("dropped", MULTIPLIERS)
def test_a_dropped_multiplier_fails_the_tolerance(weights, dropped):
    """The same model with one multiplier left at its default (no
    operation) is far outside the tolerance, on the prefill and on the
    chunk-then-decode path."""
    cfg = CFG.replace(**{dropped: 0.0 if dropped == "attention_multiplier"
                         else 1.0})
    tokens = _tokens(29, seed=1)
    want = _reference_logits(weights, tokens)
    assert _rel_err(_prefill_last_logits(cfg, weights, tokens),
                    want[-1]) > 100 * TOL
    assert _rel_err(_chunked_then_decoded(cfg, weights, tokens, 24, 8,
                                          "xla"), want) > 100 * TOL


@pytest.mark.parametrize("pos", [[0, 5], [127, 129], [200, 255]])
def test_decode_kernel_takes_the_scale(pos):
    """The decode-attention kernel (interpret mode) and the xla decode
    path at Granite's 1/64 against the oracle at the same scale, GQA 3:1
    as in Granite; the default scale would give another answer."""
    B, Hq, Hkv, D, T = 2, 6, 2, 64, 256
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32) * 4
    k = jax.random.normal(ks[1], (1, B, T, Hkv * D), jnp.float32) * 4
    v = jax.random.normal(ks[2], (1, B, T, Hkv * D), jnp.float32)
    p = jnp.asarray(pos, jnp.int32)
    scale = 1 / 64
    want = kref.decode_attention_ref(q, k[0], v[0], p, scale=scale)
    got = decode_attention(q, k, v, p, 0, scale=scale, interpret=True)
    xla = ops.decode_attention(q, k, v, p, 0, scale=scale, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    default = kref.decode_attention_ref(q, k[0], v[0], p)
    assert float(jnp.max(jnp.abs(default - want))) > 1e-2


@pytest.mark.parametrize("impl", ["interpret", "xla", "ref"])
def test_prefill_attention_takes_the_scale(impl):
    """Flash (interpret mode), the xla paths and the oracle's own path,
    causal over 256 positions at 1/64, against ``attention_ref``."""
    B, S, Hq, Hkv, D = 1, 256, 6, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), jnp.float32) * 4
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32) * 4
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
    scale = 1 / 64
    want = kref.attention_ref(q, k, v, causal=True, scale=scale)
    if impl == "interpret":
        got = flash_attention(q, k, v, causal=True, scale=scale,
                              interpret=True)
    else:
        got = ops.attention(q, k, v, causal=True, scale=scale, impl=impl,
                            kv_chunk=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    default = kref.attention_ref(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(default - want))) > 1e-2


def test_xla_chunked_attention_takes_the_scale():
    """The kv-chunked online softmax (taken when the full logits would
    pass 64 MB) at 1/64, against the oracle."""
    B, S, Hq, Hkv, D = 1, 64, 6, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), jnp.float32) * 4
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32) * 4
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
    want = kref.attention_ref(q, k, v, causal=True, scale=1 / 64)
    got = ops._attention_chunked(q, k, v, causal=True, window=0, softcap=0.0,
                                 q_offset=0, prefix_len=None, kv_chunk=16,
                                 scale=1 / 64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
