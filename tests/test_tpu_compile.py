"""Compile the serving path's kernels and steps for a described TPU v5e.

No chip is attached: the TPU compiler runs against a ``v5e:2x2``
topology described inside a module fixture, so what Mosaic or XLA would
refuse on the chip (a block not aligned to the tiling, an unsupported
primitive, a program over the device's memory) fails here.  Nothing
runs; the tests say nothing about results or times.  Kernels get shapes
at the published widths of the model that uses them.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_router import router_topk_pallas
from repro.kernels.rglru_scan import rglru_pallas
from repro.kernels.ssd import ssd_pallas
from repro.models import Model, unzip
from repro.serve.engine import decode_executable, sample_executable

HBM_BYTES = 16 * 2 ** 30        # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compile
    cache off (an entry compiled for a described chip cannot be read
    back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _qwen_shapes(one_chip, batch, cache_len):
    model = Model(configs.get("qwen1.5-0.5b"))
    params = _on(one_chip, unzip(jax.eval_shape(model.init,
                                                jax.random.PRNGKey(0)))[0])
    cache = _on(one_chip, unzip(jax.eval_shape(
        lambda: model.cache_specs(batch, cache_len)))[0])
    return model, params, cache


@pytest.mark.parametrize("seq", [128, 512])
def test_flash_attention_qwen_width(one_chip, seq):
    cfg = configs.get("qwen1.5-0.5b")
    q = _sds(one_chip, (1, seq, cfg.n_heads, cfg.hd))
    kv = _sds(one_chip, (1, seq, cfg.n_kv_heads, cfg.hd))
    c = _compile(lambda q, k, v: flash_attention(q, k, v, causal=True),
                 q, kv, kv)
    assert "tpu_custom_call" in c.as_text()


def test_qwen_prefill_holds_flash_kernel(one_chip):
    model, params, _ = _qwen_shapes(one_chip, 1, 1024)
    tokens = _sds(one_chip, (1, 128), jnp.int32)
    c = _compile(lambda p, t: model.prefill(p, {"tokens": t},
                                            cache_len=1024, impl="pallas"),
                 params, tokens)
    assert "tpu_custom_call" in c.as_text()


def test_decode_attention_qwen_width(one_chip):
    cfg = configs.get("qwen1.5-0.5b")
    B, T = 24, 2048
    q = _sds(one_chip, (B, cfg.n_heads, cfg.hd))
    kv = _sds(one_chip, (cfg.n_layers, B, T, cfg.n_kv_heads * cfg.hd))
    c = _compile(lambda q, k, v, p, i: decode_attention(q, k, v, p, i),
                 q, kv, kv, _sds(one_chip, (B,), jnp.int32),
                 _sds(one_chip, (), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def _copy_bytes(hlo: str):
    """Bytes of each ``copy`` (or async ``copy-start``) result in an HLO
    text."""
    width = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
             "u8": 1, "pred": 1}
    for m in re.finditer(r"= \(?(\w+)\[([\d,]*)\]\S* copy(?:-start)?\(",
                         hlo):
        dims = [int(d) for d in m.group(2).split(",") if d]
        yield width.get(m.group(1), 4) * int(np.prod(dims))


@pytest.mark.parametrize("batch,cache_len,budget", [
    (8, 1024, HBM_BYTES),
    (24, 2048, 8.2e9),          # the benchmark's short_decode deployment
])
def test_qwen_decode_step_fits_one_chip(one_chip, batch, cache_len, budget):
    """The engine's own decode executable, as it builds it on a chip
    (kernel path, cache donated), updates the bfloat16 cache in place:
    the output aliases the whole cache, no copy of one layer's K or V or
    more is made, and what the step holds fits the budget."""
    model, params, cache = _qwen_shapes(one_chip, batch, cache_len)
    toks = _sds(one_chip, (batch, 1), jnp.int32)
    pos = _sds(one_chip, (batch,), jnp.int32)
    c = decode_executable(model, "pallas", donate=True).lower(
        params, cache, toks, pos).compile()
    m = c.memory_analysis()
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(cache))
    assert m.alias_size_in_bytes >= cache_bytes, (m.alias_size_in_bytes,
                                                  cache_bytes)
    k = cache["periods"][0]["k"]
    layer_bytes = k.size // k.shape[0] * k.dtype.itemsize
    big = [b for b in _copy_bytes(c.as_text()) if b >= layer_bytes]
    assert not big, big
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert held <= budget, held


def test_granite_decode_step_fits_one_chip(one_chip):
    """Granite-3.0 MoE's engine decode at the benchmark's deployment (24
    slots × 2048, bfloat16 weights, the decode-attention kernel at its
    1/64 scale, the dropless 40-expert dispatch): the cache is updated in
    place, no copy of one layer's K or V is made, and weights, cache and
    step fit 10.5 GB (compiled: 9.82 GB)."""
    model = Model(configs.get("granite-moe-3b-a800m").replace(
        param_dtype="bfloat16"))
    params = _on(one_chip, unzip(jax.eval_shape(model.init,
                                                jax.random.PRNGKey(0)))[0])
    cache = _on(one_chip, unzip(jax.eval_shape(
        lambda: model.cache_specs(24, 2048)))[0])
    c = decode_executable(model, "pallas", donate=True).lower(
        params, cache, _sds(one_chip, (24, 1), jnp.int32),
        _sds(one_chip, (24,), jnp.int32)).compile()
    text = c.as_text()
    assert "tpu_custom_call" in text
    m = c.memory_analysis()
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(cache))
    assert m.alias_size_in_bytes >= cache_bytes
    k = cache["periods"][0]["k"]
    layer_bytes = k.size // k.shape[0] * k.dtype.itemsize
    assert not [b for b in _copy_bytes(text) if b >= layer_bytes]
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert held <= 10.5e9, held


@pytest.mark.parametrize("name,shape,rows", [
    ("qwen1.5-0.5b", (24, None), 24),      # a decode step's 24 slots
    ("granite-moe-3b-a800m", (24, None), 24),
    ("qwen1.5-0.5b", (1, 256, None), 1),   # a prompt's last real row
])
def test_sampler_compiles_at_vocab_width(one_chip, name, shape, rows):
    """The engine's sampler over a call's float32 logits at the model's
    vocabulary: greedy and temperature rows in one program, the draw
    behind a branch that an all-greedy call does not take."""
    vocab = configs.get(name).vocab
    shape = tuple(vocab if d is None else d for d in shape)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    c = sample_executable().lower(
        _sds(one_chip, shape, jnp.float32),
        _sds(one_chip, (), jnp.int32),
        _sds(one_chip, (rows,), jnp.float32),
        _sds(one_chip, key.shape, key.dtype)).compile()
    assert " conditional(" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < HBM_BYTES


def test_router_topk_granite_width(one_chip):
    cfg = configs.get("granite-moe-3b-a800m")
    logits = _sds(one_chip, (4096, cfg.moe.num_experts), jnp.float32)
    c = _compile(lambda x: router_topk_pallas(x, cfg.moe.top_k), logits)
    assert "tpu_custom_call" in c.as_text()


def test_ssd_mamba2_width(one_chip):
    cfg = configs.get("mamba2-1.3b")
    ssm = cfg.ssm
    H = ssm.expand * cfg.d_model // ssm.head_dim
    B, S, G = 1, 512, ssm.ngroups
    args = (_sds(one_chip, (B, S, H, ssm.head_dim)),
            _sds(one_chip, (B, S, H)),
            _sds(one_chip, (H,), jnp.float32),
            _sds(one_chip, (B, S, G, ssm.state_dim)),
            _sds(one_chip, (B, S, G, ssm.state_dim)),
            _sds(one_chip, (H,), jnp.float32))
    c = _compile(lambda x, dt, A, Bm, Cm, D: ssd_pallas(
        x, dt, A, Bm, Cm, D, chunk=ssm.chunk), *args)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("batch", [1, 4])
def test_rglru_recurrentgemma_width(one_chip, batch):
    W = configs.get("recurrentgemma-9b").rglru.lru_width
    x = _sds(one_chip, (batch, 512, W))
    args = (x, x, x, _sds(one_chip, (W,), jnp.float32),
            _sds(one_chip, (batch, W), jnp.float32))
    c = _compile(lambda x, r, i, ll, h0: rglru_pallas(x, r, i, ll, h0),
                 *args)
    assert "tpu_custom_call" in c.as_text()
