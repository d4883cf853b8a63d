"""MoE invariants: dropless exactness, capacity-drop monotonicity,
weight normalization, aux-loss bounds, expert-parallel parity (SPMD run
in a subprocess with 8 host devices)."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import moe as moe_mod
from repro.models.common import unzip

from proptest import cases

RNG = jax.random.PRNGKey(0)


def tiny_cfg(E=8, k=2, shared=0):
    from repro.configs.base import ModelConfig, MoEConfig
    return ModelConfig(d_model=32, d_ff=16, vocab=64,
                       moe=MoEConfig(num_experts=E, top_k=k,
                                     num_shared_experts=shared))


def dense_gather_oracle(cfg, params, x2d):
    """Reference: per-token gather of expert FFNs (no capacity)."""
    logits = x2d @ params["router"]
    w, idx, _ = jax.lax.top_k(jax.nn.softmax(logits, -1), cfg.moe.top_k), None, None
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), cfg.moe.top_k)
    w = w / w.sum(-1, keepdims=True)
    y = jnp.zeros_like(x2d)
    for t in range(x2d.shape[0]):
        acc = jnp.zeros((x2d.shape[1],))
        for j in range(cfg.moe.top_k):
            e = idx[t, j]
            g = x2d[t] @ params["wi_gate"][e]
            u = x2d[t] @ params["wi_up"][e]
            acc = acc + w[t, j] * ((jax.nn.silu(g) * u) @ params["wo"][e])
        y = y.at[t].set(acc)
    return y


@cases(5)
def test_dropless_equals_dense_gather(rng):
    cfg = tiny_cfg()
    pp = moe_mod.moe_params(cfg, RNG, ("moe",))
    params, _ = unzip(pp)
    T = int(rng.integers(4, 24))
    x = jnp.asarray(rng.standard_normal((1, T, 32)), jnp.float32)
    cfgf = cfg.replace(compute_dtype="float32")
    y, aux = moe_mod.moe_apply(cfgf, params, x, dropless=True)
    want = dense_gather_oracle(cfgf, params, x[0])
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_capacity_monotone_drops():
    """Raising the capacity factor monotonically increases the number of
    tokens whose output matches the dropless reference; at high capacity
    the outputs are identical."""
    cfg = tiny_cfg().replace(compute_dtype="float32")
    params, _ = unzip(moe_mod.moe_params(cfg, RNG, ("moe",)))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 64, 32))
    y_full, _ = moe_mod.moe_apply(cfg, params, x, dropless=True)

    def equal_rows(cf):
        y_cap, _ = moe_mod.moe_apply(cfg, params, x, capacity_factor=cf)
        return int(jnp.sum(jnp.all(jnp.abs(y_cap[0] - y_full[0]) < 1e-5,
                                   axis=-1)))

    counts = [equal_rows(cf) for cf in (0.25, 0.5, 1.0, 8.0)]
    assert counts == sorted(counts), counts
    assert counts[-1] == 64


def test_aux_losses_bounded():
    cfg = tiny_cfg().replace(compute_dtype="float32")
    params, _ = unzip(moe_mod.moe_params(cfg, RNG, ("moe",)))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32))
    _, aux = moe_mod.moe_apply(cfg, params, x, dropless=True)
    # perfectly balanced load ⇒ lb = aux_coef; random ⇒ close to it
    assert 0.0 < float(aux["moe_lb"]) < 10 * cfg.moe.aux_coef
    assert float(aux["moe_z"]) >= 0.0


def test_padded_experts_masked():
    cfg = tiny_cfg(E=5, k=2).replace(compute_dtype="float32")
    params, _ = unzip(moe_mod.moe_params(cfg, RNG, ("moe",), e_pad=8))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 16, 32))
    y, _ = moe_mod.moe_apply(cfg, params, x, dropless=True)
    # routing must never select padded experts 5..7
    logits = x[0] @ params["router"]
    logits = jnp.where(jnp.arange(8) >= 5, -1e30, logits)
    _, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), 2)
    assert int(jnp.max(idx)) < 5
    assert np.all(np.isfinite(np.asarray(y)))


SPMD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import ModelConfig, MoEConfig
    from repro.models import moe as moe_mod
    from repro.models.common import unzip

    cfg = ModelConfig(d_model=32, d_ff=16, vocab=64,
                      moe=MoEConfig(num_experts=8, top_k=2),
                      compute_dtype="float32")
    params, _ = unzip(moe_mod.moe_params(cfg, jax.random.PRNGKey(0), ("m",)))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
    y_local, aux_local = moe_mod.moe_apply(cfg, params, x, dropless=True)

    from jax.sharding import AxisType
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    spmd = moe_mod.MoESpmd(mesh=mesh, token_axes=("data",),
                           expert_axis="model")
    with mesh:
        y_spmd, aux_spmd = jax.jit(
            lambda p, xx: moe_mod.moe_apply(cfg, p, xx, spmd=spmd,
                                            dropless=True))(params, x)
    np.testing.assert_allclose(np.asarray(y_spmd), np.asarray(y_local),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(aux_spmd["moe_lb"]),
                               float(aux_local["moe_lb"]), rtol=1e-3)
    print("SPMD_PARITY_OK")
""")


def test_expert_parallel_parity_spmd():
    """MoE over a real (2,4) device mesh == single-device math."""
    r = subprocess.run([sys.executable, "-c", SPMD_SCRIPT],
                       capture_output=True, text=True, timeout=300,
                       cwd=".")
    assert "SPMD_PARITY_OK" in r.stdout, r.stdout + r.stderr
