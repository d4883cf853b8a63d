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
from repro.kernels import ops
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
    """Reference: each token through its own top-k experts' FFNs,
    gathered per token (no capacity, no dispatch); padded experts are
    never routed to."""
    logits = x2d @ params["router"]
    real = jnp.arange(logits.shape[-1]) < cfg.moe.num_experts
    logits = jnp.where(real, logits, -1e30)
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), cfg.moe.top_k)
    w = w / w.sum(-1, keepdims=True)
    g = jnp.einsum("td,tkdf->tkf", x2d, params["wi_gate"][idx])
    u = jnp.einsum("td,tkdf->tkf", x2d, params["wi_up"][idx])
    return jnp.einsum("tkf,tkfd,tk->td", jax.nn.silu(g) * u,
                      params["wo"][idx], w)


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


def _route_to(params, experts):
    """Router weights under which every token of a positive input picks
    ``experts``, in that order, and no other."""
    col = np.zeros(params["router"].shape, np.float32)
    for rank, e in enumerate(experts):
        col[:, e] = 4.0 / (rank + 1)
    return dict(params, router=jnp.asarray(col))


# (E, k, e_pad, T, routing, impl): Granite's 40 experts top-8 at reduced
# width at a decode step's and a chunk's tokens, also through the TPU
# kernel's body; fewer tokens than experts (empty groups); one expert
# pair taking every token; padded experts; a token count that is a
# multiple of no tile
GROUPED_CASES = {
    "granite_T24": (40, 8, None, 24, None, "xla"),
    "granite_T256": (40, 8, None, 256, None, "xla"),
    "granite_T24_kernel": (40, 8, None, 24, None, "interpret"),
    "empty_groups": (16, 2, None, 3, None, "xla"),
    "all_to_one_expert": (8, 2, None, 16, (5, 2), "xla"),
    "padded_experts": (5, 2, 8, 16, None, "xla"),
    "untiled_T": (8, 2, None, 37, None, "interpret"),
}


@pytest.mark.parametrize("case", list(GROUPED_CASES))
def test_grouped_dropless_equals_per_token_reference(case):
    """The dropless dispatch (a grouped matmul over the routed rows)
    gives each token exactly its top-k experts' weighted outputs."""
    E, k, e_pad, T, routing, impl = GROUPED_CASES[case]
    cfg = tiny_cfg(E=E, k=k).replace(compute_dtype="float32")
    params, _ = unzip(moe_mod.moe_params(cfg, RNG, ("moe",), e_pad=e_pad))
    x = jax.random.normal(jax.random.PRNGKey(T), (1, T, 32))
    if routing is not None:
        params = _route_to(params, routing)
        x = jnp.abs(x)
    y, _ = moe_mod.moe_apply(cfg, params, x, dropless=True, impl=impl)
    want = dense_gather_oracle(cfg, params, x[0])
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# group sizes over M rows: uneven, empty groups among them, one group
# taking every row, rows past the last group, a row count of no tile
GROUP_SIZES = {
    "uneven": (24, [5, 0, 9, 1, 0, 9]),
    "one_group": (16, [0, 0, 16, 0]),
    "rows_past_groups": (20, [3, 4, 0, 6]),
    "untiled_M": (37, [11, 0, 13, 13]),
}


@pytest.mark.parametrize("impl", ["xla", "interpret"])
@pytest.mark.parametrize("case", list(GROUP_SIZES))
def test_grouped_matmul_matches_oracle(case, impl):
    M, sizes = GROUP_SIZES[case]
    kl, kr = jax.random.split(jax.random.PRNGKey(M))
    lhs = jax.random.normal(kl, (M, 24))
    rhs = jax.random.normal(kr, (len(sizes), 24, 40))
    gs = jnp.asarray(sizes, jnp.int32)
    got = ops.grouped_matmul(lhs, rhs, gs, impl=impl)
    want = ops.grouped_matmul(lhs, rhs, gs, impl="ref")
    assert got.dtype == want.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    end = sum(sizes)
    assert not np.any(np.asarray(got)[end:])
    # the oracle itself: the first row of the last group that has rows
    g = max(i for i, n in enumerate(sizes) if n)
    r = sum(sizes[:g])
    np.testing.assert_allclose(np.asarray(want[r]),
                               np.asarray(lhs[r] @ rhs[g]), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("impl", ["xla", "interpret", "ref"])
@pytest.mark.parametrize("layer", [0, 2])
def test_grouped_matmul_reads_a_layer_of_the_stack(layer, impl):
    """Given every layer's matrices stacked (L, G, K, N) and a traced
    layer index, as a layer scan hands them, the product is the one with
    that layer's matrices alone."""
    M, sizes = GROUP_SIZES["uneven"]
    kl, kr = jax.random.split(jax.random.PRNGKey(layer))
    lhs = jax.random.normal(kl, (M, 24))
    stack = jax.random.normal(kr, (3, len(sizes), 24, 40))
    gs = jnp.asarray(sizes, jnp.int32)
    got = jax.jit(lambda lay: ops.grouped_matmul(
        lhs, stack, gs, layer=lay, impl=impl))(jnp.int32(layer))
    want = ops.grouped_matmul(lhs, stack[layer], gs, impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


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
