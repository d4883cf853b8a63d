"""Distribution layer: sharding resolver rules, compressed collectives
(convergence parity), pipeline-parallel stage runner (device-mesh
subprocesses)."""
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from proptest import cases


def fake_mesh(shape=(16, 16), axes=("data", "model")):
    """AbstractMesh: enough for spec resolution without devices."""
    from repro.distrib.sharding import abstract_mesh
    return abstract_mesh(shape, axes)


def test_spec_resolution_basics():
    from jax.sharding import PartitionSpec as PS
    from repro.distrib.sharding import DEFAULT_RULES, spec_for
    mesh = fake_mesh()
    # TP + FSDP weight
    s = spec_for((1024, 16, 64), ("embed", "heads", "head_dim"), mesh,
                 DEFAULT_RULES)
    assert s == PS("data", "model")
    # kv_heads=8 does not divide 16 -> replicated
    s = spec_for((1024, 8, 64), ("embed", "kv_heads", "head_dim"), mesh,
                 DEFAULT_RULES)
    assert s == PS("data")
    # vocab-parallel embedding
    s = spec_for((49155, 1536), ("vocab", "embed"), mesh, DEFAULT_RULES)
    assert s == PS(None, "data")        # 49155 odd -> vocab replicated!
    s = spec_for((151936, 1024), ("vocab", "embed"), mesh, DEFAULT_RULES)
    assert s == PS("model", "data")


def test_spec_multi_axis_and_fallback():
    from jax.sharding import PartitionSpec as PS
    from repro.distrib.sharding import DEFAULT_RULES, merge_rules, spec_for
    mesh = fake_mesh((2, 16, 16), ("pod", "data", "model"))
    s = spec_for((4096, 16384), ("embed", "mlp"), mesh, DEFAULT_RULES)
    assert s == PS(("pod", "data"), "model")
    # batch=1 cannot shard -> None; kv_seq spreads over (data, model)
    rules = merge_rules(DEFAULT_RULES, {"kv_seq": ("data", "model")})
    s = spec_for((1, 524288, 1, 256),
                 ("batch", "kv_seq", "kv_heads", "head_dim"), mesh, rules)
    assert s == PS(None, ("data", "model"))


def test_no_double_axis_use():
    from repro.distrib.sharding import DEFAULT_RULES, merge_rules, spec_for
    mesh = fake_mesh()
    rules = merge_rules(DEFAULT_RULES, {"a": ("model",), "b": ("model",)})
    s = spec_for((32, 32), ("a", "b"), mesh, rules)
    flat = [x for e in s if e for x in (e if isinstance(e, tuple) else (e,))]
    assert len(flat) == len(set(flat)) == 1


@cases(10)
def test_bytes_per_device_consistent(rng):
    import jax
    from repro.distrib.sharding import bytes_per_device
    mesh = fake_mesh((4, 4), ("data", "model"))
    d = int(rng.integers(1, 8)) * 16
    f = int(rng.integers(1, 8)) * 16
    tree = {"w": jax.ShapeDtypeStruct((d, f), np.dtype("float32"))}
    axes = {"w": ("embed", "mlp")}
    got = bytes_per_device(tree, axes, mesh)
    assert got == d * f * 4 // 16


QUANT_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType, PartitionSpec as PS
    from repro.distrib.collectives import compressed_psum

    mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))

    def f(x_l):
        out, err = compressed_psum(x_l[0], "data")
        return out[None], err[None]

    with mesh:
        out, err = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(PS("data"),),
            out_specs=(PS("data"), PS("data")), check_vma=False))(x)
    want = np.asarray(x.mean(0))
    got = np.asarray(out[0])
    # int8 with a shared per-tensor scale: per-element error bounded by
    # scale/2 = max|x|/254 (relative-to-zero errors are meaningless)
    scale = np.abs(np.asarray(x)).max() / 127.0
    assert np.abs(got - want).max() <= scale * 0.75, \
        (np.abs(got - want).max(), scale)
    print("QUANT_OK", float(np.abs(got - want).max() / scale))

    # convergence parity: toy regression, compressed vs exact grads
    k = jax.random.PRNGKey(1)
    Xd = jax.random.normal(k, (4, 64, 8))
    wt = jax.random.normal(jax.random.PRNGKey(2), (8,))
    yd = jnp.einsum("dbi,i->db", Xd, wt)

    def loss_grad(w, X, y):
        pred = X @ w
        return X.T @ (pred - y) / y.size

    def step_exact(w):
        g = jnp.mean(jax.vmap(loss_grad, (None, 0, 0))(w, Xd, yd), 0)
        return w - 0.3 * g

    def step_comp(w, e):
        def f(X, y, err):
            g = loss_grad(w, X[0], y[0])
            out, new_err = compressed_psum(g + err[0], "data")
            return out[None], new_err[None]
        with mesh:
            g, e = jax.shard_map(f, mesh=mesh,
                                 in_specs=(PS("data"), PS("data"),
                                           PS("data")),
                                 out_specs=(PS("data"), PS("data")),
                                 check_vma=False)(Xd, yd, e)
        return w - 0.3 * g[0], e

    w1 = jnp.zeros(8); w2 = jnp.zeros(8); e = jnp.zeros((4, 8))
    for i in range(60):
        w1 = step_exact(w1)
        w2, e = step_comp(w2, e)
    d_exact = float(jnp.linalg.norm(w1 - wt))
    d_comp = float(jnp.linalg.norm(w2 - wt))
    assert d_comp < 0.05, (d_exact, d_comp)
    print("CONV_OK", d_exact, d_comp)
""")


def test_compressed_allreduce_and_convergence():
    r = subprocess.run([sys.executable, "-c", QUANT_SCRIPT],
                       capture_output=True, text=True, timeout=300, cwd=".")
    assert "QUANT_OK" in r.stdout and "CONV_OK" in r.stdout, \
        r.stdout + r.stderr


PIPE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.distrib.pipeline import pipeline_apply

    from jax.sharding import AxisType
    mesh = jax.make_mesh((4,), ("stage",), axis_types=(AxisType.Auto,))
    n_stages, n_micro, mb, d = 4, 8, 2, 16
    Ws = jax.random.normal(jax.random.PRNGKey(0), (n_stages, d, d)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))

    def stage_fn(W, h):
        return jnp.tanh(h @ W)

    with mesh:
        out = jax.jit(lambda W, xx: pipeline_apply(
            stage_fn, W, xx, mesh, stage_axis="stage"))(Ws, x)

    want = x
    for s in range(n_stages):
        want = jnp.tanh(want @ Ws[s])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    print("PIPE_OK")
""")


def test_pipeline_stage_runner():
    r = subprocess.run([sys.executable, "-c", PIPE_SCRIPT],
                       capture_output=True, text=True, timeout=300, cwd=".")
    assert "PIPE_OK" in r.stdout, r.stdout + r.stderr


SP_DECODE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.distrib.collectives import sp_decode_attention
    from repro.kernels import ref

    from jax.sharding import AxisType
    mesh = jax.make_mesh((1, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    B, T, Hq, Hkv, D = 2, 64, 4, 2, 16
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (B, 1, Hq, D))
    k = jax.random.normal(k2, (B, T, Hkv, D))
    v = jax.random.normal(k3, (B, T, Hkv, D))
    with mesh:
        out = jax.jit(lambda q, k, v: sp_decode_attention(
            q, k, v, mesh, seq_axis="model"))(q, k, v)
    want = ref.attention_ref(q, k, v, causal=True, q_offset=T - 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    print("SP_OK")
""")


def test_sp_decode_attention():
    r = subprocess.run([sys.executable, "-c", SP_DECODE_SCRIPT],
                       capture_output=True, text=True, timeout=300, cwd=".")
    assert "SP_OK" in r.stdout, r.stdout + r.stderr
