"""Per-architecture smoke + consistency tests (reduced configs, CPU).

For every assigned arch: one train step runs, outputs have the right
shapes, loss is finite and non-NaN; the incremental decode path matches a
fresh full prefill bit-for-bit (within f32 tolerance)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import Model, unzip

ARCHS = configs.names()
KEY = jax.random.PRNGKey(7)
RNG = jax.random.PRNGKey(0)


def make_batch(cfg, B, S, with_targets=True):
    F = cfg.frontend_seq if cfg.family == "vlm" else 0
    toks = jax.random.randint(KEY, (B, S - F), 0, cfg.vocab)
    b = {"tokens": toks}
    if cfg.family == "vlm":
        b["frontend"] = jax.random.normal(KEY, (B, F, cfg.frontend_dim)) * .1
        if with_targets:
            pad = jnp.full((B, F), -1, jnp.int32)
            b["targets"] = jnp.concatenate(
                [pad, jax.random.randint(KEY, (B, S - F), 0, cfg.vocab)], 1)
    else:
        if cfg.family in ("encdec", "audio"):
            b["frontend"] = jax.random.normal(
                KEY, (B, cfg.frontend_seq, cfg.frontend_dim)) * .1
        if with_targets:
            b["targets"] = jax.random.randint(KEY, (B, S), 0, cfg.vocab)
    return b


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    cfg = configs.reduced(arch)
    m = Model(cfg)
    params, axes = unzip(m.init(RNG))
    batch = make_batch(cfg, 2, 64)
    (loss, metrics), grads = jax.jit(
        jax.value_and_grad(
            lambda p, b: m.loss_fn(p, b, impl="xla", remat="block"),
            has_aux=True))(params, batch)
    assert np.isfinite(float(loss)), arch
    # all grads finite, at least one nonzero
    leaves = jax.tree_util.tree_leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in leaves)
    assert any(float(jnp.max(jnp.abs(g))) > 0 for g in leaves)
    # params and axes trees are parallel (Axes leaves are natural leaves)
    p_leaves = jax.tree_util.tree_leaves(params)
    a_leaves = jax.tree_util.tree_leaves(axes)
    assert len(p_leaves) == len(a_leaves)
    for p, a in zip(p_leaves, a_leaves):
        assert p.ndim == len(a), (p.shape, a)


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_prefill(arch):
    cfg = configs.reduced(arch).replace(compute_dtype="float32")
    m = Model(cfg)
    params, _ = unzip(m.init(RNG))
    B, S, EXT = 2, 48, 4
    F = cfg.frontend_seq if cfg.family == "vlm" else 0
    all_text = jax.random.randint(KEY, (B, S + EXT - F), 0, cfg.vocab)
    fe = None
    if cfg.family == "vlm":
        fe = jax.random.normal(KEY, (B, F, cfg.frontend_dim)) * 0.1
    elif cfg.family in ("encdec", "audio"):
        fe = jax.random.normal(KEY, (B, cfg.frontend_seq, cfg.frontend_dim)) * .1

    def mk(n):
        b = {"tokens": all_text[:, :n]}
        if fe is not None:
            b["frontend"] = fe
        return b

    pf = jax.jit(lambda p, b: m.prefill(p, b, cache_len=S + 8, impl="xla"))
    lg, cache = pf(params, mk(S - F))
    want, _ = pf(params, mk(S + EXT - F))
    step = jax.jit(lambda p, c, t, pos: m.decode_step(p, c, t, pos,
                                                      impl="xla"))
    for i in range(EXT):
        pos = S + i
        lg, cache = step(params, cache, all_text[:, pos - F][..., None],
                         jnp.int32(pos))
    np.testing.assert_allclose(np.asarray(lg), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "mamba2-1.3b",
                                  "recurrentgemma-9b"])
def test_vector_pos_decode_matches_scalar(arch):
    """Continuous-batching (vector pos) decode == lockstep (scalar pos)."""
    cfg = configs.reduced(arch).replace(compute_dtype="float32")
    m = Model(cfg)
    params, _ = unzip(m.init(RNG))
    B, S = 2, 32
    toks = jax.random.randint(KEY, (B, S + 1), 0, cfg.vocab)
    _, cache = jax.jit(lambda p, b: m.prefill(p, b, cache_len=S + 4,
                                              impl="xla"))(
        params, {"tokens": toks[:, :S]})
    lg_s, _ = m.decode_step(params, cache, toks[:, S:S + 1], jnp.int32(S),
                            impl="xla")
    lg_v, _ = m.decode_step(params, cache, toks[:, S:S + 1],
                            jnp.full((B,), S, jnp.int32), impl="xla")
    np.testing.assert_allclose(np.asarray(lg_v), np.asarray(lg_s),
                               rtol=2e-4, atol=2e-4)


def test_long_context_flags():
    longs = [a for a in ARCHS if configs.get(a).supports_long_context]
    assert sorted(longs) == ["gemma3-12b", "mamba2-1.3b",
                             "recurrentgemma-9b"]


def test_param_counts_match_published():
    expect = {  # billions, loose band vs published sizes
        "granite-moe-3b-a800m": (2.5, 4.0),
        "deepseek-moe-16b": (15.0, 18.0),
        "gemma3-12b": (10.0, 13.5),
        "qwen1.5-0.5b": (0.4, 0.65),
        "nemotron-4-340b": (320.0, 360.0),
        "command-r-35b": (28.0, 38.0),
        "recurrentgemma-9b": (7.5, 10.0),
        "mamba2-1.3b": (1.2, 1.5),
    }
    for arch, (lo, hi) in expect.items():
        n = configs.get(arch).param_count() / 1e9
        assert lo <= n <= hi, (arch, n)


def test_moe_active_params():
    g = configs.get("granite-moe-3b-a800m")
    assert g.active_param_count() < 0.35 * g.param_count()
    d = configs.get("deepseek-moe-16b")
    assert 2.0e9 < d.active_param_count() < 3.5e9


CHECKSUM_SCRIPT = """
import sys
sys.path.insert(0, "src")
import jax, numpy as np
from repro import configs
from repro.models import Model, unzip
params, _ = unzip(Model(configs.reduced("qwen1.5-0.5b")).init(
    jax.random.PRNGKey(0)))
leaves = jax.tree_util.tree_leaves(params)
print("SUM", repr(float(sum(np.abs(np.asarray(x, np.float64)).sum()
                            for x in leaves))))
"""


def test_param_init_same_in_every_process():
    """init(PRNGKey(0)) gives the same parameters in every process, whatever
    the interpreter's per-process string-hash salt."""
    import os
    import subprocess
    import sys
    sums = []
    for salt in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=salt, JAX_PLATFORMS="cpu")
        r = subprocess.run([sys.executable, "-c", CHECKSUM_SCRIPT],
                           capture_output=True, text=True, timeout=300,
                           env=env)
        line = [ln for ln in r.stdout.splitlines() if ln.startswith("SUM")]
        assert line, r.stdout + r.stderr
        sums.append(line[0])
    assert sums[0] == sums[1], sums
