"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) and the fast-XLA
paths vs the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fletcher import fletcher64_pallas
from repro.kernels.moe_router import router_topk_pallas
from repro.kernels.rglru_scan import rglru_pallas
from repro.kernels.ssd import ssd_pallas

from proptest import cases

R = np.random.default_rng(0)


def t(*s, dtype=np.float32):
    return jnp.asarray(R.standard_normal(s), dtype)


ATTN_SWEEP = [
    # S, T, Hq, Hkv, D, causal, window, softcap, prefix, dtype
    (64, 64, 4, 2, 16, True, 0, 0.0, None, "float32"),
    (128, 128, 4, 4, 32, True, 32, 0.0, None, "float32"),
    (96, 96, 8, 1, 64, True, 0, 30.0, None, "float32"),
    (80, 80, 4, 2, 16, True, 0, 0.0, 24, "float32"),
    (200, 200, 2, 2, 16, True, 0, 0.0, None, "float32"),
    (64, 64, 2, 2, 16, False, 0, 0.0, None, "float32"),
    (128, 128, 4, 2, 32, True, 0, 0.0, None, "bfloat16"),
]


@pytest.mark.parametrize("case", ATTN_SWEEP)
def test_flash_attention_vs_ref(case):
    S, T, Hq, Hkv, D, causal, window, softcap, prefix, dt = case
    q, k, v = t(2, S, Hq, D, dtype=dt), t(2, T, Hkv, D, dtype=dt), \
        t(2, T, Hkv, D, dtype=dt)
    pl_arr = None if prefix is None else jnp.asarray(prefix)
    want = ref.attention_ref(q, k, v, causal=causal, window=window,
                             softcap=softcap, prefix_len=pl_arr)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          softcap=softcap, prefix_len=prefix,
                          interpret=True, block_q=64, block_k=64)
    tol = 2e-2 if dt == "bfloat16" else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("case", ATTN_SWEEP)
def test_xla_attention_vs_ref(case):
    S, T, Hq, Hkv, D, causal, window, softcap, prefix, dt = case
    q, k, v = t(2, S, Hq, D, dtype=dt), t(2, T, Hkv, D, dtype=dt), \
        t(2, T, Hkv, D, dtype=dt)
    pl_arr = None if prefix is None else jnp.asarray(prefix)
    want = ref.attention_ref(q, k, v, causal=causal, window=window,
                             softcap=softcap, prefix_len=pl_arr)
    got = ops._attention_chunked(q, k, v, causal=causal, window=window,
                                 softcap=softcap, q_offset=0,
                                 prefix_len=pl_arr, kv_chunk=48)
    tol = 2e-2 if dt == "bfloat16" else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@cases(8)
def test_attention_decode_property(rng):
    """Decode (S=1 at offset T-1) equals the last row of full attention."""
    B, T = 2, int(rng.integers(8, 64))
    Hq, Hkv, D = 4, 2, 16
    q = t(B, T, Hq, D)
    k, v = t(B, T, Hkv, D), t(B, T, Hkv, D)
    full = ref.attention_ref(q, k, v, causal=True)
    got = ops._attention_decode(q[:, -1:], k, v, causal=True, window=0,
                                softcap=0.0, q_offset=T - 1, prefix_len=None)
    np.testing.assert_allclose(got[:, 0], full[:, -1], rtol=2e-5, atol=2e-5)


DECODE_SWEEP = [
    # Hq, Hkv, D, window, softcap, dtype
    (4, 4, 32, 0, 0.0, "float32"),          # GQA repeat 1
    (8, 4, 16, 0, 0.0, "float32"),          # repeat 2
    (16, 2, 32, 0, 0.0, "float32"),         # repeat 8
    (8, 4, 16, 200, 0.0, "float32"),        # sliding window
    (4, 2, 32, 0, 30.0, "float32"),         # soft-capped logits
    (16, 2, 64, 300, 50.0, "bfloat16"),
]


@pytest.mark.parametrize("case", DECODE_SWEEP)
def test_decode_attention_vs_ref(case):
    """The decode kernel (interpret mode) and the xla path read a stacked
    lane-dense cache at one layer; per-slot positions at the first
    position, either side of a block edge, and the cache's last."""
    Hq, Hkv, D, window, softcap, dt = case
    L, T, layer = 3, 512, 2
    pos = jnp.asarray([0, 127, 128, T - 1], jnp.int32)
    B = pos.shape[0]
    q = t(B, Hq, D, dtype=dt)
    k, v = t(L, B, T, Hkv * D, dtype=dt), t(L, B, T, Hkv * D, dtype=dt)
    want = ref.decode_attention_ref(q, k[layer], v[layer], pos,
                                    window=window, softcap=softcap)
    tol = 2e-2 if dt == "bfloat16" else 2e-5
    for impl in ("interpret", "xla"):
        got = ops.decode_attention(q, k, v, pos, layer, window=window,
                                   softcap=softcap, impl=impl)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol, err_msg=impl)


SSD_SWEEP = [
    (2, 64, 4, 8, 2, 16, 32, True, True),
    (1, 100, 2, 16, 1, 8, 32, False, False),
    (3, 33, 4, 4, 4, 4, 16, True, False),
]


@pytest.mark.parametrize("case", SSD_SWEEP)
def test_ssd_pallas_vs_ref(case):
    B, S, H, P, G, N, Q, use_D, use_h0 = case
    x, dt_ = t(B, S, H, P), jax.nn.softplus(t(B, S, H))
    A = -jnp.exp(t(H) * 0.5)
    Bm, Cm = t(B, S, G, N) * 0.3, t(B, S, G, N) * 0.3
    Dm = t(H) if use_D else None
    h0 = t(B, H, P, N) * 0.1 if use_h0 else None
    yr, hr = ref.ssd_ref(x, dt_, A, Bm, Cm, Dm, h0)
    yp, hp = ssd_pallas(x, dt_, A, Bm, Cm, Dm, h0, chunk=Q, interpret=True)
    np.testing.assert_allclose(yp, yr, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(hp, hr, rtol=2e-4, atol=2e-4)
    yx, hx = ops._ssd_chunked(x, dt_, A, Bm, Cm, Dm, h0, chunk=Q)
    np.testing.assert_allclose(yx, yr, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(hx, hr, rtol=2e-4, atol=2e-4)


@cases(6)
def test_ssd_chunk_invariance(rng):
    """SSD output must not depend on the chunk size (pure algebra)."""
    B, S, H, P, G, N = 1, 48, 2, 4, 1, 8
    x, dt_ = t(B, S, H, P), jax.nn.softplus(t(B, S, H))
    A = -jnp.exp(t(H) * 0.5)
    Bm, Cm = t(B, S, G, N) * 0.3, t(B, S, G, N) * 0.3
    y1, h1 = ops._ssd_chunked(x, dt_, A, Bm, Cm, None, None, chunk=8)
    y2, h2 = ops._ssd_chunked(x, dt_, A, Bm, Cm, None, None,
                              chunk=int(rng.choice([12, 16, 24, 48])))
    np.testing.assert_allclose(y1, y2, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h1, h2, rtol=2e-4, atol=2e-4)


RGLRU_SWEEP = [(2, 64, 32, 16, 32, True), (1, 70, 40, 16, 32, False),
               (3, 128, 8, 64, 8, True)]


@pytest.mark.parametrize("case", RGLRU_SWEEP)
def test_rglru_pallas_vs_ref(case):
    B, S, W, bt, bw, use_h0 = case
    x, rg, ig = t(B, S, W), t(B, S, W), t(B, S, W)
    ll = t(W)
    h0 = t(B, W) * 0.2 if use_h0 else None
    hr, hrf = ref.rglru_ref(x, rg, ig, ll, h0)
    hp, hpf = rglru_pallas(x, rg, ig, ll, h0, interpret=True,
                           block_w=bw, block_t=bt)
    np.testing.assert_allclose(hp, hr, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(hpf, hrf, rtol=2e-5, atol=2e-5)
    hx, hxf = ops._rglru_assoc(x, rg, ig, ll, h0)
    np.testing.assert_allclose(hx, hr, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(hxf, hrf, rtol=2e-4, atol=2e-4)


def test_rglru_pallas_bfloat16_rows():
    """16-bit inputs walk 16-row groups; the state stays float32, so the
    output matches the reference up to bfloat16 rounding of inputs and
    outputs."""
    B, S, W = 2, 40, 24
    x, rg, ig = (t(B, S, W).astype(jnp.bfloat16) for _ in range(3))
    ll, h0 = t(W), t(B, W) * 0.2
    hr, hrf = ref.rglru_ref(*(a.astype(jnp.float32) for a in (x, rg, ig)),
                            ll, h0)
    hp, hpf = rglru_pallas(x, rg, ig, ll, h0, interpret=True, block_t=32)
    assert hp.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(hp, np.float32), hr,
                               rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(hpf, hrf, rtol=1e-4, atol=1e-4)


@cases(10)
def test_rglru_stability_property(rng):
    """|h| stays bounded: a ∈ (0,1) and beta = sqrt(1-a²) normalizes."""
    B, S, W = 1, 256, 8
    x = jnp.asarray(rng.standard_normal((B, S, W)), jnp.float32)
    h, hf = ref.rglru_ref(x, x * 0, x * 0 + 4.0, jnp.zeros(W))
    assert float(jnp.max(jnp.abs(h))) < 10.0 * float(jnp.max(jnp.abs(x)))


@pytest.mark.parametrize("TE", [(32, 8), (100, 16), (256, 40)])
@pytest.mark.parametrize("k", [1, 2, 6])
def test_router_pallas_vs_ref(TE, k):
    T, E = TE
    if k > E:
        pytest.skip("k > E")
    logits = t(T, E)
    wr, ir, pr = ref.router_topk_ref(logits, k)
    wp, ip, pp = router_topk_pallas(logits, k, interpret=True, block_t=32)
    np.testing.assert_allclose(wp, wr, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(ip, ir)
    np.testing.assert_allclose(pp, pr, rtol=1e-5, atol=1e-6)


@cases(12)
def test_fletcher_pallas_vs_ref(rng):
    n = int(rng.integers(1, 50_000))
    buf = rng.integers(0, 2 ** 32, size=n, dtype=np.uint32)
    assert fletcher64_pallas(buf, interpret=True) == \
        ref.fletcher64_ref(buf) == ops.fletcher64(buf, impl="xla")


@cases(8)
def test_fletcher_detects_corruption(rng):
    buf = rng.integers(0, 2 ** 32, size=1000, dtype=np.uint32)
    want = ops.fletcher64(buf, impl="xla")
    i = int(rng.integers(0, buf.size))
    buf2 = buf.copy()
    buf2[i] ^= np.uint32(1 << int(rng.integers(0, 32)))
    assert ops.fletcher64(buf2, impl="xla") != want
