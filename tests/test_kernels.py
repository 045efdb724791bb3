"""Kernel sweeps: shapes x dtypes vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow        # shape x dtype sweeps: CI slow tier

RNG = np.random.default_rng(7)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------- flash attn
@pytest.mark.parametrize("B,S,H,KH,Dh", [
    (2, 256, 4, 2, 64), (1, 128, 8, 8, 128), (2, 128, 4, 1, 64),
    (1, 512, 2, 2, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 128),
                                           (False, 0)])
def test_flash_attention_sweep(B, S, H, KH, Dh, dtype, causal, window):
    from repro.kernels.flash_attention import ops, ref
    q = jnp.asarray(RNG.standard_normal((B, S, H, Dh)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, S, KH, Dh)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, S, KH, Dh)), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window)
    want = ref.reference_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_flash_attention_odd_length_falls_back():
    from repro.kernels.flash_attention import ops, ref
    q = jnp.asarray(RNG.standard_normal((1, 96, 2, 64)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 96, 2, 64)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 96, 2, 64)), jnp.float32)
    out = ops.flash_attention(q, k, v)
    want = ref.reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)


# ------------------------------------------------------------------- moe gmm
@pytest.mark.parametrize("E,C,d,f,act", [
    (4, 128, 256, 512, "swiglu"), (2, 64, 128, 512, "geglu"),
    (3, 128, 128, 640, "relu2"), (8, 256, 64, 512, "gelu"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm_sweep(E, C, d, f, act, dtype):
    from repro.kernels.moe_gmm import ops, ref
    xe = jnp.asarray(RNG.standard_normal((E, C, d)), dtype)
    p = {"w1": jnp.asarray(RNG.standard_normal((E, d, f)) * 0.05),
         "w2": jnp.asarray(RNG.standard_normal((E, f, d)) * 0.05)}
    if act in ("swiglu", "geglu"):
        p["w3"] = jnp.asarray(RNG.standard_normal((E, d, f)) * 0.05)
    out = ops.expert_ffn(xe, p, act)
    want = ref.reference_expert_ffn(xe, p, act)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


# --------------------------------------------------------------------- rglru
@pytest.mark.parametrize("B,S,D", [(2, 256, 256), (1, 128, 128),
                                   (4, 64, 384), (2, 512, 128),
                                   (2, 200, 128), (2, 64, 640),
                                   (12, 64, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_sweep(B, S, D, dtype):
    from repro.kernels.rglru_scan import ops, ref
    x = jnp.asarray(RNG.standard_normal((B, S, D)), dtype)
    lam = jnp.asarray(RNG.standard_normal((D,)), jnp.float32)
    ga = jnp.asarray(RNG.standard_normal((B, S, D)), dtype)
    gx = jnp.asarray(RNG.standard_normal((B, S, D)), dtype)
    y, h = ops.rglru(x, lam, ga, gx)
    wy, wh = ref.reference_rglru(x, lam, ga, gx)
    np.testing.assert_allclose(np.asarray(y), np.asarray(wy), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(h), np.asarray(wh), **_tol(dtype))


# --------------------------------------------------------------------- mlstm
@pytest.mark.parametrize("B,S,H,Dh,chunk", [
    (2, 128, 2, 64, 32), (1, 256, 4, 128, 64), (2, 64, 1, 128, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mlstm_sweep(B, S, H, Dh, chunk, dtype):
    from repro.kernels.mlstm_scan import ops, ref
    q = jnp.asarray(RNG.standard_normal((B, S, H, Dh)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, S, H, Dh)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, S, H, Dh)), dtype)
    ig = jnp.asarray(RNG.standard_normal((B, S, H)), jnp.float32)
    fg = jnp.asarray(RNG.standard_normal((B, S, H)) + 2.0, jnp.float32)
    h, (C, n, m) = ops.mlstm_chunkwise(q, k, v, ig, fg, chunk=chunk)
    wh, (wC, wn, wm) = ref.reference_mlstm(q, k, v, ig, fg, chunk=chunk)
    np.testing.assert_allclose(np.asarray(h), np.asarray(wh),
                               atol=5e-2 if dtype == jnp.bfloat16 else 5e-4,
                               rtol=5e-2 if dtype == jnp.bfloat16 else 5e-4)
    np.testing.assert_allclose(np.asarray(C), np.asarray(wC),
                               atol=5e-2, rtol=5e-2)


def test_mlstm_chunkwise_equals_sequential_oracle():
    from repro.kernels.mlstm_scan import ref
    q = jnp.asarray(RNG.standard_normal((1, 96, 2, 32)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 96, 2, 32)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 96, 2, 32)), jnp.float32)
    ig = jnp.asarray(RNG.standard_normal((1, 96, 2)), jnp.float32)
    fg = jnp.asarray(RNG.standard_normal((1, 96, 2)) + 1.5, jnp.float32)
    h1, _ = ref.reference_mlstm(q, k, v, ig, fg, chunk=32)
    h2, _ = ref.sequential_oracle(q, k, v, ig, fg)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               atol=1e-4, rtol=1e-4)
