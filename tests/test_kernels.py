"""Per-kernel allclose tests: Pallas (interpret=True) vs pure-jnp oracles,
sweeping shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def rand(*s, dtype=jnp.float32):
    return jnp.asarray(RNG.normal(size=s), dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("B,S,Hq,Hkv,D", [
    (1, 128, 2, 2, 32),      # MHA
    (2, 256, 4, 2, 64),      # GQA
    (1, 512, 8, 1, 64),      # MQA
    (2, 128, 4, 4, 128),     # wide head
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(B, S, Hq, Hkv, D, dtype):
    q, k, v = rand(B, S, Hq, D, dtype=dtype), rand(B, S, Hkv, D, dtype=dtype), \
        rand(B, S, Hkv, D, dtype=dtype)
    out = ops.flash_attention(q, k, v, causal=True, bq=64, bk=64)
    want = jnp.moveaxis(
        ref.attention_ref(jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2),
                          jnp.moveaxis(v, 1, 2), causal=True), 1, 2)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), atol=TOL[dtype],
                               rtol=TOL[dtype])


@pytest.mark.parametrize("window", [32, 64, 128])
def test_flash_attention_window(window):
    B, S, Hq, Hkv, D = 2, 256, 4, 2, 64
    q, k, v = rand(B, S, Hq, D), rand(B, S, Hkv, D), rand(B, S, Hkv, D)
    out = ops.flash_attention(q, k, v, causal=True, window=window, bq=64, bk=64)
    want = jnp.moveaxis(
        ref.attention_ref(jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2),
                          jnp.moveaxis(v, 1, 2), causal=True, window=window),
        1, 2)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("Skv,kv_len", [(256, 256), (512, 300), (512, 1),
                                        (1024, 777)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(Skv, kv_len, dtype):
    B, Hq, Hkv, D = 2, 4, 2, 64
    group = Hq // Hkv
    q = rand(B, 1, Hq, D, dtype=dtype)
    k = rand(B, Skv, Hkv, D, dtype=dtype)
    v = rand(B, Skv, Hkv, D, dtype=dtype)
    out = ops.decode_attention(q, k, v, jnp.int32(kv_len), bk=128)
    want = ref.decode_attention_ref(
        q[:, 0].reshape(B, Hkv, group, D), jnp.moveaxis(k, 1, 2),
        jnp.moveaxis(v, 1, 2), jnp.int32(kv_len)).reshape(B, 1, Hq, D)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), atol=TOL[dtype],
                               rtol=TOL[dtype])


@pytest.mark.parametrize("l,chunk", [(64, 16), (128, 32), (256, 64),
                                     (128, 128)])
@pytest.mark.parametrize("n,p", [(8, 16), (16, 32)])
def test_ssd_scan(l, chunk, n, p):
    b, h = 2, 3
    x = rand(b, l, h, p)
    dt = jnp.abs(rand(b, l, h)) * 0.1
    A = -jnp.abs(rand(h))
    Bm, Cm = rand(b, l, n), rand(b, l, n)
    y, s = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    yr, sr = ref.ssd_ref(x, dt, A, Bm, Cm, chunk)
    np.testing.assert_allclose(y, yr, atol=5e-5, rtol=5e-4)
    np.testing.assert_allclose(s, sr, atol=5e-5, rtol=5e-4)


def test_ssd_matches_sequential_recurrence():
    """SSD chunked form == naive per-token recurrence (independent oracle)."""
    b, l, h, p, n = 1, 32, 2, 8, 4
    x = rand(b, l, h, p)
    dt = jnp.abs(rand(b, l, h)) * 0.2
    A = -jnp.abs(rand(h))
    Bm, Cm = rand(b, l, n), rand(b, l, n)
    y, _ = ref.ssd_ref(x, dt, A, Bm, Cm, chunk=8)
    S = np.zeros((b, h, n, p))
    want = np.zeros((b, l, h, p))
    for t in range(l):
        a = np.exp(np.asarray(dt[:, t]) * np.asarray(A)[None])      # [b,h]
        S = S * a[..., None, None] + np.einsum(
            "bn,bh,bhp->bhnp", Bm[:, t], dt[:, t], x[:, t])
        want[:, t] = np.einsum("bn,bhnp->bhp", Cm[:, t], S)
    np.testing.assert_allclose(y, want, atol=1e-4, rtol=1e-3)


@pytest.mark.parametrize("L,D,bt,bd", [(64, 32, 16, 16), (128, 64, 32, 32),
                                       (256, 128, 64, 128), (128, 64, 128, 64)])
def test_rglru_scan(L, D, bt, bd):
    B = 2
    a = jax.nn.sigmoid(rand(B, L, D)) * 0.98
    bi = rand(B, L, D)
    h, hl = ops.rglru_scan(bi, a, block_t=bt, block_d=bd)
    hr, hlr = ref.rglru_ref(bi, a)
    np.testing.assert_allclose(h, hr, atol=2e-5, rtol=2e-4)
    np.testing.assert_allclose(hl, hlr, atol=2e-5, rtol=2e-4)


def test_rglru_matches_sequential():
    B, L, D = 1, 48, 8
    a = jax.nn.sigmoid(rand(B, L, D)) * 0.95
    bi = rand(B, L, D)
    h, _ = ops.rglru_scan(bi, a, block_t=16, block_d=8)
    hs = np.zeros((B, D))
    want = np.zeros((B, L, D))
    an, bn = np.asarray(a), np.asarray(bi)
    for t in range(L):
        hs = an[:, t] * hs + np.sqrt(1 - an[:, t] ** 2) * bn[:, t]
        want[:, t] = hs
    np.testing.assert_allclose(h, want, atol=1e-5, rtol=1e-4)
