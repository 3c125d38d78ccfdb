"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_decode.kernel import flash_decode, pick_block_s
from repro.kernels.flash_decode.ref import flash_decode_ref
from repro.kernels.rowstream_matmul.kernel import pick_bk, rowstream_matmul
from repro.kernels.rowstream_matmul.ref import rowstream_matmul_ref
from repro.kernels.rwkv_scan.kernel import pick_chunk, rwkv_scan
from repro.kernels.rwkv_scan.ref import rwkv_scan_ref

KEY = jax.random.PRNGKey(7)


# --- rowstream matmul --------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(128, 256, 128), (64, 512, 256),
                                   (256, 1024, 128), (8, 256, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rowstream_matmul(m, k, n, dtype):
    k1, k2 = jax.random.split(KEY)
    x = jax.random.normal(k1, (m, k), dtype)
    w = jax.random.normal(k2, (k, n), dtype)
    out = rowstream_matmul(x, w, interpret=True)
    ref = rowstream_matmul_ref(x, w)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol * 8)


def test_pick_bk_row_aligned():
    for k, n, isz in ((4096, 1024, 2), (2048, 512, 2), (8192, 4096, 4)):
        bk = pick_bk(k, n, isz)
        assert bk % 128 == 0
        assert k % bk == 0
        assert (bk * n * isz) % 4096 == 0   # whole DRAM rows


# --- flash decode ------------------------------------------------------------

@pytest.mark.parametrize("b,h,hkv,s,d", [(2, 8, 2, 128, 64),
                                         (1, 4, 4, 256, 64),
                                         (3, 16, 4, 64, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode(b, h, hkv, s, d, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, h, d), dtype)
    kc = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
    vc = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
    pos = jnp.array(s // 2, jnp.int32)
    out = flash_decode(q, kc, vc, pos, interpret=True)
    ref = flash_decode_ref(q, kc, vc, pos)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_decode_masks_future():
    """Slots beyond pos are unwritten garbage and must not leak."""
    ks = jax.random.split(KEY, 3)
    b, h, hkv, s, d = 1, 4, 2, 64, 32
    q = jax.random.normal(ks[0], (b, h, d))
    kc = jax.random.normal(ks[1], (b, hkv, s, d))
    vc = jax.random.normal(ks[2], (b, hkv, s, d))
    pos = jnp.array(10, jnp.int32)
    out1 = flash_decode(q, kc, vc, pos, interpret=True)
    kc2 = kc.at[:, :, 11:].set(1e9)
    vc2 = vc.at[:, :, 11:].set(-1e9)
    out2 = flash_decode(q, kc2, vc2, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-6)


def test_pick_block_s_row_aligned():
    for s, d, isz in ((32768, 128, 2), (2048, 64, 2), (4096, 128, 4)):
        bs = pick_block_s(s, d, isz)
        assert s % bs == 0
        assert (bs * d * isz) % 4096 == 0


# --- rwkv scan ---------------------------------------------------------------

@pytest.mark.parametrize("b,s,H,hd,chunk", [(2, 64, 3, 16, 16),
                                            (1, 128, 2, 32, 32),
                                            (2, 48, 4, 16, 8)])
def test_rwkv_scan(b, s, H, hd, chunk):
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (b, s, H, hd))
    k = jax.random.normal(ks[1], (b, s, H, hd))
    v = jax.random.normal(ks[2], (b, s, H, hd))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, s, H, hd))) * 0.5 + 0.4
    u = jax.random.normal(ks[4], (H, hd)) * 0.1
    o, S = rwkv_scan(r, k, v, w, u, chunk=chunk, interpret=True)
    o_ref, S_ref = rwkv_scan_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_ref),
                               rtol=1e-3, atol=1e-3)


def test_rwkv_scan_extreme_decay_stable():
    """Near-zero decays (log w = -inf-ish) must not produce NaN/Inf — the
    log-space masking guarantees exponent differences <= 0."""
    ks = jax.random.split(KEY, 5)
    b, s, H, hd = 1, 32, 2, 16
    r = jax.random.normal(ks[0], (b, s, H, hd))
    k = jax.random.normal(ks[1], (b, s, H, hd))
    v = jax.random.normal(ks[2], (b, s, H, hd))
    w = jnp.where(jax.random.bernoulli(ks[3], 0.4, (b, s, H, hd)),
                  1e-35, 0.9)
    u = jnp.zeros((H, hd))
    o, S = rwkv_scan(r, k, v, w, u, chunk=8, interpret=True)
    o_ref, S_ref = rwkv_scan_ref(r, k, v, w, u)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(S).all())
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=1e-3, atol=2e-3)


def test_pick_chunk_row_aligned():
    for s, hd in ((4096, 64), (1024, 128), (512, 64)):
        c = pick_chunk(s, hd, 4)
        assert s % c == 0
        assert (c * hd * 4) % 4096 == 0
