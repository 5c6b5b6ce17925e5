"""Per-kernel correctness: shape/dtype sweeps against the pure-jnp oracles,
executed with interpret=True (Pallas kernel body runs on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.common import dtype_tol
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.masked_matmul.ops import masked_matmul
from repro.kernels.masked_matmul.ref import masked_matmul_ref
from repro.kernels.mamba_scan.ref import selective_scan_ref, selective_step_ref

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return dtype_tol(dtype)[0]


# ---------------------------------------------------------------------------
# masked_matmul
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "m,k,n,r,c,bm,bn,bk",
    [
        (64, 128, 96, 16, 16, 32, 32, 32),
        (8, 256, 256, 32, 32, 64, 64, 64),
        (128, 64, 64, 64, 64, 64, 64, 64),  # block == period
        (33, 100, 77, 16, 16, 32, 32, 32),  # ragged -> padding path
        (16, 512, 128, 128, 64, 64, 64, 256),  # block > period rows
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_masked_matmul_sweep(m, k, n, r, c, bm, bn, bk, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    x = jax.random.normal(k1, (m, k), dtype)
    w = jax.random.normal(k2, (k, n), dtype)
    ok = (jax.random.uniform(k3, (r, c)) > 0.1).astype(jnp.float32)
    ref = masked_matmul_ref(x, w, ok)
    out = masked_matmul(x, w, ok, bm=bm, bn=bn, bk=bk, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(ref, np.float32),
        rtol=_tol(dtype),
        atol=_tol(dtype) * 10,
    )


def test_masked_matmul_zero_mask_kills_everything():
    x = jax.random.normal(KEY, (32, 64))
    w = jax.random.normal(KEY, (64, 32))
    ok = jnp.zeros((16, 16), jnp.float32)
    out = masked_matmul(x, w, ok, bm=32, bn=32, bk=32, interpret=True)
    assert float(jnp.max(jnp.abs(out))) == 0.0


def test_masked_matmul_batch_dims():
    x = jax.random.normal(KEY, (2, 3, 64))
    w = jax.random.normal(KEY, (64, 32))
    ok = (jax.random.uniform(KEY, (16, 16)) > 0.2).astype(jnp.float32)
    out = masked_matmul(x, w, ok, bm=32, bn=32, bk=32, interpret=True)
    ref = masked_matmul_ref(x, w, ok)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "b,hq,hkv,sq,skv,d,causal,window,off",
    [
        (2, 4, 2, 128, 128, 32, True, None, 0),
        (1, 8, 2, 256, 256, 64, True, 64, 0),  # sliding window
        (2, 2, 2, 128, 128, 32, False, None, 0),  # encoder
        (1, 4, 4, 1, 256, 32, True, None, 255),  # decode
        (2, 4, 2, 100, 100, 32, True, None, 0),  # padding path
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, hq, hkv, sq, skv, d, causal, window, off, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, sq, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, skv, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, skv, d), dtype)
    ref = attention_ref(q, k, v, causal=causal, window=window, q_offset=off)
    out = flash_attention(
        q, k, v, causal=causal, window=window, q_offset=off, bq=64, bkv=64,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(ref, np.float32),
        rtol=_tol(dtype),
        atol=_tol(dtype) * 5,
    )


# ---------------------------------------------------------------------------
# mamba selective scan
# ---------------------------------------------------------------------------


def _scan_inputs(b, l, d, n, dtype=jnp.float32, key=KEY):
    """u, B and C at ``dtype``; dt, A, D and a state float32, as the model
    passes them."""
    ks = jax.random.split(key, 7)
    u = jax.random.normal(ks[0], (b, l, d)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, d)))
    a = -jnp.exp(jax.random.normal(ks[2], (d, n)))
    bb = jax.random.normal(ks[3], (b, l, n)).astype(dtype)
    c = jax.random.normal(ks[4], (b, l, n)).astype(dtype)
    dd = jax.random.normal(ks[5], (d,))
    h0 = jax.random.normal(ks[6], (b, d, n))
    return u, dt, a, bb, c, dd, h0


def _assert_scan_close(y, h, yr, hr):
    """y at its own dtype's tolerance; the float32 state tightly."""
    rtol = dtype_tol(y.dtype)[0]
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(yr, np.float32),
                               rtol=rtol, atol=max(1e-4, rtol))
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize(
    "b,l,d,n,bd,bl,dtype",
    [
        pytest.param(2, 64, 32, 8, 16, 16, jnp.float32, id="2-64-32-8-16-16"),
        pytest.param(1, 128, 64, 16, 64, 32, jnp.float32, id="1-128-64-16-64-32"),
        pytest.param(3, 32, 16, 4, 16, 32, jnp.float32, id="3-32-16-4-16-32"),
        # channels on the lanes: 200 channels padded to two lane blocks and
        # sliced back; bf16 u/B/C with float32 dt over two time blocks, the
        # second half padded; the blocks chosen from the shape
        pytest.param(1, 32, 200, 16, 128, 32, jnp.float32, id="lane-pad-sliced"),
        pytest.param(2, 48, 256, 16, 128, 32, jnp.bfloat16, id="bf16-two-time-blocks"),
        pytest.param(1, 64, 384, 16, None, None, jnp.bfloat16, id="blocks-from-shape"),
    ],
)
def test_selective_scan_sweep(b, l, d, n, bd, bl, dtype):
    from repro.kernels.mamba_scan.ops import selective_scan

    u, dt, a, bb, c, dd, _ = _scan_inputs(b, l, d, n, dtype)
    yr, hr = selective_scan_ref(u, dt, a, bb, c, dd)
    yk, hk = selective_scan(u, dt, a, bb, c, dd, bd=bd, bl=bl, interpret=True)
    assert yk.shape == yr.shape and yk.dtype == yr.dtype and hk.shape == hr.shape
    _assert_scan_close(yk, hk, yr, hr)


@pytest.mark.parametrize(
    "split,b,l,d,n,bd,bl,dtype,pad",
    [
        pytest.param(0, 2, 40, 24, 8, 8, 8, jnp.float32, 0, id="0"),
        pytest.param(16, 2, 40, 24, 8, 8, 8, jnp.float32, 0, id="16"),
        pytest.param(23, 2, 40, 24, 8, 8, 8, jnp.float32, 0, id="23"),
        pytest.param(40, 2, 40, 24, 8, 8, 8, jnp.float32, 0, id="40"),
        # a bf16 chunk of two time blocks from a nonzero state, continued by
        # the next chunk at the block boundary (Jamba's chunked prefill)
        pytest.param(32, 2, 64, 256, 16, 128, 32, jnp.bfloat16, 0, id="bf16-two-blocks"),
        # the last 16 steps right-padded (dt = u = 0): the final state is
        # the one after the last real step; 200 channels lane-padded
        pytest.param(24, 1, 64, 200, 16, 128, 32, jnp.float32, 16, id="right-padded"),
    ],
)
def test_selective_scan_continues_from_h0(split, b, l, d, n, bd, bl, dtype, pad):
    """The kernel started from ``h0`` (interpret mode) matches the reference
    started from it, and a scan split at ``split`` — the second part
    continued from the first part's final state — reproduces the unsplit
    scan (0 and 40: one part empty of the other's steps). Right-padded
    steps with ``dt = 0`` leave the final state as the last real step left
    it."""
    from repro.kernels.mamba_scan.ops import selective_scan

    u, dt, a, bb, c, dd, h0 = _scan_inputs(b, l, d, n, dtype)
    real = jnp.arange(l) < l - pad
    u = jnp.where(real[None, :, None], u, 0).astype(dtype)
    dt = jnp.where(real[None, :, None], dt, 0)
    yr, hr = selective_scan_ref(u, dt, a, bb, c, dd, h0)
    yk, hk = selective_scan(u, dt, a, bb, c, dd, h0, bd=bd, bl=bl, interpret=True)
    _assert_scan_close(yk, hk, yr, hr)
    if pad:
        r = l - pad
        _, h_real = selective_scan_ref(u[:, :r], dt[:, :r], a, bb[:, :r], c[:, :r], dd, h0)
        np.testing.assert_allclose(np.asarray(hk), np.asarray(h_real), rtol=2e-5, atol=1e-4)
    ys, h = [], h0
    for lo, hi in ((0, split), (split, l)):
        if hi > lo:
            y, h = selective_scan(u[:, lo:hi], dt[:, lo:hi], a, bb[:, lo:hi], c[:, lo:hi], dd, h,
                                  bd=bd, bl=2 * bl, interpret=True)
            ys.append(y)
    _assert_scan_close(jnp.concatenate(ys, 1), h, yr, hr)


def test_selective_step_matches_scan():
    b, l, d, n = 2, 16, 8, 4
    ks = jax.random.split(KEY, 6)
    u = jax.random.normal(ks[0], (b, l, d))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, d)))
    a = -jnp.exp(jax.random.normal(ks[2], (d, n)))
    bb = jax.random.normal(ks[3], (b, l, n))
    c = jax.random.normal(ks[4], (b, l, n))
    dd = jax.random.normal(ks[5], (d,))
    yr, hr = selective_scan_ref(u, dt, a, bb, c, dd)
    h = jnp.zeros((b, d, n))
    for i in range(l):
        y, h = selective_step_ref(h, u[:, i], dt[:, i], a, bb[:, i], c[:, i], dd)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr[:, i]), rtol=2e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), rtol=2e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# int8-KV decode attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "b,hq,hkv,skv,d,valid",
    [(2, 4, 2, 256, 32, 256), (1, 8, 2, 256, 64, 200), (2, 2, 2, 128, 32, 1),
     (1, 4, 4, 192, 32, 100)],
)
def test_decode_attention_int8kv(b, hq, hkv, skv, d, valid):
    from repro.kernels.decode_attention.ops import decode_attention, quantize_kv
    from repro.kernels.decode_attention.ref import decode_attention_ref

    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, 1, d))
    k = jax.random.normal(ks[1], (b, hkv, skv, d))
    v = jax.random.normal(ks[2], (b, hkv, skv, d))
    ki, ksc = quantize_kv(k)
    vi, vsc = quantize_kv(v)
    ref = decode_attention_ref(q, ki, ksc, vi, vsc, kv_valid_len=valid)
    out = decode_attention(q, ki, ksc, vi, vsc, valid, bkv=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    # quantization error vs fp attention over the valid prefix stays small
    fp = attention_ref(q, k[:, :, :valid], v[:, :, :valid], causal=False, window=None)
    assert float(jnp.max(jnp.abs(out - fp))) < 5e-2


def test_quantize_kv_roundtrip_error():
    from repro.kernels.decode_attention.ops import dequantize_kv, quantize_kv

    k = jax.random.normal(KEY, (2, 2, 64, 32))
    ki, sc = quantize_kv(k)
    assert ki.dtype == jnp.int8
    back = dequantize_kv(ki, sc)
    rel = float(jnp.max(jnp.abs(back - k)) / jnp.max(jnp.abs(k)))
    assert rel < 0.01
