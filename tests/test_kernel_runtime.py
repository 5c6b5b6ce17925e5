"""Unit tests for the shared kernel-runtime layer (repro.kernels.common):
the compiler-params helper, pad/unpad geometry, backend
autodetection, and the per-dtype tolerance table.

``hypothesis`` is optional (same contract as tests/test_core.py): without
it only the ``choose_block`` property tests skip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - exercised in offline environments

    def given(*_args, **_kwargs):
        def deco(fn):
            return pytest.mark.skip(reason="hypothesis is not installed")(fn)

        return deco

    def settings(*_args, **_kwargs):
        return lambda fn: fn

    class _StrategyStub:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _StrategyStub()

from repro.kernels import common


# ---------------------------------------------------------------------------
# compiler params (the one pltpu.CompilerParams spelling)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "semantics,extra",
    [
        (("parallel", "arbitrary"), {}),
        (["parallel"], {"vmem_limit_bytes": 1 << 20}),
        (None, {}),
    ],
)
def test_compiler_params_fields(semantics, extra):
    from jax.experimental.pallas import tpu as pltpu

    out = common.tpu_compiler_params(dimension_semantics=semantics, **extra)
    assert isinstance(out, pltpu.CompilerParams)
    if semantics is not None:
        assert out.dimension_semantics == tuple(semantics)
    for k, v in extra.items():
        assert getattr(out, k) == v


def test_shim_works_against_installed_jax():
    from jax.experimental.pallas import tpu as pltpu

    out = common.tpu_compiler_params(
        dimension_semantics=("parallel", "parallel", "arbitrary")
    )
    assert isinstance(out, pltpu.CompilerParams)
    assert out.dimension_semantics == ("parallel", "parallel", "arbitrary")


# ---------------------------------------------------------------------------
# pad / unpad round-trips on non-block-multiple shapes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,b", [(1, 8), (7, 8), (8, 8), (33, 32), (100, 64)])
def test_pad_to_multiple(n, b):
    p = common.pad_to_multiple(n, b)
    assert p >= n and p % b == 0 and p - n < b
    assert common.pad_amount(n, b) == p - n


@pytest.mark.parametrize("shape,targets", [((33, 100), {0: 64, 1: 128}), ((5, 7, 3), {1: 8})])
def test_pad_axes_round_trip(shape, targets):
    x = jnp.asarray(np.random.default_rng(0).normal(size=shape), jnp.float32)
    xp = common.pad_axes_to(x, targets)
    for axis in range(x.ndim):
        assert xp.shape[axis] == targets.get(axis, x.shape[axis])
    sl = tuple(slice(0, s) for s in shape)
    np.testing.assert_array_equal(np.asarray(xp[sl]), np.asarray(x))
    # padded region is zero
    assert float(jnp.sum(jnp.abs(xp))) == pytest.approx(float(jnp.sum(jnp.abs(x))), rel=1e-6)


def test_pad_axis_rejects_shrinking():
    x = jnp.ones((8, 8))
    with pytest.raises(ValueError):
        common.pad_axis_to(x, 0, 4)


def test_choose_block_respects_period():
    assert common.choose_block(256, 64) == 64
    assert common.choose_block(48, 64) == 48  # clamped to dim
    # block below the mask period that doesn't divide it -> snap to period
    assert common.choose_block(256, 24, multiple_of=32) == 32
    # block that divides the period stays
    assert common.choose_block(256, 16, multiple_of=32) == 16
    # incompatible block above the period -> the period multiple with the
    # least padding of dim (24 pads 100 -> 120; 96 would pad to 192)
    assert common.choose_block(100, 512, multiple_of=24) == 24
    # on equal padding, prefer the largest compatible block
    assert common.choose_block(96, 512, multiple_of=24) == 96


@settings(max_examples=200, deadline=None)
@given(
    dim=st.integers(min_value=1, max_value=4096),
    requested=st.integers(min_value=1, max_value=8192),
    period=st.integers(min_value=1, max_value=256),
)
def test_choose_block_properties(dim, requested, period):
    """The tuner normalizes every lattice point through choose_block, so its
    contract is load-bearing: the result is positive, period-compatible
    (divides the period or is a multiple of it), never exceeds
    max(dim, period), and when it lands on a period multiple it is the
    minimal-padding choice with LARGEST-block tie-breaking."""
    b = common.choose_block(dim, requested, multiple_of=period)
    assert b >= 1
    assert b <= max(dim, period)
    if period > 1:
        assert period % b == 0 or b % period == 0
    # never bigger than asked for, except when snapping up to the period
    assert b <= max(min(requested, dim), period)
    b0 = max(1, min(requested, dim))
    if period > 1 and b0 >= period and b0 % period:
        pad = common.pad_to_multiple(dim, b) - dim
        for c in range(period, b0 + 1, period):
            pad_c = common.pad_to_multiple(dim, c) - dim
            assert (pad_c, -c) >= (pad, -b), (
                f"candidate {c} (pad {pad_c}) beats chosen {b} (pad {pad})"
            )


@settings(max_examples=100, deadline=None)
@given(
    dim=st.integers(min_value=1, max_value=2048),
    requested=st.integers(min_value=1, max_value=4096),
    period=st.integers(min_value=1, max_value=128),
)
def test_choose_block_idempotent(dim, requested, period):
    """Re-normalizing a chosen block is a fixed point (what lets the tuner
    memoize candidates by their normalized key)."""
    b = common.choose_block(dim, requested, multiple_of=period)
    assert common.choose_block(dim, b, multiple_of=period) == b


def test_masked_matmul_dim_exceeds_non_power_of_two_period():
    """dim > mask period but not a period multiple must pad, not raise."""
    from repro.kernels.masked_matmul.ops import masked_matmul
    from repro.kernels.masked_matmul.ref import masked_matmul_ref

    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (64, 96))
    w = jax.random.normal(key, (96, 100))
    ok = (jax.random.uniform(key, (24, 24)) > 0.2).astype(jnp.float32)
    out = masked_matmul(x, w, ok, interpret=True)
    assert out.shape == (64, 100)
    common.assert_close(out, masked_matmul_ref(x, w, ok), jnp.float32)


def test_grid_for():
    assert common.grid_for((64, 128), (32, 32)) == (2, 4)
    with pytest.raises(ValueError):
        common.grid_for((65, 128), (32, 32))
    with pytest.raises(ValueError):
        common.grid_for((64,), (32, 32))


def test_kernel_pad_round_trip_non_multiple_shapes():
    """ops-level check: ragged shapes go through pad -> kernel -> unpad."""
    from repro.kernels.masked_matmul.ops import masked_matmul
    from repro.kernels.masked_matmul.ref import masked_matmul_ref

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (19, 70))
    w = jax.random.normal(key, (70, 45))
    ok = (jax.random.uniform(key, (8, 8)) > 0.2).astype(jnp.float32)
    out = masked_matmul(x, w, ok, bm=16, bn=16, bk=16, interpret=True)
    assert out.shape == (19, 45)
    common.assert_close(out, masked_matmul_ref(x, w, ok), jnp.float32)


def test_mamba_pad_round_trip_non_multiple_shapes():
    from repro.kernels.mamba_scan.ops import selective_scan
    from repro.kernels.mamba_scan.ref import selective_scan_ref

    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    b, l, d, n = 2, 37, 11, 4  # neither l nor d block-multiples
    u = jax.random.normal(ks[0], (b, l, d))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, d)))
    a = -jnp.exp(jax.random.normal(ks[2], (d, n)))
    bb = jax.random.normal(ks[3], (b, l, n))
    c = jax.random.normal(ks[4], (b, l, n))
    dd = jax.random.normal(ks[5], (d,))
    yr, hr = selective_scan_ref(u, dt, a, bb, c, dd)
    yk, hk = selective_scan(u, dt, a, bb, c, dd, bd=8, bl=16, interpret=True)
    assert yk.shape == yr.shape and hk.shape == hr.shape
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr), rtol=2e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hk), np.asarray(hr), rtol=2e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# backend autodetection
# ---------------------------------------------------------------------------


def test_resolve_interpret_autodetects_cpu():
    # the test suite pins JAX_PLATFORMS=cpu, so autodetection must pick
    # interpret mode and explicit flags must pass through untouched
    assert common.is_tpu_backend() is False
    assert common.resolve_interpret(None) is True
    assert common.resolve_interpret(True) is True
    assert common.resolve_interpret(False) is False


def test_resolve_interpret_compiles_on_tpu(monkeypatch):
    monkeypatch.setattr(common.jax, "default_backend", lambda: "tpu")
    assert common.is_tpu_backend() is True
    assert common.resolve_interpret(None) is False
    assert common.resolve_interpret(True) is True


def test_kernel_entrypoint_autodetects_interpret_on_cpu():
    """Calling the raw pallas entry point with no interpret flag must run on
    a CPU-only host (previously: hard default interpret=False -> crash)."""
    from repro.kernels.masked_matmul.masked_matmul import masked_matmul_pallas
    from repro.kernels.masked_matmul.ref import masked_matmul_ref

    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (32, 32))
    w = jax.random.normal(key, (32, 32))
    ok = (jax.random.uniform(key, (16, 16)) > 0.3).astype(jnp.float32)
    out = masked_matmul_pallas(x, w, ok, bm=16, bn=16, bk=16)
    common.assert_close(out, masked_matmul_ref(x, w, ok), jnp.float32)


# ---------------------------------------------------------------------------
# analytic VMEM model
# ---------------------------------------------------------------------------


def test_vmem_footprint_single_buffered_default():
    blocks = [((128, 128), jnp.float32), ((128, 128), jnp.bfloat16)]
    assert common.vmem_footprint(blocks) == 128 * 128 * 4 + 128 * 128 * 2


def test_vmem_footprint_double_buffered_doubles_io_blocks_only():
    io = ((64, 64), jnp.float32)  # 2-tuple: DMA'd in/out block
    scratch = ((64, 64), jnp.float32, False)  # accumulator, never DMA'd
    single = common.vmem_footprint([io, scratch])
    double = common.vmem_footprint([io, scratch], double_buffered=True)
    assert single == 2 * 64 * 64 * 4
    # only the io block doubles: 2x io + 1x scratch
    assert double == 3 * 64 * 64 * 4


def test_vmem_footprint_explicit_io_flag_matches_two_tuple():
    a = common.vmem_footprint([((32, 8), jnp.float32)], double_buffered=True)
    b = common.vmem_footprint([((32, 8), jnp.float32, True)], double_buffered=True)
    assert a == b == 2 * 32 * 8 * 4


def test_kernelgeom_launches_mark_scratch_non_io():
    """The launch builders must tag accumulator scratch with is_io=False so
    the tuner's double-buffered bound doesn't double-count it."""
    from repro.analysis.kernelgeom import masked_matmul_launch

    launch = masked_matmul_launch(256, 256, 256, (32, 32), bm=64, bn=64, bk=64)
    flags = [e[2] if len(e) > 2 else True for e in launch.vmem_blocks]
    assert False in flags and True in flags
    assert common.vmem_footprint(
        launch.vmem_blocks, double_buffered=True
    ) < 2 * common.vmem_footprint(launch.vmem_blocks)


# ---------------------------------------------------------------------------
# tolerance table
# ---------------------------------------------------------------------------


def test_dtype_tol_table():
    rtol32, atol32 = common.dtype_tol(jnp.float32)
    rtol16, atol16 = common.dtype_tol(jnp.bfloat16)
    assert rtol16 > rtol32
    assert atol32 == pytest.approx(rtol32 * 10)
    # unknown dtypes fall back to the float32 default
    assert common.dtype_tol(jnp.int8)[0] == rtol32


def test_assert_close_uses_dtype_tolerance():
    a = jnp.ones((4, 4), jnp.bfloat16)
    b = a * (1.0 + 1e-3)  # within bf16 tolerance, outside fp32 tolerance
    common.assert_close(a, b, jnp.bfloat16)
    with pytest.raises(AssertionError):
        common.assert_close(
            jnp.ones((4, 4)), jnp.ones((4, 4)) * 1.01, jnp.float32
        )
