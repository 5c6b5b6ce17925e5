"""Compile the four Pallas kernels for a TPU v5e that is described, not
attached, at the main path's widths.

Interpret mode (every other kernel test) does not apply Mosaic's tiling and
alignment rules; the TPU compiler, installed with JAX, does. Each test
lowers one kernel launch through its ``ops.py`` wrapper with
``interpret=False`` for one chip of a described ``v5e:2x2`` topology and
checks that the compiled program holds the Pallas kernel
(``tpu_custom_call``). Widths: smollm-135m's GEMMs and attention heads, and
falcon-mamba-7b's scan.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch

SMOLLM = get_arch("smollm-135m")
MAMBA = get_arch("falcon-mamba-7b")
D, F, HD = SMOLLM.d_model, SMOLLM.d_ff, SMOLLM.resolved_head_dim
HQ, HKV = SMOLLM.num_heads, SMOLLM.num_kv_heads
GEMMS = [(D, D), (D, HKV * HD), (D, F), (F, D), (D, SMOLLM.vocab_size)]
M_ROWS, SEQ, CTX, SLOTS, PAGE, SCAN_LEN = 2048, 2048, 2048, 4, 16, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache without one: keep these compiles out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        yield desc
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def spec(topo):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_compiles_to_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k,n", GEMMS, ids=[f"{k}x{n}" for k, n in GEMMS])
def test_masked_matmul_compiles(spec, k, n):
    from repro.kernels.masked_matmul.ops import masked_matmul

    _assert_compiles_to_kernel(
        lambda x, w, ok: masked_matmul(x, w, ok, interpret=False),
        spec((M_ROWS, k), jnp.bfloat16),
        spec((k, n), jnp.bfloat16),
        spec((SMOLLM.array_rows, SMOLLM.array_cols), jnp.float32),
    )


def test_flash_attention_compiles(spec):
    from repro.kernels.flash_attention.ops import flash_attention

    _assert_compiles_to_kernel(
        lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False),
        spec((1, HQ, SEQ, HD), jnp.bfloat16),
        spec((1, HKV, SEQ, HD), jnp.bfloat16),
        spec((1, HKV, SEQ, HD), jnp.bfloat16),
    )


def test_decode_attention_int8_dense_compiles(spec):
    from repro.kernels.decode_attention.ops import decode_attention

    kv, scales = spec((SLOTS, HKV, CTX, HD), jnp.int8), spec((SLOTS, HKV, CTX), jnp.float32)
    _assert_compiles_to_kernel(
        lambda *a: decode_attention(*a, interpret=False),
        spec((SLOTS, HQ, 1, HD), jnp.bfloat16), kv, scales, kv, scales,
        spec((), jnp.int32),
    )


def test_paged_decode_attention_int8_compiles(spec):
    from repro.kernels.decode_attention.ops import paged_decode_attention

    maxp = CTX // PAGE
    pool = SLOTS * maxp + 1
    pages = spec((HKV, pool, PAGE, HD), jnp.int8)
    scales = spec((HKV, pool, PAGE), jnp.float32)
    _assert_compiles_to_kernel(
        lambda *a: paged_decode_attention(*a, interpret=False),
        spec((SLOTS, HQ, 1, HD), jnp.bfloat16), pages, scales, pages, scales,
        spec((SLOTS, maxp), jnp.int32), spec((SLOTS,), jnp.int32),
    )


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_selective_scan_compiles(spec, dtype):
    from repro.kernels.mamba_scan.ops import selective_scan

    seq = spec((1, SCAN_LEN, MAMBA.d_inner), dtype)
    bc = spec((1, SCAN_LEN, MAMBA.ssm_state), dtype)
    _assert_compiles_to_kernel(
        lambda *a: selective_scan(*a, interpret=False),
        seq, seq, spec((MAMBA.d_inner, MAMBA.ssm_state), jnp.float32), bc, bc,
        spec((MAMBA.d_inner,), jnp.float32),
    )
