"""The float32 reference against the program's own forward pass, at a
tiny size on the CPU in float32, faulty and healthy."""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import model as ref
from repro.configs import get_arch
from repro.core import from_fault_map
from repro.core.faults import FaultMap
from repro.core.mapping import periodic_mask
from repro.models import model as M

TINY = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, intermediate_size=96, vocab_size=200)


def tiny(arch, qk_norm):
    cfg = replace(get_arch(arch), num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                  head_dim=16, d_ff=96, vocab_size=200, array_rows=16, array_cols=16,
                  dtype="float32")
    model = dict(TINY, rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta, qk_norm=qk_norm)
    return cfg, model


@pytest.mark.parametrize("arch,qk_norm", [("smollm-135m", False), ("qwen3-0.6b", True)])
@pytest.mark.parametrize("rate", [0.0, 0.2])
def test_reference_matches_the_program_in_float32(arch, qk_norm, rate):
    cfg, model = tiny(arch, qk_norm)
    params = jax.jit(lambda k: ref.make_params(model, k))(jax.random.PRNGKey(3))
    shapes = jax.eval_shape(lambda k: M.init_params(cfg, k)[0], jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(shapes) == jax.tree_util.tree_structure(params)
    assert all(jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(lambda a, b: a.shape == b.shape, shapes, params)))
    rng = np.random.default_rng(0)
    faulty = rng.random((16, 16)) < rate
    ctx = from_fault_map(FaultMap(faulty))
    toks = jnp.asarray(rng.integers(0, 200, 40), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = M.forward(params, {"tokens": toks[None]}, cfg, ctx, remat="none")
    got = ref.forward(ref.masked_weights(params, ~faulty if rate else None), toks, model)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]), atol=2e-5, rtol=1e-5)
    ctrl = ref.forward(ref.masked_weights(params, ~faulty if rate else None), toks, model,
                       ref.fp8_dot)
    assert float(jnp.abs(ctrl - got).max()) > 100 * float(jnp.abs(got - want[0]).max())


def test_fault_mask_is_the_periodic_mapping():
    ok = np.random.default_rng(1).random((16, 16)) > 0.3
    for shape in [(20, 50), (32, 48), (3, 20, 50)]:
        np.testing.assert_array_equal(ref.fault_mask(shape, ok),
                                      np.asarray(periodic_mask(shape[-2:], ok)))
