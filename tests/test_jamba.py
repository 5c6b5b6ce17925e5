"""Jamba (Mamba-1 layers interleaved with attention) through the continuous
engine, against the benchmark's plain float32 reference
(``bench/reference/jamba.py``), at a small size on seeded random weights.

The engine's own programs are driven as ``serve()`` drives them — a packed
(bucket) admission, a prompt streamed in chunks whose length is not a
multiple of the chunk, then fused decode steps through the paged cache —
and every slot's logits are compared with the reference's full forward
pass over the same tokens."""
from __future__ import annotations

import sys
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.masking import is_array_mapped
from repro.models import model as M
from repro.obs import Recorder
from repro.serve import ContinuousBatchingEngine, Request
from repro.serve.bucketing import PackItem, build_pack, chunk_step_maps, plan_prefill
from repro.serve.kvcache import ssm_state_bytes

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))  # the benchmark's reference
from bench.reference import jamba as ref  # noqa: E402

# five layers, attention in the middle: Mamba runs of two on either side
MODEL = dict(num_hidden_layers=5, attn_layer_period=5, attn_layer_offset=2, hidden_size=64,
             num_attention_heads=4, num_key_value_heads=1, intermediate_size=96,
             vocab_size=128, mamba_d_state=8, mamba_d_conv=4, mamba_expand=2,
             mamba_dt_rank=8, rms_norm_eps=1e-6)
CFG = replace(get_arch("jamba2-3b"), num_layers=5, attn_layer_period=5, attn_layer_offset=2,
              d_model=64, num_heads=4, num_kv_heads=1, head_dim=0, d_ff=96, vocab_size=128,
              ssm_state=8, ssm_dt_rank=8, dtype="float32", array_rows=16, array_cols=16)
PAGE, MAXP, CHUNK = 4, 16, 16
# float32 both sides; the program sums in other orders (blocked conv,
# per-layer casts, the paged gather) than the reference
TOL = dict(rtol=1e-4, atol=1e-4)


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: ref.make_params(MODEL, k))(jax.random.PRNGKey(7))


def _engine(params, **kw):
    kw = dict(dict(num_slots=3, page_size=PAGE, num_pages=3 * MAXP + 1, max_pages_per_seq=MAXP,
                   prefill_buckets=(8, 16), chunk_size=CHUNK, max_pack=4), **kw)
    return ContinuousBatchingEngine(CFG, params, **kw)


def _ref_logits(params, tokens):
    return np.asarray(ref.logits(params, jnp.asarray(tokens, jnp.int32), MODEL))


def test_the_layer_stack_holds_one_period():
    assert [k for k, _, _ in M.layer_runs(CFG)] == ["mamba_layers", "layers", "mamba_layers"]
    assert (CFG.num_attn_layers, CFG.num_ssm_layers) == (1, 4)
    full = get_arch("jamba2-3b")
    assert [i for i in range(full.num_layers) if full.is_attention_layer(i)] == [7, 21]
    assert full.param_count() == 3_029_337_472
    assert replace(full, num_layers=14).param_count() == 1_598_556_096


def test_engine_programs_match_the_reference_on_logits(params):
    """Slot 1 admits an 11-token prompt packed into the 16 bucket, slot 0
    streams a 37-token prompt in chunks of 16 (16, 16, 5), slot 2 stays
    empty; then greedy decode steps. Every live slot's logits equal the
    reference's at its position; the empty slot's state stays zero."""
    eng = _engine(params)
    rng = np.random.default_rng(3)
    a, b = rng.integers(0, MODEL["vocab_size"], 11), rng.integers(0, MODEL["vocab_size"], 37)
    cache = M.init_paged_cache(CFG, eng.num_pages, PAGE, 3, MAXP)
    cur = jnp.zeros((3, MODEL["vocab_size"]), jnp.float32)
    active = jnp.zeros((3,), bool)
    remaining = jnp.zeros((3,), jnp.int32)
    ctx = eng._serve_ctx
    pages_a, pages_b = list(range(1, 1 + MAXP)), list(range(1 + MAXP, 1 + 2 * MAXP))

    arr = build_pack([PackItem(a.astype(np.int32), 1, tuple(pages_a), 8)], bucket=16,
                     max_pack=eng.max_pack, page_size=PAGE, max_pages_per_seq=MAXP,
                     num_slots=3)
    cache, cur, active, remaining = eng._packed_admit(
        params, arr["tokens"], arr["positions"], arr["segments"], ctx, cache, cur, active,
        remaining, arr["page_ix"], arr["page_off"], arr["gather_pos"], arr["slots"],
        arr["rows"], arr["seq_lens"], arr["budgets"])
    np.testing.assert_allclose(np.asarray(cur[1]), _ref_logits(params, a)[-1], **TOL)

    steps = plan_prefill(len(b), buckets=(8, 16), chunk_size=CHUNK)
    assert [s.valid for s in steps] == [16, 16, 5]
    row = np.asarray(pages_b, np.int32)
    for st in steps:
        maps = chunk_step_maps(st, pages_b, page_size=PAGE)
        ct = np.zeros((st.size,), np.int32)
        ct[: st.valid] = b[st.start : st.start + st.valid]
        cache, cur, active, remaining = eng._prefill_chunk(
            params, ct[None], ctx, cache, cur, active, remaining, np.int32(0), row,
            maps["page_ix"], maps["page_off"], np.int32(st.start), np.int32(st.valid),
            np.int32(8), np.bool_(st.final))
    np.testing.assert_allclose(np.asarray(cur[0]), _ref_logits(params, b)[-1], **TOL)

    seqs = {0: list(b), 1: list(a)}
    key = jax.random.PRNGKey(0)
    for _ in range(5):
        emitted, _, cur, cache, key, active, remaining = eng._sample_decode(
            params, cur, cache, key, ctx, jnp.float32(0.0), active, jnp.int32(-1), remaining)
        for slot, seq in seqs.items():
            seq.append(int(emitted[slot]))
            np.testing.assert_allclose(np.asarray(cur[slot]), _ref_logits(params, seq)[-1],
                                       **TOL)
    assert not np.any(np.asarray(cache["h"][:, 2])) and not np.any(np.asarray(cache["conv"][:, 2]))


def test_two_prompts_admitted_together_keep_their_state_apart(params):
    """Two short prompts arrive together, each fitting one bucket with the
    other: the engine packs one prompt per admission for a model with Mamba
    layers, so each is served exactly as the reference serves it alone."""
    rec = Recorder()
    eng = _engine(params, recorder=rec)
    assert eng.max_pack == 1
    rng = np.random.default_rng(5)
    reqs = [Request(i, rng.integers(0, MODEL["vocab_size"], n), 4) for i, n in enumerate((5, 7))]
    outs, stats = eng.serve(reqs)
    assert stats.prefill_dispatches == 2
    for r in reqs:
        seq = list(r.tokens) + list(outs[r.rid].tokens[:-1])
        lp = jax.nn.log_softmax(_ref_logits(params, seq), axis=-1)[len(r.tokens) - 1 :]
        np.testing.assert_array_equal(outs[r.rid].tokens, np.argmax(lp, axis=-1))
        got = np.take_along_axis(lp, outs[r.rid].tokens[:, None], axis=-1)[:, 0]
        np.testing.assert_allclose(outs[r.rid].logprobs, got, **TOL)
    assert rec.metrics.counter("ssm.state_reset").value == 2
    assert rec.metrics.counter("ssm.state_bytes").value == ssm_state_bytes(CFG, 3)
    assert ssm_state_bytes(CFG, 3) == 4 * 128 * (3 + 8) * 4 * 3  # layers x d_inner x (K-1 + N)


def test_the_published_maps_name_the_mamba_and_scan_ops(params):
    eng = _engine(params)
    eng.warmup()
    for program in ("jit_sample_decode", "jit__prefill_chunk_fn", "jit__packed_admit_fn"):
        mamba, scan = set(eng.mamba_ops[program]), set(eng.scan_ops[program])
        assert scan and mamba and scan <= mamba
        assert not eng.mask_ops[program]


def test_only_the_gemm_weights_are_array_mapped(params):
    mapped = {jax.tree_util.keystr(p) for p, w in jax.tree_util.tree_leaves_with_path(params)
              if is_array_mapped(p, w)}
    attn = {f"['layers']['attn']['{k}']" for k in ("wq", "wk", "wv", "wo")}
    mixer = {f"['mamba_layers']['ssm']['{k}']" for k in ("in_proj", "x_proj", "dt_w", "out_proj")}
    mlps = {f"['{s}']['mlp']['{k}']" for s in ("layers", "mamba_layers") for k in ("wg", "wu", "wd")}
    assert mapped == attn | mixer | mlps
