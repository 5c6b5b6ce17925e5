"""Continuous-batching serve subsystem tests (repro.serve + fleet serving).

Contracts pinned here:
* the page allocator never double-books pages, page 0 stays reserved;
* the paged decode path in models/model.py::decode_step matches the dense
  decode path logit-for-logit (and the paged int8 decode-attention kernel
  matches the dense kernel's reference within kernel-runtime tolerances);
* ContinuousBatchingEngine greedy outputs are pinned token-for-token
  against per-request ServeEngine runs — including requests admitted
  mid-flight into slots freed by retirement;
* static (EOS-masked) and continuous engines agree on EOS semantics;
* ShardedFleetServeEngine serves N chips' independent ragged streams with
  per-chip outputs identical to per-chip ContinuousBatchingEngine runs, and
  per-chip temperature sampling is reproducible and chip-independent.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduce_config
from repro.core import from_fault_map, healthy, random_fault_map
from repro.fleet import ShardedFleetServeEngine
from repro.kernels.common import assert_close
from repro.kernels.decode_attention.ops import (
    decode_attention,
    paged_decode_attention,
    quantize_kv,
)
from repro.kernels.decode_attention.ref import paged_decode_attention_ref
from repro.models import model as M
from repro.serve import (
    ContinuousBatchingEngine,
    PageAllocator,
    Request,
    ServeEngine,
    dense_kv_bytes,
    page_bytes,
    pages_needed,
)
from repro.serve.kvcache import chain_layout

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def served_model():
    cfg = reduce_config(get_arch("smollm-135m"))
    params, _ = M.init_params(cfg, KEY)
    return cfg, params


def _prompt(cfg, seed: int, length: int) -> np.ndarray:
    return np.asarray(
        jax.random.randint(jax.random.fold_in(KEY, seed), (length,), 0, cfg.vocab_size)
    )


# ---------------------------------------------------------------------------
# Page allocator + layout helpers
# ---------------------------------------------------------------------------


def test_page_allocator_freelist():
    a = PageAllocator(num_pages=6, page_size=4)
    assert a.free_pages == 5  # page 0 reserved
    p1 = a.alloc(2)
    p2 = a.alloc(1)
    assert 0 not in p1 + p2
    assert len(set(p1 + p2)) == 3
    assert a.pages_in_use == 3 and a.peak_pages == 3
    a.free(p1)
    assert a.pages_in_use == 1
    p3 = a.alloc(4)  # freed pages are reusable
    assert len(set(p2 + p3)) == 5
    with pytest.raises(MemoryError):
        a.alloc(1)
    a.free(p3)
    with pytest.raises(ValueError):
        a.free(p3[:1])  # double free
    with pytest.raises(ValueError):
        a.free([0])  # reserved page
    with pytest.raises(ValueError):
        PageAllocator(num_pages=1, page_size=4)


def test_pages_needed_and_bytes(served_model):
    cfg, _ = served_model
    assert pages_needed(1, 8) == 1 and pages_needed(8, 8) == 1 and pages_needed(9, 8) == 2
    # one page of 8 tokens == a dense cache of batch 1 x 8 tokens
    assert page_bytes(cfg, 8) == dense_kv_bytes(cfg, 1, 8)


def test_chain_layout_roundtrip(served_model):
    cfg, _ = served_model
    L, hkv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    k = jnp.arange(L * hkv * 7 * hd, dtype=jnp.float32).reshape(L, 1, hkv, 7, hd)
    chain = chain_layout(k, page_size=4, chain_len=2)  # (L, 2, Hkv, 4, hd)
    assert chain.shape == (L, 2, hkv, 4, hd)
    # tokens 0..6 land in order; slot 7 of the tail page is zero padding
    flat = jnp.moveaxis(chain, 2, 1).reshape(L, hkv, 8, hd)
    assert np.array_equal(np.asarray(flat[..., :7, :]), np.asarray(k[:, 0]))
    assert np.all(np.asarray(flat[..., 7, :]) == 0)
    with pytest.raises(ValueError):
        chain_layout(k, page_size=4, chain_len=1)


def test_init_paged_cache_rejects_unpageable():
    ssm_cfg = reduce_config(get_arch("falcon-mamba-7b"))
    with pytest.raises(ValueError, match="attention"):
        M.init_paged_cache(ssm_cfg, 8, 4, 2, 4)
    enc_cfg = reduce_config(get_arch("hubert-xlarge"))
    with pytest.raises(ValueError, match="decode"):
        M.init_paged_cache(enc_cfg, 8, 4, 2, 4)


# ---------------------------------------------------------------------------
# Paged decode path vs dense decode path (models/model.py)
# ---------------------------------------------------------------------------


def test_paged_decode_step_matches_dense(served_model):
    """Same prompts through the dense cache and through a paged cache with
    shuffled slots + an inactive lane: logits equal, greedy tokens equal,
    inactive slot's seq_len frozen."""
    cfg, params = served_model
    B, plen, page, maxp, P = 2, 8, 4, 8, 17
    prompts = jnp.stack([jnp.asarray(_prompt(cfg, 10 + b, plen)) for b in range(B)])

    logits_d, cache_d = M.prefill(params, {"tokens": prompts}, cfg, None, cache_len=32)

    cache_p = M.init_paged_cache(cfg, P, page, num_slots=3, max_pages_per_seq=maxp)
    alloc = PageAllocator(P, page)
    bt = np.zeros((3, maxp), np.int32)
    lens = np.zeros(3, np.int32)
    cur = np.zeros((B, cfg.vocab_size), np.float32)
    slot_of = [1, 2]  # slot 0 stays inactive the whole time
    for b in range(B):
        lo, c = M.prefill(params, {"tokens": prompts[b : b + 1]}, cfg, None, cache_len=plen)
        pids = alloc.alloc(pages_needed(plen + 6, page))
        cache_p["k_pages"] = cache_p["k_pages"].at[:, np.asarray(pids)].set(
            chain_layout(c["k"], page, len(pids))
        )
        cache_p["v_pages"] = cache_p["v_pages"].at[:, np.asarray(pids)].set(
            chain_layout(c["v"], page, len(pids))
        )
        bt[slot_of[b], : len(pids)] = pids
        lens[slot_of[b]] = plen
        cur[b] = np.asarray(lo[0])
    cache_p["block_tables"] = jnp.asarray(bt)
    cache_p["seq_lens"] = jnp.asarray(lens)
    np.testing.assert_allclose(cur, np.asarray(logits_d), rtol=1e-5, atol=1e-5)

    sel = jnp.asarray(slot_of)
    active = jnp.asarray([False, True, True])
    toks = jnp.argmax(logits_d, -1)
    for _ in range(5):
        ld, cache_d = M.decode_step(params, toks[:, None], cache_d, cfg, None)
        full = jnp.zeros((3,), jnp.int32).at[sel].set(toks)
        lp, cache_p = M.decode_step(params, full[:, None], cache_p, cfg, None, active=active)
        np.testing.assert_allclose(
            np.asarray(lp[:, 0][sel]), np.asarray(ld[:, 0]), rtol=2e-5, atol=2e-5
        )
        tp = jnp.argmax(lp[:, 0][sel], -1)
        toks_d = jnp.argmax(ld[:, 0], -1)
        assert np.array_equal(np.asarray(toks_d), np.asarray(tp))
        toks = toks_d
    assert int(cache_p["seq_lens"][0]) == 0  # inactive slot never advanced
    assert np.all(np.asarray(cache_p["seq_lens"][sel]) == plen + 5)


# ---------------------------------------------------------------------------
# Paged int8 decode-attention kernel (interpret mode, kernel-runtime pinning)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def paged_kv_pool():
    rng = np.random.default_rng(0)
    B, Hq, Hkv, D, page, maxp, P = 3, 4, 2, 16, 8, 4, 14
    lens = jnp.asarray([5, 17, 32], jnp.int32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, maxp * page, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, maxp * page, D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, Hq, 1, D)), jnp.float32)
    tbl = jnp.asarray(
        rng.permutation(np.arange(1, P))[: B * maxp].reshape(B, maxp), jnp.int32
    )
    ki8, ks = quantize_kv(k)
    vi8, vs = quantize_kv(v)
    pool_k = jnp.zeros((Hkv, P, page, D), jnp.int8)
    pool_ks = jnp.zeros((Hkv, P, page), jnp.float32)
    pool_v = jnp.zeros((Hkv, P, page, D), jnp.int8)
    pool_vs = jnp.zeros((Hkv, P, page), jnp.float32)
    for b in range(B):
        for i in range(maxp):
            pid, sl = int(tbl[b, i]), slice(i * page, (i + 1) * page)
            pool_k = pool_k.at[:, pid].set(ki8[b, :, sl])
            pool_ks = pool_ks.at[:, pid].set(ks[b, :, sl])
            pool_v = pool_v.at[:, pid].set(vi8[b, :, sl])
            pool_vs = pool_vs.at[:, pid].set(vs[b, :, sl])
    return q, (ki8, ks, vi8, vs), (pool_k, pool_ks, pool_v, pool_vs), tbl, lens


def test_paged_ref_matches_dense_ref_per_sequence(paged_kv_pool):
    q, dense, pool, tbl, lens = paged_kv_pool
    ki8, ks, vi8, vs = dense
    ref = paged_decode_attention_ref(q, *pool, tbl, lens)
    for b in range(q.shape[0]):
        d = decode_attention(
            q[b : b + 1], ki8[b : b + 1], ks[b : b + 1], vi8[b : b + 1],
            vs[b : b + 1], lens[b],
        )
        assert_close(ref[b : b + 1], d)


def test_paged_kernel_interpret_matches_ref(paged_kv_pool):
    q, _, pool, tbl, lens = paged_kv_pool
    ref = paged_decode_attention_ref(q, *pool, tbl, lens)
    out = paged_decode_attention(q, *pool, tbl, lens, interpret=True)
    assert_close(out, ref)


def test_paged_op_fallback_dispatch(paged_kv_pool):
    """interpret=None off-TPU routes to the gather reference."""
    q, _, pool, tbl, lens = paged_kv_pool
    out = paged_decode_attention(q, *pool, tbl, lens)
    assert_close(out, paged_decode_attention_ref(q, *pool, tbl, lens))
    with pytest.raises(ValueError, match="one query token"):
        paged_decode_attention(jnp.concatenate([q, q], axis=2), *pool, tbl, lens)


# ---------------------------------------------------------------------------
# ContinuousBatchingEngine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def skewed_trace(served_model):
    cfg, _ = served_model
    return [
        Request(0, _prompt(cfg, 0, 6), max_new_tokens=4),
        Request(1, _prompt(cfg, 1, 7), max_new_tokens=12),
        Request(2, _prompt(cfg, 2, 8), max_new_tokens=6, arrival=2),
        Request(3, _prompt(cfg, 3, 9), max_new_tokens=3, arrival=5),
        Request(4, _prompt(cfg, 4, 6), max_new_tokens=8, arrival=5),
    ]


def test_continuous_greedy_pinned_per_request(served_model, skewed_trace):
    """Every request — including the ones admitted mid-flight into slots
    freed by retirement — reproduces a per-request ServeEngine run
    token-for-token."""
    cfg, params = served_model
    eng = ContinuousBatchingEngine(cfg, params, num_slots=2, page_size=4, num_pages=32)
    outs, stats = eng.serve(skewed_trace)
    assert set(outs) == {r.rid for r in skewed_trace}
    ref_eng = ServeEngine(cfg, params, max_len=None, page_size=4)
    for r in skewed_trace:
        ref = ref_eng.generate(jnp.asarray(r.tokens)[None], max_new_tokens=r.max_new_tokens)
        got = outs[r.rid]
        assert got.finish_reason == "length"
        assert np.array_equal(got.tokens, np.asarray(ref.tokens[0, len(r.tokens):])), r.rid
        np.testing.assert_allclose(
            got.logprobs, np.asarray(ref.logprobs[0]), rtol=1e-4, atol=1e-4
        )
    # mid-flight refill actually happened: 5 requests through 2 slots
    assert stats.admitted == 5 and stats.num_slots == 2
    # and it saves dispatches over draining slot-table-sized static batches
    assert stats.decode_dispatches < 4 + 12 + 6 + 8
    assert 0.0 < stats.slot_utilization <= 1.0


def test_continuous_retirement_frees_pages(served_model):
    cfg, params = served_model
    eng = ContinuousBatchingEngine(cfg, params, num_slots=2, page_size=4, num_pages=16)
    reqs = [
        Request(0, _prompt(cfg, 20, 6), max_new_tokens=2),
        Request(1, _prompt(cfg, 21, 6), max_new_tokens=4),
        # needs pages that only exist once request 0 and 1 retire
        Request(2, _prompt(cfg, 22, 20), max_new_tokens=8, arrival=1),
    ]
    outs, stats = eng.serve(reqs)
    assert set(outs) == {0, 1, 2}
    ref = ServeEngine(cfg, params, max_len=None, page_size=4).generate(
        jnp.asarray(reqs[2].tokens)[None], max_new_tokens=8
    )
    assert np.array_equal(outs[2].tokens, np.asarray(ref.tokens[0, 20:]))
    # peak residency stayed within the (tiny) pool
    assert stats.peak_resident_kv_bytes <= (16 - 1) * page_bytes(cfg, 4)


def test_continuous_validates(served_model):
    cfg, params = served_model
    with pytest.raises(ValueError, match="attention"):
        ContinuousBatchingEngine(reduce_config(get_arch("falcon-mamba-7b")), params)
    eng = ContinuousBatchingEngine(cfg, params, num_slots=1, page_size=4, num_pages=4)
    with pytest.raises(ValueError, match="pages"):
        eng.serve([Request(0, _prompt(cfg, 30, 30), max_new_tokens=30)])
    with pytest.raises(ValueError, match="duplicate"):
        eng.serve([
            Request(0, _prompt(cfg, 31, 4), max_new_tokens=2),
            Request(0, _prompt(cfg, 32, 4), max_new_tokens=2),
        ])
    outs, st = eng.serve([])
    assert outs == {} and st.decode_dispatches == 0


def test_continuous_sliding_window_prompt_longer_than_window(served_model):
    """SWA regression: prefill's ring-buffered cache must be un-permuted
    into the page chain, so prompts LONGER than the window stay pinned
    against the static engine (which serves the same ring buffer)."""
    cfg = reduce_config(get_arch("mixtral-8x22b"))
    assert cfg.sliding_window and cfg.sliding_window < 40
    params, _ = M.init_params(cfg, KEY)
    reqs = [
        Request(0, _prompt(cfg, 80, 40), max_new_tokens=6),  # prompt > window
        Request(1, _prompt(cfg, 81, 12), max_new_tokens=8),  # prompt < window
    ]
    outs, _ = ContinuousBatchingEngine(
        cfg, params, num_slots=2, page_size=8, num_pages=32
    ).serve(reqs)
    ref_eng = ServeEngine(cfg, params, max_len=None, page_size=8)
    for r in reqs:
        ref = ref_eng.generate(jnp.asarray(r.tokens)[None], max_new_tokens=r.max_new_tokens)
        assert np.array_equal(
            outs[r.rid].tokens, np.asarray(ref.tokens[0, len(r.tokens):])
        ), r.rid


def test_bucketed_chunked_prefill_greedy_parity(served_model):
    """The tentpole contract: admission through the bucketed planner —
    padded buckets, packed short prompts, chunked long prompts, mid-flight
    admissions into freed slots — changes NOTHING about greedy outputs.
    Every request matches a per-request ServeEngine run token-for-token,
    and after AOT warmup the whole run compiles zero programs at traffic
    time (|buckets| + chunk + decode, nothing else)."""
    cfg, params = served_model
    buckets = (8, 16)
    eng = ContinuousBatchingEngine(
        cfg, params, num_slots=2, page_size=4, num_pages=64,
        prefill_buckets=buckets, chunk_size=8, max_pack=2,
    )
    assert eng.warmup() == len(buckets) + 2
    reqs = [
        Request(0, _prompt(cfg, 60, 6), max_new_tokens=5),
        Request(1, _prompt(cfg, 61, 13), max_new_tokens=4),
        Request(2, _prompt(cfg, 62, 40), max_new_tokens=6),  # 5 chunks of 8
        Request(3, _prompt(cfg, 63, 3), max_new_tokens=5, arrival=2),  # mid-flight
        Request(4, _prompt(cfg, 64, 5), max_new_tokens=4),
    ]
    outs, stats = eng.serve(reqs)
    assert stats.admitted == 5 and stats.chunk_dispatches == 5
    cc = eng.compile_counts()
    assert cc["jit_fallback"] == 0 and cc["aot"] == len(buckets) + 2
    ref = ServeEngine(cfg, params, max_len=None, page_size=4)
    for r in reqs:
        res = ref.generate(jnp.asarray(r.tokens)[None], max_new_tokens=r.max_new_tokens)
        assert np.array_equal(
            outs[r.rid].tokens, np.asarray(res.tokens[0, len(r.tokens):])
        ), r.rid
        assert outs[r.rid].queue_wait_steps >= 0
        assert np.isfinite(outs[r.rid].ttft_wall_s)


def test_swa_prompt_spanning_chunk_boundary():
    """Chunked prefill must reproduce the sliding-window math exactly when a
    prompt longer than the window streams in across chunk boundaries (each
    chunk re-reads the paged prefix, including tokens the window has slid
    past). Dense FFN + window: capacity-MoE routing is dispatch-width-
    dependent by construction (``moe_block`` computes expert capacity per
    dispatch), so chunk-vs-one-shot bit-parity is only defined for dense
    families — see serve/README.md."""
    from dataclasses import replace

    cfg = replace(reduce_config(get_arch("smollm-135m")), sliding_window=16)
    params, _ = M.init_params(cfg, KEY)
    eng = ContinuousBatchingEngine(
        cfg, params, num_slots=2, page_size=8, num_pages=32,
        prefill_buckets=(8, 16), chunk_size=8,
    )
    reqs = [
        Request(0, _prompt(cfg, 82, 40), max_new_tokens=6),  # prompt > window
        Request(1, _prompt(cfg, 83, 12), max_new_tokens=8),
    ]
    outs, stats = eng.serve(reqs)
    assert stats.chunk_dispatches == 5  # the 40-token prompt, 8 at a time
    ref_eng = ServeEngine(cfg, params, max_len=None, page_size=8)
    for r in reqs:
        ref = ref_eng.generate(jnp.asarray(r.tokens)[None], max_new_tokens=r.max_new_tokens)
        assert np.array_equal(
            outs[r.rid].tokens, np.asarray(ref.tokens[0, len(r.tokens):])
        ), r.rid


def test_packed_admission_burst(served_model):
    """A burst of short prompts arriving together shares bucket dispatches
    (segment-masked packing) instead of serializing one prefill each — and
    still matches per-request ServeEngine outputs."""
    cfg, params = served_model
    eng = ContinuousBatchingEngine(
        cfg, params, num_slots=4, page_size=4, num_pages=64,
        prefill_buckets=(16, 32), max_pack=4,
    )
    reqs = [Request(i, _prompt(cfg, 70 + i, 3 + i), max_new_tokens=4) for i in range(4)]
    outs, stats = eng.serve(reqs)
    assert stats.admitted == 4
    assert stats.prefill_dispatches < 4  # the burst actually packed
    ref = ServeEngine(cfg, params, max_len=None, page_size=4)
    for r in reqs:
        res = ref.generate(jnp.asarray(r.tokens)[None], max_new_tokens=r.max_new_tokens)
        assert np.array_equal(
            outs[r.rid].tokens, np.asarray(res.tokens[0, len(r.tokens):])
        ), r.rid


def test_serve_engine_bucketed_prefill_program_count(served_model):
    """Distinct prompt lengths within one ladder rung share ONE compiled
    prefill program (the RCP001:serve.prefill:prompt_len fix)."""
    cfg, params = served_model
    eng = ServeEngine(cfg, params, max_len=64)
    for plen in (3, 5, 9, 20):
        eng.generate(jnp.asarray(_prompt(cfg, 95 + plen, plen))[None], max_new_tokens=2)
    assert eng._prefill_len._cache_size() == 1


def test_continuous_eos_on_last_budgeted_token_reports_eos(served_model):
    """A request whose final budgeted token IS the EOS retires via the EOS
    check on the device — finish_reason must say so."""
    cfg, params = served_model
    prompt = _prompt(cfg, 90, 8)
    eng = ContinuousBatchingEngine(cfg, params, num_slots=1, page_size=4)
    plain, _ = eng.serve([Request(0, prompt, max_new_tokens=6)])
    eos = int(plain[0].tokens[-1])  # budget ends exactly on this token
    out, _ = eng.serve([Request(0, prompt, max_new_tokens=6)], eos_id=eos)
    first = int(np.nonzero(plain[0].tokens == eos)[0][0])
    assert out[0].finish_reason == "eos"
    assert np.array_equal(out[0].tokens, plain[0].tokens[: first + 1])


def test_continuous_faulty_chip_differs(served_model):
    cfg, params = served_model
    req = [Request(0, _prompt(cfg, 40, 8), max_new_tokens=8)]
    ctx = from_fault_map(random_fault_map(1, cfg.array_rows, cfg.array_cols, 0.3))
    healthy_out, _ = ContinuousBatchingEngine(cfg, params, healthy(), num_slots=1).serve(req)
    faulty_out, _ = ContinuousBatchingEngine(cfg, params, ctx, num_slots=1).serve(req)
    assert not np.array_equal(healthy_out[0].tokens, faulty_out[0].tokens)


def _mixed_stream(cfg, late: int):
    """Packed, bucketed and chunked prompts, at clock 0 and at ``late``."""
    return [
        Request(0, _prompt(cfg, 110, 6), max_new_tokens=3),
        Request(1, _prompt(cfg, 111, 5), max_new_tokens=4),
        Request(2, _prompt(cfg, 112, 13), max_new_tokens=7),
        Request(3, _prompt(cfg, 113, 4), max_new_tokens=5, arrival=late),
        Request(4, _prompt(cfg, 114, 3), max_new_tokens=4, arrival=late),
        Request(5, _prompt(cfg, 115, 30), max_new_tokens=4, arrival=late),
    ]


def _premask_engine(cfg, params, ctx):
    return ContinuousBatchingEngine(
        cfg, params, ctx, num_slots=3, page_size=4, num_pages=64,
        prefill_buckets=(8, 16), chunk_size=8, max_pack=2,
    )


@pytest.mark.parametrize("switch_at", [None, 4], ids=["at_build", "on_set_silicon"])
def test_premasked_engine_serves_the_per_use_masked_tokens(served_model, switch_at):
    """A faulty engine masks its frozen weights once — at build, and again
    on a mid-serve set_silicon — and serves what masking inside every GEMM
    serves: each request admitted under a map, packed, bucketed or chunked,
    matches a per-use masked ServeEngine run under that map, and the weights
    a swap leaves are those a fresh engine built with the new map serves."""
    cfg, params = served_model
    R, C = cfg.array_rows, cfg.array_cols
    old = from_fault_map(random_fault_map(5, R, C, 0.2))
    new = from_fault_map(random_fault_map(6, R, C, 0.3))
    eng = _premask_engine(cfg, params, old)
    eng.warmup()

    def on_step(clock):
        if clock == switch_at:
            eng.set_silicon(new)

    reqs = _mixed_stream(cfg, late=4)
    outs, stats = eng.serve(reqs, on_step=on_step)
    assert stats.chunk_dispatches and stats.prefill_dispatches > stats.chunk_dispatches + 1
    assert eng.compile_counts()["jit_fallback"] == 0
    refs = {id(old): ServeEngine(cfg, params, old, max_len=None, page_size=4),
            id(new): ServeEngine(cfg, params, new, max_len=None, page_size=4)}
    under = {}
    for r in reqs:
        o = outs[r.rid]
        if switch_at is None or o.finished_step <= switch_at:
            under[r.rid] = old
        elif o.admitted_step >= switch_at:
            under[r.rid] = new
    # on a swap: 0 and 1 finish under the old map, 2 spans both, and the
    # late arrivals (a pack of 3 and 4, then 5 in chunks) run under the new
    assert {rid for rid, c in under.items() if c is new} == (
        set() if switch_at is None else {3, 4, 5})
    assert len(under) == (6 if switch_at is None else 5)
    for rid, ctx in under.items():
        r = reqs[rid]
        res = refs[id(ctx)].generate(jnp.asarray(r.tokens)[None], max_new_tokens=r.max_new_tokens)
        assert np.array_equal(outs[rid].tokens, np.asarray(res.tokens[0, len(r.tokens):])), rid
        np.testing.assert_allclose(outs[rid].logprobs, np.asarray(res.logprobs[0]),
                                   rtol=0, atol=1e-5)
    if switch_at is not None:
        fresh = _premask_engine(cfg, params, new).served_params
        jax.tree_util.tree_map(np.testing.assert_array_equal, eng.served_params, fresh)


def test_a_healthy_engine_serves_its_params_unmasked(served_model):
    """No fault context, no premask: the served params are the params the
    engine was given, and its premask program is never compiled."""
    cfg, params = served_model
    eng = _premask_engine(cfg, params, None)
    assert eng.served_params is params
    assert eng._premask._cache_size() == 0
    outs, _ = eng.serve(_mixed_stream(cfg, late=2))
    assert len(outs) == 6 and eng._premask._cache_size() == 0


# ---------------------------------------------------------------------------
# EOS semantics: static (masked) and continuous (retiring) engines agree
# ---------------------------------------------------------------------------


def test_static_eos_masks_finished_sequences(served_model):
    cfg, params = served_model
    eng = ServeEngine(cfg, params, max_len=64)
    prompts = jnp.stack([jnp.asarray(_prompt(cfg, 50 + b, 8)) for b in range(2)])
    plain = eng.generate(prompts, max_new_tokens=10)
    gen = np.asarray(plain.tokens[:, 8:])
    eos = int(gen[0, 3])  # force an early EOS for sequence 0
    out = eng.generate(prompts, max_new_tokens=10, eos_id=eos)
    got = np.asarray(out.tokens[:, 8:])
    lps = np.asarray(out.logprobs)
    for b in range(2):
        hits = np.nonzero(gen[b] == eos)[0]
        cut = int(hits[0]) if hits.size else gen.shape[1] - 1
        # identical up to and including the EOS token...
        assert np.array_equal(got[b, : cut + 1], gen[b, : cut + 1])
        # ...then pad emission with logprob exactly 0
        assert np.all(got[b, cut + 1 :] == eng.pad_id)
        assert np.all(lps[b, cut + 1 :] == 0.0)
    assert np.any(got[0, 4:] != gen[0, 4:]) or gen.shape[1] == 5


def test_static_and_continuous_agree_on_eos(served_model):
    cfg, params = served_model
    prompt = _prompt(cfg, 60, 8)
    plain = ServeEngine(cfg, params, max_len=64).generate(
        jnp.asarray(prompt)[None], max_new_tokens=12
    )
    gen = np.asarray(plain.tokens[0, 8:])
    eos = int(gen[5])
    static = ServeEngine(cfg, params, max_len=64).generate(
        jnp.asarray(prompt)[None], max_new_tokens=12, eos_id=eos
    )
    cont, _ = ContinuousBatchingEngine(cfg, params, num_slots=1, page_size=4).serve(
        [Request(0, prompt, max_new_tokens=12)], eos_id=eos
    )
    out = cont[0]
    assert out.finish_reason == "eos"
    cut = int(np.nonzero(gen == eos)[0][0])
    # continuous stops AT the EOS; static pads past it — same tokens before
    assert np.array_equal(out.tokens, np.asarray(static.tokens[0, 8 : 8 + cut + 1]))
    static_tail = np.asarray(static.tokens[0, 8 + cut + 1 :])
    assert np.all(static_tail == 0)


# ---------------------------------------------------------------------------
# ServeEngine explicit KV capacity (max_len=None)
# ---------------------------------------------------------------------------


def test_serve_engine_derives_cache_len(served_model):
    cfg, params = served_model
    eng = ServeEngine(cfg, params, max_len=None, page_size=8)
    assert eng.cache_len_for(6, 5) == 32  # 11 tokens -> bottom ladder rung
    assert eng.cache_len_for(8, 8) == 32
    assert eng.cache_len_for(200, 100) == 512  # past the top bucket: doubled rung
    unbucketed = ServeEngine(cfg, params, max_len=None, page_size=8,
                             prefill_buckets=None)
    assert unbucketed.cache_len_for(6, 5) == 16  # 11 tokens -> 2 pages
    fixed = ServeEngine(cfg, params, max_len=48)
    assert fixed.cache_len_for(6, 5) == 48
    prompts = jnp.stack([jnp.asarray(_prompt(cfg, 70 + b, 6)) for b in range(2)])
    a = eng.generate(prompts, max_new_tokens=5)
    b = fixed.generate(prompts, max_new_tokens=5)
    assert np.array_equal(np.asarray(a.tokens), np.asarray(b.tokens))


# ---------------------------------------------------------------------------
# ShardedFleetServeEngine: ragged per-chip streams
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fleet(served_model):
    cfg, _ = served_model
    chips = []
    for i, rate in enumerate((0.0, 0.25, 0.4, 0.1)):
        params, _ = M.init_params(cfg, jax.random.PRNGKey(i))
        ctx = (
            healthy()
            if rate == 0.0
            else from_fault_map(random_fault_map(i, cfg.array_rows, cfg.array_cols, rate))
        )
        chips.append((params, ctx))
    streams = []
    for c in range(len(chips)):
        streams.append([
            Request(0, _prompt(cfg, 100 * c, 5 + c), max_new_tokens=3 + c),
            Request(1, _prompt(cfg, 100 * c + 1, 7), max_new_tokens=9 - c),
            Request(2, _prompt(cfg, 100 * c + 2, 4), max_new_tokens=5, arrival=2 + c),
        ])
    return cfg, chips, streams


def test_fleet_sharded_serve_pinned_per_chip(fleet):
    cfg, chips, streams = fleet
    eng = ShardedFleetServeEngine(
        cfg, [p for p, _ in chips], [c for _, c in chips],
        num_slots=2, page_size=4, num_pages=32,
    )
    outs, stats = eng.serve(streams)
    assert stats.decode_dispatches > 0
    for c, (params, ctx) in enumerate(chips):
        ref, _ = ContinuousBatchingEngine(
            cfg, params, ctx, num_slots=2, page_size=4, num_pages=32
        ).serve(streams[c])
        assert set(outs[c]) == set(ref)
        for rid in ref:
            assert np.array_equal(outs[c][rid].tokens, ref[rid].tokens), (c, rid)
            np.testing.assert_allclose(
                outs[c][rid].logprobs, ref[rid].logprobs, rtol=1e-4, atol=1e-4
            )
    # ragged streams: chips retire independently — the fused dispatch count
    # is bounded by the busiest chip, not the fleet-wide sum
    assert stats.decode_dispatches < sum(
        r.max_new_tokens for s in streams for r in s
    )


def test_fleet_temperature_keys_reproducible_and_independent(fleet):
    """Same fleet key -> identical tokens across runs; different chips (same
    params, same stream) -> different samples (per-chip key streams)."""
    cfg, chips, _ = fleet
    params0 = chips[0][0]
    stream = [
        Request(0, _prompt(cfg, 300, 6), max_new_tokens=8),
        Request(1, _prompt(cfg, 301, 6), max_new_tokens=8),
    ]
    eng = ShardedFleetServeEngine(
        cfg, [params0, params0], None, num_slots=2, page_size=4, num_pages=32
    )
    k = jax.random.PRNGKey(11)
    o1, _ = eng.serve([stream, stream], temperature=1.0, key=k)
    o2, _ = eng.serve([stream, stream], temperature=1.0, key=k)
    for c in range(2):
        for rid in o1[c]:
            assert np.array_equal(o1[c][rid].tokens, o2[c][rid].tokens)
    # identical chips + identical streams, but independent per-chip keys
    assert any(
        not np.array_equal(o1[0][rid].tokens, o1[1][rid].tokens) for rid in o1[0]
    )
    o3, _ = eng.serve([stream, stream], temperature=1.0, key=jax.random.PRNGKey(12))
    assert any(
        not np.array_equal(o1[0][rid].tokens, o3[0][rid].tokens) for rid in o1[0]
    )


def test_fleet_sharded_serve_validates(fleet):
    cfg, chips, streams = fleet
    with pytest.raises(ValueError, match="at least one"):
        ShardedFleetServeEngine(cfg, [])
    with pytest.raises(ValueError, match="fault contexts"):
        ShardedFleetServeEngine(cfg, [chips[0][0]], [healthy(), healthy()])
    eng = ShardedFleetServeEngine(cfg, [p for p, _ in chips[:2]], num_slots=1)
    with pytest.raises(ValueError, match="streams"):
        eng.serve([streams[0]])
