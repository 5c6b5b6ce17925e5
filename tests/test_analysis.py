"""Golden-violation + clean-stack tests for the static program linter
(``repro.analysis``): each pass must fire the right finding code on a
deliberately broken program and stay silent on the shipped entry points."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (
    EntryTraceModel,
    FakeMesh,
    KernelLaunch,
    ProgramSpec,
    ShardingEntry,
    TraceRequest,
    analyze_stack,
    check_launch,
    default_baseline_path,
    lint_donation,
    lint_recompile,
    lint_sharding,
    load_baseline,
    synthetic_trace,
)
from repro.analysis.kernelgeom import (
    decode_attention_launch,
    flash_attention_launch,
    mamba_scan_launch,
    masked_matmul_launch,
)
from repro.analysis.recompile import census
from repro.configs import get_arch, reduce_config
from repro.core.masking import FaultContext
from repro.launch.sharding import MeshContext


def _codes(findings):
    return [f.code for f in findings]


# ---------------------------------------------------------------------------
# donation pass (DON001)
# ---------------------------------------------------------------------------


def _loop_spec(*, donate: bool) -> ProgramSpec:
    """A tiny serve-loop shape: a big carried buffer + a small accumulator."""
    donate_argnums = (0,) if donate else ()
    fn = jax.jit(
        lambda buf, acc: (buf + 1.0, acc + buf.sum()),
        donate_argnums=donate_argnums,
    )
    return ProgramSpec(
        name="golden.loop",
        fn=fn,
        args=(
            jax.ShapeDtypeStruct((256, 256), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.float32),
        ),
        carried=frozenset({0}),
        arg_names=("buf", "acc"),
    )


def test_donation_flags_undonated_loop_buffer():
    findings, stats = lint_donation(_loop_spec(donate=False))
    assert _codes(findings) == ["DON001"]
    f = findings[0]
    assert f.subject == "buf"
    assert f.bytes == 256 * 256 * 4
    assert stats["hlo_alias_table"]  # verified against the compiled module
    assert stats["donated_fraction"] < 1.0


def test_donation_clean_after_donating():
    findings, stats = lint_donation(_loop_spec(donate=True))
    assert findings == []
    assert stats["donated_fraction"] == 1.0
    # the aliasing is real, not just a jit-level flag: the optimized HLO
    # module's own input_output_alias table covers the carried buffer
    assert stats["hlo_alias_table"]
    assert stats["aliased_params"] >= 1


def test_donation_fix_measurably_reduces_undonated_bytes():
    """The shipped fused decode donates its KV cache; stripping the
    donation (the pre-fix engine) must regress the analyzer report."""
    from repro.launch.specs import cache_struct, param_struct
    from repro.serve.engine import ServeEngine, make_sample_decode

    cfg = reduce_config(get_arch("smollm-135m"))
    eng = ServeEngine(cfg, None, max_len=64)
    params_s, _ = param_struct(cfg)
    cache_s = cache_struct(cfg, 2, 64)
    args = (
        params_s,
        jax.ShapeDtypeStruct((2, cfg.vocab_size), jnp.float32),
        cache_s,
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        FaultContext(ok=None, mode="none"),
        jax.ShapeDtypeStruct((), jnp.float32),
    )
    carried = frozenset({1, 2, 3})

    def spec(fn, name):
        return ProgramSpec(name=name, fn=fn, args=args, carried=carried)

    # reduced-config KV leaves are ~32 KiB: lint at the analyze_stack default
    min_bytes = 1 << 14
    pre_fix = jax.jit(make_sample_decode(cfg, pad_id=0))  # no donate_argnums
    f_pre, s_pre = lint_donation(spec(pre_fix, "prefix.sample_decode"),
                                 min_bytes=min_bytes)
    f_now, s_now = lint_donation(spec(eng._sample_decode, "serve.sample_decode"),
                                 min_bytes=min_bytes)

    assert "DON001" in _codes(f_pre)
    cache_bytes = sum(
        int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
        for leaf in jax.tree_util.tree_leaves(cache_s)
    )
    assert s_pre["undonated_carried_bytes"] >= cache_bytes
    assert f_now == []
    assert s_now["undonated_carried_bytes"] == 0
    assert s_now["donated_fraction"] == 1.0


# ---------------------------------------------------------------------------
# recompile pass (RCP001/RCP002)
# ---------------------------------------------------------------------------


def test_recompile_flags_length_polymorphic_jit():
    raw = EntryTraceModel(
        "golden.raw_prefill",
        lambda r: ("prefill", r.prompt_len),
        dims=("prompt_len",),
    )
    findings, stats = lint_recompile([raw], synthetic_trace())
    assert "RCP001" in _codes(findings)
    assert findings[0].subject == "prompt_len"
    # the mixed-length trace alone also blows the signature budget
    assert "RCP002" in _codes(findings)


def test_recompile_clean_when_bucketed():
    bucketed = EntryTraceModel(
        "golden.bucketed_prefill",
        lambda r: ("prefill", 64 * -(-r.prompt_len // 64)),
        dims=("prompt_len",),
    )
    findings, stats = lint_recompile([bucketed], synthetic_trace())
    assert findings == []
    assert stats["golden.bucketed_prefill"]["sweep_prompt_len"] < 12


def test_signature_function_matches_real_jit_cache():
    """The analytic census must agree with jax's own compile cache: one
    compile per distinct tokens length, repeats are cache hits."""
    fn = jax.jit(lambda t: t.sum())
    lens = [4, 8, 8, 12, 4, 16]
    for n in lens:
        fn(jnp.zeros((n,), jnp.int32))
    model = EntryTraceModel(
        "golden.cache", lambda r: (r.prompt_len,), dims=("prompt_len",)
    )
    trace = [TraceRequest(prompt_len=n) for n in lens]
    assert census(model, trace)["signatures"] == fn._cache_size()


# ---------------------------------------------------------------------------
# sharding pass (SHD001/SHD002)
# ---------------------------------------------------------------------------


def _entry(rules, axes_leaf, shape, *, reserved=(), engine_axes=(), units=None):
    mctx = MeshContext(
        mesh=FakeMesh.of(pop=2, model=4),
        rules=rules,
        units=units or {},
        reserved_axes=reserved,
    )
    return ShardingEntry(
        name="golden.shard",
        mctx=mctx,
        axes={"w": axes_leaf},
        structs={"w": jax.ShapeDtypeStruct(shape, jnp.float32)},
        engine_axes=engine_axes,
    )


def test_sharding_flags_lost_replication():
    # "model"=4 exists and is live for "qkv", but 1002 % 4 != 0: the rule
    # engine silently replicates 4 MiB — exactly what SHD001 is for
    entry = _entry({"qkv": ("model",)}, ("embed", "qkv"), (1024, 1002))
    findings, stats = lint_sharding([entry])
    assert _codes(findings) == ["SHD001"]
    assert findings[0].subject == "w"
    assert stats["golden.shard"]["replicated"] == 1


def test_sharding_replication_by_design_is_clean():
    # no rule at all for the leaf's axes -> replication is intentional
    entry = _entry({}, ("embed", "qkv"), (1024, 1002))
    findings, _ = lint_sharding([entry])
    assert findings == []


def test_sharding_small_replicated_leaf_below_threshold_is_clean():
    entry = _entry({"qkv": ("model",)}, ("embed", "qkv"), (16, 10))
    findings, _ = lint_sharding([entry])
    assert findings == []


def test_sharding_flags_engine_owned_axis_use():
    # a rule that grabs the fleet's "pop" axis inside a shard_map lane
    entry = _entry(
        {"member": ("pop",)}, ("member", None), (8, 4), engine_axes=("pop",)
    )
    findings, _ = lint_sharding([entry])
    assert _codes(findings) == ["SHD002"]


def test_sharding_reserved_axis_resolves_clean():
    # same rules, but the entry declares "pop" reserved the way
    # fleet/serve.py builds its MeshContext: resolution skips the axis
    entry = _entry(
        {"member": ("pop",)}, ("member", None), (8, 4),
        reserved=("pop",), engine_axes=("pop",),
    )
    findings, _ = lint_sharding([entry])
    assert findings == []


# ---------------------------------------------------------------------------
# kernel geometry pass (KRN001-KRN004)
# ---------------------------------------------------------------------------


def test_kernel_flags_non_dividing_block():
    bad = KernelLaunch(
        kernel="golden.matmul",
        dims=(100, 64),
        blocks=(33, 64),
        vmem_blocks=(((33, 64), jnp.float32),),
    )
    findings = check_launch(bad)
    assert _codes(findings) == ["KRN001"]
    assert findings[0].subject == "axis0"


def test_kernel_flags_mask_period_incompatibility():
    bad = KernelLaunch(
        kernel="golden.masked",
        dims=(96,),
        blocks=(48,),
        vmem_blocks=(((48, 48), jnp.float32),),
        mask_blocks=((48, 32),),  # 48 not a multiple of period 32
    )
    assert _codes(check_launch(bad)) == ["KRN001"]


@pytest.mark.parametrize("paged", [False, True])
def test_kernel_flags_old_decode_scale_blocks(paged):
    """The int8 decode kernels once carried their scales as (1, bkv) rows of
    a (B*Hkv, S) array (dense) and (1, 1, page) rows of an (Hkv, P, page)
    pool (paged). Mosaic refused both: a second-last block dim of 1 is
    neither a multiple of 8 nor the array's own."""
    if paged:
        scale_block, scale_arr = (1, 1, 16), (3, 512, 16)
        kv_block, kv_arr = (1, 1, 16, 64), (3, 512, 16, 64)
    else:
        scale_block, scale_arr = (1, 128), (12, 2048)
        kv_block, kv_arr = (1, 128, 64), (12, 2048, 64)
    bad = KernelLaunch(
        kernel="golden.decode_scales",
        dims=(36, 8, 2048),
        blocks=(1, 8, 128),
        vmem_blocks=(
            ((1, 8, 64), jnp.float32, True, (36, 8, 64)),  # q block
            (kv_block, jnp.int8, True, kv_arr),  # k block
            (scale_block, jnp.float32, True, scale_arr),  # k scales
        ),
    )
    findings = check_launch(bad)
    assert _codes(findings) == ["KRN001"]
    assert findings[0].subject == "block2"
    assert "(8, 128)" in findings[0].message


def test_kernel_flags_vmem_overflow():
    bad = KernelLaunch(
        kernel="golden.fat",
        dims=(4096,),
        blocks=(4096,),
        vmem_blocks=(((4096, 4096), jnp.float32),),  # 64 MiB resident
    )
    findings = check_launch(bad)
    assert _codes(findings) == ["KRN002"]
    assert findings[0].bytes == 4096 * 4096 * 4


def test_kernel_flags_degenerate_grid():
    bad = KernelLaunch(
        kernel="golden.zero",
        dims=(128,),
        blocks=(0,),
        vmem_blocks=(),
    )
    assert _codes(check_launch(bad)) == ["KRN003"]


def test_kernel_flags_batched_context_leak():
    cfg = reduce_config(get_arch("smollm-135m"))
    pop_ctx = FaultContext(
        ok=jax.ShapeDtypeStruct((4, cfg.array_rows, cfg.array_cols), jnp.float32),
        mode="fap",
    )
    launch = masked_matmul_launch(
        256, cfg.d_model, cfg.d_ff, (cfg.array_rows, cfg.array_cols), ctx=pop_ctx
    )
    assert "KRN004" in _codes(check_launch(launch))


def test_kernel_builders_clean_at_stack_shapes():
    cfg = get_arch("smollm-135m")
    mask = (cfg.array_rows, cfg.array_cols)
    chip = FaultContext(
        ok=jax.ShapeDtypeStruct(mask, jnp.float32), mode="pallas"
    )
    launches = [
        masked_matmul_launch(2048, cfg.d_model, cfg.d_ff, mask, ctx=chip),
        flash_attention_launch(8, cfg.num_heads, cfg.num_kv_heads, 2048, 2048,
                               cfg.resolved_head_dim),
        decode_attention_launch(8, cfg.num_heads, cfg.num_kv_heads, 4096,
                                cfg.resolved_head_dim),
        decode_attention_launch(4, cfg.num_heads, cfg.num_kv_heads, 4096,
                                cfg.resolved_head_dim, paged=True, page_size=8),
        decode_attention_launch(4, cfg.num_heads, cfg.num_kv_heads, 2048,
                                cfg.resolved_head_dim, paged=True, page_size=16),
        mamba_scan_launch(1, 512, 8192, 16, dtype=jnp.bfloat16),
    ]
    for launch in launches:
        assert check_launch(launch) == [], launch.kernel


# ---------------------------------------------------------------------------
# the shipped stack, end to end
# ---------------------------------------------------------------------------


def test_shipped_stack_cheap_passes_have_only_baselined_findings():
    report = analyze_stack(passes=("recompile", "sharding", "kernels"))
    baseline = load_baseline(default_baseline_path())
    new = report.new_vs_baseline(baseline)
    assert new == [], [f.key for f in new]
    # bucketed prefill closed the recompile hazards (ROADMAP item 1): the
    # census must stay clean — a prompt-length-shaped signature reappearing
    # here is a regression, not a baselining candidate ...
    assert not [k for k in report.keys() if k.startswith("RCP")]
    # ... and every kernel launch is geometrically clean
    assert not [f for f in report.findings if f.code.startswith("KRN")]


def test_shipped_stack_donation_pass_is_fully_donated():
    report = analyze_stack(passes=("donation",))
    assert [f for f in report.findings if f.code == "DON001"] == []
    stats = report.passes["donation"]
    assert stats["donated_fraction"] == 1.0
    for name, entry in stats["entries"].items():
        assert entry["hlo_alias_table"], name
        assert entry["undonated_carried_bytes"] == 0, name
    # the population sweep must NOT donate (params0 is reused by the caller)
    assert stats["entries"]["population.fit_run"]["carried_bytes"] == 0


# ---------------------------------------------------------------------------
# donation regressions: token streams are unchanged under donate_argnums
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small_setup():
    from repro.models import model as M

    cfg = reduce_config(get_arch("smollm-135m"))
    params, _ = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_serve_engine_donated_tokens_match_undonated_reference(small_setup):
    from repro.models import model as M
    from repro.serve.engine import ServeEngine, make_sample_decode

    cfg, params = small_setup
    eng = ServeEngine(cfg, params, max_len=32)
    prompts = jax.random.randint(jax.random.PRNGKey(7), (2, 8), 0, cfg.vocab_size)
    out = eng.generate(prompts, max_new_tokens=8)

    # reference loop with NO donation anywhere
    ref_step = jax.jit(make_sample_decode(cfg, pad_id=0))
    logits, cache = jax.jit(
        lambda p, b, ctx: M.prefill(p, b, cfg, ctx, cache_len=32)
    )(params, {"tokens": prompts}, eng.ctx)
    cur, key = logits, jax.random.PRNGKey(0)
    toks, lps = [], []
    for _ in range(8):
        nxt, tok_lp, cur, cache, key = ref_step(
            params, cur, cache, key, eng.ctx, jnp.float32(0.0)
        )
        toks.append(np.asarray(nxt))
        lps.append(np.asarray(tok_lp))
    np.testing.assert_array_equal(
        np.asarray(out.tokens[:, 8:]), np.stack(toks, axis=1)
    )
    np.testing.assert_allclose(
        np.asarray(out.logprobs), np.stack(lps, axis=1), rtol=1e-6, atol=1e-6
    )


def test_continuous_engine_donated_tokens_match_undonated_reference(small_setup):
    from repro.serve.continuous import ContinuousBatchingEngine, Request
    from repro.serve.engine import make_sample_decode

    cfg, params = small_setup
    kw = dict(num_slots=2, page_size=8, num_pages=16, max_pages_per_seq=4)
    reqs = [
        Request(0, np.arange(5) % cfg.vocab_size, max_new_tokens=6),
        Request(1, (np.arange(9) * 3) % cfg.vocab_size, max_new_tokens=4),
        Request(2, (np.arange(7) * 5) % cfg.vocab_size, max_new_tokens=8, arrival=2),
    ]

    eng = ContinuousBatchingEngine(cfg, params, **kw)
    outs, _ = eng.serve(reqs)

    ref = ContinuousBatchingEngine(cfg, params, **kw)
    ref._sample_decode = jax.jit(make_sample_decode(cfg, pad_id=0))
    ref._packed_admit = jax.jit(ref._packed_admit_fn)
    ref._prefill_chunk = jax.jit(ref._prefill_chunk_fn)
    ref_outs, _ = ref.serve(reqs)

    assert set(outs) == set(ref_outs) == {0, 1, 2}
    for rid in outs:
        np.testing.assert_array_equal(outs[rid].tokens, ref_outs[rid].tokens)
        np.testing.assert_allclose(
            outs[rid].logprobs, ref_outs[rid].logprobs, rtol=1e-6, atol=1e-6
        )
