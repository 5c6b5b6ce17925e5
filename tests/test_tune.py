"""Tests for the kernel autotuner stack (repro.tune): cache key
canonicalization, corrupt/stale cache degradation, the tuned_block seam's
resolution order and bitwise empty-cache identity, lint gating (rejected
candidates never reach pallas_call), the hillclimb search, an end-to-end
interpret-mode tune, and the capacity planner's kernel-VMEM reserve.

``hypothesis`` is optional (same contract as tests/test_core.py): without
it only the key round-trip property test skips."""
import dataclasses
import json

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

from repro.tune import cache as tc
from repro.tune.cache import (
    CACHE_VERSION,
    TuningCache,
    cache_key,
    parse_key,
    set_tuning_cache,
)
from repro.tune.search import hillclimb, lattice_neighbors, pow2_lattice
from repro.tune.tuner import (
    HEURISTIC_BLOCKS,
    KERNELS,
    lint_candidate,
    normalize_blocks,
    tune_kernel,
    tune_many,
)


@pytest.fixture
def isolated_cache():
    """Run a test against an empty process-wide cache; restore after."""
    prev = set_tuning_cache(TuningCache())
    try:
        yield tc.get_tuning_cache()
    finally:
        set_tuning_cache(prev)


# ---------------------------------------------------------------------------
# cache keys
# ---------------------------------------------------------------------------


def test_cache_key_canonicalizes_shape_order():
    a = cache_key("masked_matmul", dict(m=64, k=32, n=16), "float32", "interpret")
    b = cache_key("masked_matmul", dict(n=16, m=64, k=32), "float32", "interpret")
    assert a == b == "masked_matmul|k=32,m=64,n=16|float32|interpret"


def test_cache_key_round_trip_unit():
    key = cache_key("flash_attention", dict(b=2, sq=128, causal=1), "bfloat16", "tpu")
    kernel, shape, dtype, backend = parse_key(key)
    assert kernel == "flash_attention"
    assert shape == dict(b=2, sq=128, causal=1)
    assert (dtype, backend) == ("bfloat16", "tpu")
    assert cache_key(kernel, shape, dtype, backend) == key


def test_cache_key_rejects_bad_kernel_names():
    with pytest.raises(ValueError):
        cache_key("", dict(m=1), "float32", "cpu")
    with pytest.raises(ValueError):
        cache_key("a|b", dict(m=1), "float32", "cpu")


@settings(max_examples=100, deadline=None)
@given(
    kernel=st.sampled_from(sorted(KERNELS)),
    shape=st.dictionaries(
        st.sampled_from(["b", "m", "k", "n", "sq", "skv", "d", "l", "causal"]),
        st.integers(min_value=0, max_value=1 << 20),
        min_size=1,
        max_size=6,
    ),
    dtype=st.sampled_from(["float32", "bfloat16", "float16"]),
    backend=st.sampled_from(["interpret", "tpu", "cpu"]),
)
def test_cache_key_round_trip_property(kernel, shape, dtype, backend):
    key = cache_key(kernel, shape, dtype, backend)
    assert parse_key(key) == (kernel, shape, dtype, backend)


# ---------------------------------------------------------------------------
# cache persistence: corrupt / stale / malformed files degrade, never raise
# ---------------------------------------------------------------------------


def test_cache_save_load_round_trip(tmp_path):
    path = str(tmp_path / "cache.json")
    cache = TuningCache()
    key = cache_key("mamba_scan", dict(b=1, l=64, d=16, n=4), "float32", "interpret")
    cache.put(key, dict(blocks=dict(bd=16, bl=32), vmem_bytes=1234))
    cache.save(path)
    loaded = TuningCache.load(path)
    assert loaded.entries == cache.entries
    assert loaded.source == path
    assert loaded.lookup_blocks(
        "mamba_scan", dict(b=1, l=64, d=16, n=4), "float32", "interpret"
    ) == dict(bd=16, bl=32)


def test_cache_load_missing_file_is_silently_empty(tmp_path, recwarn):
    cache = TuningCache.load(str(tmp_path / "nope.json"))
    assert len(cache) == 0
    assert len(recwarn) == 0


def test_cache_load_corrupt_json_warns_and_falls_back(tmp_path):
    path = tmp_path / "corrupt.json"
    path.write_text("{this is not json")
    with pytest.warns(UserWarning, match="unreadable"):
        cache = TuningCache.load(str(path))
    assert len(cache) == 0


def test_cache_load_stale_version_warns_and_falls_back(tmp_path):
    path = tmp_path / "stale.json"
    path.write_text(json.dumps({"version": CACHE_VERSION + 1, "entries": {
        "masked_matmul|m=8|float32|cpu": {"blocks": {"bm": 8}},
    }}))
    with pytest.warns(UserWarning, match="version"):
        cache = TuningCache.load(str(path))
    assert len(cache) == 0


def test_cache_load_drops_malformed_entries_keeps_good(tmp_path):
    good_key = cache_key("masked_matmul", dict(m=8, k=8, n=8, r=4, c=4), "float32", "cpu")
    path = tmp_path / "mixed.json"
    path.write_text(json.dumps({"version": CACHE_VERSION, "entries": {
        good_key: {"blocks": {"bm": 8}},
        "not-a-canonical-key": {"blocks": {"bm": 8}},
        "too|few|parts": {"blocks": {"bm": 8}},
    }}))
    with pytest.warns(UserWarning, match="malformed"):
        cache = TuningCache.load(str(path))
    assert list(cache.entries) == [good_key]


def test_cache_merge_other_wins():
    key = cache_key("decode_attention", dict(b=1, skv=64), "float32", "cpu")
    base = TuningCache(entries={key: dict(blocks=dict(bkv=32))})
    over = TuningCache(entries={key: dict(blocks=dict(bkv=64))})
    assert base.merge(over).entries[key]["blocks"] == dict(bkv=64)
    assert over.merge(base).entries[key]["blocks"] == dict(bkv=32)


def test_env_overlay_wins_over_default_table(tmp_path, monkeypatch):
    key = cache_key("masked_matmul", dict(m=8, k=8, n=8, r=4, c=4), "float32", "cpu")
    user = tmp_path / "user.json"
    user.write_text(json.dumps({"version": CACHE_VERSION, "entries": {
        key: {"blocks": {"bm": 8, "bn": 8, "bk": 8}},
    }}))
    monkeypatch.setenv(tc.ENV_CACHE_PATH, str(user))
    prev = set_tuning_cache(None)
    try:
        tc.reset_tuning_cache()
        cache = tc.get_tuning_cache()
        assert cache.entries[key]["blocks"] == {"bm": 8, "bn": 8, "bk": 8}
        assert str(user) in cache.source
    finally:
        set_tuning_cache(prev)


def test_lookup_blocks_rejects_malformed_blocks():
    key = cache_key("masked_matmul", dict(m=8), "float32", "cpu")
    for bad in (None, "big", dict(bm="not-an-int"), 7):
        cache = TuningCache(entries={key: dict(blocks=bad)})
        assert cache.lookup_blocks("masked_matmul", dict(m=8), "float32", "cpu") is None


# ---------------------------------------------------------------------------
# the tuned_block seam (kernels/common.py)
# ---------------------------------------------------------------------------


def test_tuned_block_empty_cache_returns_defaults(isolated_cache):
    from repro.kernels.common import tuned_block

    out = tuned_block(
        "masked_matmul", dict(m=64, k=64, n=64, r=16, c=16), jnp.float32,
        interpret=True, defaults=dict(bm=512, bn=512, bk=512),
    )
    assert out == dict(bm=512, bn=512, bk=512)


def test_tuned_block_resolution_order(isolated_cache):
    from repro.kernels.common import tuned_block

    shape = dict(m=64, k=64, n=64, r=16, c=16)
    key = cache_key("masked_matmul", shape, "float32", "interpret")
    isolated_cache.put(key, dict(blocks=dict(bm=32, bn=32, bk=32, bogus=99)))
    # cache hit overrides defaults — but only for known block params
    out = tuned_block(
        "masked_matmul", shape, jnp.float32,
        interpret=True, defaults=dict(bm=512, bn=512, bk=512),
    )
    assert out == dict(bm=32, bn=32, bk=32)
    # explicit caller overrides beat the cache, per parameter
    out = tuned_block(
        "masked_matmul", shape, jnp.float32,
        interpret=True, defaults=dict(bm=512, bn=512, bk=512),
        overrides=dict(bm=16, bn=None, bk=None),
    )
    assert out == dict(bm=16, bn=32, bk=32)
    # a different backend tag misses the cache entirely
    out = tuned_block(
        "masked_matmul", shape, jnp.float32,
        interpret=False, defaults=dict(bm=512, bn=512, bk=512),
    )
    assert out == dict(bm=512, bn=512, bk=512)


def test_empty_cache_ops_output_is_bitwise_heuristic(isolated_cache):
    """The acceptance pin: with an empty cache the wrappers must produce
    BITWISE-identical outputs to explicit heuristic block arguments."""
    from repro.kernels.masked_matmul.ops import masked_matmul

    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (64, 64))
    w = jax.random.normal(key, (64, 64))
    ok = (jax.random.uniform(key, (16, 16)) > 0.2).astype(jnp.float32)
    auto = masked_matmul(x, w, ok, interpret=True)
    explicit = masked_matmul(x, w, ok, bm=512, bn=512, bk=512, interpret=True)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(explicit))


def test_cached_blocks_change_launch_not_numerics(isolated_cache):
    """A cache hit must steer geometry (observable) while output stays
    within float tolerance of the heuristic launch."""
    from repro.kernels.flash_attention.ops import flash_attention

    shape = dict(b=1, hq=1, hkv=1, sq=128, skv=128, d=16, causal=1)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 1, 128, 16))
    k = jax.random.normal(ks[1], (1, 1, 128, 16))
    v = jax.random.normal(ks[2], (1, 1, 128, 16))
    base = flash_attention(q, k, v, interpret=True)
    key = cache_key("flash_attention", shape, "float32", "interpret")
    isolated_cache.put(key, dict(blocks=dict(bq=32, bkv=32)))
    tuned = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(
        np.asarray(tuned), np.asarray(base), rtol=1e-5, atol=1e-5
    )


# ---------------------------------------------------------------------------
# search primitives
# ---------------------------------------------------------------------------


def test_pow2_lattice_contents():
    assert pow2_lattice(64, lo=8) == [8, 16, 32, 64]
    # non-power-of-two dim rides along as its own (clamped) point
    assert pow2_lattice(96, lo=8) == [8, 16, 32, 64, 96]
    assert pow2_lattice(4, lo=8) == [4]


def test_lattice_neighbors_single_param_moves():
    lat = dict(bm=[8, 16, 32], bn=[8, 16, 32])
    moves = list(lattice_neighbors(dict(bm=16, bn=8), lat))
    assert dict(bm=32, bn=8) in moves  # up first
    assert dict(bm=8, bn=8) in moves
    assert dict(bm=16, bn=16) in moves
    assert all(sum(a != b for a, b in zip(m.values(), (16, 8))) == 1 for m in moves)


def test_hillclimb_greedy_first_improvement():
    lat = dict(x=[1, 2, 4, 8])
    score = lambda b: -b["x"]  # bigger x is better  # noqa: E731
    best, best_s, evals = hillclimb(
        dict(x=1), lambda b: lattice_neighbors(b, lat), score, max_evals=16
    )
    assert best == dict(x=8) and best_s == -8


def test_hillclimb_unscoreable_start_raises():
    with pytest.raises(ValueError):
        hillclimb(dict(x=1), lambda b: [], lambda b: None)


# ---------------------------------------------------------------------------
# lint gating: rejected candidates are never compiled / launched
# ---------------------------------------------------------------------------


def test_lint_rejected_candidates_never_reach_pallas_call(monkeypatch, isolated_cache):
    import repro.kernels.flash_attention.ops as fa_ops

    shape = dict(b=1, hq=1, hkv=1, sq=256, skv=256, d=8, causal=1)
    heur = normalize_blocks("flash_attention", shape, HEURISTIC_BLOCKS["flash_attention"])
    up = normalize_blocks("flash_attention", shape, dict(bq=256, bkv=256))
    _, heur_vmem = lint_candidate("flash_attention", shape, jnp.float32, heur)
    _, up_vmem = lint_candidate("flash_attention", shape, jnp.float32, up)
    assert up_vmem > heur_vmem
    limit = (heur_vmem + up_vmem) // 2  # heuristic passes, up-neighbors fail

    seen = []
    real = fa_ops.flash_attention

    def spy(q, k, v, *args, **kwargs):
        seen.append({p: kwargs.get(p) for p in ("bq", "bkv")})
        return real(q, k, v, *args, **kwargs)

    monkeypatch.setattr(fa_ops, "flash_attention", spy)
    res = tune_kernel(
        "flash_attention", shape, jnp.float32,
        iters=1, max_evals=6, interpret=True, vmem_limit_bytes=limit,
    )
    assert res.rejected > 0
    rejected = [tuple(sorted(r["blocks"].items())) for r in res.rejected_configs]
    launched = [tuple(sorted(s.items())) for s in seen]
    assert launched, "the tuner never ran the kernel at all"
    assert not set(rejected) & set(launched), (
        "a lint-rejected candidate was compiled/launched"
    )
    for blocks in seen:
        findings, _ = lint_candidate(
            "flash_attention", shape, jnp.float32, blocks, vmem_limit_bytes=limit
        )
        assert not findings


def test_heuristic_failing_lint_raises_before_any_launch(monkeypatch):
    called = []
    space = KERNELS["masked_matmul"]
    monkeypatch.setitem(
        KERNELS,
        "masked_matmul",
        dataclasses.replace(
            space,
            make_runner=lambda *a, **k: lambda blocks: called.append(blocks),
        ),
    )
    with pytest.raises(ValueError, match="fails the"):
        tune_kernel(
            "masked_matmul", dict(m=64, k=64, n=64, r=16, c=16),
            interpret=True, vmem_limit_bytes=1,  # everything over budget
        )
    assert not called


# ---------------------------------------------------------------------------
# end-to-end interpret-mode tune
# ---------------------------------------------------------------------------


def test_tune_masked_matmul_beats_or_ties_heuristic(isolated_cache):
    shape = dict(m=64, k=64, n=64, r=16, c=16)
    res = tune_kernel("masked_matmul", shape, iters=1, max_evals=6, interpret=True)
    assert res.best_s <= res.heuristic_s  # hillclimb is seeded at the heuristic
    assert res.speedup >= 1.0
    assert res.backend == "interpret"
    assert res.evaluated >= 1
    assert res.vmem_bytes > 0
    assert res.roofline_fraction is None  # interpret mode has no roofline
    # the cache entry round-trips through the seam
    kernel, pshape, dtype, backend = parse_key(res.key)
    assert (kernel, pshape, dtype, backend) == (
        "masked_matmul", shape, "float32", "interpret"
    )
    isolated_cache.put(res.key, res.entry)
    assert isolated_cache.lookup_blocks(
        "masked_matmul", shape, "float32", "interpret"
    ) == res.best_blocks


def test_tune_many_fills_cache():
    cells = [("masked_matmul", dict(m=32, k=32, n=32, r=8, c=8))]
    results, cache = tune_many(cells, iters=1, max_evals=4, interpret=True)
    assert len(results) == 1 and len(cache) == 1
    assert cache.get(results[0].key)["blocks"] == results[0].best_blocks


# ---------------------------------------------------------------------------
# capacity planner's kernel-VMEM reserve
# ---------------------------------------------------------------------------


def test_kernel_vmem_reserve_sums_per_kernel_maxima():
    from repro.fleet.capacity import kernel_vmem_reserve

    cache = TuningCache()
    cache.put(cache_key("masked_matmul", dict(m=8), "float32", "cpu"),
              dict(blocks=dict(bm=8), vmem_bytes=100))
    cache.put(cache_key("masked_matmul", dict(m=16), "float32", "cpu"),
              dict(blocks=dict(bm=16), vmem_bytes=300))
    cache.put(cache_key("mamba_scan", dict(l=8), "float32", "cpu"),
              dict(blocks=dict(bl=8), vmem_bytes=50))
    assert kernel_vmem_reserve(cache) == 300 + 50
    assert kernel_vmem_reserve(TuningCache()) == 0


def test_suggest_population_size_reserve_is_opt_in_and_shrinks():
    from repro.configs import get_arch
    from repro.fleet.capacity import BYTES_PER_PARAM, suggest_population_size

    cfg = get_arch("paper-mlp")
    member = int(cfg.param_count()) * BYTES_PER_PARAM
    cache = TuningCache()
    cache.put(cache_key("masked_matmul", dict(m=8), "float32", "cpu"),
              dict(blocks=dict(bm=8), vmem_bytes=4 * member))
    budget = 10 * member  # fits 10 members at headroom=1.0
    base = suggest_population_size(cfg, None, hbm_bytes=budget, headroom=1.0)
    reserved = suggest_population_size(
        cfg, None, hbm_bytes=budget, headroom=1.0,
        reserve_kernel_vmem=True, tuning_cache=cache,
    )
    assert base == 10
    assert reserved == 6  # (10 - 4) members after the kernel reserve
    # a reserve that eats the whole device is a hard error, not pop=0
    with pytest.raises(ValueError, match="reserve"):
        suggest_population_size(
            cfg, None, hbm_bytes=3 * member, headroom=1.0,
            reserve_kernel_vmem=True,
            tuning_cache=TuningCache(entries={
                cache_key("masked_matmul", dict(m=8), "float32", "cpu"):
                    dict(blocks=dict(bm=8), vmem_bytes=4 * member),
            }),
        )


def test_roofline_peaks_keyed_by_device_kind():
    from repro.tune.roofline import PEAKS, peaks_for, roofline_fraction

    v5e = peaks_for("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    assert "TPU v5e" in v5e.source
    assert set(PEAKS) == {"TPU v5 lite"}
    # 819 MB in 1 ms is exactly the HBM bound: share 1.0
    assert roofline_fraction(0.0, 819e6, 1e-3, "TPU v5 lite") == pytest.approx(1.0)
    assert roofline_fraction(0.0, 819e6, 1e-3, None) is None
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("cpu")
    with pytest.raises(KeyError):
        roofline_fraction(1.0, 1.0, 1.0, "TPU v9 imaginary")
