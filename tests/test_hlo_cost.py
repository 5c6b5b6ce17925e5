"""Regression tests for the loop-aware HLO cost model that feeds the
roofline analysis (EXPERIMENTS.md §Roofline), plus the per-instruction /
alias-table API the donation lint (repro.analysis) consumes."""
import numpy as np

from repro.launch.hlo_cost import (
    analyze_hlo,
    entry_parameters,
    input_output_aliases,
    iter_instructions,
    module_name,
    scoped_instructions,
)

# A hand-written post-SPMD-style HLO module:
#   body: one dot (M=8,K=16,N=32 f32) + an all-gather (out 4096 B, groups of 4)
#   entry: while(body) with known_trip_count 5 + one all-reduce (f32[100])
_HLO = """
HloModule test

%body.1 (p: (s32[], f32[8,16], f32[4,16])) -> (s32[], f32[8,16], f32[4,16]) {
  %p = (s32[], f32[8,16], f32[4,16]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %a = f32[8,16]{1,0} get-tuple-element(%p), index=1
  %kshard = f32[4,16]{1,0} get-tuple-element(%p), index=2
  %w = f32[16,32]{1,0} constant({...})
  %dot.1 = f32[8,32]{1,0} dot(%a, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ag = f32[16,16]{1,0} all-gather(%kshard), replica_groups=[4,4]<=[16], dimensions={0}
  %one = s32[] constant(1)
  %ip = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,16], f32[4,16]) tuple(%ip, %a, %kshard)
}

%cond.1 (p: (s32[], f32[8,16], f32[4,16])) -> pred[] {
  %p = (s32[], f32[8,16], f32[4,16]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main.1 (x: f32[8,16], ks: f32[4,16], g: f32[100]) -> f32[100] {
  %x = f32[8,16]{1,0} parameter(0)
  %ks = f32[4,16]{1,0} parameter(1)
  %g = f32[100]{0} parameter(2)
  %zero = s32[] constant(0)
  %init = (s32[], f32[8,16], f32[4,16]) tuple(%zero, %x, %ks)
  %while.1 = (s32[], f32[8,16], f32[4,16]) while(%init), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %ar = f32[100]{0} all-reduce(%g), replica_groups=[16,16]<=[256], to_apply=%add.1
}
"""


def test_dot_flops_with_trip_count():
    cost = analyze_hlo(_HLO, n_devices_default=256)
    # dot: 2*8*32*16 = 8192 flops, x5 trips
    assert cost.flops == 2 * 8 * 32 * 16 * 5


def test_collective_wire_bytes():
    cost = analyze_hlo(_HLO, n_devices_default=256)
    d = cost.as_dict()
    # all-gather: out 16*16*4 = 1024 B, groups of 4 -> 1024 * 3/4, x5 trips
    assert np.isclose(d["coll_by_kind"]["all-gather"], 1024 * 0.75 * 5)
    # all-reduce: out 400 B, groups of 16 -> 2 * 400 * 15/16, x1
    assert np.isclose(d["coll_by_kind"]["all-reduce"], 2 * 400 * 15 / 16)
    assert d["coll_count"]["all-gather"] == 5
    assert d["coll_count"]["all-reduce"] == 1


def test_bytes_include_dot_operands_and_result():
    cost = analyze_hlo(_HLO, n_devices_default=256)
    # per trip the dot touches a(512) + w(2048) + out(1024) bytes; the
    # all-gather adds local read+write of the gathered buffer (2*1024)
    per_trip = (8 * 16 + 16 * 32 + 8 * 32) * 4 + 2 * 1024
    assert cost.bytes >= per_trip * 5


def test_iter_instructions_yields_parsed_entry():
    instrs = list(iter_instructions(_HLO, entry_only=True))
    by_name = {i.name: i for i in instrs}
    assert by_name["x"].opcode == "parameter"
    assert by_name["x"].result_bytes == 8 * 16 * 4
    assert by_name["while.1"].opcode == "while"
    assert by_name["ar"].is_root and by_name["ar"].opcode == "all-reduce"
    assert by_name["ar"].operands == ("g",)
    # computation-scoped iteration sees the body's dot but not the entry
    body = list(iter_instructions(_HLO, computation="body.1"))
    assert any(i.opcode == "dot" for i in body)
    assert not any(i.name == "while.1" for i in body)


def test_entry_parameters_by_number():
    params = entry_parameters(_HLO)
    assert sorted(params) == [0, 1, 2]
    assert params[2].result_bytes == 100 * 4


def test_input_output_alias_header_parse():
    hlo = (
        "HloModule jit_f, input_output_alias={ {0}: (1, {}, may-alias), "
        "{1}: (3, {}, must-alias) }, entry_computation_layout={(f32[8])->f32[8]}\n"
        "ENTRY %main (p0: f32[8]) -> f32[8] {\n"
        "  ROOT %p0 = f32[8]{0} parameter(0)\n"
        "}\n"
    )
    aliases = input_output_aliases(hlo)
    assert [(a.output_index, a.param_number, a.kind) for a in aliases] == [
        ((0,), 1, "may-alias"),
        ((1,), 3, "must-alias"),
    ]
    assert input_output_aliases(_HLO) == []  # no table -> nothing donated


def test_alias_table_from_real_compiled_module():
    """End to end on a real jit: donation shows up in the optimized HLO and
    the donated parameter's byte size matches entry_parameters."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda x, y: (x + y, y * 2.0), donate_argnums=(0,))
    s = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    hlo = fn.lower(s, s).compile().as_text()
    aliases = input_output_aliases(hlo)
    assert {a.param_number for a in aliases} == {0}
    params = entry_parameters(hlo)
    assert params[0].result_bytes == 64 * 64 * 4

    undonated = jax.jit(lambda x, y: (x + y, y * 2.0))
    assert input_output_aliases(undonated.lower(s, s).compile().as_text()) == []


def test_real_cell_attribution_smollm():
    """End-to-end sanity on a stored artifact: attention dot FLOPs in the
    smollm train HLO match the analytic count (the validation quoted in
    EXPERIMENTS.md §Roofline)."""
    import gzip
    import os

    path = "experiments/dryrun/smollm_135m__train_4k__pod1.hlo.gz"
    if not os.path.exists(path):
        import pytest

        pytest.skip("dry-run artifact not present")
    hlo = gzip.open(path, "rt").read()
    cost = analyze_hlo(hlo, n_devices_default=256)
    dots = dict(cost.as_dict()["top_dots"])
    qk = dots.get("bhgqd,bhkd->bhgqk", 0.0)
    # analytic: L30 * B16 * H9 * S^2 * hd64 * 2 (no causal skip in the scan
    # form) * 4 executions (fwd + remat + 2 bwd dots share the label)
    analytic = 30 * 16 * 9 * 4096**2 * 64 * 2 * 4
    assert abs(qk - analytic) / analytic < 0.05


# TPU-compiled HLO: layouts carry upper-case tiling/memory-space tags, and a
# fusion carries its root's op_name
_TPU_HLO = """
HloModule jit_sample_decode, is_scheduled=true

%fused_computation.8 (param_0.1: bf16[576,64], param_1.2: f32[4,4]) -> bf16[36864] {
  %param_0.1 = bf16[576,64]{1,0:T(8,128)(2,1)} parameter(0)
  %gather.1 = bf16[576,64]{1,0:T(8,128)(2,1)} gather(%param_0.1), metadata={op_name="jit(sample_decode)/fault_mask/gather"}
  ROOT %mul.1 = bf16[36864]{0:T(1024)(128)(2,1)} multiply(%gather.1), metadata={op_name="jit(sample_decode)/fault_mask/mul"}
}

ENTRY %main.2 (w: bf16[576,64], ok: f32[4,4], x: bf16[8,576]) -> bf16[8,64] {
  %w = bf16[576,64]{1,0:T(8,128)(2,1)} parameter(0)
  %ok = f32[4,4]{1,0:T(4,128)} parameter(1)
  %x = bf16[8,576]{1,0:T(8,128)(2,1)} parameter(2)
  %fusion.8 = bf16[36864]{0:T(1024)(128)(2,1)S(1)} fusion(%w, %ok), kind=kCustom, calls=%fused_computation.8, metadata={op_name="jit(sample_decode)/fault_mask/gather"}
  %reshape.3 = bf16[576,64]{1,0:T(8,128)(2,1)} reshape(%fusion.8), metadata={op_name="jit(sample_decode)/fault_mask_extra/reshape"}
  ROOT %dot.4 = bf16[8,64]{1,0:T(8,128)(2,1)} dot(%x, %reshape.3), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(sample_decode)/dot_general"}
}
"""


def test_opcodes_parse_past_tpu_layout_tags():
    ops = {i.name: i.opcode for i in iter_instructions(_TPU_HLO)}
    assert ops["fusion.8"] == "fusion" and ops["gather.1"] == "gather"
    assert ops["dot.4"] == "dot" and ops["w"] == "parameter"


def test_scoped_instructions_are_the_unfused_ops_under_the_scope():
    assert module_name(_TPU_HLO) == "jit_sample_decode"
    # the fused computation's gather and multiply are not ops of their own;
    # a scope whose name only starts with the scope's is another scope
    assert scoped_instructions(_TPU_HLO, "fault_mask") == ["fusion.8"]
    assert scoped_instructions(_TPU_HLO, "dot_general") == ["dot.4"]
    assert scoped_instructions(_TPU_HLO, "no_such_scope") == []
