"""Loop-aware cost accounting from optimized (post-SPMD) HLO text.

XLA's ``compiled.cost_analysis()`` counts each while-loop body ONCE, which
undercounts scan-over-layers / microbatch programs by orders of magnitude.
This module walks the HLO call graph from ENTRY, multiplying while bodies
by their ``known_trip_count`` backend config, and accounts per top-level
instruction:

  flops  — dot instructions: 2 * prod(result dims) * prod(contracting dims)
           (contracting sizes resolved via a per-computation symbol table)
  bytes  — HBM traffic model: operands + result per top-level op; fusions
           count as single ops; bookkeeping ops (tuple/GTE/bitcast/param/
           constant) are free; dynamic-update-slice counts 2x update size
           (read+write, aliased buffer)
  collective wire bytes — ring-model per kind (see _collective_bytes)

This intentionally mirrors HloCostAnalysis conventions where they are
defensible and documents divergences; the roofline terms in EXPERIMENTS.md
cite this module as the source.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Iterator, Optional

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_ASSIGN_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
# opcodes are lower case; a TPU layout's tiling and memory space in the
# result type (``{1,0:T(8,128)S(1)}``) are upper case and must not match
_OPCODE_RE = re.compile(r"([a-z][\w\-]*)\(")
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*?\)\s*->.*\{\s*$")
_TRIP_RE = re.compile(r'known_trip_count[^}]*?"n":"(\d+)"')
_OPERAND_RE = re.compile(r"%([\w.\-]+)")

_FREE_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "while", "conditional",
    "call", "custom-call",  # custom-calls on this path are layout/control
}

_COLLECTIVES = {
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
}


def _shapes_in(text: str) -> list[tuple[str, list[int]]]:
    out = []
    for dt, dims in _SHAPE_RE.findall(text):
        if dt in _DTYPE_BYTES:
            out.append((dt, [int(d) for d in dims.split(",")] if dims else []))
    return out


def _bytes_of(text: str) -> int:
    total = 0
    for dt, dims in _shapes_in(text):
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:
        return int(m.group(2))
    return default


def _collective_bytes(kind: str, out_bytes: int, n: int) -> float:
    frac = (n - 1) / n if n > 1 else 0.0
    if kind == "all-gather":
        return out_bytes * frac
    if kind == "all-reduce":
        return 2 * out_bytes * frac
    if kind == "reduce-scatter":
        return out_bytes * (n - 1)
    if kind == "all-to-all":
        return out_bytes * frac
    return float(out_bytes)  # collective-permute


@dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    collective_bytes: float = 0.0
    coll_by_kind: dict = field(default_factory=lambda: defaultdict(float))
    coll_count: dict = field(default_factory=lambda: defaultdict(int))
    dot_flops_by_name: dict = field(default_factory=lambda: defaultdict(float))

    def add(self, other: "Cost", mult: float = 1.0) -> None:
        self.flops += other.flops * mult
        self.bytes += other.bytes * mult
        self.collective_bytes += other.collective_bytes * mult
        for k, v in other.coll_by_kind.items():
            self.coll_by_kind[k] += v * mult
        for k, v in other.coll_count.items():
            self.coll_count[k] += int(v * mult)
        for k, v in other.dot_flops_by_name.items():
            self.dot_flops_by_name[k] += v * mult

    def as_dict(self) -> dict:
        top_dots = sorted(
            self.dot_flops_by_name.items(), key=lambda kv: -kv[1]
        )[:8]
        return dict(
            flops=self.flops,
            bytes=self.bytes,
            collective_bytes=self.collective_bytes,
            coll_by_kind={k: float(v) for k, v in self.coll_by_kind.items()},
            coll_count=dict(self.coll_count),
            top_dots=[(k, float(v)) for k, v in top_dots],
        )


def _parse_computations(hlo: str):
    comps: dict[str, list[str]] = {}
    entry = None
    cur = None
    for line in hlo.splitlines():
        m = _COMP_RE.match(line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            if m.group(1):
                entry = cur
            continue
        if cur is not None:
            if line.strip() == "}":
                cur = None
            else:
                comps[cur].append(line)
    return comps, entry


def _parse_instr(line: str):
    """Returns (name, result_text, opcode) or None.

    Result types may be tuples containing '=' inside /*index=N*/ comments,
    so the opcode is located as the first 'word(' after the '='."""
    am = _ASSIGN_RE.match(line)
    if not am:
        return None
    name, rest = am.groups()
    om = _OPCODE_RE.search(rest)
    if not om:
        return None
    return name, rest[: om.start()], om.group(1)


# ---------------------------------------------------------------------------
# Public per-instruction API
#
# Downstream passes (repro.analysis) consume parsed instructions and the
# module's input/output alias table through these instead of re-parsing the
# HLO text with their own regexes.
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Instruction:
    """One parsed HLO instruction (top level of one computation)."""

    computation: str
    name: str
    opcode: str
    result_text: str  # raw result-type text, e.g. "f32[128,256]{1,0} "
    operands: tuple[str, ...]
    is_root: bool
    line: str

    @property
    def result_bytes(self) -> int:
        return _bytes_of(self.result_text)


@dataclass(frozen=True)
class IOAlias:
    """One entry of the module's ``input_output_alias`` table: output (tuple
    index into the result) aliases entry parameter ``param_number`` — i.e.
    that parameter's buffer was donated and XLA reuses it in place."""

    output_index: tuple[int, ...]
    param_number: int
    kind: str = "may-alias"


def _operands_of(line: str, opcode: str) -> tuple[str, ...]:
    """Operand instruction names of one HLO line (shared by the cost walk)."""
    tail = line.split(opcode + "(", 1)
    if len(tail) < 2:
        return ()
    return tuple(_OPERAND_RE.findall(tail[1].split("), ")[0]))


def iter_instructions(
    hlo: str, computation: Optional[str] = None, entry_only: bool = False
) -> Iterator[Instruction]:
    """Yield every parsed instruction of ``hlo``.

    ``computation`` restricts to one computation by name; ``entry_only``
    restricts to the ENTRY computation. Lines that are not instructions
    (headers, braces, metadata continuations) are skipped.
    """
    comps, entry = _parse_computations(hlo)
    if entry_only:
        if entry is None:
            return
        names = [entry]
    elif computation is not None:
        names = [computation] if computation in comps else []
    else:
        names = list(comps)
    for comp in names:
        for line in comps[comp]:
            parsed = _parse_instr(line)
            if not parsed:
                continue
            name, result_text, op = parsed
            yield Instruction(
                computation=comp,
                name=name,
                opcode=op,
                result_text=result_text,
                operands=_operands_of(line, op),
                is_root=line.strip().startswith("ROOT"),
                line=line,
            )


_ALIAS_TABLE_RE = re.compile(r"input_output_alias=\{(.*?)\}\s*(?:,|$)")
_ALIAS_ENTRY_RE = re.compile(
    r"\{([\d,\s]*)\}:\s*\((\d+)\s*,\s*\{[\d,\s]*\}\s*(?:,\s*([\w\-]+))?\)"
)


def input_output_aliases(hlo: str) -> list[IOAlias]:
    """Parse the ``input_output_alias={...}`` table from the HloModule header.

    Returns one :class:`IOAlias` per aliased (donated) entry parameter; an
    empty list when the program donates nothing. The table only appears in
    *optimized* HLO (``compiled.as_text()``), not in pre-compile StableHLO.
    """
    out: list[IOAlias] = []
    for line in hlo.splitlines():
        if not line.startswith("HloModule"):
            continue
        # the table's inner braces nest one level: grab everything between
        # 'input_output_alias={' and the matching close brace
        start = line.find("input_output_alias={")
        if start < 0:
            return []
        depth = 0
        body = []
        for ch in line[start + len("input_output_alias=") :]:
            if ch == "{":
                depth += 1
                if depth == 1:
                    continue
            elif ch == "}":
                depth -= 1
                if depth == 0:
                    break
            body.append(ch)
        for m in _ALIAS_ENTRY_RE.finditer("".join(body)):
            idx = tuple(int(x) for x in m.group(1).split(",") if x.strip())
            out.append(
                IOAlias(
                    output_index=idx,
                    param_number=int(m.group(2)),
                    kind=m.group(3) or "must-alias",
                )
            )
        break
    return out


def module_name(hlo: str) -> str:
    """The module's name from its ``HloModule <name>, ...`` header, e.g.
    ``jit_sample_decode`` (a profiler trace names the program's runs so)."""
    head = hlo.lstrip().split(None, 2)
    if len(head) < 2 or head[0] != "HloModule":
        raise ValueError("not HLO module text: no 'HloModule <name>' header")
    return head[1].rstrip(",")


def scoped_instructions(hlo: str, scope: str) -> list[str]:
    """Names of the instructions whose ``op_name`` metadata lies under the
    name scope ``scope`` (``jax.named_scope``), leaving out those inside
    fused computations: what remains is what runs as an op of its own, and
    so what a device trace names. A fusion carries its root's metadata."""
    instrs = list(iter_instructions(hlo))
    fused = {m for i in instrs if i.opcode == "fusion"
             for m in re.findall(r"calls=%?([\w.\-]+)", i.line)}
    under = re.compile(r'op_name="(?:[^"]*/)?' + re.escape(scope) + r'(?:/[^"]*)?"')
    return sorted(i.name for i in instrs if i.computation not in fused and under.search(i.line))


def entry_parameters(hlo: str) -> dict[int, Instruction]:
    """ENTRY computation parameters by parameter number.

    ``entry_parameters(hlo)[n].result_bytes`` is the byte size of entry
    parameter ``n`` — the donation lint joins this against
    :func:`input_output_aliases` to weigh undonated buffers.
    """
    out: dict[int, Instruction] = {}
    for instr in iter_instructions(hlo, entry_only=True):
        if instr.opcode != "parameter":
            continue
        m = re.search(r"parameter\((\d+)\)", instr.line)
        if m:
            out[int(m.group(1))] = instr
    return out


def analyze_hlo(hlo: str, n_devices_default: int = 1) -> Cost:
    comps, entry = _parse_computations(hlo)

    # fusion computations are called via fusion instructions; never walk them
    fusion_comps = set()
    for lines in comps.values():
        for line in lines:
            if " fusion(" in line:
                fm = re.search(r"calls=%?([\w.\-]+)", line)
                if fm:
                    fusion_comps.add(fm.group(1))

    # per-computation symbol table: instruction name -> result-type text
    symtab: dict[str, dict[str, str]] = {}
    for name, lines in comps.items():
        tab = {}
        for line in lines:
            parsed = _parse_instr(line)
            if parsed:
                tab[parsed[0]] = parsed[1]
        symtab[name] = tab

    # Per fused computation: (bytes per parameter index, output-bytes
    # override). A parameter consumed ONLY by dynamic-slice/gather reads
    # just the slice (the scan access pattern); a parameter that is the
    # in-place target of a ROOT dynamic-update-slice is aliased (0 bytes);
    # a DUS-rooted fusion writes only the update slice, not the buffer.
    fusion_info: dict[str, tuple[dict[int, float], float | None]] = {}

    def _fusion_params(comp: str) -> tuple[dict[int, float], float | None]:
        if comp in fusion_info:
            return fusion_info[comp]
        out: dict[int, float] = {}
        out_override: float | None = None
        lines = comps.get(comp, [])
        tab = symtab.get(comp, {})
        # parameter name -> index
        pidx: dict[str, int] = {}
        for line in lines:
            parsed = _parse_instr(line)
            if parsed and parsed[2] == "parameter":
                m = re.search(r"parameter\((\d+)\)", line)
                if m:
                    pidx[parsed[0]] = int(m.group(1))
        # classify uses
        sliced_bytes: dict[str, float] = {p: 0.0 for p in pidx}
        full_use: dict[str, bool] = {p: False for p in pidx}
        dus_target: set[str] = set()
        root_name = None
        defs: dict[str, tuple[str, list[str], str]] = {}
        for line in lines:
            parsed = _parse_instr(line)
            if not parsed:
                continue
            nm, rtext, op = parsed
            tail = line.split(op + "(", 1)
            otext = tail[1].split("), ")[0] if len(tail) > 1 else ""
            onames = _OPERAND_RE.findall(otext)
            defs[nm] = (op, onames, rtext)
            if line.strip().startswith("ROOT"):
                root_name = nm
            if parsed[2] == "parameter":
                continue
            for j, o in enumerate(onames):
                if o not in pidx:
                    continue
                if op in ("dynamic-slice", "gather", "slice"):
                    sliced_bytes[o] += _bytes_of(rtext)
                elif op == "dynamic-update-slice" and j == 0:
                    dus_target.add(o)  # aliased buffer, not traffic
                else:
                    full_use[o] = True
        # DUS-rooted fusion (possibly through a bitcast chain): the write is
        # the update slice
        node = root_name
        for _ in range(3):
            if node not in defs:
                break
            op, onames, rtext = defs[node]
            if op == "dynamic-update-slice":
                upd = onames[1] if len(onames) > 1 else None
                if upd and upd in defs:
                    out_override = _bytes_of(defs[upd][2])
                elif upd in pidx:
                    out_override = _bytes_of(tab.get(upd, ""))
                break
            if op in ("bitcast", "copy") and onames:
                node = onames[0]
            else:
                break
        for p, i in pidx.items():
            if full_use[p]:
                out[i] = _bytes_of(tab.get(p, ""))
            elif p in dus_target:
                out[i] = 0.0
            else:
                out[i] = sliced_bytes[p]
        fusion_info[comp] = (out, out_override)
        return fusion_info[comp]

    memo: dict[str, Cost] = {}

    def walk(comp: str, depth: int = 0) -> Cost:
        if comp in memo:
            return memo[comp]
        cost = Cost()
        memo[comp] = cost  # break cycles defensively
        if depth > 60 or comp not in comps:
            return cost
        tab = symtab[comp]
        for line in comps[comp]:
            parsed = _parse_instr(line)
            if not parsed:
                continue
            name, result_text, op = parsed
            if op.endswith("-done"):
                continue  # counted at -start
            base_op = op[:-6] if op.endswith("-start") else op
            # ---- collectives ------------------------------------------
            if base_op in _COLLECTIVES:
                out_b = _bytes_of(result_text)
                n = _group_size(line, n_devices_default)
                moved = _collective_bytes(base_op, out_b, n)
                cost.collective_bytes += moved
                cost.coll_by_kind[base_op] += moved
                cost.coll_count[base_op] += 1
                cost.bytes += 2 * out_b  # local read+write of the buffer
                continue
            # ---- control flow -----------------------------------------
            if base_op == "while":
                bm = re.search(r"body=%?([\w.\-]+)", line)
                trips = 1
                tm = _TRIP_RE.search(line)
                if tm:
                    trips = int(tm.group(1))
                if bm:
                    cost.add(walk(bm.group(1), depth + 1), trips)
                continue
            if base_op in ("call", "conditional"):
                for cm in re.finditer(r"(?:to_apply|body)=%?([\w.\-]+)", line):
                    cost.add(walk(cm.group(1), depth + 1), 1)
                for cm in re.finditer(r"branch_computations=\{([^}]*)\}", line):
                    for b in _OPERAND_RE.findall(cm.group(1)):
                        cost.add(walk(b, depth + 1), 1)
                continue
            if base_op in _FREE_OPS:
                continue
            # ---- operand byte lookup ----------------------------------
            operand_names = _operands_of(line, op)
            op_bytes = sum(_bytes_of(tab.get(o, "")) for o in operand_names)
            out_bytes = _bytes_of(result_text)
            if base_op == "dynamic-update-slice":
                # aliased in-place update: read+write of the update slice
                upd = operand_names[1] if len(operand_names) > 1 else None
                ub = _bytes_of(tab.get(upd, "")) if upd else 0
                cost.bytes += 2 * ub
            elif base_op in ("dynamic-slice", "slice", "gather"):
                # reads only the sliced/gathered region, not the operand
                cost.bytes += 2 * out_bytes
            elif base_op in ("broadcast", "iota"):
                cost.bytes += out_bytes
            elif base_op == "fusion":
                fm = re.search(r"calls=%?([\w.\-]+)", line)
                if fm:
                    pb, out_override = _fusion_params(fm.group(1))
                    op_bytes = sum(
                        pb.get(i, _bytes_of(tab.get(o, "")))
                        for i, o in enumerate(operand_names)
                    )
                    if out_override is not None:
                        out_bytes = out_override
                cost.bytes += op_bytes + out_bytes
            else:
                cost.bytes += op_bytes + out_bytes
            # ---- dot flops --------------------------------------------
            if base_op == "dot":
                shapes = _shapes_in(result_text)
                out_elems = 1
                for _, dims in shapes:
                    for d in dims:
                        out_elems *= d
                lhs = operand_names[0] if operand_names else None
                lhs_shapes = _shapes_in(tab.get(lhs, "")) if lhs else []
                kdim = 1
                cm = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", line)
                if cm and lhs_shapes:
                    dims = lhs_shapes[0][1]
                    for idx in cm.group(1).split(","):
                        if idx and int(idx) < len(dims):
                            kdim *= dims[int(idx)]
                flops = 2.0 * out_elems * kdim
                cost.flops += flops
                meta = re.search(r'op_name="([^"]*)"', line)
                label = meta.group(1).split("/")[-2] if meta and "/" in (meta.group(1)) else base_op
                cost.dot_flops_by_name[label] += flops
            elif base_op == "convolution":
                cost.flops += 2 * _bytes_of(result_text)  # rough; unused path
        return cost

    if entry is None:
        return Cost()
    return walk(entry)
