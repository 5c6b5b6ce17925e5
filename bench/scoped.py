"""Device time of a program's ops that lie under one name scope.

The serving engine publishes, in one ``serve.programs`` instant at the
start of ``serve()``, per program (HLO module name) the names of its ops
whose HLO ``op_name`` lies under each of a few name scopes, from each AOT
executable's ``as_text()``. A TPU op event carries no scope path (it is
named ``%fusion.8 = bf16[...] fusion(...)``), so this map is how a trace's
ops are told apart by scope; no reader looks for a scope in an event's
name.
"""
from __future__ import annotations

from bisect import bisect_right

import numpy as np

from bench.trace import op_name, union_length


def published_ops(run, scope: str, program: str) -> set:
    """The op names the engine published for ``program`` under ``scope``;
    raises where the instant, the scope's map or the program is missing."""
    programs = next((e.args for e in run.job.events
                     if e.kind == "instant" and e.name == "serve.programs"), None)
    if programs is None:
        raise RuntimeError("the engine published no serve.programs instant in the window")
    if scope not in programs:
        raise RuntimeError(f"serve.programs has no {scope!r} map: {sorted(programs)}")
    if program not in programs[scope]:
        raise RuntimeError(f"serve.programs' {scope!r} map has no program {program!r}: "
                           f"{sorted(programs[scope])}")
    return set(programs[scope][program])


def scoped_time(run, program: str, scope: str) -> tuple[float, float, int, int]:
    """Over every run of ``program`` that lies whole inside the traced
    window, on every device: (ns covered by the union of the intervals of
    the program's ops under ``scope``, ns of the runs, number of runs,
    number of such op events). Raises where the window holds no whole run."""
    ops = published_ops(run, scope, program)
    t = run.trace
    covered = total = 0.0
    n_runs = n_ops = 0
    for d in t.devices.values():
        runs = sorted((s, e) for n, s, e in d.modules
                      if n == program and s >= t.t0 and e <= t.t1)
        if not runs:
            continue
        n_runs += len(runs)
        total += sum(e - s for s, e in runs)
        starts = [s for s, _ in runs]
        inside = []
        for n, s, e in d.ops:
            i = bisect_right(starts, s) - 1
            if i >= 0 and e <= runs[i][1] and op_name(n).split(" ", 1)[0] in ops:
                inside.append((s, e))
        if inside:
            iv = np.array(inside, np.float64)
            covered += union_length(iv[:, 0], iv[:, 1])
            n_ops += len(inside)
    if not n_runs:
        raise RuntimeError(f"no whole run of {program!r} in the traced window")
    log = getattr(run.job, "log", None)
    if log is not None:
        log(f"{program} under {scope!r}: {n_runs} whole runs, {total * 1e-6:.3f} ms, "
            f"{n_ops} of {len(ops)} published ops' events, {covered * 1e-6:.3f} ms covered")
    return covered, total, n_runs, n_ops


def device_trace(run) -> bool:
    """Whether the run has a device trace to read: a traced run on a chip
    (a CPU trace holds no device plane)."""
    return run.trace is not None and bool(run.trace.devices)
