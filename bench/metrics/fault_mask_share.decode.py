"""Share of the fused decode program's device time spent building and
applying the fault mask.

The engine publishes, in one ``serve.programs`` instant at the start of
``serve()``, the names of each program's ops whose HLO ``op_name`` lies
under the ``fault_mask`` name scope (``core/masking.py``). Over every
``jit_sample_decode`` run that lies whole inside the traced window: the
union of the intervals of those ops (so a nested op counts once), over the
runs' total length. 0.0 where the program has no mask ops (a healthy
chip); nothing where the program publishes no map."""

from bisect import bisect_right

import numpy as np

from bench.trace import op_name, union_length

PROGRAM = "jit_sample_decode"


def read(run):
    t = run.trace
    if t is None:
        return None
    programs = next((e.args for e in run.job.events
                     if e.kind == "instant" and e.name == "serve.programs"), None)
    if programs is None:
        return None
    mask = set(programs["fault_mask"].get(PROGRAM, ()))
    total = masked = 0.0
    for d in t.devices.values():
        runs = sorted((s, e) for n, s, e in d.modules
                      if n == PROGRAM and s >= t.t0 and e <= t.t1)
        if not runs:
            continue
        total += sum(e - s for s, e in runs)
        starts = [s for s, _ in runs]
        inside = []
        for n, s, e in d.ops:
            i = bisect_right(starts, s) - 1
            if i >= 0 and e <= runs[i][1] and op_name(n).split(" ", 1)[0] in mask:
                inside.append((s, e))
        if inside:
            iv = np.array(inside, np.float64)
            masked += union_length(iv[:, 0], iv[:, 1])
    if total <= 0:
        return None
    return 100.0 * masked / total
