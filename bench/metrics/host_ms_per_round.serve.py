"""Host time of one scheduler round: the median, over the window's
``serve_round`` spans, of the round's length less the time it spent
waiting on the device (its ``prefill.wait`` and ``decode.wait`` spans).

Rounds that overlap the profiled stretch (the mix's ``trace_start`` and
``trace_seconds`` from the window's start) are left out: the profiler's
Python tracer slows the host there. Nothing where the program records no
rounds."""

from bisect import bisect_right

import numpy as np

WAITS = ("prefill.wait", "decode.wait")


def read(run):
    job = run.job
    rounds = sorted((e.ts, e.ts + e.dur) for e in job.events
                    if e.kind == "span" and e.name == "serve_round")
    lo = job.t_w0 + float(job.mix.get("trace_start", 0.0))
    hi = lo + float(job.mix.get("trace_seconds", job.window_s))
    rounds = [r for r in rounds if r[1] < lo or r[0] > hi]
    if not rounds:
        return None
    starts = [s for s, _ in rounds]
    host = [e - s for s, e in rounds]
    for e in job.events:
        if e.kind == "span" and e.name in WAITS:
            i = bisect_right(starts, e.ts) - 1
            if i >= 0 and e.ts + e.dur <= rounds[i][1]:
                host[i] -= e.dur
    return 1e3 * float(np.median(host))
