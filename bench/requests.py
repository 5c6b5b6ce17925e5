"""Request times rebuilt from the serving engine's raw recorder events.

The engine records an ``enqueue`` instant when a request's arrival step
comes, an ``admit`` span (packed prefill) or ``chunk`` spans (chunked
prefill, the last one ``final``) on the request's slot track, one
``decode_step`` span per fused decode dispatch, and a ``retire`` instant.
From those alone:

* arrival is the ``enqueue`` instant;
* the first token is the end of the first ``decode_step`` that starts
  after the request's admission (or final chunk) has ended;
* every later ``decode_step`` until ``retire`` is one more token.

A request that arrived in the window and has no first token at the cut
enters the TTFT sample with the wait it had reached, so a stall cannot
shorten the tail. Inter-token gaps are those of every request, whole or
cut.
"""
from __future__ import annotations

from bisect import bisect_left
from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Times:
    arrived: dict  # rid -> arrival (recorder seconds), requests arriving in the window
    first: dict  # rid -> first-token time, where one came before the cut
    served: dict  # rid -> tokens it was served in the window
    tokens: int  # tokens emitted (sum of n_active) by dispatches that ended in the window
    gaps: np.ndarray  # every inter-token gap, seconds
    ttft: np.ndarray  # one per arrived request, censored at the cut
    decode_steps: int

    @property
    def n_first(self) -> int:
        return len(self.first)

    @staticmethod
    def _pct(x: np.ndarray, q: float) -> float:
        return float(np.percentile(x, q)) if len(x) else float("nan")

    @property
    def tpot_p50(self) -> float:
        return self._pct(self.gaps, 50)

    @property
    def tpot_p95(self) -> float:
        return self._pct(self.gaps, 95)

    @property
    def ttft_p50(self) -> float:
        return self._pct(self.ttft, 50)

    @property
    def ttft_p95(self) -> float:
        return self._pct(self.ttft, 95)


def reconstruct(events, t0: float, t1: float) -> Times:
    """Times of the requests that arrived in the window [t0, t1] (recorder
    seconds), from its events."""
    arrived, ready, retired = {}, {}, {}
    steps = []  # (start, end, n_active) of decode dispatches
    for e in events:
        if e.ts < t0:
            continue
        if e.kind == "instant" and e.name == "enqueue" and e.ts <= t1:
            arrived[e.args["rid"]] = e.ts
        elif e.kind == "instant" and e.name == "retire":
            retired[e.args["rid"]] = e.ts
        elif e.kind == "span" and e.name == "admit":
            ready[e.args["rid"]] = e.ts + e.dur
        elif e.kind == "span" and e.name == "chunk" and e.args.get("final"):
            ready[e.args["rid"]] = e.ts + e.dur
        elif e.kind == "span" and e.name == "decode_step" and e.ts + e.dur <= t1:
            steps.append((e.ts, e.ts + e.dur, int(e.args["n_active"])))
    steps.sort()
    starts = [s for s, _, _ in steps]
    ends = np.array([e for _, e, _ in steps])
    first, served, gaps, ttft = {}, {}, [], []
    for rid, t_arr in arrived.items():
        if rid in ready:
            i = bisect_left(starts, ready[rid])
            stop = retired.get(rid, np.inf)
            j = i + int(np.searchsorted(ends[i:], stop, side="right"))
            mine = ends[i:j]
            served[rid] = len(mine)
            if len(mine):
                first[rid] = float(mine[0])
                gaps.extend(np.diff(mine).tolist())
        ttft.append(first.get(rid, t1) - t_arr)
    return Times(
        arrived=arrived,
        first=first,
        served=served,
        tokens=sum(n for _, _, n in steps),
        gaps=np.asarray(gaps, np.float64),
        ttft=np.asarray(ttft, np.float64),
        decode_steps=len(steps),
    )
