"""Profiler control and the reduction from a profiler trace to numbers.

A traced run starts JAX's profiler a set time into the window and stops
it a few seconds later (the mix's ``trace_start`` and ``trace_seconds``);
the harness wraps that stretch in the host annotation
``bench.traced_window``. :func:`load` reads the ``.xplane.pb`` the
profiler wrote and keeps, on one timeline in nanoseconds:

* per device plane (``/device:TPU:<n>``): the ``XLA Ops`` events (ops nest:
  a ``while`` op spans its body's ops) and the ``XLA Modules`` events, one
  per executed program, named ``jit_<function>(<fingerprint>)``;
* the host's Python line (named after the interpreter, ``python`` or
  ``python3``): the profiler's Python tracer records every Python call,
  which is what labels an idle gap with what the host was doing, and the
  harness's annotations.

Device busy time is the union of the op intervals inside the traced window,
averaged over the devices used; the idle share is 1 - busy / window.
"""
from __future__ import annotations

import glob
import re
import shutil
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

WINDOW_ANNOTATION = "bench.traced_window"
_FINGERPRINT = re.compile(r"\(\d+\)$")


class Profiler:
    """Starts and stops one trace into ``directory``; ``stop`` is idempotent."""

    def __init__(self, directory: Path):
        self.directory = Path(directory)
        self.active = False
        self.started = False
        self._annotation = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        jax.profiler.start_trace(str(self.directory))
        self._annotation = jax.profiler.TraceAnnotation(WINDOW_ANNOTATION)
        self._annotation.__enter__()
        self.active = self.started = True

    def stop(self) -> None:
        import jax

        if not self.active:
            return
        self._annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False


def module_name(event_name: str) -> str:
    """``jit_sample_decode(1406...)`` -> ``jit_sample_decode``."""
    return _FINGERPRINT.sub("", event_name)


def op_name(event_name: str) -> str:
    """The short name of an HLO op event: its instruction name and result
    shape, e.g. ``fusion.8 bf16[28311552]``."""
    head = event_name.lstrip("%")
    name, _, rest = head.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{name} {shape}".strip()


def covered_segments(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """The union of the intervals as sorted disjoint (start, end) rows."""
    if len(starts) == 0:
        return np.zeros((0, 2))
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    # an interval opens a new covered stretch where it starts past the reach
    # of everything before it
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    seg_start = s[new]
    seg_end = np.append(reach[np.flatnonzero(new)[1:] - 1], reach[-1])
    return np.stack([seg_start, seg_end], axis=1)


def union_length(starts: np.ndarray, ends: np.ndarray) -> float:
    """Total length covered by the intervals [starts, ends)."""
    segs = covered_segments(starts, ends)
    return float(np.sum(segs[:, 1] - segs[:, 0]))


@dataclass
class Device:
    ops: list = field(default_factory=list)  # (name, start, end) ns
    modules: list = field(default_factory=list)  # (module name, start, end) ns


@dataclass
class Trace:
    t0: float  # traced window, ns
    t1: float
    devices: dict  # plane name -> Device
    python: list  # (name, start, end) ns of the host's Python calls
    host_lines: dict = field(default_factory=dict)  # host line name -> events

    def describe(self) -> str:
        dev = {n: (len(d.ops), len(d.modules)) for n, d in self.devices.items()}
        return (f"trace: window {self.window_s:.3f} s, device (ops, programs) {dev}, "
                f"host lines {self.host_lines}")

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def _clip(self, rows):
        return [(n, max(s, self.t0), min(e, self.t1)) for n, s, e in rows
                if e > self.t0 and s < self.t1]

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        per = []
        for d in self.devices.values():
            rows = self._clip(d.ops)
            s = np.array([r[1] for r in rows], np.float64)
            e = np.array([r[2] for r in rows], np.float64)
            per.append(union_length(s, e) * 1e-9)
        return float(np.mean(per)) if per else 0.0

    def module_spans(self, prefix: str, whole: bool = True) -> list:
        """(start, end) ns of the programs whose name starts with ``prefix``
        on every device; with ``whole`` only those inside the window."""
        out = []
        for d in self.devices.values():
            for n, s, e in d.modules:
                if n.startswith(prefix) and (not whole or (s >= self.t0 and e <= self.t1)):
                    out.append((s, e))
        return out

    def module_time_s(self, prefix: str) -> float:
        """Device seconds inside the window of the programs named ``prefix*``."""
        tot = 0.0
        for d in self.devices.values():
            for n, s, e in self._clip(d.modules):
                if n.startswith(prefix):
                    tot += e - s
        return tot * 1e-9

    def top_ops(self, k: int = 10) -> list:
        """The ``k`` ops that took most device time, by self time (a parent
        op's time less that of the ops nested in it), summed by short name,
        averaged over the devices."""
        tot: dict = {}
        for d in self.devices.values():
            rows = sorted(self._clip(d.ops), key=lambda r: (r[1], -r[2]))
            stack = []  # open parents: [end, name, self time]
            for n, s, e in rows:
                while stack and stack[-1][0] <= s:
                    end, nm, self_t = stack.pop()
                    tot[nm] = tot.get(nm, 0.0) + self_t
                if stack:
                    stack[-1][2] -= e - s
                stack.append([e, op_name(n), e - s])
            for end, nm, self_t in stack:
                tot[nm] = tot.get(nm, 0.0) + self_t
        n_dev = max(1, len(self.devices))
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, t * 1e-9 / n_dev] for name, t in top]

    def idle_gaps(self, k: int = 10) -> list:
        """The ``k`` longest stretches of the window in which no op ran on
        the first device, each named by the three innermost host Python
        calls that cover its middle, innermost first; where none does (the
        host runs the body of a call that began before the trace), by the
        call that ended last before it."""
        if not self.devices:
            return []
        d = next(iter(self.devices.values()))
        rows = self._clip(d.ops)
        segs = covered_segments(np.array([r[1] for r in rows], np.float64),
                                np.array([r[2] for r in rows], np.float64))
        edges = np.concatenate([[self.t0], segs.ravel(), [self.t1]]).reshape(-1, 2)
        gaps = [(float(a), float(b)) for a, b in edges if b > a]
        gaps.sort(key=lambda g: g[0] - g[1])
        py = [r for r in self.python if r[0] != WINDOW_ANNOTATION]
        out = []
        for a, b in gaps[:k]:
            mid = (a + b) / 2
            inner = sorted((r for r in py if r[1] <= mid <= r[2]), key=lambda r: r[2] - r[1])
            if inner:
                label = " < ".join(r[0] for r in inner[:3])
            else:
                before = [r for r in py if r[2] <= mid]
                label = ("after " + max(before, key=lambda r: r[2])[0]) if before else "no Python call"
            out.append([f"host: {label}", (b - a) * 1e-9])
        return out


def load(directory: Path) -> Trace:
    """Read the one ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData

    files = glob.glob(str(Path(directory) / "**" / "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file under {directory}, found {files}")
    return reduce(ProfileData.from_file(files[0]))


def reduce(profile) -> Trace:
    devices: dict = {}
    python: list = []
    host_lines: dict = {}
    window = None
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:") or plane.name.startswith("/device:GPU:"):
            dev = devices.setdefault(plane.name, Device())
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events]
                elif line.name == "XLA Modules":
                    dev.modules = [(module_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                                   for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                rows = [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
                host_lines[line.name] = len(rows)
                if line.name.startswith("python"):  # named after the interpreter
                    python = rows
                # the annotation lands on the Python line or on its thread's line
                window = window or next((r for r in rows if r[0] == WINDOW_ANNOTATION), None)
    if window is None:
        raise RuntimeError(f"the trace holds no {WINDOW_ANNOTATION!r} annotation")
    return Trace(t0=window[1], t1=window[2], devices=devices, python=python,
                 host_lines=host_lines)
