"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``); the mix names the job that drives the
window (``bench/jobs/<job>.py``); each per-layer metric is read by
``bench/metrics/<metric>.py``; the limits of the correctness check are in
``bench/limits/<cell>.json``. A new cell, mix, job or metric is new files
and new entries, and no edit of a file that is here.

The run finds a TPU with as many chips as the cell asks for, or fails.
Set-up (weights from the seed, the engine, its compilation and warm-up)
is timed from process start as ``setup_s``; then the window runs for
``--seconds``. With ``--trace 0`` the result carries the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, read from a profiler
trace of the first seconds of the window and from the recorder's events.
After the window the program's state is freed and what the window served
is compared with the plain float32 reference; each number compared is
printed beside its limit, as the last lines of standard error and as the
last key of the result line.

``--rehearse`` runs the same code on the CPU at a tiny size; every line
it prints says so. ``--control`` puts the control, the reference at the
precision below the configuration's, in the program's place: the same
served requests are read from it and judged by the same limits, so its
run has to come out not correct. The benchmark's own runs never use it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


class BenchError(Exception):
    pass


def load_cell(name: str, root: Path = ROOT) -> tuple[dict, dict, dict]:
    """(BENCHMARK.json, the cell's entry, its configuration entry)."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    confs = {c["name"]: c for c in spec["configs"]}
    return spec, cell, confs[cell["config"]]


def metric_entries(spec: dict, cell: dict, traced: bool) -> list[dict]:
    """The metrics this cell reports: end-to-end ones that name it (or name
    no cells), or per-layer ones that name it (or, naming no cells, move
    an end-to-end metric it reports)."""
    e2e = [m for m in spec["end_to_end"] if cell["name"] in m.get("workloads", [cell["name"]])]
    if not traced:
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if cell["name"] in m.get("workloads", [cell["name"]] if m["moves"] in mine else [])]


def _load_module(path: Path, what: str):
    if not path.is_file():
        raise BenchError(f"no {what} at {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, root: Path = BENCH):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    return _load_module(root / "metrics" / f"{name}.py", f"reader for metric {name!r}").read


def load_job(job: str, root: Path = BENCH):
    """The ``Job`` class of ``bench/jobs/<job>.py``."""
    return _load_module(root / "jobs" / f"{job}.py", f"job {job!r}").Job


def load_limits(cell: str, rehearse: bool, root: Path = BENCH) -> dict:
    path = root / "limits" / f"{cell}.json"
    if not path.is_file():
        raise BenchError(f"no limits for cell {cell!r} at {path}")
    lim = json.loads(path.read_text())
    return lim["rehearse"] if rehearse else lim["limits"]


def check_devices(chips: int, rehearse: bool):
    import jax

    devs = jax.devices()
    if not rehearse:
        if devs[0].platform != "tpu":
            raise BenchError(f"JAX found no TPU: its first device is {devs[0].platform!r}")
        if len(devs) < chips:
            raise BenchError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips] if len(devs) >= chips else devs


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return int(max(peaks))


def run(args, tamper=None) -> dict:
    from bench import traffic
    from bench.peaks import peaks_for
    from bench.trace import Profiler, load

    tag = " [rehearsal: cpu, tiny sizes, no TPU checked]" if args.rehearse else ""

    def log(msg: str) -> None:
        print(f"{msg}{tag}", file=sys.stderr, flush=True)

    spec, cell, conf_entry = load_cell(args.workload)
    conf = json.loads((ROOT / conf_entry["file"]).read_text())
    mix = traffic.load_mix(cell["traffic"])
    limits = load_limits(cell["name"], args.rehearse)
    entries = metric_entries(spec, cell, bool(args.trace))
    readers = {m["name"]: load_reader(m["name"]) for m in entries if m in spec["per_layer"]}
    Job = load_job(mix["job"])

    import jax

    if not args.rehearse:
        from repro.launch.compile_cache import enable_compile_cache

        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = check_devices(int(cell["chips"]), args.rehearse)
    job = Job(cell, conf, mix, args.seed, rehearse=args.rehearse, limits=limits, log=log)
    job.tamper = tamper
    job.setup()
    setup_s = time.perf_counter() - T_START
    log(f"setup_s {setup_s:.3f}")

    trace_dir = ROOT / ".bench_trace" / cell["name"]
    profiler = Profiler(trace_dir) if args.trace else None
    job.window(float(args.seconds), profiler)
    log(job.describe())
    mem = memory_peak(devs)
    trace = None
    if args.trace:
        trace = load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(trace.describe())
    job.release()

    checks = job.check(control=args.control)
    if args.control:  # the program's own readings, for the lower end of each limit
        for name, v, lim in job.check():
            log(f"program reading {name}: {v!r} (limit {lim!r})")
    attempted, failed = job.counts()
    correct = failed == 0 and all(not math.isnan(v) and v <= lim for _, v, lim in checks)

    device = dict(platform=devs[0].platform, kind=devs[0].device_kind, count=len(devs),
                  memory_peak_bytes=mem)
    metrics = {}
    breakdown = None
    if args.trace:
        kind = devs[0].device_kind if not args.rehearse else None
        view = SimpleNamespace(cell=cell, conf=conf, mix=mix, job=job, trace=trace,
                               peaks=peaks_for(kind) if kind else None,
                               rehearse=args.rehearse)
        for m in entries:
            v = readers[m["name"]](view)
            if v is not None:
                metrics[m["name"]] = dict(value=float(v), unit=m["unit"])
        device.update(busy_s=trace.busy_s(), window_s=trace.window_s)
        breakdown = dict(device_ops=trace.top_ops(10), idle_gaps=trace.idle_gaps(10))
    else:
        values = dict(job.end_to_end(), setup_s=setup_s)
        for m in entries:
            if m["name"] not in values:
                raise BenchError(f"job {mix['job']!r} does not measure {m['name']!r}")
            metrics[m["name"]] = dict(value=float(values[m["name"]]), unit=m["unit"])

    for name, v, lim in checks:
        log(f"check {name}: {v!r} limit {lim!r} {'ok' if v <= lim else 'FAILED'}")
    log(f"check failed_requests: {failed} limit 0 {'ok' if failed == 0 else 'FAILED'}")
    line = dict(correct=bool(correct), attempted=int(attempted), failed=int(failed),
                metrics=metrics, device=device)
    if breakdown is not None:
        line["breakdown"] = breakdown
    if args.rehearse:
        line["rehearsal"] = True
    if args.control:
        line["control"] = True
    line["checks"] = {name: dict(value=None if math.isnan(v) else v, limit=lim)
                      for name, v, lim in checks}
    line["checks"]["failed_requests"] = dict(value=failed, limit=0)
    return line


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a tiny size; never a measurement")
    ap.add_argument("--control", action="store_true",
                    help="judge the control in the program's place (for setting limits)")
    return ap.parse_args(argv)


def main(argv=None, tamper=None) -> int:
    args = parse(argv)
    try:
        line = run(args, tamper=tamper)
    except BenchError as e:
        print(f"error: {e}", file=sys.stderr, flush=True)
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
