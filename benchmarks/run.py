"""Benchmark harness entry point — one function per paper table plus the
roofline summary. Prints ``name,us_per_call,derived`` CSV and exits
non-zero when any phase raises."""
from __future__ import annotations


def main() -> int:
    import benchmarks.kernel_bench as kb
    import benchmarks.paper_tables as pt
    from benchmarks import roofline
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    failed = kb.print_rows(pt.ALL + kb.ALL)

    # roofline summary over dry-run artifacts, when there are any
    rows = roofline.analyze("experiments/dryrun", "pod1")
    if rows:
        print()
        print(roofline.table(rows))
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
