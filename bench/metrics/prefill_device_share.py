"""Share of the device's busy time spent in the prefill programs (packed
bucket admission and chunked prefill) inside the traced window."""

PREFILL = ("jit__packed_admit_fn", "jit__prefill_chunk_fn")


def read(run):
    if run.trace is None:
        return None
    busy = run.trace.busy_s()
    if busy <= 0:
        return None
    return 100.0 * sum(run.trace.module_time_s(p) for p in PREFILL) / busy
