"""Share of the fused decode program's device time spent in the Mamba
mixers: over every ``jit_sample_decode`` run that lies whole inside the
traced window, the union of the intervals of the ops the engine publishes
under the ``mamba`` name scope (``models/ssm.py``) for that program, over
the runs' total length (``bench/scoped.py``). Nothing without a device
trace; an error where the map or the program is missing."""

from bench.scoped import device_trace, scoped_time

PROGRAM = "jit_sample_decode"


def read(run):
    if not device_trace(run):
        return None
    covered, total, _, _ = scoped_time(run, PROGRAM, "mamba")
    return 100.0 * covered / total
