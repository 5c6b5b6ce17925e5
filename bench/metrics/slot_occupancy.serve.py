"""Share of decode slots that carried a request, over the window's decode
dispatches: the sum of each ``decode_step`` span's ``n_active`` over
dispatches x slots, from the serving engine's recorder."""


def read(run):
    t = run.job.times
    if not t.decode_steps:
        return None
    return 100.0 * t.tokens / (t.decode_steps * run.job.sv["num_slots"])
