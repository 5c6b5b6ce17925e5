"""Device time of one fused decode dispatch: the mean length of the
``jit_sample_decode`` program runs that lie whole inside the traced window."""


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.module_spans("jit_sample_decode")
    if not spans:
        return None
    return 1e-6 * sum(e - s for s, e in spans) / len(spans)
