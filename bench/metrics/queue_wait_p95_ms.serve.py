"""p95 of the time a request waits to be admitted: each ``queue`` span
(its ``enqueue`` instant to the start of its first prefill dispatch) of
the requests that arrived in the window. A request still queued at the
cut enters with the wait it had reached, as ``bench/requests.py`` does
for TTFT. Nothing where the program records no ``queue`` spans."""

import numpy as np


def read(run):
    job = run.job
    arrived, waited = {}, {}
    for e in job.events:
        if e.kind == "instant" and e.name == "enqueue" and e.ts <= job.t_w1:
            arrived[e.args["rid"]] = e.ts
        elif e.kind == "span" and e.name == "queue":
            waited[e.args["rid"]] = e.dur
    if not waited or not arrived:
        return None
    waits = [waited.get(rid, job.t_w1 - ts) for rid, ts in arrived.items()]
    return 1e3 * float(np.percentile(waits, 95))
