"""The whole serve step's share of the chip's peak: forward FLOPs of every
prompt token prefilled and every token generated in the window, from the
configuration's shapes (``bench/flops.py``; fault masks not counted), over
the window's length times the chip's peak bf16 FLOP/s."""

from bench.flops import prompt_flops, token_flops


def read(run):
    if run.peaks is None:
        return None
    job = run.job
    model = job.model
    flops = 0.0
    for e in job.events:
        if e.kind != "span":
            continue
        if e.name == "admit":
            flops += prompt_flops(model, 0, int(e.args["prompt_len"]))
        elif e.name == "chunk":
            flops += prompt_flops(model, int(e.args["start"]), int(e.args["valid"]),
                                  logits=bool(e.args["final"]))
    for rid, n in job.times.served.items():
        p = len(job.stream.tokens[rid])
        flops += sum(token_flops(model, p + k) for k in range(1, n + 1))
    return 100.0 * flops / (job.window_s * run.peaks.flops)
