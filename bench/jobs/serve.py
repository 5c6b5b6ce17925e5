"""Serve job: one chip serves a seeded request stream through the program's
``ContinuousBatchingEngine`` for ``--seconds``.

Set-up makes the weights on the device from the seed, builds the engine
with its own ``Recorder`` attached (in every run, traced or not, so both
time the same program), compiles its closed program set (``warmup()``)
and serves a few requests that touch every prefill bucket, the chunked
path, decode and the ABFT probe, so that nothing compiles or runs for the
first time inside the window.

The window serves the mix's stream, arrivals clocked in decode steps, and
ends from ``on_step`` once ``--seconds`` have passed: the engine has no
deadline of its own, so ``on_step`` raises and the harness reads what the
interrupted ``serve()`` had produced from its frame. Every request's times
are rebuilt from the recorder's raw events (``bench/requests.py``).

``correct`` compares what the window served with the float32 reference
(``bench/reference/model.py``): for a sample of the served requests drawn
from the seed, the longest among them, the reference runs once over each
prompt and its served tokens, and two numbers are compared with their
limits (``bench/limits/<cell>.json``):

* ``logit_gap``: the widest gap by which a served (greedy) token's logit
  lies below the reference's best logit at that position;
* ``logprob_err``: the widest distance between the log-probability the
  engine returned for a served token and the reference's.
"""
from __future__ import annotations

import gc
import time
from dataclasses import replace

import jax
import numpy as np

from bench import traffic
from bench.reference import model as ref
from bench.requests import reconstruct

# the program's ArchConfig field for each key of a configuration's model block
ARCH_FIELDS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "resolved_head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
    "qk_norm": "qk_norm",
}

# CPU rehearsal: the same code at a size the CPU runs in seconds
REHEARSE_MODEL = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=16, intermediate_size=96,
                      vocab_size=256)
REHEARSE_SERVE = dict(num_slots=4, page_size=8, max_pages_per_seq=12, num_pages=49,
                      prefill_buckets=[16, 32], chunk_size=32, max_pack=4)
REHEARSE_MIX = dict(prompt_len=dict(median=24, sigma=1.0, min=4, max=80),
                    output_len=dict(median=6, sigma=0.5, min=2, max=12), pool=8192)


class WindowClosed(Exception):
    """Raised from ``on_step`` to end a serve at the window's close."""


def program_config(conf: dict, rehearse: bool = False):
    """The program's ArchConfig for a configuration file, checked key by key
    against the file, so that the file states what runs. The norm's epsilon
    is an option of the ArchConfig, so it is set from the file."""
    from repro.configs import get_arch

    model = conf["model"]
    cfg = replace(get_arch(conf["arch"]), norm_eps=float(model["rms_norm_eps"]))
    if rehearse:
        cfg = replace(cfg, num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                      head_dim=16, d_ff=96, vocab_size=256, array_rows=16, array_cols=16)
        model = {**model, **REHEARSE_MODEL}
    want = {f: model[k] for k, f in ARCH_FIELDS.items() if k in model}
    want.update(array_rows=conf["array"][0], array_cols=conf["array"][1],
                dtype=conf["dtype"], param_dtype=conf["param_dtype"])
    if rehearse:
        want.update(array_rows=16, array_cols=16)
    have = {f: getattr(cfg, f) for f in want}
    bad = {f: (have[f], want[f]) for f in want if have[f] != want[f]}
    if bad or cfg.activation != "swiglu" or cfg.family != "dense":
        raise ValueError(f"{conf['name']}: the program's config differs from the file: {bad}")
    return cfg


def _interrupted_table(exc: BaseException):
    """The slot table of the ``serve()`` call that ``exc`` interrupted."""
    tb = exc.__traceback__
    table = None
    while tb is not None:
        if tb.tb_frame.f_code.co_name == "serve":
            table = tb.tb_frame.f_locals.get("table", table)
        tb = tb.tb_next
    if table is None:
        raise RuntimeError("found no slot table in the interrupted serve()")
    return table


class Job:
    def __init__(self, cell: dict, conf: dict, mix: dict, seed: int, *,
                 rehearse: bool = False, limits: dict | None = None, log=print):
        self.seed, self.log = seed, log
        self.model = {**conf["model"], **(REHEARSE_MODEL if rehearse else {})}
        self.sv = dict(REHEARSE_SERVE if rehearse else conf["serve"])
        self.mix = {**mix, **(REHEARSE_MIX if rehearse else {})}
        self.limits = limits or {}
        self.cfg = program_config(conf, rehearse)
        self.tamper = None  # tests break the timed path through this hook
        self.recorder_capacity = 1 << 24  # events; a run that drops one fails

    # -- set-up -------------------------------------------------------------

    def setup(self) -> None:
        from repro.core import from_fault_map
        from repro.core.faults import FaultMap
        from repro.obs import Recorder
        from repro.serve import ContinuousBatchingEngine, Request

        ss = np.random.SeedSequence(self.seed)
        s_weights, s_chip, s_traffic, self._s_sample = ss.spawn(4)
        key = jax.random.PRNGKey(int(s_weights.generate_state(1)[0] >> 1))
        self.params = jax.jit(lambda k: ref.make_params(self.model, k))(key)
        jax.block_until_ready(self.params)

        chip = self.mix["chip"]
        r, c = self.cfg.array_rows, self.cfg.array_cols
        self.ok = None
        ctx = None
        if chip["fault_rate"] > 0:
            rng = np.random.default_rng(s_chip)
            faulty = np.zeros(r * c, bool)
            faulty[rng.choice(r * c, int(round(chip["fault_rate"] * r * c)), replace=False)] = True
            faulty = faulty.reshape(r, c)
            self.ok = ~faulty
            ctx = from_fault_map(FaultMap(faulty), mode=chip["mode"])

        sv = self.sv
        self.stream = traffic.generate(self.mix, s_traffic, self.cfg.vocab_size, sv["num_slots"])
        self.rec = Recorder(capacity=self.recorder_capacity)
        self.engine = ContinuousBatchingEngine(
            self.cfg, self.params, ctx, num_slots=sv["num_slots"],
            page_size=sv["page_size"], num_pages=sv["num_pages"],
            max_pages_per_seq=sv["max_pages_per_seq"],
            prefill_buckets=sv["prefill_buckets"], chunk_size=sv["chunk_size"],
            max_pack=sv["max_pack"], recorder=self.rec,
            probe_every=self.mix.get("probe_every"),
        )
        self.engine.warmup()
        if self.tamper is not None:
            self.tamper(self)
        # one request per prefill bucket and one chunked prompt, each at its
        # own arrival step, decoding past the first probe
        buckets = sv["prefill_buckets"]
        lens = [max(1, b - 3) for b in buckets] + [buckets[-1] + sv["chunk_size"] // 2]
        steps = (self.mix.get("probe_every") or 1) + len(lens) + 1
        rng = np.random.default_rng(0)
        warm = [Request(i, rng.integers(0, self.cfg.vocab_size, n), steps, arrival=i)
                for i, n in enumerate(lens)]
        self.engine.serve(warm)
        self.fallback0 = self.engine.compile_counts()["jit_fallback"]
        self.requests = [
            Request(i, self.stream.tokens[i], int(self.stream.output_lens[i]),
                    arrival=int(self.stream.arrivals[i]))
            for i in range(len(self.stream))
        ]

    # -- the window -----------------------------------------------------------

    def window(self, seconds: float, profiler=None) -> None:
        rec = self.rec
        # the traced stretch: [trace_start, trace_start + trace_seconds] of the window
        trace_from = min(float(self.mix.get("trace_start", 0.0)), seconds)
        trace_to = min(trace_from + float(self.mix.get("trace_seconds", seconds)), seconds)
        t0 = time.perf_counter()
        self.t_w0 = rec.now()

        def on_step(clock):
            now = time.perf_counter() - t0
            if profiler is not None:
                if not profiler.started and now >= trace_from:
                    profiler.start()
                elif profiler.active and now >= trace_to:
                    profiler.stop()
            if now >= seconds:
                raise WindowClosed()

        try:
            self.engine.serve(self.requests, temperature=float(self.mix.get("temperature", 0.0)),
                              on_step=on_step)
            raise RuntimeError(
                f"the stream of {len(self.requests)} requests ran out inside the window: "
                "grow the mix's pool")
        except WindowClosed as e:
            self.t_w1 = rec.now()
            table = _interrupted_table(e)
            self.finished = {rid: (o.tokens, o.logprobs) for rid, o in table.outputs.items()}
            self.inflight = {
                r.rid: (np.asarray(table._tok[r.rid], np.int64),
                        np.asarray(table._lp[r.rid], np.float64))
                for r in table.slots if r is not None and table._tok.get(r.rid)
            }
            del table
            e.__traceback__ = None
        finally:
            if profiler is not None and profiler.active:
                profiler.stop()
        self.window_s = self.t_w1 - self.t_w0
        if rec.events.dropped:
            raise RuntimeError(f"the recorder dropped {rec.events.dropped} events")
        self.fallback = self.engine.compile_counts()["jit_fallback"] - self.fallback0
        self.log(f"window: {self.window_s:.3f} s, programs compiled in it: {self.fallback}")
        if self.fallback:
            raise RuntimeError(f"{self.fallback} programs compiled inside the window")
        self.events = [e for e in rec.event_list() if e.ts >= self.t_w0]
        self.times = reconstruct(self.events, self.t_w0, self.t_w1)

    def release(self) -> None:
        """Free the program's state before the reference runs."""
        self.engine = None
        self.rec = None
        gc.collect()

    # -- results ------------------------------------------------------------------

    def counts(self) -> tuple[int, int]:
        """(attempted, failed): requests that arrived in the window, and
        finished requests that came back short of their budget."""
        short = sum(1 for rid, (toks, _) in self.finished.items()
                    if len(toks) != int(self.stream.output_lens[rid]))
        return len(self.times.arrived), short

    def end_to_end(self) -> dict:
        t = self.times
        return dict(
            serve_tokens_per_s=t.tokens / self.window_s,
            tpot_p95_ms=1e3 * t.tpot_p95,
            ttft_p95_ms=1e3 * t.ttft_p95,
        )

    def describe(self) -> str:
        t = self.times
        tpot = " ".join(f"p{q} {1e3 * t._pct(t.gaps, q):.3f}" for q in (50, 90, 95, 99))
        return (f"requests arrived {len(t.arrived)}, first token {t.n_first}, finished "
                f"{len(self.finished)}, tokens {t.tokens}, gaps {len(t.gaps)}, "
                f"tpot ms {tpot}, ttft p50 {1e3 * t.ttft_p50:.3f} ms")

    # -- correctness ----------------------------------------------------------

    def sample(self) -> list[int]:
        """The requests compared: drawn from the seed among those that
        served at least one token, the longest always among them."""
        served = {**self.inflight, **self.finished}
        rids = sorted(served, key=lambda r: (len(self.stream.tokens[r]) + len(served[r][0]), r))
        if not rids:
            return []
        rng = np.random.default_rng(self._s_sample)
        n = int(self.mix.get("check_requests", 8))
        rest = rids[:-1]
        pick = list(rng.choice(rest, min(n - 1, len(rest)), replace=False)) if rest else []
        return [rids[-1]] + [int(r) for r in pick]

    def readings(self, dot=ref.f32_dot, control: bool = False) -> dict:
        """Per sampled token: the reference's readings at the served token
        (or, for the control, at the control's own top token)."""
        served = {**self.inflight, **self.finished}
        w = ref.masked_weights(self.params, self.ok)
        s_pad = self.sv["max_pages_per_seq"] * self.sv["page_size"]
        model = self.model
        fn = jax.jit(lambda w, t, s: ref.token_readings(w, t, s, model, dot))
        fref = fn if not control else jax.jit(
            lambda w, t, s: ref.token_readings(w, t, s, model, ref.f32_dot))
        out = dict(gap=[], lp_err=[], tokens=0, requests=0)
        for rid in self.sample():
            prompt = np.asarray(self.stream.tokens[rid], np.int32)
            toks, lps = served[rid]
            n = len(toks)
            seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
            pos = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
            t_pad = np.zeros(s_pad, np.int32)
            t_pad[: len(seq)] = seq
            s_tok = np.zeros(s_pad, np.int32)
            s_tok[pos] = np.asarray(toks, np.int32)
            r = jax.device_get(fn(w, t_pad, s_tok))
            if control:
                # the lower precision's own first choice at each position,
                # read against the reference
                s_tok[pos] = r["top"][pos]
                lse = r["at"][pos] - r["logprob"][pos]
                lp_ctrl = r["best"][pos] - lse
                rr = jax.device_get(fref(w, t_pad, s_tok))
                gap = rr["best"][pos] - rr["at"][pos]
                err = np.abs(lp_ctrl - rr["logprob"][pos])
            else:
                gap = r["best"][pos] - r["at"][pos]
                err = np.abs(np.asarray(lps, np.float64) - r["logprob"][pos])
            out["gap"].append(float(np.max(gap)))
            out["lp_err"].append(float(np.max(err)))
            out["tokens"] += n
            out["requests"] += 1
        return out

    def check(self, control: bool = False) -> list[tuple[str, float, float]]:
        """(name, reading, limit) of each number compared; with ``control``
        the readings are the control's, in the program's place."""
        r = self.readings(dot=ref.fp8_dot, control=True) if control else self.readings()
        who = "the control (float8 e4m3)" if control else "the program"
        self.log(f"compared {who} on {r['requests']} requests, {r['tokens']} served tokens")
        lim = self.limits
        nan = float("nan")
        return [
            ("logit_gap", max(r["gap"], default=nan), float(lim.get("logit_gap", 0.0))),
            ("logprob_err", max(r["lp_err"], default=nan), float(lim.get("logprob_err", 0.0))),
        ]
