"""Serve job for a model whose stack interleaves Mamba layers with
attention (Jamba): the ``serve`` job's window, timings and comparison, on
the program's ``ContinuousBatchingEngine`` and nothing else.

The architecture is resolved only through the program's registry
(``repro.configs.get_arch``) and served only through the continuous
engine, so a program that does not know the model fails at set-up. The
configuration file holds the published ``config.json`` keys at its top
level (``bench/configs/<config>.json``); the program's ``ArchConfig`` is
checked against them key by key.

``correct`` compares what the window served with the float32 reference
(``bench/reference/jamba.py``), as the ``serve`` job does with its own:
``logit_gap`` and ``logprob_err`` over a sample of the served requests
drawn from the seed, the longest among them (``serve.Job.check``, which
reads :meth:`Job.readings` here). The chip is healthy: a mix with faults
is refused, as the reference models none.
"""
from __future__ import annotations

from dataclasses import replace

import jax
import numpy as np

from bench import traffic
from bench.jobs import serve
from bench.reference import jamba as ref
from bench.reference.model import f32_dot

# the program's ArchConfig field for each key of the configuration
ARCH_FIELDS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "attn_layer_period": "attn_layer_period",
    "attn_layer_offset": "attn_layer_offset",
    "mamba_d_state": "ssm_state",
    "mamba_d_conv": "ssm_conv",
    "mamba_expand": "ssm_expand",
    "mamba_dt_rank": "ssm_dt_rank",
}
# what the program fixes and the file has to state alike
FIXED = {"hidden_act": "silu", "mamba_conv_bias": True, "mamba_proj_bias": False,
         "num_experts": 1}

# CPU rehearsal: one period of three layers, attention in the middle
REHEARSE_MODEL = dict(num_hidden_layers=3, attn_layer_period=3, attn_layer_offset=1,
                      hidden_size=64, num_attention_heads=4, num_key_value_heads=1,
                      intermediate_size=96, vocab_size=256, mamba_d_state=8,
                      mamba_dt_rank=8)
REHEARSE_SERVE = dict(serve.REHEARSE_SERVE, max_pack=1)


def model_of(conf: dict, rehearse: bool = False) -> dict:
    """The configuration's model keys (its top level), at the rehearsal's
    sizes with ``rehearse``."""
    keys = set(ARCH_FIELDS) | set(FIXED) | {"mamba_d_conv"}
    return {**{k: conf[k] for k in keys}, **(REHEARSE_MODEL if rehearse else {})}


def program_config(conf: dict, rehearse: bool = False):
    """The program's ArchConfig for a configuration file, checked key by key
    against it. Depth and the norm's epsilon are set from the file, as the
    cut and an option of the ArchConfig."""
    from repro.configs import get_arch

    model = model_of(conf, rehearse)
    cfg = replace(get_arch(conf["arch"]), num_layers=int(model["num_hidden_layers"]),
                  norm_eps=float(model["rms_norm_eps"]))
    if rehearse:
        cfg = replace(cfg, **{ARCH_FIELDS[k]: v for k, v in REHEARSE_MODEL.items()},
                      head_dim=0, array_rows=16, array_cols=16)
    want = {f: model[k] for k, f in ARCH_FIELDS.items()}
    want.update(array_rows=16 if rehearse else conf["array"][0],
                array_cols=16 if rehearse else conf["array"][1],
                dtype=conf["dtype"], param_dtype=conf["param_dtype"],
                resolved_head_dim=model["hidden_size"] // model["num_attention_heads"],
                family="interleaved", activation="swiglu", use_rope=False,
                ssm_inner_norm=True, num_experts=0)
    bad = {f: (getattr(cfg, f), v) for f, v in want.items() if getattr(cfg, f) != v}
    bad.update({k: (model[k], v) for k, v in FIXED.items() if model[k] != v})
    if bad:
        raise ValueError(f"{conf['name']}: the program's config differs from the file: {bad}")
    return cfg


class Job(serve.Job):
    def __init__(self, cell: dict, conf: dict, mix: dict, seed: int, *,
                 rehearse: bool = False, limits: dict | None = None, log=print):
        self.seed, self.log = seed, log
        self.model = model_of(conf, rehearse)
        self.sv = dict(REHEARSE_SERVE if rehearse else conf["serve"])
        self.mix = {**mix, **(serve.REHEARSE_MIX if rehearse else {})}
        self.limits = limits or {}
        self.cfg = program_config(conf, rehearse)
        self.tamper = None
        self.recorder_capacity = 1 << 24
        if self.mix["chip"]["fault_rate"] > 0:
            raise ValueError("the hybrid serve job runs a healthy chip; its reference has no faults")

    def setup(self) -> None:
        from repro.obs import Recorder
        from repro.serve import ContinuousBatchingEngine, Request

        ss = np.random.SeedSequence(self.seed)
        s_weights, _, s_traffic, self._s_sample = ss.spawn(4)
        key = jax.random.PRNGKey(int(s_weights.generate_state(1)[0] >> 1))
        self.params = jax.jit(lambda k: ref.make_params(self.model, k))(key)
        jax.block_until_ready(self.params)
        self.ok = None

        sv = self.sv
        self.stream = traffic.generate(self.mix, s_traffic, self.cfg.vocab_size, sv["num_slots"])
        self.rec = Recorder(capacity=self.recorder_capacity)
        self.engine = ContinuousBatchingEngine(
            self.cfg, self.params, None, num_slots=sv["num_slots"],
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

    def readings(self, dot=f32_dot, control: bool = False) -> dict:
        """Per sampled token: the reference's readings at the served token
        (or, for the control, at the control's own top token)."""
        served = {**self.inflight, **self.finished}
        s_pad = self.sv["max_pages_per_seq"] * self.sv["page_size"]
        model = self.model
        fn = jax.jit(lambda w, t, s: ref.token_readings(w, t, s, model, dot))
        fref = jax.jit(lambda w, t, s: ref.token_readings(w, t, s, model, f32_dot))
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
            r = jax.device_get(fn(self.params, t_pad, s_tok))
            if control:
                # the lower precision's own first choice at each position,
                # read against the reference
                s_tok[pos] = r["top"][pos]
                lse = r["at"][pos] - r["logprob"][pos]
                lp_ctrl = r["best"][pos] - lse
                rr = jax.device_get(fref(self.params, t_pad, s_tok))
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
