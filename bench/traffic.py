"""The one traffic generator: reads a mix file of parameters and a seed.

The stream is made of blocks of ``block`` requests. Every block holds the
same multiset of prompt lengths, output lengths and inter-arrival gaps,
taken at evenly spaced quantiles of the mix's distributions, each block
in its own order drawn from the mix's ``order_seed``. The run's seed
draws only the prompts' token ids. So every seed does the same work, also
in a window that sees only the first block or two (as the faulty-chip
cell does), and the spread between runs is the system's, not the
sample's.

Arrivals are clocked in decode steps (``Request.arrival`` of the serving
engine), at ``load`` x slots / mean output length requests per step: a
rate relative to slot capacity, which holds whatever a step costs.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str, root: Path = TRAFFIC_DIR) -> dict:
    path = root / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    mix = json.loads(path.read_text())
    mix["name"] = name
    return mix


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the evenly spaced quantiles of a lognormal with the
    given median and sigma, rounded and clipped to [min, max]."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    v = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    return np.clip(np.rint(v), int(spec["min"]), int(spec["max"])).astype(np.int64)


@dataclass(frozen=True)
class Stream:
    prompt_lens: np.ndarray  # (n,)
    output_lens: np.ndarray  # (n,)
    arrivals: np.ndarray  # (n,) decode-step index each request arrives at
    tokens: list  # n int32 arrays of prompt token ids

    def __len__(self) -> int:
        return len(self.prompt_lens)


def generate(mix: dict, seed: int, vocab_size: int, slots: int, n: int | None = None) -> Stream:
    """The request stream of one run: sizes, gaps and their order fixed by
    the mix, the prompts' token ids drawn from ``seed``."""
    block = int(mix["block"])
    blocks = -(-int(n or mix["pool"]) // block)
    n = blocks * block
    order = np.random.default_rng(int(mix["order_seed"]))

    def shuffled(values):
        return np.concatenate([values[order.permutation(block)] for _ in range(blocks)])

    out_q = quantile_lengths(mix["output_len"], block)
    rate = float(mix["load"]) * slots / float(out_q.mean())
    u = (np.arange(block) + 0.5) / block
    prompts = shuffled(quantile_lengths(mix["prompt_len"], block))
    outputs = shuffled(out_q)
    gaps = shuffled(-np.log1p(-u) / rate)
    arrivals = np.floor(np.cumsum(gaps) - gaps[0]).astype(np.int64)
    rng = np.random.default_rng(seed)
    flat = rng.integers(0, vocab_size, int(prompts.sum()), dtype=np.int32)
    tokens = np.split(flat, np.cumsum(prompts)[:-1])
    return Stream(prompts, outputs, arrivals, tokens)
