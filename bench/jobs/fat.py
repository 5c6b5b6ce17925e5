"""FAT job: the eFAT paper's consolidated retraining (its Step 4) of a fleet
of faulty chips, through the program's ``LMFATTrainer.train_batch``.

A plan is the mix's ``jobs`` chips, their fault rates at evenly spaced
points of the mix's range and each its budget of steps by the mix's law
(``ceil(steps x rate / at_rate)``), so every plan and every seed does the
same work; the seed draws the weights and where each chip's faults lie.
``train_batch`` packs a plan into populations (the program's scheduler)
and trains each member from the same base weights, with its chip's fault
mask, for its own budget.

Set-up makes the weights on the device from the seed and builds the
trainer at the configuration's ``fat`` sizes (the program compiles its fit
and eval programs there). It then drives the trainer through the first
steps: one ``train_batch`` call on the plan's jobs whose budget the check
follows (``check_budget`` steps or fewer), which also compiles the fit
program the window runs. The window runs whole plans on fresh fleets, one
call each, blocking on each, until ``--seconds`` have passed.
``fat_tokens_per_s`` counts the useful member-tokens: budget steps x batch
x sequence length, over the window's wall time.

``correct`` compares what set-up's call returned, the weights shipped to
each chip, with the float32 reference (``bench/reference/train.py``) run
from the same weights, masks and batches: per leaf, the gap between the
norms of the program's change of that leaf and the reference's, over the
reference's norm of that leaf or of the median leaf, whichever is larger
(``change_gap``, the worst leaf of every job). A change is taken from the
shipped base weights, so that the masked weights, zero on both sides, do
not count. Leaves that the reference's first gradient leaves at under a
thousandth of the median leaf's are left out. ``train_batch`` hands back
neither a loss nor the optimizer's state, so the change is all it can be
judged by.
"""
from __future__ import annotations

import gc
import math
import time

import jax
import numpy as np

from bench.jobs.serve import REHEARSE_MODEL, program_config
from bench.reference import model as ref
from bench.reference import train as rtrain

REHEARSE_FAT = dict(population=2, batch=2, seq_len=32)


def plan(mix: dict) -> tuple[np.ndarray, np.ndarray]:
    """(fault rates, step budgets) of a plan's jobs, fixed by the mix."""
    n = int(mix["jobs"])
    lo, hi = float(mix["fault_rate"]["min"]), float(mix["fault_rate"]["max"])
    rates = lo + (np.arange(n) + 0.5) / n * (hi - lo)
    law = mix["budget"]
    budgets = np.ceil(np.round(float(law["steps"]) * rates / float(law["at_rate"]), 9))
    return rates, budgets.astype(np.int64)


def fleet(seed, rates, shape) -> list[np.ndarray]:
    """One faulty-PE grid per rate: exactly round(rate x PEs) faulty PEs,
    placed at random from ``seed``."""
    rng = np.random.default_rng(seed)
    n = shape[0] * shape[1]
    out = []
    for r in rates:
        faulty = np.zeros(n, bool)
        faulty[rng.choice(n, int(round(r * n)), replace=False)] = True
        out.append(faulty.reshape(shape))
    return out


def change_gaps(got: dict, want: dict, base: dict, keep: set) -> dict:
    """{leaf: gap} of the norms of ``got - base`` and ``want - base``, over
    the reference's norm of the leaf or of the median leaf."""
    d_got = rtrain.leaf_norms(jax.tree_util.tree_map(lambda a, b: a - b, got, base))
    d_ref = rtrain.leaf_norms(jax.tree_util.tree_map(lambda a, b: a - b, want, base))
    med = float(np.median([d_ref[k] for k in keep]))
    return {k: abs(d_got[k] - d_ref[k]) / max(d_ref[k], med) for k in sorted(keep)}


class Job:
    def __init__(self, cell: dict, conf: dict, mix: dict, seed: int, *,
                 rehearse: bool = False, limits: dict | None = None, log=print):
        self.seed, self.log, self.mix = seed, log, mix
        self.model = {**conf["model"], **(REHEARSE_MODEL if rehearse else {})}
        self.fat = dict(REHEARSE_FAT if rehearse else conf["fat"])
        self.limits = limits or {}
        self.cfg = program_config(conf, rehearse)
        self.tamper = None  # tests break the timed path through this hook
        self._ref = {}

    # -- set-up -------------------------------------------------------------

    def _maps(self, faults):
        from repro.core.faults import FaultMap

        return [FaultMap(faulty) for faulty in faults]

    def setup(self) -> None:
        from repro.train.fat_trainer import LMFATTrainer

        mix, f = self.mix, self.fat
        if mix["mode"] != "fap":
            raise ValueError("train_batch trains under fault-aware pruning ('fap') only")
        s_weights, s_check, self._s_window = np.random.SeedSequence(self.seed).spawn(3)
        key = jax.random.PRNGKey(int(s_weights.generate_state(1)[0] >> 1))
        self.params0 = jax.jit(lambda k: ref.make_params(self.model, k))(key)
        jax.block_until_ready(self.params0)

        opt, law = mix["optimizer"], mix["data"]
        self.trainer = LMFATTrainer(
            self.cfg, seed=int(law["seed"]), batch_size=int(f["batch"]),
            seq_len=int(f["seq_len"]), lr=float(opt["lr"]), pretrain_steps=0,
            eval_batches=1, population_size=int(f["population"]),
        )
        have = self.trainer.opt_cfg
        want = dict(b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                    weight_decay=opt["weight_decay"], grad_clip_norm=opt["grad_clip_norm"])
        bad = {k: (getattr(have, k), v) for k, v in want.items() if getattr(have, k) != v}
        if bad or self.trainer.stream.noise != law["noise"]:
            raise ValueError(f"the program's optimizer or data law differs from the mix: {bad}")
        self.trainer.base_params = self.params0
        if self.tamper is not None:
            self.tamper(self)

        self.rates, self.budgets = plan(mix)
        pick = self.budgets <= int(mix["check_budget"])
        self.check_faults = fleet(s_check, self.rates[pick], (self.cfg.array_rows, self.cfg.array_cols))
        self.check_budgets = [int(b) for b in self.budgets[pick]]
        out = self.trainer.train_batch(self._maps(self.check_faults), self.check_budgets)
        self.check_out = jax.device_get(out)
        self.log(f"set-up trained {len(out)} jobs for {self.check_budgets} steps")

    # -- the window -----------------------------------------------------------

    def window(self, seconds: float, profiler=None) -> None:
        tokens_per_step = int(self.fat["batch"]) * int(self.fat["seq_len"])
        shape = (self.cfg.array_rows, self.cfg.array_cols)
        self.plans, self.tokens, self.jobs = 0, 0, 0
        self.plan_s = []
        t0 = time.perf_counter()
        while True:
            maps = self._maps(fleet(self._s_window.spawn(1)[0], self.rates, shape))
            if profiler is not None and not profiler.started:
                profiler.start()
            t = time.perf_counter()
            jax.block_until_ready(self.trainer.train_batch(maps, list(self.budgets)))
            self.plan_s.append(time.perf_counter() - t)
            if profiler is not None and profiler.active:
                profiler.stop()
            self.plans += 1
            self.jobs += len(maps)
            self.tokens += int(self.budgets.sum()) * tokens_per_step
            if time.perf_counter() - t0 >= seconds:
                break
        self.window_s = time.perf_counter() - t0

    def release(self) -> None:
        """Free the program's state before the reference runs."""
        self.trainer = None
        gc.collect()

    # -- results ------------------------------------------------------------------

    def counts(self) -> tuple[int, int]:
        """(attempted, failed): jobs trained in the window, and jobs of the
        check whose shipped weights hold a value that is not finite."""
        bad = sum(1 for p in self.check_out
                  if not all(np.isfinite(x).all() for x in jax.tree_util.tree_leaves(p)))
        return self.jobs, bad

    def end_to_end(self) -> dict:
        return dict(fat_tokens_per_s=self.tokens / self.window_s)

    def describe(self) -> str:
        return (f"window {self.window_s:.3f} s: {self.plans} plans of {len(self.budgets)} jobs "
                f"(budgets {list(map(int, self.budgets))}), {self.tokens} member-tokens, "
                f"plan seconds {[round(s, 3) for s in self.plan_s]}")

    # -- correctness ----------------------------------------------------------

    def reference(self, dot=ref.f32_dot) -> list[dict]:
        """The reference's shipped weights for each job of the check, and
        its readings, computed once per ``dot``."""
        if dot not in self._ref:
            t = rtrain.Trainer(self.model, self.mix["data"], self.mix["optimizer"],
                               int(self.fat["seq_len"]), int(self.fat["batch"]), dot=dot)
            runs = []
            for faulty, steps in zip(self.check_faults, self.check_budgets):
                ok = ~faulty
                p, r = t.train(self.params0, ok, steps)
                runs.append(dict(shipped=jax.device_get(rtrain.shipped(p, ok)), **r))
            self._ref[dot] = runs
        return self._ref[dot]

    def readings(self, shipped: list) -> list[dict]:
        """{leaf: gap} per job of the check, for the weights ``shipped``."""
        want = self.reference()
        out = []
        for faulty, got, w in zip(self.check_faults, shipped, want):
            base = jax.device_get(rtrain.shipped(self.params0, ~faulty))
            g = w["first_grad"]
            med = float(np.median(list(g.values())))
            keep = {k for k, v in g.items() if v >= 1e-3 * med}
            out.append(change_gaps(got, w["shipped"], base, keep))
        return out

    def check(self, control: bool = False) -> list[tuple[str, float, float]]:
        """(name, reading, limit); with ``control`` the control's weights,
        the reference at float8 e4m3, stand in the program's place."""
        got = ([r["shipped"] for r in self.reference(ref.fp8_dot)] if control
               else self.check_out)
        per_job = self.readings(got)
        worst = max(((v, k, j) for j, gaps in enumerate(per_job) for k, v in gaps.items()),
                    default=(math.nan, "", -1))
        for j, (gaps, w) in enumerate(zip(per_job, self.reference())):
            top = sorted(gaps.items(), key=lambda kv: -kv[1])[:4]
            self.log(f"job {j} ({self.check_budgets[j]} steps): reference loss "
                     f"{[round(x, 5) for x in w['loss']]}, worst leaves "
                     + ", ".join(f"{k} {v:.6g}" for k, v in top))
        who = "the control (float8 e4m3)" if control else "the program"
        self.log(f"compared {who}: worst leaf {worst[1]} of job {worst[2]}")
        return [("change_gap", float(worst[0]), float(self.limits.get("change_gap", 0.0)))]
