"""Population FAT engines — train a fleet of fault maps as ONE program.

The whole point of eFAT is amortizing retraining over many faulty chips,
yet a naive pipeline trains one fault map at a time: the Step-1 resilience
sweep, Step-4 plan execution and every SIV-C baseline differ per job only
in a tiny (R, C) mask constant. ``FaultContext`` is a pytree whose single
leaf is that mask, so a population of N jobs is just a batched context
(leading population axis on ``ok``, shared static mode) plus per-member
``(params, opt_state)`` — which ``jax.vmap`` turns into one batched train
step and ``jax.lax`` loops turn into one compiled program:

* :class:`PopulationFATEngine` — ``fit_batch`` runs all members through a
  single ``fori_loop`` with per-member step budgets enforced by a select
  mask (a member stops receiving updates after its own budget, exactly as
  if it had been trained alone); ``steps_to_constraint_batch`` runs a
  ``while_loop`` of eval-period chunks with in-loop periodic eval and
  records each member's first constraint crossing via a ``lax`` mask,
  exiting early once every member has crossed. N fault maps cost one
  dispatch, not N Python loops of per-step dispatches.
* :class:`SerialFATEngine` — the reference implementation (one Python loop
  per member, jitted grad + eager optimizer), kept behind
  ``engine="serial"`` and used to prove numerical equivalence in tests.

Both engines share one interface so ``ClassifierFATTrainer`` /
``LMFATTrainer`` delegate their ``_fit`` / ``steps_to_constraint`` bodies
here unchanged. Memory scales linearly with the population, so batched
calls are chunked to ``population_size`` members; chunking only changes
how work is submitted, never per-member math.

A third engine, ``repro.fleet.sharding.ShardedPopulationEngine``
(``engine="sharded"``), subclasses the population engine and wraps the same
run bodies in ``shard_map`` over a "pop" mesh axis so each device trains a
sub-population — see ``src/repro/fleet/README.md``.
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.masking import FaultContext, healthy, stack_contexts
from repro.obs.recorder import NULL_RECORDER, Recorder
from repro.train.optimizer import AdamWConfig, adamw_init, adamw_update

__all__ = ["PopulationFATEngine", "SerialFATEngine", "make_fat_engine"]

# steps-to-constraint bucket ladder (training steps, not seconds)
STEPS_BUCKETS = (0.0, 5.0, 10.0, 20.0, 40.0, 80.0, 160.0, 320.0, 640.0, 1280.0)

# batch_fn(step) -> batch dict; must be jax-traceable in ``step`` for the
# population engine (the deterministic (seed, step) streams in
# repro.data.synthetic are).
BatchFn = Callable[[Any], dict]


def _stack_trees(trees: Sequence[Any]):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _member_slice(tree, i: int):
    return jax.tree_util.tree_map(lambda x: x[i], tree)


class PopulationFATEngine:
    """vmap + scan FAT over a population of fault maps.

    Parameters
    ----------
    loss_fn : ``(params, batch, ctx) -> (loss, metrics)`` — the per-member
        training objective; ``metrics[metric]`` is the constraint metric.
    opt_cfg : AdamW settings shared by every member.
    eval_batches : the fixed eval batches; stacked once and evaluated
        in-program.
    metric / higher_is_better : constraint metric key and its direction
        (``loss`` style metrics are negated so 'metric >= constraint' is
        uniform, matching the serial trainers' protocol).
    eval_every : periodic-eval interval inside ``steps_to_constraint_batch``.
    population_size : max members per compiled program; larger batches are
        chunked (memory / compile-shape trade-off, see train/README.md).
    param_axes : optional logical-axes pytree mirroring the params structure
        (``repro.launch.sharding`` names). Ignored by this engine and the
        serial reference; the fleet engine uses it to lay member params out
        over the "model" axis of a 2-D ``("pop", "model")`` mesh.
    recorder : optional :class:`repro.obs.Recorder`. Per-lane telemetry is
        collected host-side at chunk boundaries — chunk spans with lane
        widths and wasted lane-steps, per-member constraint-crossing
        instants, steps-consumed-vs-budget counters — so nothing enters the
        traced run bodies and the serial↔vmap↔sharded pins hold untouched.
    """

    kind = "population"

    def __init__(
        self,
        *,
        loss_fn,
        opt_cfg: AdamWConfig,
        eval_batches: Sequence[dict],
        metric: str = "accuracy",
        higher_is_better: bool = True,
        eval_every: int = 5,
        population_size: int = 16,
        param_axes: Optional[Any] = None,
        recorder: Optional[Recorder] = None,
    ):
        self.loss_fn = loss_fn
        self.opt_cfg = opt_cfg
        self.metric = metric
        self.higher_is_better = higher_is_better
        self.eval_every = int(eval_every)
        self.population_size = max(1, int(population_size))
        self.param_axes = param_axes
        self.obs = recorder if recorder is not None else NULL_RECORDER
        self._eval_stack = _stack_trees(list(eval_batches))
        self._grad = jax.value_and_grad(loss_fn, has_aux=True)
        # compiled programs are cached per (batch_fn, context mode): the
        # mode is a static part of the trace, and trainers create their
        # batch fns once, so each distinct data stream compiles once
        self._fit_programs: dict = {}
        self._steps_programs: dict = {}
        self._eval_programs: dict = {}

    # -- per-member building blocks (always traced under vmap) -----------
    # The contexts' shared mode is threaded through as a static closure
    # value, never rebuilt from engine state — a population of 'pallas'
    # contexts trains in pallas mode.

    @staticmethod
    def _ctx(ok, mode: str) -> FaultContext:
        return healthy() if ok is None else FaultContext(ok=ok, mode=mode)

    def _member_eval(self, params, ok, mode: str, eval_stack=None):
        ctx = self._ctx(ok, mode)
        stack = self._eval_stack if eval_stack is None else eval_stack

        def one(batch):
            v = self.loss_fn(params, batch, ctx)[1][self.metric]
            return v if self.higher_is_better else -v

        return jnp.mean(jax.vmap(one)(stack))

    def _member_update(self, params, opt, ok, batch, mode: str):
        (_, _m), g = self._grad(params, batch, self._ctx(ok, mode))
        params, opt, _ = adamw_update(g, opt, params, self.opt_cfg)
        return params, opt

    def _broadcast_members(self, params0, n: int):
        params_pop = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), params0
        )
        opt_pop = jax.vmap(lambda p: adamw_init(p, self.opt_cfg))(params_pop)
        return params_pop, opt_pop

    # -- member-state layout hooks ---------------------------------------
    # The run bodies thread per-member (params, opt) through these at every
    # loop-carry boundary (stored layout) and before every update/eval
    # (compute layout). They are identity here — the fleet engine overrides
    # them to keep member state sharded over a 2-D mesh's "model" axis
    # between steps while gathering full-shape replicas for the math, so
    # per-member trajectories stay bit-identical to the single-device path.

    def _constrain_member_state(self, params_pop, opt_pop):
        """Persistent (loop-carry / program-output) layout of member state."""
        return params_pop, opt_pop

    def _gather_member_state(self, params_pop, opt_pop):
        """Layout member state for an update step (full-shape by default)."""
        return params_pop, opt_pop

    def _gather_member_params(self, params_pop):
        """Layout member params for evaluation (full-shape by default)."""
        return params_pop

    def _constrain_batch(self, tree):
        """Layout of non-member data entering the math (train/eval batches,
        stacked masks): identity here; the fleet engine pins these replicated
        along the model axis so compute stays at single-device shapes."""
        return tree

    def _eval_pop(self, params_pop, ok_pop, mode: str):
        params_pop = self._gather_member_params(params_pop)
        ok_pop = None if ok_pop is None else self._constrain_batch(ok_pop)
        stack = self._constrain_batch(self._eval_stack)
        ok_axis = None if ok_pop is None else 0
        return jax.vmap(
            lambda p, ok: self._member_eval(p, ok, mode, stack), in_axes=(0, ok_axis)
        )(params_pop, ok_pop)

    def _eval_run(self, mode: str):
        return lambda pp, ok: self._eval_pop(pp, ok, mode)

    def _make_eval(self, mode: str):
        return jax.jit(self._eval_run(mode))

    def _eval_program(self, mode: str):
        if mode not in self._eval_programs:
            self._eval_programs[mode] = self._make_eval(mode)
        return self._eval_programs[mode]

    # -- compiled programs ------------------------------------------------
    # Each program comes in two layers: ``_*_run`` builds the plain traced
    # function over a full population chunk, and ``_make_*`` wraps it for
    # execution (jit here; jit(shard_map(...)) in the fleet subclass, which
    # reuses the same run bodies so per-member math cannot diverge).

    def _fit_run(self, batch_fn: BatchFn, mode: str):
        """One fori_loop trains every member to its own step budget: updates
        are computed for the whole population and select-masked off once a
        member's budget is spent — identical trajectories to training each
        member alone for ``budgets[i]`` steps on the same batch schedule."""

        def run(params0, ok_pop, budgets):
            n = budgets.shape[0]
            ok_axis = None if ok_pop is None else 0
            if ok_pop is not None:
                ok_pop = self._constrain_batch(ok_pop)
            params_pop, opt_pop = self._broadcast_members(params0, n)
            params_pop, opt_pop = self._constrain_member_state(params_pop, opt_pop)
            update = jax.vmap(
                lambda p, o, ok, b: self._member_update(p, o, ok, b, mode),
                in_axes=(0, 0, ok_axis, None),
            )

            def body(i, state):
                params, opt = self._gather_member_state(*state)
                new_params, new_opt = update(
                    params, opt, ok_pop, self._constrain_batch(batch_fn(i))
                )
                active = i < budgets  # (n,)

                def sel(new, old):
                    a = active.reshape((n,) + (1,) * (new.ndim - 1))
                    return jnp.where(a, new, old)

                return self._constrain_member_state(
                    jax.tree_util.tree_map(sel, new_params, params),
                    jax.tree_util.tree_map(sel, new_opt, opt),
                )

            params_pop, _ = jax.lax.fori_loop(
                0, jnp.max(budgets), body, (params_pop, opt_pop)
            )
            return params_pop

        return run

    def _make_fit(self, batch_fn: BatchFn, mode: str):
        return jax.jit(self._fit_run(batch_fn, mode))

    def _steps_run(self, batch_fn: BatchFn, mode: str):
        """steps-to-constraint for the whole population as one while_loop of
        eval-period chunks. ``crossed[i]`` latches the first step at which
        member i's metric reached the constraint (sentinel max_steps+1 when
        never); the loop exits as soon as every member has crossed."""
        ee = self.eval_every

        def run(params0, ok_pop, constraint, max_steps):
            n = ok_pop.shape[0]
            max_steps = jnp.asarray(max_steps, jnp.int32)
            ok_pop = self._constrain_batch(ok_pop)
            params_pop, opt_pop = self._broadcast_members(params0, n)
            update = jax.vmap(
                lambda p, o, ok, b: self._member_update(p, o, ok, b, mode),
                in_axes=(0, 0, 0, None),
            )

            base = self._eval_pop(params_pop, ok_pop, mode)
            sentinel = max_steps + 1
            crossed = jnp.where(base >= constraint, jnp.int32(0), sentinel)
            params_pop, opt_pop = self._constrain_member_state(params_pop, opt_pop)

            def cond(carry):
                step, _params, _opt, cr = carry
                return (step < max_steps) & jnp.any(cr > max_steps)

            def body(carry):
                step, params, opt, cr = carry
                params, opt = self._gather_member_state(params, opt)

                def train_one(i, state):
                    p, o = state
                    return update(
                        p, o, ok_pop, self._constrain_batch(batch_fn(step + i + 1))
                    )

                params, opt = jax.lax.fori_loop(0, ee, train_one, (params, opt))
                step = step + ee
                metric = self._eval_pop(params, ok_pop, mode)
                # first crossing only; a chunk overshooting max_steps is a
                # step the serial reference never evaluated, so it can't hit
                hit = (metric >= constraint) & (cr > max_steps) & (step <= max_steps)
                cr = jnp.where(hit, step.astype(cr.dtype), cr)
                params, opt = self._constrain_member_state(params, opt)
                return step, params, opt, cr

            _, _, _, crossed = jax.lax.while_loop(
                cond, body, (jnp.int32(0), params_pop, opt_pop, crossed)
            )
            return crossed

        return run

    def _make_steps(self, batch_fn: BatchFn, mode: str):
        return jax.jit(self._steps_run(batch_fn, mode))

    # -- chunking ---------------------------------------------------------

    def _chunks(self, n: int):
        size = max(1, min(self.population_size, n))
        for lo in range(0, n, size):
            keep = min(size, n - lo)
            yield lo, keep, size

    # -- engine interface -------------------------------------------------

    def fit_batch(
        self,
        params0,
        contexts: Sequence[Optional[FaultContext]],
        budgets: Sequence[int],
        batch_fn: BatchFn,
    ) -> list:
        """Train one member per context from ``params0`` for its own budget
        of steps (batches ``batch_fn(0..budget-1)``); returns per-member
        params (NOT FAP-masked — shipping policy belongs to the trainer)."""
        if len(contexts) != len(budgets):
            raise ValueError("contexts and budgets must align")
        out: list = []
        for lo, keep, size in self._chunks(len(contexts)):
            chunk = list(contexts[lo : lo + keep])
            chunk_budgets = [int(b) for b in budgets[lo : lo + keep]]
            # pad with zero-budget copies: they ride along untouched
            chunk += [chunk[-1]] * (size - keep)
            chunk_budgets += [0] * (size - keep)
            stacked = stack_contexts([c or healthy() for c in chunk])
            key = (batch_fn, stacked.mode)
            if key not in self._fit_programs:
                self._fit_programs[key] = self._make_fit(batch_fn, stacked.mode)
            t0 = self.obs.now() if self.obs else 0.0
            with self._annotation("fit_chunk", lo):
                trained = self._fit_programs[key](
                    params0, stacked.ok, jnp.asarray(chunk_budgets, jnp.int32)
                )
                if self.obs:
                    trained = jax.block_until_ready(trained)
            if self.obs:
                maxb = max(chunk_budgets) if chunk_budgets else 0
                lane_steps = size * maxb  # padding lanes occupy real width
                wasted = lane_steps - sum(chunk_budgets)
                self.obs.span(
                    "fit_chunk", proc="train", track="engine", t0=t0,
                    args=dict(members=keep, width=size, max_budget=maxb,
                              budget_steps=sum(chunk_budgets),
                              wasted_lane_steps=wasted),
                )
                self.obs.count("train.members_trained", keep)
                self.obs.count("train.lane_steps", lane_steps)
                self.obs.count("train.budget_steps", sum(chunk_budgets))
                self.obs.count("train.wasted_lane_steps", wasted)
            self._record_fit_output(trained, keep, size)
            out.extend(_member_slice(trained, i) for i in range(keep))
        return out

    def _annotation(self, name: str, lo: int):
        """A profiler step annotation (numbered by the chunk's first member)
        over a chunk's dispatch, so its recorder span has a twin on the
        device trace's clock; nothing when no recorder is attached."""
        if not self.obs:
            return contextlib.nullcontext()
        return jax.profiler.StepTraceAnnotation(name, step_num=lo)

    def _record_fit_output(self, trained, keep: int, width: int) -> None:
        """Hook on each raw (still member-stacked) fit-program output before
        padding lanes are sliced off — the fleet engine records per-device
        resident-byte stats here; no-op otherwise."""

    def steps_to_constraint_batch(
        self,
        params0,
        contexts: Sequence[FaultContext],
        constraint: float,
        max_steps: int,
        batch_fn: BatchFn,
    ) -> list[Optional[int]]:
        """Per-member steps until metric >= constraint (eval every
        ``eval_every`` steps, batches ``batch_fn(1..max_steps)``), or None
        when not reached within ``max_steps`` — one compiled program per
        chunk instead of per-member Python loops."""
        out: list[Optional[int]] = []
        for lo, keep, size in self._chunks(len(contexts)):
            chunk = list(contexts[lo : lo + keep])
            chunk += [chunk[-1]] * (size - keep)
            stacked = stack_contexts(chunk)
            if stacked.ok is None:
                raise ValueError("steps_to_constraint needs fault contexts")
            key = (batch_fn, stacked.mode)
            if key not in self._steps_programs:
                self._steps_programs[key] = self._make_steps(batch_fn, stacked.mode)
            t0 = self.obs.now() if self.obs else 0.0
            with self._annotation("probe_chunk", lo):
                crossed = np.asarray(
                    self._steps_programs[key](params0, stacked.ok, constraint, max_steps)
                )
            if self.obs:
                # Every lane runs until the slowest member crosses (or
                # max_steps): realized lane-steps = width * max(realized).
                realized = [min(int(c), int(max_steps)) for c in crossed[:keep]]
                worst = max(realized) if realized else 0
                lane_steps = size * worst
                wasted = lane_steps - sum(realized)
                self.obs.span(
                    "probe_chunk", proc="train", track="engine", t0=t0,
                    args=dict(members=keep, width=size, max_steps=int(max_steps),
                              realized_steps=worst, wasted_lane_steps=wasted),
                )
                self.obs.count("train.probe_lane_steps", lane_steps)
                self.obs.count("train.probe_wasted_lane_steps", wasted)
                for i, c in enumerate(crossed[:keep]):
                    if int(c) > int(max_steps):
                        self.obs.count("train.members_never_crossed")
                    else:
                        self.obs.observe(
                            "train.steps_to_constraint", float(c),
                            buckets=STEPS_BUCKETS,
                        )
                        self.obs.instant(
                            "constraint_crossed", proc="train", track="engine",
                            args=dict(member=lo + i, steps=int(c)),
                        )
            out.extend(
                None if int(c) > int(max_steps) else int(c) for c in crossed[:keep]
            )
        return out

    def evaluate_batch(
        self, params_list: Sequence[Any], contexts: Sequence[Optional[FaultContext]]
    ) -> list[float]:
        """Signed constraint metric of params_list[i] under contexts[i],
        vmapped across the population (chunked like training)."""
        if len(params_list) != len(contexts):
            raise ValueError("params and contexts must align")
        out: list[float] = []
        for lo, keep, size in self._chunks(len(contexts)):
            chunk_params = list(params_list[lo : lo + keep])
            chunk_ctx = list(contexts[lo : lo + keep])
            chunk_params += [chunk_params[-1]] * (size - keep)
            chunk_ctx += [chunk_ctx[-1]] * (size - keep)
            stacked = stack_contexts([c or healthy() for c in chunk_ctx])
            vals = np.asarray(
                self._eval_program(stacked.mode)(_stack_trees(chunk_params), stacked.ok)
            )
            out.extend(float(v) for v in vals[:keep])
        return out

    def evaluate_one(self, params, ctx: Optional[FaultContext]) -> float:
        return self.evaluate_batch([params], [ctx])[0]


class SerialFATEngine:
    """Reference serial implementation of the engine interface — the exact
    one-map-at-a-time loops the trainers ran before the population refactor
    (jitted grad, eager optimizer, host-side periodic eval). Kept behind
    ``engine="serial"`` for equivalence tests and benchmarking."""

    kind = "serial"

    def __init__(
        self,
        *,
        loss_fn,
        opt_cfg: AdamWConfig,
        eval_batches: Sequence[dict],
        metric: str = "accuracy",
        higher_is_better: bool = True,
        eval_every: int = 5,
        population_size: int = 16,  # interface parity; serial chunks are 1-wide
        param_axes: Optional[Any] = None,  # interface parity; serial never shards
        recorder: Optional[Recorder] = None,  # interface parity with population
    ):
        self.population_size = 1  # one member at a time — schedulers see no packing
        self.param_axes = param_axes
        self.obs = recorder if recorder is not None else NULL_RECORDER
        self.loss_fn = loss_fn
        self.opt_cfg = opt_cfg
        self.metric = metric
        self.higher_is_better = higher_is_better
        self.eval_every = int(eval_every)
        self.eval_batches = list(eval_batches)
        self._grad = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))
        self._eval = jax.jit(lambda p, b, ctx: loss_fn(p, b, ctx)[1])

    def evaluate_one(self, params, ctx: Optional[FaultContext]) -> float:
        ctx = ctx or healthy()
        vals = [float(self._eval(params, b, ctx)[self.metric]) for b in self.eval_batches]
        v = float(np.mean(vals))
        return v if self.higher_is_better else -v

    def _fit_one(self, params0, ctx: FaultContext, steps: int, batch_fn: BatchFn):
        params, opt = params0, adamw_init(params0, self.opt_cfg)
        for s in range(int(steps)):
            (_, _m), g = self._grad(params, batch_fn(s), ctx)
            params, opt, _ = adamw_update(g, opt, params, self.opt_cfg)
        return params

    def fit_batch(self, params0, contexts, budgets, batch_fn: BatchFn) -> list:
        return [
            self._fit_one(params0, ctx or healthy(), steps, batch_fn)
            for ctx, steps in zip(contexts, budgets)
        ]

    def steps_to_constraint_batch(
        self, params0, contexts, constraint, max_steps, batch_fn: BatchFn
    ) -> list[Optional[int]]:
        out: list[Optional[int]] = []
        for ctx in contexts:
            if self.evaluate_one(params0, ctx) >= constraint:
                out.append(0)  # paper Fig. 3: relaxed constraints may need no retraining
                continue
            params, opt = params0, adamw_init(params0, self.opt_cfg)
            found: Optional[int] = None
            for s in range(1, int(max_steps) + 1):
                (_, _m), g = self._grad(params, batch_fn(s), ctx)
                params, opt, _ = adamw_update(g, opt, params, self.opt_cfg)
                if s % self.eval_every == 0 and self.evaluate_one(params, ctx) >= constraint:
                    found = s
                    break
            out.append(found)
        return out

    def evaluate_batch(self, params_list, contexts) -> list[float]:
        return [self.evaluate_one(p, c) for p, c in zip(params_list, contexts)]


def make_fat_engine(kind: str, **kwargs):
    if kind == "population":
        return PopulationFATEngine(**kwargs)
    if kind == "serial":
        return SerialFATEngine(**kwargs)
    if kind == "sharded":
        # lazy: repro.fleet.sharding imports this module
        from repro.fleet.sharding import ShardedPopulationEngine

        return ShardedPopulationEngine(**kwargs)
    raise ValueError(
        f"unknown FAT engine {kind!r} (use 'population', 'serial', or 'sharded')"
    )
