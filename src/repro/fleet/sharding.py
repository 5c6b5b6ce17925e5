"""Device-sharded population FAT — ``shard_map`` over the "pop" mesh axis,
composed with tensor-parallel member-param layout over a "model" axis.

``PopulationFATEngine`` (repro.train.population) turns N fault maps into one
vmap+scan program on a single device. This module makes the population axis
a *device* axis: the same run bodies wrapped in ``shard_map`` over the
leading "pop" axis of a fleet mesh (``repro.launch.mesh.make_pop_mesh`` /
``make_fleet_mesh``), so each pop slice runs a sub-population of
``fit_batch`` / ``steps_to_constraint_batch`` / ``evaluate_batch``.

On a 2-D ``("pop", "model")`` mesh each pop slice is itself a
tensor-parallel sub-mesh: per-member ``(params, opt_state)`` are laid out
over the "model" axis with the logical-axis rules from
``repro.launch.sharding`` (``make_rules_for_mesh`` with "pop" reserved), so
a fleet of large models trains without replicating full weights per member.
The "pop" axis is *manual* (``shard_map``); the "model" axis is *auto* —
left to the compiler, steered by ``with_sharding_constraint`` at the layout
points the run bodies expose.

Design invariants
-----------------
* **Identical math.** The engine wraps the *same* un-jitted run bodies
  (``_fit_run`` / ``_steps_run`` / ``_eval_run``) the vmap engine jits; a
  member's trajectory depends only on its own (mask, budget) and the shared
  batch stream, so serial, vmap, 1-D shard_map and 2-D shard_map produce
  identical steps-to-constraint and resilience tables (pinned in
  tests/test_fleet.py, including a forced-8-device 4x2 subprocess test).
  With the default ``compute="gathered"`` this holds *bitwise*: member
  state is stored "model"-sharded between steps but gathered to full-shape
  replicas for every update/eval, so every matmul runs at exactly the
  single-device shapes (XLA changes accumulation blocking with operand
  shapes, so sharded-compute GEMMs are NOT bit-identical). ``"sharded"``
  leaves compute under the stored layout — true tensor-parallel math, HBM
  *and* FLOPs sharded, results equal to float tolerance instead of bitwise.
* **Population -> device mapping.** A chunk of ``population_size`` members
  is padded to a multiple of the pop-axis extent and split contiguously:
  pop slice d takes members ``[d*k, (d+1)*k)`` of the chunk. Padding
  members are zero-budget (fit) or duplicates (steps) and are sliced off
  the results — they never leak out.
* **Per-shard early exit.** ``fit_batch``'s fori_loop bound is
  ``max(budgets)`` *of the local shard*, and ``steps_to_constraint_batch``'s
  while_loop exits when the local sub-population has crossed — each pop
  slice stops as soon as its own members are done. (No cross-slice
  collectives run inside the loops, so divergent per-slice trip counts are
  legal SPMD; "model"-axis collectives stay *inside* a slice, whose devices
  always agree on the trip count.)

CPU testing: export ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
before the first jax import (see tests/test_fleet.py and the CI fleet job).
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.fleet.scheduler import round_up_to_multiple
from repro.launch.mesh import make_pop_mesh
from repro.launch.sharding import MeshContext, make_rules_for_mesh, resolve_spec
from repro.train.optimizer import opt_state_specs
from repro.train.population import BatchFn, PopulationFATEngine

__all__ = ["ShardedPopulationEngine"]

_is_axes_leaf = lambda a: isinstance(a, tuple) and all(
    x is None or isinstance(x, str) for x in a
)


class ShardedPopulationEngine(PopulationFATEngine):
    """PopulationFATEngine whose compiled programs run under ``shard_map``.

    Parameters (beyond the population engine's):

    mesh : a 1-D pop mesh (``make_pop_mesh``) or a 2-D ``("pop", "model")``
        fleet mesh (``make_fleet_mesh``). Default: ``make_pop_mesh()`` over
        every visible device. Any trailing non-pop axes are treated as the
        model sub-mesh of each pop slice.
    axis_name : the population axis name ("pop").
    cfg : ArchConfig used to build the tensor-parallel rules
        (``make_rules_for_mesh`` with the pop axis reserved). Required —
        together with ``param_axes`` — when the mesh has a model axis of
        extent > 1; ``mesh_rules`` overrides it with a prebuilt MeshContext.
    compute : "gathered" (default) stores member state "model"-sharded but
        gathers full-shape replicas for each update/eval — bitwise-pinned
        against the 1-D/vmap/serial engines, memory sharded. "sharded"
        leaves compute under the stored layout (true tensor-parallel math;
        equal to float tolerance, not bitwise).

    ``population_size`` is rounded up to a multiple of the pop-axis extent
    so every chunk tiles the mesh; all-healthy submissions (mode "none",
    e.g. the pretrain call) have no mask to shard and fall back to the
    parent's single-device program.
    """

    kind = "sharded"

    def __init__(
        self,
        *,
        mesh: Optional[Mesh] = None,
        axis_name: str = "pop",
        cfg: Any = None,
        mesh_rules: Optional[MeshContext] = None,
        compute: str = "gathered",
        **kwargs,
    ):
        super().__init__(**kwargs)
        self.mesh = mesh if mesh is not None else make_pop_mesh(axis=axis_name)
        if axis_name not in self.mesh.shape:
            raise ValueError(
                f"mesh axes {tuple(self.mesh.shape)} lack population axis {axis_name!r}"
            )
        if compute not in ("gathered", "sharded"):
            raise ValueError(
                f"compute must be 'gathered' or 'sharded', got {compute!r}"
            )
        self.axis_name = axis_name
        self.compute = compute
        # num_shards is the POP-AXIS EXTENT, not the device count: chunk
        # rounding, scheduler width rounding and padding all key on how many
        # pop slices exist, however many devices each slice spans.
        self.num_shards = int(self.mesh.shape[axis_name])
        self.model_axes = tuple(a for a in self.mesh.axis_names if a != axis_name)
        self.model_size = int(
            math.prod(self.mesh.shape[a] for a in self.model_axes)
        )
        if self.model_size > 1:
            if mesh_rules is not None:
                self.mesh_rules: Optional[MeshContext] = mesh_rules
            elif cfg is not None:
                self.mesh_rules = make_rules_for_mesh(
                    cfg, self.mesh, fsdp=False, reserved_axes=(axis_name,)
                )
            else:
                raise ValueError(
                    "a 2-D fleet mesh with a model axis needs tensor-parallel "
                    "rules: pass cfg= (an ArchConfig) or mesh_rules= (a "
                    "MeshContext built with the pop axis reserved)"
                )
            if self.param_axes is None:
                raise ValueError(
                    "a 2-D fleet mesh with a model axis needs param_axes= "
                    "(the logical-axes pytree mirroring the params structure, "
                    "e.g. models.model.param_specs(cfg) or "
                    "models.classifier.classifier_param_axes(cfg))"
                )
        else:
            self.mesh_rules = mesh_rules
        # chunks must tile the pop axis: round the configured width up
        self.population_size = max(
            self.num_shards,
            round_up_to_multiple(self.population_size, self.num_shards),
        )
        self.last_fit_stats: Optional[dict] = None

    # -- chunking: every chunk width is a multiple of the pop extent -------

    def _chunks(self, n: int):
        size = max(1, min(self.population_size, n))
        size = round_up_to_multiple(size, self.num_shards)
        for lo in range(0, n, size):
            yield lo, min(size, n - lo), size

    # -- member-state layout over the model axis ---------------------------
    # Only the member axis is manual (shard_map over "pop"); every other
    # mesh axis is auto, so these with_sharding_constraint calls — legal on
    # auto axes inside a partial-auto shard_map body — are what lay member
    # params/opt out over the pop slice's model sub-mesh.

    @property
    def _model_sharded(self) -> bool:
        return self.model_size > 1

    def _member_sharding(self, axes, leaf):
        """NamedSharding for one member-stacked leaf: member axis replicated
        (it is manual / already local), trailing dims per the model rules."""
        spec = resolve_spec(tuple(axes), leaf.shape[1:], self.mesh_rules)
        return NamedSharding(self.mesh, P(None, *tuple(spec)))

    def _apply_member_specs(self, axes_tree, tree):
        return jax.tree_util.tree_map(
            lambda axes, leaf: jax.lax.with_sharding_constraint(
                leaf, self._member_sharding(axes, leaf)
            ),
            axes_tree,
            tree,
            is_leaf=_is_axes_leaf,
        )

    def _replicate_tree(self, tree):
        rep = NamedSharding(self.mesh, P())
        return jax.tree_util.tree_map(
            lambda leaf: jax.lax.with_sharding_constraint(leaf, rep), tree
        )

    # hooks called by the shared run bodies (repro.train.population)

    def _constrain_member_state(self, params_pop, opt_pop):
        if not self._model_sharded:
            return params_pop, opt_pop
        if self.compute == "gathered":
            # pin an explicitly replicated point between the update math and
            # the sharded store: GSPMD propagates shardings backward, and
            # without this the stored layout leaks into the preceding GEMMs,
            # re-blocking their accumulation (one-ulp drift vs the 1-D path)
            params_pop = self._replicate_tree(params_pop)
            opt_pop = self._replicate_tree(opt_pop)
        return (
            self._apply_member_specs(self.param_axes, params_pop),
            self._apply_member_specs(opt_state_specs(self.param_axes), opt_pop),
        )

    def _gather_member_state(self, params_pop, opt_pop):
        if not self._model_sharded or self.compute == "sharded":
            return params_pop, opt_pop
        return self._replicate_tree(params_pop), self._replicate_tree(opt_pop)

    def _gather_member_params(self, params_pop):
        if not self._model_sharded or self.compute == "sharded":
            return params_pop
        return self._replicate_tree(params_pop)

    def _constrain_batch(self, tree):
        # batches / masks / the eval stack enter the math replicated along
        # the model axis (Megatron-style: data replicated, weights sharded).
        # Without this the compiler is free to pick model-sharded input
        # layouts, which turns grad contractions into partial-sum psums —
        # numerically fine but not bitwise against the 1-D path.
        if not self._model_sharded:
            return tree
        return self._replicate_tree(tree)

    # -- program wrappers: jit(shard_map(run)) over the pop axis -----------

    def _shard(self, run, in_specs):
        return jax.jit(
            jax.shard_map(
                run,
                mesh=self.mesh,
                in_specs=in_specs,
                out_specs=P(self.axis_name),
                check_vma=False,  # per-shard loop trip counts legitimately diverge
                # only the pop axis is manual: trailing mesh axes stay under
                # compiler (GSPMD) control so the model rules can shard member
                # state within each pop slice
                axis_names=frozenset({self.axis_name}),
            )
        )

    def _make_fit(self, batch_fn: BatchFn, mode: str):
        run = self._fit_run(batch_fn, mode)
        if mode == "none":  # all-healthy population: ok is None, nothing to shard
            return jax.jit(run)
        a = self.axis_name
        # (params0 replicated, ok_pop sharded, budgets sharded)
        return self._shard(run, (P(), P(a), P(a)))

    def _make_steps(self, batch_fn: BatchFn, mode: str):
        run = self._steps_run(batch_fn, mode)
        a = self.axis_name
        # (params0 replicated, ok_pop sharded, constraint, max_steps)
        return self._shard(run, (P(), P(a), P(), P()))

    def _make_eval(self, mode: str):
        run = self._eval_run(mode)
        if mode == "none":
            return jax.jit(run)
        a = self.axis_name
        return self._shard(run, (P(a), P(a)))

    # -- resident-memory accounting ----------------------------------------

    def _record_fit_output(self, trained, keep: int, width: int) -> None:
        """Per-device resident bytes of the raw member-stacked fit output —
        the proof that member params live "model"-sharded within each pop
        slice instead of replicated (surfaced by efat_bench.py --mesh)."""
        leaves = jax.tree_util.tree_leaves(trained)
        if not leaves or not hasattr(leaves[0], "addressable_shards"):
            return
        dev0 = self.mesh.devices.flat[0]
        dev0_bytes = 0
        for leaf in leaves:
            dev0_bytes += sum(
                sh.data.nbytes
                for sh in leaf.addressable_shards
                if sh.device == dev0
            )
        total_bytes = sum(int(leaf.nbytes) for leaf in leaves)
        members_per_lane = max(1, width // self.num_shards)
        self.last_fit_stats = dict(
            chunk_width=width,
            members_kept=keep,
            members_per_lane=members_per_lane,
            pop_extent=self.num_shards,
            model_extent=self.model_size,
            device0_resident_bytes=int(dev0_bytes),
            per_member_resident_bytes=dev0_bytes / members_per_lane,
            per_member_total_bytes=total_bytes / width,
        )
