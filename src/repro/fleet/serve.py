"""Multi-chip serving: the continuous engine over a chip axis.

The deployment half of eFAT ships one fault-aware artifact per retraining
job, each deployed on chips with their own fault maps.
``ShardedFleetServeEngine`` is ``repro.serve.continuous.
ContinuousBatchingEngine`` with a chip axis on the devices of a "pop" mesh
(``repro.launch.mesh.make_pop_mesh``, as the training-side
``ShardedPopulationEngine``): the same program bodies, premask and round
loop, over one ragged request stream and paged slot table per chip. One
``shard_map`` decode dispatch advances every chip's in-flight slots, so no
chip waits for another chip's prompts.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.masking import FaultContext, healthy
from repro.launch.mesh import make_pop_mesh
from repro.obs.alerts import AlertRule
from repro.obs.health import HealthConfig
from repro.obs.recorder import Recorder
from repro.serve.bucketing import DEFAULT_PREFILL_BUCKETS
from repro.serve.continuous import (
    _ADMIT_CARRIED,
    _CHUNK_CARRIED,
    _DECODE_CARRIED,
    ContinuousBatchingEngine,
    Request,
    RequestOutput,
    ServeStats,
    _Lane,
    _SlotTable,
)
from repro.serve.engine import make_sample_decode
from repro.serve.kvcache import DEFAULT_PAGE_SIZE, PageAllocator

__all__ = ["ShardedFleetServeEngine"]


def _on_chip(body, carried, axis_name, per_device):
    """A ``shard_map`` body taking ``body``'s operands and, last, a traced
    chip ``c``: the device holding chip ``c`` runs ``body`` on its row of
    the weights (operand 0) and of the ``carried`` operands and writes the
    new row back; nothing moves between devices."""
    lo, hi = carried[0], carried[-1] + 1

    def program(*args):
        *args, chip = args
        c = chip % per_device  # the chip's row on the device that holds it

        def run(state):
            row = lambda tree: jax.tree_util.tree_map(lambda x: x[c], tree)
            new = body(row(args[0]), *args[1:lo], *row(state), *args[hi:])
            return jax.tree_util.tree_map(lambda s, n: s.at[c].set(n), state, new)

        mine = jax.lax.axis_index(axis_name) == chip // per_device
        return jax.lax.cond(mine, run, lambda state: state, tuple(args[lo:hi]))

    program.__name__ = body.__name__
    return program


class ShardedFleetServeEngine(ContinuousBatchingEngine):
    """Sharded, ragged fleet serving: chips → devices, streams → slot tables.

    ``params_list[c]`` are chip ``c``'s shipped weights and ``ctxs[c]`` its
    fault context (None/healthy for a fault-free chip; mixed fleets are
    fine). Each chip's served weights are the continuous engine's premask
    of its weights under its context (a healthy chip's under an all-ones
    mask when any chip is faulty, so every row has one structure), stacked
    and split over the pop axis. Decode is ``shard_map`` of a ``vmap`` of
    the same fused step, under a context that masks nothing; admission runs
    the same admit and chunk bodies on the device holding the chip, the
    chip a traced operand (one program per bucket). The chip count must be
    a multiple of the pop extent; each device holds as many chips.

    Greedy outputs reproduce a per-chip ``ContinuousBatchingEngine`` on
    the same stream (tests/test_serve_continuous.py); with temperature > 0
    each chip samples from its own key stream (the key split once per chip).
    """

    _proc = "fleet"
    _chip_operand = (np.int32(0),)

    def __init__(
        self,
        cfg,
        params_list: Sequence,
        ctxs: Optional[Sequence[Optional[FaultContext]]] = None,
        *,
        mesh=None,
        axis_name: str = "pop",
        num_slots: int = 4,
        page_size: int = DEFAULT_PAGE_SIZE,
        num_pages: int = 128,
        max_pages_per_seq: Optional[int] = None,
        pad_id: int = 0,
        prefill_buckets=DEFAULT_PREFILL_BUCKETS,
        chunk_size: Optional[int] = None,
        max_pack: int = 4,
        recorder: Optional[Recorder] = None,
        probe_every: Optional[int] = None,
        health_config: Optional[HealthConfig] = None,
        alert_rules: Optional[Sequence[AlertRule]] = None,
    ):
        n = len(params_list)
        if n == 0:
            raise ValueError("ShardedFleetServeEngine needs at least one chip")
        if cfg.has_ssm:
            raise ValueError(
                f"continuous fleet serving supports attention families only; "
                f"{cfg.family!r} carries unpaged SSM state"
            )
        if cfg.is_encoder:
            raise ValueError("encoder-only arch has no decode path")
        ctxs = list(ctxs) if ctxs is not None else [healthy()] * n
        if len(ctxs) != n:
            raise ValueError(f"{n} params sets but {len(ctxs)} fault contexts")
        if mesh is None:
            # largest pop extent that both fits the backend and tiles the fleet
            ndev = len(jax.devices())
            extent = max(d for d in range(1, min(n, ndev) + 1) if n % d == 0)
            mesh = make_pop_mesh(extent, axis=axis_name)
        if axis_name not in mesh.shape:
            raise ValueError(
                f"mesh axes {tuple(mesh.shape)} lack population axis {axis_name!r}"
            )
        extent = int(mesh.shape[axis_name])
        if n % extent != 0:
            raise ValueError(
                f"{n} chips don't tile the {extent}-slice {axis_name!r} mesh; "
                "pad the fleet or pass a mesh whose pop extent divides it"
            )
        self.mesh = mesh
        self.axis_name = axis_name
        self.num_chips = n
        # each chip's raw weights and LIVE fault context
        self.params_list = list(params_list)
        self.ctxs = [c or healthy() for c in ctxs]
        # chip-stacked weights and state live split over the pop axis, each
        # device holding its own chips' rows, so the fused step reads them
        # in place instead of scattering them from one device every dispatch
        self._chip_sharding = NamedSharding(mesh, P(axis_name))
        # set_silicon's row swap: the stack is donated, so a swap holds one copy
        self._put_row = jax.jit(
            lambda stack, row, c: jax.tree_util.tree_map(
                lambda s, r: s.at[c].set(r), stack, row),
            donate_argnums=0, out_shardings=self._chip_sharding,
        )
        self._start(
            cfg, num_slots=num_slots, page_size=page_size, num_pages=num_pages,
            max_pages_per_seq=max_pages_per_seq, pad_id=pad_id,
            prefill_buckets=prefill_buckets, chunk_size=chunk_size, max_pack=max_pack,
            recorder=recorder, probe_every=probe_every, health_config=health_config,
            alert_rules=alert_rules,
        )

    def _programs(self):
        chips, rep = P(self.axis_name), P()
        per_device = self.num_chips // int(self.mesh.shape[self.axis_name])

        def chip_program(fn, carried, n_args):
            # weights and carried state split over the pop axis, the rest
            # replicated; the carried state donated, as on one chip
            specs = tuple(chips if i == 0 or i in carried else rep for i in range(n_args))
            return jax.jit(
                jax.shard_map(fn, mesh=self.mesh, in_specs=specs, out_specs=chips,
                              check_vma=False),
                donate_argnums=carried, out_shardings=self._chip_sharding,
                in_shardings=tuple(NamedSharding(self.mesh, s) for s in specs),
            )

        axes = tuple(0 if i == 0 or i in _DECODE_CARRIED else None for i in range(9))
        sample = jax.vmap(make_sample_decode(self.cfg, pad_id=self.pad_id), in_axes=axes)
        on_chip = lambda body, carried: _on_chip(body, carried, self.axis_name, per_device)
        return (
            chip_program(sample, _DECODE_CARRIED, 9),
            chip_program(on_chip(self._packed_admit_fn, _ADMIT_CARRIED), _ADMIT_CARRIED, 17),
            chip_program(on_chip(self._prefill_chunk_fn, _CHUNK_CARRIED), _CHUNK_CARRIED, 16),
        )

    def _served_weights(self):
        like = self._active_ctx()
        rows = self.params_list
        if like is not None:
            ones = np.ones(like.ok.shape, np.float32)
            rows = [self._premasked(p, c.ok if c.active else ones)
                    for p, c in zip(self.params_list, self.ctxs)]
        stack = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *rows)
        return jax.device_put(stack, self._chip_sharding)

    def _active_ctx(self) -> Optional[FaultContext]:
        return next((c for c in self.ctxs if c.active), None)

    def _silicon(self, chip: int):
        return self.params_list[chip], self.ctxs[chip]

    def set_silicon(self, chip: int, ctx: FaultContext) -> None:
        """Simulate a mid-flight silicon change on one chip: swap the LIVE
        fault context chip ``chip`` computes through and replace its row of
        the served weights with its premask under the new map (the same
        premask program, so nothing compiles but the row swap, once),
        WITHOUT rebasing that chip's prober goldens — so its next probe
        sees the divergence and the other chips' don't. The fleet must have
        been built with an ACTIVE context on some chip (a zero-fault
        FaultMap models pristine silicon), so its served weights are
        premasked."""
        if not 0 <= chip < self.num_chips:
            raise ValueError(f"chip {chip} out of range [0, {self.num_chips})")
        row = self._resilicon(self.params_list[chip], ctx, like=self._active_ctx())
        self.ctxs[chip] = ctx
        self.served_params = self._put_row(self.served_params, row, np.int32(chip))

    def _state_structs(self):
        stack = lambda s: jax.ShapeDtypeStruct((self.num_chips,) + s.shape, s.dtype)
        return jax.tree_util.tree_map(stack, super()._state_structs())

    def _init_state(self, key):
        return jax.device_put(super()._init_state(key), self._chip_sharding)

    def _sample_keys(self, key):
        return jax.random.split(key, self.num_chips)  # one sample stream per chip

    def serve(
        self,
        streams: Sequence[Sequence[Request]],
        *,
        temperature: float = 0.0,
        eos_id: Optional[int] = None,
        key: Optional[jax.Array] = None,
        on_step: Optional[Callable[[int], None]] = None,
    ) -> tuple[list[dict[int, RequestOutput]], ServeStats]:
        """Serve one ragged request stream per chip to completion.

        Returns (per-chip outputs-by-rid, fleet-level stats). Stats count
        fused dispatches — the whole fleet advances per dispatch, so the
        total is driven by the busiest chip, not the sum over chips.
        ``on_step(clock)`` runs at the top of every scheduler round — the
        injection hook benchmarks use to flip one chip's silicon mid-serve
        (``set_silicon``). Each chip's spans are on its ``chip{c}/`` tracks."""
        if len(streams) != self.num_chips:
            raise ValueError(f"{self.num_chips} chips but {len(streams)} request streams")
        lanes = tuple(
            _Lane(c, _SlotTable(list(s), self.num_slots, PageAllocator(self.num_pages, self.page_size),
                                self.max_pages_per_seq),
                  self.obs, self._proc, prefix=f"chip{c}/", operand=(np.int32(c),), row=c)
            for c, s in enumerate(streams)
        )
        stats = self._rounds(lanes, temperature=temperature, eos_id=eos_id, key=key,
                             on_step=on_step)
        return [lane.table.outputs for lane in lanes], stats
