"""repro.fleet — fleet-scale execution layer between the FAT engines and the
launch/serve stack.

Three cooperating modules (see README.md in this directory):

* :mod:`repro.fleet.sharding` — :class:`ShardedPopulationEngine`, the
  population FAT programs under ``shard_map`` over the "pop" axis of a 1-D
  pop mesh or a 2-D ``("pop", "model")`` fleet mesh (one sub-population per
  pop slice; member params sharded over the slice's model sub-mesh).
* :mod:`repro.fleet.scheduler` — :class:`FleetScheduler`, budget-aware
  (LPT) packing of retraining jobs into population chunks, with
  ``wasted_steps`` accounting keyed on the pop-axis extent.
* :mod:`repro.fleet.capacity` — :func:`suggest_population_size`, sizing
  population lanes against per-device memory from param/opt bytes.
* :mod:`repro.fleet.serve` — :class:`ShardedFleetServeEngine`, the
  continuous-batching engine over a chip axis placed on the pop mesh: one
  ragged request stream and paged-KV slot table per chip, the single
  engine's programs and round loop, one ``shard_map`` decode dispatch for
  the whole fleet.
"""
from repro.fleet.capacity import suggest_population_size
from repro.fleet.scheduler import FleetSchedule, FleetScheduler, ScheduledChunk
from repro.fleet.serve import ShardedFleetServeEngine
from repro.fleet.sharding import ShardedPopulationEngine

__all__ = [
    "FleetSchedule",
    "FleetScheduler",
    "ScheduledChunk",
    "ShardedFleetServeEngine",
    "ShardedPopulationEngine",
    "suggest_population_size",
]
