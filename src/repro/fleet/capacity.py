"""Fleet capacity planning — size population lanes against device memory.

A population chunk holds, per member, fp32 master params plus the two AdamW
moments, and each vmapped update holds that member's gradients and its new
params and moments besides; with a 2-D ``("pop", "model")`` fleet mesh that
state is sharded
``model_extent``-ways within each pop slice (see ``fleet/sharding.py``), so
the members a single device can hold grows linearly with the model axis.
``suggest_population_size`` turns (arch, mesh, per-device memory) into a
``population_size`` the sharded engine can run without paging — the ROADMAP
"size ``population_size`` against HBM" item, consumed by
``benchmarks/efat_bench.py --population-size auto``.

With ``reserve_kernel_vmem=True`` the planner additionally reserves the
per-lane scratch the Pallas kernels keep resident, read from the tuning
cache's recorded per-kernel VMEM footprints (:func:`kernel_vmem_reserve`)
— tuned geometry often trades bigger blocks for fewer grid steps, so the
reserve grows with the tuned table instead of assuming heuristic blocks.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
from jax.sharding import Mesh

__all__ = ["BYTES_PER_PARAM", "suggest_population_size", "kernel_vmem_reserve"]

# Per member and parameter: fp32 master params + fp32 AdamW m and v (12 B)
# resident, plus what one vmapped update holds at once: fp32 gradients (4 B),
# the new params, m and v the budget select picks from (12 B), and the bf16
# weight casts and their masked copies (4 B). Compiled for a TPU v5e, the
# one-member smollm-135m fit program needs 4.4 GiB, 35 B/param; resident
# state alone (12 B) would size a 16 GiB chip at five members, whose
# program cannot fit.
BYTES_PER_PARAM = 32
# the host CPU backend reports no limit: plan CPU runs as for a 16 GiB v5e
_CPU_PLANNING_BYTES = 16 << 30


def _device_memory_bytes(mesh: Optional[Mesh]) -> int:
    """The device's reported ``bytes_limit``. Only the CPU backend, which
    reports none, is planned at a fixed 16 GiB; an accelerator that reports
    no limit is an error."""
    dev = mesh.devices.flat[0] if mesh is not None else jax.devices()[0]
    stats = dev.memory_stats() if dev.platform != "cpu" else None
    if stats and stats.get("bytes_limit"):
        return int(stats["bytes_limit"])
    if dev.platform == "cpu":
        return _CPU_PLANNING_BYTES
    raise RuntimeError(
        f"{dev.platform} device {dev.device_kind!r} reports no bytes_limit; "
        "pass hbm_bytes= explicitly"
    )


def kernel_vmem_reserve(cache=None) -> int:
    """Total per-lane VMEM the tuned kernels keep resident, in bytes.

    Sums the tuning cache's recorded per-kernel maximum VMEM footprints
    (``TuningCache.vmem_footprints()``) — the worst tuned block geometry each
    kernel may pick. An empty or missing cache contributes 0, matching the
    "empty cache == heuristic behaviour" contract. ``cache=None`` reads the
    process-global cache (default table + env overlay).
    """
    if cache is None:
        from repro.tune.cache import get_tuning_cache

        cache = get_tuning_cache()
    return int(sum(cache.vmem_footprints().values()))


def suggest_population_size(
    cfg,
    mesh: Optional[Mesh] = None,
    *,
    hbm_bytes: Optional[int] = None,
    headroom: float = 0.6,
    bytes_per_param: int = BYTES_PER_PARAM,
    max_members_per_lane: int = 64,
    reserve_kernel_vmem: bool = False,
    tuning_cache=None,
) -> int:
    """Largest population chunk width the mesh can hold resident.

    Parameters
    ----------
    cfg : ArchConfig — ``cfg.param_count()`` sets the per-member state size.
    mesh : fleet mesh (1-D pop or 2-D pop x model). None = a single lane on
        the default device (the vmap engine's situation).
    hbm_bytes : per-device memory budget; default: the device's reported
        ``bytes_limit`` — 16 GiB on the CPU backend, which reports none; an
        accelerator that reports none raises.
    headroom : fraction of ``hbm_bytes`` the member state may use — the rest
        is activations/gradients for the in-flight update and XLA scratch.
    bytes_per_param : bytes per parameter per member: resident params and
        optimizer state plus the update's transient copies
        (:data:`BYTES_PER_PARAM`).
    max_members_per_lane : cap on members per pop slice (compile-shape and
        latency guard, matching ``population_size`` chunking semantics).
    reserve_kernel_vmem : opt-in — subtract :func:`kernel_vmem_reserve` from
        the member-state budget before sizing, so tuned kernel geometry
        (bigger resident blocks) shrinks the suggestion instead of paging.
    tuning_cache : explicit ``TuningCache`` for the reserve; None reads the
        process-global cache. Ignored unless ``reserve_kernel_vmem=True``.

    Returns a population size that is a positive multiple of the pop-axis
    extent (the sharded engine would round it up anyway). Raises ValueError
    when even ONE member per lane exceeds the budget — the model needs a
    bigger model axis, not a smaller population.
    """
    if hbm_bytes is None:
        hbm_bytes = _device_memory_bytes(mesh)
    if hbm_bytes <= 0:
        raise ValueError(f"hbm_bytes must be positive, got {hbm_bytes}")
    if not 0.0 < headroom <= 1.0:
        raise ValueError(f"headroom must be in (0, 1], got {headroom}")
    if reserve_kernel_vmem:
        reserve = kernel_vmem_reserve(tuning_cache)
        if reserve >= hbm_bytes:
            raise ValueError(
                f"kernel VMEM reserve {reserve} bytes exceeds the "
                f"{hbm_bytes}-byte device budget"
            )
        hbm_bytes = hbm_bytes - reserve

    pop_extent, model_extent = 1, 1
    if mesh is not None:
        sizes = dict(mesh.shape)
        pop_extent = int(sizes.pop("pop", 1))
        model_extent = int(math.prod(sizes.values())) if sizes else 1

    member_bytes = int(cfg.param_count()) * int(bytes_per_param)
    # the model axis shards each member's resident state within a pop slice
    per_device_member_bytes = max(1, member_bytes // model_extent)
    budget = int(hbm_bytes * headroom)
    members_per_lane = budget // per_device_member_bytes
    if members_per_lane < 1:
        raise ValueError(
            f"one member needs {per_device_member_bytes / 2**30:.2f} GiB resident "
            f"({member_bytes / 2**30:.2f} GiB / model extent {model_extent}) but the "
            f"budget is {budget / 2**30:.2f} GiB ({headroom:.0%} of "
            f"{hbm_bytes / 2**30:.2f} GiB) — grow the mesh's model axis"
        )
    members_per_lane = min(int(members_per_lane), int(max_members_per_lane))
    return members_per_lane * pop_extent
