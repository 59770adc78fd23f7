"""Backend registry for the ``repro.api`` Parsa facade.

Every partitioning strategy in the repo is one registered backend with the
uniform signature ``fn(graph, config, init_sets=None) -> BackendOutput``:

  * ``host``                — Algorithm 3 (sequential reference); with
    ``config.blocks > 1`` or ``config.init_iters > 0`` the §4.2/§4.4
    subgraph-streaming driver (``sequential_parsa_impl``).
  * ``device_scan``         — the device-resident blocked pipeline: one
    jitted ``lax.scan`` over packed bitmask blocks, fused cost+select
    (``blocked_partition_u_impl``).
  * ``host_blocked_oracle`` — the seed per-block host loop, kept as the
    test reference ``device_scan`` must match bit for bit.
  * ``parallel_sim``        — the deterministic Alg 4 parameter-server
    simulation with W workers and bounded delay τ, on the packed-word wire
    format; fills ``BackendOutput.traffic``.
  * ``parallel_device``     — the real distributed Alg 4: shard_map multi-
    worker blocked scans over packed bitmasks with periodic all_gather +
    OR merges (``merge_every`` blocks of staleness); fills
    ``BackendOutput.traffic`` with the same word-byte units.

New distributed strategies (e.g. randomized distributed submodular
maximization, arXiv:1502.02606, or sparse-DNN partitioning workloads,
arXiv:2104.11805) plug in as one more ``@register_backend`` function
instead of another ad-hoc module-level entry point.

This module is imported by ``repro.api`` and must not import it back —
backends receive the (duck-typed) ``ParsaConfig`` and return plain
``BackendOutput`` records; the facade owns result assembly.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np

from .core.bipartite import BipartiteGraph
from .core.jax_partition import (
    blocked_partition_u_hostloop_impl,
    blocked_partition_u_impl,
    parallel_blocked_partition_u_impl,
)
from .core.parallel import global_initialization, parallel_parsa_impl
from .core.partition_u import partition_u_impl
from .core.subgraphs import sequential_parsa_impl

__all__ = [
    "BackendOutput",
    "TrafficCounters",
    "register_backend",
    "get_backend",
    "available_backends",
    "BACKENDS",
]


@dataclasses.dataclass(frozen=True)
class TrafficCounters:
    """Parameter-server traffic of the partitioning run itself (Alg 4).

    Units are *bitmask-word bytes* in both directions (4 bytes per 32
    parameters, the packed wire format shared by ``parallel_sim`` and
    ``parallel_device``): pulls count the packed words a worker reads
    (``parallel_sim``: the words covering the task's V support;
    ``parallel_device``: the full (k, W) set per merge), pushes count the
    delta-encoded changed words (Alg 4 worker line 9)."""

    pushed_bytes: int = 0          # worker→server traffic (delta-encoded words)
    pulled_bytes: int = 0          # server→worker traffic (packed words)
    tasks: int = 0
    stale_pushes_missed: int = 0   # pushes invisible to a pull due to delay
    migration_bytes: int = 0       # one-time recovery/re-shard traffic
                                   #   (worker loss, grow/shrink, drift
                                   #   repair) — split from push/pull so
                                   #   steady-state and recovery traffic
                                   #   stay separable in benchmark rows

    def __add__(self, other: "TrafficCounters") -> "TrafficCounters":
        """Component-wise accumulation — streaming sessions sum per-feed
        and migration counters into one session total (same units, so the
        sum is meaningful)."""
        if not isinstance(other, TrafficCounters):
            return NotImplemented
        return TrafficCounters(
            self.pushed_bytes + other.pushed_bytes,
            self.pulled_bytes + other.pulled_bytes,
            self.tasks + other.tasks,
            self.stale_pushes_missed + other.stale_pushes_missed,
            self.migration_bytes + other.migration_bytes)


@dataclasses.dataclass
class BackendOutput:
    """What a backend hands back to the facade.

    Exactly one of ``s_masks`` (packed (k, W) int32 bitmasks) or
    ``neighbor_sets`` (dense (k, |V|) bool) must be set; the facade packs /
    lazily unpacks the other view.  Device backends may return ``parts_u``
    and ``s_masks`` as *device* arrays (when the config asks for a device-
    resident refine/metrics phase, ``refine_backend="device"``) — the facade
    converts to numpy only at result assembly, so nothing round-trips
    through the host between phases.  ``timings`` carries backend-internal
    phase attribution (today: ``"pack"``, the host-side bitmask packing
    seconds the facade splits out of ``timings["partition_u"]``).
    """

    parts_u: np.ndarray
    s_masks: np.ndarray | None = None
    neighbor_sets: np.ndarray | None = None
    traffic: TrafficCounters | None = None
    timings: dict | None = None


BackendFn = Callable[..., BackendOutput]
BACKENDS: dict[str, BackendFn] = {}


def register_backend(name: str) -> Callable[[BackendFn], BackendFn]:
    """Decorator: register ``fn(graph, config, init_sets=None)`` under
    ``name`` so ``ParsaConfig(backend=name)`` can reach it."""

    def deco(fn: BackendFn) -> BackendFn:
        BACKENDS[name] = fn
        fn.backend_name = name  # type: ignore[attr-defined]
        return fn

    return deco


def get_backend(name: str) -> BackendFn:
    try:
        return BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown Parsa backend {name!r}; available: "
            f"{', '.join(available_backends())}") from None


def available_backends() -> list[str]:
    return sorted(BACKENDS)


# --------------------------------------------------------------------------
# Registered adapters over the existing implementations.
# --------------------------------------------------------------------------
@register_backend("host")
def host_backend(graph: BipartiteGraph, config, init_sets=None) -> BackendOutput:
    """Sequential reference: Alg 3, optionally streamed over ``blocks``
    subgraphs with ``init_iters`` individual-initialization passes."""
    if config.blocks <= 1 and config.init_iters == 0:
        res = partition_u_impl(
            graph, config.k, init_sets=init_sets, theta=config.theta,
            select=config.select, seed=config.seed)
        return BackendOutput(res.parts_u, neighbor_sets=res.neighbor_sets)
    parts_u, sets = sequential_parsa_impl(
        graph, config.k, b=config.blocks, a=config.init_iters,
        theta=config.theta, select=config.select, seed=config.seed,
        init_sets=init_sets)
    return BackendOutput(parts_u, neighbor_sets=sets)


@register_backend("device_scan")
def device_scan_backend(graph: BipartiteGraph, config, init_sets=None) -> BackendOutput:
    """Device-resident blocked pipeline: one jitted scan, O(1) dispatches."""
    timings: dict = {}
    parts_u, s_masks = blocked_partition_u_impl(
        graph, config.k, block=config.block_size, init_sets=init_sets,
        use_kernel=config.use_kernel, interpret=config.interpret,
        seed=config.seed, cap=config.cap,
        as_numpy=getattr(config, "refine_backend", "host") != "device",
        timings=timings,
        sketch=getattr(config, "set_repr", "exact") == "sketch")
    return BackendOutput(parts_u, s_masks=s_masks, timings=timings)


@register_backend("host_blocked_oracle")
def host_blocked_oracle_backend(graph: BipartiteGraph, config, init_sets=None) -> BackendOutput:
    """Seed per-block host loop — the test reference for ``device_scan``."""
    parts_u, s_masks = blocked_partition_u_hostloop_impl(
        graph, config.k, block=config.block_size, init_sets=init_sets,
        use_kernel=config.use_kernel, interpret=config.interpret,
        seed=config.seed)
    return BackendOutput(parts_u, s_masks=s_masks)


@register_backend("parallel_sim")
def parallel_sim_backend(graph: BipartiteGraph, config, init_sets=None) -> BackendOutput:
    """Alg 4 parameter-server simulation (W workers, bounded delay τ).

    With ``config.global_init_frac > 0`` and no explicit warm start, runs
    §4.4 global initialization first and seeds every worker from it.
    """
    if init_sets is None and config.global_init_frac > 0:
        init_sets = global_initialization(
            graph, config.k, sample_frac=config.global_init_frac,
            theta=config.theta, select=config.select, seed=config.seed)
    report, s_masks = parallel_parsa_impl(
        graph, config.k, b=config.blocks, a=config.init_iters,
        workers=config.workers, tau=config.tau, theta=config.theta,
        select=config.select, seed=config.seed, init_sets=init_sets)
    traffic = TrafficCounters(
        pushed_bytes=report.pushed_bytes, pulled_bytes=report.pulled_bytes,
        tasks=report.tasks, stale_pushes_missed=report.stale_pushes_missed)
    return BackendOutput(report.parts_u, s_masks=s_masks, traffic=traffic)


@register_backend("parallel_device")
def parallel_device_backend(graph: BipartiteGraph, config, init_sets=None) -> BackendOutput:
    """Device-parallel Algorithm 4: shard_map multi-worker blocked Parsa.

    ``config.workers`` shards of U run the single-dispatch blocked scan
    concurrently, one per mesh device, each against a device-local stale
    copy of the packed server sets; every ``config.merge_every`` blocks the
    shards OR-merge (all_gather + lattice OR on uint32 words, the bulk-
    synchronous server union-push, τ ≡ merge_every − 1).  ``config.devices``
    overrides the mesh width (defaults to ``workers``); with one worker the
    output is bit-identical to ``device_scan``.  Global sizes stay balanced
    within ``workers`` (stale catch-ups can overlap when k ∤ |U| — see
    ``parallel_blocked_partition_u_impl``).  Supports §4.4 global
    initialization via ``global_init_frac`` like ``parallel_sim``.
    """
    if init_sets is None and config.global_init_frac > 0:
        init_sets = global_initialization(
            graph, config.k, sample_frac=config.global_init_frac,
            theta=config.theta, select=config.select, seed=config.seed)
    workers = config.devices if config.devices is not None else config.workers
    timings: dict = {}
    parts_u, s_masks, traffic = parallel_blocked_partition_u_impl(
        graph, config.k, workers=workers, block=config.block_size,
        merge_every=config.merge_every, init_sets=init_sets,
        use_kernel=config.use_kernel, interpret=config.interpret,
        seed=config.seed, cap=config.cap,
        as_numpy=getattr(config, "refine_backend", "host") != "device",
        timings=timings,
        sketch=getattr(config, "set_repr", "exact") == "sketch")
    return BackendOutput(parts_u, s_masks=s_masks,
                         traffic=TrafficCounters(**traffic), timings=timings)
