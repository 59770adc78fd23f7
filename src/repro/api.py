"""Public Parsa facade: one config, one ``partition()`` entry point, one
result type.

The paper's pipeline is one conceptual operation — partition U (Alg 3/4),
refine V (Alg 2), place parameters, measure traffic — and this module is
the one place it is exposed:

    from repro.api import ParsaConfig, partition

    cfg = ParsaConfig(k=16, backend="host", blocks=8, init_iters=8)
    res = partition(graph, cfg)           # PartitionResult
    res.parts_u, res.parts_v              # Alg 3 + Alg 2 assignments
    res.metrics.traffic_max               # objectives (4)/(6)/(7)
    res.timings["partition_u"]            # wall clock per phase
    res2 = res.refine(tomorrows_graph)    # warm-start / incremental

Backends (``host``, ``device_scan``, ``host_blocked_oracle``,
``parallel_sim``, ``parallel_device``) live in the registry in
``repro.api_backends``; add a strategy with ``@register_backend`` instead
of a new module-level function.  This is the one entry point: the
``_impl`` functions under ``repro.core`` are the registered
implementations, not a second public door.

``PartitionResult`` uniformly carries the final neighbor sets as packed
bitmasks (``s_masks``, (k, ceil(|V|/32)) int32) with a lazy dense bool view
(``neighbor_sets``), so host- and device-produced sets are interchangeable
for warm starts.
"""
from __future__ import annotations

import dataclasses
import time
from typing import TYPE_CHECKING

import numpy as np

from .api_backends import (
    BACKENDS,
    BackendOutput,
    TrafficCounters,
    available_backends,
    get_backend,
    register_backend,
)
from .core.bipartite import BipartiteGraph
from .core.costs import PartitionMetrics, evaluate
from .core.partition_v import partition_v
from .kernels.parsa_cost import pack_bitmask, unpack_bitmask

if TYPE_CHECKING:  # avoid the placement ↔ api import cycle at runtime
    from .core.placement import Placement
    from .sketch import SketchSpec

__all__ = [
    "ParsaConfig",
    "PartitionResult",
    "PartitionMetrics",
    "TrafficCounters",
    "partition",
    "register_backend",
    "available_backends",
    # streaming surface (lazy — see __getattr__)
    "ParsaStreamConfig",
    "StreamSession",
    "StreamUpdate",
    "stream_partition",
    # elastic surface (lazy — see __getattr__)
    "ChaosEvent",
    "ChaosSchedule",
    "ElasticConfig",
    "ElasticPolicy",
    "ElasticSession",
    "SLOAutoscaler",
    "SLOConfig",
    "ThresholdPolicy",
    # serving surface (lazy — see __getattr__)
    "PSRequestSource",
    "RequestMix",
    "ServingConfig",
    "ServingEngine",
    "TelemetryBus",
    "TelemetrySnapshot",
    "ZipfWorkload",
    # observability surface (lazy — see __getattr__)
    "Observability",
    "Tracer",
    "FlightRecorder",
    "Explanation",
    "to_chrome_trace",
    "chrome_trace_json",
    "save_chrome_trace",
    "prometheus_text",
]

# Streaming lives in ``repro.stream`` (online incremental Parsa over
# growing graphs) but is surfaced here so the facade stays the one import:
#     from repro.api import ParsaStreamConfig, stream_partition
# Loaded lazily to keep `import repro.api` free of the stream module's
# device-state machinery until it is actually used (and to avoid the
# stream → api → stream import cycle at module load).
_STREAM_EXPORTS = ("ParsaStreamConfig", "StreamSession", "StreamUpdate",
                   "stream_partition")

# The elastic serving layer (``repro.elastic``: runtime-variable k, chaos
# injection, straggler-aware routing) is surfaced the same lazy way.
_ELASTIC_EXPORTS = ("ChaosEvent", "ChaosSchedule", "ElasticConfig",
                    "ElasticPolicy", "ElasticSession", "SLOAutoscaler",
                    "SLOConfig", "ThresholdPolicy")

# The request-driven serving engine (``repro.serving``: async pull/compute
# overlap over PSCluster shards) — same lazy surfacing.
_SERVING_EXPORTS = ("PSRequestSource", "RequestMix", "ServingConfig",
                    "ServingEngine", "TelemetryBus", "TelemetrySnapshot",
                    "ZipfWorkload")

# Observability (``repro.obs``: virtual-clock tracing, flight recorder,
# Perfetto/Prometheus export) — the ``obs=`` hook's types.
_OBS_EXPORTS = ("Observability", "Tracer", "FlightRecorder", "Explanation",
                "to_chrome_trace", "chrome_trace_json", "save_chrome_trace",
                "prometheus_text")


def __getattr__(name: str):
    if name in _STREAM_EXPORTS:
        from . import stream

        return getattr(stream, name)
    if name in _ELASTIC_EXPORTS:
        from . import elastic

        return getattr(elastic, name)
    if name in _SERVING_EXPORTS:
        from . import serving

        return getattr(serving, name)
    if name in _OBS_EXPORTS:
        from . import obs

        return getattr(obs, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

_SELECTS = ("size", "footprint")
_REFINE_BACKENDS = ("host", "device")
_SET_REPRS = ("exact", "sketch")


@dataclasses.dataclass(frozen=True)
class ParsaConfig:
    """Every knob of the Parsa pipeline, validated at construction.

    Only ``k`` is required.  Fields group by the phase they drive; backends
    ignore knobs that don't apply to them (e.g. ``workers`` outside
    ``parallel_sim``).
    """

    k: int
    backend: str = "host"

    # ---- subgraph streaming (§4.2/§4.4) — host / parallel_sim backends
    blocks: int = 1            # b: number of subgraphs (1 = global greedy)
    init_iters: int = 0        # a: individual-initialization iterations
    theta: int = 1000          # bucket-queue head-pointer range (§4.1)
    select: str = "size"       # "size" (perfect balance) | "footprint"
    seed: int = 0

    # ---- device backend knobs (device_scan / host_blocked_oracle)
    block_size: int = 256      # B: vertices greedily assigned per block
    cap: int = 48              # compact word-list width per vertex
    use_kernel: bool = False   # fused Pallas cost+select (TPU) vs jnp path
    interpret: bool | None = None  # force Pallas interpret mode (CI)

    # ---- parallel backend knobs (Alg 4: parallel_sim / parallel_device)
    workers: int = 4           # W concurrent workers
    tau: int | None = 0        # max push delay in tasks; None = eventual
    global_init_frac: float = 0.0  # §4.4 global-init sample fraction
    merge_every: int = 1       # parallel_device: blocks between OR-merges
                               #   (τ ≡ merge_every − 1 blocks of staleness)
    devices: int | None = None  # parallel_device mesh width; None → workers

    # ---- sketched server sets (repro.sketch — any backend; unlocks the
    #      VMEM-resident select kernel on the device backends)
    set_repr: str = "exact"    # "exact" | "sketch" (column-compressed sets)
    sketch_hot_bits: int = 4096    # exact identity slots (top-footprint V)
    sketch_bucket_bits: int = 8192  # hashed shared slots for the cold tail

    # ---- composition
    refine_v: bool = True      # run Alg 2 (partition_v) after partition_u
    sweeps: int = 2            # Alg 2 re-assignment sweeps
    refine_backend: str = "host"   # "host" = numpy oracle; "device" = the
                               #   packed-word refine + metrics pipeline
                               #   (bit-identical, O(1) dispatches/phase)
    refine_chunk: int = 1024   # C: parameters swept per device chunk
    placement: bool = False    # also derive an embedding Placement

    def __post_init__(self):
        if not isinstance(self.k, (int, np.integer)) or self.k <= 0:
            raise ValueError(f"k must be a positive int, got {self.k!r}")
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown Parsa backend {self.backend!r}; available: "
                f"{', '.join(available_backends())}")
        if self.blocks < 1:
            raise ValueError(f"blocks must be >= 1, got {self.blocks}")
        if self.init_iters < 0:
            raise ValueError(f"init_iters must be >= 0, got {self.init_iters}")
        if self.select not in _SELECTS:
            raise ValueError(f"select must be one of {_SELECTS}, got {self.select!r}")
        if self.block_size <= 0 or self.block_size % 8 != 0:
            raise ValueError(
                f"block_size must be a positive multiple of 8 (sublane "
                f"alignment of the fused select kernel), got {self.block_size}")
        if self.cap <= 0:
            raise ValueError(f"cap must be > 0, got {self.cap}")
        if self.workers < 1:
            raise ValueError(f"workers must be >= 1, got {self.workers}")
        if self.tau is not None and self.tau < 0:
            raise ValueError(f"tau must be >= 0 or None, got {self.tau}")
        if not 0.0 <= self.global_init_frac <= 1.0:
            raise ValueError(
                f"global_init_frac must be in [0, 1], got {self.global_init_frac}")
        if self.merge_every < 1:
            raise ValueError(
                f"merge_every must be >= 1, got {self.merge_every}")
        if self.devices is not None and self.devices < 1:
            raise ValueError(
                f"devices must be >= 1 or None, got {self.devices}")
        if self.set_repr not in _SET_REPRS:
            raise ValueError(
                f"set_repr must be one of {_SET_REPRS}, got "
                f"{self.set_repr!r}")
        if self.sketch_hot_bits < 0 or self.sketch_hot_bits % 32 != 0:
            raise ValueError(
                f"sketch_hot_bits must be a nonnegative multiple of 32 "
                f"(packed word alignment), got {self.sketch_hot_bits}")
        if self.sketch_bucket_bits <= 0 or self.sketch_bucket_bits % 32 != 0:
            raise ValueError(
                f"sketch_bucket_bits must be a positive multiple of 32 "
                f"(packed word alignment), got {self.sketch_bucket_bits}")
        if self.sweeps < 1:
            raise ValueError(f"sweeps must be >= 1, got {self.sweeps}")
        if self.refine_backend not in _REFINE_BACKENDS:
            raise ValueError(
                f"refine_backend must be one of {_REFINE_BACKENDS}, got "
                f"{self.refine_backend!r}")
        if self.refine_chunk <= 0 or self.refine_chunk % 32 != 0:
            raise ValueError(
                f"refine_chunk must be a positive multiple of 32 (the packed "
                f"word width), got {self.refine_chunk}")
        if self.placement and not self.refine_v:
            raise ValueError("placement=True requires refine_v=True "
                             "(the embedding layout needs parts_v)")

    def replace(self, **changes) -> "ParsaConfig":
        return dataclasses.replace(self, **changes)


@dataclasses.dataclass
class PartitionResult:
    """Uniform output of every backend.

    The final neighbor sets are carried in whichever representation the
    backend produced and converted lazily on first access: ``s_masks`` is
    the packed int32 bitmask view (the device-native layout),
    ``neighbor_sets`` the dense bool view of the same bits.
    """

    parts_u: np.ndarray                 # (|U|,) int32
    parts_v: np.ndarray | None          # (|V|,) int32 or None (refine_v=False)
    num_v: int                          # domain of s_masks — the sketched
                                        #   width when ``sketch`` is set
    k: int
    config: ParsaConfig
    metrics: PartitionMetrics
    timings: dict[str, float]           # seconds per phase + "total"
    traffic: TrafficCounters | None = None   # parallel_sim / parallel_device
    placement: "Placement | None" = None     # config.placement only
    sketch: "SketchSpec | None" = None  # set_repr="sketch": the column map
                                        #   (parts_v is expanded to the TRUE
                                        #   extent ``sketch.num_v``; metrics
                                        #   are sketch-space estimates)
    _packed_sets: np.ndarray | None = dataclasses.field(
        default=None, repr=False, compare=False)
    _dense_sets: np.ndarray | None = dataclasses.field(
        default=None, repr=False, compare=False)

    def __post_init__(self):
        if self._packed_sets is None and self._dense_sets is None:
            raise ValueError("PartitionResult needs packed or dense "
                             "neighbor sets")

    @property
    def s_masks(self) -> np.ndarray:
        """(k, ceil(|V|/32)) int32 — packed bitmask view, built on first use."""
        if self._packed_sets is None:
            self._packed_sets = np.asarray(pack_bitmask(
                np.asarray(self._dense_sets, dtype=bool), self.num_v))
        return self._packed_sets

    @property
    def neighbor_sets(self) -> np.ndarray:
        """(k, |V|) bool — dense view of the neighbor sets, built on first use."""
        if self._dense_sets is None:
            self._dense_sets = unpack_bitmask(self._packed_sets, self.num_v)
        return self._dense_sets

    def refine(self, graph: BipartiteGraph,
               config: ParsaConfig | None = None) -> "PartitionResult":
        """Warm-start / incremental repartitioning: partition ``graph``
        seeding the neighbor sets from this result (§4.4 incremental mode)
        instead of hand-threading ``init_sets``.

        Hands over whichever neighbor-set view already exists: a device
        backend's packed ``s_masks`` flow straight into the next run's
        packed warm start (no dense (k, |V|) unpack), a host backend's
        dense sets stay dense — every backend accepts both.

        Sketched results refine against the TRUE graph: the stored
        ``SketchSpec`` is handed through so the new run reuses the exact
        same column map (re-deriving a footprint-ranked map on the new
        graph would silently scramble the warm-start masks).
        """
        if graph.num_v != self.num_v and not (
                self.sketch is not None
                and graph.num_v == self.sketch.num_v):
            raise ValueError(
                f"refine() needs a graph over the same parameter side: "
                f"result has num_v={self.num_v}, graph has "
                f"num_v={graph.num_v}")
        sets = (self._packed_sets if self._packed_sets is not None
                else self._dense_sets)
        return partition(graph, config or self.config, init_sets=sets,
                         sketch_spec=self.sketch)


def partition(
    graph: BipartiteGraph,
    config: ParsaConfig,
    *,
    init_sets: np.ndarray | None = None,
    sketch_spec: "SketchSpec | None" = None,
) -> PartitionResult:
    """Run the full Parsa pipeline described by ``config`` on ``graph``.

    Phases: backend partition_u → optional Alg 2 V-refinement → exact
    metrics (objectives (4)/(6)/(7)) → optional embedding placement.  Each
    phase's wall clock lands in ``result.timings``; device backends report
    their host-side bitmask packing separately as ``timings["pack"]`` so
    ``timings["partition_u"]`` is the scan alone.  ``init_sets`` is the
    internal warm-start hook — prefer ``PartitionResult.refine``; both
    dense (k, |V|) bool sets and packed (k, W) int32 words are accepted.

    With ``config.refine_backend == "device"`` the V-refinement and the
    metrics run on device over packed words (``core.jax_refine``),
    consuming the backend's ``parts_u`` without a host round trip and
    sharing one packed need matrix between the two phases — bit-identical
    to the host oracles, O(1) XLA dispatches per phase.
    """
    backend = get_backend(config.backend)
    timings: dict[str, float] = {}
    t_start = time.perf_counter()

    # ---- sketch phase: compress the V columns once, then run the WHOLE
    # pipeline (backend scan, refine, metrics) at the sketched width.  The
    # union lattice the backends rely on is preserved exactly (a hash of a
    # union is the union of the hashes), so nothing downstream changes —
    # only the packed width does.
    sketch = None
    run_graph = graph
    if getattr(config, "set_repr", "exact") == "sketch":
        from .sketch import SketchSpec, rank_hot_columns

        t0 = time.perf_counter()
        if sketch_spec is not None:
            sketch = sketch_spec
        else:
            hot_ids = None
            if 0 < config.sketch_hot_bits < graph.num_v:
                hot_ids = rank_hot_columns(graph, config.sketch_hot_bits)
            sketch = SketchSpec.for_graph(
                graph.num_v, config.sketch_hot_bits,
                config.sketch_bucket_bits, seed=config.seed,
                hot_ids=hot_ids)
        if config.placement and not sketch.is_exact:
            raise ValueError(
                "placement=True needs exact parameter identities; a "
                "compressing sketch co-locates hashed columns — raise "
                "sketch_hot_bits to >= num_v or use set_repr='exact'")
        run_graph = sketch.sketch_graph(graph)
        if init_sets is not None and not sketch.is_exact:
            w = np.asarray(init_sets).shape[1]
            if w != sketch.width_words:  # true-domain sets: compress them
                init_sets = sketch.sketch_masks(init_sets, graph.num_v)
        timings["sketch"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    out: BackendOutput = backend(run_graph, config, init_sets=init_sets)
    if hasattr(out.parts_u, "block_until_ready"):
        # device-resident outputs: sync (no transfer) so phase attribution
        # doesn't leak the async scan into the refine clock
        out.parts_u.block_until_ready()
    elapsed = time.perf_counter() - t0
    pack_s = (out.timings or {}).get("pack")
    if pack_s is not None:
        timings["pack"] = pack_s
        timings["partition_u"] = elapsed - pack_s
    else:
        timings["partition_u"] = elapsed

    on_device = config.refine_backend == "device"
    parts_v = parts_v_dev = need_words = None
    if on_device and init_sets is None and config.init_iters == 0 \
            and config.global_init_frac == 0.0:
        # Cold-start invariant: with no warm start and no §4.4 seeding,
        # every backend's final S_i is EXACTLY N(U_i) (union of assigned
        # vertices' neighborhoods), so the packed sets it already returned
        # ARE the need matrix — the refine/metrics phases reuse them and
        # skip the segment-OR need pack entirely.
        import jax.numpy as jnp  # lazy: keep host-only paths jax-free

        from .kernels.parsa_cost import coerce_packed_sets

        # s_masks may already live on device — jnp.asarray keeps it there;
        # only host backends' dense sets go through the packing coercion
        need_words = (jnp.asarray(out.s_masks) if out.s_masks is not None
                      else jnp.asarray(coerce_packed_sets(
                          out.neighbor_sets, run_graph.num_v)))
    if config.refine_v:
        t0 = time.perf_counter()
        if on_device:
            from .core.jax_refine import refine_v_device  # lazy: jax cost

            parts_v_dev, need_words = refine_v_device(
                run_graph, out.parts_u, config.k, sweeps=config.sweeps,
                chunk=config.refine_chunk, use_kernel=config.use_kernel,
                interpret=config.interpret, need_words=need_words)
            parts_v_dev.block_until_ready()
            parts_v = np.asarray(parts_v_dev)
        else:
            parts_v = partition_v(run_graph, np.asarray(out.parts_u),
                                  config.k, sweeps=config.sweeps)
        timings["partition_v"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    if on_device:
        from .core.jax_refine import evaluate_device

        metrics = evaluate_device(run_graph, out.parts_u, parts_v_dev,
                                  config.k, need_words=need_words)
    else:
        metrics = evaluate(run_graph, np.asarray(out.parts_u), parts_v,
                           config.k)
    timings["metrics"] = time.perf_counter() - t0

    if sketch is not None and parts_v is not None and not sketch.is_exact:
        # back to the true parameter extent: every real column is served by
        # the machine of its sketch slot (hot → its exact Alg 2 host,
        # bucketed tail → hash co-location)
        parts_v = sketch.expand_parts_v(parts_v)

    placement = None
    if config.placement:
        from .core.placement import placement_from_parts  # lazy: cycle

        t0 = time.perf_counter()
        placement = placement_from_parts(out.parts_u, parts_v,
                                         run_graph.num_v, config.k)
        timings["placement"] = time.perf_counter() - t0

    timings["total"] = time.perf_counter() - t_start

    return PartitionResult(
        parts_u=np.asarray(out.parts_u),
        parts_v=parts_v,
        num_v=run_graph.num_v,
        k=config.k,
        config=config,
        metrics=metrics,
        timings=timings,
        traffic=out.traffic,
        placement=placement,
        sketch=sketch,
        _packed_sets=None if out.s_masks is None else np.asarray(out.s_masks),
        _dense_sets=out.neighbor_sets,
    )
