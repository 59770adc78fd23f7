r"""Online incremental Parsa: partition a growing graph chunk by chunk.

The paper's blocked greedy (§4.2) is already an online algorithm — every
block is assigned against the live neighbor sets and never revisited — so
a *streaming* partitioner needs no new math, only new plumbing: keep the
packed ``(k, W)`` server sets resident on device across arrivals and run
each arriving chunk through the existing fused cost+select scan with the
live sets as the carry.

    session = StreamSession(ParsaStreamConfig(base=ParsaConfig(
        k=16, backend="device_scan")), num_v=65_536)
    for chunk in arriving_graphs:          # BipartiteGraph chunks
        upd = session.feed(chunk)          # ONE scan dispatch (asserted)
        upd.parts, upd.metrics             # incremental delta
    res = session.result()                 # full PartitionResult

``feed`` is O(chunk) work and O(1) XLA dispatches: one ``_partition_scan``
launch (the same jitted program ``device_scan`` runs, carries donated) plus
one popcount-metrics launch.  Same-shaped chunks hit the jit cache: the
list of overflow words (the words of rows past ``cap``) keeps a
power-of-two capacity that starts at a floor set by ``tb_pad`` and only
grows, on a feed that would not fit, so data jitter does not retrigger
compilation.  With ``workers > 1`` (Alg 4) the chunk's blocks fan out
across the ``parallel_device`` mesh, with *randomized* block→worker
assignment (arXiv:1502.02606: random data distribution preserves the
distributed greedy's approximation guarantees in expectation): each
worker's share is copied straight to its own device, one
``_parallel_partition_scan`` launch runs every worker's blocks and
OR-merges the sets every ``merge_every`` blocks, and the live sets stay
replicated on the mesh between feeds.  Both paths time the same phases.

Drift repair: assignments are never revisited by ``feed``, so under
distribution drift the partition decays.  A ``DriftTracker`` watches the
per-feed popcount metrics and triggers ``repartition()`` — a warm-started
(§4.4 global-initialization) full repartition of the arena — whose result
is matched back onto the old labels by ``plan_migration`` so serving
machines keep the part closest to what they already host, with migration
bytes metered in ``TrafficCounters`` units.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Iterable

import numpy as np

from ..api import ParsaConfig, PartitionResult
from ..api_backends import TrafficCounters
from ..core.bipartite import BipartiteGraph
from ..core.costs import PartitionMetrics
from ..core.jax_partition import (
    _SCAN_BLOCK_FIELDS,
    _count_dispatch,
    _launch_parallel_scan,
    _parallel_traffic,
    _partition_scan,
    _place_parallel_blocks,
    _upload_blocks,
    blocked_partition_u_impl,
    pack_graph_blocks,
    parallel_blocked_partition_u_impl,
)
from ..core.parallel import global_initialization
from ..kernels.parsa_cost import coerce_packed_sets
from ..obs import phase
from .arena import StreamArena
from .drift import DriftDecision, DriftTracker
from .migrate import MigrationPlan, plan_migration

__all__ = ["ParsaStreamConfig", "StreamSession", "StreamUpdate",
           "stream_partition"]

_STREAM_BACKENDS = ("device_scan", "parallel_device")


@dataclasses.dataclass(frozen=True)
class ParsaStreamConfig:
    """Streaming knobs on top of a device ``ParsaConfig``.

    ``base`` supplies the partitioning knobs the feed scan shares with the
    one-shot pipeline (k, block_size, cap, use_kernel/interpret, seed;
    workers/merge_every/devices when ``base.backend == "parallel_device"``).
    The stream fields control drift repair and shape stability.
    """

    base: ParsaConfig
    drift_window: int = 8          # feeds the drift baseline spans
    drift_threshold: float = 1.15  # degradation ratio that trips repair
    drift_min_feeds: int = 2       # history before a trigger is allowed
    repartition: str = "drift"     # "drift" (auto) | "never" (manual only)
    repartition_frac: float = 0.02  # §4.4 global-init sample; 0 = cold
    tb_pad: int = 8                # overflow-list floor: truncated rows
                                   #   a block holds at cap words each
    shuffle_blocks: bool = True    # randomized block→worker assignment

    def __post_init__(self):
        if self.base.backend not in _STREAM_BACKENDS:
            raise ValueError(
                f"streaming needs a device backend {_STREAM_BACKENDS}, got "
                f"base.backend={self.base.backend!r}")
        if self.repartition not in ("drift", "never"):
            raise ValueError(
                f"repartition must be 'drift' or 'never', got "
                f"{self.repartition!r}")
        if not 0.0 <= self.repartition_frac <= 1.0:
            raise ValueError(
                f"repartition_frac must be in [0, 1], got "
                f"{self.repartition_frac}")
        if self.tb_pad < 1:
            raise ValueError(f"tb_pad must be >= 1, got {self.tb_pad}")
        # window/threshold/min_feeds: fail at construction, not first feed
        DriftTracker(self.drift_window, self.drift_threshold,
                     self.drift_min_feeds)

    @property
    def workers(self) -> int:
        if self.base.backend != "parallel_device":
            return 1
        return (self.base.devices if self.base.devices is not None
                else self.base.workers)

    def replace(self, **changes) -> "ParsaStreamConfig":
        return dataclasses.replace(self, **changes)


@dataclasses.dataclass
class StreamUpdate:
    """Incremental ``PartitionResult`` delta for one fed chunk."""

    chunk: int                      # feed ordinal
    u_start: int                    # global U-id range this chunk occupies
    u_stop: int
    parts: np.ndarray               # (u_stop - u_start,) int32 assignments
    metrics: PartitionMetrics       # popcount objectives after this feed
    drift: DriftDecision | None     # None when repartition == "never"
    repartitioned: bool
    migration: MigrationPlan | None  # set when this feed triggered repair
    traffic: TrafficCounters | None  # parallel feeds: push/pull this feed
    timings: dict[str, float]       # host seconds per phase (see feed)
    dispatches: dict[str, int]      # device launches issued by this feed
    counters: dict[str, int]        # what the packed blocks hold and cost


# timings keys that sum phases rather than name one
_PHASE_SUMS = ("partition_u", "total")


def _packed_counters(packed, grew: bool) -> dict[str, int]:
    """Per-feed counts of the packed blocks a scan is given: the bytes of
    the arrays put on the device (``_SCAN_BLOCK_FIELDS``; ``order`` stays
    on the host); the truncated rows, the overflow words they carry and
    the overflow list's capacity; whether this feed raised that capacity
    (a new shape: the scan compiles); the blocks with no truncated row,
    which the jnp scan runs in compact space; and whether the chunk's rows
    arrived in ascending word order, so the pack ran without its sort."""
    spans = packed.overflow_spans
    return {"upload_bytes": sum(int(getattr(packed, f).nbytes)
                                for f in _SCAN_BLOCK_FIELDS),
            "channel_rows": int(packed.trunc.sum()),
            "channel_words": int((spans[:, 1] - spans[:, 0]).sum()),
            "channel_slots": int(packed.overflow_words.shape[1]),
            "channel_grew": int(grew),
            "compact_blocks": int((~packed.trunc.any(axis=1)).sum()),
            "pack_sorted": int(packed.sorted)}


def _merge_counters(blocks, traffic: TrafficCounters, k: int,
                    W: int) -> dict[str, int]:
    """Per-feed counts of an Alg 4 scan's merges: the rounds, the bytes
    each chip receives in their all-gathers (the other workers' (k, W)
    int32 sets, every round) and the changed words the workers pushed
    (``traffic.pushed_bytes`` in words)."""
    workers = len(blocks.devices)
    return {"merge_rounds": blocks.n_super,
            "merge_bytes": blocks.n_super * (workers - 1) * k * W * 4,
            "pushed_words": traffic.pushed_bytes // 4}


class StreamSession:
    """Partition a graph that grows over time, entirely on device.

    The live state (packed server sets + sizes) never leaves the device
    between feeds; the arena keeps the appended CSR on the host for
    snapshots, repartitions, and exact metrics.  ``parts`` holds the
    current assignment of every fed U vertex (relabeled in place when a
    drift repair lands).
    """

    def __init__(self, config: ParsaStreamConfig, num_v: int, obs=None):
        self.obs = obs   # repro.obs.Observability hook; None = off
        if config.workers > 1:
            # fail at construction, not mid-stream
            from ..core.jax_partition import resolve_worker_devices

            resolve_worker_devices(config.workers)
        self.config = config
        self.k = config.base.k
        # Sketched arenas (base.set_repr="sketch"): the live sets, the
        # appended CSR, and every scan run at the sketched width.  Streams
        # use the IDENTITY hot prefix [0, hot_bits) — a footprint ranking
        # cannot see future data — and the hash covers arbitrary column
        # ids, so V growth is free: the arena width never grows in sketch
        # mode.  ``self.sketch`` stays None when the spec collapses to the
        # exact identity (hot_bits ≥ num_v), keeping bit-parity for free.
        self.sketch = None
        self._true_num_v = num_v
        arena_v = num_v
        base = config.base
        if getattr(base, "set_repr", "exact") == "sketch":
            from ..sketch import SketchSpec

            spec = SketchSpec.for_graph(
                num_v, base.sketch_hot_bits, base.sketch_bucket_bits,
                seed=base.seed)
            if not spec.is_exact:
                self.sketch = spec
                arena_v = spec.width_bits
        self.arena = StreamArena(config.base.k, arena_v)
        self._parts_buf = np.empty(1024, np.int32)  # doubles with the arena
        self.tracker = DriftTracker(config.drift_window,
                                    config.drift_threshold,
                                    config.drift_min_feeds)
        self._rng = np.random.default_rng(config.base.seed)
        self.n_feeds = 0
        self.repartitions = 0
        # S_i == N(U_i) holds for pure cold streaming; a §4.4-seeded
        # repartition may add sampled bits, after which popcount metrics
        # over s_masks are an upper bound and result() recomputes exactly.
        self._need_exact = True
        self._pushed = 0
        self._pulled = 0
        self._tasks = 0
        self._stale = 0
        self._migrated = 0
        self._channel_slots = 0   # overflow-list capacity: only grows

    # ------------------------------------------------------------- feeding
    def feed(self, chunk: BipartiteGraph,
             worker_weights: np.ndarray | None = None) -> StreamUpdate:
        """Assign one arriving chunk of U vertices against the live sets.

        ``worker_weights`` (parallel feeds only) biases the randomized
        block→worker assignment toward faster workers — see
        ``_place_parallel_blocks``; the elastic layer supplies an EWMA
        of per-worker scan times here so stragglers receive fewer blocks.

        One jitted scan dispatch (plus one popcount-metrics dispatch) per
        call, O(1) in both stream length and chunk count — asserted via
        ``dispatch_counter`` in tests and CI.  May additionally run a
        drift-triggered ``repartition()`` before returning.

        Failure atomicity: the chunk is appended to the arena only AFTER
        its scan succeeds, so an error while packing or launching leaves
        the session's graph and parts consistent (retry-safe).  The live
        server sets are donated into the dispatch itself — a failure
        *inside* the launch remains unrecoverable, like any donated-carry
        jax program.

        Every host step falls in one ``repro.obs.phase``, timed into
        ``timings`` and marked as the profiler span ``parsa.feed.<phase>``
        (``feed=`` the ordinal): ``prepare``, ``pack``, ``upload`` (the
        packed blocks put on the device; with Alg 4 each worker's share
        on its own device), ``launch``, ``wait`` (the host blocked on the
        scan's parts), ``append``, ``metrics``, ``repartition`` when drift
        fires, and ``release`` (the packed blocks freed).  One-chip and
        Alg 4 feeds time the same phases.  ``partition_u`` is the sum from
        upload to append; ``counters`` are ``_packed_counters``'s, also
        attributes of the ``pack`` span, and on Alg 4 feeds
        ``_merge_counters``'s too, also attributes of the ``wait`` span.
        """
        from ..core.jax_partition import dispatch_counter

        base = self.config.base
        ordinal = self.n_feeds   # shared by every span of this feed
        timings: dict[str, float] = {}

        def step(name: str) -> phase:
            return phase(timings, name, span=f"parsa.feed.{name}",
                         feed=ordinal)

        t_total = time.perf_counter()
        with dispatch_counter() as counts:
            n = chunk.num_u
            with step("prepare"):
                if self.sketch is not None:
                    # host column remap only; the scan stays one dispatch
                    self._true_num_v = max(self._true_num_v, chunk.num_v)
                    chunk = self.sketch.sketch_graph(chunk)
                self.arena.prepare(chunk)  # validate + capacity growth only
                order = self._rng.permutation(n)
            with step("pack") as span:
                packed = pack_graph_blocks(
                    self.arena.capacity_graph(chunk), base.block_size,
                    order=order, cap=base.cap, tb_pad=self.config.tb_pad,
                    min_slots=self._channel_slots)
                slots = packed.overflow_words.shape[1]
                counters = _packed_counters(packed,
                                            slots > self._channel_slots)
                self._channel_slots = slots
                span.set_metadata(**counters)

            traffic = None
            if self.config.workers == 1:
                with step("upload"):
                    _count_dispatch(
                        "stream_feed_scan",
                        nbytes=(int(self.arena.s_masks.nbytes)
                                + int(self.arena.sizes.nbytes)),
                        k=self.k)
                    blocks = _upload_blocks(packed)
                with step("launch"):
                    parts_blocks, s_out, sz_out = _partition_scan(
                        *blocks, self.arena.s_masks, self.arena.sizes,
                        k=self.k, use_kernel=base.use_kernel,
                        interpret=base.interpret,
                        sketch=self.sketch is not None)
                with step("wait"):
                    flat = np.asarray(parts_blocks).reshape(-1)[:n]
            else:
                # the Alg 4 core the one-shot facade runs, step by step
                with step("upload"):
                    blocks = _place_parallel_blocks(
                        packed, workers=self.config.workers,
                        merge_every=base.merge_every,
                        shuffle_rng=(self._rng if self.config.shuffle_blocks
                                     else None),
                        worker_weights=worker_weights)
                with step("launch"):
                    parts_blocks, s_out, sz_out, pushed = \
                        _launch_parallel_scan(
                            blocks, self.arena.s_masks, self.arena.sizes,
                            k=self.k, use_kernel=base.use_kernel,
                            interpret=base.interpret,
                            count_name="stream_feed_scan",
                            sketch=self.sketch is not None)
                with step("wait") as span:
                    flat = blocks.in_stack_order(parts_blocks)[:n]
                    traffic = TrafficCounters(**_parallel_traffic(
                        blocks, int(pushed), self.k, self.arena.W_cap))
                    self._accumulate(traffic)
                    merge = _merge_counters(blocks, traffic, self.k,
                                            self.arena.W_cap)
                    counters.update(merge)
                    span.set_metadata(**merge)
            with step("append"):
                # scan succeeded — commit: live sets, CSR append, parts
                self.arena.s_masks, self.arena.sizes = s_out, sz_out
                u_start, u_stop = self.arena.append(chunk)
                parts_chunk = np.empty(n, np.int32)
                parts_chunk[order] = flat
                self._store_parts(u_start, parts_chunk)
            timings["partition_u"] = sum(
                timings.get(name, 0.0)
                for name in ("upload", "launch", "wait", "append"))

            decision = migration = None
            with step("metrics"):
                metrics = self._popcount_metrics()
                if self.config.repartition == "drift":
                    decision = self.tracker.update(metrics)
            if decision is not None and decision.repartition:
                with step("repartition"):
                    migration = self.repartition()
                    metrics = self._popcount_metrics()
            with step("release"):
                # free the packed blocks on the feed's clock rather than
                # untimed at its return
                packed = blocks = None
        self.n_feeds += 1
        timings["total"] = time.perf_counter() - t_total
        dispatches = {name: c for name, c in counts.items() if c}
        if self.obs is not None:
            self._trace_feed(n, u_start, u_stop, timings)
        return StreamUpdate(
            chunk=ordinal, u_start=u_start, u_stop=u_stop,
            parts=self.parts[u_start:u_stop].copy(), metrics=metrics,
            drift=decision, repartitioned=migration is not None,
            migration=migration, traffic=traffic, timings=timings,
            dispatches=dispatches, counters=counters)

    def _trace_feed(self, n: int, u_start: int, u_stop: int,
                    timings: dict) -> None:
        """Emit the ``feed → <phase>...`` span tree, one child per phase
        ``feed`` timed, in order.

        A feed has no modeled duration (it is host work, not a priced
        transfer), so the span occupies one fixed virtual unit shared
        equally by its children — deterministic across replays — and each
        phase's measured seconds ride along as ``wall_s`` evidence."""
        tr = self.obs.tracer
        sp = tr.begin("feed", v_start=tr.now, v_dur=1.0, track="stream",
                      feed=self.n_feeds - 1, rows=n, u_start=u_start,
                      u_stop=u_stop, k=self.k, workers=self.config.workers,
                      wall_s=timings.get("total"))
        phases = [name for name in timings if name not in _PHASE_SUMS]
        share = 1.0 / len(phases)
        for i, name in enumerate(phases):
            sp.child(name, i * share, share, wall_s=timings[name])
        tr.advance(1.0)

    @property
    def parts(self) -> np.ndarray:
        """Current assignment of every fed U vertex (view, not a copy)."""
        return self._parts_buf[: self.arena.num_u]

    def _store_parts(self, start: int, parts_chunk: np.ndarray) -> None:
        """Amortized-O(chunk) append: double the buffer like the arena
        does instead of re-concatenating the whole history every feed."""
        need = start + parts_chunk.shape[0]
        if need > self._parts_buf.shape[0]:
            cap = max(1, self._parts_buf.shape[0])
            while cap < need:
                cap *= 2
            buf = np.empty(cap, np.int32)
            buf[:start] = self._parts_buf[:start]
            self._parts_buf = buf
        self._parts_buf[start:need] = parts_chunk

    def _accumulate(self, t: TrafficCounters) -> None:
        self._pushed += t.pushed_bytes
        self._pulled += t.pulled_bytes
        self._tasks += t.tasks
        self._stale += t.stale_pushes_missed
        self._migrated += t.migration_bytes

    @property
    def traffic(self) -> TrafficCounters:
        """Cumulative session traffic: parallel-feed push/pull plus metered
        migration bytes, all in bitmask-word-byte units."""
        return TrafficCounters(self._pushed, self._pulled, self._tasks,
                               self._stale, self._migrated)

    # ------------------------------------------------------------- metrics
    def _popcount_metrics(self) -> PartitionMetrics:
        """Objectives (4)/(6) (+ the parts_v=None traffic convention) from
        the live packed sets — one tiny device launch, O(k·W)."""
        _count_dispatch("stream_metrics",
                        nbytes=int(self.arena.s_masks.nbytes))
        sizes, footprint = _popcount_rows(self.arena.s_masks,
                                          self.arena.sizes)
        sizes = np.asarray(sizes).astype(np.int64)
        footprint = np.asarray(footprint).astype(np.int64)
        return PartitionMetrics(self.k, sizes, footprint, footprint.copy(),
                                footprint.copy(), np.zeros(self.k, np.int64))

    # --------------------------------------------------------- drift repair
    def repartition(self) -> MigrationPlan:
        """Full repartition of everything fed so far, warm-started per §4.4
        (``repartition_frac`` sample seeds the sets; 0 = cold), matched back
        onto the live labels by the packed intersection matrix so serving
        machines keep their closest part.  Updates the live state in place
        and returns the metered ``MigrationPlan``."""
        import jax.numpy as jnp

        base = self.config.base
        g = self.arena.graph()
        old_parts = self.parts.copy()   # the buffer is overwritten below
        old_masks = self.arena.masks_np(logical=False)
        init_sets = None
        if self.config.repartition_frac > 0:
            dense = global_initialization(
                g, self.k, sample_frac=self.config.repartition_frac,
                theta=base.theta, select=base.select, seed=base.seed)
            packed = coerce_packed_sets(dense, g.num_v)
            init_sets = np.pad(
                packed, [(0, 0), (0, self.arena.W_cap - packed.shape[1])])
            self._need_exact = False
        g_cap = BipartiteGraph(g.num_u, self.arena.capacity_v,
                               g.u_indptr, g.u_indices)
        if self.config.workers > 1:
            new_parts, new_masks, scan_traffic = \
                parallel_blocked_partition_u_impl(
                    g_cap, self.k, workers=self.config.workers,
                    block=base.block_size, merge_every=base.merge_every,
                    init_sets=init_sets, use_kernel=base.use_kernel,
                    interpret=base.interpret, seed=base.seed, cap=base.cap,
                    sketch=self.sketch is not None)
            # the repair's own Alg 4 push/pull rides on the session total,
            # same units as the per-feed counters
            self._accumulate(TrafficCounters(**scan_traffic))
        else:
            new_parts, new_masks = blocked_partition_u_impl(
                g_cap, self.k, block=base.block_size, init_sets=init_sets,
                use_kernel=base.use_kernel, interpret=base.interpret,
                seed=base.seed, cap=base.cap,
                sketch=self.sketch is not None)
        plan = plan_migration(new_parts, new_masks, old_parts, old_masks,
                              degrees=g.degree_u())
        self._parts_buf[: plan.parts_u.shape[0]] = plan.parts_u
        self.arena.s_masks = jnp.asarray(plan.s_masks)
        self.arena.sizes = jnp.asarray(
            np.bincount(plan.parts_u, minlength=self.k).astype(np.int32))
        self._accumulate(plan.traffic)
        self.repartitions += 1
        self.tracker.reset()
        return plan

    # ----------------------------------------------------------- elasticity
    def apply_partition_state(self, parts_u: np.ndarray, s_masks,
                              sizes: np.ndarray | None = None,
                              k: int | None = None) -> None:
        """Commit an externally computed partition state, possibly with a
        different machine count ``k`` — the mid-run hook the elastic layer
        (``repro.elastic``) uses for grow/shrink/repair.

        ``s_masks`` must already be capacity-stable — shaped
        ``(k, arena.W_cap)`` with the padding-bit invariant intact (bits at
        columns ≥ ``num_v`` zero) — so subsequent feeds hit the same jit
        cache entry per k.  ``sizes`` defaults to the bincount of
        ``parts_u``.  The drift tracker resets: its baseline compares
        metrics at a fixed k, which just changed (or the partition was
        rebuilt in place).
        """
        import jax.numpy as jnp

        parts_u = np.asarray(parts_u, np.int32)
        if parts_u.shape[0] != self.arena.num_u:
            raise ValueError(
                f"parts_u covers {parts_u.shape[0]} U rows, arena holds "
                f"{self.arena.num_u}")
        new_k = self.k if k is None else int(k)
        masks_np = np.asarray(s_masks)
        if masks_np.shape != (new_k, self.arena.W_cap):
            raise ValueError(
                f"s_masks must be capacity-stable ({new_k}, "
                f"{self.arena.W_cap}), got {masks_np.shape}")
        if sizes is None:
            sizes = np.bincount(parts_u, minlength=new_k).astype(np.int32)
        self.k = new_k
        self.arena.set_partition_state(jnp.asarray(masks_np),
                                       jnp.asarray(np.asarray(sizes,
                                                              np.int32)),
                                       new_k)
        self._parts_buf[: parts_u.shape[0]] = parts_u
        self.tracker.reset()

    # ------------------------------------------------------------ snapshot
    def save(self, path) -> None:
        """Snapshot the FULL stream state — arena (graph + live sets),
        per-vertex parts, feed counters, and the RNG state — so ``load``
        resumes the stream exactly where it stopped (the next feed of the
        same chunk sequence is bit-identical).  The drift tracker's sliding
        window is not persisted: after a restore the baseline restarts,
        which can only delay (never corrupt) the next repair."""
        import json

        np.savez_compressed(
            path, **self.arena.state_arrays(),
            parts=self.parts,
            true_num_v=self._true_num_v,
            n_feeds=self.n_feeds, repartitions=self.repartitions,
            need_exact=self._need_exact,
            traffic=np.asarray([self._pushed, self._pulled, self._tasks,
                                self._stale, self._migrated], np.int64),
            rng_state=np.frombuffer(
                json.dumps(self._rng.bit_generator.state).encode(),
                dtype=np.uint8))

    @classmethod
    def load(cls, path, config: ParsaStreamConfig) -> "StreamSession":
        """Restore a stream saved by ``save``.  ``config.base.k`` must
        match the snapshot's k (the packed sets are k-shaped)."""
        import json

        z = np.load(path)
        if int(z["k"]) != config.base.k:
            raise ValueError(
                f"snapshot has k={int(z['k'])} but config.base.k="
                f"{config.base.k}")
        # sketched sessions store the arena at the sketched width; the
        # session is rebuilt from the TRUE extent so __init__ re-derives
        # the identical spec (identity prefix + seeded hash — no data
        # dependence), then the saved arena replaces the fresh one.
        true_v = int(z["true_num_v"]) if "true_num_v" in z else int(z["num_v"])
        session = cls(config, num_v=true_v)
        session._true_num_v = true_v
        session.arena = StreamArena.from_state(z)
        parts = np.asarray(z["parts"], np.int32)
        session._store_parts(0, parts)
        session.n_feeds = int(z["n_feeds"])
        session.repartitions = int(z["repartitions"])
        session._need_exact = bool(z["need_exact"])
        # pre-migration_bytes snapshots carry 4 counters, current ones 5
        t = [int(x) for x in z["traffic"]] + [0]
        (session._pushed, session._pulled, session._tasks, session._stale,
         session._migrated) = t[:5]
        session._rng.bit_generator.state = json.loads(
            bytes(z["rng_state"]).decode())
        return session

    # ------------------------------------------------------------- results
    def result(self, refine_v: bool | None = None) -> PartitionResult:
        """Assemble the current stream state into a full
        ``PartitionResult`` (device-resident Alg 2 + exact metrics), the
        same record the one-shot facade returns."""
        import jax.numpy as jnp

        from ..core.jax_refine import evaluate_device, refine_v_device

        base = self.config.base
        g = self.arena.graph()
        timings: dict[str, float] = {}
        t_total = time.perf_counter()
        s_logical = self.arena.masks_np()
        need_words = jnp.asarray(s_logical) if self._need_exact else None
        refine = base.refine_v if refine_v is None else refine_v
        parts_v = parts_v_dev = None
        if refine:
            t0 = time.perf_counter()
            parts_v_dev, need_words = refine_v_device(
                g, jnp.asarray(self.parts), self.k, sweeps=base.sweeps,
                chunk=base.refine_chunk, use_kernel=base.use_kernel,
                interpret=base.interpret, need_words=need_words)
            parts_v = np.asarray(parts_v_dev)
            timings["partition_v"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        metrics = evaluate_device(g, self.parts, parts_v_dev, self.k,
                                  need_words=need_words)
        timings["metrics"] = time.perf_counter() - t0
        if self.sketch is not None and parts_v is not None:
            # sketch-space V assignment → the true parameter extent (every
            # real column served by the machine of its sketch slot)
            parts_v = self.sketch.expand_parts_v(parts_v, self._true_num_v)
        timings["total"] = time.perf_counter() - t_total
        return PartitionResult(
            parts_u=self.parts.copy(), parts_v=parts_v, num_v=g.num_v,
            k=self.k, config=base, metrics=metrics, timings=timings,
            traffic=(self.traffic
                     if self._tasks or self._pushed or self._migrated
                     else None),
            sketch=self.sketch,
            _packed_sets=s_logical)


_POPCOUNT_FN = None


def _popcount_rows(s_masks, sizes):
    """One fused launch: (sizes, per-row popcount of the packed sets)."""
    global _POPCOUNT_FN
    if _POPCOUNT_FN is None:
        import jax
        import jax.numpy as jnp

        def body(m, s):
            return s, jax.lax.population_count(m).astype(jnp.int32).sum(
                axis=1)

        _POPCOUNT_FN = jax.jit(body)
    return _POPCOUNT_FN(s_masks, sizes)


def stream_partition(
    chunks: Iterable[BipartiteGraph],
    config: ParsaStreamConfig,
    num_v: int | None = None,
) -> tuple[PartitionResult, list[StreamUpdate]]:
    """Facade convenience: feed every chunk through one ``StreamSession``
    and return ``(final PartitionResult, per-chunk StreamUpdate deltas)``.
    ``num_v`` defaults to the first chunk's parameter extent (the arena
    grows if later chunks exceed it)."""
    it = iter(chunks)
    try:
        first = next(it)
    except StopIteration:
        raise ValueError("stream_partition needs at least one chunk") \
            from None
    session = StreamSession(config,
                            num_v=num_v if num_v is not None else first.num_v)
    updates = [session.feed(first)]
    updates.extend(session.feed(c) for c in it)
    return session.result(), updates
