"""Quickstart: the whole Parsa pipeline is ONE call now.

``repro.api.partition(graph, ParsaConfig(...))`` partitions U (Algorithm
3/4), refines V (Algorithm 2), and measures all three paper objectives —
returning a single ``PartitionResult``.  Swap the ``backend`` field to move
the same workload between the sequential reference (``host``), the
device-resident blocked scan (``device_scan``), the simulated
parameter-server run (``parallel_sim``), and the real shard_map multi-
worker partitioner (``parallel_device``); nothing else changes.

    PYTHONPATH=src python examples/quickstart.py
    # multi-worker parallel_device on a CPU host:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/quickstart.py
"""
import dataclasses
import pathlib

import jax
import numpy as np

from repro.api import ParsaConfig, partition
from repro.core import evaluate, improvement, random_parts
from repro.graphs import text_like
from repro.runtime import enable_compile_cache

enable_compile_cache()
k = 16
print("building a documents × vocabulary bipartite graph ...")
g = text_like(num_docs=2000, vocab=6000, mean_len=50, seed=0)
print(f"  |U|={g.num_u} docs  |V|={g.num_v} vocab  |E|={g.num_edges} edges")

cfg = ParsaConfig(k=k, backend="host", blocks=8, init_iters=8, seed=0)
print(f"running Parsa via repro.api.partition ({cfg.backend} backend, "
      f"b={cfg.blocks} subgraphs, a={cfg.init_iters} init iterations, k={k}) ...")
res = partition(g, cfg)   # one call: partition U, refine V, measure

m = res.metrics
mr = evaluate(g, random_parts(g.num_u, k, 0), random_parts(g.num_v, k, 1), k)

print("\nobjective             parsa      random   improvement")
for name, a, b in [
    ("(4) max |U_i|      ", m.size_max, mr.size_max),
    ("(6) max |N(U_i)|   ", m.mem_max, mr.mem_max),
    ("(7) max traffic    ", m.traffic_max, mr.traffic_max),
    ("    total traffic  ", m.traffic_sum, mr.traffic_sum),
]:
    print(f"{name}  {a:8d}  {b:8d}   {improvement(b, a):6.0f}%")
print("\n(improvement = (random − parsa)/parsa × 100%, as in the paper §5.1;")
print(" the paper's CTR runs cut inter-machine traffic by >90%)")

print("\nphase timings:",
      {name: f"{dt * 1e3:.1f}ms" for name, dt in res.timings.items()})

# the fully device-resident pipeline: partition U on device (one scan
# dispatch), refine V on device (Algorithm 2 over packed words), measure on
# device (popcount reductions) — no host round trip between phases, and
# per-phase wall clocks in res.timings ("pack" is the host-side bitmask
# packing, split out so "partition_u" is the scan alone).  A single cold
# call includes jit compilation; steady-state numbers live in
# benchmarks/bench_fig10_scalability.run_acceptance() → BENCH_pipeline.json.
cfg_dev = ParsaConfig(k=k, backend="device_scan", refine_backend="device",
                      seed=0)
res_dev = partition(g, cfg_dev)
assert res_dev.metrics.as_dict() == partition(
    g, cfg_dev.replace(refine_backend="host")).metrics.as_dict()
print("\ndevice-resident pipeline (device_scan + device refine/metrics, "
      "bit-identical):")
print("  phase timings:",
      {name: f"{dt * 1e3:.1f}ms" for name, dt in res_dev.timings.items()})

# warm-start / incremental repartitioning: tomorrow's graph reuses today's
# neighbor sets with one method call (§4.4 incremental mode).
g2 = text_like(num_docs=2000, vocab=6000, mean_len=50, seed=1)
res2 = res.refine(g2)
print(f"\nincremental repartition of a fresh graph via res.refine(): "
      f"max traffic {res2.metrics.traffic_max} "
      f"(cold: {partition(g2, cfg).metrics.traffic_max})")

# the distributed partitioner (Algorithm 4 on shard_map): W workers run the
# blocked bitmask scan concurrently, one per device, OR-merging their
# neighbor sets every `merge_every` blocks.  One worker per visible device.
W = min(8, len(jax.devices()))
cfg_par = ParsaConfig(k=k, backend="parallel_device", workers=W,
                      merge_every=2, seed=0)
res_par = partition(g, cfg_par)
t = res_par.traffic
print(f"\nparallel_device backend ({W} worker{'s' if W > 1 else ''}): "
      f"max traffic {res_par.metrics.traffic_max}, "
      f"partition_u {res_par.timings['partition_u'] * 1e3:.0f}ms, "
      f"PS traffic pushed/pulled {t.pushed_bytes}/{t.pulled_bytes} bytes")
if W == 1:
    print("  (single device — set "
          "XLA_FLAGS=--xla_force_host_platform_device_count=8 for a real "
          "multi-worker run)")

# --------------------------------------------------------------------------
# sketched server sets: partition at a width the exact path cannot allocate
# (repro.sketch).  Every packed structure — server sets, need words, the
# parallel workers' stale copies — is O(k·|V|/32); at the paper's CTR scale
# (|V| ~ 10^8) that is tens of GB of live set structures plus a transpose
# side channel in the V-refine measured in terabytes.  set_repr="sketch"
# maps the 10^8 columns into hot exact slots (top features by footprint)
# plus hashed buckets for the cold tail; the SAME packed-uint32 pipeline
# then runs at the sketched width, and parts_v is expanded back to all
# 10^8 features at the end.
from repro.sketch import set_structure_bytes

NUM_V_HUGE = 100_000_000
print(f"\nsketched sets: {NUM_V_HUGE:,} features (the paper's CTR scale)")
rng_s = np.random.default_rng(0)
rows_s, hot_s, tail_s = 20_000, 100_000, NUM_V_HUGE
cols = np.where(rng_s.random((rows_s, 12)) < 0.7,
                rng_s.zipf(1.3, (rows_s, 12)) % hot_s,     # hot Zipf head
                rng_s.integers(0, tail_s, (rows_s, 12)))   # long cold tail
from repro.core.bipartite import from_edges
g_huge = from_edges(rows_s, NUM_V_HUGE,
                    np.repeat(np.arange(rows_s), 12), cols.reshape(-1))
cfg_sk = ParsaConfig(k=k, backend="device_scan", set_repr="sketch",
                     sketch_hot_bits=16_384, sketch_bucket_bits=16_384,
                     refine_backend="device", seed=0)
exact_b = set_structure_bytes(NUM_V_HUGE, k, cfg_sk.block_size)
res_sk = partition(g_huge, cfg_sk)
sk = res_sk.sketch
print(f"  exact-mode set structures would need {exact_b / 2**30:.1f} GiB "
      f"(plus a ~TB-scale refine transpose) — never allocated")
print(f"  sketch width {sk.width_bits:,} bits -> "
      f"{sk.mem_bytes(k, cfg_sk.block_size) / 2**20:.1f} MiB "
      f"({exact_b / sk.mem_bytes(k, cfg_sk.block_size):.0f}x smaller), "
      f"traffic_max {res_sk.metrics.traffic_max}")
print(f"  parts_v covers all {res_sk.parts_v.size:,} true features "
      f"(hot exact, cold tail co-located by hash); "
      f"total {res_sk.timings['total']:.1f}s on this host")
print("(hot prefix >= |V| is bit-identical to the exact pipeline — "
      "regression-tested; acceptance gates: benchmarks/bench_sketch.py "
      "--acceptance)")

# --------------------------------------------------------------------------
# streaming: partition a graph that GROWS over time (repro.stream).
# Examples arrive continuously in production (ad impressions, social
# edges); a StreamSession keeps the packed server sets live on device and
# assigns each arriving chunk with ONE scan dispatch against them —
# O(chunk) work instead of repartitioning everything from scratch.  A
# sliding-window drift tracker watches the popcount objectives and, when
# the arriving distribution has drifted enough to decay the partition,
# triggers a full repartition that is matched back onto the old labels
# (minimal migration, metered in bytes).
from repro.api import ParsaStreamConfig, StreamSession
from repro.graphs import ctr_like_stream

print("\nstreaming: 6 chunks of drifting CTR-like traffic "
      "(campaign churn) ...")
chunks = ctr_like_stream(3000, 6000, chunks=6, nnz_per_row=20, churn=0.5,
                         seed=0)
scfg = ParsaStreamConfig(
    base=ParsaConfig(k=k, backend="device_scan", refine_v=False, seed=0),
    drift_threshold=1.02)     # repartition on >2% imbalance degradation
session = StreamSession(scfg, num_v=6000)
for chunk in chunks:
    upd = session.feed(chunk)   # ONE jitted scan against the live sets
    note = ""
    if upd.repartitioned:
        note = (f"  <- drift repair: {upd.migration.moved_u} examples "
                f"migrated, {upd.migration.traffic.pushed_bytes} bytes")
    print(f"  chunk {upd.chunk}: +{upd.u_stop - upd.u_start} examples, "
          f"traffic_max {upd.metrics.traffic_max}, "
          f"feed {upd.timings['total'] * 1e3:.0f}ms{note}")
res_stream = session.result(refine_v=True)   # a full PartitionResult
print("final streamed partition:", res_stream.metrics.as_dict())
print("(one-chunk feeds are bit-identical to the device_scan backend; "
      "see benchmarks/bench_stream.py)")

# --------------------------------------------------------------------------
# elastic serving: the machine count k is a RUNTIME VARIABLE (repro.elastic).
# Fleets are not static — capacity arrives mid-stream, machines die, some
# straggle.  An ElasticSession wraps the stream and composes the pieces:
# grow_k splits the largest part with one jitted scan, repair survives a
# machine loss by warm-starting §4.4 from the SURVIVING packed sets (the
# lost part's vertices re-assigned in one dispatch — no cold repartition),
# and a seeded ChaosSchedule replays the same disaster deterministically.
# Every move is metered in TrafficCounters.migration_bytes and gated by an
# ElasticPolicy that weighs the one-time cost against steady-state savings.
from repro.api import (ChaosEvent, ChaosSchedule, ElasticConfig,
                       ElasticSession)

print("\nelastic: grow the fleet 8->12 mid-stream, then lose a machine ...")
chunks = ctr_like_stream(3000, 6000, chunks=6, nnz_per_row=20, churn=0.5,
                         seed=0)
ecfg = ElasticConfig(stream=ParsaStreamConfig(
    base=ParsaConfig(k=8, backend="device_scan", refine_v=False, seed=0),
    repartition="never"))
chaos = ChaosSchedule([
    ChaosEvent(feed=1, kind="add"),        # four machines join ...
    ChaosEvent(feed=2, kind="add"),
    ChaosEvent(feed=3, kind="add"),
    ChaosEvent(feed=4, kind="add"),
    ChaosEvent(feed=5, kind="kill"),       # ... then one dies (seeded pick)
], seed=0)
es = ElasticSession(ecfg, num_v=6000, chaos=chaos)
for chunk in chunks:
    upd = es.feed(chunk)                   # chaos events apply, then feed
    print(f"  chunk {upd.chunk}: k={es.k}, "
          f"traffic_max {upd.metrics.traffic_max}, migration so far "
          f"{es.traffic.migration_bytes} bytes")
for op in es.ops:
    what = f"{op.kind}{' (' + op.mode + ')' if op.mode else ''}"
    print(f"  {what}: k {op.k_before}->{op.k_after}, moved {op.moved_u} "
          f"examples, {op.traffic.migration_bytes} migration bytes in "
          f"{op.seconds * 1e3:.0f}ms")
print("(warm repair re-assigns only the lost part's vertices — one scan "
      "dispatch, ~10x faster than a cold repartition of the whole stream; "
      "see benchmarks/bench_chaos.py --acceptance)")

# --------------------------------------------------------------------------
# serving: turn the traffic cut into a measured end-to-end speedup
# (repro.serving).  A ServingEngine drives k PSCluster shards through
# batched pull -> compute -> push requests for a Zipf-skewed tenant mix;
# async mode double-buffers the next request's pull behind the current
# compute (τ=1 bounded staleness), and every modeled byte becomes real
# wall-clock through the bandwidth model — so tokens/s and p99 below are
# measured, not derived from byte counts.
from repro.api import (PSRequestSource, RequestMix, ServingConfig,
                       ServingEngine, ZipfWorkload)
from repro.core import random_parts
from repro.graphs import ctr_like
from repro.ml import DBPGConfig, PSCluster

print("\nserving: random vs Parsa placement under a Zipf request mix ...")
g_srv = ctr_like(num_impressions=3000, num_features=5000, nnz_per_row=20,
                 clusters=24, locality=0.85, seed=0)
res_srv = partition(g_srv, ParsaConfig(k=8, backend="device_scan",
                                       refine_backend="device", seed=0))
labels = np.where(np.random.default_rng(0).random(g_srv.num_u) < 0.5,
                  1.0, -1.0).astype(np.float32)
mix = RequestMix((ZipfWorkload("text", batch=96, zipf_s=1.1),
                  ZipfWorkload("ctr", batch=48, zipf_s=1.3,
                               hot_offset=777, weight=0.5)))
dcfg = DBPGConfig(lam=0.05, lr=0.1, kkt_eps=0.0, compress=False,
                  error_feedback=False)
for name, (pu, pv) in [
    ("random", (random_parts(g_srv.num_u, 8, 0),
                random_parts(g_srv.num_v, 8, 1))),
    ("parsa", (np.asarray(res_srv.parts_u), np.asarray(res_srv.parts_v))),
]:
    cluster = PSCluster(g_srv, labels, pu, pv, 8, dcfg, bandwidth=2.5e5)
    cluster.commit_weights(np.random.default_rng(1).normal(
        0, 0.1, g_srv.num_v).astype(np.float32))   # serve a trained model
    engine = ServingEngine(PSRequestSource(
        cluster, mix, ServingConfig(prefetch=True, warmup=16, seed=0)))
    s = engine.run(46)
    print(f"  {name:6s} async: {s['tokens_s']:8.0f} tokens/s  "
          f"{s['examples_s']:7.0f} examples/s  p99 {s['p99_ms']:.1f}ms  "
          f"(pull inter {s['pull_inter_bytes']} B, "
          f"{s['hidden_s'] * 1e3:.0f}ms of wire hidden behind compute)")
print("(full {random,parsa} x {sync,async} grid with acceptance gates: "
      "benchmarks/bench_system.py --acceptance -> BENCH_system.json)")

# --------------------------------------------------------------------------
# closed loop: hold a p99 SLO through chaos (repro.elastic.SLOAutoscaler).
# The serving source keeps a deterministic virtual clock (requests arrive
# every service_model_s; every pull/push books a virtual per-machine NIC),
# a TelemetryBus windows the modeled latencies, and every decide_every
# slots the autoscaler reads a snapshot: grow on sustained p99-over-SLO
# (splitting the hottest part by live footprint), shrink when cold, warm
# repair immediately on circuit-open, straggler-bias the router on EWMA
# drift.  Under overload the engine degrades gracefully instead of falling
# over: per-home admission control sheds lowest-weight tenants first.
from repro.api import Observability, SLOAutoscaler, SLOConfig, prometheus_text
from repro.runtime import RetryPolicy

print("\nclosed loop: a load burst + a machine kill, static k=8 vs "
      "autoscaled ...")
SLO_MS = 30.0
chaos_events = [
    ChaosEvent(feed=32, kind="burst", factor=2.5),    # traffic 2.5x
    ChaosEvent(feed=160, kind="burst", factor=1.0),   # ... and back
    ChaosEvent(feed=200, kind="kill", machine=3),     # then a shard dies
]
slo_cfg = SLOConfig(slo_ms=SLO_MS, window_requests=16, decide_every=16,
                    warmup_windows=2, patience=1, cooldown_windows=0,
                    shrink_patience=3, shrink_p99_frac=0.5,
                    shrink_occupancy_s=0.015, min_k=8, max_k=14,
                    drift_ratio=2.0, tau_escalation=4)
serve_kw = dict(prefetch=True, warmup=16, seed=0, bandwidth=6e4,
                service_model_s=2e-3, window_requests=16,
                retry=RetryPolicy(timeout_s=0.004, retries=0))
for name, autoscale in [("static k=8", False), ("autoscaled", True)]:
    cluster = PSCluster(g_srv, labels, np.asarray(res_srv.parts_u),
                        np.asarray(res_srv.parts_v), 8, dcfg,
                        bandwidth=serve_kw["bandwidth"])
    cluster.commit_weights(np.random.default_rng(1).normal(
        0, 0.1, g_srv.num_v).astype(np.float32))
    obs = Observability() if autoscale else None   # traced pass, see below
    asc = SLOAutoscaler(dataclasses.replace(slo_cfg, obs=obs))
    elastic = None
    if autoscale:
        elastic = ElasticSession(ElasticConfig(
            stream=ParsaStreamConfig(base=ParsaConfig(
                k=8, backend="device_scan", refine_v=False, seed=0),
                repartition="never"),
            min_k=slo_cfg.min_k, max_k=slo_cfg.max_k),
            num_v=g_srv.num_v, policy=asc)
        elastic.feed(g_srv)
        cluster.apply_placement(elastic.parts.copy(),
                                np.asarray(res_srv.parts_v))
    src = PSRequestSource(
        cluster, mix,
        ServingConfig(max_backlog_s=0.025 if autoscale else None,
                      tau_escalation=slo_cfg.tau_escalation, obs=obs,
                      **serve_kw),
        chaos=ChaosSchedule(list(chaos_events), seed=0),
        elastic=elastic, autoscaler=asc)
    engine = ServingEngine(src)
    s = engine.run(256)
    windows = asc.decisions[slo_cfg.warmup_windows:]
    hold = sum(snap.p99_ms <= SLO_MS for snap, _ in windows) / len(windows)
    peak = max(snap.p99_ms for snap, _ in windows)
    ops = ([f"{op.kind} k{op.k_before}->{op.k_after}"
            for op in elastic.ops if op.committed] if elastic else [])
    print(f"  {name:11s}: held p99<={SLO_MS:.0f}ms in {hold:5.1%} of "
          f"windows, peak window p99 {peak:6.1f}ms, shed "
          f"{s['shed_requests']:2d}" + (f"  ops: {', '.join(ops)}"
                                        if ops else ""))
print("(every decision is recorded with its telemetry snapshot and the "
      "seeded chaos replay is bit-deterministic; acceptance gates: "
      "benchmarks/bench_slo.py --acceptance -> BENCH_system.json slo_rows)")

# --------------------------------------------------------------------------
# observability: the autoscaled run above was fully traced (repro.obs).
# One Observability handle threads through every layer as the single obs=
# hook (ServingConfig.obs / SLOConfig.obs / StreamSession / ElasticSession):
# the tracer emits nested virtual-clock spans (request -> pull/wire/retry/
# queue -> compute -> push, elastic ops -> plan/scan/migrate, feeds ->
# pack/scan/merge) on the same deterministic clock the engine models, and
# the flight recorder correlates chaos events, window verdicts, breaker
# trips and elastic ops on one slot timeline — so recorder.explain(window)
# answers "WHY did this window violate the SLO" from the recording alone.
# Off by default: with obs=None every hook is a single attribute check.
out_dir = pathlib.Path(__file__).resolve().parent / "out"
paths = obs.save(out_dir, prefix="quickstart")
print(f"\nobservability: {len(obs.tracer.spans)} virtual-clock spans, "
      f"{len(obs.recorder)} recorded facts from the autoscaled run")
print(f"  Perfetto trace -> {paths['trace']}  (open in ui.perfetto.dev)")
print(f"  flight recorder -> {paths['events']}")

violated = [i for i, (snap, _) in enumerate(asc.decisions)
            if i >= slo_cfg.warmup_windows and snap.p99_ms > SLO_MS]
print(f"  {len(violated)} post-warmup windows violated the SLO; "
      f"asking the flight recorder why:")
for i in violated[:2]:
    print("    " + str(obs.explain(i)).replace("\n", "\n    "))

metrics = prometheus_text(latency=engine.recorder, telemetry=src.telemetry,
                          traffic=elastic.traffic, meter=cluster.meter)
lines = metrics.splitlines()
n_fams = sum(ln.startswith("# TYPE") for ln in lines)
n_samples = sum(bool(ln) and not ln.startswith("#") for ln in lines)
print(f"  prometheus snapshot: {n_samples} samples across {n_fams} "
      f"metric families, e.g.")
for ln in lines:
    if ln.startswith("parsa_telemetry_p99_ms"):
        print(f"    {ln}")
print("(the seeded replay exports byte-identical traces and event streams "
      "— gated in tests/test_obs.py and benchmarks/bench_slo.py)")
