"""Smoke run of Parsa's main path on a TPU: partition a sparse learning
problem, hold the device path to its references, then serve DBPG requests
on the placement it found.

    python chip_smoke.py [--seed 0]        # one chip, phases (a)-(e)
    python chip_smoke.py --chips 4         # Alg 4 across four chips only

The deployment is the paper's §5.5 application in the shape of the Criteo
Display Advertising Challenge: sparse logistic regression over k = 16
machines, 2^20 impressions × 2^22 hashed features, 39 fields (13 integer,
26 categorical) per impression, generated from ``--seed``.

Phases, each a function of its size so that a CPU test can run it small:

  (a) ``device_info``      — fail at once unless JAX sees a TPU;
  (b) ``phase_partition``  — ``device_scan`` + device refine on the whole
      graph: parts balanced, ``traffic_max`` below a random placement;
  (c) ``phase_parity``     — on a 2^14-impression slice at full feature
      width, the Pallas kernel path, the host oracle and the sketched sets
      give bit-identical ``parts_u``/``parts_v``/``s_masks`` to their
      references; ``kernel_scan_hlo`` shows the kernels compiled for the
      chip (``tpu_custom_call``), not interpreted;
  (d) ``phase_serve``      — ``PSCluster.from_partition`` + ``ServingEngine``
      serve 64 requests; one more request's loss and gradient match a
      plain numpy DBPG step;
  (e) the last line of standard output: ``{"ok": true, "device": ...}``.

``--chips 4`` runs only ``phase_four_chips``: ``parallel_device`` with four
workers against ``device_scan`` on one chip.

Any failed check raises, so the script exits non-zero and prints no final
line.  The times it prints come from one smoke run: they are not a
benchmark.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

NUM_U = 1 << 20           # impressions
NUM_V = 1 << 22           # hashed features
NNZ_PER_ROW = 39          # 13 integer + 26 categorical fields
K = 16                    # machines (paper §5.5)
SLICE_U = 1 << 14         # impressions of the parity slice
REQUESTS = 64
# bench_fig10's §5.4 band for parallel vs sequential traffic.  The cost of
# stale merges shrinks as the graph grows; the band holds at this size.
FOUR_CHIP_BAND = 0.05

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


def require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"smoke check failed: {what}")


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling — a persistent
    cache hit counts only its retrieval."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += duration


# ------------------------------------------------------------------ (a)
def device_info(want_count: int) -> dict:
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
    print("device", json.dumps(info), flush=True)
    if info["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX runs on {info['platform']}")
    if info["count"] < want_count:
        raise SystemExit(f"need {want_count} chips, JAX sees {info['count']}")
    return info


def ctr_graph(num_u: int, num_v: int, seed: int):
    from repro.graphs import ctr_like

    return ctr_like(num_u, num_v, nnz_per_row=NNZ_PER_ROW, seed=seed)


def base_config(k: int, seed: int):
    from repro.api import ParsaConfig

    return ParsaConfig(k=k, backend="device_scan", refine_backend="device",
                       seed=seed)


# ------------------------------------------------------------------ (b)
def phase_partition(graph, k: int, seed: int):
    """Partition the whole graph on the device; returns the result."""
    from repro.api import partition
    from repro.core import random_parts
    from repro.core.jax_refine import evaluate_device

    res = partition(graph, base_config(k, seed))
    sizes = np.bincount(res.parts_u, minlength=k)
    require(sizes.max() - sizes.min() <= 1, f"unbalanced parts {sizes}")
    rand = evaluate_device(graph, random_parts(graph.num_u, k, seed),
                           random_parts(graph.num_v, k, seed + 1), k)
    require(res.metrics.traffic_max < rand.traffic_max,
            f"traffic_max {res.metrics.traffic_max} not below random "
            f"{rand.traffic_max}")
    print(f"(b) partition {graph.num_u}x{graph.num_v} k={k}: sizes "
          f"{sizes.min()}..{sizes.max()}, traffic_max "
          f"{res.metrics.traffic_max} vs random {rand.traffic_max}, "
          f"phase seconds {_rounded(res.timings)}", flush=True)
    return res


# ------------------------------------------------------------------ (c)
def phase_parity(graph, k: int, seed: int) -> None:
    """Each pair must agree bit for bit on parts_u, parts_v and s_masks."""
    from repro.api import partition

    base = base_config(k, seed)
    sketch = base.replace(set_repr="sketch")
    pairs = [
        ("kernel vs jnp", base.replace(use_kernel=True), base),
        ("device_scan vs host_blocked_oracle", base,
         base.replace(backend="host_blocked_oracle")),
        ("sketch kernel vs jnp", sketch.replace(use_kernel=True), sketch),
    ]
    runs: dict = {}

    def run(cfg):
        if cfg not in runs:
            runs[cfg] = partition(graph, cfg)
        return runs[cfg]

    for name, cfg_a, cfg_b in pairs:
        a, b = run(cfg_a), run(cfg_b)
        for field in ("parts_u", "parts_v", "s_masks"):
            require(np.array_equal(getattr(a, field), getattr(b, field)),
                    f"{name}: {field} differ")
        print(f"(c) {name}: parts_u, parts_v, s_masks bit-identical "
              f"(traffic_max {a.metrics.traffic_max})", flush=True)


def kernel_scan_hlo(num_u: int, num_v: int, k: int,
                    sketch: bool = False) -> str:
    """StableHLO of the kernel-path partition scan over ``num_u`` x
    ``num_v``, lowered as ``device_scan`` with ``use_kernel=True`` lowers
    it on this backend (exact or sketched packed width)."""
    import jax
    import jax.numpy as jnp

    from repro.core.jax_partition import _partition_scan
    from repro.sketch import SketchSpec

    cfg = base_config(k, 0)
    words = (num_v + 31) // 32
    if sketch:
        words = SketchSpec.for_graph(num_v, cfg.sketch_hot_bits,
                                     cfg.sketch_bucket_bits).width_words
    nb, b, cap = -(-num_u // cfg.block_size), cfg.block_size, cfg.cap

    def shape(*dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype)

    return _partition_scan.lower(
        shape(nb, b, dtype=jnp.bool_), shape(nb, b, cap), shape(nb, b, cap),
        shape(nb, b, dtype=jnp.bool_), shape(nb, 2), shape(3, 1),
        shape(k, words), shape(k), k=k, use_kernel=True,
        interpret=cfg.interpret, sketch=sketch).as_text()


# ------------------------------------------------------------------ (d)
def dbpg_step_numpy(graph, rows, labels, w, need, lr: float, lam: float):
    """One DBPG step of ℓ1 logistic regression, row by row in float64:
    (loss, smooth gradient, proximal update on the working set)."""
    w = np.asarray(w, np.float64)
    g = np.zeros(graph.num_v, np.float64)
    loss = 0.0
    for r in rows:
        cols = graph.u_indices[graph.u_indptr[r]:graph.u_indptr[r + 1]]
        y = float(labels[r])
        m = y * w[cols].sum()
        loss += np.logaddexp(0.0, -m)
        g[cols] += -y / (1.0 + np.exp(m))
    step = w - lr * g
    prox = np.sign(step) * np.maximum(np.abs(step) - lr * lam, 0.0)
    return loss, g, np.where(need, prox, w)


def phase_serve(graph, result, labels, requests: int, seed: int) -> dict:
    """Serve ``requests`` DBPG requests on the partition's placement, then
    check one more against ``dbpg_step_numpy``; returns the summary."""
    import jax

    from repro.api import (PSRequestSource, RequestMix, ServingConfig,
                           ServingEngine, ZipfWorkload)
    from repro.ml import DBPGConfig, PSCluster

    dbpg = DBPGConfig()
    cluster = PSCluster.from_partition(graph, labels, result, dbpg,
                                       seed=seed)
    source = PSRequestSource(cluster, RequestMix((ZipfWorkload("ctr"),)),
                             ServingConfig(prefetch=True, seed=seed))
    engine = ServingEngine(source)
    summary = engine.run(requests)
    losses = np.array([r.loss for r in engine.recorder.records])
    require(losses.size == requests,
            f"{losses.size} of {requests} requests completed")
    require(bool(np.isfinite(losses).all()), "a served loss is not finite")

    t = requests
    source.on_step(t)
    req = source.next_request(t)
    w = source.issue(req, t).block()
    new_w, g, loss = jax.device_get(source.compute(req, w))
    ref_loss, ref_g, ref_w = dbpg_step_numpy(
        graph, req.rows, labels, jax.device_get(w), req.need, dbpg.lr,
        dbpg.lam)
    # float32 sums of a few hundred O(1) terms against float64
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-4)
    np.testing.assert_allclose(g, ref_g, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(new_w, ref_w, rtol=1e-4, atol=1e-6)
    print(f"(d) served {requests} requests (prefetch on), losses finite; "
          f"request {t}: loss {float(loss):.6g} vs numpy {ref_loss:.6g}, "
          f"gradient within float32 tolerance", flush=True)
    print(f"(d) smoke run on the device, not a benchmark: "
          f"{summary['requests'] / summary['wall_s']:.1f} requests/s, "
          f"p50 {summary['p50_ms']:.2f} ms, p99 {summary['p99_ms']:.2f} ms "
          f"over {summary['requests']} requests after warm-up", flush=True)
    return summary


# ------------------------------------------------------------- 4 chips
def phase_four_chips(graph, k: int, seed: int, workers: int = 4) -> None:
    """Alg 4 (``parallel_device``) over ``workers`` chips against
    ``device_scan`` on one of them."""
    from repro.api import partition
    from repro.core.jax_partition import dispatch_counter

    cfg = base_config(k, seed)
    one = partition(graph, cfg)
    with dispatch_counter() as log:
        par = partition(graph, cfg.replace(backend="parallel_device",
                                           workers=workers))
    scan = [r for r in log.records if r.phase == "parallel_partition_scan"]
    require(len(scan) == 1, f"{len(scan)} parallel scan dispatches")
    mesh, shards = scan[0].meta["devices"], scan[0].meta["shard_devices"]
    require(len(set(mesh)) == workers and len(set(shards)) == workers,
            f"mesh devices {mesh}, output shards on {shards}")
    bound = (1 + FOUR_CHIP_BAND) * one.metrics.traffic_max
    require(par.metrics.traffic_max <= bound,
            f"parallel traffic_max {par.metrics.traffic_max} above "
            f"{bound:.0f}")
    sizes = np.bincount(par.parts_u, minlength=k)
    print(f"(4 chips) parallel_device workers={workers}: mesh {mesh}, "
          f"output shards on {shards}, sizes {sizes.min()}..{sizes.max()}",
          flush=True)
    print(f"(4 chips) traffic_max {par.metrics.traffic_max} vs device_scan "
          f"{one.metrics.traffic_max} "
          f"({par.metrics.traffic_max / one.metrics.traffic_max - 1:+.2%}, "
          f"band +{FOUR_CHIP_BAND:.0%}); phase seconds "
          f"{_rounded(par.timings)} vs {_rounded(one.timings)}", flush=True)


def _rounded(timings: dict) -> dict:
    return {p: round(s, 3) for p, s in timings.items()}


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip Alg 4 phase")
    args = ap.parse_args(argv)

    info = device_info(args.chips)                                   # (a)
    from repro.runtime import enable_compile_cache

    cache_dir = enable_compile_cache()
    clock = CompileClock()
    t0 = time.perf_counter()
    graph = ctr_graph(NUM_U, NUM_V, args.seed)
    print(f"graph: {graph.num_u} impressions x {graph.num_v} features, "
          f"{graph.num_edges} nonzeros, made in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)

    if args.chips == 4:
        phase_four_chips(graph, K, args.seed)
    else:
        result = phase_partition(graph, K, args.seed)                # (b)
        part = graph.slice_u(0, SLICE_U)
        phase_parity(part, K, args.seed)                             # (c)
        for sketch in (False, True):
            require("tpu_custom_call" in kernel_scan_hlo(
                SLICE_U, NUM_V, K, sketch=sketch),
                f"kernel-path scan (sketch={sketch}) has no Mosaic kernel")
        print("(c) kernel-path scans lower to tpu_custom_call", flush=True)
        from repro.ml import make_problem

        _, labels = make_problem(graph, seed=args.seed)
        phase_serve(graph, result, labels, REQUESTS, args.seed)      # (d)

    print(f"compile seconds {clock.seconds:.1f} (persistent cache "
          f"{cache_dir}); wall seconds {time.perf_counter() - t0:.1f}",
          flush=True)
    print(json.dumps({"ok": True, "device": info}))                  # (e)


if __name__ == "__main__":
    main()
