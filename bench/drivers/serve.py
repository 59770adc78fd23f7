"""DBPG serving on the Parsa placement: one closed-loop client that
prefetches (request t+1's pull is issued before request t is served, so
a worker computes on weights at most one commit stale, τ = 1).

Set-up generates the configuration's impressions, partitions them with
``repro.api.partition`` (device scan, device refine) and builds the
``PSCluster`` and ``PSRequestSource`` on that placement.  The window
drives the source's own steps — issue, block, compute, commit — the loop
of ``ServingEngine`` with the benchmark's spans around each.

Traffic parameters: ``batch_rows``, ``zipf_s`` (rows drawn Zipf over the
home's rows; homes round-robin), ``link_bytes_per_s`` (the modeled link),
``warmup_requests``, ``check_share`` (the share of window requests, drawn
from the seed, that the check recomputes) and ``trace_seconds``.

The check, for each sampled request: the pulled buffer holds the
server's weights at issue on the whole working set (exact); the loss and
gradient match a float64 step on that buffer; the commit is read back:
the server's weights after it equal the float64 proximal update on the
working set, and off the working set the weights as they stood just
before it (exact); the push's inter-machine bytes match a recount by
owner.

The window runs the loop of ``ServingEngine`` step by step, and so
leaves out what ``ServingEngine.run`` adds around it: admission,
``after_slot``, the telemetry's ``observe_request``, the
``LatencyRecorder`` and the ``OverlapMeter``.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import gen
from bench.reference import dbpg_step, push_bytes

__all__ = ["Cell", "LIMITS"]

# Limits of the compared numbers, with the readings they were set from in
# PERF.md: float32 sums of a few hundred O(1) terms against float64 on
# one side, the same step in bfloat16 (the control) on the other.
LIMITS = {"pull_mismatch": 0, "loss_gap": 1e-4, "grad_gap": 1e-4,
          "update_gap": 1e-4, "write_off_need": 0, "push_bytes_gap": 0}


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.traffic = ctx.traffic
        self.records: list = []
        self.samples: list = []
        self.window_s = 0.0
        self.t = 0
        self.bad = 0

    def setup(self) -> None:
        from repro.api import (ParsaConfig, PSRequestSource, RequestMix,
                               ServingConfig, ZipfWorkload, partition)
        from repro.ml import DBPGConfig, PSCluster

        cfg, tr, seed = self.cfg, self.traffic, self.ctx.seed
        p = cfg["parsa"]
        self.num_v = gen.num_columns(cfg)
        t0 = time.perf_counter()
        self.csr = gen.concat(gen.chunk_pool(cfg, seed, cfg["impressions"], 1))
        self.labels = gen.labels_for(self.csr, self.num_v, seed)
        graph = gen.as_graph(self.csr, self.num_v)
        self.ctx.log(f"data: {graph.num_u} impressions x {graph.num_v} "
                     f"features, {graph.num_edges} nonzeros, made in "
                     f"{time.perf_counter() - t0:.3f} s")
        res = partition(graph, ParsaConfig(
            k=cfg["k"], backend=p["backend"], block_size=p["block_size"],
            cap=p["cap"], use_kernel=p["use_kernel"],
            refine_backend=p["refine_backend"], seed=seed))
        self.ctx.log("placement: phase seconds " + " ".join(
            f"{k}={v:.3f}" for k, v in res.timings.items())
            + f"; traffic_max {res.metrics.traffic_max}")
        d = cfg["dbpg"]
        self.dbpg = DBPGConfig(lr=d["lr"], lam=d["lam"],
                               compress=d["compress"])
        self.cluster = PSCluster.from_partition(
            graph, self.labels, res, self.dbpg,
            bandwidth=tr["link_bytes_per_s"], seed=seed)
        self.owner = self.cluster.owner.copy()
        self.source = PSRequestSource(
            self.cluster,
            RequestMix((ZipfWorkload("ctr", batch=tr["batch_rows"],
                                     zipf_s=tr["zipf_s"]),)),
            ServingConfig(prefetch=True, seed=seed))
        rng = np.random.default_rng([seed & (2**64 - 1), 5])
        self._sampled = rng.random(1 << 20) < tr["check_share"]
        self._sampled[0] = True        # every run checks at least one
        self._cur = self._produce()
        for _ in range(tr["warmup_requests"]):
            self._serve(record=False)

    # ------------------------------------------------------------ loop
    def _produce(self):
        src, t = self.source, self.t
        self._t_produce = time.perf_counter()
        with self.ctx.annotate("produce"):
            src.on_step(t)
            req = src.next_request(t)
            w_issue = self.cluster.w
            handle = src.issue(req, t)
        return t, req, handle, w_issue

    def _serve(self, record: bool) -> float:
        src = self.source
        t, req, handle, w_issue = self._cur
        self.t += 1
        self._cur = self._produce()          # prefetch t+1 before serving t
        produce = time.perf_counter() - self._t_produce
        with self.ctx.annotate("block"):
            tb = time.perf_counter()
            payload = handle.block()
            blocked = time.perf_counter() - tb
        with self.ctx.annotate("compute"):
            tc = time.perf_counter()
            out = src.compute(req, payload)
            jax.block_until_ready(out)
            compute = time.perf_counter() - tc
        sample = record and self._sampled[len(self.records) % (1 << 20)]
        keys = self.cluster._keys_sent[req.home].copy() if sample else None
        w_before = self.cluster.w   # commit replaces, never mutates, it
        tm = time.perf_counter()
        with self.ctx.annotate("commit"):
            stats = src.commit(req, out, t)
        end = time.perf_counter()
        if record:
            self.records.append({
                "latency_s": end - handle.issued_at, "blocked_s": blocked,
                "compute_s": compute, "produce_s": produce,
                "commit_s": end - tm, "wire_s": handle.wire_s,
                "queue_s": handle.queue_s})
            if sample:
                self.samples.append((req.rows, req.home, req.need, w_issue,
                                     payload, out, w_before,
                                     self.cluster.w,
                                     stats["push_inter_bytes"], keys))
        return end

    def window(self, seconds: float) -> None:
        start = time.perf_counter()
        end = start
        while end - start < seconds:
            end = self._serve(record=True)
        self.window_s = end - start

        def total(key):
            return sum(r[key] for r in self.records)

        self.ctx.log(f"window: {len(self.records)} requests in "
                     f"{self.window_s:.3f} s; summed seconds: modeled wire "
                     f"{total('wire_s') + total('queue_s'):.3f}, produce "
                     f"{total('produce_s'):.3f}, blocked "
                     f"{total('blocked_s'):.3f}, compute "
                     f"{total('compute_s'):.3f}, commit "
                     f"{total('commit_s'):.3f}")

    def end_to_end(self) -> dict:
        lat = np.array([r["latency_s"] for r in self.records]) * 1e3
        return {"serve_rate": len(self.records) / self.window_s,
                "serve_p95_ms": float(np.percentile(lat, 95))}

    def release(self) -> None:
        """Keep the sampled requests on the host and free the device."""
        host = []
        for rows, home, need, w_issue, payload, out, w_before, w_after, \
                push, keys in self.samples:
            _, g, loss = out
            host.append((rows, home, need, np.asarray(w_issue),
                         np.asarray(payload), np.asarray(g), float(loss),
                         np.asarray(w_before), np.asarray(w_after), push,
                         keys))
        self.samples = host
        self.source = self.cluster = self._cur = None

    def check(self) -> list:
        indptr, indices = self.csr
        d = self.dbpg
        gaps = dict.fromkeys(LIMITS, 0.0)
        gaps["pull_mismatch"] = gaps["push_bytes_gap"] = 0
        gaps["write_off_need"] = 0
        bad = 0
        for rows, home, need, w_issue, payload, g, loss, w_before, w_after, \
                push, keys in self.samples:
            pull = int((payload[need] != w_issue[need]).sum())
            ref_loss, ref_g, ref_w = dbpg_step(
                indptr, indices, rows, self.labels, payload, need, d.lr,
                d.lam)
            g_scale = max(float(np.abs(ref_g[need]).max()), 1e-30)
            one = {
                "pull_mismatch": pull,
                "loss_gap": abs(loss - ref_loss) / max(abs(ref_loss), 1.0),
                "grad_gap": float(np.abs(g[need] - ref_g[need]).max())
                / g_scale,
                "update_gap": float(np.abs(w_after[need] - ref_w[need]).max())
                / (d.lr * g_scale),
                "write_off_need": int((w_after[~need] != w_before[~need]
                                       ).sum()),
                "push_bytes_gap": abs(push - push_bytes(
                    need & (g != 0), self.owner, home, self.cfg["k"],
                    1 if d.compress else 4, keys)),
            }
            bad += any(one[k] > LIMITS[k] for k in LIMITS)
            for k, v in one.items():
                gaps[k] = max(gaps[k], v)
        self.bad = bad
        self.ctx.log(f"reference: {len(self.samples)} sampled requests "
                     f"of {len(self.records)} recomputed")
        return [{"name": k, "value": gaps[k], "limit": LIMITS[k]}
                for k in LIMITS]

    def counts(self) -> tuple[int, int]:
        return len(self.records), self.bad

    def record(self, summary) -> dict:
        return {"kind": "serve", "window_s": self.window_s,
                "requests": self.records, "trace": summary}
