"""A copy of the benchmark with tiny cells added as new files only, for
runs on the CPU (and for recording a small trace on the chip)."""
from __future__ import annotations

import json
import os
import pathlib
import shutil

REPO = pathlib.Path(__file__).resolve().parents[2]

TINY_CELLS = {
    "tiny.ctr_stream": ("tiny_ctr", "tiny_stream"),
    "tiny.social_stream": ("tiny_social", "tiny_stream"),
    "tiny.ctr_serve": ("tiny_ctr", "tiny_serve"),
}
# needs four devices: XLA_FLAGS=--xla_force_host_platform_device_count=4
TINY_W4 = "tiny.ctr_stream_w4"

# The serving cell's metrics, declared as a benchmark entry would declare
# them; added to the copy where the repository's spec lacks them.
SERVE_METRICS = {
    "end_to_end": [
        {"name": "serve_rate", "unit": "req/s", "better": "higher",
         "source": "host_clock"},
        {"name": "serve_p95_ms", "unit": "ms", "better": "lower",
         "source": "host_clock"}],
    "per_layer": [
        {"name": "serve.host_ms", "unit": "ms", "better": "lower",
         "source": "host_clock", "moves": "serve_rate",
         "layer": "PS host path (ml/ps.py, serving engine)"},
        {"name": "serve.compute_ms", "unit": "ms", "better": "lower",
         "source": "host_clock", "moves": "serve_rate",
         "layer": "serve step (_serve_step)"},
        {"name": "serve.blocked_ms", "unit": "ms", "better": "lower",
         "source": "host_clock", "moves": "serve_p95_ms",
         "layer": "pull wait (PullHandle.block)"},
        {"name": "serve.idle_share", "unit": "%", "better": "lower",
         "source": "device_trace", "moves": "serve_rate",
         "layer": "device"}],
}


def fix_commit(monkeypatch) -> None:
    """Make ``PSRequestSource.commit`` write the request's working set
    only, as the configuration's guarantees state; the program's commit
    writes the home's whole pull cache back."""
    import jax.numpy as jnp

    from repro.serving import engine

    real = engine.PSRequestSource.commit

    def commit(self, req, out, t):
        new_w, g, loss = out
        merged = jnp.where(jnp.asarray(req.need), new_w, self.cluster.w)
        return real(self, req, (merged, g, loss), t)

    monkeypatch.setattr(engine.PSRequestSource, "commit", commit)


def make_root(dst) -> pathlib.Path:
    """Copy ``BENCHMARK.json`` and ``bench/`` to ``dst``, link the
    program beside them, and add the tiny cells: two configurations, two
    traffic mixes and three ``workloads`` entries, all new."""
    dst = pathlib.Path(dst)
    shutil.copytree(REPO / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(REPO / "src", dst / "src")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    with open(REPO / "bench/configs/criteo_k16.json") as f:
        ctr = json.load(f)
    ctr.update(name="tiny_ctr", features=1 << 16, impressions=4096)
    with open(REPO / "bench/configs/livejournal_k16.json") as f:
        social = json.load(f)
    social.update(name="tiny_social", nodes=60000, edges=850000,
                  max_degree=3000)
    with open(REPO / "bench/configs/criteo_k16_w4.json") as f:
        ctr_w4 = json.load(f)
    ctr_w4.update(name="tiny_ctr_w4", features=1 << 16)
    files = {
        "bench/configs/tiny_ctr.json": ctr,
        "bench/configs/tiny_social.json": social,
        "bench/configs/tiny_ctr_w4.json": ctr_w4,
        "bench/traffic/tiny_stream.json": {
            "driver": "stream", "rows_per_feed": 1024, "pool_feeds": 4,
            "warmup_feeds": 1, "trace_seconds": 1},
        "bench/traffic/tiny_serve.json": {
            "driver": "serve", "batch_rows": 64, "zipf_s": 1.1,
            "link_bytes_per_s": 125000000, "warmup_requests": 4,
            "check_share": 0.25, "trace_seconds": 1},
    }
    for rel, body in files.items():
        assert not (dst / rel).exists()
        (dst / rel).write_text(json.dumps(body))
    for name in ("tiny_ctr", "tiny_social", "tiny_ctr_w4"):
        spec["configs"].append({"name": name, "source": "tiny",
                                "file": f"bench/configs/{name}.json",
                                "reduced": [], "why": "tiny"})
    for kind, metrics in SERVE_METRICS.items():
        have = {m["name"] for m in spec[kind]}
        spec[kind] += [dict(m, workloads=[]) for m in metrics
                       if m["name"] not in have]
    serve = {m["name"] for ms in SERVE_METRICS.values() for m in ms}
    cells = dict(TINY_CELLS, **{TINY_W4: ("tiny_ctr_w4", "tiny_stream")})
    for name, (config, traffic) in cells.items():
        spec["workloads"].append({"name": name, "config": config,
                                  "traffic": traffic,
                                  "chips": 4 if name == TINY_W4 else 1,
                                  "why": "tiny"})
        driver = files[f"bench/traffic/{traffic}.json"]["driver"]
        for m in spec["end_to_end"] + spec["per_layer"]:
            if "workloads" in m and (m["name"] in serve) == (
                    driver == "serve"):
                m["workloads"].append(name)
    (dst / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return dst

