"""End-to-end behaviour tests: the paper's full pipeline + the mini dry-run
(subprocess with 8 forced host devices — proves the sharded lowering path
without the production 512-device compile)."""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.api import ParsaConfig, partition
from repro.core import evaluate, partition_v, random_parts
from repro.core.placement import build_placement
from repro.graphs import ctr_like
from repro.ml import DBPGConfig, PSCluster, make_problem

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_end_to_end_paper_pipeline():
    """§5.5 in miniature: generate data → Parsa partition → DBPG → less
    traffic AND no worse convergence than random placement."""
    g = ctr_like(400, 1200, nnz_per_row=12, seed=21)
    w_star, labels = make_problem(g, seed=21)
    k = 8
    cfg = DBPGConfig(lam=0.3, lr=0.03, max_delay=1)
    pl = build_placement(g, k, b=4, a=2)
    res_p = PSCluster(g, labels, pl.doc_to_shard, pl.vocab_to_shard, k, cfg,
                      seed=1).run(10, log_every=9)
    ru, rv = random_parts(g.num_u, k, 0), random_parts(g.num_v, k, 1)
    res_r = PSCluster(g, labels, ru, rv, k, cfg, seed=1).run(10, log_every=9)
    assert res_p["inter_bytes"] < res_r["inter_bytes"]
    assert res_p["objective"][-1] < res_p["objective"][0]
    # modeled end-to-end time (the Table 3 quantity) improves
    assert res_p["modeled_time_s"] <= res_r["modeled_time_s"]


def test_partition_quality_objectives_jointly():
    """All three §2.4 objectives beat random simultaneously (Table 2 shape)."""
    g = ctr_like(600, 2000, nnz_per_row=18, seed=5)
    k = 16
    pu = partition(g, ParsaConfig(k=k, backend="host", blocks=4,
                                  init_iters=4, refine_v=False)).parts_u
    pv = partition_v(g, pu, k, sweeps=2)
    m = evaluate(g, pu, pv, k)
    mr = evaluate(g, random_parts(g.num_u, k, 0), random_parts(g.num_v, k, 1), k)
    assert m.size_max <= mr.size_max + 1
    assert m.mem_max < mr.mem_max
    assert m.traffic_max < mr.traffic_max


@pytest.mark.slow
def test_mini_dryrun_subprocess(tmp_path):
    """dryrun.py on a 2×2×2 mesh with 8 forced host devices: the multi-pod
    lowering path (pod axis + shardings + collectives) compiles."""
    env = dict(os.environ)
    env.update(
        DRYRUN_XLA_FLAGS="--xla_force_host_platform_device_count=8",
        REPRO_MESH="2,2,2",
        PYTHONPATH=str(ROOT / "src"),
    )
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "whisper-medium",
         "--shape", "train_4k", "--multi-pod", "--force"],
        env=env, capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert "[ok]" in out.stdout, out.stdout + out.stderr
    cell = json.loads(
        (ROOT / "benchmarks/out/dryrun/whisper-medium__train_4k__2x2x2.json").read_text())
    assert cell["status"] == "ok"
    assert cell["roofline"]["wire_bytes_per_device"] > 0
