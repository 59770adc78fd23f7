"""Alg 4 feeds of ``StreamSession`` on four forced CPU devices, in one
subprocess: the same phases as a one-chip feed, the merge counters, each
worker's blocks on its own device, the live sets replicated on the mesh
from feed to feed, and parts, sets and sizes equal to the plain Alg 4
reference (``bench/reference.py``'s ``StreamReference(workers=4)``)."""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]

SCRIPT = r"""
import glob, json, sys, tempfile
import jax, numpy as np
assert len(jax.devices()) == 4, jax.devices()
from bench.reference import StreamReference
from repro.api import ParsaConfig, ParsaStreamConfig, StreamSession
from repro.core import jax_partition as jp
from repro.graphs import text_like

K, B, CAP, SEED = 4, 64, 8, 11
g = text_like(1536, 1 << 13, mean_len=20, seed=5)
chunks = [g.slice_u(512 * i, 512 * (i + 1)) for i in range(3)]
base = ParsaConfig(k=K, backend="parallel_device", workers=4, merge_every=1,
                   block_size=B, cap=CAP, use_kernel=False, refine_v=False,
                   seed=SEED)
sess = StreamSession(ParsaStreamConfig(base=base, repartition="never"),
                     num_v=g.num_v)

def placed(x):
    return {"devices": sorted(d.id for d in x.sharding.device_set),
            "replicated": bool(x.sharding.is_fully_replicated)}

out = {"W": sess.arena.W_cap, "feeds": [], "live": []}
for i, ch in enumerate(chunks):
    if i == len(chunks) - 1:
        tmp = tempfile.mkdtemp()
        with jax.profiler.trace(tmp):
            upd = sess.feed(ch)
    else:
        upd = sess.feed(ch)
    out["feeds"].append({"timings": upd.timings, "counters": upd.counters,
                         "pushed_bytes": upd.traffic.pushed_bytes,
                         "tasks": upd.traffic.tasks})
    out["live"].append([placed(sess.arena.s_masks), placed(sess.arena.sizes)])

from jax.profiler import ProfileData
spans = {}
for plane in ProfileData.from_file(glob.glob(
        tmp + "/**/*.xplane.pb", recursive=True)[-1]).planes:
    for line in plane.lines:
        for ev in line.events:
            if ev.name.startswith("parsa.feed."):
                spans[ev.name] = {k: v for k, v in ev.stats}
out["spans"] = spans

packed = jp.pack_graph_blocks(chunks[0], B, cap=CAP)
blocks = jp._place_parallel_blocks(packed, workers=4, merge_every=1)
out["shards"] = [[[s.index[0].start, s.device.id] for s in a.addressable_shards]
                 for a in blocks.arrays[:5]]
out["overflow"] = placed(blocks.arrays[5])
out["device_ids"] = [d.id for d in jax.devices()]

ref = StreamReference(K, g.num_v, B, CAP, SEED, workers=4)
want = np.concatenate([ref.feed(c.u_indptr, c.u_indices) for c in chunks])
words = np.ascontiguousarray(sess.arena.masks_np()).view(np.uint32)
bits = np.unpackbits(words.view(np.uint8).reshape(K, -1), axis=-1,
                     bitorder="little")[:, :g.num_v].astype(bool)
out["parts_differ"] = int((want != sess.parts).sum())
out["bits_differ"] = int((bits != ref.sets).sum())
out["sizes"] = [np.asarray(sess.arena.sizes).tolist(), ref.sizes.tolist()]
out["rows"] = int(sess.parts.shape[0])
print("ALG4_STREAM " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def alg4():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    p = subprocess.run([sys.executable, "-c", SCRIPT], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    lines = [ln for ln in p.stdout.splitlines()
             if ln.startswith("ALG4_STREAM ")]
    assert p.returncode == 0 and lines, p.stdout[-2000:] + p.stderr[-4000:]
    return json.loads(lines[-1][len("ALG4_STREAM "):])


PHASES = ["prepare", "pack", "upload", "launch", "wait", "append",
          "partition_u", "metrics", "release", "total"]


def test_alg4_feed_times_the_one_chip_phases(alg4):
    for f in alg4["feeds"]:
        t = f["timings"]
        assert list(t) == PHASES
        assert "scan" not in t
        assert t["partition_u"] == pytest.approx(
            sum(t[p] for p in ("upload", "launch", "wait", "append")),
            rel=1e-12)


def test_alg4_merge_counters_match_formulas_and_traffic(alg4):
    W, k, workers = alg4["W"], 4, 4
    rounds = -(-512 // 64) // workers       # 8 blocks over 4 workers
    for f in alg4["feeds"]:
        c = f["counters"]
        assert c["merge_rounds"] == rounds
        assert c["merge_bytes"] == rounds * (workers - 1) * k * W * 4
        assert c["pushed_words"] * 4 == f["pushed_bytes"] > 0
        assert f["tasks"] == workers * rounds
        # the packed blocks' counts are there as on one chip
        assert {"upload_bytes", "channel_rows", "channel_slots",
                "compact_blocks"} <= set(c)


def test_alg4_phases_and_merge_counters_in_the_trace(alg4):
    spans, last = alg4["spans"], alg4["feeds"][-1]
    for name in ("upload", "launch", "wait"):
        assert spans[f"parsa.feed.{name}"]["feed"] == 2
    assert "parsa.feed.scan" not in spans
    merge = ("merge_rounds", "merge_bytes", "pushed_words")
    assert {m: spans["parsa.feed.wait"][m] for m in merge} == {
        m: last["counters"][m] for m in merge}
    assert not set(merge) & set(spans["parsa.feed.pack"])
    assert spans["parsa.feed.pack"]["compact_blocks"] == last["counters"][
        "compact_blocks"]


def test_alg4_worker_shards_sit_on_their_own_devices(alg4):
    ids = alg4["device_ids"]
    for shards in alg4["shards"]:
        # row w of every (workers, nb_per, …) stack lives on device w alone
        assert sorted(shards) == [[w, ids[w]] for w in range(4)]
    assert alg4["overflow"] == {"devices": sorted(ids), "replicated": True}


def test_alg4_live_sets_stay_replicated_across_feeds(alg4):
    ids = sorted(alg4["device_ids"])
    assert len(alg4["live"]) == len(alg4["feeds"])
    for s_masks, sizes in alg4["live"]:
        assert s_masks == sizes == {"devices": ids, "replicated": True}


def test_alg4_stream_matches_the_plain_reference(alg4):
    assert alg4["rows"] == 1536
    assert alg4["parts_differ"] == 0
    assert alg4["bits_differ"] == 0
    got, want = alg4["sizes"]
    assert got == want
    assert np.ptp(got) <= 4        # the documented stale catch-up bound
