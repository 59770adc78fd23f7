"""Faults planted under the timed path must turn ``correct`` false, and
the controls must fail the checks their cells make."""
import numpy as np
import pytest

from bench import control, harness
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("faults") / "root")


def _run(root, cell):
    line, _ = harness.run_cell(cell, 2**31 + 23, 0.5, False, root=root,
                               accelerator=False)
    return line


def _scan_fault(kind):
    import jax.numpy as jnp

    from repro.core.jax_partition import _partition_scan

    def scan(valid, widx, vals, trunc, tr_ids, tr_masks, s, sz, **kw):
        if kind == "half_batch":
            valid = valid.at[:, valid.shape[1] // 2:].set(False)
        keep = (jnp.array(s), jnp.array(sz))
        parts, s_out, sz_out = _partition_scan(
            valid, widx, vals, trunc, tr_ids, tr_masks, s, sz, **kw)
        if kind == "state_unchanged":
            return parts, keep[0], keep[1]
        if kind == "answer_altered":
            parts = parts.at[0, 0].set((parts[0, 0] + 1) % kw["k"])
        return parts, s_out, sz_out
    return scan


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "answer_altered"])
def test_stream_fault_is_caught(root, monkeypatch, kind):
    from repro.stream import online

    monkeypatch.setattr(online, "_partition_scan", _scan_fault(kind))
    line = _run(root, "tiny.ctr_stream")
    assert not line["correct"], line["checks"]
    assert line["checks"]["rows_misplaced"]["value"] > 0


def _serve_fault(kind):
    import jax.numpy as jnp

    from repro.serving.engine import _serve_step

    def step(batch, w, need, lr, lam, update):
        if kind == "half_batch":
            half = batch.row_ids < batch.num_rows // 2
            batch = type(batch)(batch.num_rows, batch.num_features,
                                batch.row_ids, batch.col_ids,
                                jnp.where(half, batch.values, 0.0),
                                batch.labels)
        new_w, g, loss = _serve_step(batch, w, need, lr=lr, lam=lam,
                                     update=update)
        if kind == "state_unchanged":
            new_w = w
        if kind == "answer_altered":
            loss = loss * 1.001
        return new_w, g, loss
    return step


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "answer_altered", "commit_noop"])
def test_serve_fault_is_caught(root, monkeypatch, kind):
    """Each fault under a commit that keeps the guarantees: the fault
    alone turns ``correct`` false."""
    from repro.ml import ps
    from repro.serving import engine

    tiny.fix_commit(monkeypatch)
    if kind == "commit_noop":
        monkeypatch.setattr(ps.PSCluster, "commit_weights",
                            lambda self, new_w: None)
    else:
        monkeypatch.setattr(engine, "_serve_step", _serve_fault(kind))
    line = _run(root, "tiny.ctr_serve")
    assert not line["correct"], line["checks"]


def test_program_commit_overwrites_other_homes(root):
    """The program's own commit writes the home's pull cache over the
    whole weight vector: the read-back catches it off the working set,
    while the step itself matches the reference."""
    line = _run(root, "tiny.ctr_serve")
    checks = line["checks"]
    assert not line["correct"]
    assert checks["write_off_need"]["value"] > 0
    assert all(c["value"] <= c["limit"] for name, c in checks.items()
               if name != "write_off_need"), checks


def test_stream_control_fails(root):
    spec = harness.load_spec(root)
    _, config, traffic, _ = harness.resolve(spec, "tiny.ctr_stream", root)
    got = control.stream_readings(config, traffic, 2**31 + 5, feeds=4)
    assert got["rows_misplaced"] > 0 and got["set_bits_differ"] >= 0


def test_serve_control_fails(root, monkeypatch):
    from bench.drivers import serve

    tiny.fix_commit(monkeypatch)
    got = control.serve_readings("tiny.ctr_serve", 2**31 + 5, 0.5,
                                 root=root, accelerator=False)
    over = [k for k, v in got.items() if v > serve.LIMITS[k]]
    assert set(over) & {"loss_gap", "grad_gap", "update_gap"}, got
    assert got["write_off_need"] == 0, got
    assert np.isfinite(list(got.values())).all()
