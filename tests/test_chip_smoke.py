"""The phases of ``chip_smoke.py`` at a tiny size on the CPU (Pallas in
interpret mode), so the script cannot rot between runs on the chip."""
from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)

K, SEED = 16, 0


@pytest.fixture(scope="module")
def graph():
    return smoke.ctr_graph(2048, 4096, SEED)


@pytest.fixture(scope="module")
def result(graph):
    return smoke.phase_partition(graph, K, SEED)


def test_partition_phase_balanced_and_beats_random(result):
    assert result.parts_v is not None
    assert np.bincount(result.parts_u, minlength=K).min() > 0


def test_parity_phase(graph):
    smoke.phase_parity(graph.slice_u(0, 512), K, SEED)


@pytest.mark.parametrize("sketch", [False, True])
def test_kernel_scan_lowers_interpreted_off_tpu(sketch):
    hlo = smoke.kernel_scan_hlo(512, 1 << 14, K, sketch=sketch)
    assert "func.func public @main" in hlo
    assert "tpu_custom_call" not in hlo  # interpret mode on the CPU


def test_serve_phase(graph, result):
    from repro.ml import make_problem

    _, labels = make_problem(graph, seed=SEED)
    summary = smoke.phase_serve(graph, result, labels, 8, SEED)
    assert summary["requests"] == 8 - 3  # after the default warm-up


def test_device_info_refuses_the_cpu():
    with pytest.raises(SystemExit, match="no TPU"):
        smoke.device_info(1)


def test_script_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    last = out.stdout.strip().splitlines()[-1]
    assert not last.startswith("{")
    assert json.loads(last.removeprefix("device "))["platform"] == "cpu"


def test_four_chip_phase_on_four_virtual_devices():
    """Mesh and output shards span four distinct devices.  At this size
    stale merges cost far more than at the deployment's, so the quality
    band is loose here."""
    script = (
        "import importlib.util\n"
        f"spec = importlib.util.spec_from_file_location('s', {str(ROOT / 'chip_smoke.py')!r})\n"
        "s = importlib.util.module_from_spec(spec); spec.loader.exec_module(s)\n"
        "s.FOUR_CHIP_BAND = 0.5\n"
        "s.phase_four_chips(s.ctr_graph(4096, 8192, 0), 16, 0)\n"
        "print('FOUR_CHIP_PHASE_OK')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert "FOUR_CHIP_PHASE_OK" in out.stdout, out.stdout + out.stderr
    assert "output shards on [0, 1, 2, 3]" in out.stdout
