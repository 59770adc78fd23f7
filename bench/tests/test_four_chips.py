"""The Alg 4 stream cell on four virtual devices: a sound run is correct
and one whose chips never exchange their sets is caught."""
import json
import os
import pathlib
import subprocess
import sys

HERE = pathlib.Path(__file__).resolve().parent


def test_four_chip_cell_and_missing_exchange(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(HERE / "four_chips.py"),
                        str(tmp_path)], env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    runs = {r["run"]: r["line"] for r in
            map(json.loads, p.stdout.strip().splitlines())}
    assert runs["sound"]["correct"], runs["sound"]["checks"]
    assert runs["sound"]["device"]["count"] == 4
    assert not runs["no_exchange"]["correct"]
    assert runs["no_exchange"]["checks"]["rows_misplaced"]["value"] > 0
