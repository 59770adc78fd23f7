"""Drive the tiny Alg 4 cell through the ``stream_mesh`` driver on four
virtual CPU devices, untraced and traced; prints one JSON line per run.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python bench/tests/mesh_cell.py <tmp_dir>
"""
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE.parents[1]))

from bench import harness  # noqa: E402
from bench.tests import tiny  # noqa: E402

CELL = "tiny.ctr_stream_mesh"


def make_root(dst: pathlib.Path) -> pathlib.Path:
    """``tiny.make_root`` plus the tiny Alg 4 configuration under the
    ``stream_mesh`` driver, as a new traffic file and a new cell that
    every stream metric lists."""
    root = tiny.make_root(dst)
    traffic = {"driver": "stream_mesh", "rows_per_feed": 1024,
               "pool_feeds": 4, "warmup_feeds": 1, "trace_seconds": 1}
    (root / "bench/traffic/tiny_stream_mesh.json").write_text(
        json.dumps(traffic))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": CELL, "config": "tiny_ctr_w4",
                              "traffic": "tiny_stream_mesh", "chips": 4,
                              "why": "tiny"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m and tiny.TINY_W4 in m["workloads"]:
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return root


def main(tmp: str) -> None:
    root = make_root(pathlib.Path(tmp) / "root")
    for trace in (False, True):
        line, _ = harness.run_cell(CELL, 2**31 + 37, 0.5, trace, root=root,
                                   accelerator=False)
        print(json.dumps({"trace": trace, "line": line}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
