"""Drive the tiny Alg 4 cell on four virtual CPU devices, sound and with
the exchange between chips left out; prints one JSON line per run.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python bench/tests/four_chips.py <tmp_dir>
"""
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE.parents[1]))

from bench import harness  # noqa: E402
from bench.tests import tiny  # noqa: E402


def _no_exchange():
    """Every worker keeps its own sets: the all-gather returns only the
    local copy, so the OR-merge merges nothing."""
    import jax

    from repro.core import jax_partition

    def local_only(x, axis_name, **_):
        return x[None]

    jax_partition._parallel_scan_fn.cache_clear()
    real = jax.lax.all_gather
    jax.lax.all_gather = local_only
    return real


def main(tmp: str) -> None:
    import jax

    from repro.core import jax_partition

    root = tiny.make_root(pathlib.Path(tmp) / "root")
    line, _ = harness.run_cell(tiny.TINY_W4, 2**31 + 31, 0.5, False,
                               root=root, accelerator=False)
    print(json.dumps({"run": "sound", "line": line}), flush=True)
    real = _no_exchange()
    try:
        line, _ = harness.run_cell(tiny.TINY_W4, 2**31 + 31, 0.5, False,
                                   root=root, accelerator=False)
    finally:
        jax.lax.all_gather = real
        jax_partition._parallel_scan_fn.cache_clear()
    print(json.dumps({"run": "no_exchange", "line": line}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
