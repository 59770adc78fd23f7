"""Benchmark entrypoint: one function per paper table/figure.
``python -m benchmarks.run [--quick]`` prints name,us_per_call,derived CSVs
to stdout and benchmarks/out/*.csv."""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller graphs (CI-scale)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    scale = 0.35 if args.quick else 1.0

    from repro.runtime import enable_compile_cache

    enable_compile_cache()

    from . import (bench_chaos, bench_embedding_traffic, bench_fig7_vary_k,
                   bench_fig8_subgraphs, bench_fig9_global_init,
                   bench_fig10_scalability, bench_kernels, bench_sketch,
                   bench_slo, bench_stream, bench_system, bench_table2,
                   bench_table34_dbpg)

    suites = {
        "table2": lambda: bench_table2.run(scale=scale),
        "fig7": lambda: bench_fig7_vary_k.run(scale=0.7 * scale),
        "fig8": lambda: bench_fig8_subgraphs.run(scale=0.6 * scale),
        "fig9": lambda: bench_fig9_global_init.run(scale=0.6 * scale),
        "fig10": lambda: bench_fig10_scalability.run(scale=0.6 * scale),
        "table34": lambda: bench_table34_dbpg.run(scale=scale),
        "embedding": lambda: bench_embedding_traffic.run(),
        "kernels": lambda: bench_kernels.run(scale=scale),
        "sketch": lambda: bench_sketch.run(scale=scale),
        "stream": lambda: bench_stream.run(scale=scale),
        "chaos": lambda: bench_chaos.run(scale=scale),
        "system": lambda: bench_system.run(scale=scale),
        "slo": lambda: bench_slo.run(scale=scale),
    }
    for name, fn in suites.items():
        if args.only and name != args.only:
            continue
        print(f"\n### {name} " + "=" * 50, flush=True)
        t0 = time.time()
        fn()
        print(f"### {name} done in {time.time() - t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
