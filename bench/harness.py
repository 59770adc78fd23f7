"""Run one cell of ``BENCHMARK.json`` once and print its result line.

Everything is found by name.  A cell names a configuration, whose
``file`` the spec gives, and a traffic mix, read from
``bench/traffic/<traffic>.json``.  The mix's ``driver`` names the module
``bench/drivers/<driver>.py`` that sets the cell up, drives its window
and checks it.  Each per-layer metric is read by
``bench/metrics/<metric>.py``.  A new cell or metric is new files and a
new entry; nothing here changes.

The steps of a run, in order:

1. refuse any device that is not a TPU, or too few chips;
2. turn on JAX's persistent compile cache at its fixed path;
3. set up: make the data from ``--seed``, build the system, warm up
   every shape the window uses (``setup_s`` ends here);
4. measure for ``--seconds`` (under the profiler with ``--trace 1``);
5. read the memory peak, free the program's state, and check what the
   window produced against the plain reference;
6. print the compared numbers beside their limits as the last lines of
   standard error, and the result as the last line of standard output.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent

__all__ = ["main", "run_cell", "load_spec", "Context"]


def load_spec(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Context:
    """What a driver gets: the cell's configuration, its traffic, the
    seed, a logger and the annotation hook."""

    def __init__(self, config, traffic, seed, trace):
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.trace = bool(trace)

    @staticmethod
    def log(*parts) -> None:
        print(*parts, file=sys.stderr, flush=True)

    def annotate(self, name: str):
        """A host span in the profiler's trace (a no-op when untraced)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("bench." + name)


def resolve(spec: dict, workload: str, root: pathlib.Path):
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    with open(root / configs[cell["config"]]["file"]) as f:
        config = json.load(f)
    bench = root / spec["paths"][0]
    with open(bench / "traffic" / f"{cell['traffic']}.json") as f:
        traffic = json.load(f)
    driver = _module(bench / "drivers" / f"{traffic['driver']}.py",
                     f"bench_driver_{traffic['driver']}")
    return cell, config, traffic, driver


def _metric_names(spec: dict, kind: str, workload: str) -> list:
    return [m for m in spec[kind]
            if "workloads" not in m or workload in m["workloads"]]


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: pathlib.Path = ROOT, accelerator: bool = True,
             t_process: float | None = None,
             keep_trace: str | None = None) -> tuple[dict, list]:
    """One run of one cell; returns (result line, compared numbers).
    ``keep_trace`` names a file to copy the raw trace to."""
    t_process = time.perf_counter() if t_process is None else t_process
    spec = load_spec(root)
    cell, config, traffic, driver = resolve(spec, workload, root)
    from bench import device

    info = device.require_chips(cell["chips"], accelerator=accelerator)
    cache = device.enable_cache()
    clock = device.CompileClock()
    ctx = Context(config, traffic, seed, trace)
    ctx.log(f"cell {workload}: config {cell['config']}, traffic "
            f"{cell['traffic']}, seed {seed}, {seconds} s, trace "
            f"{int(trace)}; compile cache {cache}; host "
            f"{len(os.sched_getaffinity(0))} cores, load "
            f"{os.getloadavg()[0]:.2f}")

    run = driver.Cell(ctx)
    run.setup()
    setup_s = time.perf_counter() - t_process
    compiles_before, compile_s_before = clock.compiles, clock.seconds
    ctx.log(f"setup {setup_s:.3f} s, of it compiling {clock.seconds:.3f} s "
            f"({clock.compiles} programs built)")

    tdir = None
    if trace:
        import jax

        tdir = device.trace_dir(root, workload, seed)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with jax.profiler.trace(tdir, profiler_options=opts):
            with ctx.annotate("window"):
                run.window(min(seconds, traffic.get("trace_seconds",
                                                    seconds)))
    else:
        run.window(seconds)
    ctx.log(f"compiles inside the window: "
            f"{clock.compiles - compiles_before} "
            f"({clock.seconds - compile_s_before:.3f} s)")
    import jax

    used = jax.devices()[: cell["chips"]]
    info["memory_peak_bytes"] = device.memory_peak(used)
    run.release()
    checks = run.check()
    correct = all(c["value"] <= c["limit"] for c in checks)

    metrics = {}
    breakdown = None
    if trace:
        from bench import trace as tracemod

        path = tracemod.find_xplane(tdir)
        if keep_trace:
            shutil.copyfile(path, keep_trace)
        summary = tracemod.reduce_file(path)
        info["busy_s"] = summary.busy_s
        info["window_s"] = summary.window_s
        breakdown = {"device_ops": summary.top(summary.ops),
                     "idle_gaps": summary.top(summary.gaps)}
        ctx.log("device seconds per program:",
                json.dumps(summary.top(summary.programs)))
        ctx.log("host span seconds:", json.dumps(summary.top(summary.spans)))
        record = run.record(summary)
        for m in _metric_names(spec, "per_layer", workload):
            reader = _module(root / spec["paths"][0] / "metrics" /
                             f"{m['name']}.py", "bench_metric")
            value = reader.read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        shutil.rmtree(tdir, ignore_errors=True)
    else:
        e2e = run.end_to_end()
        e2e["setup_s"] = setup_s
        for m in _metric_names(spec, "end_to_end", workload):
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    attempted, failed = run.counts()
    line = {"correct": bool(correct), "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": info}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in checks}
    return line, checks


def main(argv: list[str] | None = None) -> int:
    t_process = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "BENCHMARK.json").exists() or not (ROOT / "src").exists():
        print("no program beside the benchmark", file=sys.stderr)
        return 3
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    os.chdir(ROOT)
    line, checks = run_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace), t_process=t_process)
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0
