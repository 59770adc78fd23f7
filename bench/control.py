"""The controls that each cell's check has to fail, run at the cell's own
size (on the chip, or on the CPU at a tiny size in ``bench/tests``).

    python3 bench/control.py --workload <cell> --seeds 1 2 3 [--seconds 30]

Stream cells: the plain greedy with one guarantee of the configuration
broken — every block of a feed sees the server sets as they stood when
the feed began, the step a pipelined scan would tempt — is put in the
program's place, and its parts are compared with the reference's as a run
compares the program's: ``rows_misplaced`` and ``set_bits_differ``.  It
replays as many feeds as the cell's runs place in ``--seconds``
(``--feeds``, or from the run's log).

Serving cells: the served step is replaced by the same step computed in
bfloat16 (the precision below the configuration's float32) and a whole
run is made; the check's gaps are its readings.

Each seed prints one JSON line: the cell, the seed, and the readings.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]

__all__ = ["stream_readings", "bf16_serve_step", "serve_readings"]


def stream_readings(config: dict, traffic: dict, seed: int,
                    feeds: int) -> dict:
    """Control against reference over ``feeds`` chunks of the cell's pool
    (wrapping as a run does)."""
    import numpy as np

    from bench import gen
    from bench.reference import StreamReference

    p = config["parsa"]
    num_v = gen.num_columns(config)
    pool = gen.chunk_pool(config, seed, traffic["rows_per_feed"],
                          min(feeds, traffic["pool_feeds"]))
    args = (config["k"], num_v, p["block_size"], p["cap"], seed)
    workers = p["workers"] if p["backend"] == "parallel_device" else 1
    ref = StreamReference(*args, workers=workers)
    ctl = StreamReference(*args, stale=True, workers=workers)
    misplaced = 0
    for j in range(feeds):
        chunk = pool[j % len(pool)]
        misplaced += int((ref.feed(*chunk) != ctl.feed(*chunk)).sum())
    return {"rows_misplaced": misplaced,
            "set_bits_differ": int((ref.sets != ctl.sets).sum()),
            "sizes_differ": int(np.abs(ref.sizes - ctl.sizes).sum())}


def bf16_serve_step(batch, w, need, lr, lam, update):
    """``_serve_step``'s arithmetic, every value rounded to bfloat16."""
    import jax
    import jax.numpy as jnp

    bf = jnp.bfloat16
    wb = w.astype(bf)
    xw = jax.ops.segment_sum(batch.values.astype(bf) * wb[batch.col_ids],
                             batch.row_ids, num_segments=batch.num_rows)
    m = batch.labels.astype(bf) * xw
    loss = jnp.sum(jnp.logaddexp(jnp.zeros((), bf), -m))
    coef = -batch.labels.astype(bf) * jax.nn.sigmoid(-m)
    g = jax.ops.segment_sum(batch.values.astype(bf) * coef[batch.row_ids],
                            batch.col_ids, num_segments=batch.num_features)
    step = wb - jnp.asarray(lr, bf) * g
    prox = jnp.sign(step) * jnp.maximum(jnp.abs(step)
                                        - jnp.asarray(lr * lam, bf), 0)
    new_w = jnp.where(need, prox, wb) if update else wb
    return (new_w.astype(jnp.float32), g.astype(jnp.float32),
            loss.astype(jnp.float32))


def serve_readings(workload: str, seed: int, seconds: float,
                   root: pathlib.Path = ROOT,
                   accelerator: bool = True) -> dict:
    import jax

    from bench import harness
    from repro.serving import engine

    real = engine._serve_step
    engine._serve_step = jax.jit(bf16_serve_step,
                                 static_argnames=("lr", "lam", "update"))
    try:
        line, checks = harness.run_cell(workload, seed, seconds, False,
                                        root=root, accelerator=accelerator)
    finally:
        engine._serve_step = real
    return {c["name"]: c["value"] for c in checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--feeds", type=int, default=None,
                    help="stream cells: feeds to replay")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench import harness

    spec = harness.load_spec(ROOT)
    cell, config, traffic, _ = harness.resolve(spec, args.workload, ROOT)
    for seed in args.seeds:
        if traffic["driver"] == "stream":
            feeds = args.feeds or traffic["pool_feeds"]
            got = stream_readings(config, traffic, seed, feeds)
        else:
            got = serve_readings(args.workload, seed, args.seconds)
        print(json.dumps({"cell": args.workload, "seed": seed,
                          "control": got}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
