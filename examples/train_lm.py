"""Train a small LM end-to-end through the full framework path (config →
model → AdamW → data pipeline → checkpointed TrainLoop), with optional
Parsa-placed embedding data sharding.

Any of the 10 architectures works via --arch; default trains a reduced
qwen3-family model for a few hundred steps on CPU.

    PYTHONPATH=src python examples/train_lm.py --arch qwen3-14b --steps 200
"""
import argparse
import sys

from repro.launch import train as train_mod
from repro.runtime import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()
    enable_compile_cache()
    hist = train_mod.main([
        "--arch", args.arch, "--reduce", "--steps", str(args.steps),
        "--batch", str(args.batch), "--seq", str(args.seq),
        "--ckpt-dir", "/tmp/repro_example_lm", "--log-every", "20",
    ])
    assert hist and hist[-1]["loss"] < hist[0]["loss"], "loss must decrease"
    print("OK: loss decreased over training")


if __name__ == "__main__":
    main()
