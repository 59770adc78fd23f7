"""Batched greedy decoding through the serving path (KV cache / SSM state),
for any of the 10 architectures.

    PYTHONPATH=src python examples/serve_lm.py --arch zamba2-2.7b
"""
import argparse

from repro.launch import serve as serve_mod
from repro.runtime import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x22b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--gen", type=int, default=24)
    args = ap.parse_args()
    enable_compile_cache()
    serve_mod.main(["--arch", args.arch, "--reduce", "--batch",
                    str(args.batch), "--prompt-len", "12", "--gen",
                    str(args.gen)])


if __name__ == "__main__":
    main()
