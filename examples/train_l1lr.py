"""End-to-end driver (the paper's own application, §5.5): distributed
ℓ1-regularized logistic regression with DBPG on a parameter-server layout,
Parsa vs random placement, exact traffic metering + modeled wall-clock.

    PYTHONPATH=src python examples/train_l1lr.py [--iters 45] [--k 16]
"""
import argparse

from repro.api import ParsaConfig, partition
from repro.core import random_parts
from repro.graphs import ctr_like
from repro.ml import DBPGConfig, PSCluster, make_problem
from repro.runtime import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--rows", type=int, default=1200)
    ap.add_argument("--features", type=int, default=5000)
    args = ap.parse_args()
    enable_compile_cache()
    k = args.k

    print("generating CTR-like training data ...")
    g = ctr_like(args.rows, args.features, nnz_per_row=25, seed=5)
    w_star, labels = make_problem(g, seed=5)
    print(f"  {g.num_u} examples × {g.num_v} features, {g.num_edges} nnz")

    print("Parsa-partitioning data + parameters (4 workers, τ=∞) ...")
    parsa = partition(g, ParsaConfig(
        k=k, backend="parallel_sim", blocks=8, workers=4, tau=None,
        global_init_frac=0.01, seed=0, refine_v=True, sweeps=2))

    cfg = DBPGConfig(lam=0.3, lr=0.005, max_delay=1)
    for name in ("random", "parsa"):
        if name == "parsa":
            cl = PSCluster.from_partition(g, labels, parsa, cfg, seed=1)
        else:
            cl = PSCluster(g, labels, random_parts(g.num_u, k, 0),
                           random_parts(g.num_v, k, 1), k, cfg, seed=1)
        res = cl.run(args.iters, log_every=max(args.iters // 5, 1))
        print(f"\n[{name}] after {args.iters} DBPG iterations:")
        print(f"  objective      : {res['objective'][0]:.1f} -> {res['objective'][-1]:.1f}")
        print(f"  nnz(w)         : {res['nnz_w']}")
        print(f"  inner-machine  : {res['inner_bytes']/1e6:.2f} MB")
        print(f"  inter-machine  : {res['inter_bytes']/1e6:.2f} MB")
        print(f"  local fraction : {res['inner_fraction']*100:.0f}%")
        print(f"  modeled time   : {res['modeled_time_s']*1e3:.2f} ms")


if __name__ == "__main__":
    main()
