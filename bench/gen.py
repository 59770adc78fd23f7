"""Seeded data for the benchmark's deployments, made in bulk on the host.

Every chunk is a function of ``(seed, chunk index)`` alone, so a run that
wraps round its pool, or a reference that replays it, sees the same rows.
A chunk is returned as CSR arrays ``(indptr int64, indices int32)`` with
sorted, distinct columns per row; ``as_graph`` wraps one for the program.

Generators, chosen by a configuration's ``generator`` key:

* ``criteo`` — impressions of a click log field by field: each row takes
  one value in each of its fields, drawn Zipf over that field's
  vocabulary (the published per-field counts for the categorical
  fields), and ``field:value`` is hashed into the weight vector, as a
  hashed logistic regression on the log does it.
* ``chung_lu`` — rows of a social graph under the U' = V construction:
  row lengths follow a power-law expected-degree sequence with the
  source's mean, and each row's neighbours are drawn in proportion to the
  same weights (Chung–Lu), so hub columns are popular and hub rows long.
"""
from __future__ import annotations

import functools

import numpy as np

__all__ = ["chunk_pool", "concat", "num_columns", "as_graph", "labels_for"]


def _rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**64 - 1), *tags])


def _zipf_ranks(rng: np.random.Generator, n: np.ndarray,
                s: float) -> np.ndarray:
    """Ranks 0..n-1, one per entry of ``n``, with P(r) about 1/(r+1)^s: the
    inverse of the continuous power law's CDF on [1, n + 1), floored, so
    a vocabulary of ten million values costs no table."""
    u = rng.random(n.shape)
    n1 = n.astype(np.float64) + 1.0
    if abs(s - 1.0) < 1e-12:
        x = np.exp(u * np.log(n1))
    else:
        a = 1.0 - s
        x = ((n1 ** a - 1.0) * u + 1.0) ** (1.0 / a)
    return np.minimum(x.astype(np.int64) - 1, n.astype(np.int64) - 1)


def _hash(key: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer over uint64 keys."""
    z = key.astype(np.uint64)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def _rows_to_csr(cols: np.ndarray):
    """(n, L) rows → CSR with sorted, distinct columns per row."""
    n = cols.shape[0]
    c = np.sort(cols.astype(np.int64), axis=1)
    keep = np.ones(c.shape, bool)
    keep[:, 1:] = c[:, 1:] != c[:, :-1]
    counts = keep.sum(axis=1)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(counts, out=indptr[1:])
    return indptr, c[keep].astype(np.int32)


# --------------------------------------------------------------- criteo
def _field_vocabularies(cfg: dict) -> np.ndarray:
    """Values of each of a row's fields: the integer fields' buckets, then
    the categorical fields' published counts."""
    return np.array([cfg["integer_buckets"]] * cfg["integer_fields"]
                    + list(cfg["categorical_cardinality"]), np.int64)


def _criteo_chunk(cfg: dict, seed: int, c: int, n: int):
    """Every row has one value in each field, Zipf over the field's
    vocabulary; ``field:value`` is hashed into the ``features`` weights."""
    vocab = _field_vocabularies(cfg)
    rng = _rng(seed, 1, c)
    ranks = _zipf_ranks(rng, np.broadcast_to(vocab, (n, vocab.size)),
                        cfg["zipf_s"])
    field = np.arange(1, vocab.size + 1, dtype=np.uint64)
    key = (field << np.uint64(40)) | ranks.astype(np.uint64)
    return _rows_to_csr(_hash(key) % np.uint64(cfg["features"]))


# ------------------------------------------------------------- chung_lu
@functools.lru_cache(maxsize=4)
def _chung_lu_weights(nodes: int, mean: float, exponent: float,
                      max_degree: int, seed: int):
    """Expected degrees w_i ∝ ((i + 1) / n)^(-1/(α-1)), capped at the
    maximum degree and scaled to the source's mean, then placed on node
    ids by a seeded permutation; returns (weights by node, column CDF)."""
    ranks = np.arange(1, nodes + 1, dtype=np.float64) / nodes
    w = ranks ** (-1.0 / (exponent - 1.0))
    for _ in range(8):  # the cap and the scale pull against each other
        w *= mean / w.mean()
        w = np.minimum(w, max_degree)
    w = w[_rng(seed, 2).permutation(nodes)]
    cdf = np.cumsum(w)
    return w, cdf / cdf[-1]


def _chung_lu_chunk(cfg: dict, seed: int, c: int, n: int):
    nodes = cfg["nodes"]
    w, cdf = _chung_lu_weights(nodes, 2.0 * cfg["edges"] / nodes,
                               cfg["degree_exponent"], cfg["max_degree"],
                               int(seed))
    rng = _rng(seed, 3, c)
    ids = (np.int64(c) * n + np.arange(n)) % nodes
    lens = np.clip(rng.poisson(w[ids]), 1, cfg["max_degree"])
    flat = np.searchsorted(cdf, rng.random(int(lens.sum())), side="right")
    key = (np.repeat(np.arange(n, dtype=np.int64), lens) * nodes
           + np.minimum(flat, nodes - 1))
    key = np.unique(key)                 # sorted; repeats within a row go
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(key // nodes, minlength=n), out=indptr[1:])
    return indptr, (key % nodes).astype(np.int32)


_GENERATORS = {"criteo": _criteo_chunk, "chung_lu": _chung_lu_chunk}


def num_columns(cfg: dict) -> int:
    return int(cfg["features"] if cfg["generator"] == "criteo"
               else cfg["nodes"])


def chunk_pool(cfg: dict, seed: int, rows: int, chunks: int) -> list:
    """``chunks`` chunks of ``rows`` rows each, as CSR pairs."""
    gen = _GENERATORS[cfg["generator"]]
    return [gen(cfg, seed, c, rows) for c in range(chunks)]


def concat(csrs: list):
    """Stack CSR chunks row-wise."""
    indptr = [np.zeros(1, np.int64)]
    off = 0
    for ip, _ in csrs:
        indptr.append(ip[1:] + off)
        off += int(ip[-1])
    return (np.concatenate(indptr),
            np.concatenate([ix for _, ix in csrs]).astype(np.int32))


def as_graph(csr, num_v: int):
    from repro.core.bipartite import BipartiteGraph

    indptr, indices = csr
    return BipartiteGraph(int(indptr.shape[0] - 1), int(num_v), indptr,
                          indices)


def labels_for(csr, num_v: int, seed: int, noise: float = 0.1) -> np.ndarray:
    """±1 labels from a planted sparse w* (5% support), with ``noise`` of
    them flipped — the logistic-regression problem a deployment trains."""
    indptr, indices = csr
    rng = _rng(seed, 4)
    w_star = np.zeros(num_v, np.float32)
    support = rng.choice(num_v, size=max(1, num_v // 20), replace=False)
    w_star[support] = rng.normal(0, 1, size=support.size).astype(np.float32)
    rows = np.repeat(np.arange(indptr.shape[0] - 1), np.diff(indptr))
    margins = np.bincount(rows, weights=w_star[indices],
                          minlength=indptr.shape[0] - 1)
    flip = rng.random(margins.shape[0]) < noise
    return np.where(np.sign(margins + 1e-6) * (1 - 2 * flip) >= 0,
                    1.0, -1.0).astype(np.float32)
