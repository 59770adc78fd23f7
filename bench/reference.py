"""Plain references the benchmark holds the program to.  Nothing here
imports the program.

* ``StreamReference`` — Parsa's blocked greedy (§4.1/§4.2) vertex by
  vertex, in numpy, over boolean server sets: in each block of ``block``
  rows, pick the smallest part (lowest index on ties), give it the
  unassigned row that adds the fewest new columns to its set (lowest row
  on ties), grow the set, repeat.  Rows are taken in the order the
  configuration fixes: per feed, ``numpy.random.default_rng(seed)``
  draws one permutation of the feed's rows; blocks are consecutive runs
  of it.  ``stale=True`` is the control: every block of a feed sees the
  sets as they stood when the feed began, as if the feed's blocks were
  scanned side by side, so one guarantee of the configuration (each
  block sees every block before it) is broken.
* ``dbpg_step`` — one served DBPG step of ℓ1 logistic regression in
  float64.
* ``push_bytes`` — the push metering recounted by owner.
"""
from __future__ import annotations

import numpy as np

__all__ = ["StreamReference", "dbpg_step", "push_bytes"]

def truncated_rows(indptr: np.ndarray, indices: np.ndarray,
                   cap: int) -> np.ndarray:
    """Rows whose columns fill more than ``cap`` distinct 32-bit words."""
    n = indptr.shape[0] - 1
    rows = np.repeat(np.arange(n), np.diff(indptr))
    words = indices >> 5
    new = np.ones(indices.shape[0], bool)
    new[1:] = (words[1:] != words[:-1]) | (rows[1:] != rows[:-1])
    return np.bincount(rows[new], minlength=n) > cap


class StreamReference:
    """Server sets and sizes of a stream, fed chunk by chunk.

    ``workers > 1`` follows Alg 4 as the configuration runs it: per feed
    a second permutation deals the blocks to the workers, ``workers``
    blocks at a time are assigned side by side against the same sets and
    sizes, then the sets are OR-merged and the size increments summed
    (one merge per block, ``merge_every`` = 1)."""

    def __init__(self, k: int, num_v: int, block: int, cap: int,
                 seed: int, stale: bool = False, workers: int = 1):
        self.k, self.block, self.cap = k, block, cap
        self._sets_t = np.zeros((num_v, k), bool)   # column-major: a block
                                                    # gathers whole rows
        self.sizes = np.zeros(k, np.int64)
        self.rng = np.random.default_rng(seed)
        self.stale = stale
        self.workers = workers
        self.max_tb = 0   # most truncated rows in one block, over all feeds

    @property
    def sets(self) -> np.ndarray:
        """(k, num_v) bool server sets."""
        return self._sets_t.T

    def feed(self, indptr: np.ndarray, indices: np.ndarray) -> np.ndarray:
        """Assign one chunk's rows; returns their parts in chunk order."""
        n = indptr.shape[0] - 1
        order = self.rng.permutation(n)
        trunc = truncated_rows(indptr, indices, self.cap)[order]
        n_blocks = -(-n // self.block)
        blocks = []
        for b0 in range(0, n, self.block):
            rows = order[b0:b0 + self.block]
            self.max_tb = max(self.max_tb,
                              int(trunc[b0:b0 + self.block].sum()))
            lo, hi = indptr[rows], indptr[rows + 1]
            lens = hi - lo
            sub_ptr = np.zeros(rows.size + 1, np.int64)
            np.cumsum(lens, out=sub_ptr[1:])
            sub_idx = indices[np.repeat(lo - sub_ptr[:-1], lens)
                              + np.arange(sub_ptr[-1])]
            blocks.append((rows, sub_ptr, sub_idx))
        if self.workers > 1:
            per = -(-n_blocks // self.workers)
            perm = self.rng.permutation(per * self.workers)
            steps = [[int(perm[w * per + t]) for w in range(self.workers)
                      if perm[w * per + t] < n_blocks] for t in range(per)]
        else:
            steps = [[j] for j in range(n_blocks)]
        start = self._sets_t.copy() if self.stale else None
        parts = np.empty(n, np.int64)
        for step in steps:
            seen = self._sets_t if start is None else start
            sizes0 = self.sizes.copy()
            grown = []
            for j in step:
                rows, sub_ptr, sub_idx = blocks[j]
                sizes = sizes0.copy()
                got, cols, owners = self._block(sub_ptr, sub_idx, seen, sizes)
                parts[rows] = got
                grown.append((cols, owners))
                self.sizes += sizes - sizes0
            for cols, owners in grown:
                self._sets_t[cols, owners] = True
        return parts

    def _block(self, indptr, idx, sets_t, sizes):
        """Greedy over one block against ``sets_t``, which it only reads;
        updates ``sizes`` in place and returns (parts, grown columns, their
        parts).  Works on the block's own columns: ``S`` is the sets
        restricted to them and ``M`` the (column, row) incidence, so a
        down-date is one product.  Costs stay below 2^24, exact in
        float32."""
        B = indptr.shape[0] - 1
        deg = np.diff(indptr)
        ucol, inv = np.unique(idx, return_inverse=True)
        S = np.ascontiguousarray(sets_t[ucol].T)
        M = np.zeros((ucol.shape[0], B), np.float32)
        M[inv, np.repeat(np.arange(B), deg)] = 1.0
        cs = np.zeros((self.k, idx.shape[0] + 1), np.int32)
        np.cumsum(S[:, inv], axis=1, dtype=np.int32, out=cs[:, 1:])
        cost = (deg[None, :] - (cs[:, indptr[1:]] - cs[:, indptr[:-1]])
                ).astype(np.float32)
        ones = np.ones(int(deg.max()), np.float32)
        ptr = indptr.tolist()
        size_list = sizes.tolist()
        parts = np.full(B, -1, np.int64)
        grown_part, grown_col = [np.zeros(0, np.int64)], [np.zeros(0, np.int64)]
        for _ in range(B):
            i = size_list.index(min(size_list))
            u = int(np.argmin(cost[i]))
            loc = inv[ptr[u]:ptr[u + 1]]
            fresh = loc[~S[i, loc]]
            if fresh.shape[0]:
                S[i, fresh] = True
                cost[i] -= ones[:fresh.shape[0]] @ M[fresh]
                grown_part.append(np.full(fresh.shape[0], i))
                grown_col.append(fresh)
            cost[:, u] = np.inf
            size_list[i] += 1
            parts[u] = i
        sizes[:] = size_list
        return (parts, ucol[np.concatenate(grown_col)],
                np.concatenate(grown_part))


def dbpg_step(indptr, indices, rows, labels, w, need, lr: float,
              lam: float):
    """(loss, smooth gradient, proximal update on ``need``) of one batch
    of ``rows`` at weights ``w``, in float64."""
    lo, hi = indptr[rows], indptr[rows + 1]
    lens = hi - lo
    cols = indices[np.repeat(lo - np.cumsum(lens) + lens, lens)
                   + np.arange(int(lens.sum()))]
    rid = np.repeat(np.arange(rows.shape[0]), lens)
    w = np.asarray(w, np.float64)
    y = labels[rows].astype(np.float64)
    m = y * np.bincount(rid, weights=w[cols], minlength=rows.shape[0])
    loss = float(np.logaddexp(0.0, -m).sum())
    coef = -y / (1.0 + np.exp(m))
    g = np.bincount(cols, weights=coef[rid], minlength=w.shape[0])
    step = w - lr * g
    prox = np.sign(step) * np.maximum(np.abs(step) - lr * lam, 0.0)
    return loss, g, np.where(need, prox, w)


def push_bytes(mask: np.ndarray, owner: np.ndarray, home: int, k: int,
               val_bytes: int, keys_sent: np.ndarray) -> int:
    """Inter-machine bytes of a push of ``mask`` from ``home``: each
    remote owner gets ``val_bytes`` per entry, plus a 4-byte key per entry
    on a link whose keys were not sent before."""
    per = np.bincount(owner[mask], minlength=k).astype(np.int64)
    total = 0
    for j in np.flatnonzero(per):
        if j == home:
            continue
        total += int(per[j]) * (val_bytes + (0 if keys_sent[j] else 4))
    return total
