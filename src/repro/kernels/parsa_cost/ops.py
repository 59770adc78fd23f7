"""jit'd public wrappers for the parsa_cost / parsa_select kernels
(padding + dispatch) and the host-side bitmask packing routines."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .parsa_cost import parsa_cost_kernel
from .ref import (
    parsa_cost_ref,
    parsa_select_greedy_ref,
    parsa_select_ref,
    refine_sweep_ref,
    sketch_select_ref,
)
from .select import (
    SKETCH_KERNEL_MAX_WORDS,
    packed_union_delta_kernel,
    parsa_select_kernel,
    refine_sweep_kernel,
    sketch_select_kernel,
)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pack_bitmask(ids_per_row: list[np.ndarray] | np.ndarray, num_v: int) -> np.ndarray:
    """Pack per-row V-id sets into (rows, ceil(num_v/32)) int32 bitmasks."""
    W = (num_v + 31) // 32
    if isinstance(ids_per_row, np.ndarray) and ids_per_row.ndim == 2:
        # boolean membership matrix (rows, num_v); packbits binarizes the
        # rows directly so no dense-sized astype/pad transient is allocated
        rows = ids_per_row.shape[0]
        dense = ids_per_row if ids_per_row.dtype == np.bool_ \
            else ids_per_row.astype(bool)
        packed = np.packbits(dense, axis=-1, bitorder="little")  # (rows, ⌈V/8⌉)
        out = np.zeros((rows, W * 4), dtype=np.uint8)
        out[:, : packed.shape[1]] = packed
        return out.view(np.uint32).reshape(rows, W).view(np.int32)
    out = np.zeros((len(ids_per_row), W), dtype=np.uint32)
    for r, ids in enumerate(ids_per_row):
        ids = np.asarray(ids, dtype=np.int64)
        np.bitwise_or.at(out[r], ids // 32, np.uint32(1) << (ids % 32).astype(np.uint32))
    return out.view(np.int32)


def unpack_bitmask(masks: np.ndarray, num_v: int) -> np.ndarray:
    """Inverse of ``pack_bitmask``: (rows, ceil(num_v/32)) int32 bitmasks →
    (rows, num_v) bool membership matrix.  Exact round trip:
    ``unpack_bitmask(pack_bitmask(x, num_v), num_v) == x``.

    Allocates exactly one dense array: the 0/1 bytes from ``unpackbits``
    are reinterpreted as bool (same itemsize) instead of copied, so a
    worker pull in ``parallel.py`` costs one (rows, |V|) scratch, not two.
    """
    masks = np.ascontiguousarray(masks).view(np.uint32)
    rows, W = masks.shape
    bits = np.unpackbits(
        masks.view(np.uint8).reshape(rows, W * 4), axis=-1, bitorder="little")
    return bits[:, :num_v].view(np.bool_)


def coerce_packed_sets(sets, num_v: int) -> np.ndarray:
    """Normalize neighbor sets to the packed (k, ⌈num_v/32⌉) int32 wire
    format.  Accepts packed int32/uint32 words (returned as-is, no copy),
    a dense (k, num_v) bool membership matrix, or anything castable to one
    — so warm starts can hand ``PartitionResult.s_masks`` straight to a
    device backend without a dense round trip."""
    W = (num_v + 31) // 32
    a = np.asarray(sets)
    if a.ndim != 2:
        raise ValueError(f"neighbor sets must be 2-D, got shape {a.shape}")
    if a.dtype != np.bool_ and np.issubdtype(a.dtype, np.integer) \
            and a.shape[1] == W and a.shape[1] != num_v:
        return a.view(np.int32) if a.dtype == np.uint32 else \
            a.astype(np.int32, copy=False)
    if a.shape[1] != num_v:
        raise ValueError(
            f"neighbor sets width {a.shape[1]} matches neither num_v="
            f"{num_v} (dense) nor {W} packed words")
    return pack_bitmask(a.astype(bool, copy=False), num_v)


def coerce_dense_sets(sets, num_v: int) -> np.ndarray:
    """Inverse normalization: dense (k, num_v) bool view of neighbor sets
    handed in either format (packed input is unpacked into a fresh,
    writable scratch)."""
    W = (num_v + 31) // 32
    a = np.asarray(sets)
    if a.ndim != 2:
        raise ValueError(f"neighbor sets must be 2-D, got shape {a.shape}")
    if a.dtype != np.bool_ and np.issubdtype(a.dtype, np.integer) \
            and a.shape[1] == W and a.shape[1] != num_v:
        return unpack_bitmask(a, num_v)
    if a.shape[1] != num_v:
        raise ValueError(
            f"neighbor sets width {a.shape[1]} matches neither num_v="
            f"{num_v} (dense) nor {W} packed words")
    return a.astype(bool, copy=False)


def packed_union(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Word-wise union of packed bitmasks: the Alg 4 server OR-merge
    (line 9) on the wire format — works on any int word dtype."""
    return a | b


def packed_delta(new: np.ndarray, old: np.ndarray) -> np.ndarray:
    """Word-wise set difference ``new \\ old`` on packed bitmasks — the
    delta a worker pushes back to the server (Alg 4 worker line 9).
    ``packed_union(old, packed_delta(new, old)) == packed_union(old, new)``."""
    return new & ~old


def packed_intersect_counts(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """All-pairs intersection sizes of two packed bitmask stacks:
    ``out[i, j] = |rows_a[i] ∩ rows_b[j]|`` for (ka, W) × (kb, W) int32
    words → (ka, kb) int64 counts.

    Host-side mirror of the (k, k) packed intersection matrix the device
    metrics use (``jax_refine._metrics_popcount``); the stream migration
    planner matches old→new parts with it.  The (ka, kb, W) AND transient
    is materialized in one go — fine for partition counts (k ≤ 1024)."""
    a = np.ascontiguousarray(a).view(np.uint32)
    b = np.ascontiguousarray(b).view(np.uint32)
    if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[1]:
        raise ValueError(
            f"packed stacks must share the word width, got {a.shape} "
            f"vs {b.shape}")
    inter = a[:, None, :] & b[None, :, :]
    return np.bitwise_count(inter).sum(axis=-1, dtype=np.int64)


def packed_union_delta(
    new_masks: jax.Array,
    old_masks: jax.Array,
    *,
    bw: int = 512,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Fused (union, delta) over packed (k, W) int32 words.

    Pads W to a multiple of ``bw`` and k to the int32 sublane height (both
    lattice ops map zero words to zero words, so padding is exact), then
    dispatches the Pallas kernel (interpret mode off-TPU) or the jnp
    fallback.
    """
    if not use_kernel:
        return new_masks | old_masks, new_masks & ~old_masks
    if interpret is None:
        interpret = not _on_tpu()
    k, W = new_masks.shape
    bw_ = min(bw, max(128, 128 * ((W + 127) // 128)))
    pk = (-k) % 8
    pw = (-W) % bw_
    new_p = jnp.pad(new_masks, [(0, pk), (0, pw)])
    old_p = jnp.pad(old_masks, [(0, pk), (0, pw)])
    union, delta = packed_union_delta_kernel(new_p, old_p, bw=bw_,
                                             interpret=interpret)
    return union[:k, :W], delta[:k, :W]


def refine_sweep_chunk(
    tile_words: jax.Array,  # (k, cw) int32 packed need bits of one V chunk
    prev: jax.Array,        # (C,) int32 entering assignments, C == 32·cw
    cost: jax.Array,        # (k,) int32 Alg 2 cost vector
    *,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Fused Algorithm 2 chunk sweep → (cost' (k,), parts (C,)).

    Pads k to the int32 sublane height with zero need words (a padding
    partition needs nothing, so it is never picked and its cost row is
    sliced away) and dispatches the Pallas kernel (interpret mode off-TPU)
    or the jnp oracle.  Lane alignment of ``cw`` is the caller's choice —
    use 32·cw ≥ 4096 chunks for real-TPU runs.
    """
    k, cw = tile_words.shape
    C = cw * 32
    if not use_kernel:
        cost_out, parts = refine_sweep_ref(tile_words, prev, cost)
        return cost_out, parts
    if interpret is None:
        interpret = not _on_tpu()
    pk = (-k) % 8
    words_p = jnp.pad(tile_words, [(0, pk), (0, 0)])
    cost_p = jnp.pad(cost, [(0, pk)])
    parts, cost_out = refine_sweep_kernel(
        words_p, prev.reshape(1, C), cost_p.reshape(k + pk, 1),
        interpret=interpret)
    return cost_out[:k, 0], parts[0]


def _gather_row_cols(
    indptr: np.ndarray,
    indices: np.ndarray,
    rows: np.ndarray | None,
) -> tuple[int, np.ndarray, np.ndarray, np.ndarray]:
    """Gather the CSR edge array in (optionally permuted) row order.

    Returns (n, lens, row_ids, cols): per-edge destination row ids and V
    columns, fully vectorized — the global position of edge e is
    start-of-its-row + offset-within-row.
    """
    indptr = np.asarray(indptr, dtype=np.int64)
    indices = np.asarray(indices, dtype=np.int64)
    if rows is None:
        n = indptr.shape[0] - 1
        lens = np.diff(indptr)
        cols = indices
    else:
        rows = np.asarray(rows, dtype=np.int64)
        n = rows.shape[0]
        lens = indptr[rows + 1] - indptr[rows]
        total = int(lens.sum())
        ends = np.cumsum(lens)
        offs = np.arange(total, dtype=np.int64) - np.repeat(ends - lens, lens)
        cols = indices[np.repeat(indptr[rows], lens) + offs]
    row_ids = np.repeat(np.arange(n, dtype=np.int64), lens)
    return n, lens, row_ids, cols


def pack_bitmask_csr(
    indptr: np.ndarray,
    indices: np.ndarray,
    num_v: int,
    rows: np.ndarray | None = None,
) -> np.ndarray:
    """Vectorized CSR → (rows, ceil(num_v/32)) int32 bitmask packing.

    Equivalent to ``pack_bitmask([indices[indptr[r]:indptr[r+1]] for r in
    rows], num_v)`` but with zero Python-level per-row work: one gather over
    the whole edge array plus one fused ``bitwise_or.at`` scatter.

    ``rows`` optionally selects/permutes rows (e.g. the random vertex order
    of the blocked partitioner); ``None`` packs all rows in CSR order.
    """
    n, _, row_ids, cols = _gather_row_cols(indptr, indices, rows)
    W = (num_v + 31) // 32
    out = np.zeros(n * W, dtype=np.uint32)
    np.bitwise_or.at(
        out,
        row_ids * W + (cols >> 5),
        (np.int64(1) << (cols & 31)).astype(np.uint32),
    )
    return out.reshape(n, W).view(np.int32)


def pack_bitmask_csr_sparse(
    indptr: np.ndarray,
    indices: np.ndarray,
    num_v: int,
    rows: np.ndarray | None = None,
    cap: int = 48,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, np.ndarray, int, int]:
    """Sparse fused packing: the bitmask as (distinct flat word index, word
    value) pairs plus per-row compact word lists, in one sorted pass.

    One argsort over (row, word) keys yields both representations without
    ever touching a dense (n, W) array — the caller chooses where (and
    whether) to densify: ``pack_bitmask_csr_compact`` scatters on the host,
    while the device scan (``pack_graph_blocks``) never densifies globally
    at all — it ships only the compact lists plus the truncated rows'
    overflow words (the entries at ``pos >= cap``) and rebuilds a block's
    (B, W) bitmask on device inside the scan where it needs one.

    Returns (uniq (nnz,) int64 flat indices into the (n, W) mask,
    wordvals (nnz,) int32, widx (n, cap) int32, vals (n, cap) int32,
    truncated (n,) bool, n, W, pos (nnz,) int64 — each entry's rank among
    its row's nonzero words).
    """
    n, _, row_ids, cols = _gather_row_cols(indptr, indices, rows)
    W = (num_v + 31) // 32
    widx = np.zeros((n, cap), dtype=np.int32)
    vals = np.zeros((n, cap), dtype=np.uint32)
    if cols.size == 0:
        return (np.zeros(0, np.int64), np.zeros(0, np.int32), widx,
                vals.view(np.int32), np.zeros(n, bool), n, W,
                np.zeros(0, np.int64))
    fw = row_ids * W + (cols >> 5)            # flat (row, word) key per edge
    bit = (np.int64(1) << (cols & 31)).astype(np.uint32)
    srt = np.argsort(fw, kind="stable")
    fs, bs = fw[srt], bit[srt]
    boundary = np.empty(fs.size, bool)
    boundary[0] = True
    np.not_equal(fs[1:], fs[:-1], out=boundary[1:])
    first = np.flatnonzero(boundary)
    uniq = fs[first]                          # distinct (row, word), sorted
    acc = np.bitwise_or.reduceat(bs, first)   # the word values
    r = uniq // W
    counts = np.bincount(r, minlength=n)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pos = np.arange(uniq.size, dtype=np.int64) - starts[r]
    keep = pos < cap
    flat = r[keep] * cap + pos[keep]
    widx.reshape(-1)[flat] = (uniq[keep] % W).astype(np.int32)
    vals.reshape(-1)[flat] = acc[keep]
    return (uniq, acc.view(np.int32), widx, vals.view(np.int32),
            counts > cap, n, W, pos)


def pack_bitmask_csr_compact(
    indptr: np.ndarray,
    indices: np.ndarray,
    num_v: int,
    rows: np.ndarray | None = None,
    cap: int = 48,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Fused ``pack_bitmask_csr`` + ``compact_row_words`` in one sorted pass.

    Returns (masks (n, W) int32, widx (n, cap) int32, vals (n, cap) int32,
    truncated (n,) bool), matching the two-step reference exactly.
    """
    uniq, wordvals, widx, vals, trunc, n, W, _ = pack_bitmask_csr_sparse(
        indptr, indices, num_v, rows=rows, cap=cap)
    masks = np.zeros(n * W, dtype=np.int32)
    masks[uniq] = wordvals
    return masks.reshape(n, W), widx, vals, trunc


def compact_row_words(
    masks: np.ndarray, cap: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-row compact word lists of a packed (N, W) bitmask.

    Returns (widx (N, cap) int32, vals (N, cap) int32, truncated (N,) bool).
    Rows with ≤ cap nonzero words are represented exactly: for any mask X,
    Σ_d popcount(vals[r, d] & X[widx[r, d]]) == popcount(masks[r] & X).
    Rows with more nonzero words keep their first ``cap`` words and are
    flagged in ``truncated`` so callers can fall back to the dense mask.
    Padding slots point at word 0 with value 0 (safe to gather, adds 0).
    """
    n = masks.shape[0]
    r, c = np.nonzero(masks)
    counts = np.bincount(r, minlength=n)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pos = np.arange(r.size, dtype=np.int64) - starts[r]
    keep = pos < cap
    widx = np.zeros((n, cap), dtype=np.int32)
    vals = np.zeros((n, cap), dtype=np.int32)
    flat = r[keep] * cap + pos[keep]
    widx.reshape(-1)[flat] = c[keep]
    vals.reshape(-1)[flat] = masks[r[keep], c[keep]]
    return widx, vals, counts > cap


def parsa_cost(
    nbr_masks: jax.Array,
    s_masks: jax.Array,
    *,
    bu: int = 256,
    bw: int = 512,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> jax.Array:
    """cost[u, i] = |N(u) \\ S_i| for packed int32 bitmasks.

    Pads U to a multiple of ``bu`` and W to a multiple of ``bw`` (zero words
    contribute zero popcount, so padding is exact), then dispatches to the
    Pallas kernel (interpret mode off-TPU) or the jnp oracle.
    """
    if interpret is None:
        interpret = not _on_tpu()
    U, W = nbr_masks.shape
    if not use_kernel:
        return parsa_cost_ref(nbr_masks, s_masks)
    bu_ = min(bu, max(8, 8 * ((U + 7) // 8)))
    bw_ = min(bw, max(128, 128 * ((W + 127) // 128)))
    pu = (-U) % bu_
    pw = (-W) % bw_
    nbr_p = jnp.pad(nbr_masks, [(0, pu), (0, pw)])
    s_p = jnp.pad(s_masks, [(0, 0), (0, pw)])
    out = parsa_cost_kernel(nbr_p, s_p, bu=bu_, bw=bw_, interpret=interpret)
    return out[:U]


def parsa_cost_select(
    nbr_masks: jax.Array,   # (B, W) int32 packed N(u)
    s_masks: jax.Array,     # (k, W) int32 packed S_i
    retired: jax.Array,     # (B,) bool — rows excluded from selection
    *,
    order: jax.Array | None = None,    # (k,) int32 → greedy-round mode
    enabled: jax.Array | None = None,  # (k,) bool slot gate (greedy mode)
    bw: int = 512,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Fused cost+select: reduce the (B, k) cost tile to per-partition
    (min, argmin) without materializing it outside VMEM.

    Independent mode (``order is None``) returns ((k,) mins, (k,) argmins)
    over unretired rows, ties to the lowest row.  Greedy mode visits columns
    in ``order`` with progressive retirement (one balanced greedy round) and
    returns ((k,) u_sel, (k,) c_sel) with u_sel = -1 / c_sel = BIG for
    inactive slots.  Bit-exact vs the ``ref.py`` oracles.
    """
    if interpret is None:
        interpret = not _on_tpu()
    B, W = nbr_masks.shape
    k = s_masks.shape[0]
    greedy = order is not None
    if enabled is None:
        enabled = jnp.ones((k,), bool)
    if not use_kernel:
        if greedy:
            return parsa_select_greedy_ref(nbr_masks, s_masks, retired,
                                           order, enabled)
        return parsa_select_ref(nbr_masks, s_masks, retired)
    bw_ = min(bw, max(128, 128 * ((W + 127) // 128)))
    pb = (-B) % 8
    pw = (-W) % bw_
    nbr_p = jnp.pad(nbr_masks, [(0, pb), (0, pw)])
    s_p = jnp.pad(s_masks, [(0, 0), (0, pw)])
    # padded rows are born retired so they never win a selection
    ret_p = jnp.pad(retired, [(0, pb)], constant_values=True)
    if greedy:
        order_in = order.astype(jnp.int32)[None, :]
    else:
        order_in = jnp.arange(k, dtype=jnp.int32)[None, :]
    enabled_in = enabled.astype(jnp.int32)[None, :]
    u_sel, c_sel = parsa_select_kernel(
        nbr_p, s_p, ret_p.astype(jnp.int32)[:, None], order_in, enabled_in,
        greedy=greedy, bw=bw_, interpret=interpret)
    if greedy:
        return u_sel[0], c_sel[0]
    return c_sel[0], u_sel[0]  # independent mode: (mins, argmins)


def sketch_cost_select(
    nbr_masks: jax.Array,   # (B, Ws) int32 packed sketched N(u)
    s_masks: jax.Array,     # (k, Ws) int32 packed sketched S_i
    retired: jax.Array,     # (B,) bool — rows excluded from selection
    *,
    order: jax.Array | None = None,    # (k,) int32 → greedy-round mode
    enabled: jax.Array | None = None,  # (k,) bool slot gate (greedy mode)
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Fused cost+select at sketched widths: the whole (B, Ws) tile VMEM
    resident in ONE grid step — no word grid, no cross-step accumulator.

    Same contract as ``parsa_cost_select`` (independent → (mins, argmins),
    greedy → (u_sel, c_sel)), bit-exact vs ``sketch_select_ref``.  Sketch
    widths padded beyond ``SKETCH_KERNEL_MAX_WORDS`` words fall back to
    the W-gridded ``parsa_cost_select`` — they no longer fit the
    single-step VMEM budget.
    """
    if interpret is None:
        interpret = not _on_tpu()
    B, W = nbr_masks.shape
    k = s_masks.shape[0]
    greedy = order is not None
    if enabled is None:
        enabled = jnp.ones((k,), bool)
    if not use_kernel:
        u_sel, c_sel = sketch_select_ref(nbr_masks, s_masks, retired,
                                         order, enabled, greedy=greedy)
        if greedy:
            return u_sel[0], c_sel[0]
        return c_sel[0], u_sel[0]  # independent mode: (mins, argmins)
    pw = (-W) % 128
    if W + pw > SKETCH_KERNEL_MAX_WORDS:
        return parsa_cost_select(nbr_masks, s_masks, retired, order=order,
                                 enabled=enabled, interpret=interpret,
                                 use_kernel=True)
    pb = (-B) % 8
    nbr_p = jnp.pad(nbr_masks, [(0, pb), (0, pw)])
    s_p = jnp.pad(s_masks, [(0, 0), (0, pw)])
    # padded rows are born retired so they never win a selection
    ret_p = jnp.pad(retired, [(0, pb)], constant_values=True)
    if greedy:
        order_in = order.astype(jnp.int32)[None, :]
    else:
        order_in = jnp.arange(k, dtype=jnp.int32)[None, :]
    enabled_in = enabled.astype(jnp.int32)[None, :]
    u_sel, c_sel = sketch_select_kernel(
        nbr_p, s_p, ret_p.astype(jnp.int32)[:, None], order_in, enabled_in,
        greedy=greedy, interpret=interpret)
    if greedy:
        return u_sel[0], c_sel[0]
    return c_sel[0], u_sel[0]  # independent mode: (mins, argmins)
