r"""TPU-native Parsa: blocked greedy over packed bitmasks (DESIGN.md §2).

The CPU algorithm's O(1) pointer updates don't map to TPU; instead we
*recompute over blocks*: a block of B candidate vertices is greedily
assigned by repeatedly picking the partition to grow (smallest size, Alg 1
line 7 / §4.1 perfect balance) and the minimum-cost unassigned vertex
within the block for it.  Block-local greedy is a sampling approximation in
exactly the sense of §4.2 (a block plays the role of a subgraph R); its
reference is the per-vertex block loop of the ``host_blocked_oracle``
backend (``blocked_partition_u_hostloop_impl``).

Dispatch model (one scan, donated carries, fused select)
--------------------------------------------------------

The pipeline is fully device-resident:

1. *Packing* — the whole permuted U is packed host-side in one vectorized
   pass over the edge array in its own row order (``pack_row_words``;
   zero Python-level per-vertex work; a sort only when some row's
   columns arrive out of order) into per-row *compact word lists* of the
   first ``cap`` nonzero words, moved as whole rows into the permuted
   block layout, plus a flat list of the *overflow words* of the rows
   that have more (row, word, value entries, with a span per block).
   No dense ``(…, W)`` array exists on the host, and none is copied to
   the device.  On the jnp path a block with no truncated row is
   greedy-assigned from its word lists alone; the kernel path, and a
   jnp-path block that holds a truncated row, rebuild the block's (B, W)
   bitmask inside the scan by a 12K-element scatter-add plus its
   overflow span (``_rebuild_nbr``).

2. *One dispatch* — ``blocked_partition_u_impl`` issues a single jitted
   ``jax.lax.scan`` over the block stack (``_partition_scan``) with the
   ``(S, sizes)`` carries donated, instead of one host dispatch per block.
   ``dispatch_counter()`` observes exactly one launch per partition call.

3. *Greedy rounds + fused select* — perfect balance makes the partition
   visit order deterministic: when partition sizes differ by at most one
   (always true here: sizes start equal and every round preserves it), the
   next k picks visit each partition exactly once — first the catch-up set
   (partitions at the current min size, in index order), then full rounds
   in plain index order.  ``_assign_block_rounds`` therefore runs
   ceil-ish(B/k) *rounds* instead of B scalar steps.  Each round selects
   one vertex per partition with progressive retirement — on TPU via the
   fused cost+select Pallas kernel (``parsa_cost_select``), which reduces
   the (B, k) cost tile to per-partition (min, argmin) inside VMEM without
   materializing it, enabling B=1024 blocks; on the jnp path
   (``use_kernel=False``) from a carried cost tile down-dated by the ≤ cap
   words of each selected vertex.  A block with no truncated row never
   leaves compact space: its rounds hold S at the block's words only,
   down-date the tile by matching word lists, and the block's commits
   reach S by one scatter after its last round.  A block that holds a
   hub row past cap takes the densified branch (``lax.cond`` per block)
   — bit-exact either way.

   Both paths produce *identical* assignments to the sequential per-vertex
   reference ``blocked_partition_u_hostloop_impl`` (property-tested),
   because a round's selections see exactly the tile state the per-vertex
   loop would: within a round each column is picked at most once,
   down-dates touch only the picked column, and cross-column interaction
   is pure retirement.

``_parallel_scan_fn`` builds the one Alg 4 program
(``_parallel_partition_scan``), a shard_map over the worker mesh: each
worker partitions its own share of the blocks against a device-local
*stale* bitmask copy; every ``merge_every`` blocks an all_gather + OR
merges the sets — the bulk-synchronous image of the parameter server's
union-push (server line 9), with τ == merge_every − 1 blocks of staleness.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import trace as _obs_trace

from ..kernels.parsa_cost import (
    BIG,
    coerce_packed_sets,
    pack_bitmask,
    pack_row_words,
    parsa_cost,
    parsa_cost_select,
    select_greedy_from_cost,
    sketch_cost_select,
)
from .bipartite import BipartiteGraph

__all__ = [
    "blocked_partition_u_impl",
    "blocked_partition_u_hostloop_impl",
    "parallel_blocked_partition_u_impl",
    "pack_graph_blocks",
    "PackedBlocks",
    "dispatch_counter",
    "reset_dispatch_counts",
    "annotate_dispatch",
    "DispatchEvent",
    "DispatchLog",
    "resolve_worker_devices",
]

# Dispatch accounting: one entry per *host→device pipeline launch*;
# blocked_partition_u_impl bumps it exactly once per call regardless of
# graph size (O(1)-dispatch invariant, asserted in
# tests/test_jax_partition.py).  Counts are observed through the
# ``dispatch_counter()`` context manager so concurrent tests can't leak
# counts into each other the way the old module-global dict did.


@dataclasses.dataclass
class DispatchEvent:
    """One labeled pipeline launch: phase, donated-carry bytes, extras
    (jit cache hit/miss, worker id, ...)."""

    phase: str
    nbytes: int = 0
    meta: dict = dataclasses.field(default_factory=dict)


class DispatchLog(dict):
    """The dict ``dispatch_counter`` yields, upgraded with labeled
    per-launch records.

    Still a plain ``phase -> count`` mapping (every existing
    ``counts["partition_scan"] == 1`` / ``counts == {...}`` assert keeps
    working); ``.records`` carries the ordered ``DispatchEvent`` stream
    behind those totals."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.records: list[DispatchEvent] = []

    def bytes_by_phase(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for r in self.records:
            out[r.phase] = out.get(r.phase, 0) + r.nbytes
        return out


_ACTIVE_COUNTERS: list[DispatchLog] = []


def _count_dispatch(name: str, nbytes: int = 0, **meta) -> None:
    for counts in _ACTIVE_COUNTERS:
        counts[name] = counts.get(name, 0) + 1
        counts.records.append(DispatchEvent(name, int(nbytes),
                                            dict(meta)))
    _obs_trace.dispatch_instant(name, nbytes=nbytes, meta=meta or None)


def annotate_dispatch(**meta) -> None:
    """Attach after-the-fact labels (jit ``cache_miss`` is only knowable
    once the call returns) to the launch just counted."""
    for counts in _ACTIVE_COUNTERS:
        if counts.records:
            counts.records[-1].meta.update(meta)
    _obs_trace.annotate_last_instant(**meta)


@contextlib.contextmanager
def dispatch_counter():
    """Yield a fresh ``{"partition_scan": 0, ...}`` log (a dict subclass;
    see ``DispatchLog``) that records only the pipeline launches issued
    inside this ``with`` block."""
    counts = DispatchLog({"partition_scan": 0})
    _ACTIVE_COUNTERS.append(counts)
    try:
        yield counts
    finally:
        # remove by identity: equal-valued dicts from nested scopes must not
        # deregister each other
        for i, c in enumerate(_ACTIVE_COUNTERS):
            if c is counts:
                del _ACTIVE_COUNTERS[i]
                break


def reset_dispatch_counts() -> None:
    """Zero every active counter (test-isolation helper)."""
    for counts in _ACTIVE_COUNTERS:
        for key in counts:
            counts[key] = 0
        counts.records.clear()


class PackedBlocks(NamedTuple):
    """Device-ready blocked packing of (a permutation of) U.

    The dense (B, W) bitmask of a block is *not* stored — it is rebuilt on
    device inside the scan from the compact word lists (a 12K-element
    scatter-add per block), so the packing ships ~cap words per vertex
    instead of W.  The rare rows with more than ``cap`` nonzero words
    (``trunc``) keep their first ``cap`` words in ``widx``/``vals``; the
    words past those, their *overflow words*, ride in one flat list of
    (block-local row, word index, word value) entries shared by all
    blocks, which block b reads from ``overflow_spans[b]``.  Entries past
    the last span are ``(0, 0, 0)`` padding: scatter-adding one is a no-op.
    """

    valid: np.ndarray     # (n_blocks, B) bool — False for padding rows
    widx: np.ndarray      # (n_blocks, B, cap) int32 nonzero-word indices
    vals: np.ndarray      # (n_blocks, B, cap) int32 word values at widx
    trunc: np.ndarray     # (n_blocks, B) bool — row has > cap nonzero words
    overflow_spans: np.ndarray  # (n_blocks, 2) int32 [start, stop) of each
                                #   block's entries in overflow_words
    overflow_words: np.ndarray  # (3, L) int32 rows: block-local row, word
                                #   index, word value; L a power of two
    order: np.ndarray     # (num_u,) int64 — global vertex id per packed row
    sorted: bool          # every row's words arrived ascending: no sort ran


# the per-block arrays ``_partition_scan`` takes, in its argument order
_SCAN_BLOCK_FIELDS = ("valid", "widx", "vals", "trunc", "overflow_spans",
                      "overflow_words")


def _upload_blocks(packed: PackedBlocks) -> tuple[jax.Array, ...]:
    """The six per-block arrays ``_partition_scan`` takes
    (``_SCAN_BLOCK_FIELDS``), each put on the default device by
    ``jnp.asarray``: the one place the scan's block layout is spelled out
    for a one-chip call (``_place_parallel_blocks`` places the same six on
    a worker mesh)."""
    return tuple(jnp.asarray(getattr(packed, f)) for f in _SCAN_BLOCK_FIELDS)


def _overflow_slots(words: int, floor: int, slots: int) -> int:
    """Capacity L of the overflow-word list for a feed of ``words``
    entries: ``slots`` (the capacity already compiled) while the feed fits
    in it, else the smallest power of two ≥ 2 × max(words, floor) — so a
    session's L only grows, and only on a feed that would not fit."""
    need = int(max(words, floor))
    if slots and need <= slots:
        return slots
    return 1 << max(2 * need - 1, 0).bit_length()


def pack_graph_blocks(
    graph: BipartiteGraph,
    block: int,
    order: np.ndarray | None = None,
    cap: int = 48,
    tb_pad: int | None = None,
    min_slots: int = 0,
) -> PackedBlocks:
    """Pack all of U (in ``order``) into padded (n_blocks, B, …) stacks.

    Fully vectorized (``pack_row_words``): one pass over the edge array
    in the graph's own row order yields each row's compact word list and
    the overflow words of the truncated rows (each row's nonzero words
    past its first ``cap``), and ``order`` moves whole rows into the block
    layout; no edge is gathered into permuted order.  Rows whose columns
    arrive in ascending word order need no sort; a graph with a row that
    does not is sorted by (row, word) first, to the same output.  No
    per-vertex Python work, and no dense (…, W) array on the host.

    The overflow list holds ``_overflow_slots(words, floor, min_slots)``
    entries, ``floor`` = ``tb_pad · cap · n_blocks`` (as many words as
    ``tb_pad`` fully truncated rows a block would spill, counted at cap),
    or 0 when ``tb_pad`` is None.  Padding entries are ``(0, 0, 0)``, so
    the output is bit-equivalent at any capacity — the point is shape
    stability: streaming feeds re-pack same-sized chunks whose overflow
    count jitters with the data, and a capacity that holds (``min_slots``,
    the session's high-water mark) keeps every feed on the
    already-compiled scan.  ``sorted`` says whether the pass ran without
    its sort.
    """
    n = graph.num_u
    if order is None:
        order = np.arange(n, dtype=np.int64)
    order = np.asarray(order, dtype=np.int64)
    n_blocks = max(1, -(-n // block))
    rw = pack_row_words(graph.u_indptr, graph.u_indices, cap, rows=order,
                        n_out=n_blocks * block)
    valid = (np.arange(n_blocks * block) < n).reshape(n_blocks, block)
    # overflow words: in (row, word) order, so each block's are contiguous
    words = rw.over_rows.size
    floor = 0 if tb_pad is None else tb_pad * cap * n_blocks
    L = _overflow_slots(words, floor, min_slots)
    overflow_words = np.zeros((3, L), np.int32)
    overflow_words[0, :words] = rw.over_rows % block
    overflow_words[1, :words] = rw.over_words
    overflow_words[2, :words] = rw.over_vals
    stops = np.cumsum(np.bincount(rw.over_rows // block, minlength=n_blocks))
    overflow_spans = np.stack(
        [np.concatenate([[0], stops[:-1]]), stops], axis=1).astype(np.int32)
    return PackedBlocks(
        valid=valid,
        widx=rw.widx.reshape(n_blocks, block, cap),
        vals=rw.vals.reshape(n_blocks, block, cap),
        trunc=rw.trunc.reshape(n_blocks, block),
        overflow_spans=overflow_spans,
        overflow_words=overflow_words,
        order=order,
        sorted=rw.sorted,
    )


# --------------------------------------------------------------------------
# Sequential per-vertex reference (the seed implementation, kept as the
# parity oracle the tests hold the scan to).
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("k", "use_kernel", "interpret"))
def _assign_block(
    nbr: jax.Array,        # (B, W) int32 packed N(u)
    s_masks: jax.Array,    # (k, W) int32 packed S_i
    sizes: jax.Array,      # (k,) int32 |U_i|
    valid: jax.Array | None = None,  # (B,) bool — padding rows, if any
    *,
    k: int,
    use_kernel: bool = True,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Greedy-assign every vertex in the block, one scalar step at a time.

    Returns (parts, S', sizes').  This is the sequential reference: B scan
    steps, each down-dating one column of the (B, k) cost tile.  With
    ``valid=None`` the loop is exactly the seed implementation (the parity
    oracle — every row is assigned).  Passing ``valid`` marks padding rows
    unpickable so a ragged block doesn't leak phantom picks into ``sizes``
    or skew the assignment order.
    """
    B, W = nbr.shape
    cost = parsa_cost(nbr, s_masks, use_kernel=use_kernel, interpret=interpret)  # (B, k)
    if valid is not None:
        cost = jnp.where(valid[:, None], cost, BIG)

    def step(state, _):
        cost, s_masks, sizes, parts = state
        i = jnp.argmin(sizes)  # partition to grow (perfect balance)
        u = jnp.argmin(cost[:, i])  # cheapest unassigned vertex in block
        if valid is None:
            active = jnp.bool_(True)
        else:
            # once only retired/padding rows remain their cost sits near
            # BIG (down-dates can drift it a little); stop assigning then
            active = cost[u, i] < BIG // 2
        mask_u = jnp.where(active, nbr[u], 0)
        delta = mask_u & ~s_masks[i]
        new_si = s_masks[i] | mask_u
        # down-date column i only: cost never increases (§4.1)
        dec = jax.lax.population_count(nbr & delta[None, :]).astype(jnp.int32).sum(-1)
        cost = cost.at[:, i].add(-dec)
        cost = cost.at[u, :].set(BIG)  # retire u from the block
        s_masks = s_masks.at[i].set(new_si)
        sizes = sizes.at[i].add(active.astype(jnp.int32))
        parts = parts.at[u].set(
            jnp.where(active, i.astype(jnp.int32), parts[u]))
        return (cost, s_masks, sizes, parts), None

    parts0 = jnp.full((B,), -1, jnp.int32)
    (cost, s_masks, sizes, parts), _ = jax.lax.scan(
        step, (cost, s_masks, sizes, parts0), None, length=B
    )
    return parts, s_masks, sizes


# --------------------------------------------------------------------------
# Rounds-based device-resident block greedy.
# --------------------------------------------------------------------------
# overflow entries scatter-added per while-loop step of ``_rebuild_nbr``
_OVERFLOW_PAGE = 1024


def _rebuild_nbr(widx: jax.Array, vals: jax.Array, span: jax.Array,
                 overflow: jax.Array, W: int) -> jax.Array:
    """Densify a block's (B, W) bitmask from its compact word lists and
    its ``span`` = [start, stop) of the ``overflow`` (3, L) entries.

    Padding slots all target word 0 with value 0, so scatter-*add* is
    duplicate-safe; a truncated row's overflow words are distinct from its
    first ``cap`` words, so adding them sets them.  The span is read in
    fixed pages of ``dynamic_slice``d entries, so an empty span costs one
    loop test; entries of a page outside the span add 0."""
    B, _ = widx.shape
    nbr = jnp.zeros((B, W), jnp.int32)
    nbr = nbr.at[jnp.arange(B, dtype=jnp.int32)[:, None], widx].add(vals)
    L = overflow.shape[1]
    page = min(_OVERFLOW_PAGE, L)
    lane = jnp.arange(page, dtype=jnp.int32)
    start, stop = span[0], span[1]

    def add_page(carry):
        off, nbr = carry
        at = jnp.minimum(off, L - page)   # where dynamic_slice would clamp
        row, word, val = jax.lax.dynamic_slice(overflow, (0, at), (3, page))
        idx = at + lane
        val = jnp.where((idx >= off) & (idx < stop), val, 0)
        return off + page, nbr.at[row, word].add(val)

    _, nbr = jax.lax.while_loop(lambda c: c[0] < stop, add_page,
                                (start, nbr))
    return nbr


def _full_rounds(B: int, k: int) -> int:
    """Rounds after the catch-up round: it may assign as little as one
    vertex."""
    return -(-(B - 1) // k)


def _block_rounds(tile0, select, commit, valid, s_masks, sizes, *, k):
    """Greedy-assign a block in balanced rounds, however its rows are
    held.  Returns (parts, S', sizes').

    ``s_masks`` is whatever the caller holds the sets as.
    ``select(tile, s_masks, retired, ord_, en)`` picks one row per slot
    as ``select_greedy_from_cost`` does; ``commit(s_masks, u_safe, act,
    ord_, inv)`` ORs the picked rows into their partitions' sets and
    returns ``(S', dec)``, ``dec`` the (B, k) down-date of the cost tile
    in slot order against the sets before the commit, or None where no
    tile is carried.  ``inv`` maps partitions to slots (None: identity).
    """
    B = valid.shape[0]
    iota_b = jnp.arange(B, dtype=jnp.int32)
    iota_k = jnp.arange(k, dtype=jnp.int32)

    def round_body(state, ord_, en):
        """One greedy round.  ord_ = None means the identity visit order
        0..k-1 (every round after the catch-up), which skips all the
        slot→partition permutation gathers."""
        tile, s_masks, sizes, parts, retired = state
        u_sel, c_sel = select(tile, s_masks, retired, ord_, en)
        act = c_sel < BIG
        u_safe = jnp.where(act, u_sel, 0)
        inv = None if ord_ is None else jnp.argsort(ord_)
        # commit: S_i |= N(u), sizes, parts, retirement, tile down-date
        s_masks, dec = commit(s_masks, u_safe, act, ord_, inv)
        match = (iota_b[:, None] == u_sel[None, :]) & act[None, :]  # (B, k)
        assigned = match.any(axis=1)
        retired = retired | assigned
        if ord_ is None:
            sizes = sizes + act.astype(jnp.int32)
            col_id = (match * iota_k[None, :]).sum(axis=1).astype(jnp.int32)
        else:
            sizes = sizes + act[inv].astype(jnp.int32)
            col_id = (match * ord_[None, :]).sum(axis=1).astype(jnp.int32)
            dec = None if dec is None else dec[:, inv]
        if dec is not None:
            tile = tile - dec
        parts = jnp.where(assigned, col_id, parts)
        return tile, s_masks, sizes, parts, retired

    # catch-up round (partition visit order = stable argsort of sizes,
    # only the min-sized partitions may pick), then full identity rounds
    parts0 = jnp.full((B,), -1, jnp.int32)
    ord0 = jnp.argsort(sizes, stable=True).astype(jnp.int32)
    en0 = sizes[ord0] == jnp.min(sizes)
    state = round_body((tile0, s_masks, sizes, parts0, ~valid), ord0, en0)
    en_all = jnp.ones((k,), bool)

    def full_round(state, _):
        return round_body(state, None, en_all), None

    (_, s_masks, sizes, parts, _), _ = jax.lax.scan(
        full_round, state, None, length=_full_rounds(B, k))
    return parts, s_masks, sizes


def _select_from_tile(tile, s_masks, retired, ord_, en):
    return select_greedy_from_cost(tile, retired, ord_, en)


def _or_rows(s_masks, rows, act, inv):
    """S_i |= the dense row picked for partition i (``rows`` in slot
    order, inactive slots add nothing)."""
    add = jnp.where(act[:, None], rows, 0)
    return s_masks | (add if inv is None else add[inv])


def _dense_block_rounds(valid, widx, vals, trunc, span, overflow, s_masks,
                        sizes, *, k):
    """The jnp greedy of a block that holds a truncated row: its (B, W)
    bitmask is densified (``_rebuild_nbr``), the initial tile is the dense
    product, and a round down-dates the tile densely only when it picks a
    truncated row (``lax.cond``; otherwise through the picked rows'
    compact words, gathered from the transposed mask)."""
    nbr = _rebuild_nbr(widx, vals, span, overflow, s_masks.shape[1])
    B, cap = widx.shape
    # Both sparse gathers run over *transposed* operands so each gathered
    # index pulls a contiguous row instead of a strided column — XLA CPU's
    # element gather was the down-date bottleneck (~45% of scan time).
    nbr_t = nbr.T                                          # (W, B)
    tile0 = parsa_cost(nbr, s_masks, use_kernel=False)

    def commit(s_masks, u_safe, act, ord_, inv):
        sel_nbr = nbr[u_safe]                              # (k, W)
        # Down-date values in compact space: delta_j's nonzero words
        # are a subset of the selected vertex's word list, so gather S
        # (pre-update) at widx[u_j] instead of materializing delta
        # full-width.  Padding slots carry vals == 0 → contribute 0.
        d_widx = widx[u_safe]                              # (k, cap)
        if ord_ is None:
            s_at = jnp.take_along_axis(s_masks, d_widx, axis=1)
        else:
            s_at = s_masks[ord_[:, None], d_widx]
        d_vals = jnp.where(act[:, None], vals[u_safe] & ~s_at, 0)

        def sparse_dec(_):
            g = nbr_t[d_widx.reshape(-1)].reshape(k, cap, B)
            return jax.lax.population_count(
                g & d_vals[:, :, None]).astype(jnp.int32).sum(1).T

        def dense_dec(_):
            s_cols = s_masks if ord_ is None else s_masks[ord_]
            delta = jnp.where(act[:, None], sel_nbr & ~s_cols, 0)
            return jax.lax.population_count(
                nbr[:, None, :] & delta[None]).astype(jnp.int32).sum(-1)

        any_trunc = jnp.any(act & trunc[u_safe])
        dec = jax.lax.cond(any_trunc, dense_dec, sparse_dec, None)
        return _or_rows(s_masks, sel_nbr, act, inv), dec

    return _block_rounds(tile0, _select_from_tile, commit, valid, s_masks,
                         sizes, k=k)


def _compact_block_rounds(valid, widx, vals, s_masks, sizes, *, k):
    """The jnp greedy of a block with no truncated row, in compact space:
    its compact word lists are its rows whole, so no (B, W) or (W, B)
    array is built.  The rounds hold S only at the block's words, in the
    word lists' (k, cap, B) layout: ``s_at[i, c, v]`` is S_i at word
    ``widx[v, c]``.  A round takes the picked rows' fresh bits from it,
    delta_j = N(u_j) & ~S_j on u_j's ≤ cap words, and matches word
    lists: ``hit[j, c, v]`` is delta_j at word ``widx[v, c]``, looked up
    among u_j's words one slot at a time.  The tile's down-date is
    dec[v, j] = Σ_c popcount(vals[v, c] & hit[j, c, v]), and the commit
    is ``s_at |= hit``.  Each round's (word, fresh bits) pairs are logged
    and scatter-added into S once, after the last round (on a v5e one
    scatter of the whole log costs about two of a round's).  Add is OR
    there: a partition's fresh bits are disjoint from S and from each
    other's, a row's words are distinct, and padding slots add 0 at word
    0."""
    B, cap = widx.shape
    iota_b = jnp.arange(B, dtype=jnp.int32)
    iota_k = jnp.arange(k, dtype=jnp.int32)
    widx_t, vals_t = widx.T, vals.T                        # (cap, B)
    s_at = s_masks[:, widx_t]                              # (k, cap, B)
    # initial tile cost[v, i] = deg(v) − |N(v) ∩ S_i|
    deg = jax.lax.population_count(vals).astype(jnp.int32).sum(-1)
    tile0 = deg[:, None] - jax.lax.population_count(
        s_at & vals_t).astype(jnp.int32).sum(1).T
    log = jnp.zeros((1 + _full_rounds(B, k), 2, k, cap), jnp.int32)

    def commit(state, u_safe, act, ord_, inv):
        s_at, log, r = state
        own = s_at if ord_ is None else s_at[ord_]         # slot order
        s_row = jnp.where(iota_b == u_safe[:, None, None], own, 0).sum(-1)
        d_widx = widx[u_safe]                              # (k, cap)
        d_vals = jnp.where(act[:, None], vals[u_safe] & ~s_row, 0)
        hit = jnp.zeros((k, cap, B), jnp.int32)
        for c in range(cap):
            hit = hit | jnp.where(widx_t == d_widx[:, c, None, None],
                                  d_vals[:, c, None, None], 0)
        dec = jax.lax.population_count(
            vals_t & hit).astype(jnp.int32).sum(1).T       # (B, k)
        entry = jnp.stack([d_widx, d_vals])                # (2, k, cap)
        if inv is not None:
            hit, entry = hit[inv], entry[:, inv]
        log = jax.lax.dynamic_update_index_in_dim(log, entry, r, 0)
        return (s_at | hit, log, r + 1), dec

    parts, (_, log, _), sizes = _block_rounds(
        tile0, _select_from_tile, commit, valid,
        (s_at, log, jnp.int32(0)), sizes, k=k)
    part = jnp.broadcast_to(iota_k[:, None], (k, cap))
    return parts, s_masks.at[part, log[:, 0]].add(log[:, 1]), sizes


def _assign_block_rounds(
    valid: jax.Array,     # (B,) bool
    widx: jax.Array,      # (B, cap) int32
    vals: jax.Array,      # (B, cap) int32
    trunc: jax.Array,     # (B,) bool
    span: jax.Array,      # (2,) int32 [start, stop) into overflow
    overflow: jax.Array,  # (3, L) int32 overflow-word entries
    s_masks: jax.Array,   # (k, W) int32
    sizes: jax.Array,     # (k,) int32
    *,
    k: int,
    use_kernel: bool,
    interpret: bool | None,
    sketch: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Greedy-assign a block in balanced rounds.  Returns (parts, S', sizes').

    Identical output to ``_assign_block`` whenever sizes differ by ≤ 1 at
    entry (property-tested).  On the kernel path the cost tile lives only
    in VMEM (fused cost+select over the densified block).  On the jnp path
    the tile is carried and down-dated, and the block's rows are held as
    its input shows they must be: a block with no truncated row runs in
    compact space (``_compact_block_rounds``), one with a truncated row
    over its densified mask (``_dense_block_rounds``), chosen on device.

    ``sketch=True`` marks the packed width as a sketched domain
    (``repro.sketch``): the kernel path switches to the gridless
    VMEM-resident ``sketch_cost_select`` (the whole block tile fits in one
    grid step at sketch widths).  The jnp path is width-agnostic — the
    same integer program at a smaller W — so the flag changes nothing
    there, which is precisely why the exact-parity regression holds.
    """
    if not use_kernel:
        dense = functools.partial(_dense_block_rounds, valid, widx, vals,
                                  trunc, span, overflow, k=k)
        compact = functools.partial(_compact_block_rounds, valid, widx,
                                    vals, k=k)
        return jax.lax.cond(trunc.any(), dense, compact, s_masks, sizes)
    # Fused cost+select recomputes the (B, k) tile in VMEM each round and
    # reduces it in the same pass — no tile is carried at all, so the
    # state holds a placeholder.
    nbr = _rebuild_nbr(widx, vals, span, overflow, s_masks.shape[1])
    select_fn = sketch_cost_select if sketch else parsa_cost_select
    iota_k = jnp.arange(k, dtype=jnp.int32)

    def select(_tile, s_masks, retired, ord_, en):
        return select_fn(nbr, s_masks, retired,
                         order=iota_k if ord_ is None else ord_, enabled=en,
                         use_kernel=True, interpret=interpret)

    def commit(s_masks, u_safe, act, ord_, inv):
        return _or_rows(s_masks, nbr[u_safe], act, inv), None

    return _block_rounds(jnp.zeros((1, 1), jnp.int32), select, commit,
                         valid, s_masks, sizes, k=k)


@functools.partial(
    jax.jit,
    static_argnames=("k", "use_kernel", "interpret", "sketch"),
    donate_argnums=(6, 7),
)
def _partition_scan(
    valid: jax.Array,     # (n_blocks, B) bool
    widx: jax.Array,      # (n_blocks, B, cap) int32
    vals: jax.Array,      # (n_blocks, B, cap) int32
    trunc: jax.Array,     # (n_blocks, B) bool
    overflow_spans: jax.Array,  # (n_blocks, 2) int32
    overflow_words: jax.Array,  # (3, L) int32 — not scanned: all blocks'
    s_masks: jax.Array,   # (k, W) int32 — donated
    sizes: jax.Array,     # (k,) int32 — donated
    *,
    k: int,
    use_kernel: bool,
    interpret: bool | None,
    sketch: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The whole partition as ONE XLA dispatch: scan blocks, carry (S, sizes)."""

    def per_block(carry, xs):
        s, sz = carry
        parts, s, sz = _assign_block_rounds(
            *xs, overflow_words, s, sz, k=k, use_kernel=use_kernel,
            interpret=interpret, sketch=sketch)
        return (s, sz), parts

    (s_masks, sizes), parts = jax.lax.scan(
        per_block, (s_masks, sizes),
        (valid, widx, vals, trunc, overflow_spans))
    return parts, s_masks, sizes


def blocked_partition_u_impl(
    graph: BipartiteGraph,
    k: int,
    block: int = 256,
    init_sets: np.ndarray | None = None,
    use_kernel: bool = True,
    interpret: bool | None = None,
    seed: int = 0,
    cap: int = 48,
    as_numpy: bool = True,
    timings: dict | None = None,
    sketch: bool = False,
) -> tuple[np.ndarray, np.ndarray]:
    """Device-resident blocked greedy partition.
    Returns (parts_u, final packed s_masks (k, W) int32).

    Packs the entire permuted U once (vectorized, compact word lists —
    ~cap words per vertex instead of W; the dense (B, W) bitmask of each
    block is rebuilt on device inside the scan, so a gigabyte-scale stack
    never exists on either side) and issues one jitted scan over the block
    stack — O(1) XLA dispatches per call.  The final neighbor-set bitmasks
    come back with the scan carry, so the device path supports warm-start /
    incremental repartitioning with full parity to the host path.

    ``init_sets`` may be dense (k, |V|) bool or already-packed (k, W) int32
    words (the ``PartitionResult.s_masks`` fast path — no dense detour).
    ``as_numpy=False`` keeps both outputs as device arrays so the V-refine
    and metrics phases can consume them without a host round trip.
    A ``timings`` dict, when given, receives the host-side ``"pack"``
    seconds so the facade can report packing separately from the scan.
    """
    t_pack = time.perf_counter()
    W = (graph.num_v + 31) // 32
    if init_sets is None:
        s_masks = jnp.zeros((k, W), jnp.int32)
    else:
        s_masks = jnp.asarray(coerce_packed_sets(init_sets, graph.num_v))
    sizes = jnp.zeros((k,), jnp.int32)
    rng = np.random.default_rng(seed)
    order = rng.permutation(graph.num_u)
    packed = pack_graph_blocks(graph, block, order=order, cap=cap)
    if timings is not None:
        timings["pack"] = time.perf_counter() - t_pack
    _count_dispatch("partition_scan",
                    nbytes=int(s_masks.nbytes) + int(sizes.nbytes),
                    k=k, blocks=int(packed.valid.shape[0]))
    parts_blocks, s_out, _ = _partition_scan(
        *_upload_blocks(packed), s_masks, sizes,
        k=k, use_kernel=use_kernel, interpret=interpret, sketch=sketch)
    if not as_numpy:
        flat = parts_blocks.reshape(-1)[: graph.num_u]
        parts = jnp.zeros((graph.num_u,), jnp.int32).at[
            jnp.asarray(order)].set(flat)
        return parts, s_out
    flat = np.asarray(parts_blocks).reshape(-1)[: graph.num_u]
    parts = np.full(graph.num_u, -1, np.int32)
    parts[order] = flat
    return parts, np.asarray(s_out)


def blocked_partition_u_hostloop_impl(
    graph: BipartiteGraph,
    k: int,
    block: int = 256,
    init_sets: np.ndarray | None = None,
    use_kernel: bool = True,
    interpret: bool | None = None,
    seed: int = 0,
) -> tuple[np.ndarray, np.ndarray]:
    """The seed implementation: per-block Python packing + one dispatch per
    block + per-vertex greedy.  Kept verbatim as the test reference that
    the single-dispatch pipeline must match bit for bit.
    Returns (parts_u, final packed s_masks)."""
    W = (graph.num_v + 31) // 32
    if init_sets is None:
        s_masks = jnp.zeros((k, W), jnp.int32)
    else:
        s_masks = jnp.asarray(coerce_packed_sets(init_sets, graph.num_v))
    sizes = jnp.zeros((k,), jnp.int32)
    rng = np.random.default_rng(seed)
    order = rng.permutation(graph.num_u)
    parts = np.full(graph.num_u, -1, np.int32)
    for start in range(0, graph.num_u, block):
        ids = order[start : start + block]
        masks = pack_bitmask([graph.neighbors(int(u)) for u in ids], graph.num_v)
        p, s_masks, sizes = _assign_block(
            jnp.asarray(masks), s_masks, sizes,
            k=k, use_kernel=use_kernel, interpret=interpret,
        )
        parts[ids] = np.asarray(p)
    return parts, np.asarray(s_masks)


def _pad_block_stack(packed: PackedBlocks, n_total: int) -> PackedBlocks:
    """Append ``n_total - n_blocks`` empty blocks (all rows padding: valid
    False, an empty overflow span) so a block stack divides evenly into
    per-worker shards and merge groups.  Empty blocks assign nothing and
    leave (S, sizes) untouched, so trailing padding is parity-safe."""
    nb = packed.valid.shape[0]
    if n_total == nb:
        return packed
    e = n_total - nb

    def pad0(a):
        return np.pad(a, [(0, e)] + [(0, 0)] * (a.ndim - 1))

    return packed._replace(
        valid=pad0(packed.valid),
        widx=pad0(packed.widx),
        vals=pad0(packed.vals),
        trunc=pad0(packed.trunc),
        overflow_spans=pad0(packed.overflow_spans),
    )


_WORKER_AXIS = "parsa_workers"


@functools.cache
def _worker_mesh(devices: tuple):
    """The one-axis mesh of Alg 4's workers, one worker per device."""
    from jax.sharding import Mesh

    return Mesh(np.asarray(devices), (_WORKER_AXIS,))


def _replicate_on_workers(x, devices: tuple) -> jax.Array:
    """``x`` replicated on every worker device of the mesh (no copy where
    it already is): how the live ``(S, sizes)`` of an Alg 4 stream stay
    on the mesh from feed to feed, so the scan's donation holds."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.device_put(x, NamedSharding(_worker_mesh(devices), P()))


@functools.cache
def _parallel_scan_fn(devices, k: int, merge_every: int, use_kernel: bool,
                      interpret: bool | None, sketch: bool = False):
    """Build (and cache) the jitted shard_map program for one worker mesh,
    named ``_parallel_partition_scan`` (``jit__parallel_partition_scan``
    in a profiler trace).

    Each device scans its (n_super, merge_every, B, …) block stack (the
    overflow-word list, indexed by each block's span, is replicated)
    against a device-local *stale* copy of the packed (k, W) server sets;
    after every ``merge_every`` blocks the shards merge by all_gather +
    lattice OR on uint32 words (the bulk-synchronous image of the Alg 4
    server union-push, τ ≡ merge_every − 1 blocks of staleness) and sizes
    by psum of the local deltas.  The (S, sizes) carries are donated and
    come out replicated on the mesh, so nothing round-trips through the
    host, or through one chip, between merges or between feeds.  Also
    returns the total number of changed words pushed across all merges
    (the delta-encoded worker→server traffic of Alg 4 worker line 9).
    """
    from jax.sharding import PartitionSpec as P

    axis = _WORKER_AXIS

    def _parallel_partition_scan(valid, widx, vals, trunc, spans, overflow,
                                 s_masks, sizes):
        # shard_map leaves the sharded leading axis in place with local
        # extent 1 — drop it, then group blocks into merge rounds.
        valid, widx, vals, trunc, spans = (
            x[0] for x in (valid, widx, vals, trunc, spans))
        nb = valid.shape[0]
        n_super = nb // merge_every

        def group(x):
            return x.reshape((n_super, merge_every) + x.shape[1:])

        def per_block(carry, xs):
            s, sz = carry
            parts, s, sz = _assign_block_rounds(
                *xs, overflow, s, sz, k=k, use_kernel=use_kernel,
                interpret=interpret, sketch=sketch)
            return (s, sz), parts

        def super_step(carry, xs):
            s_global, sz_global, pushed = carry
            # local greedy over merge_every blocks against the stale copy
            (s_local, sz_local), parts = jax.lax.scan(
                per_block, (s_global, sz_global), xs)
            # worker push is delta-encoded: count the changed words
            pushed = pushed + jnp.count_nonzero(
                s_local & ~s_global).astype(jnp.int32)
            # server union-push: OR-merge the neighbor sets across workers,
            # and psum the size *deltas* onto the shared pre-merge totals
            gathered = jax.lax.all_gather(s_local, axis)
            s_merged = jax.lax.reduce(
                gathered, jnp.int32(0), jax.lax.bitwise_or, dimensions=(0,))
            sz_merged = sz_global + jax.lax.psum(sz_local - sz_global, axis)
            return (s_merged, sz_merged, pushed), parts

        (s_masks, sizes, pushed), parts = jax.lax.scan(
            super_step, (s_masks, sizes, jnp.int32(0)),
            tuple(group(x) for x in (valid, widx, vals, trunc, spans)))
        pushed = jax.lax.psum(pushed, axis)
        return parts[None], s_masks, sizes, pushed

    fn = jax.shard_map(
        _parallel_partition_scan, mesh=_worker_mesh(devices),
        in_specs=(P(axis),) * 5 + (P(), P(), P()),
        out_specs=(P(axis), P(), P(), P()),
        check_vma=False)
    return jax.jit(fn, donate_argnums=(6, 7))


def resolve_worker_devices(workers: int, devices: tuple | None = None) -> tuple:
    """The ``workers``-wide device slice, or a fail-fast ValueError when
    the mesh cannot exist — cheap, so callers run it BEFORE any O(edges)
    host packing."""
    if devices is None:
        devices = tuple(jax.devices())
    if len(devices) < workers:
        raise ValueError(
            f"need {workers} devices but only {len(devices)} are visible; "
            f"on CPU hosts set XLA_FLAGS=--xla_force_host_platform_device_"
            f"count={workers} before importing jax")
    return tuple(devices[:workers])


def _weighted_block_targets(weights: np.ndarray, nb: int) -> np.ndarray:
    """Largest-remainder apportionment of ``nb`` real blocks proportional
    to per-worker ``weights`` (higher weight ⇒ more blocks)."""
    raw = weights / weights.sum() * nb
    t = np.floor(raw).astype(np.int64)
    short = nb - int(t.sum())
    if short:
        t[np.argsort(-(raw - t), kind="stable")[:short]] += 1
    return t


def _biased_perm(targets: np.ndarray, nb: int, nb_per: int,
                 shuffle_rng: np.random.Generator | None) -> np.ndarray:
    """Block→worker permutation handing worker ``w`` exactly
    ``targets[w]`` real blocks (randomized across workers when a rng is
    given) and topping every worker up to ``nb_per`` with trailing padding
    blocks — the parity-safe no-ops ``_pad_block_stack`` appends — so the
    sharded shapes stay identical while slow workers scan mostly padding.
    """
    real = (shuffle_rng.permutation(nb) if shuffle_rng is not None
            else np.arange(nb, dtype=np.int64))
    pad_ids = np.arange(nb, nb_per * targets.shape[0], dtype=np.int64)
    out, r0, p0 = [], 0, 0
    for t_w in targets:
        t_w = int(t_w)
        out.append(real[r0 : r0 + t_w])
        out.append(pad_ids[p0 : p0 + nb_per - t_w])
        r0 += t_w
        p0 += nb_per - t_w
    return np.concatenate(out)


class WorkerBlocks(NamedTuple):
    """One block stack placed on the worker mesh by
    ``_place_parallel_blocks``: the five per-block stacks as (workers,
    nb_per, …) arrays, row ``w`` on worker ``w``'s device, and the
    overflow-word list replicated on every worker."""

    arrays: tuple            # valid, widx, vals, trunc, spans, overflow
    devices: tuple           # the mesh, one worker per device
    nb_per: int              # blocks per worker, whole merge groups
    merge_every: int
    perm: np.ndarray | None  # stack → sharded block order, None = identity

    @property
    def n_super(self) -> int:
        """Merge rounds: every worker merges after each group."""
        return self.nb_per // self.merge_every

    def in_stack_order(self, parts_blocks) -> np.ndarray:
        """The (workers, n_super, merge_every, B) parts back on the host,
        flattened in block-stack order."""
        by_block = np.asarray(parts_blocks)
        by_block = by_block.reshape(-1, by_block.shape[-1])
        if self.perm is not None:
            by_block = by_block[np.argsort(self.perm)]
        return by_block.reshape(-1)


def _place_parallel_blocks(
    packed: PackedBlocks,
    *,
    workers: int,
    merge_every: int,
    devices: tuple | None = None,
    shuffle_rng: np.random.Generator | None = None,
    worker_weights: np.ndarray | None = None,
) -> WorkerBlocks:
    """Place step of the Alg 4 core: pad the block stack to whole
    per-worker merge groups, deal it to the workers (optionally in a
    randomized block→worker order drawn from ``shuffle_rng`` — the
    arXiv:1502.02606 assignment the stream uses), and copy each worker's
    share from the host straight to its own device through a
    ``NamedSharding`` over the worker mesh; the overflow list goes to
    every device.

    ``worker_weights`` (workers-long, nonnegative, e.g. the inverse-EWMA
    speeds from ``runtime.straggler.StragglerEWMA``) biases the block
    distribution: real blocks are apportioned proportionally to weight
    (largest remainder) and the shortfall on slow workers is filled with
    parity-safe padding blocks, so every shard keeps the same shape —
    shard_map's requirement — while a straggler's wall-clock share
    shrinks.  The merge cadence is untouched: each worker still syncs
    every ``merge_every`` blocks, so the τ ≡ merge_every − 1 staleness
    bound of the bounded-delay model holds regardless of the bias.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    devices = resolve_worker_devices(workers, devices)
    nb = packed.valid.shape[0]
    if worker_weights is not None and workers > 1:
        w = np.asarray(worker_weights, np.float64)
        if w.shape != (workers,):
            raise ValueError(
                f"worker_weights must have shape ({workers},), got {w.shape}")
        if not np.all(np.isfinite(w)) or np.any(w < 0) or w.sum() <= 0:
            raise ValueError(
                "worker_weights must be finite, nonnegative, with a "
                "positive sum")
        targets = _weighted_block_targets(w, nb)
        nb_per = max(int(targets.max()), 1)
        nb_per = -(-nb_per // merge_every) * merge_every
        packed = _pad_block_stack(packed, nb_per * workers)
        perm = _biased_perm(targets, nb, nb_per, shuffle_rng)
    else:
        # blocks per worker, rounded up to whole merge groups
        nb_per = -(-nb // workers)
        nb_per = -(-nb_per // merge_every) * merge_every
        packed = _pad_block_stack(packed, nb_per * workers)
        total = nb_per * workers
        perm = (shuffle_rng.permutation(total) if shuffle_rng is not None
                else None)

    mesh = _worker_mesh(devices)
    by_worker = NamedSharding(mesh, P(_WORKER_AXIS))

    def shard(x):
        if perm is not None:
            x = x[perm]
        return jax.device_put(x.reshape((workers, nb_per) + x.shape[1:]),
                              by_worker)

    arrays = tuple(shard(x) for x in (packed.valid, packed.widx, packed.vals,
                                      packed.trunc, packed.overflow_spans))
    arrays += (jax.device_put(packed.overflow_words,
                              NamedSharding(mesh, P())),)
    return WorkerBlocks(arrays, devices, nb_per, merge_every, perm)


def _launch_parallel_scan(
    blocks: WorkerBlocks,
    s_masks: jax.Array,
    sizes: jax.Array,
    *,
    k: int,
    use_kernel: bool,
    interpret: bool | None,
    count_name: str = "parallel_partition_scan",
    sketch: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Run step of the Alg 4 core: launch ``_parallel_partition_scan`` on
    placed blocks against the live ``(s_masks, sizes)``, replicated on the
    mesh first where they are not (the first feed, or after a repair) and
    donated.  Returns without waiting: ``(parts_blocks, s_out, sizes_out,
    pushed_words)``, the parts in *sharded* block order (see
    ``WorkerBlocks.in_stack_order``), the rest replicated on the mesh."""
    devices = blocks.devices
    s_masks = _replicate_on_workers(s_masks, devices)
    sizes = _replicate_on_workers(sizes, devices)
    fn = _parallel_scan_fn(devices, k, blocks.merge_every, use_kernel,
                           interpret, sketch)
    _count_dispatch(count_name,
                    nbytes=int(s_masks.nbytes) + int(sizes.nbytes),
                    k=k, workers=len(devices),
                    blocks=blocks.nb_per * len(devices),
                    devices=[d.id for d in devices])
    out = fn(*blocks.arrays, s_masks, sizes)
    # where the per-worker outputs landed: one shard per mesh device
    annotate_dispatch(shard_devices=sorted(
        s.device.id for s in out[0].addressable_shards))
    return out


def _parallel_traffic(blocks: WorkerBlocks, pushed_words: int, k: int,
                      W: int) -> dict:
    """The push/pull dict of one Alg 4 scan in bitmask-word bytes — the
    single source of the Alg 4 counter formulas: each worker pushes its
    changed words and pulls the full packed (k, W) sets at every merge."""
    workers, n_super = len(blocks.devices), blocks.n_super
    return {
        "pushed_bytes": 4 * int(pushed_words),
        "pulled_bytes": 4 * workers * n_super * k * W,
        "tasks": workers * n_super,
        "stale_pushes_missed": n_super * workers * (workers - 1),
    }


def parallel_blocked_partition_u_impl(
    graph: BipartiteGraph,
    k: int,
    workers: int = 4,
    block: int = 256,
    merge_every: int = 1,
    init_sets: np.ndarray | None = None,
    use_kernel: bool = False,
    interpret: bool | None = None,
    seed: int = 0,
    cap: int = 48,
    devices: tuple | None = None,
    as_numpy: bool = True,
    timings: dict | None = None,
    sketch: bool = False,
) -> tuple[np.ndarray, np.ndarray, dict]:
    """Device-parallel Algorithm 4: shard_map multi-worker Parsa.

    The permuted U is packed once (same permutation as ``device_scan``) and
    split into ``workers`` contiguous shards of whole blocks; one jitted
    shard_map dispatch runs every worker's blocked scan and all the
    periodic OR-merges.  With ``workers=1`` the schedule collapses to the
    sequential ``device_scan`` pipeline bit-for-bit (the merge is the
    identity), for any ``merge_every``.

    Balance: every worker enforces §4.1 perfect balance against its *stale*
    view of the global sizes, so when a merge lands with uneven sizes
    (possible whenever k ∤ |U|) each worker independently applies the same
    catch-up and the corrections overlap — global ``max|U_i| − min|U_i|``
    is bounded by ``workers`` (exactly ≤ 1 at workers=1), a ≤ W/⌈|U|/k⌉
    relative slack on objective (4).  This is the BSP analogue of the
    staleness-induced quality slack of §5.4.

    Returns (parts_u, final packed s_masks, traffic dict).  Traffic units
    are bitmask-word bytes (4 bytes per 32 parameters): each worker pulls
    the full packed (k, W) set at every merge and pushes only its changed
    words (delta encoding); ``stale_pushes_missed`` counts the peer pushes
    in flight during each worker's local phase — W−1 peers per worker per
    merge round.
    """
    if workers < 1:
        raise ValueError(f"workers must be >= 1, got {workers}")
    if merge_every < 1:
        raise ValueError(f"merge_every must be >= 1, got {merge_every}")
    devices = resolve_worker_devices(workers, devices)  # before the pack
    t_pack = time.perf_counter()
    W = (graph.num_v + 31) // 32
    if init_sets is None:
        s_masks = jnp.zeros((k, W), jnp.int32)
    else:
        s_masks = jnp.asarray(coerce_packed_sets(init_sets, graph.num_v))
    sizes = jnp.zeros((k,), jnp.int32)
    rng = np.random.default_rng(seed)
    order = rng.permutation(graph.num_u)
    packed = pack_graph_blocks(graph, block, order=order, cap=cap)
    if timings is not None:
        timings["pack"] = time.perf_counter() - t_pack
    blocks = _place_parallel_blocks(packed, workers=workers,
                                    merge_every=merge_every, devices=devices)
    parts_blocks, s_out, _, pushed = _launch_parallel_scan(
        blocks, s_masks, sizes, k=k, use_kernel=use_kernel,
        interpret=interpret, sketch=sketch)
    traffic = _parallel_traffic(blocks, int(pushed), k, s_masks.shape[-1])
    if not as_numpy:
        flat = parts_blocks.reshape(-1)[: graph.num_u]
        parts = jnp.zeros((graph.num_u,), jnp.int32).at[
            jnp.asarray(order)].set(flat)
        return parts, s_out, traffic
    flat = np.asarray(parts_blocks).reshape(-1)[: graph.num_u]
    parts = np.full(graph.num_u, -1, np.int32)
    parts[order] = flat
    return parts, np.asarray(s_out), traffic

