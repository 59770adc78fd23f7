r"""Pallas TPU kernel: fused Parsa cost + select over packed bitmasks.

The blocked greedy partitioner (``jax_partition._assign_block_rounds``) never
needs the full (B × k) cost tile in HBM — per round it only needs, for every
partition i, the cheapest unassigned vertex of the block:

    cost[u, i] = Σ_w popcount(nbr[u, w] & ~s[i, w])
    (min_i, argmin_i) = min/argmin over unretired u of cost[u, i]

This kernel computes the tile *and* the reduction in one pass: the (B, k)
partials accumulate in a VMEM scratch across the W grid axis, and the final
grid step reduces them to two (1, k) outputs.  The tile never leaves VMEM,
so B=1024 blocks cost 4·B·k bytes of scratch instead of an HBM round-trip —
that is what lets the greedy path scale past B=256.

Two selection modes (static switch):

  * independent — each column reduced in isolation over unretired rows
    (retired→BIG); ties take the lowest row index.
  * greedy — one *round* of the perfectly-balanced greedy loop: columns are
    visited in ``order``; each active pick retires its row before the next
    column is reduced, so the k picks are distinct.  Slots that are disabled
    or find no unretired row return (u=-1, c=BIG).

VMEM budget per step (B=1024, bw=512, k≤64):
    nbr tile  1024×512×4 = 2 MiB
    s tile      64×512×4 = 128 KiB
    acc       1024×64×4  = 256 KiB
    per-k temp 1024×512×4 = 2 MiB  (inside the unrolled k loop)
  ≈ 4.4 MiB — inside the ~16 MiB VMEM of a v5e core.  bw is a multiple of
  128 (lane width); B a multiple of 8 (int32 sublane).

This file also hosts the other fused lattice kernels of the pipeline:
``packed_union_delta_kernel`` (Alg 4 server merge wire ops) and
``refine_sweep_kernel`` (the Algorithm 2 cost-update sweep of one V chunk —
packed need words, cost column, and parts row all VMEM-resident; ≈
(k + 32)·cw·4 bytes ≪ VMEM for cw=128 chunks at k≤64).

Mosaic lowers no dynamic slice or dynamic update of a vector, so a column
or lane chosen at run time is always read as a one-hot select over an iota
followed by a reduction (``tests/test_tpu_compile.py`` holds every kernel
to that by compiling it for a described v5e).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import BIG


def _select_reduce(cost, retired_ref, order_ref, enabled_ref,
                   umin_ref, cmin_ref, *, greedy: bool):
    """Reduce a VMEM-resident (B, k) cost tile to the two (1, k) outputs.

    Shared by both select kernels.  Mosaic lowers no dynamic slice of a
    vector, so every "column j" below is a one-hot select over a lane iota
    followed by a lane reduction; with one hot lane the sum is exact.
    """
    B, k = cost.shape
    ret = retired_ref[...]                               # (B, 1) int32 0/1
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (B, 1), 0)
    if not greedy:
        masked = jnp.where(ret != 0, BIG, cost)          # (B, k)
        mins = jnp.min(masked, axis=0, keepdims=True)    # (1, k)
        # first-occurrence argmin via the iota-min trick
        hit = masked == mins
        cmin_ref[...] = mins
        umin_ref[...] = jnp.min(jnp.where(hit, iota_b, B), axis=0,
                                keepdims=True)
        return
    order = order_ref[...]      # (1, k) int32
    enabled = enabled_ref[...]  # (1, k) int32
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)

    def pick(j, carry):
        u_sel, c_sel, ret = carry                        # (1,k),(1,k),(B,1)
        slot = iota_k == j
        col = jnp.sum(jnp.where(slot, order, 0), axis=1, keepdims=True)
        en = jnp.sum(jnp.where(slot, enabled, 0), axis=1, keepdims=True)
        c = jnp.sum(jnp.where(iota_k == col, cost, 0), axis=1,
                    keepdims=True)                       # (B, 1)
        c = jnp.where(ret != 0, BIG, c)
        m = jnp.min(c, axis=0, keepdims=True)            # (1, 1)
        u = jnp.min(jnp.where(c == m, iota_b, B), axis=0,
                    keepdims=True)                       # first min row
        act = (en != 0) & (m < BIG)
        ret = jnp.where((iota_b == u) & act, 1, ret)
        u_sel = jnp.where(slot, jnp.where(act, u, -1), u_sel)
        c_sel = jnp.where(slot, jnp.where(act, m, BIG), c_sel)
        return u_sel, c_sel, ret

    u0 = jnp.full((1, k), -1, jnp.int32)
    c0 = jnp.full((1, k), BIG, jnp.int32)
    u_sel, c_sel, _ = jax.lax.fori_loop(0, k, pick, (u0, c0, ret))
    umin_ref[...] = u_sel
    cmin_ref[...] = c_sel


def _select_kernel(nbr_ref, s_ref, retired_ref, order_ref, enabled_ref,
                   umin_ref, cmin_ref, acc_ref, *, greedy: bool):
    w_idx = pl.program_id(0)
    nw = pl.num_programs(0)
    k = s_ref.shape[0]

    @pl.when(w_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    nbr = nbr_ref[...]  # (B, bw) int32

    def accum(i, _):
        s_row = s_ref[i, :]  # (bw,) int32
        masked = nbr & ~s_row[None, :]
        partial = jax.lax.population_count(masked).astype(jnp.int32).sum(axis=1)
        acc_ref[:, i] += partial
        return _

    jax.lax.fori_loop(0, k, accum, None, unroll=True)

    @pl.when(w_idx == nw - 1)
    def _reduce():
        _select_reduce(acc_ref[...], retired_ref, order_ref, enabled_ref,
                       umin_ref, cmin_ref, greedy=greedy)


def _sketch_select_kernel(nbr_ref, s_ref, retired_ref, order_ref, enabled_ref,
                          umin_ref, cmin_ref, *, greedy: bool):
    """Fully VMEM-resident fused cost+select for sketched widths.

    Unlike ``_select_kernel`` there is no W grid axis and no cross-step
    scratch accumulator: the sketch compresses the packed width enough
    (guarded ≤ ~2048 words by the wrapper) that the whole (B, Ws) nbr
    tile, the (k, Ws) server sets, and the (B, k) cost tile live in VMEM
    simultaneously for one grid step.  That removes the accumulator
    read-modify-write per word tile *and* the grid bookkeeping — the
    kernel is one streamed pass.  Bit-exact vs ``ref.sketch_select_ref``.
    """
    k = s_ref.shape[0]
    B = nbr_ref.shape[0]
    nbr = nbr_ref[...]  # (B, Ws) int32 — the entire sketched block tile
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)

    def accum(i, acc):
        s_row = s_ref[i, :]  # (Ws,) int32
        masked = nbr & ~s_row[None, :]
        partial = jax.lax.population_count(masked).astype(jnp.int32).sum(
            axis=1, keepdims=True)                       # (B, 1)
        return jnp.where(iota_k == i, partial, acc)

    cost = jax.lax.fori_loop(0, k, accum,
                             jnp.zeros((B, k), jnp.int32), unroll=True)
    _select_reduce(cost, retired_ref, order_ref, enabled_ref,
                   umin_ref, cmin_ref, greedy=greedy)


# padded sketch widths beyond this many words exceed the VMEM budget of the
# gridless kernel (B=1024 × 2048 × 4 B = 8 MiB for the nbr tile alone) —
# wrappers must fall back to the W-gridded kernel above it
SKETCH_KERNEL_MAX_WORDS = 2048
_DEFAULT_SCOPED_VMEM = 16 << 20


@functools.partial(jax.jit, static_argnames=("greedy", "interpret"))
def sketch_select_kernel(
    nbr_masks: jax.Array,  # (B, Ws) int32, B % 8 == 0, Ws % 128 == 0
    s_masks: jax.Array,    # (k, Ws) int32
    retired: jax.Array,    # (B, 1) int32 (0/1)
    order: jax.Array,      # (1, k) int32 column visit order (greedy mode)
    enabled: jax.Array,    # (1, k) int32 slot gate (greedy mode)
    *,
    greedy: bool,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (u_sel (1, k), c_sel (1, k)) int32 — see ``_sketch_select_kernel``."""
    B, Ws = nbr_masks.shape
    k = s_masks.shape[0]
    if Ws > SKETCH_KERNEL_MAX_WORDS:
        raise ValueError(
            f"sketch width {Ws} words exceeds the VMEM-resident budget "
            f"({SKETCH_KERNEL_MAX_WORDS}); use parsa_select_kernel")
    # the whole (B, Ws) tile, one temporary of its size and 4 MiB for the
    # rest: 20 MiB at the width guard with B=1024, above the 16 MiB default
    # scoped-VMEM limit of a v5e core; smaller tiles keep that default
    vmem = max(_DEFAULT_SCOPED_VMEM, 2 * B * Ws * 4 + (4 << 20))
    umin, cmin = pl.pallas_call(
        functools.partial(_sketch_select_kernel, greedy=greedy),
        out_shape=[
            jax.ShapeDtypeStruct((1, k), jnp.int32),
            jax.ShapeDtypeStruct((1, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        interpret=interpret,
    )(nbr_masks, s_masks, retired, order, enabled)
    return umin, cmin


def _refine_sweep_kernel(words_ref, prev_ref, cost_ref,
                         parts_ref, cost_out_ref):
    """Fused Algorithm 2 cost-update: sweep one V chunk entirely in VMEM.

    words (k, cw) int32 packed need bits; prev (1, C) int32 entering
    assignments (C = 32·cw); cost (k, 1) int32, one partition per sublane.
    Emits (parts (1, C), cost' (k, 1)).  The C greedy steps run as a
    fori_loop over VMEM state — the words, the cost column, and the
    growing parts row never leave the core.  Step j reads need column j
    straight from its packed word: a one-hot lane select picks word j/32,
    a shift picks bit j%32 (Mosaic lowers no dynamic slice of a vector).
    Bit-exact vs ``ref.refine_sweep_ref``.
    """
    k, cw = words_ref.shape
    C = cw * 32
    words = words_ref[...]
    prev = prev_ref[...]
    iota_w = jax.lax.broadcasted_iota(jnp.int32, (1, cw), 1)
    iota_kc = jax.lax.broadcasted_iota(jnp.int32, (k, 1), 0)
    iota_c = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)

    def step(j, carry):
        cost, parts = carry                                    # (k,1), (1,C)
        word = jnp.sum(jnp.where(iota_w == j // 32, words, 0), axis=1,
                       keepdims=True)                          # (k, 1)
        bcol = (word >> (j % 32)) & 1                          # (k, 1)
        nj = jnp.sum(bcol, axis=0, keepdims=True)              # (1, 1)
        at_j = iota_c == j
        cur = jnp.sum(jnp.where(at_j, prev, 0), axis=1, keepdims=True)
        # retract j's old contribution: cost_cur −= −1 + (n_j − u_{cur,j})
        bitc = jnp.sum(jnp.where(iota_kc == cur, bcol, 0), axis=0,
                       keepdims=True)
        retract = jnp.where(cur >= 0, 1 - nj + bitc, 0)
        cost = cost + jnp.where(iota_kc == cur, retract, 0)
        # pick the needing partition with minimum cost (first on ties)
        masked = jnp.where(bcol > 0, cost, BIG)                # (k, 1)
        m = jnp.min(masked, axis=0, keepdims=True)
        xi = jnp.min(jnp.where(masked == m, iota_kc, k), axis=0,
                     keepdims=True)
        act = nj > 0
        # line 8: cost_ξ += −1 + (n_j − 1)
        cost = cost + jnp.where((iota_kc == xi) & act, nj - 2, 0)
        parts = jnp.where(at_j, jnp.where(act, xi, -1), parts)
        return cost, parts

    cost0 = cost_ref[...]
    parts0 = jnp.full((1, C), -1, jnp.int32)
    cost, parts = jax.lax.fori_loop(0, C, step, (cost0, parts0))
    parts_ref[...] = parts
    cost_out_ref[...] = cost


@functools.partial(jax.jit, static_argnames=("interpret",))
def refine_sweep_kernel(
    tile_words: jax.Array,  # (k, cw) int32, k % 8 == 0
    prev: jax.Array,        # (1, C) int32, C == 32·cw
    cost: jax.Array,        # (k, 1) int32
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (parts (1, C), cost' (k, 1)) int32 — see ``_refine_sweep_kernel``."""
    k, cw = tile_words.shape
    C = cw * 32
    parts, cost_out = pl.pallas_call(
        _refine_sweep_kernel,
        out_shape=[
            jax.ShapeDtypeStruct((1, C), jnp.int32),
            jax.ShapeDtypeStruct((k, 1), jnp.int32),
        ],
        interpret=interpret,
    )(tile_words, prev, cost)
    return parts, cost_out


def _union_delta_kernel(new_ref, old_ref, union_ref, delta_ref):
    new = new_ref[...]
    old = old_ref[...]
    union_ref[...] = new | old
    delta_ref[...] = new & ~old


@functools.partial(jax.jit, static_argnames=("bw", "interpret"))
def packed_union_delta_kernel(
    new_masks: jax.Array,  # (k, W) int32 packed words, W % bw == 0
    old_masks: jax.Array,  # (k, W) int32
    *,
    bw: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused lattice ops of the Alg-4 server line 9 on packed words:
    union = new | old (the OR-merge) and delta = new & ~old (the worker's
    delta-encoded push) in one VMEM pass over the word axis — the wire
    format shared by the host simulation and the shard_map backend."""
    k, W = new_masks.shape
    grid = (W // bw,)
    union, delta = pl.pallas_call(
        _union_delta_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, bw), lambda w: (0, w)),
            pl.BlockSpec((k, bw), lambda w: (0, w)),
        ],
        out_specs=[
            pl.BlockSpec((k, bw), lambda w: (0, w)),
            pl.BlockSpec((k, bw), lambda w: (0, w)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, W), jnp.int32),
            jax.ShapeDtypeStruct((k, W), jnp.int32),
        ],
        interpret=interpret,
    )(new_masks, old_masks)
    return union, delta


@functools.partial(jax.jit,
                   static_argnames=("greedy", "bw", "interpret"))
def parsa_select_kernel(
    nbr_masks: jax.Array,  # (B, W) int32, B % 8 == 0, W % bw == 0
    s_masks: jax.Array,    # (k, W) int32
    retired: jax.Array,    # (B, 1) int32 (0/1)
    order: jax.Array,      # (1, k) int32 column visit order (greedy mode)
    enabled: jax.Array,    # (1, k) int32 slot gate (greedy mode)
    *,
    greedy: bool,
    bw: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (u_sel (1, k), c_sel (1, k)) int32 — see module docstring."""
    B, W = nbr_masks.shape
    k = s_masks.shape[0]
    grid = (W // bw,)
    umin, cmin = pl.pallas_call(
        functools.partial(_select_kernel, greedy=greedy),
        grid=grid,
        in_specs=[
            pl.BlockSpec((B, bw), lambda w: (0, w)),
            pl.BlockSpec((k, bw), lambda w: (0, w)),
            pl.BlockSpec((B, 1), lambda w: (0, 0)),
            pl.BlockSpec((1, k), lambda w: (0, 0)),
            pl.BlockSpec((1, k), lambda w: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, k), lambda w: (0, 0)),
            pl.BlockSpec((1, k), lambda w: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, k), jnp.int32),
            jax.ShapeDtypeStruct((1, k), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((B, k), jnp.int32)],
        interpret=interpret,
    )(nbr_masks, s_masks, retired, order, enabled)
    return umin, cmin
