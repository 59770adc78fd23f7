"""Mixture-of-experts layer: top-k routing, capacity-based dispatch, shared
experts, EP-sharded via shard_map, optional Parsa expert permutation.

Two execution paths:

  * LOCAL (no mesh context / 1-wide model axis): sort-and-pack dispatch on
    one device — the reference semantics (smoke tests, CPU training).

  * SHARD_MAP (mesh context active): GSPMD cannot shard the data-dependent
    dispatch gather/scatter — left to sharding propagation it *replicates*
    the token buffer onto every device (measured: a 45 TB/step collective
    term for deepseek-v2; EXPERIMENTS.md §Perf).  Instead the routed part
    runs in shard_map where dispatch is an explicit LOCAL scatter:
      - activations are batch-sharded on dp and replicated across tp, so
        each tp rank packs only the assignments of ITS experts (E % tp == 0:
        expert-parallel) or all experts on its FFN slice (E < tp:
        hidden-sharded), computes, and contributes a partial (T, D) output;
      - one psum over tp completes the layer — the same wire cost as a
        Megatron row-parallel matmul, with zero dispatch replication.

Top-k routing + aux loss + shared experts stay in the GSPMD path (small
dense math).  FLOP overhead vs ideal = capacity_factor (default 1.25).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .layers import _dense_init
from .shardctx import constrain, current_rules


def init_moe(key, cfg):
    ks = jax.random.split(key, 8)
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    pd = jnp.float32
    p = {
        "router": _dense_init(ks[0], (D, E), 0, pd),
        "wg": _dense_init(ks[1], (E, D, F), 1, pd),
        "wu": _dense_init(ks[2], (E, D, F), 1, pd),
        "wd": _dense_init(ks[3], (E, F, D), 1, pd),
    }
    if cfg.num_shared_experts:
        Fs = cfg.d_ff * cfg.num_shared_experts
        p["shared"] = {
            "wg": _dense_init(ks[4], (D, Fs), 0, pd),
            "wu": _dense_init(ks[5], (D, Fs), 0, pd),
            "wd": _dense_init(ks[6], (Fs, D), 0, pd),
        }
    return p


def capacity(cfg, tokens: int) -> int:
    c = int(np.ceil(tokens * cfg.num_experts_per_tok / cfg.num_experts
                    * cfg.moe_capacity_factor))
    return max(8, int(np.ceil(c / 8) * 8))


def _route(p, xt, cfg):
    """fp32 router → (weights, ids) (T, K), renormalized."""
    K = cfg.num_experts_per_tok
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, K)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    return probs, top_w, top_e


def _pack_compute_combine(xt, top_e, top_w, wg, wu, wd, cfg, *,
                          e_lo, e_num, dtype):
    """Sort-pack assignments of experts [e_lo, e_lo+e_num) into a capacity
    buffer, run the expert MLPs, combine back to (T, D).  Pure local math."""
    T, D = xt.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    C = capacity(cfg, T)
    flat_e = top_e.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_w = top_w.reshape(-1).astype(dtype)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    group_sizes = jnp.bincount(flat_e, length=E)
    group_start = jnp.concatenate([jnp.zeros(1, group_sizes.dtype),
                                   jnp.cumsum(group_sizes)[:-1]])
    pos = jnp.arange(T * K) - group_start[se]
    mine = (se >= e_lo) & (se < e_lo + e_num) & (pos < C)
    dest = jnp.where(mine, (se - e_lo) * C + pos, e_num * C)

    rows = xt[st].astype(dtype)
    buf = jnp.zeros((e_num * C, D), dtype).at[dest].set(rows, mode="drop")
    buf = buf.reshape(e_num, C, D)
    g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(dtype))
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd.astype(dtype))
    y_flat = y.reshape(e_num * C, D)
    picked = jnp.where(mine[:, None],
                       y_flat[jnp.clip(dest, 0, e_num * C - 1)], 0)
    return jax.ops.segment_sum(picked * sw[:, None], st, num_segments=T)


def _routed_local(p, xt, top_e, top_w, cfg, dtype):
    return _pack_compute_combine(xt, top_e, top_w, p["wg"], p["wu"], p["wd"],
                                 cfg, e_lo=0, e_num=cfg.num_experts,
                                 dtype=dtype)


def _routed_shard_map(p, x, top_w, top_e, cfg, dtype):
    """EP via shard_map (see module docstring)."""
    mesh, rules = current_rules()
    tp_ax = rules.get("tp")
    dp_ax = rules.get("batch")
    fsdp_ax = rules.get("fsdp")  # data-axis ZeRO shard of the d_model dim
    E = cfg.num_experts
    tp = int(mesh.shape[tp_ax]) if tp_ax else 1
    ep = E % tp == 0
    fsdp = (fsdp_ax is not None and cfg.fsdp
            and cfg.d_model % int(mesh.shape[fsdp_ax]) == 0)

    def body(x_loc, tw, te, wg, wu, wd):
        B_loc, S, D = x_loc.shape
        T_loc = B_loc * S
        xt = x_loc.reshape(T_loc, D)
        te2 = te.reshape(-1, te.shape[-1])
        tw2 = tw.reshape(-1, tw.shape[-1])
        token_path = False
        if fsdp and ep:
            nd = int(mesh.shape[fsdp_ax])
            gather_bytes = (wg.size + wu.size + wd.size) * 2 * (nd - 1)
            token_bytes = 3 * T_loc * D * 2 * (nd - 1) * nd
            # decode: tokens are tiny — move tokens to the F-sliced weights
            # instead of re-gathering GBs of expert weights per step
            token_path = token_bytes < gather_bytes
        if fsdp and not token_path:
            # ZeRO-3: re-materialize full weights in bf16 per layer
            ax_g = 2 if ep else 1
            wg = jax.lax.all_gather(wg.astype(dtype), fsdp_ax, axis=ax_g, tiled=True)
            wu = jax.lax.all_gather(wu.astype(dtype), fsdp_ax, axis=ax_g, tiled=True)
            wd = jax.lax.all_gather(wd.astype(dtype), fsdp_ax, axis=1 if ep else 2, tiled=True)
        if token_path:
            xt = jax.lax.all_gather(xt, fsdp_ax, axis=0, tiled=True)
            te2 = jax.lax.all_gather(te2, fsdp_ax, axis=0, tiled=True)
            tw2 = jax.lax.all_gather(tw2, fsdp_ax, axis=0, tiled=True)
        if ep:
            idx = jax.lax.axis_index(tp_ax)
            e_num = E // tp
            out = _pack_compute_combine(
                xt, te2, tw2, wg, wu, wd, cfg,
                e_lo=idx * e_num, e_num=e_num, dtype=dtype)
        else:
            out = _pack_compute_combine(
                xt, te2, tw2, wg, wu, wd, cfg, e_lo=0, e_num=E, dtype=dtype)
        if token_path:
            out = jax.lax.psum(out, (tp_ax, fsdp_ax))
            didx = jax.lax.axis_index(fsdp_ax)
            out = jax.lax.dynamic_slice_in_dim(out, didx * T_loc, T_loc, 0)
        else:
            out = jax.lax.psum(out, tp_ax)
        return out.reshape(B_loc, S, D)

    f1 = fsdp_ax if fsdp else None
    if ep:
        # F ZeRO-shards over data (wg/wu dim 2, wd dim 1)
        w_specs = (P(tp_ax, None, f1), P(tp_ax, None, f1), P(tp_ax, f1, None))
    else:  # hidden-sharded experts: partial products reduced by the psum
        w_specs = (P(None, f1, tp_ax), P(None, f1, tp_ax),
                   P(None, tp_ax, f1))
    x_spec = P(dp_ax, None, None)
    tk_spec = P(dp_ax, None, None)
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, tk_spec, tk_spec) + w_specs,
        out_specs=x_spec, check_vma=False)
    B, S, D = x.shape
    return fn(x, top_w.reshape(B, S, -1), top_e.reshape(B, S, -1),
              p["wg"], p["wu"], p["wd"]).reshape(B * S, D)


def apply_moe(p, x, cfg, dtype=jnp.bfloat16, return_aux=False):
    """x: (B, S, D) → (B, S, D). Router in fp32 for stability."""
    B, S, D = x.shape
    E = cfg.num_experts
    T = B * S
    xt = x.reshape(T, D)
    probs, top_w, top_e = _route(p, xt, cfg)

    ctx = current_rules()
    use_shard_map = False
    if ctx is not None:
        mesh, rules = ctx
        tp_ax = rules.get("tp")
        if tp_ax and int(mesh.shape[tp_ax]) > 1:
            use_shard_map = True
    if use_shard_map:
        out = _routed_shard_map(p, x, top_w, top_e, cfg, dtype)
    else:
        out = _routed_local(p, xt, top_e, top_w, cfg, dtype)

    if "shared" in p:
        sh = p["shared"]
        g = jnp.einsum("td,df->tf", xt.astype(dtype), sh["wg"].astype(dtype))
        u = jnp.einsum("td,df->tf", xt.astype(dtype), sh["wu"].astype(dtype))
        out = out + jnp.einsum("tf,fd->td", jax.nn.silu(g) * u,
                               sh["wd"].astype(dtype))

    out = out.reshape(B, S, D).astype(dtype)
    if return_aux:
        me = jnp.mean(jax.nn.one_hot(top_e, E, dtype=jnp.float32), axis=(0, 1))
        ce = jnp.mean(probs, axis=0)
        aux = E * jnp.sum(me * ce)
        counts = jnp.sum(jax.nn.one_hot(top_e, E, dtype=jnp.int32), axis=(0, 1))
        return out, {"aux_loss": aux, "expert_counts": counts}
    return out
