"""Mixture-of-Experts FFN: top-k token-choice routing, sort-based dispatch,
capacity-bounded grouped matmuls, expert parallelism on the 'model' axis.

Dispatch (MaxText/MegaBlocks-style, static shapes):
  1. router softmax -> top-k (weights, expert ids) per token
  2. stable sort assignments by expert id
  3. position-within-expert via segment arithmetic; drop beyond capacity
  4. gather tokens into (E, C, d), grouped einsum (E,C,d)x(E,d,ff)
  5. scatter-add weighted outputs back to tokens

All tensors with a leading E axis carry a 'model' sharding constraint, so
GSPMD partitions the expert compute (EP); the gather/scatter token sides
stay batch-sharded.  Arctic's "dense residual" (dense FFN in parallel with
the MoE) is composed in blocks.py.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.models.common import dense, ninit, shard


def init_moe(key, cfg):
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    sc = 1.0 / math.sqrt(d)
    return {
        "w_router": ninit(ks[0], (d, e), sc, jnp.float32),
        "w_in": ninit(ks[1], (e, d, ff), sc, cfg.param_dtype),
        "w_gate": ninit(ks[2], (e, d, ff), sc, cfg.param_dtype),
        "w_out": ninit(ks[3], (e, ff, d), 1.0 / math.sqrt(ff), cfg.param_dtype),
    }


def _capacity(n_tokens: int, cfg) -> int:
    c = int(cfg.capacity_factor * cfg.top_k * n_tokens / cfg.num_experts)
    return max(8, -(-c // 8) * 8)


def _route(xt, params, cfg):
    """Router: returns (topw (T,k), topi (T,k), aux)."""
    logits = dense(xt.astype(jnp.float32), params["w_router"])
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, cfg.top_k)
    if cfg.renormalize_router:
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    me = probs.mean(0)
    ce = jax.nn.one_hot(topi[:, 0], cfg.num_experts,
                        dtype=jnp.float32).mean(0)
    aux = cfg.num_experts * jnp.sum(me * ce)
    return topw, topi, aux


def _dispatch_compute_combine(xt, topw, topi, w_in, w_gate, w_out, cfg,
                              n_experts: int, e_offset, cap: int):
    """Sort-based dispatch over ``n_experts`` local experts starting at
    ``e_offset``; returns the combined (T, d) output (local contribs)."""
    t, d = xt.shape
    k = cfg.top_k
    flat_e = topi.reshape(-1) - e_offset
    flat_w = topw.reshape(-1)
    flat_tok = jnp.arange(t * k, dtype=jnp.int32) // k
    local = (flat_e >= 0) & (flat_e < n_experts)
    sort_key = jnp.where(local, flat_e, n_experts)
    order = jnp.argsort(sort_key, stable=True)
    e_s, tok_s, w_s = (sort_key[order], flat_tok[order], flat_w[order])
    loc_s = local[order]
    pos = jnp.arange(t * k, dtype=jnp.int32)
    seg_start = jax.lax.associative_scan(
        jnp.maximum,
        jnp.where(jnp.concatenate([jnp.array([True]), e_s[1:] != e_s[:-1]]),
                  pos, 0))
    slot = pos - seg_start
    keep = loc_s & (slot < cap)

    safe_e = jnp.where(keep, e_s, 0)
    safe_slot = jnp.where(keep, slot, cap - 1)
    xg = jnp.zeros((n_experts, cap, d), xt.dtype)
    xg = xg.at[safe_e, safe_slot].set(
        jnp.where(keep[:, None], xt[tok_s], 0).astype(xt.dtype))

    h = jnp.einsum("ecd,edf->ecf", xg, w_in.astype(xt.dtype),
                   preferred_element_type=jnp.float32).astype(xt.dtype)
    g = jnp.einsum("ecd,edf->ecf", xg, w_gate.astype(xt.dtype),
                   preferred_element_type=jnp.float32).astype(xt.dtype)
    h = jax.nn.silu(g) * h
    yo = jnp.einsum("ecf,efd->ecd", h, w_out.astype(xt.dtype),
                    preferred_element_type=jnp.float32).astype(xt.dtype)

    contrib = yo[safe_e, safe_slot] * w_s[:, None].astype(xt.dtype)
    contrib = jnp.where(keep[:, None], contrib, 0)
    return jnp.zeros((t, d), xt.dtype).at[tok_s].add(contrib)


def apply_moe_shard_map(params, x, cfg):
    """Expert parallelism with explicit shard_map over 'model'.

    GSPMD cannot partition the sort/scatter dispatch cleanly (it falls back
    to 'involuntary full rematerialization' all-gathers — the baseline's
    dominant collective cost, EXPERIMENTS.md §Perf).  Manual EP makes the
    communication explicit and minimal: router runs replicated, each model
    shard dispatches/computes its E/TP local experts, and ONE psum over
    'model' combines the outputs.
    """
    from repro.models.common import batch_axes_for, get_active_mesh
    mesh = get_active_mesh()
    if (mesh is None or "model" not in mesh.axis_names
            or mesh.shape["model"] == 1
            or cfg.num_experts % mesh.shape["model"] != 0):
        return apply_moe(params, x, cfg)

    b, s, d = x.shape
    tp = mesh.shape["model"]
    e_local = cfg.num_experts // tp
    baxes = batch_axes_for(b) or ()
    bspec = P(baxes if baxes else None, None, None)
    # fsdp strategy shards the batch over 'model' too: the EP body then
    # all-gathers its token block over 'model' (cheap — activations are
    # 16x smaller per chip), computes its local experts for ALL tokens,
    # psums, and keeps its own slice back.
    tokens_model_sharded = "model" in baxes

    def body(xb, wr, w_in, w_gate, w_out):
        bl, sl, _ = xb.shape
        xt = xb.reshape(-1, d)
        if tokens_model_sharded:
            xt = jax.lax.all_gather(xt, "model", axis=0, tiled=True)
        topw, topi, aux = _route(xt, {"w_router": wr}, cfg)
        cap = _capacity(xt.shape[0], cfg)
        e_off = jax.lax.axis_index("model") * e_local
        y = _dispatch_compute_combine(xt, topw, topi, w_in, w_gate, w_out,
                                      cfg, e_local, e_off, cap)
        y = jax.lax.psum(y, "model")
        if tokens_model_sharded:
            midx = jax.lax.axis_index("model")
            y = jax.lax.dynamic_slice_in_dim(y, midx * (bl * sl), bl * sl,
                                             axis=0)
        if baxes:
            aux = jax.lax.pmean(aux, baxes)
        return y.reshape(bl, sl, d), aux

    y, aux = shard_map(
        body,
        mesh=mesh,
        in_specs=(bspec, P(), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(bspec, P()),
        check_vma=False,
    )(x, params["w_router"], params["w_in"], params["w_gate"],
      params["w_out"])
    return y, aux


def apply_moe(params, x, cfg):
    """x: (B,S,d) -> (B,S,d), plus load-balancing aux loss."""
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    t = b * s
    xt = x.reshape(t, d)

    # --- routing (f32 for stability) ---
    logits = dense(xt.astype(jnp.float32), params["w_router"])
    probs = jax.nn.softmax(logits, axis=-1)                  # (T, E)
    topw, topi = jax.lax.top_k(probs, k)                     # (T, k)
    if cfg.renormalize_router:
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch-style)
    me = probs.mean(0)
    one_hot_top1 = jax.nn.one_hot(topi[:, 0], e, dtype=jnp.float32)
    ce = one_hot_top1.mean(0)
    aux = e * jnp.sum(me * ce)

    # --- sort-based dispatch ---
    flat_e = topi.reshape(-1)                                # (T*k,)
    flat_w = topw.reshape(-1)
    flat_tok = jnp.arange(t * k, dtype=jnp.int32) // k
    order = jnp.argsort(flat_e, stable=True)
    e_s = flat_e[order]
    tok_s = flat_tok[order]
    w_s = flat_w[order]
    pos = jnp.arange(t * k, dtype=jnp.int32)
    seg_start = jax.lax.associative_scan(
        jnp.maximum,
        jnp.where(jnp.concatenate([jnp.array([True]), e_s[1:] != e_s[:-1]]),
                  pos, 0))
    slot = pos - seg_start                                    # rank in expert
    cap = _capacity(t, cfg)
    keep = slot < cap

    # gather tokens into (E, C, d); dropped slots read token 0 with weight 0
    safe_e = jnp.where(keep, e_s, 0)
    safe_slot = jnp.where(keep, slot, cap - 1)
    xg = jnp.zeros((e, cap, d), x.dtype)
    xg = xg.at[safe_e, safe_slot].set(
        jnp.where(keep[:, None], xt[tok_s], 0).astype(x.dtype))
    xg = shard(xg, "model", None, None)

    # --- grouped expert matmuls (EP over 'model') ---
    h = jnp.einsum("ecd,edf->ecf", xg, params["w_in"].astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)
    g = jnp.einsum("ecd,edf->ecf", xg, params["w_gate"].astype(x.dtype),
                   preferred_element_type=jnp.float32).astype(x.dtype)
    h = jax.nn.silu(g) * h
    h = shard(h, "model", None, None)
    yo = jnp.einsum("ecf,efd->ecd", h, params["w_out"].astype(x.dtype),
                    preferred_element_type=jnp.float32).astype(x.dtype)
    yo = shard(yo, "model", None, None)

    # --- combine: scatter-add weighted expert outputs back to tokens ---
    contrib = yo[safe_e, safe_slot] * w_s[:, None].astype(x.dtype)
    contrib = jnp.where(keep[:, None], contrib, 0)
    y = jnp.zeros((t, d), x.dtype).at[tok_s].add(contrib)
    y = shard(y.reshape(b, s, d), "batch", None, None)
    return y, aux
