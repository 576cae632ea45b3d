"""Bitpacked ppermute ring all-reduce: the compressed wire, realized.

``dist.collectives`` proves the byte win of quantized gradient codes but
(with ``wire_format="int32"``) still moves full int32 codes through
``jax.lax.psum`` — the compression exists only in the accounting.  This
module closes that gap, hZCCL-style: the collective itself operates on
the PACKED representation.

Ring schedule (single data-parallel axis, n members, n-1 hops):

  * every member quantizes its leaves exactly as the int32 path does
    (same pmax-shared eb, same codes), concatenates them into per-bucket
    code streams (small leaves share one stream per hop), and keeps a
    running partial sum ``msg`` (initially its own codes);
  * each hop packs ``msg`` with ``core.bitpack.pack_blocks`` at dynamic
    per-block widths under a STATIC per-hop cap — a partial sum over h
    members needs at most ``base_width(rel_eb) + ceil(log2(h))`` bits
    (``bitpack.sum_width``), because ``|q| <= 1/(2 rel_eb) + 2`` holds
    deterministically — appends the sign bitplane (``pack_bits``), the
    per-block width bytes, ships the uint8 buffer with
    ``jax.lax.ppermute`` (the topo sidecar's fp32 values ride the same hop
    as a second message), unpacks, and adds its own codes to the received
    partial sum;
  * after n-1 hops every member holds the full integer code sum —
    bit-identical to ``jax.lax.psum`` of the codes, since integer
    addition commutes — and dequantizes once.

Topo sidecar: the per-member top-k indices circulate first (an index
pre-ring of k int32 per hop), giving every member the same member-ordered
union; each member's exact fp32 values at EVERY union index then ride
each hop beside the packed body, collected by origin.  The exact sums are folded in
member order 0..n-1 — on the CPU/TPU ring all-reduce this matches
``jax.lax.psum``'s reduction order bit-for-bit, which is what makes the
packed and int32 wire formats produce identical protected entries.

Overflow: the ring accumulates in int32 sign-magnitude (32 magnitude bits
+ separate sign plane); it requires ``n * max_code(rel_eb) <= int32 max``
and raises a clear trace-time error otherwise (the int32 psum path widens
via a hi/lo split instead — see ``collectives._psum_leaf``).
"""
from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.bitpack import (pack_bits, sum_width, unpack_bits,
                                unpack_blocks)
from repro.core.quantize import dequantize, quantize
from repro.dist.collectives import (_EB_TINY, INT32_MAX, _check_code_range,
                                    _residual, max_code, protect_k)
from repro.kernels import ops
from repro.utils import bitwidth, cdiv

BLOCK_K = 256                 # values per packed block (one width byte each)
BUCKET_ELEMS = 1 << 20        # leaf-batching target: elements per bucket


def base_width(rel_eb: float) -> int:
    """Static magnitude bit width of any per-member code at ``rel_eb``."""
    return max(1, max_code(rel_eb).bit_length())


def ring_perm(n: int) -> List[Tuple[int, int]]:
    """Unidirectional ring permutation i -> i+1 (mod n)."""
    return [(i, (i + 1) % n) for i in range(n)]


def _axis_size(axes: Sequence[str]) -> int:
    """Static member count of the (manual) mesh axes."""
    return int(jax.lax.psum(1, tuple(axes)))


def _require_single_axis(axes: Sequence[str]) -> str:
    if len(axes) != 1:
        raise NotImplementedError(
            f"wire_format='packed' runs a ppermute ring over ONE "
            f"data-parallel axis; got {tuple(axes)}.  Use "
            f"wire_format='int32' on multi-axis (pod) meshes.")
    return axes[0]


# --------------------------------------------------------------------------
# in-mesh ring primitives (shard_map manual-axes context)
# --------------------------------------------------------------------------

def ring_gather(x: jnp.ndarray, axis: str, n: int) -> jnp.ndarray:
    """Circulate originals around the ring -> (n, *x.shape) by origin.

    Member-ordered like ``jax.lax.all_gather`` but ppermute-based, so the
    per-hop payload is exactly ``x`` (the index pre-ring of the packed
    sidecar).
    """
    i = jax.lax.axis_index(axis)
    out = jnp.zeros((n,) + x.shape, x.dtype).at[i].set(x)
    if n == 1:
        return out
    perm = ring_perm(n)
    msg = x
    for h in range(1, n):
        msg = jax.lax.ppermute(msg, axis, perm)
        origin = (i - h) % n
        out = out.at[origin].set(msg)
    return out


def ordered_fold(vals: jnp.ndarray) -> jnp.ndarray:
    """Sum (n, ...) by-origin values sequentially in member order 0..n-1.

    This is the reduction order ``jax.lax.psum`` realizes on the ring
    all-reduce, so folding this way keeps the packed path's fp32 sidecar
    sums bit-identical to the int32 path's psum.
    """
    out = vals[0]
    for j in range(1, vals.shape[0]):
        out = out + vals[j]
    return out


def ring_allreduce_codes(
        q: jnp.ndarray, axis: str, n: int, rel_eb: float,
        side_vals: Optional[jnp.ndarray] = None, block_k: int = BLOCK_K,
        backend: Optional[str] = None,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray], jnp.ndarray]:
    """Bitpacked ring all-reduce of int32 codes (+ fp32 sidecar circulation).

    Args:
      q: (P,) int32 per-member codes, P a multiple of ``block_k``, with
         ``n * max|q| <= int32 max`` (caller-guarded via ``max_code``).
      side_vals: optional (U,) fp32 — this member's exact values at the
         sidecar union; circulated by origin alongside the packed body.
      backend: kernels.ops backend for the per-hop BE pack (the same
         tiled local-pack + compaction kernels the resident compressor
         runs; ``None`` resolves to the hardware default).  Buffers are
         byte-identical across backends.

    Returns:
      (code_sum (P,) int32  — bit-identical to ``psum(q, axis)``,
       vals_by_origin (n, U) fp32 or None,
       valid_bytes () f32 — measured packed payload bytes this member
       actually needed across all hops; the shipped buffers are statically
       capped at the ``sum_width`` bound).
    """
    p = q.shape[0]
    if p % block_k != 0 or p % 8 != 0:
        raise ValueError(
            f"code length {p} must be a multiple of block_k={block_k} "
            f"and of 8 (sign-plane bytes); pad the stream first")
    backend = ops.resolve_backend(backend)
    b_blocks = p // block_k
    sign_bytes = p // 8
    w0 = base_width(rel_eb)
    i = jax.lax.axis_index(axis)
    u = 0 if side_vals is None else side_vals.shape[0]
    vout = None
    if side_vals is not None:
        vout = jnp.zeros((n, u), jnp.float32).at[i].set(side_vals)
    valid = jnp.float32(0.0)
    if n == 1:
        return q, vout, valid

    perm = ring_perm(n)
    msg = q                                   # partial sum, 1 member so far
    vmsg = side_vals                          # circulating originals
    for h in range(1, n):
        with jax.named_scope(f"ring.hop{h}"):
            w_cap = sum_width(w0, h)          # static per-hop width bound
            mag_cap = b_blocks * cdiv(block_k * w_cap, 8)
            mags = jnp.abs(msg).astype(jnp.uint32).reshape(b_blocks, block_k)
            widths = bitwidth(mags.max(axis=1))   # (B,) dynamic, <= w_cap
            local = ops.local_pack(mags, widths, max_width=w_cap,
                                   backend=backend)
            buf, _, total = ops.compact_bytes(local, widths, block_k,
                                              backend=backend)
            signs = pack_bits((msg < 0).astype(jnp.uint32))
            payload = jnp.concatenate([buf, signs, widths.astype(jnp.uint8)])
            valid = valid + (total.astype(jnp.float32)
                             + jnp.float32(sign_bytes + b_blocks + 4 * u))

            payload = jax.lax.ppermute(payload, axis, perm)
            if vmsg is not None:
                # fp32 values travel as their own message: spliced into
                # the uint8 body as bytes, they made the TPU compile of a
                # hop take minutes at MiniCPM-2B leaf sizes
                vmsg = jax.lax.ppermute(vmsg, axis, perm)
                vout = vout.at[(i - h) % n].set(vmsg)

            o_sign = mag_cap
            o_width = o_sign + sign_bytes
            rwidths = payload[o_width:o_width + b_blocks].astype(jnp.int32)
            rmags = unpack_blocks(payload[:mag_cap], rwidths,
                                  block_k).reshape(-1)
            rsigns = unpack_bits(payload[o_sign:o_width], p)
            rcodes = jnp.where(rsigns == 1, -rmags.astype(jnp.int32),
                               rmags.astype(jnp.int32))
            msg = rcodes + q                  # received h members + own
    return msg, vout, valid


# --------------------------------------------------------------------------
# tree-level packed psum (bucketed leaf batching)
# --------------------------------------------------------------------------

def _obs_wire(sizes: List[int], rel_eb: float, topo_frac: float, n: int,
              block_k: int, bucket_elems: int) -> None:
    """Trace-time wire accounting: absorb the static
    :func:`packed_wire_summary` model into the obs registry.

    ``packed_psum_tree`` executes ONCE per trace (inside shard_map/jit),
    never per step, so these must be last-write-wins GAUGES — an
    accumulating counter would record trace counts, not wire bytes.  The
    one true counter here (``ring.traces``) counts exactly that:
    compilations of the packed wire."""
    if not obs.enabled():
        return
    s = packed_wire_summary(sizes, rel_eb, topo_frac, n, block_k=block_k,
                            bucket_elems=bucket_elems)
    for k in ("n_members", "hops", "base_width_bits",
              "packed_bytes_per_hop", "packed_bytes_per_step",
              "sidecar_idx_bytes", "sidecar_val_bytes",
              "int32_bytes_per_hop", "int32_bytes_per_step",
              "packed_vs_int32_per_hop"):
        obs.gauge_set(f"ring.{k}", float(s[k]))
    obs.counter_add("ring.traces", 1)

def _bucket_leaves(sizes: List[int], bucket_elems: int) -> List[List[int]]:
    """Group leaf indices so each bucket packs ~bucket_elems values."""
    buckets, cur, cur_n = [], [], 0
    for li, sz in enumerate(sizes):
        if cur and cur_n + sz > bucket_elems:
            buckets.append(cur)
            cur, cur_n = [], 0
        cur.append(li)
        cur_n += sz
    if cur:
        buckets.append(cur)
    return buckets


def packed_psum_tree(grads: Any, axes: Sequence[str], rel_eb: float,
                     err: Optional[Any], topo_frac: float,
                     block_k: int = BLOCK_K,
                     bucket_elems: int = BUCKET_ELEMS,
                     backend: Optional[str] = None) -> Tuple[Any, Any]:
    """Compressed mean-psum over a pytree with the bitpacked ring wire.

    Same contract (and bit-identical results on the ring-ordered
    backends) as ``collectives._psum_tree(wire_format="int32")``: returns
    ``(mean gradient tree, new error-feedback tree)``.  Leaves are
    concatenated into buckets so small leaves share one packed stream per
    hop; the topo sidecar rides each hop beside it (see module docstring).
    """
    axis = _require_single_axis(tuple(axes))
    n = _axis_size((axis,))
    if block_k % 8 != 0:
        raise ValueError(
            f"block_k={block_k} must be a multiple of 8: the payload "
            f"layout derives the sign-plane byte count from the padded "
            f"code length")
    q_max = _check_code_range(rel_eb)
    if n * q_max > INT32_MAX:
        raise ValueError(
            f"wire_format='packed': {n}-member partial code sums can reach "
            f"{n * q_max:.3g} > int32 max at rel_eb={rel_eb:g}; raise "
            f"rel_eb or use wire_format='int32' (which widens via a hi/lo "
            f"split)")

    leaves_g, treedef = jax.tree.flatten(grads)
    leaves_e = ([None] * len(leaves_g) if err is None
                else jax.tree.leaves(err))
    nf = jnp.float32(n)

    out: List[Optional[Tuple[jnp.ndarray, jnp.ndarray]]] = \
        [None] * len(leaves_g)
    work = []                              # non-empty leaf indices
    for li, g in enumerate(leaves_g):
        if g.size == 0:
            out[li] = (g, jnp.zeros(g.shape, jnp.float32))
        else:
            work.append(li)

    _obs_wire([leaves_g[li].size for li in work], rel_eb, topo_frac, n,
              block_k, bucket_elems)

    for bucket in _bucket_leaves([leaves_g[li].size for li in work],
                                 bucket_elems):
        lis = [work[j] for j in bucket]
        ge_l, eb_parts = [], []
        for li in lis:
            g32 = leaves_g[li].astype(jnp.float32).reshape(-1)
            e = leaves_e[li]
            ge = g32 if e is None else g32 + e.astype(jnp.float32).reshape(-1)
            ge_l.append(ge)
            eb_parts.append(jnp.max(jnp.abs(ge)))
        # one pmax for the whole bucket: per-leaf scalar scales stacked
        scales = jax.lax.pmax(jnp.stack(eb_parts), (axis,))
        ebs = jnp.maximum(scales * rel_eb, _EB_TINY)

        sizes = [ge.shape[0] for ge in ge_l]
        offs = [0]
        for sz in sizes:
            offs.append(offs[-1] + sz)
        q_l = [quantize(ge, ebs[j]) for j, ge in enumerate(ge_l)]
        deq_cat = jnp.concatenate(
            [dequantize(q, ebs[j]) for j, q in enumerate(q_l)])
        ge_cat = jnp.concatenate(ge_l)
        q_cat = jnp.concatenate(q_l)
        pad = (-q_cat.shape[0]) % block_k
        q_pad = jnp.pad(q_cat, (0, pad))

        side_vals, union = None, None
        ks = [protect_k(sz, topo_frac) for sz in sizes]
        if sum(ks) > 0:
            idx_l = [jax.lax.top_k(jnp.abs(ge), k)[1] + offs[j]
                     for j, (ge, k) in enumerate(zip(ge_l, ks)) if k > 0]
            own_idx = jnp.concatenate(idx_l)
            idx_all = ring_gather(own_idx, axis, n)      # (n, ktot) by origin
            union = idx_all.reshape(-1)                  # member-ordered
            side_vals = ge_cat[union]

        q_sum, vals_by_origin, _ = ring_allreduce_codes(
            q_pad, axis, n, rel_eb, side_vals=side_vals, block_k=block_k,
            backend=backend)
        q_sum = q_sum[:q_cat.shape[0]]

        gsum_cat = jnp.concatenate(
            [dequantize(q_sum[offs[j]:offs[j + 1]], ebs[j])
             for j in range(len(lis))])
        new_e_cat = _residual(ge_cat, deq_cat)
        if union is not None:
            exact = ordered_fold(vals_by_origin)         # == psum order
            gsum_cat = gsum_cat.at[union].set(exact)
            new_e_cat = new_e_cat.at[union].set(0.0)

        for j, li in enumerate(lis):
            g = leaves_g[li]
            sl = slice(offs[j], offs[j + 1])
            gbar = (gsum_cat[sl] / nf).reshape(g.shape).astype(g.dtype)
            out[li] = (gbar, new_e_cat[sl].reshape(g.shape))

    new_g = treedef.unflatten([p[0] for p in out])
    if err is None:
        new_e = treedef.unflatten([p[1] for p in out])
    else:
        new_e = treedef.unflatten([p[1].astype(e.dtype)
                                   for p, e in zip(out, leaves_e)])
    return new_g, new_e


# --------------------------------------------------------------------------
# wire accounting: static model + measured simulation (host-side)
# --------------------------------------------------------------------------

def packed_wire_summary(sizes: Sequence[int], rel_eb: float,
                        topo_frac: float, n_members: int,
                        block_k: int = BLOCK_K,
                        bucket_elems: int = BUCKET_ELEMS) -> dict:
    """Static bytes-shipped model of the packed ring for given leaf sizes.

    These are the ACTUAL ppermute payload sizes the compiled step moves
    per hop (the dryrun's HLO collective-permute parse sees the same
    buffers), not the ``code_bits * size`` estimate.  ``int32_*`` fields
    give the equivalent int32-ring reference for the same schedule.
    """
    sizes = [s for s in sizes if s > 0]
    w0 = base_width(rel_eb)
    hops = max(0, n_members - 1)
    body_hops = [0.0] * max(1, hops)
    idx_bytes = val_bytes = 0
    total_elems = 0
    for bucket in _bucket_leaves(list(sizes), bucket_elems):
        bsizes = [sizes[j] for j in bucket]
        p = sum(bsizes)
        p_pad = cdiv(p, block_k) * block_k
        b_blocks = p_pad // block_k
        ktot = sum(protect_k(sz, topo_frac) for sz in bsizes)
        u = n_members * ktot
        for h in range(1, hops + 1):
            w_cap = sum_width(w0, h)
            body_hops[h - 1] += (b_blocks * cdiv(block_k * w_cap, 8)
                                 + p_pad // 8 + b_blocks + 4 * u)
        idx_bytes += hops * 4 * ktot
        val_bytes += hops * 4 * u
        total_elems += p
    body_total = sum(body_hops) if hops else 0.0
    int32_hop = 4.0 * total_elems
    return {
        "n_members": n_members,
        "hops": hops,
        "base_width_bits": w0,
        "packed_bytes_per_hop": (body_total / hops if hops else 0.0),
        "packed_hop_bytes": [float(b) for b in (body_hops if hops else [])],
        "packed_bytes_per_step": float(body_total + idx_bytes),
        "sidecar_idx_bytes": float(idx_bytes),
        "sidecar_val_bytes": float(val_bytes),
        "int32_bytes_per_hop": int32_hop,
        "int32_bytes_per_step": float(hops * int32_hop + idx_bytes
                                      + val_bytes),
        "packed_vs_int32_per_hop": ((body_total / hops) / int32_hop
                                    if hops and int32_hop else 1.0),
    }


def simulate_hop_bytes(qs: jnp.ndarray, rel_eb: float,
                       block_k: int = BLOCK_K) -> dict:
    """Measured per-hop packed bytes for stacked member codes (no mesh).

    qs: (n, P) int32 codes (one row per member).  Replays the ring's
    partial-sum schedule on the host and packs every member's every-hop
    payload for real, returning mean measured (valid) and static shipped
    bytes per hop, plus the int32-ring reference.
    """
    n, p = qs.shape
    pad = (-p) % block_k
    qs = jnp.pad(qs.astype(jnp.int32), ((0, 0), (0, pad)))
    p_pad = p + pad
    b_blocks = p_pad // block_k
    w0 = base_width(rel_eb)
    fixed = p_pad // 8 + b_blocks            # sign plane + width bytes
    valid_hops, shipped_hops = [], []
    msg = qs                                  # row i: member i's partial sum
    for h in range(1, n):
        w_cap = sum_width(w0, h)
        mags = jnp.abs(msg).astype(jnp.uint32).reshape(n, b_blocks, block_k)
        widths = bitwidth(mags.max(axis=2))                   # (n, B)
        nbytes = (block_k * widths + 7) // 8
        valid_hops.append(float(jnp.mean(nbytes.sum(axis=1))) + fixed)
        shipped_hops.append(b_blocks * cdiv(block_k * w_cap, 8) + fixed)
        msg = jnp.roll(msg, 1, axis=0) + qs   # next partial sum per member
    int32_hop = 4.0 * p
    mean_valid = (sum(valid_hops) / len(valid_hops)) if valid_hops else 0.0
    mean_ship = (sum(shipped_hops) / len(shipped_hops)) if shipped_hops \
        else 0.0
    return {
        "hops": n - 1,
        "valid_bytes_per_hop": mean_valid,
        "shipped_bytes_per_hop": float(mean_ship),
        "int32_bytes_per_hop": int32_hop,
        "valid_vs_int32": mean_valid / int32_hop if int32_hop else 1.0,
        "shipped_vs_int32": mean_ship / int32_hop if int32_hop else 1.0,
    }
