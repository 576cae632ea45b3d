"""train_step factories: baseline pjit and compressed-DP (shard_map) modes.

Baseline: jax.jit with param/batch shardings; GSPMD inserts the DP gradient
all-reduce (bf16).  Compressed: the 'data' (and 'pod') axes are made manual
with jax.shard_map(axis_names=...) while 'model' stays auto, and the DP
reduction runs through dist.collectives.compressed_psum — the paper's
quantizer on the wire (error-bounded, error-feedback).  See DESIGN.md §2.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.dist.collectives import (WIRE_FORMATS, compressed_psum_tree,
                                    topo_compressed_psum_tree)
from repro.dist.sharding import batch_axes
from repro.models import lm
from repro.train.state import TrainState


def make_loss_fn(cfg) -> Callable:
    def loss(params, batch):
        return lm.loss_fn(params, cfg, batch)
    return loss


def make_train_step(cfg, optimizer, mesh=None, grad_compress: bool = False,
                    rel_eb: float = 1e-3,
                    topo_frac: Optional[float] = None,
                    wire_format: Optional[str] = None) -> Callable:
    """Returns step(state, batch) -> (state', metrics).

    ``topo_frac > 0`` upgrades the compressed DP reduction to the
    topology-aware collective: the per-member top ``topo_frac`` tail of
    each gradient leaf (by ``|g + err|``) rides an exact fp32 sidecar, so
    optimizer-driving extrema keep their exact values and rank order
    while the body stays ``rel_eb``-bounded.  ``None`` (default) defers
    to ``cfg.grad_topo_frac``; an explicit ``0.0`` forces the plain
    compressed psum regardless of the config.

    ``wire_format`` picks how the codes move: ``"int32"`` (full int32
    psum, accounting-only byte win) or ``"packed"`` (dist.ring bitpacked
    ppermute ring all-reduce — the compressed bytes ARE the wire).
    ``None`` defers to ``cfg.grad_wire_format``.
    """
    loss_fn = make_loss_fn(cfg)
    if topo_frac is None:
        topo_frac = getattr(cfg, "grad_topo_frac", 0.0)
    if wire_format is None:
        wire_format = getattr(cfg, "grad_wire_format", "int32")
    if wire_format not in WIRE_FORMATS:
        raise ValueError(f"unknown wire_format {wire_format!r}; "
                         f"expected one of {WIRE_FORMATS}")
    if topo_frac > 0.0 and not grad_compress:
        raise ValueError(
            "topo_frac > 0 requires grad_compress=True: the protected "
            "tail is a sidecar of the compressed collective, not of the "
            "uncompressed GSPMD all-reduce")
    if wire_format != "int32" and not grad_compress:
        raise ValueError(
            "wire_format='packed' requires grad_compress=True: only the "
            "compressed collective has codes to bitpack")

    if not grad_compress:
        def step(state: TrainState, batch):
            loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
            params, opt_state = optimizer.update(grads, state.opt_state,
                                                 state.params)
            new = TrainState(state.step + 1, params, opt_state, state.err)
            return new, {"loss": loss}
        return step

    assert mesh is not None, "compressed-DP mode needs the mesh"
    dp_axes = batch_axes(mesh)

    def per_shard(params, err, batch):
        # local-shard loss/grads; 'model' axis stays auto-parallel
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        if topo_frac > 0.0:
            grads, err = topo_compressed_psum_tree(
                grads, dp_axes, rel_eb, topo_frac, err,
                wire_format=wire_format)
        else:
            grads, err = compressed_psum_tree(grads, dp_axes, rel_eb, err,
                                              wire_format=wire_format)
        loss = jax.lax.pmean(loss, dp_axes)
        # NOTE: err is genuinely per-DP-member but leaves through
        # out_specs=P() (check_vma=False).  On-device across steps each
        # member keeps consuming its own residual shard, so EF-SGD is
        # exact in the steady loop; a host transfer (checkpoint) collapses
        # the tree to member 0's residual, which forfeits at most one
        # step's eb-scale compensation on restore.  The alternative — a
        # replicated pmean'd residual — would double the collective
        # volume and defeat the wire win.
        return loss, grads, err

    def step(state: TrainState, batch):
        batch_specs = jax.tree.map(
            lambda x: P(dp_axes, *([None] * (x.ndim - 1))), batch)
        sharded = shard_map(
            per_shard,
            mesh=mesh,
            in_specs=(P(), P(), batch_specs),
            out_specs=(P(), P(), P()),
            axis_names=set(dp_axes),
            check_vma=False,
        )
        loss, grads, err = sharded(state.params, state.err, batch)
        params, opt_state = optimizer.update(grads, state.opt_state,
                                             state.params)
        new = TrainState(state.step + 1, params, opt_state, err)
        return new, {"loss": loss}

    return step
