"""End-to-end training driver.

CPU-runnable at reduced scale (smoke configs) and the same code path the
production mesh would launch:

  PYTHONPATH=src python -m repro.launch.train --arch minicpm_2b --smoke \
      --steps 100 --batch 8 --seq 64 --ckpt-dir /tmp/ck --grad-compress
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro import faults, obs
from repro.ckpt import CheckpointManager
from repro.data import token_batches
from repro.launch.mesh import make_test_mesh
from repro.models import lm, registry, set_active_mesh
from repro.optim import adamw, wsd
from repro.train import init_state, make_train_step, train_loop
from repro.utils import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm_2b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU scale)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--ckpt-mode", choices=["raw", "szp", "toposzp"],
                    default=None,
                    help="v2 leaf mode for large f32 leaves: raw bytes, "
                         "error-bounded SZp, or TopoSZp (critical points "
                         "and rank order exact under a 2*eb bound); unset "
                         "defers to cfg.ckpt_mode")
    ap.add_argument("--ckpt-eb", type=float, default=None,
                    help="absolute error bound for lossy checkpoint modes; "
                         "unset defers to cfg.ckpt_eb")
    ap.add_argument("--ckpt-sync", action="store_true",
                    help="serialize+fsync on the step loop thread instead "
                         "of the async background writer")
    ap.add_argument("--max-recoveries", type=int, default=0,
                    help="how many mid-run device-loss events the loop "
                         "absorbs by rolling back to the last committed "
                         "checkpoint and rebuilding the mesh (0 = crash, "
                         "the pre-elastic behavior)")
    ap.add_argument("--barrier-timeout", type=float, default=None,
                    metavar="S",
                    help="coordinated-commit barrier timeout in seconds "
                         "(multi-process saves; default 120)")
    ap.add_argument("--inject-device-loss", default=None,
                    metavar="STEP[:KEEP]",
                    help="fault injection: raise a DeviceLoss at STEP, "
                         "keeping the first KEEP devices (default: all, "
                         "i.e. a soft restart); exercises the elastic "
                         "recovery path end to end")
    ap.add_argument("--kernel-backend",
                    choices=["auto", "pallas", "interpret", "jnp"],
                    default=None,
                    help="TopoSZp kernel dispatch for lossy checkpoint "
                         "blobs (core/szp, core/toposzp): auto picks "
                         "pallas on TPU and the jnp oracle elsewhere; "
                         "unset defers to cfg.kernel_backend")
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--rel-eb", type=float, default=1e-4)
    ap.add_argument("--topo-frac", type=float, default=None,
                    help="protected top-|g| tail fraction (TopoSZp-aware "
                         "collective); 0 forces the plain compressed psum, "
                         "unset defers to cfg.grad_topo_frac")
    ap.add_argument("--wire-format", choices=["int32", "packed"],
                    default=None,
                    help="compressed-collective wire: int32 code psum or "
                         "the dist.ring bitpacked ppermute ring all-reduce; "
                         "unset defers to cfg.grad_wire_format")
    ap.add_argument("--data-parallel", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--obs", action="store_true",
                    help="enable repro.obs (zero-sync spans/counters; "
                         "periodic [obs] lines every log_every steps); "
                         "also on via REPRO_OBS=1 or cfg.obs")
    ap.add_argument("--obs-trace", default=None, metavar="PATH",
                    help="write a Chrome-trace JSON (open at "
                         "ui.perfetto.dev) on exit; implies --obs")
    ap.add_argument("--obs-jsonl", default=None, metavar="PATH",
                    help="stream obs span/error events to PATH as JSON "
                         "lines; implies --obs")
    args = ap.parse_args()

    cfg = (registry.get_smoke_config(args.arch) if args.smoke
           else registry.get_config(args.arch))
    if args.obs or args.obs_trace or args.obs_jsonl or cfg.obs:
        obs.enable()
    if args.obs_jsonl:
        obs.configure(jsonl=args.obs_jsonl)
    mesh = None
    if args.data_parallel * args.model_parallel > 1:
        mesh = make_test_mesh(args.data_parallel, args.model_parallel)
        set_active_mesh(mesh)

    params = lm.init_params(cfg, jax.random.PRNGKey(args.seed))
    print(f"[train] arch={cfg.name} params={lm.param_count(params):,}")
    optimizer = adamw(wsd(args.lr, warmup=max(args.steps // 10, 1),
                          stable=args.steps // 2, decay=args.steps // 2))
    state = init_state(params, optimizer, args.grad_compress)
    step_fn = make_train_step(cfg, optimizer, mesh=mesh,
                              grad_compress=args.grad_compress,
                              rel_eb=args.rel_eb,
                              topo_frac=args.topo_frac,
                              wire_format=args.wire_format)

    def batches():
        for b in token_batches(cfg, args.batch, args.seq, seed=args.seed,
                               start_step=int(state.step)):
            yield {k: jnp.asarray(v) for k, v in b.items()}

    manager = None
    if args.ckpt_dir is not None:
        mgr_kw = {}
        if args.barrier_timeout is not None:
            mgr_kw["barrier_timeout_s"] = args.barrier_timeout
        manager = CheckpointManager(
            args.ckpt_dir,
            mode=args.ckpt_mode if args.ckpt_mode is not None
            else cfg.ckpt_mode,
            eb=args.ckpt_eb if args.ckpt_eb is not None else cfg.ckpt_eb,
            async_write=cfg.ckpt_async and not args.ckpt_sync,
            kernel_backend=args.kernel_backend if args.kernel_backend
            is not None else cfg.kernel_backend, **mgr_kw)

    if args.inject_device_loss is not None:
        step_s, _, keep_s = args.inject_device_loss.partition(":")
        faults.install(faults.FaultPlan(sites={
            "loop.step": faults.Fault(
                kind="device_loss", at=int(step_s),
                keep=int(keep_s) if keep_s else None)}))

    def rebuild_step(new_mesh):
        # shard_map steps close over the mesh; rebuild against the one
        # the elastic recovery produced (and point the models at it)
        set_active_mesh(new_mesh)
        return make_train_step(cfg, optimizer, mesh=new_mesh,
                               grad_compress=args.grad_compress,
                               rel_eb=args.rel_eb,
                               topo_frac=args.topo_frac,
                               wire_format=args.wire_format)

    ctx = mesh if mesh is not None else _nullcontext()
    with ctx:
        state, report = train_loop(
            state, step_fn, batches(), num_steps=args.steps,
            ckpt_manager=manager, ckpt_every=args.ckpt_every,
            mesh=mesh, model_parallel=args.model_parallel,
            max_recoveries=args.max_recoveries,
            rebuild_step=rebuild_step if args.max_recoveries else None)
    if report.resharded:
        print(f"[train] elastic restore: checkpoint mesh "
              f"{report.saved_mesh} resharded onto {report.restore_mesh}")
    for ev in report.recoveries:
        print(f"[train] recovered from device loss at step {ev['step']}: "
              f"rolled back to {ev['restored_from']}, mesh {ev['mesh']} "
              f"({ev['recovery_s'] * 1e3:.0f} ms)")
    print(f"[train] done: loss {report.losses[0]:.4f} -> "
          f"{report.losses[-1]:.4f} over {report.steps_run} steps; "
          f"stragglers={len(report.straggler_events)}")
    if obs.enabled():
        print("[obs] " + obs.summary_line())
        if args.obs_trace:
            print(f"[obs] chrome trace -> "
                  f"{obs.export_chrome_trace(args.obs_trace)}")


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
