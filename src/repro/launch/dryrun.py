import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_FLAGS")
                           or "--xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be run as its own process (the XLA flag above is read at first jax
init).  For every cell this driver:

  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. eval_shape's the parameters (ShapeDtypeStruct — zero allocation),
  3. assigns shardings from dist.sharding rules,
  4. jits the right step (train_step / prefill / serve_step) with
     in_shardings/out_shardings, .lower()s with input_specs(), .compile()s,
  5. records memory_analysis(), cost_analysis() and the per-category
     collective byte counts parsed from the compiled HLO,
  6. writes results/dryrun/<cell>.json for the roofline stage.

Usage:
  python -m repro.launch.dryrun --arch gemma2_2b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES, runnable_cells
from repro.dist import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models import lm, registry, set_active_mesh
from repro.models.registry import ARCH_IDS
from repro.optim import adamw, constant
from repro.roofline.hlo import collective_bytes_from_text
from repro.serve.engine import serve_step
from repro.train.state import TrainState

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def _cost_dict(cost) -> dict:
    """The numeric entries of ``compiled.cost_analysis()``."""
    return {k: float(v) for k, v in (cost or {}).items()
            if isinstance(v, (int, float))}


def input_specs(cfg, shape_name: str):
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    sc = SHAPES[shape_name]
    b, s = sc.global_batch, sc.seq_len
    sds = jax.ShapeDtypeStruct
    i32, act = jnp.int32, cfg.activation_dtype

    if sc.mode in ("train", "prefill"):
        if cfg.frontend == "audio_frames":
            batch = {"embeds": sds((b, s, cfg.d_model), act),
                     "labels": sds((b, s), i32)}
        elif cfg.frontend == "vision_patches":
            npre = cfg.num_prefix_embeds
            batch = {"patch_embeds": sds((b, npre, cfg.d_model), act),
                     "tokens": sds((b, s - npre), i32)}
        else:
            batch = {"tokens": sds((b, s), i32)}
        return {"batch": batch}

    # decode: one new token against caches of length seq_len
    caches = lm.make_caches(cfg, b, s, spec=True)
    return {"tokens": sds((b, 1), i32), "caches": caches}


def _params_specs(cfg):
    return jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))


def _state_specs(cfg, params_sds, optimizer):
    opt_sds = jax.eval_shape(optimizer.init, params_sds)
    err = None
    return TrainState(jax.ShapeDtypeStruct((), jnp.int32), params_sds,
                      opt_sds, err)


def _opt_shardings(opt_sds, param_sh, mesh, cfg):
    """Optimizer state inherits the parameter shardings (master/m/v)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(mesh, P())
    return type(opt_sds)(rep, param_sh, param_sh, param_sh)


def _lower_cell(cfg, shape_name, mesh):
    """Build the jitted step for one cell and lower it (no compile)."""
    from repro.models.common import set_sharding_strategy
    sc = SHAPES[shape_name]
    # fsdp (pure-DP activations + fully sharded weights) is a training
    # strategy; serving keeps TP so weights stay resident (no per-layer
    # weight gathers on the latency path).
    strategy = cfg.sharding_strategy if sc.mode == "train" else "tp"
    if cfg.sharding_strategy == "fsdp" and sc.mode != "train":
        cfg = cfg.replace(sharding_strategy="tp")
    set_sharding_strategy(strategy)
    optimizer = adamw(constant(1e-4))
    params_sds = _params_specs(cfg)
    param_sh = shd.param_shardings(params_sds, cfg, mesh)
    specs = input_specs(cfg, shape_name)

    if sc.mode == "train":
        gc = getattr(cfg, "grad_compress", False)
        state_sds = _state_specs(cfg, params_sds, optimizer)
        err_sds, err_sh = None, None
        if gc:
            err_sds = params_sds
            err_sh = param_sh
        state_sds = state_sds._replace(err=err_sds)
        state_sh = TrainState(
            shd.replicated(jnp.zeros(()), mesh), param_sh,
            _opt_shardings(state_sds.opt_state, param_sh, mesh, cfg),
            err_sh)
        batch_sh = shd.data_sharding(specs["batch"], mesh,
                                     cfg.sharding_strategy)
        from repro.train.step import make_train_step
        step = make_train_step(cfg, optimizer, mesh=mesh, grad_compress=gc,
                               topo_frac=getattr(cfg, "grad_topo_frac", 0.0),
                               wire_format=getattr(cfg, "grad_wire_format",
                                                   "int32"))
        jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, None),
                         donate_argnums=(0,))
        return jitted.lower(state_sds, specs["batch"])

    if sc.mode == "prefill":
        batch_sh = shd.data_sharding(specs["batch"], mesh,
                                     cfg.sharding_strategy)
        fn = partial(lm.prefill, cfg=cfg)
        # shard the returned caches (logits left to the partitioner)
        out_sds = jax.eval_shape(lambda p, b: fn(p, batch=b), params_sds,
                                 specs["batch"])
        cache_out_sh = shd.cache_shardings(out_sds[1], cfg, mesh)
        jitted = jax.jit(lambda p, b: fn(p, batch=b),
                         in_shardings=(param_sh, batch_sh),
                         out_shardings=(None, cache_out_sh))
        return jitted.lower(params_sds, specs["batch"])

    cache_sh = shd.cache_shardings(specs["caches"], cfg, mesh)
    tok_sh = shd.data_sharding(specs["tokens"], mesh,
                                cfg.sharding_strategy)
    fn = partial(serve_step, cfg=cfg)
    jitted = jax.jit(
        lambda p, t, c: fn(p, tokens=t, caches=c),
        in_shardings=(param_sh, tok_sh, cache_sh),
        out_shardings=(None, None, cache_sh),
        donate_argnums=(2,))
    return jitted.lower(params_sds, specs["tokens"], specs["caches"])


def _grad_wire_model(cfg, mesh, rel_eb: float = 1e-3) -> dict:
    """Analytic compressed-gradient wire model for one train cell.

    The old model costed the compressed wire at ``code_bits`` per value
    only; this one uses the ``topo_wire_bits`` decomposition (quantized
    body + exact sidecar, which ``grad_topo_frac > 0`` adds) and, for
    ``grad_wire_format="packed"``, the ACTUAL packed bytes the ring moves
    per hop (``dist.ring.packed_wire_summary`` — the same buffer sizes
    the compiled HLO's collective-permutes carry).  ``rel_eb`` mirrors
    the ``make_train_step`` default the dry-run lowers with.
    """
    from repro.dist import ring
    from repro.dist.collectives import sidecar_bits
    from repro.dist.sharding import batch_axes

    n_dp = 1
    for a in batch_axes(mesh):
        n_dp *= int(mesh.shape[a])
    topo_frac = getattr(cfg, "grad_topo_frac", 0.0)
    wire_format = getattr(cfg, "grad_wire_format", "int32")
    params_sds = _params_specs(cfg)
    sizes = [int(x.size) for x in jax.tree.leaves(params_sds)]
    body_bits = ring.base_width(rel_eb) + 1       # static bound incl. sign
    body = sum(body_bits * s for s in sizes)
    side = sum(sidecar_bits(s, topo_frac, n_dp) for s in sizes)
    rec = {
        "wire_format": wire_format,
        "rel_eb": rel_eb,
        "topo_frac": topo_frac,
        "n_dp": n_dp,
        "body_bits_per_val": body_bits,
        "body_bits_per_member": body,
        "sidecar_bits_per_member": side,
        "topo_wire_bits_per_member": body + side,
    }
    if wire_format == "packed" and len(batch_axes(mesh)) == 1:
        rec["packed"] = ring.packed_wire_summary(sizes, rel_eb, topo_frac,
                                                 n_dp)
    return rec


def _costing_cfg(cfg, n_groups: int):
    _, tail = cfg.pattern_layers()
    layers = n_groups * len(cfg.layer_pattern) + len(tail)
    return cfg.replace(num_layers=layers, unroll_groups=True,
                       unroll_loss=True)


def _cost_record(cfg, shape_name, mesh):
    """flops/bytes/collectives extrapolated from 1- and 2-group unrolled
    compiles (exact for homogeneous stacks; see dryrun docstring)."""
    g_full = cfg.num_layers // len(cfg.layer_pattern)
    recs = []
    for g in (1, 2):
        lowered = _lower_cell(_costing_cfg(cfg, g), shape_name, mesh)
        compiled = lowered.compile()
        cost = _cost_dict(compiled.cost_analysis())
        coll = collective_bytes_from_text(compiled.as_text())
        recs.append((cost, coll))
    (c1, k1), (c2, k2) = recs

    def extra(a, b):
        return {k: a.get(k, 0.0) + (g_full - 1) * (b.get(k, 0.0) - a.get(k, 0.0))
                for k in set(a) | set(b) if not isinstance(a.get(k), dict)}

    cost = extra(c1, c2)
    coll = extra({k: v for k, v in k1.items() if k != "counts"},
                 {k: v for k, v in k2.items() if k != "counts"})
    return {"cost": cost, "collectives": coll, "groups_full": g_full}


def run_cell(arch: str, shape_name: str, multi_pod: bool, mesh=None,
             cfg=None, extra_tag: str = "", save: bool = True,
             costing: bool = True):
    """Lower+compile one cell; returns the result record."""
    t_start = time.time()
    mesh = mesh if mesh is not None else make_production_mesh(
        multi_pod=multi_pod)
    cfg = cfg if cfg is not None else registry.get_config(arch)
    sc = SHAPES[shape_name]
    set_active_mesh(mesh)

    with mesh:
        lowered = _lower_cell(cfg, shape_name, mesh)
        t_lower = time.time()
        compiled = lowered.compile()
        t_compile = time.time()

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        n_dev = mesh.devices.size
        try:
            hlo = compiled.as_text()
        except Exception:
            hlo = lowered.as_text()
        coll = collective_bytes_from_text(hlo)
        costing_rec = None
        if costing and not multi_pod:
            try:
                costing_rec = _cost_record(cfg, shape_name, mesh)
            except Exception as e:
                costing_rec = {"error": str(e)[:300]}

    grad_wire = None
    if sc.mode == "train" and getattr(cfg, "grad_compress", False):
        try:
            grad_wire = _grad_wire_model(cfg, mesh)
        except Exception as e:
            grad_wire = {"error": str(e)[:300]}

    record = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "n_devices": int(n_dev),
        "mode": sc.mode,
        "grad_wire": grad_wire,
        "lower_s": round(t_lower - t_start, 2),
        "compile_s": round(t_compile - t_lower, 2),
        "memory": _mem_dict(mem),
        "cost": _cost_dict(cost),
        "collectives": coll,
        "costing": costing_rec,
        "hlo_bytes": len(hlo),
    }
    print(f"[dryrun] {arch} x {shape_name} x "
          f"{record['mesh']}: compile {record['compile_s']}s, "
          f"flops={record['cost'].get('flops', 0):.3e}, "
          f"coll_bytes={coll.get('total', 0):.3e}", flush=True)
    print("  memory_analysis:", json.dumps(record["memory"]), flush=True)

    if save:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        tag = f"{arch}__{shape_name}__{record['mesh']}{extra_tag}"
        with open(os.path.join(RESULTS_DIR, tag + ".json"), "w") as f:
            json.dump(record, f, indent=1)
    return record


def _mem_dict(mem):
    out = {}
    for attr in ("generated_code_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "temp_size_in_bytes",
                 "alias_size_in_bytes", "peak_memory_in_bytes"):
        v = getattr(mem, attr, None)
        if v is not None:
            out[attr] = int(v)
    if out:
        n = 512 if jax.device_count() >= 512 else jax.device_count()
        out["per_device_total_gb"] = round(
            (out.get("argument_size_in_bytes", 0)
             + out.get("output_size_in_bytes", 0)
             + out.get("temp_size_in_bytes", 0)
             - out.get("alias_size_in_bytes", 0)) / 1e9, 3)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    if args.all:
        cells = runnable_cells(ARCH_IDS)
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    failures = []
    for arch, shape in cells:
        for mp in meshes:
            try:
                run_cell(arch, shape, mp)
            except Exception as e:
                traceback.print_exc()
                failures.append((arch, shape, mp, str(e)[:200]))
    if failures:
        print(f"[dryrun] FAILURES: {len(failures)}")
        for f in failures:
            print("   ", f)
        raise SystemExit(1)
    print("[dryrun] all cells compiled OK")


if __name__ == "__main__":
    main()
