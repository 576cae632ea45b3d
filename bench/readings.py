#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (not part of a run).

  python3 bench/readings.py --workload atm_topo.compress --seeds 1,2,3 \\
      [--faulty-seeds N] [--out FILE]

For each seed it makes the cell's fields and drives the cell's timed path
once, at the cell's own size, through the same calls as a run
(``harness.reconstruction``), and prints the reference's readings:

* sound: the program as it is, on every seed;
* control, on the first ``--faulty-seeds`` seeds (default 3): the same
  path one precision below the configuration's float32: the fields
  rounded to bfloat16 before the compress call, or, in a decompress cell,
  the sound reconstruction rounded to bfloat16;
* restore_skipped (TopoSZp cells), on the same seeds: the sound stream's
  SZp sections decompressed by ``szp_decompress_batch``, i.e. TopoSZp's
  restore stage left out.

One JSON object per line and kind; the last line is a summary (largest
sound reading and smallest faulty one per number).  Refuses to run
without a TPU, as a run does.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--faulty-seeds", type=int, default=3)
    p.add_argument("--out")
    args = p.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp
    from bench import harness, reference, spec
    from bench.fields import make_fields
    from bench.target import Target
    cell = spec.find_cell(args.workload, ROOT)
    try:
        harness.require_chip(cell.chips)
    except harness.NoChip as e:
        harness.log(f"refusing to run: {e}")
        return 2
    harness.enable_cache()
    cfg, traffic = cell.config, cell.traffic
    shape, n, eb = tuple(cfg["grid"]), cfg["fields_per_call"], cfg["eb"]
    op = traffic["operation"]
    target = Target(cfg["compressor"], shape, eb)

    def bf16(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    def rebuild(out):
        rec, _ = harness.reconstruction(target, op, jax.block_until_ready(out))
        return rec

    def sound(comp):
        return rebuild(comp if op == "compress" else target.decompress(comp))

    def control(fields, comp, rec):
        if op == "compress":
            return rebuild(target.compress(bf16(fields)))
        return bf16(rec)

    def skipped(fields, comp, rec):
        from repro.core.szp import szp_decompress_batch
        return szp_decompress_batch(comp.szp, shape, eb)

    faulty = {"control": control}
    if cfg["compressor"] == "toposzp":
        faulty["restore_skipped"] = skipped
    kinds = ["sound", *faulty]
    rows = []
    out = open(args.out, "a") if args.out else None

    def emit(seed, kind, t, fields, rec):
        r = reference.readings(fields, rec, eb, cfg["guarantees"])
        r.pop("fields")
        row = {"workload": cell.name, "seed": seed, "kind": kind,
               "seconds": time.perf_counter() - t,
               **{k: v["value"] for k, v in r.items()}}
        rows.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    seeds = [int(s) for s in args.seeds.split(",")]
    for i, seed in enumerate(seeds):
        fields = jax.block_until_ready(make_fields(seed, n, shape,
                                                   traffic["fields"]))
        t = time.perf_counter()
        comp = jax.block_until_ready(target.compress(fields))
        rec = jax.block_until_ready(sound(comp))
        emit(seed, "sound", t, fields, rec)
        if i < args.faulty_seeds:
            for kind, fn in faulty.items():
                t = time.perf_counter()
                emit(seed, kind, t, fields,
                     jax.block_until_ready(fn(fields, comp, rec)))
        del comp, rec
    summary = {"workload": cell.name, "seeds": len({r["seed"] for r in rows})}
    for kind in kinds:
        agg = min if kind != "sound" else max
        sel = [r for r in rows if r["kind"] == kind]
        summary[kind] = {k: agg(r[k] for r in sel) for k in sel[0]
                         if k not in ("workload", "seed", "kind", "seconds")}
    print(json.dumps(summary), flush=True)
    if out:
        out.write(json.dumps(summary) + "\n")
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
