#!/usr/bin/env python3
"""Chip smoke test: the TopoSZp compressor end to end on a TPU.

  python chip_smoke.py               # one chip: CESM-ATM 1800x3600 fields
  python chip_smoke.py --four-chips  # four chips: compressed DP all-reduce

One chip: N >= 4 CESM-ATM-shaped fields (``make_dataset("ATM")``, N picked
from the compiled program's memory analysis) go through the user entry
points with compiled Pallas kernels — ``toposzp_compress_batch``
(resident and classic) + ``toposzp_decompress_batch``, single-field
``toposzp_compress``/``toposzp_decompress`` and ``szp_compress``/
``szp_decompress``, and the ``core.io`` serialize/deserialize round trip —
at eb 1e-3 and 1e-4.  Checks: max|err| <= 2*eb (<= eb for SZp) up to f32
rounding (4 ulps of the field's magnitude), zero false positives and
false types, and every serialized stream byte-identical to the same call
with ``backend="jnp"`` (the plain reference) on the chip; the batched resident stream is held to the jnp classic batch call, whose
bytes the resident path reproduces (the jnp resident program compiles all
six XLA bucket packs and would take minutes longer to build).

Four chips: the topology-aware compressed gradient all-reduce
(``topo_compressed_psum_tree``, ``int32`` and ``packed`` wire) against plain
``psum`` on a ``data=4`` mesh, with gradient leaves shaped like
``minicpm_2b`` at its published widths, depth cut to fit.

The script refuses to run (non-zero exit, no result line) unless JAX sees
a TPU and the kernels resolve to compiled Pallas.  Every phase runs in
this one process; a failed check raises and the exit code is non-zero.
Timings, ratios and memory figures printed on the way are informational.
The last line of stdout is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

EBS = (1e-3, 1e-4)
DATASET = "ATM"


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class Calls:
    """Named calls run to completion and timed.

    The first run of a name is timed too: it includes the compile unless
    the program was compiled ahead.  Pipelines run their first pass
    concurrently in threads, so their programs compile in parallel;
    ``warm`` then re-runs each call alone."""

    def __init__(self):
        self.cold = {}
        self.fns = {}

    def __call__(self, name: str, fn):
        import jax
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        self.cold[name] = time.perf_counter() - t0
        self.fns[name] = fn
        return out

    def warm(self) -> None:
        import jax
        for name, fn in self.fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            log(f"  {name}: first {self.cold[name]:.3f} s, "
                f"warm {time.perf_counter() - t0:.4f} s")


def run_concurrently(pipelines):
    """Run ``{name: thunk}`` in threads; re-raise the first failure."""
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(max_workers=len(pipelines)) as ex:
        futs = {k: ex.submit(fn) for k, fn in pipelines.items()}
        return {k: f.result() for k, f in futs.items()}


def _bytes_limit(dev) -> int:
    return dev.memory_stats()["bytes_limit"]


def _program_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


# --------------------------------------------------------------------------
# one chip: the field compressor
# --------------------------------------------------------------------------

def _pick_n_fields(dev, shape, eb) -> int:
    """N from the memory analysis of the compress pass-1 programs (the
    largest program of each pipeline), compiled at N=4 for both backends.

    The pallas pipelines run together (batch, single toposzp, single
    SZp), then the jnp references (batch, single).  In each phase the
    pass-1 programs live at once, batch bytes scaled to N, must fit in
    half the chip, which leaves the other half to pass 2, decompress and
    the results kept.  N = 8 if that holds at 8, else 4, which must
    hold.  The compiles land in the persistent cache, so the pipelines
    reuse them."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.core.toposzp import DEFAULT_BLOCK, _measure_batch, _measure_one
    from repro.kernels import ops
    limit = _bytes_limit(dev)

    def compile_(fn, lead, backend):
        spec = jax.ShapeDtypeStruct(lead + shape, jnp.float32,
                                    sharding=SingleDeviceSharding(dev))
        return lambda: fn.lower(spec, eb, block=DEFAULT_BLOCK,
                                backend=backend).compile()

    kernels = ops.resolve_backend(None)   # "pallas": main() checked it
    compiled = run_concurrently({
        (kind, backend): compile_(fn, lead, backend)
        for kind, fn, lead in (("batch", _measure_batch, (4,)),
                               ("single", _measure_one, ()))
        for backend in (kernels, "jnp")})
    b = {k: _program_bytes(c) for k, c in compiled.items()}
    for (kind, backend), v in sorted(b.items()):
        log(f"  pass 1 {kind} ({'N=4' if kind == 'batch' else '1 field'}), "
            f"{backend}: program bytes {v}")

    def phases(n):
        return {kernels: n * b["batch", kernels] // 4
                + 2 * b["single", kernels],
                "jnp": n * b["batch", "jnp"] // 4 + b["single", "jnp"]}

    for n in (8, 4):
        need = phases(n)
        log(f"  N={n}: pass-1 bytes live per phase {need}, limit/2 "
            f"{limit // 2} (bytes_limit {limit})")
        if max(need.values()) <= limit // 2:
            return n
    raise SmokeFailure(f"N=4 fields of {shape} do not fit half the chip")


def run_one_chip(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import io as cio
    from repro.core.metrics import false_cases_host, max_abs_error
    from repro.core.szp import szp_compress, szp_decompress
    from repro.core.toposzp import (batch_slice, toposzp_compress,
                                    toposzp_compress_batch,
                                    toposzp_decompress,
                                    toposzp_decompress_batch)
    from repro.data.fields import DATASETS, make_dataset

    dev = jax.devices()[0]
    shape = DATASETS[DATASET]
    t0 = time.perf_counter()
    n = _pick_n_fields(dev, shape, EBS[0])
    log(f"[size] N={n} fields of {DATASET} {shape[0]}x{shape[1]} "
        f"({time.perf_counter() - t0:.1f} s)")
    host = np.stack(make_dataset(DATASET, n_fields=n, seed=seed))
    fields = jax.device_put(jnp.asarray(host), dev)
    f0 = fields[0]
    raw = shape[0] * shape[1] * 4
    calls = Calls()

    def topo_blobs(comp, eb):
        return [cio.serialize_toposzp(batch_slice(comp, i), shape, eb)
                for i in range(n)]

    # -- the pipelines; each runs both error bounds in order -------------
    def batch():
        out = {}
        for eb in EBS:
            res = calls(f"toposzp_compress_batch resident=True eb={eb:g}",
                        lambda: toposzp_compress_batch(fields, eb,
                                                       resident=True))
            cls = calls(f"toposzp_compress_batch resident=False eb={eb:g}",
                        lambda: toposzp_compress_batch(fields, eb))
            rec = calls(f"toposzp_decompress_batch eb={eb:g}",
                        lambda: toposzp_decompress_batch(res, shape, eb))
            out[eb] = res, cls, rec
        return out

    def topo_single():
        out = {}
        for eb in EBS:
            comp = calls(f"toposzp_compress eb={eb:g}",
                         lambda: toposzp_compress(f0, eb))
            rec = calls(f"toposzp_decompress eb={eb:g}",
                        lambda: toposzp_decompress(comp, shape, eb))
            blob = cio.serialize_toposzp(comp, shape, eb)
            comp2, shape2, eb2, _ = cio.deserialize_toposzp(blob)
            check(shape2 == shape and eb2 == eb, "toposzp header round trip")
            check(cio.serialize_toposzp(comp2, shape2, eb2) == blob,
                  "toposzp deserialize -> serialize is not the identity")
            rec2 = toposzp_decompress(comp2, shape2, eb2)
            out[eb] = blob, rec, rec2
        return out

    def szp_single():
        out = {}
        for eb in EBS:
            parts = calls(f"szp_compress eb={eb:g}",
                          lambda: szp_compress(f0, eb))
            rec = calls(f"szp_decompress eb={eb:g}",
                        lambda: szp_decompress(parts, shape, eb))
            blob = cio.serialize_szp(parts, shape, eb)
            parts2, shape2, eb2, _ = cio.deserialize_szp(blob)
            check(shape2 == shape and eb2 == eb, "szp header round trip")
            rec2 = szp_decompress(parts2, shape2, eb2)
            out[eb] = blob, rec, rec2
        return out

    def reference_batch():
        # the classic jnp batch only: its resident twin compiles all six
        # XLA bucket packs and takes minutes longer to compile at this
        # size; the resident pallas stream must equal it byte for byte
        return {eb: topo_blobs(toposzp_compress_batch(
                    fields, eb, backend="jnp"), eb) for eb in EBS}

    def reference_single():
        return {eb: (cio.serialize_toposzp(toposzp_compress(
                    f0, eb, backend="jnp"), shape, eb),
                     cio.serialize_szp(szp_compress(
                         f0, eb, backend="jnp"), shape, eb))
                for eb in EBS}

    # the pallas pipelines together (their programs compile in
    # parallel), then the jnp references, so the two never share the chip
    t0 = time.perf_counter()
    got = run_concurrently({
        "batch": batch, "topo_single": topo_single, "szp_single": szp_single})
    log(f"[run] 3 pallas pipelines x {len(EBS)} error bounds, cold, "
        f"concurrent: {time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    got.update(run_concurrently({"reference_batch": reference_batch,
                                 "reference_single": reference_single}))
    log(f"[run] 2 jnp reference pipelines, cold, concurrent: "
        f"{time.perf_counter() - t0:.1f} s")

    # -- checks ------------------------------------------------------------
    def guarantees(label, orig, rec, bound, topo=True):
        # the bound holds in exact arithmetic; the f32 quantizer and
        # reconstruction each round by up to an ulp of the values (the
        # TPU's f32 division is not correctly rounded), so the check
        # allows 4 ulps of max|x| + bound, as test_train_and_ckpt does
        err = float(max_abs_error(orig, rec))
        xmax = float(jnp.abs(orig).max())
        tol = bound + 4 * float(np.spacing(np.float32(xmax + bound)))
        fc = false_cases_host(orig, rec)
        log(f"    {label}: max|err| {err!r} (bound {bound:g}, with f32 "
            f"rounding {tol!r}), FP {fc['FP']} FT {fc['FT']} FN {fc['FN']} "
            f"of {fc['n_cp']} critical points")
        check(err <= tol, f"{label}: max|err| {err} > {tol}")
        if topo:
            check(fc["FP"] == 0 and fc["FT"] == 0,
                  f"{label}: false cases {fc}")

    for eb in EBS:
        res, cls, rec = got["batch"][eb]
        ref = got["reference_batch"][eb]
        blobs = topo_blobs(res, eb)
        check(blobs == ref,
              f"eb={eb}: resident batch streams differ from backend=jnp")
        check(topo_blobs(cls, eb) == ref,
              f"eb={eb}: classic batch streams differ from backend=jnp")
        log(f"[toposzp batch] eb={eb:g}: {n} streams byte-identical to "
            f"backend=jnp, resident and classic")
        for i in range(n):
            guarantees(f"field {i} ratio {raw / len(blobs[i]):.3f}",
                       fields[i], rec[i], 2 * eb)

        blob, rec0, rec2 = got["topo_single"][eb]
        check(blob == got["reference_single"][eb][0],
              f"eb={eb}: single toposzp stream differs from backend=jnp")
        check(blob == blobs[0], f"eb={eb}: single and batch streams differ")
        check(bool(jnp.array_equal(rec2, rec0)),
              "toposzp: decompress of the deserialized stream differs")
        log(f"[toposzp single] eb={eb:g}: stream identical to backend=jnp "
            f"and to the batch; io round trip {len(blob)} bytes, "
            f"decompress identical")
        guarantees(f"field 0 ratio {raw / len(blob):.3f}", f0, rec0, 2 * eb)

        sblob, srec, srec2 = got["szp_single"][eb]
        check(sblob == got["reference_single"][eb][1],
              f"eb={eb}: szp stream differs from backend=jnp")
        check(bool(jnp.array_equal(srec2, srec)),
              "szp: decompress of the deserialized stream differs")
        log(f"[szp single] eb={eb:g}: stream identical to backend=jnp; io "
            f"round trip {len(sblob)} bytes, decompress identical")
        guarantees(f"field 0 ratio {raw / len(sblob):.3f}", f0, srec, eb,
                   topo=False)

    log("[timing] each call alone, after the cold pass")
    calls.warm()
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"[memory] peak_bytes_in_use {peak}")


# --------------------------------------------------------------------------
# four chips: the compressed DP gradient all-reduce
# --------------------------------------------------------------------------

ARCH = "minicpm_2b"
REL_EB = 1e-3
TOPO_FRAC = 1e-3
GRID_BITS = 26   # gradient values are multiples of 2^-26 below 2^-8


def grad_leaf_shapes(cfg):
    """Gradient leaf shapes of ``cfg``'s parameter tree (no allocation)."""
    import jax
    from repro.models import lm
    return jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))


def _tree_bytes(tree) -> int:
    import jax
    return sum(4 * x.size for x in jax.tree.leaves(tree))


def make_dp_programs(mesh, n: int):
    """Compressed all-reduce per wire format, and the checks against the
    plain ``psum`` (one program: it holds the sums only while it reduces
    them to scalars, so it fits beside both wires' results)."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.dist.collectives import protect_k, topo_compressed_psum_tree

    def member(tree):
        return jax.tree.map(lambda a: a[0], tree)

    def compressed(wire):
        def f(g):
            g = member(g)
            err = jax.tree.map(jnp.zeros_like, g)
            gbar, new_e = topo_compressed_psum_tree(
                g, "data", REL_EB, TOPO_FRAC, err, wire_format=wire)
            return gbar, jax.tree.map(lambda a: a[None], new_e)
        return jax.jit(shard_map(f, mesh=mesh, in_specs=P("data"),
                                 out_specs=(P(), P("data")),
                                 check_vma=False))

    def checks(g, gbar_i, gbar_p, err_i, err_p):
        """-> (packed == int32, worst body excess over the n*eb bound per
        wire, protected entries bit-exact per wire)."""
        same = jnp.array(True)
        for a, b in zip(jax.tree.leaves((gbar_i, err_i)),
                        jax.tree.leaves((gbar_p, err_p))):
            same &= jnp.array_equal(a, b)
        worst = {"int32": jnp.array(-jnp.inf), "packed": jnp.array(-jnp.inf)}
        prot = {"int32": jnp.array(True), "packed": jnp.array(True)}
        for a, gi, gp in zip(jax.tree.leaves(member(g)),
                             jax.tree.leaves(gbar_i), jax.tree.leaves(gbar_p)):
            flat = a.reshape(-1)
            total = jax.lax.psum(flat, "data")            # the plain psum
            idx = jax.lax.top_k(jnp.abs(flat),
                                protect_k(flat.shape[0], TOPO_FRAC))[1]
            union = jax.lax.all_gather(idx, "data", tiled=True)
            eb = REL_EB * jax.lax.pmax(jnp.max(jnp.abs(flat)), "data")
            for wire, gb in (("int32", gi), ("packed", gp)):
                gb = gb.reshape(-1)
                worst[wire] = jnp.maximum(worst[wire], jnp.max(
                    jnp.abs(n * gb - total) - n * eb * (1 + 1e-4)))
                prot[wire] &= jnp.all(gb[union] == (total / n)[union])
        same = jax.lax.pmin(same.astype(jnp.int32), "data") > 0
        return same, worst, prot

    check = jax.jit(shard_map(
        checks, mesh=mesh,
        in_specs=(P("data"), P(), P(), P("data"), P("data")),
        out_specs=P(), check_vma=False))
    return {"int32": compressed("int32"), "packed": compressed("packed"),
            "check": check}


def make_grads(mesh, shapes, n: int, seed: int):
    """Per-member gradient leaves (n, *shape) f32, sharded over ``data``.

    Values are normal(0, 2^-10) rounded to the 2^-26 grid and clipped
    below 2^-8, so every f32 sum of n <= 4 members is exact whatever the
    order: the plain psum is then the exact sum, and "bit-exact" does not
    depend on the collective's reduction order."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    leaves, treedef = jax.tree.flatten(shapes)
    lim = 2.0 ** (GRID_BITS - 8) - 1

    def gen(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, s in zip(keys, leaves):
            z = jax.random.normal(k, (n,) + s.shape, jnp.float32)
            q = jnp.clip(jnp.round(z * 2.0 ** (GRID_BITS - 10)), -lim, lim)
            out.append(q * 2.0 ** -GRID_BITS)
        return treedef.unflatten(out)

    shard = NamedSharding(mesh, P("data"))
    return jax.jit(gen, out_shardings=shard)(jax.random.PRNGKey(seed))


def run_four_chips(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.models import registry

    devs = jax.devices()
    check(len(devs) == 4, f"--four-chips needs 4 devices, JAX sees {len(devs)}")
    n = len(devs)
    mesh = Mesh(np.array(devs), ("data",))
    progs = make_dp_programs(mesh, n)
    cfg = registry.get_config(ARCH)
    limit = _bytes_limit(devs[0])
    shard = NamedSharding(mesh, P("data"))

    def compile_at(layers):
        """The three programs at ``layers`` deep, compiled concurrently
        (they run one at a time), and their peak bytes per chip: each
        program with the results live beside it (the packed wire's while
        the int32 wire runs; the check takes every result as argument)."""
        shapes = grad_leaf_shapes(cfg.replace(num_layers=layers))
        spec = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            (n,) + s.shape, jnp.float32, sharding=shard), shapes)
        mean = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, jnp.float32, sharding=NamedSharding(mesh, P())), shapes)
        t0 = time.perf_counter()
        compiled = run_concurrently({
            "int32": lambda: progs["int32"].lower(spec).compile(),
            "packed": lambda: progs["packed"].lower(spec).compile(),
            "check": lambda: progs["check"].lower(
                spec, mean, mean, spec, spec).compile()})
        for name, c in compiled.items():
            m = c.memory_analysis()
            log(f"  {layers} layer(s), {name}: per-chip program bytes "
                f"{_program_bytes(c)} (temp {m.temp_size_in_bytes}); "
                f"Pallas kernels: {'tpu_custom_call' in c.as_text()}")
        log(f"[compile] {layers} layer(s), 3 programs concurrently: "
            f"{time.perf_counter() - t0:.1f} s")
        pb = {k: _program_bytes(c) for k, c in compiled.items()}
        peak = max(pb["packed"], pb["check"], pb["int32"] + compiled[
            "packed"].memory_analysis().output_size_in_bytes)
        return shapes, compiled, peak

    # depth cut: compile one layer, scale its peak with the gradient
    # bytes to the deepest stack that fits 90% of the chip, and step
    # back until the compiled programs fit
    shapes, compiled, peak = compile_at(1)
    per_byte = peak / _tree_bytes(shapes)
    layers = max([d for d in range(1, cfg.num_layers + 1)
                  if per_byte * _tree_bytes(grad_leaf_shapes(
                      cfg.replace(num_layers=d))) <= 0.9 * limit] or [1])
    while layers > 1:
        deeper = compile_at(layers)
        if deeper[2] <= limit:
            shapes, compiled, peak = deeper
            break
        layers -= 1
    check(peak <= limit, f"one {ARCH} layer needs {peak} bytes per chip, "
          f"more than bytes_limit {limit}")
    log(f"[size] {ARCH}: published widths (d_model {cfg.d_model}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.padded_vocab}); depth cut {cfg.num_layers} "
        f"-> {layers} layers by memory ({_tree_bytes(shapes)} gradient "
        f"bytes per member, peak {peak} of bytes_limit {limit} per chip)")

    grads = make_grads(mesh, shapes, n, seed)
    for leaf in jax.tree.leaves(grads):
        where = {sh.device for sh in leaf.addressable_shards}
        check(len(where) == n and len(leaf.addressable_shards) == n,
              f"gradient leaf {leaf.shape} sits on {len(where)} devices")
    log(f"[mesh] data={n}: every leaf's {n} shards on {n} distinct devices "
        f"({', '.join(str(d.id) for d in devs)})")

    log(f"[all-reduce] rel_eb={REL_EB:g} topo_frac={TOPO_FRAC:g}, "
        f"{len(jax.tree.leaves(grads))} leaves")

    def timed(name, fn):
        # one run each: the programs were compiled ahead, and a second
        # run of the packed wire at this size does not fit the time limit
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        log(f"  {name}: {time.perf_counter() - t0:.3f} s")
        return out

    gbar_p, err_p = timed("topo_compressed_psum_tree wire=packed",
                          lambda: compiled["packed"](grads))
    gbar_i, err_i = timed("topo_compressed_psum_tree wire=int32",
                          lambda: compiled["int32"](grads))
    same, worst, prot = timed("checks against plain psum", lambda: compiled[
        "check"](grads, gbar_i, gbar_p, err_i, err_p))
    check(bool(same), "packed wire differs from int32 wire")
    log("    packed == int32: bit-identical mean and error feedback")
    for wire in ("int32", "packed"):
        w, ok = float(worst[wire]), bool(prot[wire])
        log(f"    {wire}: body worst |n*mean - psum| - n*eb = {w:.3g} "
            f"(<= 0 required); protected entries bit-exact: {ok}")
        check(w <= 0.0, f"{wire}: body exceeds the n*eb bound")
        check(ok, f"{wire}: protected entries are not bit-exact")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    log(f"[memory] peak_bytes_in_use per chip {peaks}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the compressed DP all-reduce on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    from repro.kernels import ops
    from repro.utils import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r}); "
              f"refusing to run", file=sys.stderr)
        return 2
    backend = ops.resolve_backend(None)
    if backend != "pallas":
        print(f"chip_smoke: kernels resolve to {backend!r}, not compiled "
              f"'pallas'; refusing to run", file=sys.stderr)
        return 2
    log(f"[env] jax {jax.__version__}, device_kind {dev.device_kind}, "
        f"{len(jax.devices())} device(s), backend {backend}")
    log(f"[env] compile cache {enable_compile_cache()}")
    if args.four_chips:
        run_four_chips(args.seed)
    else:
        run_one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
