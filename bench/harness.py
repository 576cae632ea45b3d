"""One run of one cell: set-up, a timed closed loop, the check, the result.

``run_cell`` is the whole run after the command line is read.  The check
for a chip is a parameter so that the tests can drive a run on the CPU.
"""
from __future__ import annotations

import os
import shutil
import sys
import tempfile
import time

from bench import reference, spec
from bench.target import Target

CACHE_DIR = os.path.join(spec.ROOT, ".jax_cache")


class NoChip(RuntimeError):
    """JAX finds no TPU, too few chips, or kernels that do not resolve to
    compiled Pallas."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def require_chip(chips: int):
    """The devices to run on; raises ``NoChip`` unless JAX sees at least
    ``chips`` TPUs and the kernels resolve to compiled ``pallas``."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds "
                     f"{len(devices)}")
    from repro.kernels import ops
    backend = ops.resolve_backend(None)
    if backend != "pallas":
        raise NoChip(f"kernels resolve to {backend!r}, not compiled pallas")
    return devices[:chips]


def footprint(device) -> tuple:
    """(peak bytes in use, peak bytes reserved) of one chip.  On a TPU the
    runtime holds a program's scratch as reserved memory, apart from the
    arrays it counts as in use, so a call's footprint is the sum."""
    stats = device.memory_stats() or {}
    return (int(stats.get("peak_bytes_in_use", 0)),
            int(stats.get("peak_bytes_reserved", 0)))


def enable_cache() -> None:
    """JAX's persistent compilation cache at the fixed in-checkout path,
    for every program (none is too quick to keep)."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    from repro.utils import enable_compile_cache
    jax.config.update("jax_compilation_cache_dir", enable_compile_cache())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileLog:
    """JAX's compile events from the moment it is created: every trace,
    lower and compile step (``events``), the programs compiled by XLA
    (``compiled``: name and seconds) and the persistent-cache hits
    (``hits``).  JAX's listeners cannot be removed, so one pair of
    listeners, registered by the first log, feeds all of them."""

    _all = None

    def __init__(self):
        import jax
        if CompileLog._all is None:
            CompileLog._all = {"events": 0, "compiled": [], "hits": 0}

            def on_duration(event, duration, **kw):
                if event.startswith("/jax/core/compile/"):
                    CompileLog._all["events"] += 1
                if event == "/jax/core/compile/backend_compile_duration":
                    CompileLog._all["compiled"].append(
                        (kw.get("fun_name", "?"), duration))

            def on_event(event, **kw):
                if event == "/jax/compilation_cache/cache_hits":
                    CompileLog._all["hits"] += 1
            jax.monitoring.register_event_duration_secs_listener(on_duration)
            jax.monitoring.register_event_listener(on_event)
        a = CompileLog._all
        self._start = (a["events"], len(a["compiled"]), a["hits"])

    @property
    def events(self) -> int:
        return CompileLog._all["events"] - self._start[0]

    @property
    def compiled(self) -> list:
        return CompileLog._all["compiled"][self._start[1]:]

    @property
    def hits(self) -> int:
        return CompileLog._all["hits"] - self._start[2]


def reconstruction(target: Target, operation: str, out):
    """What the reference compares with the fields.  A compress call's
    streams go through the stored format: serialized, read back and
    decompressed by the batch entry point.  Returns (fields', stored
    bytes or None)."""
    import jax
    if operation == "decompress":
        return out, None
    blobs = target.serialize(out)
    rec = jax.block_until_ready(
        target.decompress(target.deserialize(blobs, like=out)))
    return rec, sum(len(b) for b in blobs)


def raw_bytes(config: dict) -> int:
    """The f32 bytes of one call's fields: the rate's numerator per call
    and the ratio's per stream set."""
    return int(config["fields_per_call"]) * spec.grid_points(config) * 4


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, check_chip=require_chip) -> dict:
    """Set up, run the window, check, and return the result object."""
    import jax
    devices = check_chip(cell.chips)
    enable_cache()
    setup_log = CompileLog()
    from bench.fields import make_fields
    cfg, traffic = cell.config, cell.traffic
    shape = tuple(cfg["grid"])
    n = int(cfg["fields_per_call"])
    eb = float(cfg["eb"])
    op = traffic["operation"]
    target = Target(cfg["compressor"], shape, eb)
    fields = jax.block_until_ready(make_fields(seed, n, shape,
                                               traffic["fields"]))
    if op == "compress":
        def call():
            return target.compress(fields)
    elif op == "decompress":
        stored = jax.block_until_ready(target.compress(fields))

        def call():
            return target.decompress(stored)
    else:
        raise ValueError(f"unknown operation {op!r}")
    jax.block_until_ready(call())           # warm-up: the window's shapes
    if trace:
        from repro import obs
        obs.enable()
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=_profile_options())
    compiles = CompileLog()
    setup_s = time.time() - t_start
    setup_compiled, setup_hits = setup_log.compiled, setup_log.hits
    ends = []
    t0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("bench.call"):
            out = jax.block_until_ready(call())
        ends.append(time.perf_counter() - t0)
        if ends[-1] >= seconds:
            break
    calls, window_s = len(ends), ends[-1]
    in_window = compiles.events
    if trace:
        jax.profiler.stop_trace()
    mem = [footprint(d) for d in devices]
    peak = max(in_use + reserved for in_use, reserved in mem)
    raw = raw_bytes(cfg)

    rec, stored_bytes = reconstruction(target, op, out)
    checks = reference.readings(fields, rec, eb, cfg["guarantees"])
    correct = reference.passed(checks)

    e2e = {f"{op}_GBps": calls * raw / window_s / 1e9, "setup_s": setup_s}
    if stored_bytes is not None:
        e2e["ratio"] = raw / stored_bytes
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": calls * n,
              "failed": 0 if correct else n}
    log(f"[device] {device['kind']} x{len(devices)}, platform "
        f"{device['platform']}")
    per_call = sorted(b - a for a, b in zip([0.0] + ends, ends))
    log(f"[window] {calls} calls of {n} fields in {window_s!r} s; "
        f"compilations in the window: {in_window}")
    log(f"[window] s per call: first {ends[0]!r}, min {per_call[0]!r}, "
        f"median {per_call[len(per_call) // 2]!r}, max {per_call[-1]!r}")
    log("[memory] per chip: " + ", ".join(
        f"peak_bytes_in_use {a} + peak_bytes_reserved {b}" for a, b in mem)
        + f"; memory_peak_bytes {peak}")
    log(f"[setup] {setup_s!r} s; persistent-cache hits {setup_hits}; "
        f"compiled: " + (", ".join(f"{name} {sec:.1f} s" for name, sec
                                   in setup_compiled) or "nothing"))
    log("[stream] per-field section capacities (bytes): " + " ".join(
        f"{'/'.join(str(k) for k in path)}={leaf.shape[1:]}"
        for path, leaf in jax.tree_util.tree_leaves_with_path(out)
        if getattr(leaf, "ndim", 0) > 1) if op == "compress" else
        "[stream] n/a in a decompress run")
    if trace:
        from bench import devtrace
        try:
            red = devtrace.reduce_dir(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = devtrace.Context(red, cell=cell, device_kind=device["kind"])
        metrics = {}
        for m in cell.per_layer:
            v = spec.reader(m["name"])(ctx)
            if v is None:
                log(f"warning: {m['name']} found nothing to read in this "
                    f"trace and is left out (a renamed scope or kernel?)")
            else:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": red.top_ops(10),
                               "idle_gaps": red.idle_gaps(10)}
    else:
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in e2e:
                raise KeyError(f"{cell.name} lists {m['name']}, which a "
                               f"{op} run does not measure")
            metrics[m["name"]] = {"value": e2e[m["name"]],
                                  "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
    per_field = checks.pop("fields")
    for i, p in enumerate(per_field):
        log(f"[field {i}] " + " ".join(f"{k} {v!r}" for k, v in p.items()))
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} limit {c['limit']!r}")
    log(f"correct: {correct}")
    result["checks"] = checks
    return result
