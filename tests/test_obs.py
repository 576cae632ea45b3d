"""repro.obs coverage: span nesting + thread-safety, counter/gauge
exactness against the ring wire model and the serve engine's own
accounting, Chrome-trace/JSONL export validity, the async-writer error
surface, and the zero-sync regression proof (transfer_guard + single-jit
round-trip with obs ENABLED)."""
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro import obs
from repro.ckpt import AsyncWriteError, AsyncWriter, CheckpointManager
from repro.core import bitpack
from repro.core.szp import szp_compress, szp_decompress
from repro.core.toposzp import toposzp_compress, toposzp_decompress
from repro.dist.collectives import compressed_psum_tree
from jax import shard_map
from repro.dist.ring import packed_wire_summary
from repro.models import lm, registry
from repro.obs.registry import Registry, _env_enabled
from repro.serve import ContinuousServeEngine, Request


@pytest.fixture(autouse=True)
def _obs_isolation():
    """Every test starts from a clean registry and leaves the process-wide
    enable flag the way it found it (the CI REPRO_OBS=1 leg runs this file
    with obs already on)."""
    was = obs.enabled()
    obs.reset()
    yield
    obs.default_registry().close_jsonl()
    obs.set_enabled(was)
    obs.reset()


def _field(shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=shape).astype(np.float32))


# --------------------------------------------------------------------------
# primitives: disabled path, spans, metrics
# --------------------------------------------------------------------------

def test_disabled_is_noop():
    """Disabled, every entry point short-circuits: the shared NULL_SPAN,
    no counters, no events."""
    obs.disable()
    assert obs.span("x") is obs.NULL_SPAN
    assert obs.span("y", a=1) is obs.NULL_SPAN
    with obs.span("x"):
        obs.counter_add("c", 5)
        obs.gauge_set("g", 1.0)
        obs.observe("h", 0.5)
        obs.error("e", "boom")
    snap = obs.snapshot()
    assert snap["counters"] == {} and snap["gauges"] == {}
    assert snap["histograms"] == {} and snap["events"] == 0


def test_env_var_parses_truthy():
    import os
    old = os.environ.get("REPRO_OBS")
    try:
        for v, want in (("1", True), ("true", True), ("ON", True),
                        ("0", False), ("", False), ("no", False)):
            os.environ["REPRO_OBS"] = v
            assert _env_enabled() is want
        os.environ.pop("REPRO_OBS")
        assert _env_enabled() is False
    finally:
        if old is None:
            os.environ.pop("REPRO_OBS", None)
        else:
            os.environ["REPRO_OBS"] = old


def test_span_nesting_depth_and_order():
    obs.enable()
    with obs.span("outer", cat="test", k=1):
        with obs.span("inner"):
            pass
    evs = obs.events()
    assert [e["name"] for e in evs] == ["inner", "outer"]  # close order
    inner, outer = evs
    assert outer["depth"] == 0 and inner["depth"] == 1
    assert outer["ph"] == "X" and inner["ph"] == "X"
    assert outer["ts"] <= inner["ts"]
    assert outer["dur"] >= inner["dur"] >= 0.0
    assert outer["args"] == {"k": 1} and outer["cat"] == "test"
    snap = obs.snapshot()
    assert snap["histograms"]["outer"]["count"] == 1
    assert snap["histograms"]["inner"]["count"] == 1


def test_span_records_exception_and_propagates():
    obs.enable()
    with pytest.raises(ValueError):
        with obs.span("failing"):
            raise ValueError("boom")
    (ev,) = obs.events()
    assert ev["args"]["error"] == "ValueError"


def test_counter_gauge_histogram_exactness():
    obs.enable()
    for v in (1, 2, 3):
        obs.counter_add("c", v)
    obs.gauge_set("g", 7.0)
    obs.gauge_set("g", 9.0)                         # last write wins
    for v in (0.5, 1.5, 1.0):
        obs.observe("h", v)
    snap = obs.snapshot()
    assert snap["counters"]["c"] == 6
    assert snap["gauges"]["g"] == 9.0
    h = snap["histograms"]["h"]
    assert h["count"] == 3 and h["sum"] == 3.0
    assert h["min"] == 0.5 and h["max"] == 1.5 and h["last"] == 1.0
    assert h["mean"] == 1.0


def test_summary_line_prefix_filter():
    obs.enable()
    obs.counter_add("a.c", 2)
    obs.gauge_set("b.g", 3.5)
    line = obs.summary_line()
    assert "a.c=2" in line and "b.g=3.5" in line
    assert "b.g" not in obs.summary_line(("a.",))
    assert obs.summary_line(("zz.",)) == "(no metrics)"


def test_registry_thread_safety_and_per_thread_depth():
    reg = Registry()
    n_threads, n_iter = 8, 200
    depths = []

    def work(i):
        for _ in range(n_iter):
            reg.counter_add("c", 1)
        with obs.Span("t", "span", {}, reg):
            depths.append(reg._depth())     # each thread nests from 0

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = reg.snapshot()
    assert snap["counters"]["c"] == n_threads * n_iter
    assert snap["events"] == n_threads
    assert depths == [1] * n_threads


def test_event_buffer_bound_counts_drops():
    reg = Registry(max_events=3)
    for i in range(5):
        reg.record_event({"name": f"e{i}", "ph": "X"})
    assert len(reg.events()) == 3
    assert reg.snapshot()["dropped_events"] == 2


# --------------------------------------------------------------------------
# export: Chrome trace + JSONL
# --------------------------------------------------------------------------

def test_chrome_trace_doc_is_valid(tmp_path):
    obs.enable()
    with obs.span("host.tick"):
        pass
    w = AsyncWriter()
    w.submit(lambda: time.sleep(0.005), label="step 1")
    w.wait()

    path = str(tmp_path / "trace.json")
    assert obs.export_chrome_trace(path) == path
    with open(path) as f:
        doc = json.load(f)
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    metas = [e for e in evs if e["ph"] == "M"]
    spans = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in spans} >= {"host.tick", "ckpt.write"}
    for e in spans:
        assert {"name", "cat", "ts", "dur", "pid", "tid"} <= set(e)
    # the writer daemon thread gets its own labeled track
    labels = {m["args"]["name"] for m in metas}
    assert "main" in labels and any(lb.startswith("thread-")
                                    for lb in labels)
    main_tid = threading.main_thread().ident
    tids = {e["tid"] for e in spans}
    assert main_tid in tids and len(tids) == 2
    assert "counters" in doc["otherData"]


def test_jsonl_sink_streams_events(tmp_path):
    obs.enable()
    path = str(tmp_path / "events.jsonl")
    obs.configure(jsonl=path)
    with obs.span("a"):
        pass
    obs.error("a", "oops", code=3)
    obs.default_registry().close_jsonl()
    lines = [json.loads(ln) for ln in open(path) if ln.strip()]
    assert [ev["name"] for ev in lines] == ["a", "a"]
    assert lines[1]["ph"] == "i" and lines[1]["args"]["message"] == "oops"

    dump = str(tmp_path / "dump.jsonl")
    obs.export_jsonl(dump)
    assert len([1 for _ in open(dump)]) == len(obs.events())


# --------------------------------------------------------------------------
# ring / collectives: gauges match the static wire model exactly
# --------------------------------------------------------------------------

def _psum_once(g, wire_format):
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))

    def f(gs):
        gl = gs.reshape(-1)
        tree = {"a": gl[: gl.shape[0] // 2], "b": gl[gl.shape[0] // 2:]}
        gbar, _ = compressed_psum_tree(tree, "data", rel_eb=1e-3,
                                       wire_format=wire_format)
        return gbar["a"], gbar["b"]

    return jax.jit(shard_map(f, mesh=mesh, in_specs=P("data"),
                             out_specs=(P(), P()),
                             check_vma=False))(g.reshape(1, -1))


def test_ring_gauges_match_packed_wire_summary():
    obs.enable()
    g = _field((4096,), seed=0) * 1e-3
    jax.block_until_ready(_psum_once(g, "packed"))
    snap = obs.snapshot()
    want = packed_wire_summary([2048, 2048], 1e-3, 0.0, 1)
    for k in ("n_members", "hops", "base_width_bits",
              "packed_bytes_per_hop", "packed_bytes_per_step",
              "sidecar_idx_bytes", "sidecar_val_bytes",
              "int32_bytes_per_hop", "int32_bytes_per_step"):
        assert snap["gauges"][f"ring.{k}"] == float(want[k]), k
    assert snap["counters"]["ring.traces"] >= 1


def test_collectives_int32_gauges():
    obs.enable()
    g = _field((4096,), seed=1) * 1e-3
    jax.block_until_ready(_psum_once(g, "int32"))
    snap = obs.snapshot()
    assert snap["gauges"]["collectives.leaves"] == 2
    assert snap["gauges"]["collectives.elems_per_step"] == 4096
    assert snap["gauges"]["collectives.n_members"] == 1
    assert snap["counters"]["collectives.traces"] >= 1


# --------------------------------------------------------------------------
# compressor counters
# --------------------------------------------------------------------------

def test_compress_counters_and_stage_histograms():
    obs.enable()
    f = _field((64, 96), seed=2)
    comp = toposzp_compress(f, 1e-3, backend="jnp")
    toposzp_decompress(comp, (64, 96), 1e-3, backend="jnp")
    snap = obs.snapshot()
    c = snap["counters"]
    assert c["toposzp.compress.calls"] == 1
    assert c["toposzp.decompress.calls"] == 1
    bucket = bitpack.width_bucket(int(comp.szp.widths.max()))
    assert {k: v for k, v in c.items()
            if k.startswith("toposzp.compress.bucket_")} == \
        {f"toposzp.compress.bucket_{bucket}": 1}
    h = snap["histograms"]
    assert h["compress.quant"]["count"] == 1
    assert h["compress.pack"]["count"] == 1
    assert h["decompress"]["count"] == 1


def test_zero_sync_with_obs_enabled():
    """PR 7's structural guarantees survive instrumentation: the resident
    compress runs under transfer_guard('disallow') and the round-trip
    traces under ONE enclosing jit, with obs ON the whole time."""
    obs.enable()
    f = _field((64, 96), seed=3)
    eb = jnp.float32(1e-3)
    jax.block_until_ready(
        toposzp_compress(f, eb, resident=True, backend="jnp"))
    with jax.transfer_guard("disallow"):
        jax.block_until_ready(
            toposzp_compress(f, eb, resident=True, backend="jnp"))

    @jax.jit
    def roundtrip(x, eb):
        parts = szp_compress(x, eb, resident=True, backend="jnp")
        return szp_decompress(parts, (64, 96), eb, backend="jnp")

    out = jax.block_until_ready(roundtrip(f, eb))
    assert float(jnp.max(jnp.abs(out - f))) <= 2e-3
    assert obs.snapshot()["counters"]["toposzp.compress.calls"] >= 1


# --------------------------------------------------------------------------
# serve: counters must equal the engine's own accounting
# --------------------------------------------------------------------------

def test_serve_counters_match_report():
    obs.enable()
    cfg = registry.get_smoke_config("gemma2_2b").replace(
        activation_dtype=jnp.float32)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    specs = [(6, 5), (9, 4), (6, 3)]
    reqs = [Request(rid=i, inputs={"tokens": jax.random.randint(
                        jax.random.PRNGKey(40 + i), (1, plen), 0,
                        cfg.vocab_size)},
                    max_new_tokens=new)
            for i, (plen, new) in enumerate(specs)]
    eng = ContinuousServeEngine(cfg, params, max_len=16, num_slots=2,
                                page_size=8, kv_mode="szp", kv_eb=0.16)
    obs.reset()
    rep = eng.serve(reqs)

    assert rep.obs is not None
    c = rep.obs["counters"]
    assert c["serve.admitted"] == len(reqs)
    assert c["serve.evicted"] == len(reqs)
    assert c["serve.decode_steps"] == len(rep.step_times)
    assert c.get("serve.pages_compressed", 0) == \
        rep.pool_stats["pages_compressed"]
    assert rep.obs["histograms"]["serve.step_time_s"]["count"] == \
        len(rep.step_times)
    assert rep.obs["gauges"]["serve.resident_bytes"] >= 0

    obs.disable()
    rep2 = eng.serve(reqs)
    assert rep2.obs is None


# --------------------------------------------------------------------------
# ckpt: async-writer error surface + step/leaf attribution
# --------------------------------------------------------------------------

def test_async_writer_wraps_labeled_failure():
    obs.enable()
    w = AsyncWriter()

    def boom():
        raise IOError("disk gone")

    w.submit(boom, label="step 7")
    with pytest.raises(AsyncWriteError) as ei:
        w.wait()
    assert ei.value.label == "step 7"
    assert isinstance(ei.value.__cause__, IOError)
    assert "step 7" in str(ei.value) and "disk gone" in str(ei.value)
    snap = obs.snapshot()
    assert snap["counters"]["ckpt.write.errors"] == 1
    errs = [e for e in obs.events() if e.get("cat") == "error"]
    assert errs and errs[0]["args"]["label"] == "step 7"
    assert "disk gone" in errs[0]["args"]["message"]


def test_async_writer_bare_submission_keeps_exception_type():
    w = AsyncWriter()

    def boom():
        raise IOError("disk gone")

    w.submit(boom)                      # no label: original type surfaces
    with pytest.raises(IOError, match="disk gone"):
        w.wait()


def test_ckpt_manager_failure_names_step_and_leaf(tmp_path, monkeypatch):
    obs.enable()
    tree = {"w": jnp.zeros((64, 64), jnp.float32)}
    mgr = CheckpointManager(str(tmp_path), mode="raw", async_write=True,
                            verify_restore=False, log=None)

    def boom(*a, **k):
        raise IOError("disk gone")

    monkeypatch.setattr("repro.ckpt.sharded.encode_shards", boom)
    mgr.save(tree, step=3)
    with pytest.raises(AsyncWriteError) as ei:
        mgr.wait()
    assert ei.value.label == "step 3"
    cause = ei.value.__cause__
    assert isinstance(cause, RuntimeError)
    assert "step 3" in str(cause) and "'w'" in str(cause)
    assert "disk gone" in str(cause)
    snap = obs.snapshot()
    assert snap["counters"]["ckpt.submits"] == 1
    assert snap["counters"]["ckpt.write.errors"] == 1
    assert snap["gauges"]["ckpt.queue_depth"] == 0
    assert snap["histograms"]["ckpt.submit_stall_s"]["count"] == 1


def test_ckpt_save_records_spans_and_commit(tmp_path):
    obs.enable()
    tree = {"w": jnp.ones((64, 64), jnp.float32)}
    mgr = CheckpointManager(str(tmp_path), mode="raw", async_write=False,
                            verify_restore=False, log=None)
    path = mgr.save(tree, step=1)
    assert path is not None
    snap = obs.snapshot()
    assert snap["counters"]["ckpt.commits"] == 1
    assert snap["counters"]["ckpt.blob_bytes"] == 64 * 64 * 4
    names = {e["name"] for e in obs.events()}
    assert {"ckpt.save", "ckpt.snapshot", "ckpt.write_blobs",
            "ckpt.commit"} <= names


# --------------------------------------------------------------------------
# bench plumbing: legacy bench-name alias
# --------------------------------------------------------------------------

def test_check_regression_accepts_legacy_serve_name():
    from benchmarks.check_regression import canonical_bench
    assert canonical_bench("serve") == "bench_serve"
    assert canonical_bench("bench_serve") == "bench_serve"
    assert canonical_bench("bench_fig7_time") == "bench_fig7_time"
