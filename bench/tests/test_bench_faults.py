"""A run on the CPU with the timed path broken underneath: ``correct`` must
come out false.  Also the control of each cell: the same path one
precision below the configuration's float32 (bfloat16) fails too.

The check for a chip is replaced by one that accepts the CPU; everything
else is the run as ``bench/run.py`` drives it, at a small grid."""
import re

import jax
import jax.numpy as jnp
import pytest

from bench import harness, spec
from bench.target import Target

SMALL = {"grid": [40, 72], "fields_per_call": 4}


def _cell(name):
    cell = spec.find_cell(name)
    return cell._replace(config=dict(cell.config, **SMALL))


def _run(name, cpu_chip):
    return harness.run_cell(_cell(name), 2**33 + 11, 0.05, False, 0.0,
                            check_chip=cpu_chip)


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _alter_stream(comp):
    """A code altered where it is produced: one block's first value."""
    szp = comp.szp if hasattr(comp, "szp") else comp
    szp = szp._replace(first=szp.first.at[1, 3].add(7))
    return comp._replace(szp=szp) if hasattr(comp, "szp") else szp


def _half_batch(fields):
    """Half of the batch left out: the first half stands in for all."""
    h = fields.shape[0] // 2
    return jnp.concatenate([fields[:h], fields[:h]])


CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]
COMPRESS = [c for c in CELLS if c.endswith(".compress")]
DECOMPRESS = [c for c in CELLS if c.endswith(".decompress")]
# a decompress fault that every decompress cell can have, and TopoSZp's
# restore stage left out, which only a TopoSZp cell has
DECOMPRESS_FAULTS = [(c, f) for c in DECOMPRESS
                     for f in ["altered", "half_batch", "control"]
                     + (["restore_skipped"] if spec.find_cell(c).config[
                         "compressor"] == "toposzp" else [])]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, cpu_chip, no_cache):
    r = _run(name, cpu_chip)
    assert r["correct"] is True and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())


@pytest.mark.parametrize("name", COMPRESS)
@pytest.mark.parametrize("fault", ["altered", "half_batch", "control"])
def test_broken_compress_is_not_correct(name, fault, cpu_chip, no_cache,
                                        monkeypatch):
    real = Target.compress

    def broken(self, fields):
        if fault == "altered":
            return _alter_stream(real(self, fields))
        if fault == "half_batch":
            return real(self, _half_batch(fields))
        return real(self, _bf16(fields))
    monkeypatch.setattr(Target, "compress", broken)
    r = _run(name, cpu_chip)
    assert r["correct"] is False and r["failed"] > 0


@pytest.mark.parametrize("name, fault", DECOMPRESS_FAULTS,
                         ids=[f"{f}-{c}" for c, f in DECOMPRESS_FAULTS])
def test_broken_decompress_is_not_correct(name, fault, cpu_chip, no_cache,
                                          monkeypatch):
    real = Target.decompress

    def broken(self, comp):
        if fault == "restore_skipped":
            from repro.core.szp import szp_decompress_batch
            return szp_decompress_batch(comp.szp, self.shape, self.eb)
        rec = real(self, comp)
        if fault == "altered":
            return rec.at[(2,) + (5,) * (rec.ndim - 1)].add(3 * self.eb)
        if fault == "half_batch":
            return _half_batch(rec)
        return _bf16(rec)
    monkeypatch.setattr(Target, "decompress", broken)
    r = _run(name, cpu_chip)
    assert r["correct"] is False and r["failed"] > 0


def test_restack_pads_trimmed_sections_to_the_live_batch():
    from bench.target import restack
    cell = _cell("atm_topo.compress")
    t = Target("toposzp", SMALL["grid"], cell.config["eb"])
    fields = jax.random.uniform(jax.random.key(0), (2, 40, 72))
    comp = t.compress(fields)
    back = t.deserialize(t.serialize(comp), like=comp)
    assert jax.tree_util.tree_structure(back) == \
        jax.tree_util.tree_structure(comp)
    for a, b in zip(jax.tree_util.tree_leaves(back),
                    jax.tree_util.tree_leaves(comp)):
        assert a.shape == b.shape and a.dtype == b.dtype
    assert jnp.array_equal(t.decompress(back), t.decompress(comp))
    with pytest.raises(ValueError):
        restack(comp.szp.first[:, :3], [comp.szp.first[0]])


def test_traced_run_warns_of_a_metric_with_nothing_to_read(cpu_chip, no_cache,
                                                           capsys):
    # the CPU trace carries no TPU ops, so every stage reader finds nothing
    r = harness.run_cell(_cell("atm_topo.compress"), 2**33 + 13, 0.05, True,
                         0.0, check_chip=cpu_chip)
    err = capsys.readouterr().err
    assert "detect_ms" not in r["metrics"]
    assert "warning: detect_ms found nothing to read" in err


def test_a_3d_grid_runs_through_the_harness(cpu_chip, no_cache, monkeypatch,
                                             capsys):
    """A configuration whose grid is (nz, ny, nx) needs no edit of the
    benchmark: SZp's batch entry points take any rank, so the SZp
    decompress cell runs on a small volume, with 3-D fields and 3-D
    critical points counted by the reference, and its bf16 control
    fails."""
    cell = spec.find_cell("atm_szp.decompress")
    cell = cell._replace(config=dict(cell.config, grid=[6, 16, 24],
                                     fields_per_call=3))
    r = harness.run_cell(cell, 2**33 + 19, 0.05, False, 0.0,
                         check_chip=cpu_chip)
    assert r["correct"] is True and r["attempted"] % 3 == 0
    assert r["metrics"]["decompress_GBps"]["value"] > 0
    n_cp = [int(m) for m in re.findall(r"^\[field \d\] .* n_cp (\d+)",
                                       capsys.readouterr().err, re.M)]
    assert len(n_cp) == 3 and min(n_cp) > 0
    real = Target.decompress
    monkeypatch.setattr(Target, "decompress",
                        lambda self, comp: _bf16(real(self, comp)))
    r = harness.run_cell(cell, 2**33 + 19, 0.05, False, 0.0,
                         check_chip=cpu_chip)
    assert r["correct"] is False
