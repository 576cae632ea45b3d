"""``bench/run.py`` refuses to run, and prints no result, without a TPU
or without the program."""
import os
import shutil
import subprocess
import sys

from bench import spec


def _run(root, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "atm_topo.compress",
         "--seed", str(2**33 + 5), "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    r = _run(spec.ROOT)
    assert r.returncode == 2
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_refuses_when_kernels_do_not_resolve_to_pallas(monkeypatch):
    import jax
    import pytest
    from bench import harness
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [type("D", (), {"platform": "tpu"})()])
    with pytest.raises(harness.NoChip, match="pallas"):
        harness.require_chip(1)


def test_fails_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copytree(os.path.join(spec.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    r = _run(str(tmp_path))
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_footprint_adds_reserved_scratch_to_arrays_in_use():
    from bench import harness

    class Chip:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats
    chip = Chip({"peak_bytes_in_use": 1_668_321_792,
                 "peak_bytes_reserved": 4_153_278_464, "bytes_in_use": 7})
    assert harness.footprint(chip) == (1_668_321_792, 4_153_278_464)
    assert harness.footprint(Chip(None)) == (0, 0)
