"""dist.ring coverage: bitpacked ring all-reduce equivalence with the int32
psum path (single-device fast + 8-fake-device subprocess), wire accounting,
and the packed-format validation errors."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.quantize import quantize
from repro.dist.collectives import (_leaf_eb, compressed_psum_tree,
                                    topo_compressed_psum_tree)
from jax import shard_map
from repro.dist.ring import (base_width, packed_wire_summary, ring_perm,
                             simulate_hop_bytes)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_tree(wire_format, topo_frac, g, err=None, rel_eb=1e-3):
    """One-device shard_map run of the (topo_)compressed psum tree."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))

    def f(gs):
        gl = gs.reshape(-1)
        tree = {"a": gl[: gl.shape[0] // 2], "b": gl[gl.shape[0] // 2:]}
        e = None if err is None else jax.tree.map(jnp.zeros_like, tree)
        if topo_frac > 0:
            gbar, new_e = topo_compressed_psum_tree(
                tree, "data", rel_eb=rel_eb, topo_frac=topo_frac, err=e,
                wire_format=wire_format)
        else:
            gbar, new_e = compressed_psum_tree(tree, "data", rel_eb=rel_eb,
                                               err=e,
                                               wire_format=wire_format)
        return gbar["a"], gbar["b"], new_e["a"], new_e["b"]

    return jax.jit(shard_map(f, mesh=mesh, in_specs=P("data"),
                             out_specs=(P(), P(), P(), P()),
                             check_vma=False))(g.reshape(1, -1))


@pytest.mark.parametrize("topo_frac", [0.0, 1e-2])
def test_packed_matches_int32_single_device(topo_frac):
    """Full shard_map path on one device: the packed ring must reproduce
    the int32 psum path bit-for-bit (gradients AND error feedback)."""
    rng = np.random.default_rng(0)
    g = (rng.standard_normal(5000) * 1e-3).astype(np.float32)
    g[:32] *= 100.0
    ref = _run_tree("int32", topo_frac, jnp.asarray(g), err=True)
    got = _run_tree("packed", topo_frac, jnp.asarray(g), err=True)
    for r, o in zip(ref, got):
        assert np.array_equal(np.asarray(r), np.asarray(o))


def test_ring_perm_is_unidirectional_cycle():
    assert ring_perm(4) == [(0, 1), (1, 2), (2, 3), (3, 0)]
    assert ring_perm(1) == [(0, 0)]


def test_base_width_static_bound():
    """Every realizable code magnitude fits base_width bits."""
    rng = np.random.default_rng(0)
    for rel_eb in (1e-1, 1e-2, 1e-3, 1e-4):
        x = jnp.asarray((rng.standard_normal(4096) * 7.7).astype(np.float32))
        q = quantize(x, _leaf_eb(x, rel_eb))
        assert int(jnp.abs(q).max()) < 2 ** base_width(rel_eb)


def test_simulate_hop_bytes_beats_int32():
    """Measured packed bytes/hop on gradient-shaped codes stay well under
    the int32 wire at rel_eb=1e-2 (the bench regression gate's claim)."""
    rng = np.random.default_rng(0)
    g = (rng.standard_normal((8, 1 << 14)) * 1e-3).astype(np.float32)
    g[:, :50] *= 100.0
    gj = jnp.asarray(g)
    qs = quantize(gj, _leaf_eb(gj, 1e-2))
    rec = simulate_hop_bytes(qs, 1e-2)
    assert rec["hops"] == 7
    assert rec["valid_vs_int32"] <= rec["shipped_vs_int32"]
    assert rec["shipped_vs_int32"] < 0.55
    assert rec["valid_bytes_per_hop"] <= rec["shipped_bytes_per_hop"]


def test_packed_wire_summary_accounting():
    """Static wire model: per-hop growth, bucketing, sidecar terms."""
    rec = packed_wire_summary([1 << 16, 100, 3], rel_eb=1e-2,
                              topo_frac=1e-3, n_members=8)
    assert rec["hops"] == 7
    assert rec["base_width_bits"] == base_width(1e-2)
    assert len(rec["packed_hop_bytes"]) == 7
    # widths (and so hop bytes) grow monotonically along the ring
    assert rec["packed_hop_bytes"] == sorted(rec["packed_hop_bytes"])
    assert rec["packed_vs_int32_per_hop"] < 0.55
    assert rec["packed_bytes_per_step"] >= sum(rec["packed_hop_bytes"])
    # one member: nothing moves
    rec1 = packed_wire_summary([1 << 16], 1e-2, 0.0, 1)
    assert rec1["hops"] == 0 and rec1["packed_bytes_per_step"] == 0.0


def test_packed_requires_single_axis():
    from repro.dist.ring import _require_single_axis
    with pytest.raises(NotImplementedError, match="ONE"):
        _require_single_axis(("pod", "data"))
    assert _require_single_axis(("data",)) == "data"


def test_packed_rejects_overflowing_rel_eb():
    """The ring accumulates in int32 sign-magnitude: n * max_code over
    int32 must raise a clear trace-time error, not wrap."""
    g = jnp.ones((64,), jnp.float32)
    with pytest.raises(ValueError, match="int32"):
        _run_tree("packed", 0.0, g, rel_eb=1e-10)


def test_make_train_step_wire_format_validation():
    from repro.models import registry
    from repro.optim import adamw, constant
    from repro.train import make_train_step

    cfg = registry.get_smoke_config("gemma2_2b")
    opt = adamw(constant(1e-3))
    with pytest.raises(ValueError, match="grad_compress"):
        make_train_step(cfg, opt, wire_format="packed")
    with pytest.raises(ValueError, match="wire_format"):
        make_train_step(cfg, opt, wire_format="zstd")
    # config knob wires through the same validation
    with pytest.raises(ValueError, match="grad_compress"):
        make_train_step(cfg.replace(grad_wire_format="packed"), opt)


@pytest.mark.slow
def test_packed_ring_bit_identical_multi_device():
    """8 fake devices: the packed ring all-reduce must equal the int32
    psum path bit-for-bit — mean gradient, error-feedback tree — and
    protected entries must still be the exact fp32 psum mean."""
    py = textwrap.dedent("""
        import jax, numpy as np
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.dist.collectives import protect_k, topo_compressed_psum_tree
        from jax import shard_map

        n, size, topo_frac = 8, 5000, 1e-2
        rng = np.random.default_rng(0)
        x = (rng.standard_normal((n, size)) * 1e-3).astype(np.float32)
        x[:, :32] *= 100.0
        mesh = Mesh(np.array(jax.devices()[:n]), ('data',))

        def make(wire):
            def f(xs):
                gl = xs.reshape(-1)
                tree = {'a': gl[:3000].reshape(30, 100), 'b': gl[3000:]}
                err = jax.tree.map(jnp.zeros_like, tree)
                gbar, new_e = topo_compressed_psum_tree(
                    tree, 'data', rel_eb=1e-3, topo_frac=topo_frac,
                    err=err, wire_format=wire)
                return gbar['a'], gbar['b'], new_e['a'], new_e['b']
            return jax.jit(shard_map(f, mesh=mesh, in_specs=P('data'),
                                     out_specs=(P(), P(), P('data'),
                                                P('data')),
                                     check_vma=False))

        ref = make('int32')(jnp.asarray(x))
        got = make('packed')(jnp.asarray(x))
        for name, r, o in zip(('ga', 'gb', 'ea', 'eb'), ref, got):
            assert np.array_equal(np.asarray(r), np.asarray(o)), name

        # protected entries: exact fp32 psum mean (reference reduction)
        def ref_mean(xs):
            return jax.lax.psum(xs.reshape(-1), 'data') / n
        exact = np.asarray(jax.jit(shard_map(
            ref_mean, mesh=mesh, in_specs=P('data'), out_specs=P(),
            check_vma=False))(jnp.asarray(x)))
        gbar = np.concatenate([np.asarray(got[0]).reshape(-1),
                               np.asarray(got[1])])
        for lo, hi in ((0, 3000), (3000, 5000)):
            k = protect_k(hi - lo, topo_frac)
            union = np.unique(
                np.argsort(-np.abs(x[:, lo:hi]), axis=1)[:, :k]) + lo
            assert np.array_equal(gbar[union], exact[union]), (lo, hi)
        print('PACKED-RING-IDENTICAL-OK')
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", py], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "PACKED-RING-IDENTICAL-OK" in out.stdout


@pytest.mark.slow
def test_psum_leaf_widens_at_tiny_rel_eb_multi_device():
    """8 members x code 5e8 = 4e9 > int32: the int32 wire format must
    widen the psum (hi/lo split) instead of silently wrapping."""
    py = textwrap.dedent("""
        import jax, numpy as np
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.dist.collectives import compressed_psum_tree
        from jax import shard_map

        n = 8
        mesh = Mesh(np.array(jax.devices()[:n]), ('data',))

        def f(xs):
            gbar, _ = compressed_psum_tree({'g': xs.reshape(-1)}, 'data',
                                           rel_eb=1e-9)
            return gbar['g']
        gbar = np.asarray(jax.jit(shard_map(
            f, mesh=mesh, in_specs=P('data'), out_specs=P(),
            check_vma=False))(jnp.full((n, 64), 0.5, jnp.float32)))
        # pre-fix the wrapped sum gives ~-0.037; widened it is ~0.5
        assert np.abs(gbar - 0.5).max() < 1e-4, gbar[:4]
        print('WIDENED-PSUM-OK')
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", py], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "WIDENED-PSUM-OK" in out.stdout
