"""dist.collectives coverage: homomorphic-sum error bounds across dtypes and
shapes (property), elastic-mesh policy, and an 8-fake-device end-to-end
compressed-DP training run (subprocess, same pattern as
tests/test_moe_shard_map.py)."""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # no network in CI: deterministic shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.dist.collectives import (code_bits, protect_k,
                                    quantize_dequantize_sum, sidecar_bits,
                                    topk_rank_preservation,
                                    topo_compressed_psum_tree,
                                    topo_quantize_dequantize_sum)
from repro.dist.elastic import largest_mesh_shape

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bound_ok(xs: np.ndarray, rel_eb: float) -> None:
    homo, direct = quantize_dequantize_sum(jnp.asarray(xs), rel_eb=rel_eb)
    n = xs.shape[0]
    eb = rel_eb * float(np.abs(xs.astype(np.float32)).max())
    err = float(jnp.abs(homo - direct).max())
    assert err <= n * eb * (1 + 1e-5) + 1e-30, (err, n * eb)


@pytest.mark.parametrize("dtype", [np.float32, np.float16, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(2, 64), (5, 17), (8, 256), (3, 4, 33)])
def test_homomorphic_bound_dtypes_shapes(dtype, shape):
    """|homo - direct| <= n * rel_eb * max|x| for every member dtype/shape
    (the sum-of-per-member-eb bound; quantization runs in f32)."""
    rng = np.random.default_rng([len(shape), shape[0], shape[-1]])
    xs = np.asarray(jnp.asarray(rng.standard_normal(shape)).astype(dtype))
    _bound_ok(xs, 1e-3)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([1e-2, 1e-3, 1e-4]),
       st.integers(2, 16))
def test_property_homomorphic_bound(seed, rel_eb, n):
    rng = np.random.default_rng(seed)
    scale = 10.0 ** rng.integers(-4, 3)
    xs = (rng.standard_normal((n, 257)) * scale).astype(np.float32)
    _bound_ok(xs, rel_eb)


def test_all_zero_members_safe():
    """Zero gradients must not divide by a zero error bound."""
    homo, direct = quantize_dequantize_sum(jnp.zeros((4, 32)), rel_eb=1e-3)
    assert float(jnp.abs(homo).max()) == 0.0
    assert float(jnp.abs(direct).max()) == 0.0


def test_overflow_widened_at_tiny_rel_eb():
    """Regression: at rel_eb=1e-9 per-member codes are ~5e8 so an 8-member
    int32 code sum reaches 4e9 and silently wraps (the pre-fix path
    returned ~-0.29 here); the widened hi/lo accumulation recovers the
    true sum."""
    xs = jnp.full((8, 64), 0.5, jnp.float32)
    homo, direct = quantize_dequantize_sum(xs, rel_eb=1e-9)
    assert float(jnp.abs(direct - 4.0).max()) == 0.0
    assert float(jnp.abs(homo - 4.0).max()) < 1e-3, float(homo[0])


def test_overflow_widening_keeps_moderate_path_bitwise():
    """Widening must only engage when n * max_code can overflow: at
    ordinary rel_eb the raw int32 sum is still used (bit-identical)."""
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.standard_normal((8, 512)).astype(np.float32))
    homo, _ = quantize_dequantize_sum(xs, rel_eb=1e-3)
    from repro.core.quantize import dequantize, quantize
    from repro.dist.collectives import _leaf_eb
    eb = _leaf_eb(xs, 1e-3)
    ref = dequantize(quantize(xs, eb).sum(axis=0), eb)
    assert np.array_equal(np.asarray(homo), np.asarray(ref))


def test_rel_eb_too_small_raises():
    """Codes that overflow int32 in quantize() itself fail loudly."""
    xs = jnp.ones((2, 8), jnp.float32)
    with pytest.raises(ValueError, match="too small"):
        quantize_dequantize_sum(xs, rel_eb=1e-11)


def test_topo_sum_also_widened_at_tiny_rel_eb():
    """The topo variant's body sum takes the same widened path (it
    wrapped to ~-0.29 pre-fix, like the plain sum)."""
    xs = jnp.full((8, 64), 0.5, jnp.float32)
    topo, direct, prot = topo_quantize_dequantize_sum(xs, rel_eb=1e-9,
                                                      topo_frac=1e-2)
    body = np.delete(np.asarray(topo), np.asarray(prot))
    assert float(np.abs(body - 4.0).max()) < 1e-3, body[:4]
    assert np.array_equal(np.asarray(topo)[np.asarray(prot)],
                          np.asarray(direct)[np.asarray(prot)])
    with pytest.raises(ValueError, match="too small"):
        topo_quantize_dequantize_sum(xs, rel_eb=1e-11, topo_frac=1e-2)


def test_widening_member_limit_raises():
    """Past 2**15 members the lo sums would wrap int32 too: the widened
    path must refuse rather than reintroduce the silent wrap."""
    from repro.dist.collectives import _MAX_WIDEN_MEMBERS, _split_hi_lo
    q = jnp.ones((4,), jnp.int32)
    _split_hi_lo(q, _MAX_WIDEN_MEMBERS)          # boundary still exact
    with pytest.raises(ValueError, match="members"):
        _split_hi_lo(q, _MAX_WIDEN_MEMBERS + 1)


def test_rank_preservation_clamps_k():
    """Tree-level k larger than a small leaf must clamp, not crash."""
    d = jnp.asarray(np.array([5.0, 4.0, 3.0], np.float32))
    assert topk_rank_preservation(d, d, 64) == 1.0
    assert topk_rank_preservation(d, d, 0) == 1.0
    assert topk_rank_preservation(d, d, -3) == 1.0
    swapped = jnp.asarray(np.array([4.0, 5.0, 3.0], np.float32))
    assert topk_rank_preservation(d, swapped, 100) == pytest.approx(1 / 3)


def test_unknown_wire_format_raises():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal(64).astype(np.float32))
    import jax
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.dist.collectives import compressed_psum_tree
    from jax import shard_map
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    with pytest.raises(ValueError, match="wire_format"):
        jax.jit(shard_map(
            lambda x: compressed_psum_tree({"g": x.reshape(-1)}, "data",
                                           wire_format="gzip")[0]["g"],
            mesh=mesh, in_specs=P("data"), out_specs=P(),
            check_vma=False))(g.reshape(1, -1))


def test_code_bits_monotone_in_eb():
    rng = np.random.default_rng(0)
    g = jnp.asarray((rng.standard_normal(4096) * 1e-3).astype(np.float32))
    widths = [int(code_bits(g, eb)) for eb in (1e-2, 1e-3, 1e-4)]
    assert widths == sorted(widths), widths
    assert all(1 <= w <= 32 for w in widths)


# --------------------------------------------------------------------------
# Topology-aware collective: exact protected tail + bounded body
# --------------------------------------------------------------------------

def _topo_ok(xs: np.ndarray, rel_eb: float, topo_frac: float) -> None:
    """Protected entries bit-exact; body within the n * eb bound."""
    topo, direct, protected = topo_quantize_dequantize_sum(
        jnp.asarray(xs), rel_eb=rel_eb, topo_frac=topo_frac)
    topo, direct = np.asarray(topo), np.asarray(direct)
    prot = np.asarray(protected)
    n = xs.shape[0]
    k = protect_k(xs[0].size, topo_frac)
    assert prot.shape == (n * k,)
    # (b) exact values — hence preserved rank order — for protected entries
    assert np.array_equal(topo.reshape(-1)[prot], direct.reshape(-1)[prot])
    # (a) homomorphic bound on the quantized body (protected entries have
    # zero error, so the global bound still holds elementwise)
    eb = rel_eb * float(np.abs(xs.astype(np.float32)).max())
    err = float(np.abs(topo - direct).max())
    assert err <= n * eb * (1 + 1e-5) + 1e-30, (err, n * eb)


@pytest.mark.parametrize("dtype", [np.float32, np.float16, jnp.bfloat16])
@pytest.mark.parametrize("n", [1, 2, 5, 8])
def test_topo_protected_exact_dtypes_members(dtype, n):
    rng = np.random.default_rng(n)
    xs = rng.standard_normal((n, 999)) * 1e-3
    xs[:, rng.integers(0, 999, 8)] *= 100.0      # shared outlier tail
    xs = np.asarray(jnp.asarray(xs).astype(dtype))
    _topo_ok(xs, rel_eb=1e-3, topo_frac=1e-2)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([1e-2, 1e-3]),
       st.integers(2, 16), st.sampled_from([1e-3, 1e-2, 0.1]))
def test_property_topo_exact_and_bounded(seed, rel_eb, n, topo_frac):
    rng = np.random.default_rng(seed)
    scale = 10.0 ** rng.integers(-4, 3)
    xs = (rng.standard_normal((n, 257)) * scale).astype(np.float32)
    _topo_ok(xs, rel_eb, topo_frac)


def test_protect_k_sizing():
    assert protect_k(1000, 0.0) == 0
    assert protect_k(1000, -1.0) == 0
    assert protect_k(0, 1e-3) == 0           # empty leaf: nothing to pin
    assert protect_k(1, 1e-3) == 1           # every leaf keeps its peak
    assert protect_k(1000, 1e-3) == 1
    assert protect_k(10**6, 1e-3) == 1000
    assert protect_k(10, 1.0) == 10          # never more than the leaf
    assert protect_k(10, 5.0) == 10


def test_sidecar_bits_accounting():
    # k=32 indices sent + 8*32 gathered fp32 values psum'd, 32 bits each
    assert sidecar_bits(32_000, 1e-3, n_members=8) == 32 * 32 + 8 * 32 * 32
    assert sidecar_bits(100, 0.0, n_members=8) == 0
    # sub-5%-overhead claim at topo_frac=1e-3 for a 10-bit body, n=8
    size = 1 << 20
    overhead = sidecar_bits(size, 1e-3, 8) / (10 * size)
    assert overhead < 0.05, overhead


def test_topo_wire_bits_is_body_plus_sidecar():
    from repro.dist.collectives import topo_wire_bits
    rng = np.random.default_rng(7)
    g = jnp.asarray((rng.standard_normal(4096) * 1e-3).astype(np.float32))
    total = topo_wire_bits(g, 1e-3, 1e-3, n_members=8)
    body = int(code_bits(g, 1e-3)) * g.size
    assert total == body + sidecar_bits(g.size, 1e-3, 8)
    assert topo_wire_bits(g, 1e-3, 0.0, n_members=8) == body


def test_topo_frac_zero_matches_plain():
    rng = np.random.default_rng(3)
    xs = jnp.asarray(rng.standard_normal((4, 512)).astype(np.float32))
    topo, direct, prot = topo_quantize_dequantize_sum(xs, 1e-3, 0.0)
    plain, direct2 = quantize_dequantize_sum(xs, 1e-3)
    assert prot.size == 0
    assert np.array_equal(np.asarray(topo), np.asarray(plain))
    assert np.array_equal(np.asarray(direct), np.asarray(direct2))


def test_rank_preservation_metric():
    direct = jnp.asarray(np.array([5.0, 4.0, 3.0, 2.0, 1.0], np.float32))
    assert topk_rank_preservation(direct, direct, 4) == 1.0
    swapped = jnp.asarray(np.array([4.0, 5.0, 3.0, 2.0, 1.0], np.float32))
    assert topk_rank_preservation(direct, swapped, 4) == 0.5


def test_topo_frac_requires_grad_compress():
    """A topo knob without the compressed collective must fail loudly,
    not silently run the uncompressed baseline."""
    from repro.models import registry
    from repro.optim import adamw, constant
    from repro.train import make_train_step

    cfg = registry.get_smoke_config("gemma2_2b")
    opt = adamw(constant(1e-3))
    with pytest.raises(ValueError, match="grad_compress"):
        make_train_step(cfg, opt, topo_frac=1e-3)
    with pytest.raises(ValueError, match="grad_compress"):
        make_train_step(cfg.replace(grad_topo_frac=1e-3), opt)
    # explicit 0 overrides the config knob -> plain baseline is fine
    make_train_step(cfg.replace(grad_topo_frac=1e-3), opt, topo_frac=0.0)


def test_psum_tree_empty_leaf():
    """Zero-size leaves (degenerate configs) must not crash either path."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.dist.collectives import compressed_psum_tree
    from jax import shard_map

    tree = {"g": jnp.zeros((0,), jnp.float32),
            "h": jnp.ones((8,), jnp.float32)}
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))

    def run(fn, **kw):
        def f(_):
            gbar, new_e = fn(tree, "data", rel_eb=1e-3, **kw)
            return gbar["h"], gbar["g"], new_e["g"]
        return jax.jit(shard_map(f, mesh=mesh, in_specs=P("data"),
                                 out_specs=(P(), P(), P()),
                                 check_vma=False))(jnp.zeros((1,)))

    for fn, kw in ((topo_compressed_psum_tree, {"topo_frac": 1e-3}),
                   (compressed_psum_tree, {})):
        h, g0, e0 = run(fn, **kw)
        assert np.array_equal(np.asarray(h), np.ones(8, np.float32))
        assert g0.shape == (0,) and e0.shape == (0,)


def test_topo_psum_tree_single_device():
    """Full shard_map path on one device: protected entries come back as
    their exact fp32 inputs and the error feedback is zeroed there."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    rng = np.random.default_rng(0)
    g = (rng.standard_normal(4096) * 1e-3).astype(np.float32)
    g[:16] *= 100.0
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    topo_frac = 1e-2

    def f(gs):
        gl = gs.reshape(-1)
        gbar, new_e = topo_compressed_psum_tree(
            {"g": gl}, "data", rel_eb=1e-3, topo_frac=topo_frac,
            err={"g": jnp.zeros_like(gl)})
        return gbar["g"], new_e["g"]

    gbar, new_e = jax.jit(shard_map(
        f, mesh=mesh, in_specs=P("data"), out_specs=(P(), P("data")),
        check_vma=False))(g.reshape(1, -1))
    k = protect_k(g.size, topo_frac)
    idx = np.argsort(-np.abs(g))[:k]
    assert np.array_equal(np.asarray(gbar)[idx], g[idx])
    assert float(np.abs(np.asarray(new_e).reshape(-1)[idx]).max()) == 0.0
    # unprotected body still eb-bounded (n=1)
    eb = 1e-3 * float(np.abs(g).max())
    assert float(np.abs(np.asarray(gbar) - g).max()) <= eb * (1 + 1e-5)


def test_largest_mesh_shape_policy():
    """Maximize devices used; break ties toward more model parallelism."""
    assert largest_mesh_shape(8, 2) == (4, 2)
    assert largest_mesh_shape(8, 4) == (2, 4)
    assert largest_mesh_shape(7, 4) == (7, 1)
    assert largest_mesh_shape(5, 2) == (5, 1)
    assert largest_mesh_shape(6, 2) == (3, 2)
    assert largest_mesh_shape(1, 8) == (1, 1)


@pytest.mark.slow
def test_compressed_psum_trains_multi_device():
    """compressed_psum_tree drives train/loop.py for 2 steps on a (4 data,
    2 model) mesh of 8 fake devices without NaNs."""
    py = textwrap.dedent("""
        import jax, numpy as np
        from repro.data import token_batches
        from repro.dist.elastic import rebuild_mesh
        from repro.models import lm, registry
        from repro.optim import adamw, constant
        from repro.train import init_state, make_train_step, train_loop

        cfg = registry.get_smoke_config('gemma2_2b')
        mesh = rebuild_mesh(jax.devices(), model_parallel=2)
        assert dict(zip(mesh.axis_names, mesh.devices.shape)) == \\
            {'data': 4, 'model': 2}
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        opt = adamw(constant(1e-3))
        state = init_state(params, opt, grad_compress=True)
        step = make_train_step(cfg, opt, mesh=mesh, grad_compress=True,
                               rel_eb=1e-3)
        data = token_batches(cfg, 8, 32, seed=0)
        state, rep = train_loop(state, step, data, num_steps=2,
                                log=lambda *_: None)
        assert rep.steps_run == 2, rep.steps_run
        assert all(np.isfinite(l) for l in rep.losses), rep.losses
        for leaf in jax.tree.leaves(state.params):
            assert bool(jax.numpy.all(jax.numpy.isfinite(
                leaf.astype(jax.numpy.float32))))
        print('COMPRESSED-DP-OK', [round(l, 4) for l in rep.losses])
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", py], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "COMPRESSED-DP-OK" in out.stdout


@pytest.mark.slow
def test_topo_psum_exact_multi_device():
    """topo_compressed_psum_tree on 8 fake devices: every protected union
    entry equals the direct psum mean bit-exactly (same reduction order as
    the reference psum of the raw values), and the error feedback is
    zeroed at protected entries on every member."""
    py = textwrap.dedent("""
        import jax, numpy as np
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.dist.collectives import protect_k, topo_compressed_psum_tree
        from jax import shard_map

        n, size, topo_frac = 8, 4096, 1e-2
        rng = np.random.default_rng(0)
        x = (rng.standard_normal((n, size)) * 1e-3).astype(np.float32)
        x[:, :32] *= 100.0
        mesh = Mesh(np.array(jax.devices()[:n]), ('data',))

        def f(xs):
            gl = xs.reshape(-1)
            gbar, new_e = topo_compressed_psum_tree(
                {'g': gl}, 'data', rel_eb=1e-3, topo_frac=topo_frac,
                err={'g': jnp.zeros_like(gl)})
            return gbar['g'], new_e['g']

        def ref(xs):
            return jax.lax.psum(xs.reshape(-1), 'data') / n

        sm = lambda fn, outs: jax.jit(shard_map(
            fn, mesh=mesh, in_specs=P('data'), out_specs=outs,
            check_vma=False))
        gbar, new_e = sm(f, (P(), P('data')))(jnp.asarray(x))
        exact_mean = np.asarray(sm(ref, P())(jnp.asarray(x)))

        k = protect_k(size, topo_frac)
        union = np.unique(np.argsort(-np.abs(x), axis=1)[:, :k])
        gbar = np.asarray(gbar)
        assert np.array_equal(gbar[union], exact_mean[union]), \\
            np.abs(gbar[union] - exact_mean[union]).max()
        err = np.asarray(new_e).reshape(n, size)
        assert float(np.abs(err[:, union]).max()) == 0.0
        eb = 1e-3 * float(np.abs(x).max())
        assert float(np.abs(gbar - x.mean(0)).max()) <= eb * (1 + 1e-5)
        print('TOPO-PSUM-EXACT-OK', k, union.size)
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", py], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "TOPO-PSUM-EXACT-OK" in out.stdout
