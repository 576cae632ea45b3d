"""Manual-EP MoE (shard_map) vs the GSPMD einsum path (§Perf opt)."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_shard_map_matches_einsum_multi_device():
    py = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.models import lm, registry, set_active_mesh
        mesh = jax.make_mesh((2, 4), ('data', 'model'),
                             axis_types=(AxisType.Auto,) * 2)
        cfg_e = registry.get_smoke_config('olmoe_1b_7b').replace(
            capacity_factor=8.0)
        cfg_s = cfg_e.replace(moe_impl='shard_map')
        params = lm.init_params(cfg_e, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                  cfg_e.vocab_size)
        set_active_mesh(mesh)
        with mesh:
            l_e = jax.jit(lambda p: lm.loss_fn(p, cfg_e,
                                               {'tokens': toks}))(params)
            l_s = jax.jit(lambda p: lm.loss_fn(p, cfg_s,
                                               {'tokens': toks}))(params)
        assert abs(float(l_e) - float(l_s)) < 2e-2, (float(l_e), float(l_s))
        print('MOE-EP-OK', float(l_e), float(l_s))
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", py], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "MOE-EP-OK" in out.stdout
