"""shard_map expert-parallel MoE: exactness vs the gshard oracle and
gradient flow.  Runs in a subprocess (needs >1 XLA host device)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.models.moe import make_moe_defs, moe_gshard, moe_shard_map
    from repro.models.spec import materialize
    from repro.distributed import activation_sharding, ACT_RULES

    cfg = get_smoke_config("olmoe_1b_7b")
    cfg = dataclasses.replace(cfg, compute_dtype="float32",
                              moe=dataclasses.replace(cfg.moe,
                                                      capacity_factor=8.0,
                                                      dispatch="shard_map"))
    params = materialize(make_moe_defs(cfg), jax.random.PRNGKey(0))
    params = jax.tree.map(lambda x: x.astype(jnp.float32)
                          if jnp.issubdtype(x.dtype, jnp.floating) else x,
                          params)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                          jnp.float32)
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with mesh, activation_sharding(mesh, ACT_RULES):
        y_sm, _ = jax.jit(lambda p, xx: moe_shard_map(p, xx, cfg))(params, x)
    y_ref, _ = moe_gshard(params, x, cfg)
    np.testing.assert_allclose(np.asarray(y_sm), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)

    def loss(p):
        with mesh, activation_sharding(mesh, ACT_RULES):
            y, aux = moe_shard_map(p, x, cfg)
        return jnp.sum(y ** 2) + aux

    g = jax.grad(loss)(params)
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(l)) for l in jax.tree.leaves(g)))
    assert bool(jnp.isfinite(gn)) and float(gn) > 0
    print("SHARD_MAP_MOE_OK")
""")


@pytest.mark.slow
def test_moe_shard_map_exact_and_differentiable(tmp_path):
    script = tmp_path / "moe_sm.py"
    script.write_text(SCRIPT)
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    out = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, timeout=500, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "SHARD_MAP_MOE_OK" in out.stdout
