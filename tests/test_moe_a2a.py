"""a2a (shard_map all-to-all) MoE must match the gather MoE numerically."""

import subprocess
import sys
import textwrap
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs.registry import get_config
    from repro.model import moe as moe_mod
    from repro.model.moe_a2a import apply_moe_sharded
    from repro.model.sharding import init_mk, make_rules, sharding_context

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = dataclasses.replace(
        get_config("dbrx-132b").reduced(),
        d_model=32, d_ff=64, num_experts=8, num_experts_per_tok=2,
        moe_capacity_factor=8.0,  # generous: no drops -> exact match
    )
    mk = init_mk(jax.random.key(0), jnp.float32)
    params = moe_mod.init_moe(mk, cfg, "moe")
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 16, 32)).astype(np.float32))

    rules = make_rules(mesh, "train")
    with mesh, sharding_context(mesh, rules):
        ref = jax.jit(lambda p, v: moe_mod.apply_moe(p, v, cfg))(params, x)
        out = jax.jit(lambda p, v: apply_moe_sharded(p, v, cfg))(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    # Capacity drops differ between local (per-shard) and global routing —
    # just assert finiteness under pressure.
    with mesh, sharding_context(mesh, rules):
        tight = jax.jit(
            lambda p, v: apply_moe_sharded(p, v, dataclasses.replace(
                cfg, moe_capacity_factor=1.0))
        )(params, x)
    assert bool(jnp.isfinite(tight).all())

    # Gradients flow through the a2a path.
    with mesh, sharding_context(mesh, rules):
        g = jax.jit(jax.grad(
            lambda p: apply_moe_sharded(p, x, cfg).sum()
        ))(params)
    assert all(bool(jnp.isfinite(l).all()) for l in jax.tree.leaves(g))
    assert any(float(jnp.abs(l).max()) > 0 for l in jax.tree.leaves(g))

    print("MOE_A2A_OK")
    """
)


def test_moe_a2a_matches_gather():
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True,
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin:/usr/local/bin",
             "HOME": "/root",
             # The script forces host-platform devices; skip TPU probing
             # (30-retry metadata fetches) in containers with libtpu baked in.
             "JAX_PLATFORMS": "cpu"},
        timeout=900,
    )
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr[-3000:]}"
    assert "MOE_A2A_OK" in res.stdout
