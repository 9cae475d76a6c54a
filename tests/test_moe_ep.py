"""Expert-parallel MoE (shard_map, zero-a2a dispatch + psum merge) must
equal the GShard sort-dispatch oracle. Runs on 4 forced host devices in a
subprocess (the main test process keeps the container's 1-device view)."""

import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.models import moe
    from repro.models.moe_ep import apply_ep
    from repro.models.module import split_params

    mesh = jax.make_mesh((2, 2), ("data", "model"))
    d, f, e, k = 16, 32, 4, 2
    p, _ = split_params(moe.init(jax.random.key(0), d, f, e, jnp.float32,
                                 n_shared=1))
    x = jax.random.normal(jax.random.key(1), (4, 8, d))
    gold, gm = moe.apply(p, x, top_k=k, capacity_factor=8.0)
    with mesh:
        out, m = jax.jit(lambda p, x: apply_ep(p, x, k, 8.0, mesh))(p, x)
    err = float(jnp.max(jnp.abs(out - gold)))
    assert err < 1e-5, err
    assert abs(float(m["drop_frac"])) < 1e-6

    def loss(p):
        with mesh:
            o, _ = apply_ep(p, x, k, 8.0, mesh)
        return jnp.sum(o ** 2)
    g = jax.jit(jax.grad(loss))(p)
    assert all(bool(jnp.all(jnp.isfinite(v))) for v in jax.tree.leaves(g))
    print("EP_OK", err)
""")


def test_moe_ep_matches_gshard_on_mesh():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.abspath("src"))
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, (r.stdout[-800:], r.stderr[-800:])
    assert "EP_OK" in r.stdout
