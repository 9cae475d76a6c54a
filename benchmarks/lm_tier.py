"""LM-scale CCache benchmarks: flexible merge collectives + cscatter.

Collective-byte measurements need >1 device, so those benches respawn
themselves in a subprocess with 8 forced host devices (the main process
keeps the container's single-device view, per the brief).

CSV metrics:
  merge_path      wire bytes + wall time of psum (COUP fast path) vs the
                  ppermute butterfly (CCache flexible path) vs int8-compressed
  grad_accum      collectives per train step at 1 vs 8 microbatches
                  (soft-merge: deferral keeps it at one merge per step)
  cscatter        wall us of the privatized scatter vs XLA scatter-add
                  (interpret mode: structural check, not TPU timing)
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

from benchmarks.records import emit_record, iter_records


def _sub(mode: str) -> list[dict]:
    """Run a sub-benchmark in a subprocess with 8 forced host devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.pathsep.join(
                   [os.path.abspath("src"), os.path.abspath("."),
                    os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.lm_tier", "--sub", mode],
        env=env, capture_output=True, text=True, timeout=900)
    if out.returncode != 0:
        return [{"bench": mode, "error": out.stderr[-400:]}]
    return list(iter_records(out.stdout.splitlines()))


def bench_merge_paths() -> list[dict]:
    return _sub("merges")


def bench_grad_accum() -> list[dict]:
    return _sub("accum")


def bench_cscatter() -> list[dict]:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    rows = []
    key = jax.random.key(0)
    for rows_n, d, n in ((4096, 128, 8192), (16384, 256, 16384)):
        table = jax.random.normal(key, (rows_n, d), jnp.float32)
        ids = jax.random.randint(jax.random.key(1), (n,), 0, rows_n)
        vals = jax.random.normal(jax.random.key(2), (n, d), jnp.float32)

        def timed(f, *a):
            r = f(*a)
            jax.block_until_ready(r)
            t0 = time.time()
            for _ in range(3):
                r = f(*a)
            jax.block_until_ready(r)
            return (time.time() - t0) / 3 * 1e6

        t_kernel = timed(lambda: ops.commutative_scatter(
            table, ids, vals, kind="add", block_rows=512, chunk=1024))
        xla = jax.jit(lambda t, i, v: t.at[i].add(v))
        t_xla = timed(xla, table, ids, vals)
        rows.append({"bench": "cscatter", "table": f"{rows_n}x{d}",
                     "updates": n,
                     "kernel_interpret_us": round(t_kernel, 1),
                     "xla_scatter_us": round(t_xla, 1),
                     "note": "interpret-mode timing is structural only"})
    return rows


# ---------------------------------------------------------------------------
# subprocess entry points (8 forced devices)
# ---------------------------------------------------------------------------


def _merges_main() -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core import ccache, merge_functions as mf
    from repro.launch import hlo_cost

    mesh = jax.make_mesh((8,), ("data",))
    n = 1 << 20  # 4 MB f32 per device
    x = jnp.arange(8 * n, dtype=jnp.float32).reshape(8, n) / n

    cases = {
        "psum_fastpath": lambda u: ccache.reduce_update(u, "data", mf.ADD),
        "tree_flexible": lambda u: ccache.reduce_update(
            u, "data", mf.ADD, force_tree=True),
        "tree_int8_compressed": lambda u: ccache.reduce_update(
            u, "data", mf.int8_compressed_add(), compress=True),
        "tree_saturating": lambda u: ccache.reduce_update(
            u, "data", mf.saturating_add(1e9), force_tree=True),
    }
    for name, fn in cases.items():
        f = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("data"),
                                  out_specs=P("data")))
        lowered = f.lower(jax.ShapeDtypeStruct((8, n), jnp.float32))
        compiled = lowered.compile()
        walk = hlo_cost.analyze_hlo(compiled.as_text())
        r = f(x)
        jax.block_until_ready(r)
        t0 = time.time()
        for _ in range(5):
            r = f(x)
        jax.block_until_ready(r)
        wall = (time.time() - t0) / 5 * 1e6
        emit_record({
            "bench": "merge_path", "case": name,
            "wire_bytes_per_device": walk["wire_bytes"],
            "collectives": {k: v["count"]
                            for k, v in walk["per_collective"].items()},
            "wall_us_8cpudev": round(wall, 1)})


def _accum_main() -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.grad_merge import microbatched_value_and_grad
    from repro.launch import hlo_cost

    mesh = jax.make_mesh((8,), ("data",))
    d = 512

    def loss_fn(params, batch):
        h = jnp.tanh(batch["x"] @ params["w1"]) @ params["w2"]
        return jnp.mean((h - batch["y"]) ** 2)

    params = {"w1": jax.ShapeDtypeStruct((d, d), jnp.float32),
              "w2": jax.ShapeDtypeStruct((d, d), jnp.float32)}
    batch = {"x": jax.ShapeDtypeStruct((64, d), jnp.float32),
             "y": jax.ShapeDtypeStruct((64, d), jnp.float32)}
    repl = NamedSharding(mesh, P())
    shard = NamedSharding(mesh, P("data"))

    for n_micro in (1, 8):
        if n_micro == 1:
            step = jax.value_and_grad(loss_fn)
        else:
            step = microbatched_value_and_grad(loss_fn, n_micro)
        f = jax.jit(step, in_shardings=(
            {"w1": repl, "w2": repl},
            {"x": shard, "y": shard}))
        compiled = f.lower(params, batch).compile()
        walk = hlo_cost.analyze_hlo(compiled.as_text())
        emit_record({
            "bench": "grad_accum", "microbatches": n_micro,
            "wire_bytes_per_device": walk["wire_bytes"],
            "collectives": {k: v["count"]
                            for k, v in walk["per_collective"].items()},
            "note": "soft-merge defers: one cross-device merge per step"})


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--sub", choices=["merges", "accum"], required=True)
    a = ap.parse_args()
    if a.sub == "merges":
        _merges_main()
    else:
        _accum_main()
