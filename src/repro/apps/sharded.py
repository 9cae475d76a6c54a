"""shard_map drivers for the apps: a real device mesh per shard.

``mesh_spmd`` adapts the apps' per-shard step functions to ``shard_map``
over a 1D mesh axis — the same functions the fast tests drive under
``jax.vmap``. Per-step closures are memoized through ``jax.jit`` so a
multi-superstep run compiles each program variant once.

``run_app`` executes one app end-to-end on the current device set (use
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` in a subprocess for
host meshes) with the Pallas ``cscatter`` kernel on the scatter phase, and
returns the sharded-vs-reference comparison the acceptance criteria gate.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


def build_mesh(n_devices: int, axis_name: str = "shards"):
    from jax.sharding import Mesh
    devs = jax.devices()
    if len(devs) < n_devices:
        raise RuntimeError(f"need {n_devices} devices, have {len(devs)}")
    return Mesh(np.asarray(devs[:n_devices]), (axis_name,))


def mesh_spmd(mesh, axis_name: str = "shards"):
    """An ``spmd(fn, *args, donate=())`` executor over ``mesh`` for
    shard-major args.

    Matches the vmap executor's contract: every arg and result carries a
    leading shard axis; ``fn`` sees unbatched per-shard values with
    ``axis_name`` bound for collectives.  ``donate`` names argument
    positions whose buffers the caller relinquishes (state they rebind
    from the result, e.g. a store's resident tables) so XLA can update
    them in place instead of copying every step.

    ``spmd.lower(fn, *args, donate=())`` lowers the same program without
    running it; ``args`` may be shard-major ``ShapeDtypeStruct`` trees (HLO
    walks, memory checks).

    Each program compiles under ``fn``'s name (``module @jit_<name>``), so
    a profiler trace tells the programs apart.
    """
    from jax.sharding import PartitionSpec as P

    cache: dict = {}

    def program(fn, n_args: int, donate):
        def region(*locals_):
            loc = [jax.tree.map(lambda x: x[0], a) for a in locals_]
            out = fn(*loc)
            return jax.tree.map(lambda x: x[None], out)

        region.__name__ = region.__qualname__ = getattr(
            fn, "__name__", "region")
        sharded = jax.shard_map(
            region, mesh=mesh,
            in_specs=(P(axis_name),) * n_args,
            out_specs=P(axis_name), check_vma=False)
        return jax.jit(sharded, donate_argnums=tuple(donate))

    def spmd(fn, *args, donate=()):
        key = (id(fn), len(args), tuple(donate))
        if key not in cache:
            cache[key] = program(fn, len(args), donate)
        return cache[key](*args)

    spmd.lower = lambda fn, *args, donate=(): program(
        fn, len(args), donate).lower(*args)
    return spmd


def _graph(n: int, e: int, seed: int):
    rng = np.random.default_rng(seed)
    src = np.concatenate([rng.integers(0, n, e), np.arange(n)])
    dst = np.concatenate([rng.integers(0, n, e), rng.integers(0, n, n)])
    return src.astype(np.int32), dst.astype(np.int32)


def run_app(app: str, n_shards: int, *, defer_k: int = 4,
            use_pallas: bool = True, seed: int = 0,
            n_vertices: int = 48, n_edges: int = 160) -> dict:
    """Run one app sharded over ``n_shards`` devices vs its reference.

    Returns a record with ``max_err`` (0.0 expected for the bitwise MIN
    app) for the all-eager plan and, where the shard count gives the plan a
    deferrable level, the deferred/overlapped commit schedule.
    """
    from repro.apps.common import default_plan, shard_edges
    from repro.apps import (bfs_reference, run_bfs, pagerank_reference,
                            run_pagerank, kmeans_reference, run_kmeans)

    axis = "shards"
    mesh = build_mesh(n_shards, axis)
    spmd = mesh_spmd(mesh, axis)
    plan = default_plan(n_shards)
    plan_d = default_plan(n_shards, defer_top=True)
    deferrable = plan_d.has_deferred  # e.g. one shard has nothing to defer
    out: dict = {"app": app, "n_shards": n_shards, "defer_k": defer_k}

    if app == "bfs":
        from repro.apps.bfs import INF
        src, dst = _graph(n_vertices, n_edges, seed)
        ref = bfs_reference(n_vertices, src, dst, 0)
        src_sh, dst_sh = map(jnp.asarray, shard_edges(src, dst, n_shards))
        dist0 = jnp.full((n_shards, n_vertices), INF,
                         jnp.int32).at[:, 0].set(0)
        eager = run_bfs(dist0, src_sh, dst_sh, spmd, plan, axis,
                        supersteps=n_vertices, use_pallas=use_pallas)
        out["eager_max_err"] = float(
            np.abs(np.asarray(eager[0], np.int64) - ref).max())
        if deferrable:
            defer = run_bfs(dist0, src_sh, dst_sh, spmd, plan_d, axis,
                            supersteps=defer_k * n_vertices, defer_k=defer_k,
                            use_pallas=use_pallas)
            out["defer_max_err"] = float(
                np.abs(np.asarray(defer[0], np.int64) - ref).max())
        out["bitwise"] = True
    elif app == "pagerank":
        alpha, iters = 0.5, 16 * defer_k
        src, dst = _graph(n_vertices, n_edges, seed)
        ref = pagerank_reference(n_vertices, src, dst, alpha=alpha,
                                 iters=iters)
        src_sh, dst_sh = map(jnp.asarray, shard_edges(src, dst, n_shards))
        eager = run_pagerank(n_vertices, src_sh, dst_sh, spmd, plan, axis,
                             alpha=alpha, supersteps=iters,
                             use_pallas=use_pallas)
        out["eager_max_err"] = float(
            np.abs(np.asarray(eager[0], np.float64) - ref).max())
        if deferrable:
            defer = run_pagerank(n_vertices, src_sh, dst_sh, spmd, plan_d,
                                 axis, alpha=alpha, supersteps=iters,
                                 defer_k=defer_k, use_pallas=use_pallas)
            out["defer_max_err"] = float(
                np.abs(np.asarray(defer[0], np.float64) - ref).max())
        out["bitwise"] = False
    elif app == "kmeans":
        k, d, b, t = 5, 3, 16, 2 * defer_k
        rng = np.random.default_rng(seed)
        pts = rng.normal(size=(n_shards, t, b, d)).astype(np.float32)
        c0 = rng.normal(size=(k, d)).astype(np.float32)
        pts_ref = pts.transpose(1, 0, 2, 3).reshape(t, n_shards * b, d)
        errs = {}
        for label, overlap in (("defer", False), ("overlap", True)):
            ref = kmeans_reference(pts_ref, c0, commit_k=defer_k,
                                   overlap=overlap)
            got = run_kmeans(jnp.asarray(pts), jnp.asarray(c0), spmd,
                             plan_d, axis, commit_k=defer_k,
                             overlap=overlap, use_pallas=use_pallas)
            errs[f"{label}_max_err"] = float(
                np.abs(np.asarray(got[0], np.float64)
                       - ref.astype(np.float64)).max())
        out.update(errs)
        out["eager_max_err"] = errs["defer_max_err"]
        out["bitwise"] = False
    else:
        raise ValueError(f"unknown app {app!r}")
    return out
