"""Shared plumbing for the sharded apps.

Every app follows the same shape: vertices (or centroids) are replicated
per shard, the update stream is edge/point-partitioned, each superstep runs
a *per-shard scatter phase* (privatize-and-merge into a local table — the
``cscatter`` kernel) and a *cross-shard merge phase* (the hierarchical
engine over a :class:`~repro.core.merge_plan.MergePlan`).

The app step functions are axis-generic: they only use collectives through
``repro.core.ccache``, so the same code runs under ``jax.vmap(...,
axis_name=...)`` (fast in-process tests) and ``shard_map`` over a real
device mesh (the acceptance runs and benchmarks). The scatter phase picks
the Pallas kernel on real meshes and the pure-jnp oracle under vmap.
"""

from __future__ import annotations

from repro.core.merge_plan import MergePlan


def scatter(table, ids, vals, *, kind: str, use_pallas: bool = False):
    """One shard's scatter phase: fold ``vals`` into ``table`` rows by id.

    ``use_pallas`` selects the real ``cscatter`` kernel (compiled on TPU,
    interpreted elsewhere; its tile comes from ``cscatter.choose_tile``);
    the default is the vmappable jnp oracle. Out-of-range/negative ids are
    ignored (the padding convention) in both.
    """
    if use_pallas:
        from repro.kernels.cscatter import cscatter
        return cscatter(table, ids, vals, kind=kind)
    from repro.kernels.ref import ref_cscatter
    return ref_cscatter(table, ids, vals, kind)


def default_plan(n_shards: int, defer_top: bool = False,
                 lane_parallel: bool = True) -> MergePlan:
    """A chip/host/pod factorization of an ``n_shards`` merge axis.

    8 -> chip:2,host:2,pod:2 ; 16 -> chip:4,host:2,pod:2 ; odd or small
    counts degrade to fewer levels. ``defer_top`` marks the pod level
    ``:defer`` (commits ride a schedule instead of every superstep).
    """
    if n_shards < 2:
        return MergePlan.parse(f"chip:{max(n_shards, 1)}")
    if n_shards % 4 == 0 and n_shards >= 8:
        chip, host, pod = n_shards // 4, 2, 2
    elif n_shards % 2 == 0 and n_shards >= 4:
        chip, host, pod = n_shards // 2, 1, 2
    else:
        chip, host, pod = n_shards, 1, 1
    spec = f"chip:{chip},host:{host},pod:{pod}"
    if defer_top and pod > 1:
        spec += ":defer"
    return MergePlan.parse(spec, lane_parallel=lane_parallel)


def shard_edges(src, dst, n_shards: int):
    """Partition an edge list across shards, padding with id -1.

    Returns ``(src_sh, dst_sh)`` of shape [n_shards, ceil(E/n_shards)];
    padded entries carry -1 and are dropped by the scatter phase.
    """
    import numpy as np
    src = np.asarray(src, np.int32)
    dst = np.asarray(dst, np.int32)
    e = src.shape[0]
    per = -(-e // n_shards)
    pad = per * n_shards - e
    src_p = np.concatenate([src, np.full((pad,), -1, np.int32)])
    dst_p = np.concatenate([dst, np.full((pad,), -1, np.int32)])
    return (src_p.reshape(n_shards, per), dst_p.reshape(n_shards, per))
