"""BFS as a sharded MIN-merge MergePlan program (paper §6.1's bfs).

Frontier expansion is the MIN merge: every edge (u, v) proposes the
candidate distance ``dist[u] + 1`` for ``v``, all proposals to a vertex
commute under ``min``, and a superstep is one privatize-and-merge round:

    per shard   cand = cscatter(INF-table, dst, dist[src] + 1, kind=min)
    cross shard merged = hierarchical_merge(cand, plan, MIN)
    everywhere  dist  = min(dist, merged)

The MIN algebra is idempotent, so the top plan level may be ``:defer``-ed
(commits every K supersteps through ``defer_cascade``; a deferred commit
settles by *re-apply* — re-joining already-seen candidates is harmless).
Distances still converge to the same fixpoint, just in more supersteps:
cross-pod frontier hops only land at commits. Results match the
single-device reference bitwise (integer distances, lattice join).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.apps.common import scatter
from repro.core import ccache
from repro.core.merge_functions import MIN

INF = jnp.iinfo(jnp.int32).max


def bfs_reference(n: int, src, dst, source: int) -> np.ndarray:
    """Single-device BFS distances (int32; unreachable = INF)."""
    src = np.asarray(src)
    dst = np.asarray(dst)
    dist = np.full((n,), int(INF), np.int64)
    dist[source] = 0
    for _ in range(n):
        ok = (src >= 0) & (dst >= 0) & (dist[np.maximum(src, 0)] < INF)
        cand = np.where(ok, dist[np.maximum(src, 0)] + 1, int(INF))
        nxt = dist.copy()
        np.minimum.at(nxt, np.maximum(dst, 0), np.where(ok, cand, int(INF)))
        if np.array_equal(nxt, dist):
            break
        dist = nxt
    return dist.astype(np.int32)


def bfs_superstep(dist, src_ids, dst_ids, *, use_pallas: bool = False):
    """One shard's scatter phase: propose dist[src]+1 to every dst.

    Returns the shard's candidate table [n] (MIN-identity where no edge
    lands). Padded edges (id -1) are dropped by the scatter.
    """
    n = dist.shape[0]
    ok = src_ids >= 0
    d_src = dist[jnp.where(ok, src_ids, 0)]
    reachable = ok & (d_src < INF)
    vals = jnp.where(reachable, d_src + 1, INF).astype(jnp.int32)
    ids = jnp.where(reachable, dst_ids, -1)
    table = jnp.full((n, 1), INF, jnp.int32)
    cand = scatter(table, ids, vals[:, None], kind="min",
                   use_pallas=use_pallas)
    return cand[:, 0]


def run_bfs(dist0, src_sh, dst_sh, spmd, plan, axis_name, *,
            supersteps: int, defer_k: int | None = None,
            use_pallas: bool = False):
    """Drive BFS supersteps over sharded edges.

    ``dist0``/``src_sh``/``dst_sh`` are shard-major ([S, n], [S, E]);
    ``spmd(fn, *args)`` maps a per-shard function across the shard axis
    with ``axis_name`` bound (vmap in tests, shard_map on meshes).
    Without ``defer_k`` the loop stops early at the fixpoint (a superstep
    that changes no distance). ``defer_k`` routes the plan's deferred levels
    through ``defer_cascade`` committing every ``defer_k`` supersteps; the
    trailing partial cycle is flushed after the loop. Returns the final
    shard-major distances.
    """
    n_shards = dist0.shape[0]
    size = n_shards
    n_def = len(ccache.deferred_stages_of(plan, size, merge_fn=MIN))
    if defer_k is not None and n_def == 0:
        raise ValueError("defer_k given but the plan has no deferred levels")

    if defer_k is None:
        def step(dist, src_ids, dst_ids):
            cand = bfs_superstep(dist, src_ids, dst_ids,
                                 use_pallas=use_pallas)
            merged = ccache.hierarchical_merge(cand, axis_name, MIN, plan)
            return jnp.minimum(dist, merged)

        dist = dist0
        for _ in range(supersteps):
            new = spmd(step, dist, src_sh, dst_sh)
            if bool(jnp.array_equal(new, dist)):
                return new  # fixpoint: the frontier is empty
            dist = new
        return dist

    # Idempotent merge-on-evict: each superstep's eager-scope join is
    # consumed immediately (the frontier keeps advancing within the pod)
    # AND folded into a pod-scope pending; every K supersteps the pending
    # settles through the deferred stages and is *re-applied* — re-joining
    # contributions the pod already saw is harmless for a lattice join,
    # which is exactly what the ``idempotent`` trait licenses.
    pending0 = jnp.full_like(dist0, INF)

    def make_step(due: bool):
        def step(dist, src_ids, dst_ids, pending):
            cand = bfs_superstep(dist, src_ids, dst_ids,
                                 use_pallas=use_pallas)
            u = ccache.partial_merge(cand, axis_name, MIN, plan)
            dist = jnp.minimum(dist, u)
            pending = jnp.minimum(pending, u)
            if due:
                settled = ccache.settle_deferred(pending, axis_name, MIN,
                                                 plan)
                dist = jnp.minimum(dist, settled)
                pending = jnp.full_like(pending, INF)
            return dist, pending
        return step

    steps = {False: make_step(False), True: make_step(True)}
    dist, pending = dist0, pending0
    for t in range(1, supersteps + 1):
        due = t % defer_k == 0
        dist, pending = spmd(steps[due], dist, src_sh, dst_sh, pending)
    if supersteps % defer_k != 0:
        def flush(dist, pending):
            settled = ccache.settle_deferred(pending, axis_name, MIN, plan)
            return jnp.minimum(dist, settled)
        dist = spmd(flush, dist, pending)
    return dist
