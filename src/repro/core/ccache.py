"""The CCache execution engine: on-demand privatization + flexible merge.

Maps the paper's mechanism onto a TPU mesh (DESIGN.md §2):

* ``privatize``    — c_read's first-touch duplication: produces a ``CView``
  holding the preserved *source copy* and the mutable *update copy*. Inside
  ``shard_map`` each device's view is its private replica; the functional IR
  plays the role of the source buffer (the src operand simply stays live).
* ``c_read`` / ``c_write`` / ``c_update`` — COps on the update copy. No
  collectives are emitted between privatize and merge: the compiled program
  provably has zero "coherence traffic" for CData in that window.
* ``merge``        — cross-device reconciliation. Fixed-op merges take the
  XLA fused collective (the COUP fast path); arbitrary software merges run a
  recursive-doubling ``ppermute`` butterfly whose combine step is the user's
  JAX function — this is what COUP cannot express and CCache can.
* ``soft_merge``   — defers reconciliation: the local delta is coalesced into
  a pending-update accumulator (``combine``), and the expensive cross-device
  merge happens once, later (merge-on-evict at the program level).
* ``MergePlan`` / ``hierarchical_merge`` — topology-aware N-level merging:
  the device axis is described by a ``MergePlan`` IR (``repro.core.
  merge_plan``) whose levels — e.g. chip / host / pod / DCI — compile into a
  sequence of level-local combine, representative- or lane-parallel
  cross-unit exchange, and unit-broadcast stages. Levels marked ``defer``
  are excluded from the eager merge and committed from ``soft_merge``'s
  ``PendingUpdate`` every K steps (the paper's mergeable bit: merge-on-evict
  at pod scope). ``MergeTopology`` survives as the two-level shorthand and
  compiles onto the same IR. See docs/merge_topology.md.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import permutes
from repro.core.merge_functions import MergeFn
from repro.core.merge_plan import (LevelStage, MergePlan, compile_plan,
                                   split_eager_deferred)

PyTree = Any


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CView:
    """A privatized view of CData: preserved source + mutable update copy."""

    src: PyTree
    upd: PyTree


def privatize(mem: PyTree) -> CView:
    """First-touch duplication (the c_read miss path)."""
    return CView(src=mem, upd=mem)


def c_read(view: CView) -> PyTree:
    return view.upd


def c_write(view: CView, value: PyTree) -> CView:
    return CView(src=view.src, upd=value)


def c_update(view: CView, fn) -> CView:
    return CView(src=view.src, upd=fn(view.upd))


# ---------------------------------------------------------------------------
# Flexible tree merge: all-reduce with an arbitrary commutative combine.
# ---------------------------------------------------------------------------


def tree_merge(update: PyTree, axis_name, merge: MergeFn,
               compress: bool = False) -> PyTree:
    """Recursive-doubling all-reduce of ``update`` over ``axis_name``.

    log2(P) ``ppermute`` rounds; every rank ends with the full combination.
    Requires a power-of-two axis (TPU meshes are); otherwise falls back to
    all_gather + local fold. With ``compress`` and a merge that defines
    encode/decode, each round exchanges the compressed wire format.
    """
    if compress and (merge.encode is None or merge.decode is None):
        raise ValueError(
            f"compress=True but merge {merge.name!r} defines no "
            f"encode/decode wire format — the exchange would silently stay "
            f"uncompressed; use a codec merge (e.g. int8_compressed_add) or "
            f"drop compress")
    size = lax.axis_size(axis_name)
    if not permutes.is_pow2(size):  # non-power-of-two fallback
        gathered = lax.all_gather(update, axis_name, axis=0, tiled=False)
        def _fold(x):
            acc = x[0]
            for i in range(1, size):
                acc = merge.combine(acc, x[i])
            return acc
        return jax.tree.map(_fold, gathered)

    if compress:
        leaves, treedef = jax.tree.flatten(update)
        step = 1
        while step < size:
            perm = permutes.butterfly_perms(size, step)
            wire = [merge.encode(l) for l in leaves]
            other = lax.ppermute(wire, axis_name, perm=perm)
            # Decode our own wire too so both ranks fold identically-quantized
            # values — keeps the butterfly commutative up to codec noise.
            leaves = [merge.combine(merge.decode(w), merge.decode(o))
                      for w, o in zip(wire, other)]
            step <<= 1
        return jax.tree.unflatten(treedef, leaves)

    u = update
    step = 1
    while step < size:
        perm = permutes.butterfly_perms(size, step)
        other = lax.ppermute(u, axis_name, perm=perm)
        u = merge.tree_combine(u, other)
        step <<= 1
    return u


_XLA_REDUCERS = {
    "add": lax.psum,
    "max": lax.pmax,
    "min": lax.pmin,
}


# ---------------------------------------------------------------------------
# Hierarchical (topology-aware) merging on the MergePlan IR.
# See repro/core/merge_plan.py for the IR and docs/merge_topology.md for the
# usage guide.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MergeTopology:
    """Two-level shorthand: groups of ``group_size`` ranks + one inter level.

    Kept as the convenience constructor for the common "one pod per group"
    case; compiles onto the N-level ``MergePlan`` IR via ``to_plan``.
    ``use_xla_intra=False`` forces the software ppermute path at the intra
    level (testing / arbitrary combines); ``lane_parallel=True`` shards the
    representative role over a group's lanes for the inter exchange.
    """

    group_size: int
    axis_name: Optional[Any] = None
    use_xla_intra: bool = True
    lane_parallel: bool = False

    def resolve_axis(self, axis_name):
        return self.axis_name if self.axis_name is not None else axis_name

    def validate(self, size: int) -> None:
        if self.group_size < 1:
            raise ValueError(f"group_size must be >= 1: {self.group_size}")
        if size % self.group_size != 0:
            raise ValueError(
                f"axis size {size} not divisible by group_size "
                f"{self.group_size}")

    def groups(self, size: int) -> list[list[int]]:
        g = self.group_size
        return [list(range(i * g, (i + 1) * g)) for i in range(size // g)]

    def to_plan(self, size: int, compress: bool = False) -> MergePlan:
        self.validate(size)
        return MergePlan.two_level(
            self.group_size, size, axis_name=self.axis_name,
            use_xla_intra=self.use_xla_intra, compress_inter=compress,
            lane_parallel=self.lane_parallel)


Topology = Union[MergeTopology, MergePlan]


def _tree_select(pred, a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def _resolve_plan(topology: Topology, axis_name,
                  compress: bool) -> tuple[Optional[MergePlan], Any, int]:
    """Normalize (MergeTopology | MergePlan) -> (plan, axis, size).

    Returns ``plan=None`` for the degenerate flat dispatch (group_size <= 1
    or a single rank). The function-level ``compress`` flag maps onto the
    *outermost* level — compression where bytes are scarcest — matching the
    two-level engine's inter-group semantics.
    """
    axis = topology.resolve_axis(axis_name)
    size = lax.axis_size(axis)
    if not isinstance(topology, MergePlan):
        if topology.group_size <= 1 or size == 1:
            return None, axis, size
        topology = topology.to_plan(size)
    plan = topology
    plan.validate(size)
    if compress and not any(lv.compress for lv in plan.levels):
            # Attach to the outermost level that actually executes — size-1
            # levels compile away and would silently swallow the flag.
            idx = max((i for i, lv in enumerate(plan.levels) if lv.size > 1),
                      default=None)
            if idx is not None:
                levels = (plan.levels[:idx]
                          + (dataclasses.replace(plan.levels[idx],
                                                 compress=True),)
                          + plan.levels[idx + 1:])
                plan = dataclasses.replace(plan, levels=levels)
    return plan, axis, size


# -- stage executors --------------------------------------------------------


def _stage_innermost(u: PyTree, axis_name, merge: MergeFn, stage: LevelStage,
                     size: int, force_tree: bool,
                     use_compress: bool) -> PyTree:
    """stride == 1: every rank combines directly within its aligned block.

    Fixed-op merges ride the fused XLA collective (``axis_index_groups``
    blocks) — the COUP fast path; everything else (or vmap, which rejects
    grouped collectives; or a tuple merge axis, where jax restricts grouped
    collectives to a single axis) runs the block-confined software
    butterfly/ring.
    """
    fanout = stage.fanout
    if (stage.combine_mode == "xla" and not force_tree and not use_compress
            and merge.xla_reduce in _XLA_REDUCERS):
        reducer = _XLA_REDUCERS[merge.xla_reduce]
        whole_axis = stage.block == size
        if whole_axis or not isinstance(axis_name, (tuple, list)):
            kw = {} if whole_axis else {
                "axis_index_groups": [list(range(b * fanout, (b + 1) * fanout))
                                      for b in range(size // fanout)]}
            try:
                return jax.tree.map(
                    functools.partial(reducer, axis_name=axis_name, **kw), u)
            except NotImplementedError:
                pass  # vmap collectives reject axis_index_groups.

    if permutes.is_pow2(fanout):
        if use_compress:
            leaves, treedef = jax.tree.flatten(u)
            step = 1
            while step < fanout:
                perm = permutes.butterfly_perms(size, step)
                wire = [merge.encode(l) for l in leaves]
                other = lax.ppermute(wire, axis_name, perm=perm)
                leaves = [merge.combine(merge.decode(w), merge.decode(o))
                          for w, o in zip(wire, other)]
                step <<= 1
            return jax.tree.unflatten(treedef, leaves)
        step = 1
        while step < fanout:
            # Steps below the block size keep i ^ step inside the aligned
            # block, so the flat butterfly perm doubles as the confined one.
            other = lax.ppermute(u, axis_name,
                                 perm=permutes.butterfly_perms(size, step))
            u = merge.tree_combine(u, other)
            step <<= 1
        return u

    # Any block size: circulate contributions around the block ring, folding
    # as they pass — fanout-1 rounds, each rank sees every member once.
    perm = permutes.ring_perm(size, fanout)
    if use_compress:
        leaves, treedef = jax.tree.flatten(u)
        wire = [merge.encode(l) for l in leaves]
        acc = [merge.decode(w) for w in wire]
        for _ in range(fanout - 1):
            wire = lax.ppermute(wire, axis_name, perm=perm)
            acc = [merge.combine(a, merge.decode(w))
                   for a, w in zip(acc, wire)]
        return jax.tree.unflatten(treedef, acc)
    recv = u
    acc = u
    for _ in range(fanout - 1):
        recv = lax.ppermute(recv, axis_name, perm=perm)
        acc = merge.tree_combine(acc, recv)
    return acc


def _broadcast_within_units(u: PyTree, axis_name, size: int, stride: int,
                            lane) -> PyTree:
    """Binomial broadcast of lane 0's value over each aligned
    ``stride``-sized unit — ceil(log2 stride) swap rounds."""
    for k, perm in permutes.binomial_broadcast_perms(size, stride):
        recv = lax.ppermute(u, axis_name, perm=perm)
        u = _tree_select(lane < k, u, recv)
    return u


def _stage_rep(u: PyTree, axis_name, merge: MergeFn, stage: LevelStage,
               size: int, rank, use_compress: bool) -> PyTree:
    """Representative-only cross-unit exchange + broadcast down the unit.

    Unit leaders (rank % stride == 0) carry their unit's aggregate through
    the butterfly/ring across sibling units; non-representatives ride
    identity self-pairs. ``use_compress`` puts the merge's encode/decode
    wire format on these expensive rounds only.
    """
    stride, fanout = stage.stride, stage.fanout
    lane = rank % stride
    is_rep = lane == 0
    perms = permutes.rep_exchange_perms(size, stride, fanout)
    butterfly = permutes.is_pow2(fanout)

    if use_compress:
        leaves, treedef = jax.tree.flatten(u)
        if butterfly:
            for perm in perms:
                wire = [merge.encode(l) for l in leaves]
                other = lax.ppermute(wire, axis_name, perm=perm)
                combined = [merge.combine(merge.decode(w), merge.decode(o))
                            for w, o in zip(wire, other)]
                leaves = [jnp.where(is_rep, c, l)
                          for c, l in zip(combined, leaves)]
        else:
            # Ring: circulate each rep's original (encoded) contribution and
            # fold it in as it arrives; own wire is decoded too so all ranks
            # fold identically-quantized values.
            wire = [merge.encode(l) for l in leaves]
            acc = [merge.decode(w) for w in wire]
            for _ in range(fanout - 1):
                wire = lax.ppermute(wire, axis_name, perm=perms[0])
                acc = [merge.combine(a, merge.decode(w))
                       for a, w in zip(acc, wire)]
            leaves = [jnp.where(is_rep, a, l) for a, l in zip(acc, leaves)]
        u = jax.tree.unflatten(treedef, leaves)
    elif butterfly:
        for perm in perms:
            other = lax.ppermute(u, axis_name, perm=perm)
            u = _tree_select(is_rep, merge.tree_combine(u, other), u)
    else:
        recv = u
        for _ in range(fanout - 1):
            recv = lax.ppermute(recv, axis_name, perm=perms[0])
            u = _tree_select(is_rep, merge.tree_combine(u, recv), u)

    return _broadcast_within_units(u, axis_name, size, stride, lane)


def _lane_chunk(x: jax.Array, stride: int, lane, atom: int) -> jax.Array:
    """This rank's 1/stride slice of a leaf (zero-padded to divide).

    The payload flattens to rows of ``atom`` trailing elements — the unit a
    structure-sensitive combine treats as one value (e.g. COMPLEX_MUL's
    real/imag pairs, ``wire_atom=2``) — and rows are dealt round-robin-free
    (contiguous blocks) across the unit's lanes.
    """
    if atom > 1 and x.size % atom == 0:
        flat = x.reshape(-1, atom)
    else:
        flat = x.reshape(-1)
    n = flat.shape[0]
    c = -(-n // stride)
    if stride * c != n:
        flat = jnp.pad(flat, ((0, stride * c - n),)
                       + ((0, 0),) * (flat.ndim - 1))
    return lax.dynamic_index_in_dim(flat.reshape((stride, c) + flat.shape[1:]),
                                    lane, 0, keepdims=False)


def _lane_all_gather(chunks: list[jax.Array], axis_name, size: int,
                     stride: int, lane) -> list[jax.Array]:
    """Reassemble each unit's (stride, chunk) buffer from per-lane chunks:
    recursive-doubling for power-of-two units, ring otherwise. All traffic
    stays inside the unit (sub-level links)."""
    bufs = [lax.dynamic_update_slice(
        jnp.zeros((stride,) + ch.shape, ch.dtype), ch[None], (lane,) + (0,) * ch.ndim)
        for ch in chunks]
    if permutes.is_pow2(stride):
        seg = 1
        for perm in permutes.lane_gather_doubling_perms(size, stride):
            start = (lane // seg) * seg
            segs = [lax.dynamic_slice(b, (start,) + (0,) * (b.ndim - 1),
                                      (seg,) + b.shape[1:]) for b in bufs]
            other = lax.ppermute(segs, axis_name, perm=perm)
            their_start = start ^ seg
            bufs = [lax.dynamic_update_slice(
                b, o, (their_start,) + (0,) * (b.ndim - 1))
                for b, o in zip(bufs, other)]
            seg <<= 1
        return bufs
    perm = permutes.ring_perm(size, stride)
    cur = chunks
    for s in range(1, stride):
        cur = lax.ppermute(cur, axis_name, perm=perm)
        src = (lane - s) % stride
        bufs = [lax.dynamic_update_slice(
            b, ch[None], (src,) + (0,) * ch.ndim)
            for b, ch in zip(bufs, cur)]
    return bufs


def _stage_lane(u: PyTree, axis_name, merge: MergeFn, stage: LevelStage,
                size: int, rank, use_compress: bool) -> PyTree:
    """Lane-parallel cross-unit exchange: the representative role is sharded
    over the unit's lanes. Each lane carries a 1/stride chunk of the payload
    through the butterfly/ring across sibling units (same-lane pairing), then
    the unit all-gathers the combined chunks. Total cross-unit bytes equal
    the representative-only exchange; per-link bytes drop by the unit size,
    so the expensive level's bandwidth parallelizes instead of serializing
    on lane 0.
    """
    stride, fanout = stage.stride, stage.fanout
    lane = rank % stride
    leaves, treedef = jax.tree.flatten(u)
    chunks = [_lane_chunk(x, stride, lane, merge.wire_atom) for x in leaves]
    perms = permutes.lane_exchange_perms(size, stride, fanout)
    butterfly = permutes.is_pow2(fanout)

    if use_compress:
        if butterfly:
            for perm in perms:
                wire = [merge.encode(ch) for ch in chunks]
                other = lax.ppermute(wire, axis_name, perm=perm)
                chunks = [merge.combine(merge.decode(w), merge.decode(o))
                          for w, o in zip(wire, other)]
        else:
            wire = [merge.encode(ch) for ch in chunks]
            chunks = [merge.decode(w) for w in wire]
            for _ in range(fanout - 1):
                wire = lax.ppermute(wire, axis_name, perm=perms[0])
                chunks = [merge.combine(a, merge.decode(w))
                          for a, w in zip(chunks, wire)]
    elif butterfly:
        for perm in perms:
            other = lax.ppermute(chunks, axis_name, perm=perm)
            chunks = [merge.combine(a, b) for a, b in zip(chunks, other)]
    else:
        recv = chunks
        for _ in range(fanout - 1):
            recv = lax.ppermute(recv, axis_name, perm=perms[0])
            chunks = [merge.combine(a, b) for a, b in zip(chunks, recv)]

    bufs = _lane_all_gather(chunks, axis_name, size, stride, lane)
    out = []
    for x, b in zip(leaves, bufs):
        full = b.reshape((b.shape[0] * b.shape[1],) + b.shape[2:])
        atom = full.shape[1] if full.ndim > 1 else 1
        out.append(lax.slice_in_dim(full, 0, x.size // atom).reshape(x.shape))
    return jax.tree.unflatten(treedef, out)


def _run_stages(update: PyTree, axis_name, merge: MergeFn,
                stages: list[LevelStage], size: int,
                force_tree: bool) -> PyTree:
    """Execute compiled stages in order. Invariant: entering stage i every
    rank holds its stride-sized unit's combination (replicated within the
    unit); leaving it, its block's. After the last stage every rank holds
    the full combination over the covered levels. Each stage's exchange,
    the fused collective or the software butterfly, runs under
    ``jax.named_scope("exchange")``, which its ops carry in their HLO
    ``op_name``."""
    u = update
    rank = None
    if any(s.stride > 1 for s in stages):
        rank = lax.axis_index(axis_name)
    for st in stages:
        use_compress = st.compress and merge.encode is not None
        with jax.named_scope("exchange"):
            if st.stride == 1:
                u = _stage_innermost(u, axis_name, merge, st, size,
                                     force_tree, use_compress)
            elif st.lane_parallel:
                u = _stage_lane(u, axis_name, merge, st, size, rank,
                                use_compress)
            else:
                u = _stage_rep(u, axis_name, merge, st, size, rank,
                               use_compress)
    return u


def hierarchical_merge(update: PyTree, axis_name, merge: MergeFn,
                       topology: Topology, compress: bool = False,
                       force_tree: bool = False) -> PyTree:
    """N-level all-reduce of ``update`` with an arbitrary combine.

    Equivalent to ``tree_merge`` (every rank ends with the full combination)
    but wire-aware: each level's exchange is confined to its link class, and
    an upper level with units of B ranks moves P/B contributions (or P
    chunks of 1/B size when lane-parallel) instead of P — the flat
    butterfly's cross-group rounds cost P full-payload messages where this
    costs P/B. Runs ALL levels eagerly, including ones marked ``defer``
    (use ``partial_merge`` + ``commit_deferred`` for merge-on-evict).
    """
    plan, axis_name, size = _resolve_plan(topology, axis_name, compress)
    if plan is None:
        # Degenerate: every rank is its own group -> flat dispatch.
        return reduce_update(update, axis_name, merge, compress=compress,
                             force_tree=force_tree)
    stages = compile_plan(plan, size, merge_fn=merge)
    return _run_stages(update, axis_name, merge, stages, size, force_tree)


def partial_merge(update: PyTree, axis_name, merge: MergeFn,
                  topology: Topology, compress: bool = False,
                  force_tree: bool = False) -> PyTree:
    """Run only the plan's EAGER (non-deferred) levels.

    Every rank ends with its eager-scope block's combination — e.g. with
    ``chip:4,host:16,pod:2:defer`` each rank holds its host-block (64-rank)
    aggregate and no pod-crossing traffic has occurred. Accumulate results
    into a ``PendingUpdate`` (``soft_merge(..., plan=...)``) and settle the
    deferred levels with ``commit_deferred`` every K steps.
    """
    plan, axis_name, size = _resolve_plan(topology, axis_name, compress)
    if plan is None:
        return update if size == 1 else reduce_update(
            update, axis_name, merge, compress=compress,
            force_tree=force_tree)
    eager, _ = split_eager_deferred(compile_plan(plan, size, merge_fn=merge))
    return _run_stages(update, axis_name, merge, eager, size, force_tree)


def settle_deferred(update: PyTree, axis_name, merge_fn: MergeFn,
                    topology: Topology, compress: bool = False,
                    force_tree: bool = False) -> PyTree:
    """Run every DEFERRED stage of the plan on ``update``.

    ``update`` must already be settled through the eager levels (a
    ``partial_merge`` output). Does not touch memory — this is the exchange
    half of ``commit_deferred``; per-stage scheduled commits go through
    ``defer_cascade`` instead.
    """
    plan, axis_name, size = _resolve_plan(topology, axis_name, compress)
    if plan is None:
        return update
    _, deferred = split_eager_deferred(
        compile_plan(plan, size, merge_fn=merge_fn))
    return _run_stages(update, axis_name, merge_fn, deferred, size,
                       force_tree)


def settle_inflight(inflight: PyTree, axis_name, merge_fn: MergeFn,
                    topology: Topology, compress: bool = False,
                    force_tree: bool = False) -> PyTree:
    """Run only the TOP deferred stage's exchange on a launched aggregate.

    The land half of :func:`overlap_cascade` as a standalone call — used to
    drain an in-flight commit at end of run (``DeferredTrainStep.flush``)
    when there is no next step to overlap with.
    """
    plan, axis_name, size = _resolve_plan(topology, axis_name, compress)
    if plan is None:
        raise ValueError("settle_inflight needs a MergePlan with deferred "
                         "levels (got a degenerate/flat topology)")
    _, deferred = split_eager_deferred(
        compile_plan(plan, size, merge_fn=merge_fn))
    if not deferred:
        raise ValueError("settle_inflight: plan has no deferred stages")
    return _run_stages(inflight, axis_name, merge_fn, [deferred[-1]], size,
                       force_tree)


def launch_inflight(update: PyTree, axis_name, merge_fn: MergeFn,
                    topology: Topology, compress: bool = False,
                    force_tree: bool = False) -> PyTree:
    """Run every deferred stage EXCEPT the top on ``update`` — the launch
    half of an overlapped full commit, the complement of
    :func:`settle_inflight`.

    The returned aggregate is the in-flight value :func:`overlap_cascade`
    would carry: settled through the cheap inner deferred levels, with the
    expensive top-level exchange left for the land program (where it rides
    alongside the next step's independent compute). ``launch_inflight``
    then ``settle_inflight`` composes to exactly :func:`settle_deferred`.
    """
    plan, axis_name, size = _resolve_plan(topology, axis_name, compress)
    if plan is None:
        raise ValueError("launch_inflight needs a MergePlan with deferred "
                         "levels (got a degenerate/flat topology)")
    _, deferred = split_eager_deferred(
        compile_plan(plan, size, merge_fn=merge_fn))
    if not deferred:
        raise ValueError("launch_inflight: plan has no deferred stages")
    return _run_stages(update, axis_name, merge_fn, deferred[:-1], size,
                       force_tree)


def commit_launch(pending: "PendingUpdate", axis_name, merge_fn: MergeFn,
                  topology: Topology, compress: bool = False,
                  force_tree: bool = False) -> PyTree:
    """Launch half of a deferred commit: run the deferred levels' exchange.

    Returns the settled full-scope aggregate *without* touching memory — the
    in-flight value. Emitting the exchange as its own stage group is what
    makes the commit overlappable: place this call in the same program as
    the next step's compute (no data dependency between them) and XLA's
    scheduler hides the expensive upper-level exchange behind that compute.
    Land the result with :func:`commit_land`.
    """
    return settle_deferred(pending.update, axis_name, merge_fn, topology,
                           compress=compress, force_tree=force_tree)


def commit_land(inflight: PyTree, mem: PyTree, merge_fn: MergeFn,
                key: Optional[jax.Array] = None) -> PyTree:
    """Land half of a deferred commit: fold a launched (already exchanged)
    aggregate into memory. Pure local work — no collectives."""
    return merge_fn.tree_apply(mem, inflight, key=key)


def commit_deferred(pending: "PendingUpdate", mem: PyTree, axis_name,
                    merge_fn: MergeFn, topology: Topology,
                    key: Optional[jax.Array] = None, compress: bool = False,
                    force_tree: bool = False) -> PyTree:
    """Settle the DEFERRED levels of a plan and apply to memory.

    ``pending`` must have been accumulated from ``partial_merge`` outputs
    (or ``soft_merge(..., plan=...)``): each rank holds the coalesced
    eager-scope aggregate, so only the deferred upper levels' exchange —
    the expensive cross-pod traffic — remains, paid once per K steps
    instead of every step (the paper's mergeable bit, level 2). The
    serialized composition of :func:`commit_launch` + :func:`commit_land`;
    overlapping callers split the halves across two steps.
    """
    u = commit_launch(pending, axis_name, merge_fn, topology,
                      compress=compress, force_tree=force_tree)
    return commit_land(u, mem, merge_fn, key=key)


@dataclasses.dataclass(frozen=True)
class StageManifest:
    """What one compiled stage is *scheduled* to put on the wire.

    Derived host-side from the same round formulas the stage executors run
    (``_stage_innermost`` / ``_stage_rep`` / ``_stage_lane``), so an HLO
    walk of the compiled program can be checked against it: any collective
    the manifest does not schedule is XLA-introduced (CC021).

    ``exchange_rounds`` are ``ppermute`` rounds at the stage's own plan
    level (level-``index`` links); ``intra_rounds`` are the stage's
    sub-level rounds (rep-stage unit broadcast, lane-stage unit
    all-gather) riding links strictly below ``index``. ``fused_ops`` is 1
    when the stage rides the fused XLA collective (one all-reduce per
    leaf, zero ppermutes).
    """

    index: int          # plan level index the stage executes
    name: str
    defer: bool
    stride: int
    fanout: int
    kind: str           # "fused" | "butterfly" | "ring"
    fused_ops: int
    exchange_rounds: int
    intra_rounds: int

    @property
    def permute_rounds(self) -> int:
        return self.exchange_rounds + self.intra_rounds


def _cross_unit_rounds(fanout: int) -> tuple[str, int]:
    if permutes.is_pow2(fanout):
        return "butterfly", fanout.bit_length() - 1
    return "ring", fanout - 1


def collective_manifest(topology: Topology, axis_size: int,
                        merge_fn: Optional[MergeFn] = None,
                        compress: bool = False,
                        force_tree: bool = False) -> list[StageManifest]:
    """The per-level collective schedule of ``topology`` on ``axis_size``.

    One :class:`StageManifest` per compiled stage, in execution order. A
    program that runs the stage subset S (e.g. a commit tick's
    eager+due-prefix) is scheduled to emit, per payload leaf, exactly
    ``sum(m.fused_ops for m in S)`` fused collectives and
    ``sum(m.permute_rounds for m in S)`` collective-permutes — the
    multiset the HLO placement linter asserts against.
    """
    if not isinstance(topology, MergePlan):
        if topology.group_size <= 1 or axis_size == 1:
            # flat dispatch (reduce_update): fused when available,
            # butterfly/ring otherwise
            if axis_size == 1:
                return []
            fused = (not force_tree and not compress and merge_fn is not None
                     and merge_fn.xla_reduce in _XLA_REDUCERS)
            if fused:
                kind, fused_ops, rounds = "fused", 1, 0
            elif permutes.is_pow2(axis_size):
                kind, fused_ops = "butterfly", 0
                rounds = axis_size.bit_length() - 1
            else:
                # tree_merge's non-pow2 fallback is all_gather + local
                # fold; it emits one all-gather and no ppermutes.
                kind, fused_ops, rounds = "gather", 0, 0
            return [StageManifest(index=0, name="flat", defer=False,
                                  stride=1, fanout=axis_size, kind=kind,
                                  fused_ops=fused_ops,
                                  exchange_rounds=rounds, intra_rounds=0)]
        topology = topology.to_plan(axis_size, compress=compress)
    plan = topology
    stages = compile_plan(plan, axis_size, merge_fn=merge_fn)
    out: list[StageManifest] = []
    for st in stages:
        use_compress = (st.compress and merge_fn is not None
                        and merge_fn.encode is not None)
        if st.stride == 1:
            fused = (st.combine_mode == "xla" and not force_tree
                     and not use_compress and merge_fn is not None
                     and merge_fn.xla_reduce in _XLA_REDUCERS)
            if fused:
                kind, fused_ops, rounds = "fused", 1, 0
            else:
                kind, rounds = _cross_unit_rounds(st.fanout)
                fused_ops = 0
            intra = 0
        else:
            kind, rounds = _cross_unit_rounds(st.fanout)
            fused_ops = 0
            if st.lane_parallel:
                # _lane_all_gather: doubling (pow2 stride) or ring
                intra = (st.stride.bit_length() - 1
                         if permutes.is_pow2(st.stride) else st.stride - 1)
            else:
                # _broadcast_within_units: binomial swap tree
                intra = max(0, (st.stride - 1).bit_length())
        out.append(StageManifest(
            index=st.index, name=st.name, defer=st.defer, stride=st.stride,
            fanout=st.fanout, kind=kind, fused_ops=fused_ops,
            exchange_rounds=rounds, intra_rounds=intra))
    return out


def program_manifest(topology: Topology, axis_size: int, due: int,
                     merge_fn: Optional[MergeFn] = None,
                     compress: bool = False,
                     force_tree: bool = False) -> list[StageManifest]:
    """Manifest of the stages a ``defer_cascade(due=...)`` tick executes:
    every eager stage plus the leading ``due`` deferred stages."""
    manifest = collective_manifest(topology, axis_size, merge_fn=merge_fn,
                                   compress=compress, force_tree=force_tree)
    eager = [m for m in manifest if not m.defer]
    deferred = [m for m in manifest if m.defer]
    if not 0 <= due <= len(deferred):
        raise ValueError(f"program_manifest: due={due} out of range "
                         f"[0, {len(deferred)}]")
    return eager + deferred[:due]


def overlap_program_manifest(topology: Topology, axis_size: int, half: str,
                             merge_fn: Optional[MergeFn] = None,
                             compress: bool = False,
                             force_tree: bool = False) -> list[StageManifest]:
    """Manifest of one half of an *overlapped* full commit.

    ``half="launch"`` — the commit tick's program: every eager stage plus
    every deferred stage below the top (:func:`launch_inflight`); the top
    exchange is withheld. ``half="land"`` — the following tick's program:
    the top deferred stage alone (:func:`settle_inflight`), riding next to
    that tick's collective-free scatter. The two halves partition the full
    ``program_manifest(due=n_deferred)`` schedule, so an HLO walk of each
    compiled half can be CC021-checked independently.
    """
    if half not in ("launch", "land"):
        raise ValueError(f"half must be 'launch' or 'land', got {half!r}")
    manifest = collective_manifest(topology, axis_size, merge_fn=merge_fn,
                                   compress=compress, force_tree=force_tree)
    deferred = [m for m in manifest if m.defer]
    if not deferred:
        raise ValueError("overlap_program_manifest: topology has no "
                         "deferred stages to overlap")
    if half == "land":
        return [deferred[-1]]
    eager = [m for m in manifest if not m.defer]
    return eager + deferred[:-1]


def deferred_stages_of(topology: Topology, axis_size: int,
                       merge_fn: Optional[MergeFn] = None) -> list:
    """The compiled deferred stages of ``topology`` on an ``axis_size`` axis
    (size-1 levels compile away, so this can be shorter than the plan's
    ``num_deferred``)."""
    if not isinstance(topology, MergePlan):
        return []
    _, deferred = split_eager_deferred(
        compile_plan(topology, axis_size, merge_fn=merge_fn))
    return deferred


def defer_cascade(delta: PyTree, pendings: Sequence[PyTree], due: int,
                  axis_name, merge_fn: MergeFn, topology: Topology,
                  compress: bool = False, force_tree: bool = False
                  ) -> tuple[list[PyTree], Optional[PyTree]]:
    """One step of the scheduled multi-level merge-on-evict cascade.

    ``pendings`` holds one accumulator per compiled deferred stage,
    innermost first; ``pendings[i]`` is replicated within stage i's
    stride-unit (it was built from settled stage i-1 blocks). ``due`` is the
    STATIC number of leading deferred stages committing this step — a
    nested :class:`~repro.core.defer_schedule.DeferSchedule` guarantees the
    due set is a prefix, which is what keeps the upward cascade from ever
    double-counting a contribution.

    The step's ``delta`` settles through the eager levels (per-step cheap
    traffic) and coalesces into ``pendings[0]``. Each due stage then
    exchanges its pending across its units — wire paid once per its
    interval — and folds the result into the pending above. Returns the new
    accumulators and, when every deferred stage committed, the full-scope
    combination (``None`` otherwise — the optimizer has nothing to consume
    on a partial commit).
    """
    plan, axis_name, size = _resolve_plan(topology, axis_name, compress)
    if plan is None:
        raise ValueError("defer_cascade needs a MergePlan with deferred "
                         "levels (got a degenerate/flat topology)")
    stages = compile_plan(plan, size, merge_fn=merge_fn)
    eager, deferred = split_eager_deferred(stages)
    if not deferred:
        raise ValueError("defer_cascade: plan has no deferred stages "
                         "(no :defer levels, or they all have size 1)")
    pendings = list(pendings)
    if len(pendings) != len(deferred):
        raise ValueError(
            f"defer_cascade: {len(pendings)} pendings for "
            f"{len(deferred)} deferred stages "
            f"({[s.name for s in deferred]})")
    if not 0 <= due <= len(deferred):
        raise ValueError(f"defer_cascade: due={due} out of range "
                         f"[0, {len(deferred)}]")

    u = _run_stages(delta, axis_name, merge_fn, eager, size, force_tree)
    x = merge_fn.tree_combine(pendings[0], u)
    if due == 0:
        return [x] + pendings[1:], None

    new_pendings = list(pendings)
    for i in range(due):
        new_pendings[i] = merge_fn.tree_identity(pendings[i])
        x = _run_stages(x, axis_name, merge_fn, [deferred[i]], size,
                        force_tree)
        if i + 1 < len(deferred):
            if i + 1 < due:
                x = merge_fn.tree_combine(pendings[i + 1], x)
            else:
                new_pendings[i + 1] = merge_fn.tree_combine(pendings[i + 1], x)
    settled = x if due == len(deferred) else None
    return new_pendings, settled


def overlap_cascade(delta: PyTree, pendings: Sequence[PyTree],
                    inflight: PyTree, due: int, land: bool, axis_name,
                    merge_fn: MergeFn, topology: Topology,
                    compress: bool = False, force_tree: bool = False
                    ) -> tuple[list[PyTree], PyTree, Optional[PyTree]]:
    """One step of the *overlapped* scheduled merge-on-evict cascade.

    Like :func:`defer_cascade`, but the TOP deferred stage — the expensive
    cross-pod exchange that otherwise serializes the full-commit step —
    is split into launch/land halves one step apart:

    * on a full-commit step (``due == len(deferred)``), the aggregate that
      would have entered the top stage's exchange is *launched* instead:
      returned as the new ``inflight`` buffer, with no top-level traffic
      this step;
    * on the following step (``land=True``), the top stage's exchange runs
      on ``inflight`` — inside the same program as that step's compute,
      with no data dependency between them, so the collective hides behind
      the compute — and the settled full-scope aggregate is returned as
      ``landed`` for the caller to fold into memory (``commit_land`` /
      the optimizer), one step stale.

    ``due``/``land`` are STATIC (host-side schedule decisions). Inner
    deferred stages still commit inline — they ride cheap links. Returns
    ``(new_pendings, new_inflight, landed)``; ``landed`` is ``None``
    unless ``land``. A launched-then-landed cycle is numerically the same
    aggregate ``defer_cascade`` would have settled on the launch step —
    the overlap only delays *when* it lands (one-step-stale semantics).
    """
    plan, axis_name, size = _resolve_plan(topology, axis_name, compress)
    if plan is None:
        raise ValueError("overlap_cascade needs a MergePlan with deferred "
                         "levels (got a degenerate/flat topology)")
    stages = compile_plan(plan, size, merge_fn=merge_fn)
    eager, deferred = split_eager_deferred(stages)
    if not deferred:
        raise ValueError("overlap_cascade: plan has no deferred stages "
                         "(no :defer levels, or they all have size 1)")
    pendings = list(pendings)
    if len(pendings) != len(deferred):
        raise ValueError(
            f"overlap_cascade: {len(pendings)} pendings for "
            f"{len(deferred)} deferred stages "
            f"({[s.name for s in deferred]})")
    n = len(deferred)
    if not 0 <= due <= n:
        raise ValueError(f"overlap_cascade: due={due} out of range [0, {n}]")

    # Land first: the previous step's launched aggregate takes the top
    # stage's exchange. It depends only on carried state, never on this
    # step's delta — the independence that lets XLA overlap it.
    landed = None
    new_inflight = inflight
    if land:
        landed = _run_stages(inflight, axis_name, merge_fn, [deferred[-1]],
                             size, force_tree)
        new_inflight = merge_fn.tree_identity(inflight)

    u = _run_stages(delta, axis_name, merge_fn, eager, size, force_tree)
    x = merge_fn.tree_combine(pendings[0], u)
    if due == 0:
        return [x] + pendings[1:], new_inflight, landed

    new_pendings = list(pendings)
    for i in range(due):
        new_pendings[i] = merge_fn.tree_identity(pendings[i])
        if i == n - 1:
            # Top stage: launch instead of exchange. x already folded in
            # pendings[n-1] (combined below when i+1 < due), so inflight
            # carries the cycle's complete pre-exchange aggregate.
            new_inflight = x
            break
        x = _run_stages(x, axis_name, merge_fn, [deferred[i]], size,
                        force_tree)
        if i + 1 < due:
            x = merge_fn.tree_combine(pendings[i + 1], x)
        else:
            new_pendings[i + 1] = merge_fn.tree_combine(pendings[i + 1], x)
    return new_pendings, new_inflight, landed


def reduce_update(update: PyTree, axis_name, merge: MergeFn,
                  compress: bool = False, force_tree: bool = False,
                  topology: Optional[Topology] = None) -> PyTree:
    """Cross-device combination of per-device updates.

    COUP fast path (fixed op fused into the collective) when available and not
    overridden; CCache flexible path (tree_merge) otherwise. A ``topology``
    (two-level ``MergeTopology`` with ``group_size > 1``, or any
    ``MergePlan``) routes through the N-level hierarchical engine instead of
    the flat paths.
    """
    if topology is not None and (isinstance(topology, MergePlan)
                                 or topology.group_size > 1):
        return hierarchical_merge(update, axis_name, merge, topology,
                                  compress=compress, force_tree=force_tree)
    if compress:
        return tree_merge(update, axis_name, merge, compress=True)
    if not force_tree and merge.xla_reduce in _XLA_REDUCERS:
        return jax.tree.map(
            functools.partial(_XLA_REDUCERS[merge.xla_reduce], axis_name=axis_name),
            update)
    return tree_merge(update, axis_name, merge)


def merge(view: CView, mem: PyTree, axis_name, merge_fn: MergeFn,
          key: Optional[jax.Array] = None, compress: bool = False,
          force_tree: bool = False,
          topology: Optional[Topology] = None) -> PyTree:
    """Full CCache merge: delta -> cross-device combine -> apply to memory.

    Every rank computes the identical combined update, so applying it to the
    (replicated) memory copy leaves memory consistent — the paper's "when all
    cores have merged, the in-memory copy is up to date", with per-line
    atomicity by construction (no locks; see DESIGN.md §2).
    """
    u = merge_fn.tree_delta(view.src, view.upd)
    u = reduce_update(u, axis_name, merge_fn, compress=compress,
                      force_tree=force_tree, topology=topology)
    return merge_fn.tree_apply(mem, u, key=key)


# ---------------------------------------------------------------------------
# soft_merge: deferred, locally-coalesced merging (merge-on-evict analog).
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PendingUpdate:
    """Locally coalesced updates awaiting a cross-device merge."""

    update: PyTree


def soft_merge(view: CView, pending: Optional[PendingUpdate],
               merge_fn: MergeFn, axis_name=None,
               plan: Optional[Topology] = None,
               force_tree: bool = False) -> tuple[CView, PendingUpdate]:
    """Coalesce the view's delta into ``pending``; reset the view's source.

    The cross-device merge is postponed (cf. the mergeable bit): call
    ``commit`` at the merge boundary. Between soft_merges the core keeps
    locality on its private copy.

    With a ``plan`` (and its ``axis_name``), the delta is first settled
    through the plan's EAGER levels — cheap intra-chip/host traffic paid per
    step — so ``pending`` accumulates host-scope aggregates and only the
    deferred upper levels remain for ``commit_deferred``: merge-on-evict at
    pod scope.
    """
    u = merge_fn.tree_delta(view.src, view.upd)
    if plan is not None:
        u = partial_merge(u, axis_name, merge_fn, plan,
                          force_tree=force_tree)
    if pending is None:
        pending = PendingUpdate(update=u)
    else:
        pending = PendingUpdate(update=merge_fn.tree_combine(pending.update, u))
    return CView(src=view.upd, upd=view.upd), pending


def commit(pending: PendingUpdate, mem: PyTree, axis_name, merge_fn: MergeFn,
           key: Optional[jax.Array] = None, compress: bool = False,
           topology: Optional[Topology] = None) -> PyTree:
    """Apply a deferred pending update to memory (the eviction-time merge).

    Runs the FULL cross-device reduction — use for pendings accumulated
    without a plan. For plan-accumulated pendings (eager levels already
    settled) use ``commit_deferred``, which runs only the remaining levels.
    """
    u = reduce_update(pending.update, axis_name, merge_fn, compress=compress,
                      topology=topology)
    return merge_fn.tree_apply(mem, u, key=key)
