"""A sharded commutative KV store: the paper's headline app as a serving tier.

By default the table lives replicated per device (every shard can answer
any read from its *settled* copy); the **update stream** is what shards
over the mesh axis
— each device privatizes the updates it receives and cross-device agreement
is an explicit, batched merge through the MergePlan engine.  This is the
CXL-style partial-coherence structure: hot updates live in non-coherent
private state, coherence is a scheduled event, not a per-access protocol.

Two privatization engines, same algebra:

* ``engine="kernel"`` — the production hot path.  A tick's updates scatter
  into a merge-identity table: on a TPU backend through the compiled Pallas
  ``cscatter`` kernel, whose VMEM accumulator *is* the privatized copy —
  merged once per touched block, in place, with touched-mask dirty-merge
  skip;
  elsewhere through XLA's scatter (ADD) or the jnp oracle.  One shard is a
  store too: its plan has no exchanging level, so it is synchronized and
  every tick is a scatter into the settled table.
* ``engine="blocked"`` — the faithful instrumented model.  A resident
  ``core.blocked.BlockedCache`` (W ways, LRU, merge-on-evict, dirty-merge
  skip) carries privatized blocks **across ticks**; only evicted mass
  enters the merge cascade each tick, and ``flush`` drains the rest at
  commits.  Fig. 9-style counters come out of ``counters()``.

Cross-device reconciliation is ``ccache.defer_cascade`` over a (by default
fully) deferred plan: non-commit ticks run **zero collectives**, commit
ticks settle the pending cascade per the :class:`DeferSchedule` (solve one
with ``solve_defer_schedule`` from the measured wire vector — see
``benchmarks/kv_gups.py``).  The store is eventually-merged by default;
``consistency="read_your_writes"`` routes reads through the device's own
unmerged state (pendings + resident cache, ``c_read_row`` semantics) on
top of the last settled table, still with zero read-path collectives.

``KVConfig(partitioned=True)`` drops the replication: each settled row
lives on exactly ONE home shard (global key ``k`` -> shard ``k % S``,
local row ``k // S``), so the per-device settled footprint is ``n_keys /
n_shards`` rows and reads must be routed by key — exactly how
:class:`~repro.serve.frontend.BatchedFrontend` already routes traffic, and
still zero read-path collectives.  Dense per-level pending tables go away
with the replication: the kernel engine buffers a tick's raw updates in a
bounded ring (``max_period * batch`` slots — overflow is impossible by
construction, a full commit fires within ``max_period`` ticks and resets
the cursor), and the blocked engine's resident cache spills evicted blocks
into a bounded :class:`~repro.core.blocked.SpillBuffer` instead of a dense
table (spill-through-eviction).  A kernel-engine commit that does not
overlap routes the ring home: one all-gather of every shard's ring, after
which each shard folds the updates it homes into its settled rows, in
place, through the scatter kernel — no table-sized buffer, so the table
can fill the device.  Its settled rows are lane-dense: a narrow row of
``cols`` lanes shares a 128-lane line with ``128 // cols`` others (local
row ``l`` in line ``l // P``), the layout a ``(rows, cols)`` array would
take row-major.  The
blocked engine and the overlapped commit still settle the FULL cascade on
a transient dense delta — same collectives, same manifest — and each shard
keeps only its home rows of the aggregate.  ``DeferSchedule(overlap=True)``
additionally splits the commit into launch/land halves
(``ccache.launch_inflight`` / ``settle_inflight``): the top-level exchange
launched at the commit tick lands inside the NEXT tick's program, where it
overlaps that tick's scatter; the settled table runs one tick stale during
the window.  An :class:`~repro.core.defer_schedule.AdaptiveDeferSchedule`
re-solves the commit interval from the measured updates/tick EMA.
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import blocked, ccache
from repro.core.defer_schedule import DeferSchedule
from repro.core.merge_functions import ADD, MergeFn
from repro.core.merge_plan import MergeLevel, MergePlan
from repro.apps.common import default_plan, scatter
from repro.serve.spans import span

Array = jax.Array

_CONSISTENCY = ("eventual", "read_your_writes")
_ENGINES = ("kernel", "blocked")
# merge kinds the scatter phase (Pallas kernel / jnp oracle) understands:
# a MergeFn's fixed op (``xla_reduce``) names its kind.
_KERNEL_KINDS = ("add", "max", "min", "or", "xor")

DEFAULT_COMMIT_EVERY = 8
# lanes of a vector register, and of a lane-dense settled line
LANES = 128


def _valid(keys: Array, n_keys: int) -> Array:
    """Keys in ``[0, n_keys)``; a key space as wide as the key dtype needs
    only the sign test."""
    ok = keys >= 0
    if n_keys <= jnp.iinfo(keys.dtype).max:
        ok = ok & (keys < n_keys)
    return ok


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` renamed: the mesh executor compiles it as the program
    ``jit_<name>``, the name its runs carry in a profiler trace."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def _scoped(scope: str) -> Callable:
    """Trace the decorated function under ``jax.named_scope(scope)``: its
    ops carry the scope in their HLO ``op_name`` metadata, which the trace
    viewer shows as each op's scope path."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(scope):
                return fn(*args, **kwargs)
        return scoped
    return wrap


def _read_program(fn: Callable, kind: str) -> Callable:
    """A read closure as the program ``kv_read`` (``kind`` "plain") or
    ``kv_read_<kind>``, its ops under the ``read`` scope."""
    name = "kv_read" if kind == "plain" else f"kv_read_{kind}"
    return _named(_scoped("read")(fn), name)


def serving_plan(n_shards: int, defer: str = "all",
                 lane_parallel: bool = True) -> MergePlan:
    """The serving tier's merge plan: ``default_plan`` geometry, with the
    commit policy as a knob.

    ``defer="all"`` (the serving default) marks *every* level ``:defer`` —
    a non-commit tick runs no collectives at all, the whole hierarchy
    settles on schedule.  ``"top"`` defers only the outermost level
    (training's shape: cheap links eager, the expensive one amortized).
    ``"none"`` is the fully-synchronized reference — every level
    exchanges every tick (the lock-array strawman's coherence bill).
    """
    if defer not in ("all", "top", "none"):
        raise ValueError(f"defer must be all|top|none, got {defer!r}")
    base = default_plan(n_shards, lane_parallel=lane_parallel)
    exec_ix = [i for i, lv in enumerate(base.levels) if lv.size > 1]
    if defer == "none" or not exec_ix:
        return base
    # defer is a suffix property of the plan: mark from the first (or
    # last, for "top") exchanging level upward, riding over any size-1
    # levels above it (they exchange nothing either way).
    start = exec_ix[0] if defer == "all" else exec_ix[-1]
    levels = tuple(
        dataclasses.replace(lv, defer=True) if i >= start else lv
        for i, lv in enumerate(base.levels))
    return dataclasses.replace(base, levels=levels)


@dataclasses.dataclass(frozen=True)
class KVConfig:
    """Shape/policy of one :class:`ShardedKV` table."""

    n_keys: int
    cols: int = 1
    dtype: Any = jnp.int32
    merge: MergeFn = ADD
    consistency: str = "eventual"
    engine: str = "kernel"
    # blocked engine: the paper's W-way source buffer geometry.
    ways: int = 8
    block_rows: int = 8
    # partitioned settled table: every global row on exactly one home shard
    # (key % n_shards); pendings become a bounded ring (kernel engine) or
    # the blocked cache's spill-through-eviction buffer (module doc).
    partitioned: bool = False
    spill_blocks: int = 64

    def __post_init__(self):
        if self.consistency not in _CONSISTENCY:
            raise ValueError(f"consistency must be one of {_CONSISTENCY}, "
                             f"got {self.consistency!r}")
        if self.engine not in _ENGINES:
            raise ValueError(f"engine must be one of {_ENGINES}, "
                             f"got {self.engine!r}")
        if self.engine == "kernel" and \
                self.merge.xla_reduce not in _KERNEL_KINDS:
            raise ValueError(
                f"engine='kernel' scatters through the cscatter kernel, "
                f"which has no kind for merge {self.merge.name!r} "
                f"(xla_reduce={self.merge.xla_reduce!r}); use "
                f"engine='blocked' for flexible-path merges")
        if self.engine == "blocked" and self.n_keys % self.block_rows != 0:
            raise ValueError(
                f"blocked engine: n_keys={self.n_keys} must be a multiple "
                f"of block_rows={self.block_rows}")
        if self.spill_blocks < 1:
            raise ValueError(f"spill_blocks must be >= 1, "
                             f"got {self.spill_blocks}")


def _rechunk_records(records, S: int, batch: Optional[int] = None):
    """Re-chunk journaled ``(keys, vals)`` tick batches for replay into a
    store with ``S`` shards. When every record already has leading dim
    ``S`` and one common width (same-shaped store), records pass through
    untouched — bitwise-identical replay. Otherwise valid entries (key >=
    0) are flattened, re-padded, and regrouped into uniform ``[S, batch]``
    ticks (one record may become several); commutativity makes any
    regrouping settle to the same table."""
    records = [(np.asarray(k), np.asarray(v)) for k, v in records]
    if not records:
        return
    if (batch is None
            and all(k.shape[0] == S for k, _ in records)
            and len({k.shape[1] for k, _ in records}) == 1):
        yield from records
        return
    if batch is None:
        batch = max([1] + [int(np.ceil((k >= 0).sum() / S))
                           for k, _ in records])
    per = S * batch
    for k, v in records:
        kf = k.reshape(-1)
        vf = v.reshape(-1, v.shape[-1])
        ok = kf >= 0
        kf, vf = kf[ok], vf[ok]
        for lo in range(0, max(len(kf), 1), per):
            ck, cv = kf[lo:lo + per], vf[lo:lo + per]
            pk = np.full((per,), -1, np.int32)
            pv = np.zeros((per, v.shape[-1]), v.dtype)
            pk[:len(ck)] = ck
            pv[:len(ck)] = cv
            yield (pk.reshape(S, batch), pv.reshape(S, batch, v.shape[-1]))


class ShardedKV:
    """The store.  Host-side driver around per-shard compiled tick/read fns.

    ``spmd(fn, *args)`` is the executor contract shared with the apps:
    every arg/result carries a leading shard axis, ``fn`` sees unbatched
    per-shard values with ``axis_name`` bound (``apps.sharded.mesh_spmd``
    on a real mesh, ``jax.vmap(..., axis_name=...)`` in tests).  All step
    closures are created once here — both executors memoize by function
    identity, so each (engine, due) program compiles exactly once.
    """

    def __init__(self, config: KVConfig, n_shards: int,
                 spmd: Callable, *, axis_name: str = "shards",
                 plan: Optional[MergePlan] = None,
                 schedule: Optional[DeferSchedule] = None,
                 commit_every: Optional[int] = None):
        if n_shards < 1:
            raise ValueError(f"ShardedKV needs n_shards >= 1, got {n_shards}")
        self.config = config
        self.n_shards = n_shards
        self.spmd = spmd
        # state args (settled/pendings/cache) are rebound from each tick's
        # result, so their buffers can be donated for in-place updates —
        # but only executors that take the keyword support it (mesh_spmd
        # does; the tests' plain vmap lambda does not).
        try:
            self._can_donate = "donate" in inspect.signature(spmd).parameters
        except (TypeError, ValueError):
            self._can_donate = False
        self.axis_name = axis_name
        self.plan = plan if plan is not None else serving_plan(n_shards)
        merge = config.merge

        from repro.core.merge_plan import compile_plan
        all_stages = compile_plan(self.plan, n_shards, merge_fn=merge)
        stages = [s for s in all_stages if s.defer]
        self._deferred_names = tuple(s.name for s in stages)
        self.n_deferred = len(stages)
        self.synchronized = self.n_deferred == 0
        # fully deferred (no eager stages): a non-commit tick has no
        # exchange at all, so updates coalesce straight into the resident
        # pending — the merge-on-evict hot path, one pass over the touched
        # blocks per tick
        self._fully_deferred = len(all_stages) == self.n_deferred > 0
        if self.synchronized:
            if schedule is not None or commit_every is not None:
                raise ValueError("plan has no deferred levels; a commit "
                                 "schedule is meaningless — drop it or use "
                                 "a :defer plan")
        else:
            if schedule is None:
                if commit_every is None:
                    commit_every = DEFAULT_COMMIT_EVERY
                if commit_every < 1:
                    # `commit_every or DEFAULT` would silently turn an
                    # explicit 0 into the default — reject it loudly.
                    raise ValueError(
                        f"commit_every must be >= 1 (got {commit_every}); "
                        f"a zero/negative interval has no commit ticks — "
                        f"use plan=serving_plan(n, 'none') for a "
                        f"synchronized store")
                schedule = DeferSchedule.fixed(commit_every,
                                               self._deferred_names)
            elif commit_every is not None:
                raise ValueError("pass schedule= or commit_every=, not both")
            if tuple(schedule.level_names) != self._deferred_names:
                raise ValueError(
                    f"schedule levels {schedule.level_names} do not match "
                    f"the plan's deferred stages {self._deferred_names}")
        self.schedule = schedule
        if config.engine == "blocked" and not self.synchronized:
            eager = [lv.name for lv in self.plan.levels
                     if lv.size > 1 and not lv.defer]
            if eager:
                raise ValueError(
                    f"engine='blocked' needs a fully deferred plan: eager "
                    f"levels {eager} would settle per tick while the "
                    f"resident cache withholds unmerged mass from them; "
                    f"use serving_plan(n, 'all') or engine='kernel'")

        self.partitioned = config.partitioned
        self._overlap = bool(schedule is not None
                             and getattr(schedule, "overlap", False))
        if self._overlap and not config.partitioned:
            raise ValueError(
                "schedule.overlap=True: the overlapped (launch/land) commit "
                "is the partitioned store's pipeline — set "
                "KVConfig(partitioned=True) or drop overlap")
        if config.partitioned:
            if self.synchronized:
                raise ValueError(
                    "partitioned=True needs deferred commits (the "
                    "partitioned table only settles at commit ticks); "
                    "use a :defer plan")
            if not self._fully_deferred:
                raise ValueError(
                    "partitioned=True needs a fully deferred plan: the "
                    "partitioned pendings (ring/spill) only drain at "
                    "commits, so an eager level would never settle; use "
                    "serving_plan(n, 'all')")
            if config.n_keys % n_shards != 0:
                raise ValueError(
                    f"partitioned=True: n_keys={config.n_keys} must be a "
                    f"multiple of n_shards={n_shards} (each shard homes "
                    f"n_keys/n_shards rows)")
            if len(set(schedule.intervals)) > 1:
                raise ValueError(
                    f"partitioned=True commits all-or-nothing (one commit "
                    f"tick settles the whole cascade), so the schedule "
                    f"must be uniform; got nested intervals "
                    f"{schedule.intervals}")
            if self._overlap:
                merge.check_overlap("ShardedKV(partitioned, overlap)")

        # a kernel-engine commit that does not overlap routes the ring home
        # (module doc: partitioned); its settled rows are lane-dense,
        # ``_pack`` narrow rows to a line
        self._routed = (config.partitioned and config.engine == "kernel"
                        and not self._overlap)
        # -- device state (leading shard axis) ------------------------------
        S, R, D = n_shards, config.n_keys, config.cols
        self._pack = 1
        if self._routed:
            if D < LANES and LANES % D == 0 and (R // S) % (LANES // D) == 0:
                self._pack = LANES // D
            self.settled = self._init_settled()
            self.pendings = ()
        elif config.partitioned:
            self.settled = jnp.broadcast_to(
                merge.identity((R // S, D), config.dtype), (S, R // S, D))
            self.pendings = ()
        else:
            ident_row = merge.identity((R, D), config.dtype)
            self.settled = jnp.broadcast_to(ident_row, (S, R, D))
            self.pendings = tuple(
                jnp.broadcast_to(ident_row, (S, R, D))
                for _ in range(self.n_deferred))
        self.cache = None
        self.spill = None
        if config.engine == "blocked":
            c0 = blocked.init_cache(config.ways, config.block_rows, D,
                                    config.dtype)
            self.cache = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (S,) + x.shape), c0)
            if config.partitioned:
                s0 = blocked.init_spill(config.spill_blocks,
                                        config.block_rows, D, config.dtype,
                                        merge)
                self.spill = jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (S,) + x.shape), s0)
        # kernel-engine partitioned pendings: a ring of raw updates sized
        # max_period * batch — allocated at the first tick, when the fixed
        # batch shape is first seen.
        self.ring = None
        self._ring_batch = None
        self.inflight = None
        self._land_pending = False
        self._t = 0
        # durability (journal.py / snapshot / recover): the journal is the
        # write-ahead log of acknowledged ticks; _replaying suppresses
        # re-journaling while recovery replays it back through tick().
        self._journal = None
        self._dur_root = None
        self._replaying = False

        # -- compiled-once per-shard programs -------------------------------
        self._tick_fns: dict[Any, Callable] = {}
        if self.synchronized:
            self._tick_fns["sync"] = self._make_sync_tick()
            self._read_fn = self._make_read()
        elif config.partitioned:
            for land in ((False, True) if self._overlap else (False,)):
                for full in (False, True):
                    self._tick_fns[("p", full, land)] = \
                        self._make_part_tick(full, land)
            self._flush_fn = self._make_part_flush(land=False)
            if self._overlap:
                self._flush_land_fn = self._make_part_flush(land=True)
            self._read_fns = {"plain": self._make_part_read("plain")}
            if config.consistency == "read_your_writes":
                self._read_fns["ryw"] = self._make_part_read("ryw")
                if self._overlap:
                    self._read_fns["ryw_inflight"] = \
                        self._make_part_read("ryw_inflight")
        else:
            for due in range(self.n_deferred + 1):
                self._tick_fns[due] = self._make_deferred_tick(due)
            self._flush_fn = self._make_flush()
            self._read_fn = self._make_read()

    # ------------------------------------------------------------------
    # per-shard program builders (closures created once, see class doc)
    # ------------------------------------------------------------------

    def _init_settled(self) -> Array:
        """The routed store's settled lines, made on each shard's own
        device (a table that fills the devices never passes through one)."""
        cfg, S, P = self.config, self.n_shards, self._pack
        shape = (cfg.n_keys // S // P, cfg.cols * P)

        def kv_init(_):
            return cfg.merge.identity(shape, cfg.dtype)

        return self.spmd(kv_init, jnp.zeros((S,), jnp.int32))

    @_scoped("identity")
    def _identity_table(self) -> Array:
        cfg = self.config
        return cfg.merge.identity((cfg.n_keys, cfg.cols), cfg.dtype)

    @_scoped("scatter")
    def _scatter_into(self, table: Array, keys: Array, vals: Array) -> Array:
        """One shard's scatter phase: fold this tick's updates into
        ``table`` (the merge-identity table for a fresh delta, or the
        resident pending itself on the fully-deferred hot path — for the
        kernel kinds ``apply == combine``, so ``scatter(pending, ...)``
        equals ``combine(pending, scatter(identity, ...))``)."""
        cfg = self.config
        kind = cfg.merge.xla_reduce
        on_tpu = jax.default_backend() == "tpu"
        if kind == "add" and not on_tpu:
            # one-pass fused scatter-add: no identity table, no touched
            # mask — the oracle's passes cost full table sweeps
            ok = (keys >= 0) & (keys < table.shape[0])
            safe = jnp.where(ok, keys, 0).astype(jnp.int32)
            return table.at[safe].add(
                jnp.where(ok[:, None], vals, jnp.zeros_like(vals)))
        return scatter(table, keys, vals, kind=kind, use_pallas=on_tpu)

    def _scatter_delta(self, keys: Array, vals: Array) -> Array:
        """This tick's updates as a privatized delta table."""
        return self._scatter_into(self._identity_table(), keys, vals)

    def _blocked_delta(self, cache, keys: Array, vals: Array):
        """Run the tick's updates through the resident BlockedCache; the
        returned table holds only the mass *evicted* this tick."""
        cfg = self.config
        ok = (keys >= 0) & (keys < cfg.n_keys)
        ident_val = cfg.merge.identity((cfg.cols,), cfg.dtype)
        # padding: invalid keys become identity updates on row 0 — a
        # combine no-op (the scan model has no skip lane).
        safe = jnp.where(ok, keys, 0).astype(jnp.int32)
        vals = jnp.where(ok[:, None], vals, ident_val)
        return blocked.cop_scatter(cache, self._identity_table(), safe,
                                   vals, cfg.merge)

    @_scoped("apply")
    def _apply(self, settled: Array, agg: Array) -> Array:
        return self.config.merge.apply(settled, agg)

    @_scoped("settle")
    def _cascade(self, delta: Array, pendings, due: int):
        return ccache.defer_cascade(delta, list(pendings), due,
                                    self.axis_name, self.config.merge,
                                    self.plan)

    @_scoped("settle")
    def _settle(self, delta: Array) -> Array:
        return ccache.settle_deferred(delta, self.axis_name,
                                      self.config.merge, self.plan)

    @_scoped("launch")
    def _launch(self, delta: Array) -> Array:
        return ccache.launch_inflight(delta, self.axis_name,
                                      self.config.merge, self.plan)

    @_scoped("land")
    def _land(self, inflight: Array) -> Array:
        return ccache.settle_inflight(inflight, self.axis_name,
                                      self.config.merge, self.plan)

    def _make_sync_tick(self):
        merge, axis, plan = self.config.merge, self.axis_name, self.plan

        def sync_tick(settled, keys, vals):
            delta = self._scatter_delta(keys, vals)
            with jax.named_scope("settle"):
                full = ccache.hierarchical_merge(delta, axis, merge, plan)
            return self._apply(settled, full)

        return _named(sync_tick, "kv_tick_sync")

    def _make_deferred_tick(self, due: int):
        merge = self.config.merge
        full = due == self.n_deferred

        if self.config.engine == "kernel" and self._fully_deferred:
            def tick(settled, pendings, keys, vals):
                # hot path: coalesce straight into the resident pending
                p0 = self._scatter_into(pendings[0], keys, vals)
                if due == 0:
                    return settled, (p0,) + tuple(pendings[1:])
                new_p, agg = self._cascade(
                    self._identity_table(), [p0] + list(pendings[1:]), due)
                if full:
                    settled = self._apply(settled, agg)
                return settled, tuple(new_p)
        elif self.config.engine == "kernel":
            def tick(settled, pendings, keys, vals):
                delta = self._scatter_delta(keys, vals)
                new_p, agg = self._cascade(delta, pendings, due)
                if full:
                    settled = self._apply(settled, agg)
                return settled, tuple(new_p)
        else:
            def tick(settled, pendings, cache, keys, vals):
                cache, delta = self._blocked_delta(cache, keys, vals)
                if due > 0:
                    # commit tick: the resident (unevicted) mass must
                    # enter the cascade too — the explicit merge instr.
                    cache, delta = blocked.flush(cache, delta, merge)
                new_p, agg = self._cascade(delta, pendings, due)
                if full:
                    settled = self._apply(settled, agg)
                return settled, tuple(new_p), cache

        return _named(tick, f"kv_tick_commit_{due}" if due else
                      "kv_tick_defer")

    def _make_flush(self):
        merge = self.config.merge
        due = self.n_deferred

        if self.config.engine == "kernel":
            def flush_fn(settled, pendings):
                new_p, agg = self._cascade(self._identity_table(), pendings,
                                           due)
                return self._apply(settled, agg), tuple(new_p)
        else:
            def flush_fn(settled, pendings, cache):
                cache, delta = blocked.flush(cache, self._identity_table(),
                                             merge)
                new_p, agg = self._cascade(delta, pendings, due)
                return self._apply(settled, agg), tuple(new_p), cache

        return _named(flush_fn, "kv_flush")

    # -- partitioned-mode builders (module doc: partitioned) ------------

    @_scoped("home_rows")
    def _home_rows(self, agg: Array) -> Array:
        """This shard's home rows of a full ``(n_keys, cols)`` aggregate:
        global row ``r`` lives on shard ``r % S`` at local index
        ``r // S``."""
        S = self.n_shards
        me = jax.lax.axis_index(self.axis_name)
        return agg.reshape(self.config.n_keys // S, S,
                           self.config.cols)[:, me, :]

    @_scoped("ring_append")
    def _ring_append(self, ring, keys: Array, vals: Array):
        rk, rv, cur = ring
        rk = jax.lax.dynamic_update_slice_in_dim(rk, keys, cur, axis=0)
        rv = jax.lax.dynamic_update_slice_in_dim(rv, vals, cur, axis=0)
        return rk, rv, cur + keys.shape[0]

    @_scoped("ring_reset")
    def _ring_reset(self, ring):
        rk, rv, cur = ring
        return (jnp.full_like(rk, -1),
                self.config.merge.identity(rv.shape, rv.dtype),
                jnp.zeros_like(cur))

    @_scoped("route")
    def _route(self, ring):
        """Every shard's ring, gathered on each: keys ``[S*C]``, values
        ``[S*C, cols]``."""
        rk, rv, _ = ring
        return (jax.lax.all_gather(rk, self.axis_name, tiled=True),
                jax.lax.all_gather(rv, self.axis_name, tiled=True))

    def _settle_home(self, settled: Array, keys: Array, vals: Array) -> Array:
        """Fold the updates this shard homes into its settled lines, in
        place: global key ``k`` is local row ``l = k // S``, which sits in
        line ``l // P`` at lanes ``(l % P) * cols`` on; the line's other
        lanes take the merge identity."""
        cfg, S, P, D = self.config, self.n_shards, self._pack, \
            self.config.cols
        me = jax.lax.axis_index(self.axis_name)
        ok = _valid(keys, cfg.n_keys) & (keys % S == me)
        local = keys // S
        if P > 1:
            with jax.named_scope("pack"):
                lane = jnp.arange(P * D) // D
                vals = jnp.where(lane[None, :] == (local % P)[:, None],
                                 jnp.tile(vals, (1, P)),
                                 cfg.merge.identity((), vals.dtype))
        return self._scatter_into(settled, jnp.where(ok, local // P, -1),
                                  vals)

    def _commit_home(self, settled: Array, ring) -> Array:
        return self._settle_home(settled, *self._route(ring))

    def _part_delta(self, ring) -> Array:
        """The ring's buffered updates as a transient dense global delta
        (unwritten slots hold key ``-1`` — scatter's ignore convention)."""
        rk, rv, _ = ring
        return self._scatter_into(self._identity_table(), rk, rv)

    def _spill_scatter(self, cache, spill, keys: Array, vals: Array):
        """One tick through the resident cache, evictions spilling into
        the bounded buffer (same padding convention as
        :meth:`_blocked_delta`)."""
        cfg = self.config
        ok = (keys >= 0) & (keys < cfg.n_keys)
        ident_val = cfg.merge.identity((cfg.cols,), cfg.dtype)
        safe = jnp.where(ok, keys, 0).astype(jnp.int32)
        vals = jnp.where(ok[:, None], vals, ident_val)
        return blocked.spill_scatter(cache, spill, safe, vals, cfg.merge)

    def _part_drain_blocked(self, cache, spill):
        """Commit-side drain: resident dirty ways + spilled blocks into a
        transient dense global delta."""
        merge = self.config.merge
        cache, delta = blocked.flush(cache, self._identity_table(), merge)
        spill, delta = blocked.spill_drain(spill, delta, merge)
        return cache, spill, delta

    def _make_part_tick(self, full: bool, land: bool):
        overlap = self._overlap

        if self._routed:
            def tick(settled, ring, keys, vals):
                ring = self._ring_append(ring, keys, vals)
                if not full:
                    return settled, ring
                return (self._commit_home(settled, ring),
                        self._ring_reset(ring))
        elif self.config.engine == "kernel" and not land:
            # overlapped: the commit tick launches the dense delta
            def tick(settled, ring, keys, vals):
                ring = self._ring_append(ring, keys, vals)
                if not full:
                    return settled, ring
                delta = self._part_delta(ring)
                ring = self._ring_reset(ring)
                return settled, ring, self._launch(delta)
        elif self.config.engine == "kernel":
            def tick(settled, ring, inflight, keys, vals):
                ring = self._ring_append(ring, keys, vals)
                # land the previous commit's launched aggregate: its top
                # exchange overlaps this tick's scatter in one program
                agg = self._land(inflight)
                settled = self._apply(settled, self._home_rows(agg))
                if not full:
                    return settled, ring
                delta = self._part_delta(ring)
                ring = self._ring_reset(ring)
                return settled, ring, self._launch(delta)
        elif not land:
            def tick(settled, cache, spill, keys, vals):
                cache, spill = self._spill_scatter(cache, spill, keys, vals)
                if not full:
                    return settled, cache, spill
                cache, spill, delta = self._part_drain_blocked(cache, spill)
                if overlap:
                    return settled, cache, spill, self._launch(delta)
                agg = self._settle(delta)
                return (self._apply(settled, self._home_rows(agg)),
                        cache, spill)
        else:
            def tick(settled, cache, spill, inflight, keys, vals):
                cache, spill = self._spill_scatter(cache, spill, keys, vals)
                agg = self._land(inflight)
                settled = self._apply(settled, self._home_rows(agg))
                if not full:
                    return settled, cache, spill
                cache, spill, delta = self._part_drain_blocked(cache, spill)
                return settled, cache, spill, self._launch(delta)

        if land:
            name = "kv_tick_land_launch" if full else "kv_tick_land"
        elif full:
            name = "kv_tick_launch" if overlap else "kv_tick_commit"
        else:
            name = "kv_tick_ring"
        return _named(tick, name)

    def _make_part_flush(self, land: bool):
        def settle_home(settled, delta):
            agg = self._settle(delta)
            return self._apply(settled, self._home_rows(agg))

        def land_home(settled, inflight):
            return self._apply(settled, self._home_rows(self._land(inflight)))

        if self._routed:
            def flush_fn(settled, ring):
                return (self._commit_home(settled, ring),
                        self._ring_reset(ring))
        elif self.config.engine == "kernel" and not land:
            def flush_fn(settled, ring):
                settled = settle_home(settled, self._part_delta(ring))
                return settled, self._ring_reset(ring)
        elif self.config.engine == "kernel":
            def flush_fn(settled, ring, inflight):
                settled = land_home(settled, inflight)
                settled = settle_home(settled, self._part_delta(ring))
                return settled, self._ring_reset(ring)
        elif not land:
            def flush_fn(settled, cache, spill):
                cache, spill, delta = self._part_drain_blocked(cache, spill)
                return settle_home(settled, delta), cache, spill
        else:
            def flush_fn(settled, cache, spill, inflight):
                settled = land_home(settled, inflight)
                cache, spill, delta = self._part_drain_blocked(cache, spill)
                return settle_home(settled, delta), cache, spill

        return _named(flush_fn, "kv_flush_land" if land else "kv_flush")

    def _make_part_read(self, kind: str):
        cfg = self.config
        merge = cfg.merge
        S, R, D, P = self.n_shards, cfg.n_keys, cfg.cols, self._pack

        def base_gather(settled, keys):
            # routed reads: only keys homed here answer; off-home or
            # invalid keys return the merge identity (route with
            # BatchedFrontend, which shards traffic by key % n_shards)
            me = jax.lax.axis_index(self.axis_name)
            ok = _valid(keys, R) & (keys % S == me)
            local = jnp.where(ok, keys // S, 0)
            if P == 1:
                rows = settled[local]
            else:
                rows = jnp.take_along_axis(
                    settled[local // P].reshape(-1, P, D),
                    (local % P)[:, None, None], axis=1)[:, 0]
            ident = merge.identity((D,), cfg.dtype)
            return jnp.where(ok[:, None], rows, ident), ok

        if kind == "plain":
            def read(settled, keys):
                return base_gather(settled, keys)[0]
            return _read_program(read, kind)

        def ring_overlay(ring, keys, ok):
            # the device's own buffered updates for each key, reduced with
            # the merge's combine (a monoid, so lax.reduce applies)
            rk, rv, _ = ring
            match = ((rk[None, :] == keys[:, None])
                     & ok[:, None] & (rk >= 0)[None, :])
            ident = merge.identity((), rv.dtype)
            masked = jnp.where(match[:, :, None], rv[None, :, :], ident)
            return jax.lax.reduce(masked, ident,
                                  lambda a, b: merge.combine(a, b), (1,))

        def cache_overlay(cache, spill, keys, ok):
            # c_read_row semantics over the table-less cache + spill: the
            # resident way's delta(src, upd) plus any spilled mass
            br = cfg.block_rows
            safe = jnp.where(ok, keys, 0)
            block, line = safe // br, safe % br
            c_hits = cache.block_ids[None, :] == block[:, None]
            c_hit = jnp.any(c_hits, axis=-1) & ok
            way = jnp.argmax(c_hits, axis=-1)
            res = merge.delta(cache.src_vals[way, line],
                              cache.upd_vals[way, line])
            ident = merge.identity(res.shape, res.dtype)
            out = jnp.where(c_hit[:, None], res, ident)
            s_hits = spill.block_ids[None, :] == block[:, None]
            s_hit = jnp.any(s_hits, axis=-1) & ok
            slot = jnp.argmax(s_hits, axis=-1)
            return merge.combine(out, jnp.where(s_hit[:, None],
                                                spill.vals[slot, line],
                                                ident))

        def inflight_overlay(base, inflight, keys, ok):
            # launched-but-unlanded mass: includes this device's own
            # writes (plus inner-group peers' — fresher, still monotone)
            safe = jnp.where(ok, keys, 0)
            ident = merge.identity((D,), inflight.dtype)
            return merge.apply(base, jnp.where(ok[:, None], inflight[safe],
                                               ident))

        if kind == "ryw":
            if cfg.engine == "kernel":
                def read(settled, ring, keys):
                    base, ok = base_gather(settled, keys)
                    return merge.apply(base, ring_overlay(ring, keys, ok))
            else:
                def read(settled, cache, spill, keys):
                    base, ok = base_gather(settled, keys)
                    return merge.apply(base,
                                       cache_overlay(cache, spill, keys, ok))
            return _read_program(read, kind)

        if kind != "ryw_inflight":
            raise ValueError(f"unknown partitioned read kind {kind!r}")
        if cfg.engine == "kernel":
            def read(settled, ring, inflight, keys):
                base, ok = base_gather(settled, keys)
                base = inflight_overlay(base, inflight, keys, ok)
                return merge.apply(base, ring_overlay(ring, keys, ok))
        else:
            def read(settled, cache, spill, inflight, keys):
                base, ok = base_gather(settled, keys)
                base = inflight_overlay(base, inflight, keys, ok)
                return merge.apply(base,
                                   cache_overlay(cache, spill, keys, ok))
        return _read_program(read, kind)

    def _make_read(self):
        cfg = self.config
        merge = cfg.merge
        ryw = cfg.consistency == "read_your_writes" and not self.synchronized

        def gather(table, keys):
            ok = (keys >= 0) & (keys < cfg.n_keys)
            safe = jnp.where(ok, keys, 0)
            rows = table[safe]
            ident = merge.identity((cfg.cols,), cfg.dtype)
            return jnp.where(ok[:, None], rows, ident)

        if not ryw:
            def read(settled, keys):
                return gather(settled, keys)
            return _read_program(read, "plain")

        if cfg.engine == "kernel":
            def read(settled, pendings, keys):
                view = settled
                for p in pendings:
                    view = merge.apply(view, p)
                return gather(view, keys)
            return _read_program(read, "ryw")

        def read(settled, pendings, cache, keys):
            view = settled
            for p in pendings:
                view = merge.apply(view, p)
            base = gather(view, keys)
            # c_read_row semantics, vectorized: a resident way's unmerged
            # contribution delta(src, upd) overlays the settled+pending
            # view.  (upd alone would double-count the tick-local src
            # copy the cascade already carries.)
            ok = (keys >= 0) & (keys < cfg.n_keys)
            safe = jnp.where(ok, keys, 0)
            block = safe // cfg.block_rows
            line = safe % cfg.block_rows
            hits = cache.block_ids[None, :] == block[:, None]  # [B, W]
            hit = jnp.any(hits, axis=-1) & ok
            way = jnp.argmax(hits, axis=-1)
            res = merge.delta(cache.src_vals[way, line],
                              cache.upd_vals[way, line])      # [B, D]
            ident = merge.identity(res.shape, res.dtype)
            return merge.apply(base, jnp.where(hit[:, None], res, ident))

        return _read_program(read, "ryw")

    # ------------------------------------------------------------------
    # host-side driver API
    # ------------------------------------------------------------------

    def _run(self, fn, *args, donate=()):
        if donate and self._can_donate:
            return self.spmd(fn, *args, donate=donate)
        return self.spmd(fn, *args)

    def tick(self, keys, vals) -> None:
        """Ingest one fixed-shape batch of updates: ``keys`` [S, B] int32
        (< 0 = padding), ``vals`` [S, B, cols].  Commit policy rides the
        schedule; non-commit ticks of a fully deferred plan run zero
        collectives.

        In a profiler trace the call is the span ``repro.kv.tick``, split
        into ``repro.kv.stage`` (the batch onto the device),
        ``repro.kv.journal`` (with a journal attached) and
        ``repro.kv.dispatch`` (the program's launch)."""
        with span("kv.tick"):
            with span("kv.stage"):
                if not self.synchronized and hasattr(self.schedule,
                                                     "observe"):
                    # adaptive schedule: feed the real (non-padding)
                    # ingest count into the EMA before the boundary
                    # re-solve can fire
                    self.schedule.observe(int((np.asarray(keys) >= 0).sum()))
                keys = jnp.asarray(keys, jnp.int32)
                vals = jnp.asarray(vals, self.config.dtype)
            if self._journal is not None and not self._replaying:
                # Write-ahead: the batch is on disk before any device work,
                # so a crash at ANY later point in this tick is recoverable
                # — tick() returning is the acknowledgement point.
                with span("kv.journal"):
                    self._journal.append(keys, vals)
            with span("kv.dispatch"):
                self._dispatch(keys, vals)

    def _dispatch(self, keys: Array, vals: Array) -> None:
        if self.synchronized:
            self.settled = self._run(self._tick_fns["sync"], self.settled,
                                     keys, vals, donate=(0,))
            self._t += 1
            return
        if self.partitioned:
            return self._tick_partitioned(keys, vals)
        self._t += 1
        due = self.schedule.due_count(self._t)
        fn = self._tick_fns[due]
        if self.config.engine == "kernel":
            self.settled, self.pendings = self._run(
                fn, self.settled, self.pendings, keys, vals, donate=(0, 1))
        else:
            self.settled, self.pendings, self.cache = self._run(
                fn, self.settled, self.pendings, self.cache, keys, vals,
                donate=(0, 1, 2))

    def _ensure_ring(self, shape) -> None:
        S, B = shape
        if self.ring is None:
            cfg = self.config
            C = self.schedule.max_period * B
            self.ring = (jnp.full((S, C), -1, jnp.int32),
                         cfg.merge.identity((S, C, cfg.cols), cfg.dtype),
                         jnp.zeros((S,), jnp.int32))
            self._ring_batch = B
        elif B != self._ring_batch:
            raise ValueError(
                f"partitioned store compiles one fixed tick shape: the "
                f"pending ring was sized for batch {self._ring_batch}, "
                f"got {B}")

    def _check_spill_overflow(self) -> None:
        n = int(np.asarray(self.spill.n_overflow).sum())
        if n:
            raise RuntimeError(
                f"spill buffer overflowed {n} eviction(s) — pending mass "
                f"was dropped; raise KVConfig.spill_blocks (currently "
                f"{self.config.spill_blocks}) above the distinct blocks a "
                f"commit cycle can evict")

    def _tick_partitioned(self, keys: Array, vals: Array) -> None:
        kernel = self.config.engine == "kernel"
        if kernel:
            self._ensure_ring(keys.shape)
        self._t += 1
        due = self.schedule.due_count(self._t)
        if due not in (0, self.n_deferred):  # guarded at init (uniform)
            raise RuntimeError(f"partitioned commit must be all-or-nothing, "
                               f"got due={due}")
        full = due == self.n_deferred
        land = self._land_pending
        fn = self._tick_fns[("p", full, land)]
        if kernel:
            extra = (self.inflight,) if land else ()
            out = self._run(fn, self.settled, self.ring, *extra, keys, vals,
                            donate=tuple(range(2 + len(extra))))
            if full and self._overlap:
                self.settled, self.ring, self.inflight = out
                self._land_pending = True
            else:
                self.settled, self.ring = out
                if land:
                    self.inflight = None
                    self._land_pending = False
        else:
            extra = (self.inflight,) if land else ()
            out = self._run(fn, self.settled, self.cache, self.spill,
                            *extra, keys, vals,
                            donate=tuple(range(3 + len(extra))))
            if full and self._overlap:
                self.settled, self.cache, self.spill, self.inflight = out
                self._land_pending = True
            else:
                self.settled, self.cache, self.spill = out
                if land:
                    self.inflight = None
                    self._land_pending = False
            if full:
                self._check_spill_overflow()

    def read(self, keys) -> Array:
        """Serve one fixed-shape batch of gets: ``keys`` [S, B] -> [S, B,
        cols].  Zero collectives either way: ``eventual`` reads the last
        settled table; ``read_your_writes`` overlays the device's own
        unmerged pendings (+ resident cache delta, blocked engine).
        The span ``repro.kv.read`` in a profiler trace."""
        with span("kv.read"):
            keys = jnp.asarray(keys, jnp.int32)
            if self.partitioned:
                return self._read_partitioned(keys)
            if self.synchronized or self.config.consistency == "eventual":
                return self.spmd(self._read_fn, self.settled, keys)
            if self.config.engine == "kernel":
                return self.spmd(self._read_fn, self.settled, self.pendings,
                                 keys)
            return self.spmd(self._read_fn, self.settled, self.pendings,
                             self.cache, keys)

    def _read_partitioned(self, keys: Array) -> Array:
        kernel = self.config.engine == "kernel"
        ryw = self.config.consistency == "read_your_writes"
        if not ryw or (kernel and self.ring is None):
            # before the first tick there is nothing pending anywhere —
            # the settled-only read IS read-your-writes
            return self.spmd(self._read_fns["plain"], self.settled, keys)
        pending = (self.ring,) if kernel else (self.cache, self.spill)
        if self._land_pending:
            return self.spmd(self._read_fns["ryw_inflight"], self.settled,
                             *pending, self.inflight, keys)
        return self.spmd(self._read_fns["ryw"], self.settled, *pending,
                         keys)

    def flush(self) -> None:
        """Commit everything outstanding (pendings + resident cache).

        After a flush the settled table equals the fully-synchronized
        reference over the same update stream — bitwise, for integer ADD.
        Resets the schedule phase (a flush ends the current cycle). The
        span ``repro.kv.flush`` in a profiler trace."""
        if self.synchronized:
            return
        with span("kv.flush"):
            if self.partitioned:
                self._flush_partitioned()
            elif self.config.engine == "kernel":
                self.settled, self.pendings = self._run(
                    self._flush_fn, self.settled, self.pendings,
                    donate=(0, 1))
            else:
                self.settled, self.pendings, self.cache = self._run(
                    self._flush_fn, self.settled, self.pendings, self.cache,
                    donate=(0, 1, 2))
        self._t = 0
        if hasattr(self.schedule, "reset"):
            self.schedule.reset()

    def _flush_partitioned(self) -> None:
        kernel = self.config.engine == "kernel"
        land = self._land_pending
        if kernel and self.ring is None:
            return  # nothing ever ingested (land implies a prior tick)
        fn = self._flush_land_fn if land else self._flush_fn
        extra = (self.inflight,) if land else ()
        if kernel:
            self.settled, self.ring = self._run(
                fn, self.settled, self.ring, *extra,
                donate=tuple(range(2 + len(extra))))
        else:
            self.settled, self.cache, self.spill = self._run(
                fn, self.settled, self.cache, self.spill, *extra,
                donate=tuple(range(3 + len(extra))))
            self._check_spill_overflow()
        self.inflight = None
        self._land_pending = False

    def table(self) -> np.ndarray:
        """The settled table.  Replicated mode returns any shard's copy;
        partitioned mode reassembles the home-sharded rows
        (``out[s::S] = shard s``)."""
        if not self.partitioned:
            return np.asarray(self.settled[0])
        parts = self.home_tables()
        out = np.empty((self.config.n_keys, self.config.cols), parts.dtype)
        for s in range(self.n_shards):
            out[s::self.n_shards] = parts[s]
        return out

    def home_tables(self) -> np.ndarray:
        """A partitioned store's settled rows, ``(S, n_keys // S, cols)``:
        shard ``s``'s local row ``l`` holds global key ``l * S + s``."""
        cfg = self.config
        return np.asarray(self.settled).reshape(
            self.n_shards, cfg.n_keys // self.n_shards, cfg.cols)

    # ------------------------------------------------------------------
    # durability: write-ahead journal + flush-consistent snapshots
    # ------------------------------------------------------------------

    def attach_journal(self, root: str, sync: bool = False) -> None:
        """Journal every subsequent acknowledged tick under ``root`` (write-
        ahead, see ``serve.journal``). Call before serving traffic; the
        snapshot/recover pair below then guarantees zero acknowledged mass
        is lost to a crash."""
        from repro.serve.journal import UpdateJournal
        self._dur_root = root
        self._journal = UpdateJournal(root, sync=sync)

    def durable_manifest(self) -> dict:
        """Identity of the durable state (snapshot extras). ``recover``
        requires the table geometry + merge to match; shard count, engine,
        and layout may differ — that is the elastic half (the saved table
        is global, the journal records re-chunk to any shard count)."""
        from repro.checkpoint.defer_state import (plan_fingerprint,
                                                  schedule_fingerprint)
        cfg = self.config
        return {
            "n_keys": int(cfg.n_keys), "cols": int(cfg.cols),
            "dtype": str(jnp.dtype(cfg.dtype)), "merge": cfg.merge.name,
            "engine": cfg.engine, "n_shards": int(self.n_shards),
            "partitioned": bool(self.partitioned),
            "plan": plan_fingerprint(self.plan, self.n_shards,
                                     merge_name=cfg.merge.name),
            "schedule": (schedule_fingerprint(self.schedule)
                         if self.schedule is not None else None),
        }

    def _check_durable_compat(self, saved: dict) -> None:
        mine = self.durable_manifest()
        for k in ("n_keys", "cols", "dtype", "merge"):
            if saved.get(k) != mine[k]:
                raise ValueError(
                    f"recover: snapshot {k}={saved.get(k)!r} does not match "
                    f"this store's {k}={mine[k]!r} — the settled table is "
                    f"not interpretable under a different {k}")

    def _install_table(self, table: np.ndarray) -> None:
        """Land a global ``(n_keys, cols)`` settled table into this store's
        layout (the inverse of :meth:`table`)."""
        cfg, S = self.config, self.n_shards
        if table.shape != (cfg.n_keys, cfg.cols):
            raise ValueError(f"snapshot table shape {table.shape} != "
                             f"({cfg.n_keys}, {cfg.cols})")
        if self.partitioned:
            parts = np.stack([table[s::S] for s in range(S)])
            self.settled = jnp.asarray(parts.reshape(self.settled.shape),
                                       cfg.dtype)
        else:
            self.settled = jnp.broadcast_to(
                jnp.asarray(table, cfg.dtype), (S,) + table.shape)

    def snapshot(self) -> str:
        """Persist a flush-consistent snapshot and truncate the journal.

        Flushes (all volatile mass — pendings, ring, cache/spill, an
        in-flight launch — settles into the table), saves the *global*
        table via the two-phase-commit checkpoint writer, rotates the
        journal so replay after this snapshot starts at a fresh segment,
        and GCs the segments the snapshot made redundant. Crash-safe at
        every point: until the snapshot commits, the old snapshot + full
        journal still reconstruct everything."""
        import os as _os
        from repro import checkpoint as _ckpt
        if self._journal is None:
            raise ValueError("snapshot() needs attach_journal(root) first — "
                             "without the journal, ticks after the snapshot "
                             "would be unrecoverable")
        self.flush()
        seq = self._journal.segment  # ticks so far live in segments < seq+1
        snaps = _os.path.join(self._dur_root, "snaps")
        next_seg = self._journal.rotate()
        path = _ckpt.save(snaps, seq, {"settled_global": self.table()},
                          extras={"kv": self.durable_manifest(),
                                  "segment": next_seg,
                                  "ticks": int(self._t)})
        self._journal.gc(next_seg)
        return path

    def recover(self, root: str, batch: Optional[int] = None,
                sync: bool = False) -> dict:
        """Rebuild a crashed store's state from ``root`` and re-attach.

        Loads the latest committed snapshot (if any) into this store's
        layout, then replays every intact journaled tick since through the
        normal ``tick`` path. Call on a freshly constructed store; the
        table geometry + merge must match the snapshot's, but ``n_shards``,
        ``engine``, and layout may all differ — journal records are
        re-chunked to this store's shard count (``batch`` overrides the
        replay tick width; the partitioned kernel engine compiles one
        fixed shape, so re-chunked replay always uses a uniform batch).
        After recovery the store's *flushed* table is bitwise-equal to the
        crashed store's acknowledged history, and the journal is active
        again for continued serving."""
        import os as _os
        from repro import checkpoint as _ckpt
        from repro.serve.journal import UpdateJournal
        if self._t:
            raise ValueError("recover() must run on a fresh store (this "
                             "one has already ticked)")
        start_seg = 0
        report = {"snapshot_step": None, "replayed_ticks": 0}
        snaps = _os.path.join(root, "snaps")
        step = (_ckpt.latest_step(snaps) if _os.path.isdir(snaps) else None)
        if step is not None:
            raw, manifest = _ckpt.load_raw(snaps, step=step)
            extras = manifest.get("extras", {})
            self._check_durable_compat(extras.get("kv", {}))
            self._install_table(raw["settled_global"])
            start_seg = int(extras.get("segment", 0))
            report["snapshot_step"] = step
        records = list(UpdateJournal.replay(root, start_segment=start_seg))
        self._replaying = True
        try:
            for keys, vals in _rechunk_records(records, self.n_shards,
                                               batch):
                self.tick(keys, vals)
                report["replayed_ticks"] += 1
        finally:
            self._replaying = False
        self.attach_journal(root, sync=sync)
        return report

    def resident_state_bytes(self) -> int:
        """Per-device bytes of long-lived store state: the settled shard
        plus the pending machinery (dense pendings, ring, cache, spill, an
        in-flight launched aggregate).  Excludes the transient dense delta
        a commit tick materializes and frees within the tick."""
        leaves = [self.settled, *self.pendings]
        for extra in (self.cache, self.spill, self.ring, self.inflight):
            if extra is not None:
                leaves.extend(jax.tree.leaves(extra))
        return sum(x.nbytes for x in leaves) // self.n_shards

    def counters(self) -> dict:
        out = {"ticks": self._t, "engine": self.config.engine,
               "consistency": self.config.consistency,
               "synchronized": self.synchronized,
               "partitioned": self.partitioned}
        if not self.synchronized:
            out["schedule"] = self.schedule.as_dict()
        if self.partitioned:
            out["resident_state_bytes"] = self.resident_state_bytes()
            if self._overlap:
                out["overlap"] = True
                out["land_pending"] = self._land_pending
        if self.spill is not None:
            out["spills"] = int(np.asarray(self.spill.n_spills).sum())
            out["spill_overflow"] = int(
                np.asarray(self.spill.n_overflow).sum())
        if self.cache is not None:
            for k, leaf in (("evict_merges", self.cache.n_evict_merges),
                            ("silent_evicts", self.cache.n_silent_evicts),
                            ("flush_merges", self.cache.n_flush_merges)):
                out[k] = int(np.asarray(leaf).sum())
            out["total_merges"] = out["evict_merges"] + out["flush_merges"]
        return out

    # ------------------------------------------------------------------
    # introspection for benchmarks (HLO wire-vector walks)
    # ------------------------------------------------------------------

    @property
    def supported_dues(self) -> tuple:
        """The due counts :meth:`raw_tick_fn` has programs for: one sync
        program, all-or-nothing for a partitioned store, every prefix
        otherwise."""
        if self.synchronized:
            return ("sync",)
        if self.partitioned:
            return (0, self.n_deferred)
        return tuple(range(self.n_deferred + 1))

    def _check_land(self, land: bool) -> None:
        if land and not (self.partitioned and self._overlap):
            raise ValueError("land=True is the overlapped partitioned "
                             "store's landing tick — needs "
                             "partitioned=True and schedule.overlap")

    def raw_tick_fn(self, due: Optional[int] = None,
                    land: bool = False) -> Callable:
        """The per-shard tick program, for lowering under ``shard_map``
        (``hlo_cost`` wire-vector walks).  ``due=None`` on a synchronized
        store returns the sync tick; on a partitioned store, the full
        commit.  ``land=True`` selects the overlapped store's landing
        variant (the tick that settles the in-flight aggregate)."""
        self._check_land(land)
        if self.synchronized:
            return self._tick_fns["sync"]
        if self.partitioned:
            if due is None:
                due = self.n_deferred
            if due not in self.supported_dues:
                raise ValueError(f"partitioned store commits all-or-"
                                 f"nothing: due must be one of "
                                 f"{self.supported_dues}, got {due}")
            return self._tick_fns[("p", due == self.n_deferred, land)]
        if due is None:
            raise ValueError("deferred store: pass due (0..n_deferred)")
        return self._tick_fns[due]

    def raw_flush_fn(self) -> Callable:
        """The per-shard flush program (full commit of the cascade)."""
        if self.synchronized:
            raise ValueError("synchronized store has nothing to flush")
        return self._flush_fn

    def tick_arg_specs(self, batch: int, land: bool = False) -> tuple:
        """Per-shard abstract args of :meth:`raw_tick_fn` for a ``batch``-
        update tick — what the static verifier traces/lowers the tick
        against (``jax.ShapeDtypeStruct`` leaves, no device state)."""
        self._check_land(land)
        cfg = self.config
        keys = jax.ShapeDtypeStruct((batch,), jnp.int32)
        vals = jax.ShapeDtypeStruct((batch, cfg.cols), self.settled.dtype)
        if self.partitioned:
            settled = jax.ShapeDtypeStruct(self.settled.shape[1:],
                                           self.settled.dtype)
            inflight = ((jax.ShapeDtypeStruct((cfg.n_keys, cfg.cols),
                                              self.settled.dtype),)
                        if land else ())
            if cfg.engine == "kernel":
                C = self.schedule.max_period * batch
                ring = (jax.ShapeDtypeStruct((C,), jnp.int32),
                        jax.ShapeDtypeStruct((C, cfg.cols),
                                             self.settled.dtype),
                        jax.ShapeDtypeStruct((), jnp.int32))
                return (settled, ring) + inflight + (keys, vals)
            state = tuple(
                jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[1:],
                                                            x.dtype), st)
                for st in (self.cache, self.spill))
            return (settled,) + state + inflight + (keys, vals)
        table = jax.ShapeDtypeStruct((cfg.n_keys, cfg.cols),
                                     self.settled.dtype)
        if self.synchronized:
            return (table, keys, vals)
        pendings = tuple(table for _ in range(self.n_deferred))
        if cfg.engine == "kernel":
            return (table, pendings, keys, vals)
        cache = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), self.cache)
        return (table, pendings, cache, keys, vals)

    @property
    def donate_argnums(self) -> tuple:
        """The state arg positions a plain (non-landing) :meth:`tick`
        donates (in-place update buffers the compiled module must alias,
        not copy).  Landing ticks donate one extra position (the in-flight
        aggregate, right after these)."""
        if self.synchronized:
            return (0,)
        if self.partitioned:
            return (0, 1) if self.config.engine == "kernel" else (0, 1, 2)
        return (0, 1) if self.config.engine == "kernel" else (0, 1, 2)

    def scheduled_manifest(self, due: Optional[int] = None,
                           land: bool = False) -> list:
        """The collective schedule a tick is licensed to emit
        (``ccache.program_manifest``); ``due=None`` = full commit.  For an
        overlapped partitioned store the halves split per
        ``ccache.overlap_program_manifest``: a full-commit tick emits the
        launch half, the landing tick the withheld top exchange (a
        landing tick that is itself a full commit emits both, land
        first)."""
        self._check_land(land)
        if self.synchronized:
            return ccache.collective_manifest(self.plan, self.n_shards,
                                              merge_fn=self.config.merge)
        if due is None:
            due = self.n_deferred
        if self._routed:
            return [self._route_manifest()] if due == self.n_deferred else []
        if self.partitioned and self._overlap:
            out = []
            if land:
                out += ccache.overlap_program_manifest(
                    self.plan, self.n_shards, "land",
                    merge_fn=self.config.merge)
            if due == self.n_deferred:
                out += ccache.overlap_program_manifest(
                    self.plan, self.n_shards, "launch",
                    merge_fn=self.config.merge)
            return out
        return ccache.program_manifest(self.plan, self.n_shards, due,
                                       merge_fn=self.config.merge)

    def _route_manifest(self) -> ccache.StageManifest:
        """The routed commit's one exchange: an all-gather over the whole
        axis, on the plan's top level."""
        top = max(i for i, lv in enumerate(self.plan.levels) if lv.size > 1)
        return ccache.StageManifest(
            index=top, name="route", defer=True, stride=1,
            fanout=self.n_shards, kind="gather", fused_ops=0,
            exchange_rounds=0, intra_rounds=0)
