"""The sharded commutative KV serving tier: store semantics, consistency
knob, frontend ordering — and the forced-8-device GUPS configuration.

Fast tests drive :class:`repro.serve.ShardedKV` under the vmap executor
(jnp scatter oracle, same per-shard programs as the mesh).  The property
test pins the paper's correctness contract at serving granularity: after
``flush()`` the privatized-deferred store equals the fully-synchronized
reference AND a numpy serialization oracle **bitwise** (integer ADD),
whatever the commit schedule did in between.  The slow test at the bottom
reruns the store on a real forced-8-device ``shard_map`` mesh — the
``benchmarks/kv_gups.py`` configuration.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import ccache
from repro.core.defer_schedule import DeferSchedule
from repro.core.merge_functions import MAX
from repro.serve import BatchedFrontend, KVConfig, ShardedKV, serving_plan

ENV = dict(os.environ, PYTHONPATH=os.pathsep.join(
    [os.path.abspath("src"), os.environ.get("PYTHONPATH", "")]))
ENV.pop("XLA_FLAGS", None)

AXIS = "shards"


def _spmd(fn, *args):
    return jax.vmap(fn, axis_name=AXIS)(*args)


def _stream(seed, ticks, S, B, R, D):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, R, (ticks, S, B)).astype(np.int32)
    keys[:, :, -1] = -1  # every tick carries padding
    vals = rng.integers(1, 9, (ticks, S, B, D)).astype(np.int32)
    return keys, vals


def _oracle(keys, vals, R, D):
    ref = np.zeros((R, D), np.int64)
    for t in range(keys.shape[0]):
        m = keys[t] >= 0
        np.add.at(ref, keys[t][m], vals[t][m])
    return ref


# ---------------------------------------------------------------------------
# the correctness contract: flush() == sync reference == oracle, bitwise
# ---------------------------------------------------------------------------

@given(seed=st.integers(0, 2**31 - 1),
       engine=st.sampled_from(["kernel", "blocked"]),
       commit_every=st.sampled_from([1, 3, 8]))
@settings(max_examples=8, deadline=None)
def test_property_flush_equals_sync_reference_bitwise(seed, engine,
                                                      commit_every):
    """Whatever the commit schedule withheld, ``flush()`` lands the store
    on the fully-synchronized reference's table bitwise (integer ADD is
    exact) — the speedup never buys a different eventual state."""
    S, R, D, B, T = 4, 32, 2, 8, 7  # T deliberately not a cycle multiple
    keys, vals = _stream(seed, T, S, B, R, D)

    cfg = KVConfig(n_keys=R, cols=D, engine=engine)
    priv = ShardedKV(cfg, S, _spmd, commit_every=commit_every)
    sync = ShardedKV(cfg if engine == "kernel"
                     else KVConfig(n_keys=R, cols=D),
                     S, _spmd, plan=serving_plan(S, "none"))
    for t in range(T):
        priv.tick(keys[t], vals[t])
        sync.tick(keys[t], vals[t])
    priv.flush()
    want = _oracle(keys, vals, R, D)
    assert np.array_equal(sync.table().astype(np.int64), want)
    assert np.array_equal(priv.table().astype(np.int64), want)


def test_partially_deferred_plan_settles_eager_levels_per_tick():
    S, R, D, B, T = 8, 64, 2, 16, 6
    keys, vals = _stream(3, T, S, B, R, D)
    kv = ShardedKV(KVConfig(n_keys=R, cols=D), S, _spmd,
                   plan=serving_plan(S, "top"), commit_every=3)
    assert kv.n_deferred == 1 and not kv.synchronized
    for t in range(T):
        kv.tick(keys[t], vals[t])
    kv.flush()
    assert np.array_equal(kv.table().astype(np.int64),
                          _oracle(keys, vals, R, D))


def test_max_merge_and_nontrivial_schedule():
    """Idempotent MAX through the kernel engine, on an explicit nested
    DeferSchedule rather than the fixed default."""
    S, R, D, B, T = 4, 16, 1, 8, 8
    rng = np.random.default_rng(0)
    keys = rng.integers(0, R, (T, S, B)).astype(np.int32)
    vals = rng.integers(-50, 50, (T, S, B, D)).astype(np.int32)

    plan = serving_plan(4)
    names = tuple(s.name for s in
                  [lv for lv in plan.levels if lv.size > 1])
    sched = DeferSchedule(intervals=(2, 4), level_names=names)
    kv = ShardedKV(KVConfig(n_keys=R, cols=D, merge=MAX), S, _spmd,
                   plan=plan, schedule=sched)
    for t in range(T):
        kv.tick(keys[t], vals[t])
    kv.flush()
    want = np.full((R, D), np.iinfo(np.int32).min, np.int64)
    for t in range(T):
        np.maximum.at(want, keys[t].reshape(-1), vals[t].reshape(-1, D))
    assert np.array_equal(kv.table().astype(np.int64), want)


# ---------------------------------------------------------------------------
# the consistency knob
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["kernel", "blocked"])
def test_read_your_writes_sees_own_unmerged_state(engine):
    """Before any commit, an RYW read on the writing shard returns the
    buffered update; an eventual read still returns the settled (empty)
    table; other shards see nothing either way (zero read collectives)."""
    S, R, D = 4, 16, 2
    for consistency in ("eventual", "read_your_writes"):
        kv = ShardedKV(KVConfig(n_keys=R, cols=D, engine=engine,
                                consistency=consistency),
                       S, _spmd, commit_every=8)
        keys = np.full((S, 4), -1, np.int32)
        vals = np.zeros((S, 4, D), np.int32)
        keys[2, 0] = 5
        vals[2, 0] = 7
        kv.tick(keys, vals)

        got = np.asarray(kv.read(np.full((S, 1), 5, np.int32)))
        if consistency == "read_your_writes":
            assert got[2, 0].tolist() == [7, 7]  # own write visible
        else:
            assert got[2, 0].tolist() == [0, 0]  # eventual: not yet
        for s in (0, 1, 3):
            assert got[s, 0].tolist() == [0, 0]  # never cross-shard

        kv.flush()
        got = np.asarray(kv.read(np.full((S, 1), 5, np.int32)))
        assert all(got[s, 0].tolist() == [7, 7] for s in range(S))


def test_read_your_writes_blocked_overlays_resident_cache():
    """The blocked engine's RYW read must include mass still resident in
    the BlockedCache (never evicted, never flushed) — c_read_row
    semantics on top of settled + pendings."""
    S, R, D = 2, 16, 1
    kv = ShardedKV(KVConfig(n_keys=R, cols=D, engine="blocked",
                            ways=4, block_rows=4,
                            consistency="read_your_writes"),
                   S, _spmd, commit_every=8)
    keys = np.asarray([[3, 3], [-1, -1]], np.int32)
    vals = np.ones((S, 2, D), np.int32)
    kv.tick(keys, vals)
    assert kv.counters()["evict_merges"] == 0  # still resident
    got = np.asarray(kv.read(np.asarray([[3], [3]], np.int32)))
    assert got[0, 0, 0] == 2  # both adds visible on the writing shard
    assert got[1, 0, 0] == 0
    # invalid keys read the merge identity
    got = np.asarray(kv.read(np.asarray([[-1], [99]], np.int32)))
    assert got[0, 0, 0] == 0 and got[1, 0, 0] == 0


# ---------------------------------------------------------------------------
# the batched front end
# ---------------------------------------------------------------------------

def _frontend(consistency="read_your_writes", slots=4, S=4, R=64):
    kv = ShardedKV(KVConfig(n_keys=R, cols=1, consistency=consistency),
                   S, _spmd, commit_every=4)
    return BatchedFrontend(kv, slots_per_shard=slots)


def test_frontend_get_never_overtakes_earlier_add():
    """More adds than one tick's slots: a get queued after them must not
    be served until every earlier add to its shard has landed."""
    fe = _frontend(slots=4, S=4)
    key = 5  # shard 1
    for _ in range(10):           # 3 ticks worth of adds at 4 slots
        fe.add(key, 1)
    rid = fe.get(key)
    served = {}
    steps = 0
    while rid not in served:
        served.update(fe.step())
        steps += 1
    assert steps == 3             # 4 + 4 + (2 adds then the get)
    assert int(served[rid][0]) == 10


def test_frontend_interleaved_program_order():
    fe = _frontend(slots=8)
    r0 = fe.get(7)
    fe.add(7, 5)
    r1 = fe.get(7)
    fe.add(7, 1)
    r2 = fe.get(7)
    out = fe.drain()
    assert int(out[r0][0]) == 0
    assert int(out[r1][0]) == 5
    assert int(out[r2][0]) == 6
    assert fe.backlog == 0


def test_frontend_routes_by_key_and_validates():
    fe = _frontend()
    with pytest.raises(KeyError):
        fe.add(64, 1)
    with pytest.raises(KeyError):
        fe.get(-1)
    # all traffic for one key funnels through key % n_shards
    fe.add(6, 2)
    assert len(fe._q[6 % 4]) == 1 and fe.backlog == 1


# ---------------------------------------------------------------------------
# construction validation
# ---------------------------------------------------------------------------

def test_config_and_store_validation():
    with pytest.raises(ValueError, match="consistency"):
        KVConfig(n_keys=8, consistency="strong")
    with pytest.raises(ValueError, match="engine"):
        KVConfig(n_keys=8, engine="gpu")
    with pytest.raises(ValueError, match="multiple"):
        KVConfig(n_keys=9, engine="blocked", block_rows=4)
    with pytest.raises(ValueError, match="n_shards"):
        ShardedKV(KVConfig(n_keys=8), 0, _spmd)
    # sync plan: a commit schedule is meaningless
    with pytest.raises(ValueError, match="deferred"):
        ShardedKV(KVConfig(n_keys=8), 4, _spmd,
                  plan=serving_plan(4, "none"), commit_every=4)
    # blocked engine cannot ride a partially eager plan
    with pytest.raises(ValueError, match="fully deferred"):
        ShardedKV(KVConfig(n_keys=8, engine="blocked", block_rows=8),
                  8, _spmd, plan=serving_plan(8, "top"))
    # schedule levels must match the plan's deferred stages
    with pytest.raises(ValueError, match="schedule"):
        ShardedKV(KVConfig(n_keys=8), 4, _spmd,
                  schedule=DeferSchedule(intervals=(2,),
                                         level_names=("pod",)))
    with pytest.raises(ValueError, match="not both"):
        ShardedKV(KVConfig(n_keys=8), 4, _spmd,
                  schedule=DeferSchedule.fixed(2, ("chip", "pod")),
                  commit_every=2)


def test_one_shard_store_through_frontend_bitwise():
    """One shard is a store: its plan exchanges nothing, so it is
    synchronized, and adds through the frontend land bitwise — int32
    wrap-around included — in both the gets and the table."""
    R, D, B, T = 64, 4, 16, 5
    store = ShardedKV(KVConfig(n_keys=R, cols=D), 1, _spmd)
    assert store.synchronized and store.n_deferred == 0
    fe = BatchedFrontend(store, slots_per_shard=B)
    rng = np.random.default_rng(5)
    keys = rng.integers(0, R, (T, B))
    vals = rng.integers(-2**31, 2**31, (T, B, D)).astype(np.int32)
    want = np.zeros((R, D), np.int64)
    for t in range(T):
        for k, v in zip(keys[t], vals[t]):
            fe.add(int(k), v)
        np.add.at(want, keys[t], vals[t].astype(np.int64))
        fe.step()
    want = want.astype(np.int32)  # wraps like the device's int32 adds
    rids = {fe.get(k): k for k in range(R)}
    got = fe.drain()
    assert all(np.array_equal(got[r], want[k]) for r, k in rids.items())
    store.flush()
    assert np.array_equal(store.table(), want)


def test_serving_plan_defer_knob():
    for defer, n_def in (("all", 3), ("top", 1), ("none", 0)):
        p = serving_plan(8, defer)
        assert sum(lv.defer for lv in p.levels) == n_def
    with pytest.raises(ValueError, match="defer"):
        serving_plan(8, "some")


# ---------------------------------------------------------------------------
# the partitioned settled table (routed reads, spilled pendings)
# ---------------------------------------------------------------------------

def _part_cfg(engine, **kw):
    return KVConfig(n_keys=32, cols=2, engine=engine, partitioned=True,
                    ways=4, block_rows=4, spill_blocks=8, **kw)


@given(seed=st.integers(0, 2**31 - 1),
       engine=st.sampled_from(["kernel", "blocked"]),
       commit_every=st.sampled_from([1, 3, 8]))
@settings(max_examples=8, deadline=None)
def test_property_partitioned_flush_equals_oracle_bitwise(seed, engine,
                                                          commit_every):
    """The partitioned store (home-sharded settled rows, ring/spill
    pendings) lands on the same table as the replicated store and the
    numpy oracle, bitwise — partitioning changes placement, not state."""
    S, R, D, B, T = 4, 32, 2, 8, 7
    keys, vals = _stream(seed, T, S, B, R, D)
    part = ShardedKV(_part_cfg(engine), S, _spmd, commit_every=commit_every)
    repl = ShardedKV(KVConfig(n_keys=R, cols=D, engine=engine), S, _spmd,
                     commit_every=commit_every)
    for t in range(T):
        part.tick(keys[t], vals[t])
        repl.tick(keys[t], vals[t])
    part.flush()
    repl.flush()
    want = _oracle(keys, vals, R, D)
    assert np.array_equal(part.table().astype(np.int64), want)
    assert np.array_equal(repl.table().astype(np.int64), want)


@pytest.mark.parametrize("engine", ["kernel", "blocked"])
def test_partitioned_overlap_commit_bitwise(engine):
    """The launch/land split (top exchange lands one tick late) withholds
    mass only transiently: flush() still equals the oracle bitwise."""
    S, R, D, B, T = 4, 32, 2, 8, 10
    keys, vals = _stream(11, T, S, B, R, D)
    sched = DeferSchedule.fixed(3, ("chip", "pod"), overlap=True)
    kv = ShardedKV(_part_cfg(engine), S, _spmd, schedule=sched)
    for t in range(T):
        kv.tick(keys[t], vals[t])
        if kv._land_pending:
            # the settled table runs (at most) one tick stale during the
            # overlap window; it must never run AHEAD of the oracle
            part_sum = kv.table().astype(np.int64).sum()
            full_sum = _oracle(keys[:t + 1], vals[:t + 1], R, D).sum()
            assert part_sum <= full_sum
    kv.flush()
    assert not kv._land_pending and kv.inflight is None
    assert np.array_equal(kv.table().astype(np.int64),
                          _oracle(keys, vals, R, D))


def test_partitioned_adaptive_schedule_bitwise():
    from repro.core.defer_schedule import AdaptiveDeferSchedule
    S, R, D, B, T = 4, 32, 2, 8, 20
    keys, vals = _stream(5, T, S, B, R, D)
    sched = AdaptiveDeferSchedule(serving_plan(S), [1e3, 4e3],
                                  base_compute_s=1e-6, per_update_s=1e-7,
                                  k_max=8)
    kv = ShardedKV(KVConfig(n_keys=R, cols=D, partitioned=True), S, _spmd,
                   schedule=sched)
    for t in range(T):
        kv.tick(keys[t], vals[t])
    kv.flush()
    assert np.array_equal(kv.table().astype(np.int64),
                          _oracle(keys, vals, R, D))
    assert kv.counters()["schedule"]["adaptive"]["n_resolves"] >= 2


@pytest.mark.parametrize("engine", ["kernel", "blocked"])
def test_partitioned_read_your_writes_routed(engine):
    """With traffic routed by key % S (the frontend's discipline), every
    write to a key lives on its home shard, so a routed RYW read equals
    the full running oracle at every tick — commits pending or not."""
    S, R, D, B, T = 4, 32, 2, 8, 9
    rng = np.random.default_rng(17)
    kv = ShardedKV(_part_cfg(engine, consistency="read_your_writes"),
                   S, _spmd, commit_every=3)
    ref = np.zeros((R, D), np.int64)
    rkeys = np.arange(R, dtype=np.int32).reshape(R // S, S).T  # homed rows
    for t in range(T):
        keys = np.full((S, B), -1, np.int32)
        vals = np.zeros((S, B, D), np.int32)
        for s in range(S):
            for b in range(B - 1):
                k = int(rng.integers(0, R // S)) * S + s
                keys[s, b] = k
                vals[s, b] = rng.integers(1, 9, size=D)
                ref[k] += vals[s, b]
        kv.tick(keys, vals)
        out = np.asarray(kv.read(rkeys)).astype(np.int64)
        got = np.zeros((R, D), np.int64)
        for s in range(S):
            got[rkeys[s]] = out[s]
        assert np.array_equal(got, ref), f"tick {t}"
    # off-home and invalid keys answer the merge identity, not garbage
    off = np.asarray(kv.read(np.roll(rkeys, 1, axis=0)))
    assert (off == 0).all()


def test_partitioned_noncommit_tick_traces_zero_collectives():
    """CC010/CC020 at the source: the partitioned due=0 tick program
    contains no collective equations at all."""
    from repro.analysis.jaxpr import check_noncommit_region
    for engine in ("kernel", "blocked"):
        kv = ShardedKV(_part_cfg(engine), 4, _spmd, commit_every=4)
        diags = check_noncommit_region(kv.raw_tick_fn(0), AXIS, 4,
                                       kv.tick_arg_specs(8),
                                       site=f"part[{engine}] due=0")
        assert not diags, diags
    assert kv.supported_dues == (0, kv.n_deferred)


@pytest.mark.parametrize("cols,packed", [(2, True), (4, True), (3, False)])
def test_routed_commit_matches_reference_in_either_layout(cols, packed):
    """A partitioned commit that does not overlap routes every ring home;
    narrow rows pack lane-dense, ``128 // cols`` to a line, where a shard's
    rows fill whole lines. After each commit and the flush the table and
    the routed gets equal the reference, bit for bit."""
    S, R, B, K, T = 4, 1024, 8, 4, 10
    kv = ShardedKV(KVConfig(n_keys=R, cols=cols, partitioned=True), S,
                   _spmd, commit_every=K)
    P = 128 // cols if packed else 1
    assert kv.settled.shape == (S, R // S // P, cols * P)
    rng = np.random.default_rng(cols)
    keys = rng.integers(0, R, (T, S, B)).astype(np.int32)
    keys[:, :, -1] = -1
    vals = rng.integers(-2**31, 2**31, (T, S, B, cols)).astype(np.int32)
    ref = np.zeros((R, cols), np.int64)
    for t in range(T):
        kv.tick(keys[t], vals[t])
        ok = keys[t].reshape(-1) >= 0
        np.add.at(ref, keys[t].reshape(-1)[ok],
                  vals[t].reshape(-1, cols)[ok])
        if (t + 1) % K == 0:
            assert np.array_equal(kv.table(), ref.astype(np.int32)), t
    kv.flush()
    want = ref.astype(np.int32)
    assert np.array_equal(kv.table(), want)
    rkeys = np.arange(R, dtype=np.int32).reshape(R // S, S).T
    got = np.asarray(kv.read(rkeys))
    assert all(np.array_equal(got[s], want[rkeys[s]]) for s in range(S))


def test_partitioned_resident_footprint_bounded():
    """The point of the tentpole: per-device resident bytes stop scaling
    with n_keys * (1 + n_deferred) and drop >= 4x vs the replicated
    store at the same shapes."""
    S, R, D, B = 4, 1024, 2, 8
    repl = ShardedKV(KVConfig(n_keys=R, cols=D), S, _spmd, commit_every=8)
    part = ShardedKV(KVConfig(n_keys=R, cols=D, partitioned=True), S,
                     _spmd, commit_every=8)
    keys, vals = _stream(0, 1, S, B, R, D)
    repl.tick(keys[0], vals[0])
    part.tick(keys[0], vals[0])  # allocates the ring
    assert repl.resident_state_bytes() >= 4 * part.resident_state_bytes()


def test_partitioned_spill_overflow_raises_loudly():
    """Dropped evictions must never be silent: a spill buffer too small
    for the traffic raises at the commit that detects it."""
    S, B = 4, 8
    cfg = KVConfig(n_keys=64, cols=1, engine="blocked", partitioned=True,
                   ways=2, block_rows=4, spill_blocks=1)
    kv = ShardedKV(cfg, S, _spmd, commit_every=4)
    rng = np.random.default_rng(0)
    with pytest.raises(RuntimeError, match="spill"):
        for t in range(8):  # many distinct blocks -> constant evictions
            keys = rng.permutation(64)[:S * B].reshape(S, B).astype(np.int32)
            kv.tick(keys, np.ones((S, B, 1), np.int32))


def test_partitioned_scheduled_manifests():
    """Non-commit ticks are licensed to emit nothing; a commit that does
    not overlap routes the ring home in one all-gather; the overlapped
    halves partition the full cascade's manifest exactly."""
    kv = ShardedKV(_part_cfg("kernel"), 8, _spmd, commit_every=4)
    assert kv.scheduled_manifest(0) == []
    routed = kv.scheduled_manifest()
    assert [(m.name, m.kind, m.fanout) for m in routed] == [
        ("route", "gather", 8)]
    full = ccache.program_manifest(kv.plan, 8, kv.n_deferred,
                                   merge_fn=kv.config.merge)
    assert [m.name for m in full] == list(kv._deferred_names)

    ov = ShardedKV(_part_cfg("kernel"), 8, _spmd,
                   schedule=DeferSchedule.fixed(
                       4, kv._deferred_names, overlap=True))
    launch = ov.scheduled_manifest(ov.n_deferred)
    land = ov.scheduled_manifest(0, land=True)
    assert [m.name for m in launch + land] == [m.name for m in full]
    assert ov.scheduled_manifest(0) == []
    both = ov.scheduled_manifest(ov.n_deferred, land=True)
    assert len(both) == len(full)
    with pytest.raises(ValueError, match="land"):
        kv.scheduled_manifest(0, land=True)


def test_partitioned_validation():
    plain = KVConfig(n_keys=32, cols=1)
    with pytest.raises(ValueError, match="spill_blocks"):
        KVConfig(n_keys=32, spill_blocks=0)
    # rows must divide over the mesh
    with pytest.raises(ValueError, match="multiple"):
        ShardedKV(KVConfig(n_keys=30, partitioned=True), 4, _spmd)
    # partitioned table only settles at commits: needs deferred plans
    with pytest.raises(ValueError, match="deferred"):
        ShardedKV(KVConfig(n_keys=32, partitioned=True), 4, _spmd,
                  plan=serving_plan(4, "none"))
    with pytest.raises(ValueError, match="fully deferred"):
        ShardedKV(KVConfig(n_keys=32, partitioned=True), 8, _spmd,
                  plan=serving_plan(8, "top"))
    # all-or-nothing commits: nested intervals cannot partially settle
    with pytest.raises(ValueError, match="uniform"):
        ShardedKV(KVConfig(n_keys=32, partitioned=True), 4, _spmd,
                  schedule=DeferSchedule(level_names=("chip", "pod"),
                                         intervals=(2, 4)))
    # the overlapped pipeline exists only for the partitioned store
    with pytest.raises(ValueError, match="partitioned"):
        ShardedKV(plain, 4, _spmd,
                  schedule=DeferSchedule.fixed(2, ("chip", "pod"),
                                               overlap=True))
    # one compiled tick shape: the ring is sized at the first batch
    kv = ShardedKV(KVConfig(n_keys=32, partitioned=True), 4, _spmd,
                   commit_every=2)
    kv.tick(np.full((4, 8), -1, np.int32), np.zeros((4, 8, 1), np.int32))
    with pytest.raises(ValueError, match="fixed tick shape"):
        kv.tick(np.full((4, 16), -1, np.int32),
                np.zeros((4, 16, 1), np.int32))


def test_commit_every_zero_raises():
    """Regression: ``commit_every=0`` used to fall through ``or`` into the
    silent default of 8 — it must be rejected loudly instead."""
    for bad in (0, -3):
        with pytest.raises(ValueError, match="commit_every"):
            ShardedKV(KVConfig(n_keys=32), 4, _spmd, commit_every=bad)


# ---------------------------------------------------------------------------
# frontend: bounded drain + random interleavings vs a sequential oracle
# ---------------------------------------------------------------------------

def test_frontend_bounded_drain_raises_on_backlog():
    """Regression: ``drain(max_steps=...)`` used to return silently with
    gets still queued; now it raises DrainBacklog carrying the partial
    results and leftover count."""
    from repro.serve import DrainBacklog
    fe = _frontend(slots=4)
    key = 5
    for _ in range(10):
        fe.add(key, 1)
    rid = fe.get(key)
    with pytest.raises(DrainBacklog) as ei:
        fe.drain(max_steps=1)      # 4 of 11 queued entries served
    assert ei.value.backlog == 7 and ei.value.results == {}
    out = fe.drain()               # unbounded drain finishes the job
    assert int(out[rid][0]) == 10 and fe.backlog == 0


@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_property_frontend_random_trace_vs_sequential_oracle(seed):
    """Random interleaved add/get traffic, deliberately overflowing the
    per-tick slots: every get's answer equals a sequential per-key oracle
    that applies requests in program order (per shard, gets never overtake
    earlier adds)."""
    rng = np.random.default_rng(seed)
    S, R = 4, 64
    fe = _frontend(slots=2, S=S, R=R)  # tiny slots: constant overflow
    expect = {}
    running = np.zeros(R, np.int64)
    for _ in range(rng.integers(20, 60)):
        key = int(rng.integers(0, R))
        if rng.random() < 0.6:
            v = int(rng.integers(1, 9))
            fe.add(key, v)
            running[key] += v
        else:
            expect[fe.get(key)] = running[key]
    out = fe.drain()
    assert fe.backlog == 0
    assert set(out) == set(expect)
    for rid, want in expect.items():
        assert int(out[rid][0]) == want, rid


# ---------------------------------------------------------------------------
# acceptance configuration: real forced-8-device mesh
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_kv_store_on_forced_8_device_mesh():
    """The benchmarks/kv_gups.py configuration, shrunk: the deferred
    store on a real 8-device shard_map mesh (donated state buffers)
    matches the sync store and the numpy oracle bitwise after flush."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import numpy as np
        import jax.numpy as jnp
        from repro.apps.sharded import build_mesh, mesh_spmd
        from repro.serve import KVConfig, ShardedKV, serving_plan

        S, R, D, B, T = 8, 4096, 4, 128, 11
        mesh = build_mesh(S, "shards")
        spmd = mesh_spmd(mesh, "shards")
        cfg = KVConfig(n_keys=R, cols=D, dtype=jnp.int32)
        sync = ShardedKV(cfg, S, spmd, plan=serving_plan(S, "none"))
        priv = ShardedKV(cfg, S, spmd, commit_every=8)
        rng = np.random.default_rng(0)
        keys = rng.integers(0, R, (T, S, B)).astype(np.int32)
        vals = rng.integers(1, 5, (T, S, B, D)).astype(np.int32)
        ref = np.zeros((R, D), np.int64)
        for t in range(T):
            np.add.at(ref, keys[t].reshape(-1), vals[t].reshape(-1, D))
            sync.tick(keys[t], vals[t])
            priv.tick(keys[t], vals[t])
        priv.flush()
        out = {
            "sync_matches_oracle": bool(np.array_equal(
                sync.table().astype(np.int64), ref)),
            "priv_matches_sync": bool(np.array_equal(
                priv.table(), sync.table())),
        }
        print("RESULT " + json.dumps(out))
    """)
    r = subprocess.run([sys.executable, "-c", script], env=ENV,
                       capture_output=True, text=True, timeout=1200)
    assert r.returncode == 0, r.stderr[-2000:]
    line = next(ln for ln in r.stdout.splitlines()
                if ln.startswith("RESULT "))
    out = json.loads(line[len("RESULT "):])
    assert out == {"sync_matches_oracle": True, "priv_matches_sync": True}


# ---------------------------------------------------------------------------
# HPCC RandomAccess: XOR of 64-bit words (two int32 lanes) on 4 host devices
# ---------------------------------------------------------------------------

XOR_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, re
    import numpy as np
    import jax
    from repro.analysis import placement
    from repro.apps.sharded import build_mesh, mesh_spmd
    from repro.core.ccache import deferred_stages_of
    from repro.core.defer_schedule import DeferSchedule
    from repro.core.merge_functions import BITWISE_XOR
    from repro.launch import hlo_cost
    from repro.serve import KVConfig, ShardedKV, serving_plan

    S, R, D, B, K, T = 4, 256, 2, 16, 8, 27
    spmd = mesh_spmd(build_mesh(S))
    plan = serving_plan(S, "all")
    levels = tuple(s.name for s in deferred_stages_of(plan, S,
                                                      merge_fn=BITWISE_XOR))
    part = ShardedKV(KVConfig(n_keys=R, cols=D, merge=BITWISE_XOR,
                              partitioned=True), S, spmd, plan=plan,
                     schedule=DeferSchedule.fixed(K, levels))
    sync = ShardedKV(KVConfig(n_keys=R, cols=D, merge=BITWISE_XOR), S,
                     spmd, plan=serving_plan(S, "none"))
    rng = np.random.default_rng(0)
    keys = rng.integers(0, R, (T, S, B)).astype(np.int32)
    keys[:, :, -1] = -1
    vals = rng.integers(-2**31, 2**31, (T, S, B, D)).astype(np.int32)

    def ref(n):
        want = np.zeros((R, D), np.int32)
        k = keys[:n].reshape(-1)
        ok = k >= 0
        np.bitwise_xor.at(want, k[ok], vals[:n].reshape(-1, D)[ok])
        return want

    out = {"commits": [], "sync": []}
    for t in range(T):
        part.tick(keys[t], vals[t])
        sync.tick(keys[t], vals[t])
        out["sync"].append(bool(np.array_equal(sync.table(), ref(t + 1))))
        if (t + 1) % K == 0:
            out["commits"].append(bool(np.array_equal(part.table(),
                                                      ref(t + 1))))
    out["unflushed_differs"] = not np.array_equal(part.table(), ref(T))
    part.flush()
    out["flushed"] = bool(np.array_equal(part.table(), ref(T)))
    rkeys = np.arange(R, dtype=np.int32).reshape(R // S, S).T
    got = np.asarray(part.read(rkeys))
    out["gets_home"] = all(np.array_equal(got[s], ref(T)[rkeys[s]])
                           for s in range(S))
    out["gets_off_home"] = bool(
        (np.asarray(part.read(np.roll(rkeys, 1, axis=0))) == 0).all())

    sizes = tuple(lv.size for lv in plan.levels)
    names = tuple(lv.name for lv in plan.levels)
    def walk(store, fn, specs):
        args = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            (S,) + s.shape, s.dtype), specs)
        low = store.spmd.lower(fn, *args)
        hlo = low.compiler_ir("hlo").get_hlo_module().to_string()
        text = low.compile().as_text()
        return hlo, hlo_cost.analyze_hlo(text, level_sizes=sizes,
                                         level_names=names)
    out["settled_shape"] = list(part.settled.shape)
    hlo, w = walk(part, part.raw_tick_fn(part.n_deferred),
                  part.tick_arg_specs(B))
    out["commit_module"] = part.raw_tick_fn(part.n_deferred).__name__
    out["commit_route_ops"] = sorted(set(re.findall(
        r"op_name=\\"(?:[^\\"]*/)?route/[^\\"]*?(all_gather)", hlo)))
    out["commit_cc021"] = [d.format() for d in placement.check_commit_walk(
        w, part.scheduled_manifest(), "xor:commit")]
    _, w = walk(part, part.raw_tick_fn(0), part.tick_arg_specs(B))
    out["ring_cc020"] = [d.format() for d in placement.check_noncommit_walk(
        w, "xor:ring")]
    hlo, w = walk(sync, sync.raw_tick_fn(), sync.tick_arg_specs(B))
    out["sync_exchange_ops"] = sorted(set(re.findall(
        r"op_name=\\"[^\\"]*/exchange/[^\\"]*?(ppermute|xor)", hlo)))
    out["sync_cc021"] = [d.format() for d in placement.check_commit_walk(
        w, sync.scheduled_manifest(), "xor:sync")]
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def xor_report():
    r = subprocess.run([sys.executable, "-c", XOR_SCRIPT], env=ENV,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    line = next(ln for ln in r.stdout.splitlines()
                if ln.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def test_xor_partitioned_deferred_store_equals_numpy(xor_report):
    """Partitioned, a commit every 8 ticks, no overlap: after each of three
    commits and after the flush the table is ``np.bitwise_xor.at`` of every
    update, bit for bit; between commits it is not yet."""
    assert xor_report["commits"] == [True, True, True]
    assert xor_report["unflushed_differs"]
    assert xor_report["flushed"]


def test_xor_synchronized_store_equals_numpy_every_tick(xor_report):
    assert len(xor_report["sync"]) == 27 and all(xor_report["sync"])


def test_xor_gets_are_routed_home(xor_report):
    assert xor_report["gets_home"] and xor_report["gets_off_home"]


def test_xor_exchanges_run_to_their_manifests(xor_report):
    """The partitioned commit routes the rings home in an all-gather under
    the scope ``route``; the synchronized tick's software butterfly, its
    ppermutes and XOR combines, runs under ``exchange``. Each program's
    collectives match its scheduled manifest (CC021); the ring tick moves
    nothing (CC020)."""
    assert xor_report["commit_module"] == "kv_tick_commit"
    assert xor_report["commit_route_ops"] == ["all_gather"]
    assert xor_report["sync_exchange_ops"] == ["ppermute", "xor"]
    assert xor_report["commit_cc021"] == []
    assert xor_report["sync_cc021"] == []
    assert xor_report["ring_cc020"] == []


def test_xor_words_are_packed_lane_dense(xor_report):
    """Two-lane words, 64 to a 128-lane line: 256 keys over 4 shards are one
    line a shard."""
    assert xor_report["settled_shape"] == [4, 1, 128]


def test_xor_overlapped_commit_still_raises():
    """XOR is neither scalable nor idempotent: a one-step-stale landing is
    refused when the store is built."""
    from repro.core.merge_functions import BITWISE_XOR
    cfg = KVConfig(n_keys=32, cols=2, merge=BITWISE_XOR, partitioned=True)
    probe = ShardedKV(cfg, 4, _spmd, commit_every=8)
    with pytest.raises(ValueError, match="stale"):
        ShardedKV(cfg, 4, _spmd, schedule=DeferSchedule.fixed(
            8, probe._deferred_names, overlap=True))


def test_kv_serve_cli_serves_xor():
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.kv_serve", "--shards", "4",
         "--merge", "xor", "--cols", "2", "--partitioned", "--dist",
         "uniform", "--keys", "4096", "--ticks", "20", "--batch", "64"],
        env=ENV, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "merge=xor" in r.stdout
    assert "table equals numpy bitwise_xor.at: True" in r.stdout
