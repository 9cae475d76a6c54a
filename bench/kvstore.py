"""What the counter-store drivers share: the configured store, the warm-up,
the least bytes of a window, the sample of gets, and the check.

A driver (``bench/drivers/<name>.py``) decides how clients reach the store;
everything it hands to ``check`` has the same form: ``sent``, the updates
sent per shard stream; ``table``, the flushed table; ``get_keys`` and
``answers``, the keys read back and what came back (``None`` where nothing
did).
"""

from __future__ import annotations

import numpy as np

from bench import reference
from bench.generate import consumed, rng_for

BYTES_PER_ID = 4
BYTES_PER_COL = 4
GETS = 1024


def make_store(config: dict, devices: list):
    """The configured ``ShardedKV`` over a mesh of ``devices``."""
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.apps.sharded import mesh_spmd
    from repro.core.ccache import deferred_stages_of
    from repro.core.defer_schedule import DeferSchedule
    from repro.core.merge_functions import ADD
    from repro.serve import KVConfig, ShardedKV, serving_plan

    if config["dtype"] != "int32" or config["merge"] != "add":
        raise ValueError("this driver serves int32 ADD counters")
    S = config["shards"]
    spmd = mesh_spmd(Mesh(np.asarray(devices[:S]), ("shards",)))
    kv = KVConfig(n_keys=config["n_keys"], cols=config["cols"],
                  dtype=jnp.int32, merge=ADD,
                  consistency=config["consistency"], engine=config["engine"],
                  partitioned=config["partitioned"])
    plan = serving_plan(S, config["plan_defer"])
    schedule = None
    if config["commit_every"] is not None:
        names = tuple(s.name for s in deferred_stages_of(plan, S,
                                                         merge_fn=ADD))
        schedule = DeferSchedule.fixed(config["commit_every"], names,
                                       overlap=config["overlap"])
    return ShardedKV(kv, S, spmd, plan=plan, schedule=schedule)


def warm_ticks(config: dict, store) -> int:
    """Ticks that run every tick program the window runs, twice each (the
    first call takes fresh state, later calls the previous tick's output):
    the sync tick, or a whole commit cycle with its launch and land ticks."""
    cycle = 1 if store.schedule is None else config["commit_every"] + 1
    return 2 * cycle


def state(store) -> list:
    return [store.settled, store.ring, store.inflight, store.pendings]


def least_bytes(config: dict, store, stream, steps: int,
                warm: int) -> int:
    """The bytes the window's updates need at least: each update's id and
    values once, and one read and one write of every row that a scatter
    call touches, per call. A synchronized store scatters each tick; a
    deferred one scatters a shard's ring once per commit. The window's
    ``steps`` ticks follow ``warm`` warm-up ticks, and tick ``j`` sends
    updates ``j * B`` to ``(j + 1) * B`` of each shard's stream."""
    S, B = config["shards"], config["slots_per_shard"]
    sched = store.schedule
    row = BYTES_PER_COL * config["cols"]
    total = steps * S * B * (BYTES_PER_ID + row)
    groups, cur = [], []
    for j in range(steps):
        cur.append(j)
        t = warm + j + 1   # the store's tick count
        if sched is None or sched.due_count(t) == store.n_deferred:
            groups.append(cur)
            cur = []
    L = stream.length
    for g in groups:
        for s in range(S):
            idx = np.arange(g[0] * B, (g[-1] + 1) * B) % L
            total += 2 * row * len(np.unique(stream.keys[s][idx]))
    return int(total)


def get_keys(config: dict, stream, seed: int) -> np.ndarray:
    """The keys read back after the window, from the seed: half drawn from
    the keys sent, half from the whole table."""
    rng = rng_for(seed, 3)
    sent = np.concatenate(stream.keys)
    return np.concatenate([rng.choice(sent, GETS // 2),
                           rng.integers(0, config["n_keys"],
                                        GETS - GETS // 2)])


def check(config: dict, stream, out: dict):
    """``(checks, attempted, failed)``: every number compared, with its
    limit; requests sent; requests answered wrongly or not at all."""
    parts = [p for s, n in enumerate(out["sent"])
             for p in consumed(stream, s, n)]
    want = reference.expected_table(config["n_keys"], config["cols"], parts)
    bad_rows = np.flatnonzero((out["table"] != want).any(axis=1))
    unanswered = sum(a is None for a in out["answers"])
    wrong = sum(a is not None and not np.array_equal(a, want[k])
                for k, a in zip(out["get_keys"], out["answers"]))
    updates_in_bad_rows = sum(
        int(np.isin(k, bad_rows).sum()) * t for k, _, t in parts)
    checks = {"table_rows_wrong": {"value": int(len(bad_rows)), "limit": 0},
              "gets_wrong": {"value": int(wrong), "limit": 0},
              "gets_unanswered": {"value": int(unanswered), "limit": 0}}
    attempted = sum(out["sent"]) + len(out["answers"])
    return checks, attempted, updates_in_bad_rows + wrong + unanswered
