"""Driver: HPCC RandomAccess, one client per chip XORing 64-bit words into
a table partitioned over the chips.

The window ticks the store as ``kv_direct`` does: tick ``j`` hands chip
``s`` the next ``slots_per_shard`` updates of client ``s``'s stream, keys
drawn from the whole table, back to back; each chip privatizes them in
its ring, and a commit's exchange carries them to the chips that home
their rows. The store's merge is the configuration's, by name. A 64-bit
word is a row of two int32 lanes.

The window cycles through the generated block, and XOR applied twice
cancels: a store that dropped two passes would read as correct. So pass
``p`` through the block XORs a word of its own, drawn from the seed, into
every value it sends, and the reference does the same.

The block is placed on the chips as set-up, each tick's batch laid out
shard by shard as the store's programs take it, so a tick in the window
moves no data from the host: it dispatches the small program ``xor_word``
(the batch's values XOR the pass's word, on the chips) and the store's
tick. The host then keeps ahead of the chips, and the device sets the
rate. After the window
the store is flushed, a sample of keys is read back from their home
shards, as ``kv_direct`` does, and the table is copied to the host shard
by shard and kept as its rows that are not zero; ``check`` holds both to
``bench/reference_xor.py``.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import kvstore, reference_xor
from bench.drivers import kv_direct
from bench.generate import rng_for

# words drawn for passes through the block; a run that needs more fails
MAX_PASSES = 1 << 16


@dataclasses.dataclass
class Inputs(kv_direct.Inputs):
    words: np.ndarray   # [MAX_PASSES, cols] int32: pass p's word
    on_chips: tuple     # (keys, vals): tick t's batch, laid out over the chips


def shard_layout(n_shards: int):
    """How a store's programs take a shard-major argument: split along its
    first axis over the first ``n_shards`` devices, as the store's mesh."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(jax.devices()[:n_shards]), ("shards",))
    return NamedSharding(mesh, P("shards"))


def traffic(config: dict, mix: dict, seed: int) -> Inputs:
    import jax
    base = kv_direct.traffic(config, mix, seed)
    words = rng_for(seed, 4).integers(
        -2**31, 2**31, (MAX_PASSES, config["cols"])).astype(np.int32)
    on_chips = jax.device_put((list(base.keys), list(base.vals)),
                              shard_layout(config["shards"]))
    return Inputs(base.stream, base.keys, base.vals, words, on_chips)


def xor_word(vals, word):
    return vals ^ word


def make_store(config: dict, devices: list):
    """The configured ``ShardedKV`` over a mesh of ``devices``, its merge
    looked up by the configuration's name."""
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.apps.sharded import mesh_spmd
    from repro.core.ccache import deferred_stages_of
    from repro.core.defer_schedule import DeferSchedule
    from repro.core.merge_functions import default_registry
    from repro.serve import KVConfig, ShardedKV, serving_plan

    if config["dtype"] != "int32":
        raise ValueError("this driver serves int32 lanes")
    merge = default_registry()[config["merge"]]
    S = config["shards"]
    spmd = mesh_spmd(Mesh(np.asarray(devices[:S]), ("shards",)))
    kv = KVConfig(n_keys=config["n_keys"], cols=config["cols"],
                  dtype=jnp.int32, merge=merge,
                  consistency=config["consistency"], engine=config["engine"],
                  partitioned=config["partitioned"])
    plan = serving_plan(S, config["plan_defer"])
    names = tuple(s.name for s in deferred_stages_of(plan, S, merge_fn=merge))
    schedule = DeferSchedule.fixed(config["commit_every"], names,
                                   overlap=config["overlap"])
    return ShardedKV(kv, S, spmd, plan=plan, schedule=schedule)


def build(config: dict, devices: list) -> "System":
    """The store, with every program the window runs warmed on batches
    laid out as the window's are."""
    import jax
    store = make_store(config, devices)
    S, B, D = config["shards"], config["slots_per_shard"], config["cols"]
    layout = shard_layout(S)
    pad_keys = jax.device_put(np.full((S, B), -1, np.int32), layout)
    pad_vals = jax.device_put(np.zeros((S, B, D), np.int32), layout)
    word = jax.device_put(np.zeros((S, 1, D), np.int32), layout)
    xor = jax.jit(xor_word)
    warm = kvstore.warm_ticks(config, store)
    for _ in range(warm):
        store.tick(pad_keys, xor(pad_vals, word))
    jax.block_until_ready(kvstore.state(store))
    return System(config, store, warm, layout, xor)


class System(kv_direct.System):
    def __init__(self, config, store, warm_ticks: int, layout, xor):
        super().__init__(config, store, warm_ticks)
        self.layout, self.xor = layout, xor

    def run_window(self, inputs, seconds: float, span=None):
        """Ticks back to back for ``seconds``, pass ``p`` through the block
        with its word XORed into every value; ``span(name)``, where given,
        opens a named host span in the profiler's trace."""
        import jax
        S, B, D = (self.config["shards"], self.config["slots_per_shard"],
                   self.config["cols"])
        store, xor = self.store, self.xor
        keys, vals = inputs.on_chips
        T = len(keys)
        span = span or kv_direct._no_span
        tick = store.tick
        steps = 0
        with span("bench.window"):
            t0 = time.perf_counter()
            while True:
                p, i = divmod(steps, T)
                if i == 0:
                    word = jax.device_put(
                        np.broadcast_to(inputs.words[p], (S, 1, D)),
                        self.layout)
                with span("bench.store.tick"):
                    tick(keys[i], xor(vals[i], word))
                steps += 1
                if time.perf_counter() - t0 >= seconds:
                    break
            jax.block_until_ready(kvstore.state(store))
            window_s = time.perf_counter() - t0
        self.steps = steps
        real = steps * S * B
        counters = {"ticks": steps, "real_updates": real, "slots": real,
                    "window_s": window_s,
                    "least_bytes": kvstore.least_bytes(
                        self.config, store, inputs.stream, steps,
                        self.warm_ticks)}
        return {"updates_per_s": real / window_s}, counters

    def outputs(self, inputs, seed: int) -> dict:
        store, S = self.store, self.config["shards"]
        store.flush()
        gk = kvstore.get_keys(self.config, inputs.stream, seed)
        return {"sent": [self.steps * self.config["slots_per_shard"]] * S,
                "rows": nonzero_rows(store.home_tables()),
                "get_keys": gk,
                "answers": home_gets(store, gk,
                                     self.config["slots_per_shard"])}


def home_gets(store, keys: np.ndarray, B: int) -> list:
    """Each key read from the shard that homes it, ``B`` reads a call;
    ``None`` where nothing came back."""
    S = store.n_shards
    answers = [None] * len(keys)
    mine = [np.flatnonzero(keys % S == s) for s in range(S)]
    for c in range(0, max(map(len, mine)), B):
        rkeys = np.full((S, B), -1, np.int32)
        for s in range(S):
            part = mine[s][c:c + B]
            rkeys[s, :len(part)] = keys[part]
        got = np.asarray(store.read(rkeys))
        for s in range(S):
            for b, i in enumerate(mine[s][c:c + B]):
                answers[i] = got[s, b]
    return answers


def nonzero_rows(homes: np.ndarray):
    """``(keys, vals)``: the rows of a partitioned table, ``homes[s]`` shard
    ``s``'s, that are not zero, keys ascending."""
    S = len(homes)
    found = [(np.flatnonzero(h.any(axis=1)), h) for h in homes]
    keys = np.concatenate([i.astype(np.int64) * S + s
                           for s, (i, _) in enumerate(found)])
    vals = np.concatenate([h[i] for i, h in found])
    order = np.argsort(keys)
    return keys[order], vals[order]


def sent(inputs, steps: int):
    """``(keys, vals)`` of the first ``steps`` ticks, a part per pass
    through the block, each with its pass's word XORed in."""
    T = len(inputs.keys)
    for p in range(-(-steps // T)):
        n = min(T, steps - p * T)
        yield inputs.keys[:n], inputs.vals[:n] ^ inputs.words[p]


def check(config: dict, inputs, out: dict):
    """``(checks, attempted, failed)``, as ``kvstore.check`` gives them,
    against the XOR reference."""
    steps = out["sent"][0] // config["slots_per_shard"]
    parts = list(sent(inputs, steps))
    want = reference_xor.expected_rows(config["cols"], parts)
    got = out["rows"]
    keys = np.union1d(want[0], got[0])
    bad_rows = keys[(reference_xor.lookup(*want, keys)
                     != reference_xor.lookup(*got, keys)).any(axis=1)]
    unanswered = sum(a is None for a in out["answers"])
    wanted = reference_xor.lookup(*want, out["get_keys"])
    wrong = sum(a is not None and not np.array_equal(a, w)
                for a, w in zip(out["answers"], wanted))
    updates_in_bad_rows = sum(int(np.isin(k, bad_rows).sum())
                              for k, _ in parts)
    checks = {"table_rows_wrong": {"value": int(len(bad_rows)), "limit": 0},
              "gets_wrong": {"value": int(wrong), "limit": 0},
              "gets_unanswered": {"value": int(unanswered), "limit": 0}}
    attempted = sum(out["sent"]) + len(out["answers"])
    return checks, attempted, updates_in_bad_rows + wrong + unanswered
