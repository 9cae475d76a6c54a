"""Driver: one client per chip, ticking its own batches into the store.

The window drives ``ShardedKV.tick`` itself, as ``launch/kv_serve.py``
does: tick ``j`` hands chip ``s`` the next ``slots_per_shard`` updates of
client ``s``'s stream, keys drawn from the whole key space. So each chip
privatizes updates to rows that other chips home, and a commit's exchange
carries them home. A tick returning is the acknowledgement; the loop ticks
back to back until the window closes, and the runtime's bound on programs
in flight holds the host to the device. After the window the store is
flushed, a sample of keys is read back, each from the shard that homes it,
and the table is copied to the host. ``check`` then holds the table and the
answers to the plain reference over every update ticked.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from bench import kvstore
from bench.generate import Stream, generate


@dataclasses.dataclass
class Inputs:
    stream: Stream
    keys: np.ndarray    # [T, S, B] int32: tick t's keys, chip by chip
    vals: np.ndarray    # [T, S, B, cols] int32


def traffic(config: dict, mix: dict, seed: int) -> Inputs:
    if mix["loop"] != "closed":
        raise ValueError(f"this driver runs a closed loop, not {mix['loop']!r}")
    S, B = config["shards"], config["slots_per_shard"]
    stream = generate(mix, config["n_keys"], config["cols"], S, B, seed,
                      by_key=False)
    T = stream.length // B
    keys = np.stack(stream.keys).reshape(S, T, B).transpose(1, 0, 2)
    vals = np.stack(stream.vals).reshape(S, T, B, -1).transpose(1, 0, 2, 3)
    return Inputs(stream, np.ascontiguousarray(keys),
                  np.ascontiguousarray(vals))


def build(config: dict, devices: list) -> "System":
    import jax
    store = kvstore.make_store(config, devices)
    S, B, D = config["shards"], config["slots_per_shard"], config["cols"]
    pad_keys = np.full((S, B), -1, np.int32)
    pad_vals = np.zeros((S, B, D), np.int32)
    warm = kvstore.warm_ticks(config, store)
    for _ in range(warm):
        store.tick(pad_keys, pad_vals)
    jax.block_until_ready(kvstore.state(store))
    return System(config, store, warm)


def _no_span(name):
    return contextlib.nullcontext()


class System:
    def __init__(self, config, store, warm_ticks: int):
        self.config, self.store = config, store
        self.warm_ticks = warm_ticks
        self.steps = 0

    def run_window(self, inputs, seconds: float, span=None):
        """Ticks back to back for ``seconds``; ``span(name)``, where given,
        opens a named host span in the profiler's trace."""
        import jax
        S, B = self.config["shards"], self.config["slots_per_shard"]
        store, keys, vals = self.store, inputs.keys, inputs.vals
        T = len(keys)
        span = span or _no_span
        tick = store.tick
        steps = 0
        with span("bench.window"):
            t0 = time.perf_counter()
            while True:
                with span("bench.store.tick"):
                    tick(keys[steps % T], vals[steps % T])
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
        store = self.store
        S, B = self.config["shards"], self.config["slots_per_shard"]
        store.flush()
        gk = kvstore.get_keys(self.config, inputs.stream, seed)
        answers = [None] * len(gk)
        # each key read from the shard that homes it, B reads a call
        mine = [np.flatnonzero(gk % S == s) for s in range(S)]
        for c in range(0, max(map(len, mine)), B):
            rkeys = np.full((S, B), -1, np.int32)
            for s in range(S):
                part = mine[s][c:c + B]
                rkeys[s, :len(part)] = gk[part]
            got = np.asarray(store.read(rkeys))
            for s in range(S):
                for b, i in enumerate(mine[s][c:c + B]):
                    answers[i] = got[s, b]
        return {"sent": [self.steps * B] * S, "table": store.table(),
                "get_keys": gk, "answers": answers}


def check(config: dict, inputs, out: dict):
    return kvstore.check(config, inputs.stream, out)
