"""Driver: the counter store served through its batched front end.

The window drives ``BatchedFrontend.add``/``step`` over ``ShardedKV.tick``,
a closed loop: before each ``step()`` the client tops every shard's queue up
to ``queue_ticks`` ticks of adds from that shard's stream. The front end
routes a key to shard ``key % shards``, so each shard's stream holds the
keys it homes. After the window the queued adds are drained into ticks, the
store is flushed, a sample of keys is read back through the front end, and
the table is copied to the host. ``check`` then holds the table and the
answers to the plain reference over every update the client sent.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

from bench import kvstore
from bench.generate import Stream, generate


@dataclasses.dataclass
class Inputs:
    stream: Stream
    mix: dict
    key_lists: list     # the stream's keys as Python ints, for ``add``


def traffic(config: dict, mix: dict, seed: int) -> Inputs:
    if mix["loop"] != "closed":
        raise ValueError(f"this driver runs a closed loop, not {mix['loop']!r}")
    stream = generate(mix, config["n_keys"], config["cols"],
                      config["shards"], config["slots_per_shard"], seed,
                      by_key=True)
    return Inputs(stream, mix, [k.tolist() for k in stream.keys])


def build(config: dict, devices: list) -> "System":
    import jax
    from repro.serve import BatchedFrontend
    store = kvstore.make_store(config, devices)
    fe = BatchedFrontend(store, slots_per_shard=config["slots_per_shard"])
    warm = kvstore.warm_ticks(config, store)
    for _ in range(warm):
        fe.step()
    jax.block_until_ready(kvstore.state(store))
    return System(config, store, fe, warm)


def _no_span(name):
    return contextlib.nullcontext()


class System:
    def __init__(self, config, store, fe, warm_ticks: int):
        self.config, self.store, self.fe = config, store, fe
        self.warm_ticks = warm_ticks
        self.sent = [0] * config["shards"]

    def run_window(self, inputs, seconds: float, span=None):
        """The closed loop for ``seconds``; ``span(name)``, where given,
        opens a named host span in the profiler's trace."""
        import jax
        stream = inputs.stream
        S, B = self.config["shards"], self.config["slots_per_shard"]
        fe, store, L = self.fe, self.store, stream.length
        target = int(inputs.mix["queue_ticks"]) * B
        keys, vals = inputs.key_lists, stream.vals
        depth = [0] * S
        add = fe.add
        if span is None:
            span = _no_span
        else:
            tick = store.tick

            def traced_tick(k, v):
                with span("bench.store.tick"):
                    tick(k, v)
            store.tick = traced_tick

        steps = 0
        with span("bench.window"):
            t0 = time.perf_counter()
            while True:
                with span("bench.client"):
                    for s in range(S):
                        ks, vs, p = keys[s], vals[s], self.sent[s]
                        for i in range(p, p + target - depth[s]):
                            add(ks[i % L], vs[i % L])
                        self.sent[s] += target - depth[s]
                        depth[s] = target
                with span("bench.frontend.step"):
                    fe.step()
                steps += 1
                depth = [max(d - B, 0) for d in depth]
                if time.perf_counter() - t0 >= seconds:
                    break
            jax.block_until_ready(kvstore.state(store))
            window_s = time.perf_counter() - t0
        store.__dict__.pop("tick", None)
        if fe.backlog != sum(depth):
            raise RuntimeError(f"the front end holds {fe.backlog} queued "
                               f"adds, the closed loop counted "
                               f"{sum(depth)}")
        real = sum(self.sent) - fe.backlog
        counters = {"ticks": steps, "real_updates": real,
                    "slots": steps * S * B, "window_s": window_s,
                    "least_bytes": kvstore.least_bytes(
                        self.config, store, stream, steps, self.warm_ticks)}
        return {"updates_per_s": real / window_s}, counters

    def outputs(self, inputs, seed: int) -> dict:
        fe, store = self.fe, self.store
        fe.drain()
        store.flush()
        gk = kvstore.get_keys(self.config, inputs.stream, seed)
        rids = [fe.get(int(k)) for k in gk]
        answers = fe.drain()
        return {"sent": list(self.sent), "table": store.table(),
                "get_keys": gk,
                "answers": [answers.get(r) for r in rids]}


def check(config: dict, inputs, out: dict):
    return kvstore.check(config, inputs.stream, out)
