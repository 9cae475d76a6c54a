"""The one traffic generator: a mix's data file in, per-shard update streams out.

A traffic mix is a JSON file under ``bench/traffic/`` that holds only
parameters. This module reads every mix; a new mix is a new data file.

Keys:
  ``uniform``            every key equally likely over ``[0, n_keys)``: HPCC
                         RandomAccess's access pattern (random table
                         indices, no locality).
  ``scrambled_zipfian``  YCSB's ``ScrambledZipfianGenerator`` (core
                         workloads, requestdistribution=zipfian): a zipfian
                         rank over ``item_count`` items with the precomputed
                         ``zetan``, hashed by 64-bit FNV-1a and taken modulo
                         ``n_keys``, so the hot keys scatter over the table.
Values:
  ``int32_full``         every bit pattern of an int32 equally likely, so
                         sums wrap as int32 arithmetic does.

A driver asks for the stream routed as the front end routes it, by ``key %
shards``: each shard gets the subsequence of one global stream that lands
on it. Or it asks for one client per shard, each with its own run of the
global stream over the whole key space. ``block_ticks``
ticks of ``slots`` updates per shard are generated before the window; a
window that consumes more cycles through the block again.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211


@dataclasses.dataclass
class Stream:
    """Per-shard update streams: ``keys[s]`` int32 [L], ``vals[s]`` int32
    [L, cols]; every shard's stream has the same length L."""

    keys: list
    vals: list

    @property
    def length(self) -> int:
        return len(self.keys[0])


def rng_for(seed: int, purpose: int) -> np.random.Generator:
    """An independent generator per purpose, from any whole-number seed."""
    return np.random.default_rng([seed % (1 << 64), purpose])


def fnv1a64(x: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` over int64 values: FNV-1a over the eight
    little-endian octets, then ``Math.abs`` of the signed result."""
    x = x.astype(np.uint64)
    h = np.full(x.shape, FNV_OFFSET_BASIS_64, np.uint64)
    prime = np.uint64(FNV_PRIME_64)
    with np.errstate(over="ignore"):
        for i in range(8):
            octet = (x >> np.uint64(8 * i)) & np.uint64(0xFF)
            h = (h ^ octet) * prime
    return np.abs(h.view(np.int64))


def zipfian_ranks(rng: np.random.Generator, n: int, item_count: int,
                  theta: float, zetan: float) -> np.ndarray:
    """YCSB's ``ZipfianGenerator.nextLong`` (Gray et al.'s method), ``n``
    draws of a rank in ``[0, item_count)``; rank 0 is the most popular."""
    alpha = 1.0 / (1.0 - theta)
    zeta2 = 1.0 + 0.5 ** theta
    eta = (1.0 - (2.0 / item_count) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = rng.random(n)
    uz = u * zetan
    ranks = (item_count * np.power(eta * u - eta + 1.0, alpha)).astype(
        np.int64)
    ranks = np.where(uz < zeta2, 1, ranks)
    return np.where(uz < 1.0, 0, ranks)


def draw_keys(spec: dict, rng: np.random.Generator, n: int,
              n_keys: int) -> np.ndarray:
    dist = spec["dist"]
    if dist == "uniform":
        return rng.integers(0, n_keys, n, dtype=np.int64)
    if dist == "scrambled_zipfian":
        ranks = zipfian_ranks(rng, n, int(spec["item_count"]),
                              float(spec["theta"]), float(spec["zetan"]))
        return fnv1a64(ranks) % n_keys
    raise ValueError(f"unknown key distribution {dist!r}")


def draw_vals(spec: dict, rng: np.random.Generator, n: int,
              cols: int) -> np.ndarray:
    if spec["dist"] == "int32_full":
        return rng.integers(-2**31, 2**31, (n, cols),
                            dtype=np.int64).astype(np.int32)
    raise ValueError(f"unknown value distribution {spec['dist']!r}")


def generate(traffic: dict, n_keys: int, cols: int, shards: int,
             slots: int, seed: int, by_key: bool = True) -> Stream:
    """``traffic['block_ticks']`` ticks of ``slots`` updates per shard, the
    same for the same seed. ``by_key`` routes the global stream by ``key %
    shards``, as the front end does; otherwise shard ``s`` takes the
    ``s``-th run of the global stream, keys from the whole key space, as a
    client per chip sends them."""
    if traffic.get("ops") != {"update": 1.0}:
        raise ValueError(f"only update-only mixes are generated, got "
                         f"{traffic.get('ops')}")
    per_shard = int(traffic["block_ticks"]) * slots
    krng, vrng = rng_for(seed, 1), rng_for(seed, 2)
    if by_key:
        keys = _routed_keys(traffic["keys"], krng, n_keys, shards, per_shard)
    else:
        keys = list(draw_keys(traffic["keys"], krng, shards * per_shard,
                              n_keys).astype(np.int32).reshape(shards, -1))
    vals = [draw_vals(traffic["values"], vrng, per_shard, cols)
            for _ in range(shards)]
    return Stream(keys=keys, vals=vals)


def _routed_keys(spec: dict, rng: np.random.Generator, n_keys: int,
                 shards: int, per_shard: int) -> list:
    """The global stream's keys split by ``key % shards``, each shard's in
    stream order, ``per_shard`` of each."""
    parts = [[] for _ in range(shards)]
    have = [0] * shards
    chunk = math.ceil(1.25 * per_shard * shards)
    while min(have) < per_shard:
        keys = draw_keys(spec, rng, chunk, n_keys)
        home = keys % shards
        for s in range(shards):
            mine = keys[home == s]
            parts[s].append(mine)
            have[s] += len(mine)
    return [np.concatenate(p)[:per_shard].astype(np.int32) for p in parts]


def consumed(stream: Stream, shard: int, n: int):
    """The first ``n`` updates of a shard's stream as the client sent them
    (cycling through the block): ``(keys, vals, times)`` triples whose
    scatter, each repeated ``times`` times, is what was sent."""
    L = stream.length
    full, rest = divmod(n, L)
    out = []
    if full:
        out.append((stream.keys[shard], stream.vals[shard], full))
    if rest:
        out.append((stream.keys[shard][:rest], stream.vals[shard][:rest], 1))
    return out
