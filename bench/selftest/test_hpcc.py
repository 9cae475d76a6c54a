"""Whole runs of the HPCC RandomAccess cell on the CPU at a small size:
sound runs come out correct against the XOR reference, and each fault the
cell can have, planted under the timed path, comes out not correct."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve.kv import ShardedKV

from bench import control_xor, harness, reference_xor
from bench.selftest.tiny import run_tiny, tiny_cell

CELL = "hpcc4.xor-uniform"
# four ticks a pass, so a short window makes many passes
CUT = {"n_keys": 1 << 12, "slots": 64, "block_ticks": 4}


def wrong(out, name):
    return out["checks"][name]["value"] > out["checks"][name]["limit"]


def passes(out):
    ticks = (out["attempted"] - 1024) // (4 * CUT["slots"])
    return ticks // CUT["block_ticks"]


@pytest.mark.parametrize("trace", [False, True])
def test_a_sound_run_is_correct(trace):
    out = run_tiny(CELL, seed=2**31 + 5, trace=trace, **CUT)
    assert out["correct"] is True and out["failed"] == 0
    assert passes(out) >= 3
    names = set(out["metrics"])
    if trace:
        assert names <= {m["name"] for m in tiny_cell(CELL).per_layer}
        assert "busy_s" in out["device"] and "breakdown" in out
    else:
        assert names == {"updates_per_s", "setup_s"}
    assert out["compiles_in_window"] == 0


def test_the_configured_merge_builds_the_store():
    drv = harness.load_module("drivers", "hpcc_direct")
    cfg = tiny_cell(CELL, **CUT).config
    store = drv.make_store(cfg, jax.devices())
    assert store.config.merge.name == "xor" and store.partitioned
    assert not store._overlap and store.schedule.intervals[0] == 8


def test_add_in_place_of_xor_fails():
    cell = tiny_cell(CELL, **CUT)
    cell = dataclasses.replace(cell, config=dict(cell.config, merge="add"))
    out = harness.run_cell(cell, 7, 0.5, False, jax.devices(),
                           {"hbm_bytes_per_s": 819e9}, 0.0)
    assert out["correct"] is False and wrong(out, "table_rows_wrong")


def test_the_exchange_between_chips_left_out_fails(monkeypatch):
    def local(self, ring):
        return ring[0], ring[1]
    monkeypatch.setattr(ShardedKV, "_route", local)
    out = run_tiny(CELL, **CUT)
    assert out["correct"] is False and wrong(out, "table_rows_wrong")


def test_one_chips_ring_dropped_fails(monkeypatch):
    real = ShardedKV._route

    def route(self, ring):
        rk, rv, cur = ring
        first = jax.lax.axis_index(self.axis_name) == 0
        return real(self, (jnp.where(first, -1, rk), rv, cur))
    monkeypatch.setattr(ShardedKV, "_route", route)
    out = run_tiny(CELL, **CUT)
    assert out["correct"] is False and wrong(out, "table_rows_wrong")


def test_the_table_is_held_lane_dense():
    drv = harness.load_module("drivers", "hpcc_direct")
    store = drv.make_store(tiny_cell(CELL, **CUT).config, jax.devices())
    assert store.settled.shape == (4, CUT["n_keys"] // 4 // 64, 128)


def test_a_pass_dropped_fails(monkeypatch):
    real, sent = ShardedKV.tick, []

    def tick(self, keys, vals):
        if (np.asarray(keys) >= 0).any():
            sent.append(1)
            if len(sent) // CUT["block_ticks"] == 1:
                return
        real(self, keys, vals)
    monkeypatch.setattr(ShardedKV, "tick", tick)
    out = run_tiny(CELL, **CUT)
    assert len(sent) >= 2 * CUT["block_ticks"]
    assert out["correct"] is False and wrong(out, "table_rows_wrong")


def test_the_same_word_for_every_pass_fails(monkeypatch):
    drv = harness.load_module("drivers", "hpcc_direct")
    real = drv.System.run_window

    def run_window(self, inputs, *args, **kwargs):
        same = np.broadcast_to(inputs.words[:1], inputs.words.shape)
        return real(self, dataclasses.replace(inputs, words=same), *args,
                    **kwargs)
    monkeypatch.setattr(drv.System, "run_window", run_window)
    out = run_tiny(CELL, **CUT)
    assert passes(out) >= 2
    assert out["correct"] is False and wrong(out, "table_rows_wrong")


def test_the_control_is_not_correct():
    """The reference over 32-bit words, judged by the cell's own check."""
    got = control_xor.control_readings(tiny_cell(CELL, **CUT), seed=2**31 + 9)
    assert got["correct"] is False
    assert got["checks"]["table_rows_wrong"] > 0
    assert got["failed"] > 0


def test_the_breakdown_reads_the_commits_scopes():
    from bench.chip import scopes
    for scope, op in (("route", "all_gather"), ("pack", "select_n"),
                      ("scatter", "jit(cscatter)/pallas_call")):
        path = f"jit(kv_tick_commit)/shard_map/{scope}/{op}"
        assert scopes.scope_of("kv_tick_commit", path) == scope
        assert scopes.scope_of("kv_tick_ring", path) == "-"


@pytest.mark.parametrize("seed", [3, 2**31 + 1])
def test_the_sparse_reference_is_the_xor_of_every_update(seed):
    rng = np.random.default_rng(seed)
    parts = [(rng.integers(-1, 64, (5, 7)),
              rng.integers(-2**31, 2**31, (5, 7, 2)).astype(np.int32))
             for _ in range(3)]
    parts.append(parts[0])                       # a part sent twice cancels
    dense = np.zeros((64, 2), np.int32)
    for k, v in parts:
        ok = k.reshape(-1) >= 0
        np.bitwise_xor.at(dense, k.reshape(-1)[ok], v.reshape(-1, 2)[ok])
    keys, vals = reference_xor.expected_rows(2, parts)
    assert np.array_equal(keys, np.flatnonzero(dense.any(axis=1)))
    assert np.array_equal(vals, dense[keys])
    q = np.arange(-1, 66)
    assert np.array_equal(reference_xor.lookup(keys, vals, q),
                          np.concatenate([np.zeros((1, 2), np.int32), dense,
                                          np.zeros((2, 2), np.int32)]))


def test_nonzero_rows_reads_each_shards_home_rows():
    drv = harness.load_module("drivers", "hpcc_direct")
    homes = np.zeros((4, 8, 2), np.int32)
    homes[1, 3] = (5, 0)                          # key 3 * 4 + 1
    homes[0, 0] = (0, -1)                         # key 0
    keys, vals = drv.nonzero_rows(homes)
    assert keys.tolist() == [0, 13]
    assert vals.tolist() == [[0, -1], [5, 0]]
