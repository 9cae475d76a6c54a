"""Whole runs of each cell on the CPU at a small size: sound runs come out
correct, and each fault a cell can have, planted under the timed path,
comes out not correct."""

import numpy as np
import pytest

import repro.core.ccache as ccache
from repro.serve.kv import ShardedKV

from bench import control
from bench.selftest.tiny import run_tiny, tiny_cell

CELLS = ["kv1.ingest-uniform", "kv4.ingest-zipf"]


def wrong(out, name):
    return out["checks"][name]["value"] > out["checks"][name]["limit"]


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct(cell, trace):
    out = run_tiny(cell, seed=2**31 + 5, trace=trace)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    names = set(out["metrics"])
    if trace:
        assert names <= {m["name"] for m in tiny_cell(cell).per_layer}
        if cell == "kv1.ingest-uniform":
            assert {"frontend_host_ms_per_tick", "tick_fill"} <= names
            assert out["metrics"]["tick_fill"]["value"] == 100.0
        assert "busy_s" in out["device"] and "breakdown" in out
    else:
        assert names == {"updates_per_s", "setup_s"}
    assert out["compiles_in_window"] == 0


def _patch_tick(monkeypatch, change):
    real = ShardedKV.tick

    def tick(self, keys, vals):
        keys, vals = np.array(keys), np.array(vals)
        if (keys >= 0).any():
            keys, vals = change(keys, vals)
        if keys is not None:
            real(self, keys, vals)
    monkeypatch.setattr(ShardedKV, "tick", tick)


@pytest.mark.parametrize("cell", CELLS)
def test_a_tick_that_leaves_the_state_unchanged_fails(cell, monkeypatch):
    _patch_tick(monkeypatch, lambda k, v: (None, None))
    out = run_tiny(cell)
    assert out["correct"] is False and wrong(out, "table_rows_wrong")


@pytest.mark.parametrize("cell", CELLS)
def test_half_the_batch_left_out_fails(cell, monkeypatch):
    def drop(keys, vals):
        keys[:, keys.shape[1] // 2:] = -1
        return keys, vals
    _patch_tick(monkeypatch, drop)
    out = run_tiny(cell)
    assert out["correct"] is False and wrong(out, "table_rows_wrong")


@pytest.mark.parametrize("cell", CELLS)
def test_one_update_altered_where_it_is_produced_fails(cell, monkeypatch):
    def alter(keys, vals):
        vals[0, np.argmax(keys[0] >= 0), 0] += 1
        return keys, vals
    _patch_tick(monkeypatch, alter)
    out = run_tiny(cell)
    assert out["correct"] is False and wrong(out, "table_rows_wrong")


@pytest.mark.parametrize("cell", CELLS)
def test_an_answer_altered_fails(cell, monkeypatch):
    real = ShardedKV.read

    def read(self, keys):
        out = np.array(real(self, keys))
        out[0, 0, 0] += 1
        return out
    monkeypatch.setattr(ShardedKV, "read", read)
    out = run_tiny(cell)
    assert out["correct"] is False and wrong(out, "gets_wrong")


def test_the_exchange_between_chips_left_out_fails(monkeypatch):
    """Each chip's client sends updates to any key, so a commit's exchange
    carries rows to the chips that home them; with the exchange left out
    each chip settles only its own ring's share of its rows."""
    def local(update, *args, **kwargs):
        return update
    for name in ("launch_inflight", "settle_inflight", "settle_deferred"):
        monkeypatch.setattr(ccache, name, local)
    out = run_tiny("kv4.ingest-zipf")
    assert out["correct"] is False and wrong(out, "table_rows_wrong")


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_not_correct(cell):
    """The reference in float32, judged by the cell's own check."""
    got = control.control_readings(tiny_cell(cell), seed=2**31 + 9)
    assert got["correct"] is False
    assert got["checks"]["table_rows_wrong"] > 0
    assert got["failed"] > 0
