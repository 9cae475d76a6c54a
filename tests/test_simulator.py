"""Cache-simulator invariants (the paper's §4.4 correctness properties)."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from benchmarks.simulator import (ATOMIC, BARRIER, CREAD, CWRITE, MERGE,
                                  READ, WRITE, MachineConfig, run_trace)

MC = MachineConfig(scale=16)  # small hierarchy for fast tests


def _run(core, op, line, extra=None):
    n = len(op)
    return run_trace(MC, {
        "core": np.asarray(core, np.int32),
        "op": np.asarray(op, np.int32),
        "line": np.asarray(line, np.int32),
        "extra": np.zeros(n, np.int32) if extra is None else extra})


def test_cdata_generates_no_coherence():
    """Paper §4.4: COps never generate coherence actions."""
    n = 512
    rng = np.random.default_rng(0)
    r = _run(np.arange(n) % 8,
             rng.choice([CREAD, CWRITE], n),
             rng.integers(0, 64, n))
    assert r["invalidations"] == 0
    assert r["directory"] == 0


def test_coherent_writes_invalidate_sharers():
    # all 8 cores read line 5, then core 0 writes it
    core = list(range(8)) + [0]
    op = [READ] * 8 + [WRITE]
    line = [5] * 9
    r = _run(core, op, line)
    assert r["invalidations"] == 7


def test_merge_flushes_dirty_entries_only():
    # core 0: write 3 CData lines, read 2 more, then merge
    core = [0] * 6
    op = [CWRITE] * 3 + [CREAD] * 2 + [MERGE]
    line = [1, 2, 3, 4, 5, 0]
    r = _run(core, op, line)
    assert r["flush_merges"] == 3         # dirty
    assert r["silent_evicts"] == 2        # clean (dirty-merge skip)


def test_source_buffer_capacity_evicts():
    """Touching more lines than source-buffer entries forces evict-merges
    (the paper's w-1 working-set discipline)."""
    n_lines = MC.sb_entries + 4
    core = [0] * n_lines
    op = [CWRITE] * n_lines
    line = list(range(n_lines))
    r = _run(core, op, line)
    assert r["evict_merges"] == 4


def test_locality_hits_in_source_buffer():
    core = [0] * 64
    op = [CWRITE] * 64
    line = [7] * 64                        # same line over and over
    r = _run(core, op, line)
    assert r["sb_hits"] == 63
    assert r["evict_merges"] == 0


def test_barrier_aligns_cycles():
    # core 0 does expensive work; after barrier both cores are aligned
    core = [0] * 10 + [1] + [0, 1]
    op = [READ] * 10 + [READ] + [BARRIER, BARRIER]
    line = list(range(10)) + [100, 0, 0]
    r = _run(core, op, line)
    assert r["cycles_per_core"][0] == r["cycles_per_core"][1]


@given(seed=st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_counter_invariants(seed):
    rng = np.random.default_rng(seed)
    n = 256
    r = _run(rng.integers(0, 8, n),
             rng.choice([READ, WRITE, CREAD, CWRITE, ATOMIC, MERGE], n),
             rng.integers(0, 128, n))
    assert all(v >= 0 for k, v in r.items() if isinstance(v, int))
    assert r["llc_miss"] <= r["directory"] + r["sb_misses"]
    assert max(r["cycles_per_core"]) == r["cycles_max"]
    assert r["cycles_max"] >= n // 8  # at least 1 cycle per access


def test_ccache_beats_fgl_on_contended_counter():
    """The paper's headline micro-pattern: all cores increment hot lines."""
    rng = np.random.default_rng(1)
    n = 2048
    hot = rng.integers(0, 4, n)            # 4 hot lines
    cores = np.arange(n) % 8
    lockb = 10_000
    fgl_core, fgl_op, fgl_line = [], [], []
    cc_core, cc_op, cc_line = [], [], []
    for c, l in zip(cores, hot):
        fgl_core += [c] * 4
        fgl_op += [ATOMIC, READ, WRITE, WRITE]
        fgl_line += [lockb + l, l, l, lockb + l]
        cc_core += [c] * 2
        cc_op += [CREAD, CWRITE]
        cc_line += [l, l]
    for c in range(8):
        cc_core.append(c)
        cc_op.append(MERGE)
        cc_line.append(0)
    r_fgl = _run(fgl_core, fgl_op, fgl_line)
    r_cc = _run(cc_core, cc_op, cc_line)
    assert r_cc["cycles_max"] * 2 < r_fgl["cycles_max"]


# --------------------------------------------------------------------------
# Multi-level fabric model (the MergePlan IR's analytic counterpart)
# --------------------------------------------------------------------------


def test_fabric_top_level_reduction_matches_group_factor():
    from benchmarks.simulator import default_fabric
    fab = default_fabric()
    payload = 1 << 20
    flat = fab.flat_merge(payload)
    for lane in (False, True):
        hier = fab.hierarchical_merge(payload, lane_parallel=lane)
        # Top-level bytes shrink by the pod stride (16*16=256): the rep (or
        # chunked-lane) exchange moves one contribution per pod, not 512.
        assert flat["bytes_by_level"][-1] / hier["bytes_by_level"][-1] == 256
        # The per-level byte vector is monotone: cheaper links carry more.
        bl = hier["bytes_by_level"]
        assert bl[0] >= bl[1] >= bl[2]


def test_fabric_lane_parallel_is_faster_same_bytes():
    from benchmarks.simulator import default_fabric
    fab = default_fabric()
    payload = 1 << 20
    rep = fab.hierarchical_merge(payload, lane_parallel=False)
    lane = fab.hierarchical_merge(payload, lane_parallel=True)
    # Same wire bytes at every level; the lane-sharded exchange drives the
    # expensive links with every rank instead of one rep per unit.
    assert rep["bytes_by_level"] == lane["bytes_by_level"]
    assert lane["time_s"] < rep["time_s"]


def test_fabric_defer_amortizes_top_level_by_k():
    from benchmarks.simulator import default_fabric
    fab = default_fabric()
    payload = 1 << 20
    eager = fab.hierarchical_merge(payload, lane_parallel=True)
    k = 8
    deferred = fab.hierarchical_merge(payload, lane_parallel=True,
                                      defer_levels=1, commit_every=k)
    assert deferred["bytes_by_level"][-1] * k == eager["bytes_by_level"][-1]
    assert deferred["bytes_by_level"][:-1] == eager["bytes_by_level"][:-1]
    assert deferred["time_s"] < eager["time_s"]


def test_fabric_hier_beats_flat():
    from benchmarks.simulator import default_fabric
    fab = default_fabric()
    payload = 1 << 22
    flat = fab.flat_merge(payload)
    hier = fab.hierarchical_merge(payload, lane_parallel=True)
    assert hier["time_s"] < flat["time_s"]
