"""The one-chip zipfian cell runs whole on the CPU at a small size and
comes out correct; it shares its configuration and traffic mix with
cells whose faults ``test_faults.py`` plants."""

import pytest

from bench.selftest.tiny import run_tiny, tiny_cell

CELL = "kv1.ingest-zipf"


@pytest.mark.parametrize("trace", [False, True])
def test_a_sound_run_is_correct(trace):
    out = run_tiny(CELL, seed=2**31 + 11, trace=trace)
    assert out["correct"] is True and out["failed"] == 0
    names = set(out["metrics"])
    if trace:
        assert names <= {m["name"] for m in tiny_cell(CELL).per_layer}
        assert out["metrics"]["tick_fill"]["value"] == 100.0
    else:
        assert names == {"updates_per_s", "setup_s"}
    assert out["compiles_in_window"] == 0
