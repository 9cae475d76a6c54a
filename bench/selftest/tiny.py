"""A whole run of a cell on the CPU at a small size: the harness with its
look for a chip skipped, the cell's configuration cut to ``n_keys`` rows
and ``slots`` updates per shard per tick."""

from __future__ import annotations

import dataclasses
import time

from bench import harness

TINY_PEAKS = {"hbm_bytes_per_s": 819e9}


def tiny_cell(workload: str, n_keys: int = 1 << 12, slots: int = 64,
              block_ticks: int = 8) -> harness.Cell:
    cell = harness.load_cell(workload)
    config = dict(cell.config, n_keys=n_keys, slots_per_shard=slots)
    traffic = dict(cell.traffic, block_ticks=block_ticks)
    return dataclasses.replace(cell, config=config, traffic=traffic)


def run_tiny(workload: str, seed: int = 7, seconds: float = 0.5,
             trace: bool = False, **cut) -> dict:
    import jax
    cell = tiny_cell(workload, **cut)
    return harness.run_cell(cell, seed, seconds, trace, jax.devices(),
                            TINY_PEAKS, time.perf_counter())
