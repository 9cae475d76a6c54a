"""Merge cascade: the part of ``commit_collective_ms_per_tick`` during which
no other op runs on the chip, in milliseconds per tick."""

from bench.trace import collective_intervals, exposed, total


def read(run):
    t, ticks = run.trace, run.counters["ticks"]
    if t is None or not ticks:
        return None
    coll = [collective_intervals(ops, t.window) for ops in t.ops]
    if not any(coll):
        return None
    ns = sum(total(exposed(c, ops, t.window)) for c, ops in zip(coll, t.ops))
    return ns / len(t.ops) / ticks / 1e6
