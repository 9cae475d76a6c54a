"""Merge cascade (``core/ccache.py``): device milliseconds per tick in which
a collective is in flight, averaged over the chips."""

from bench.trace import collective_intervals, total


def read(run):
    t, ticks = run.trace, run.counters["ticks"]
    if t is None or not ticks:
        return None
    ns = sum(total(collective_intervals(ops, t.window)) for ops in t.ops)
    if not ns:
        return None
    return ns / len(t.ops) / ticks / 1e6
