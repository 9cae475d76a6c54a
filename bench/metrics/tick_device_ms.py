"""Store tick (``serve/kv.py``): device milliseconds of the tick programs
per tick, averaged over the chips. Every program that runs in the window is
a tick: the closed loop reads and flushes only after the window closes."""

from bench.trace import clip, total


def read(run):
    t, ticks = run.trace, run.counters["ticks"]
    if t is None or not ticks or not any(t.modules):
        return None
    ns = sum(total(clip(m, t.window)) for m in t.modules)
    return ns / len(t.modules) / ticks / 1e6
