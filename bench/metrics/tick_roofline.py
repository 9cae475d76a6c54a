"""Store tick: the least time the window's updates need (their least bytes
over the chip's HBM bandwidth) over the tick programs' device time, in
percent. The adds need far less compute than the chip's peak, so bytes set
the bound. It holds whatever the tick calls."""

from bench.trace import clip, total


def read(run):
    t = run.trace
    if t is None or not any(t.modules):
        return None
    device_ns = sum(total(clip(m, t.window)) for m in t.modules)
    least_ns = 1e9 * run.counters["least_bytes"] / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_ns / device_ns
