"""Scatter kernel (``kernels/cscatter.py``): the least time the window's
updates need (as ``tick_roofline``) over the device time of the scatter
kernel's events, in percent. A kernel that replaces ``cscatter`` brings a
metric of its own."""

from bench.trace import clip, matching, total

# the names the Pallas kernel's events carry in the "XLA Ops" line
PATTERNS = ("cscatter",)


def read(run):
    t = run.trace
    if t is None:
        return None
    device_ns = sum(total(clip(matching(ops, PATTERNS), t.window))
                    for ops in t.ops)
    if not device_ns:
        return None
    least_ns = 1e9 * run.counters["least_bytes"] / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_ns / device_ns
