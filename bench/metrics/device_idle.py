"""Device: the share of the traced window in which no op runs on a chip,
averaged over the chips, in percent."""

from bench.trace import busy, total


def read(run):
    t = run.trace
    if t is None or not t.ops:
        return None
    busy_ns = sum(total(busy(ops, t.window)) for ops in t.ops) / len(t.ops)
    return 100.0 * (1.0 - busy_ns / t.window_ns)
