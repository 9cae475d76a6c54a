"""Reduce a JAX profiler trace (``.xplane.pb``) to device and host intervals.

``load`` keeps what the metrics read: for each TPU core plane, the events
of its "XLA Ops" line (one per executed HLO op, with the op's instruction
name, e.g. ``cscatter.1``, and its opcode, e.g. ``all-reduce``) and of its
"XLA Modules" line (one per executed program); and the host spans whose
names start with the benchmark's prefix. Everything else here is interval arithmetic on those
events, so it can be checked on hand-built events.

    python3 bench/trace.py <file.xplane.pb>   # what a trace holds, by line
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import re
import sys

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# an op event's name is its HLO text, "%name = type opcode(operands), ..."
_HLO_NAME = re.compile(r"^%([^\s=]+) = ")

# opcodes of HLO ops that move data between chips (with their -start and
# -done halves)
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "collective-broadcast", "all-to-all",
               "ragged-all-to-all", "send", "recv")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float
    op: str = ""     # the HLO opcode of a device op event

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Trace:
    """``ops[c]`` / ``modules[c]``: chip ``c``'s op and program events;
    ``spans``: the benchmark's host spans; ``window``: the window span's
    ``(start_ns, end_ns)`` on the same clock."""

    ops: list
    modules: list
    spans: list
    window: tuple

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def spans_named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]


def parse_hlo(text: str) -> tuple:
    """``(instruction name, opcode)`` of an op event's HLO text; ``(text,
    "")`` for any other event name."""
    m = _HLO_NAME.match(text)
    if not m:
        return text, ""
    rest = text[m.end():]
    if rest.startswith("("):            # a tuple type: skip its parentheses
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                break
        rest = rest[i + 1:]
    else:
        rest = rest.partition(" ")[2]
    return m.group(1), rest.strip().partition("(")[0]


def _events(line) -> list:
    out = []
    for e in line.events:
        name, op = parse_hlo(e.name)
        start = float(e.start_ns)
        out.append(Event(name, start, start + float(e.duration_ns), op))
    return out


def load(path: str, n_chips=None) -> Trace:
    """The trace at ``path``, keeping the ``n_chips`` TPU planes of lowest
    index (all of them when ``None``): a cell runs on the first devices of
    its machine."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes, spans = {}, []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            planes[int(m.group(1))] = [
                _events(lines[n]) if n in lines else []
                for n in ("XLA Ops", "XLA Modules")]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [e for e in _events(ln)
                          if e.name.startswith(SPAN_PREFIX)]
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{path}: {len(windows)} {WINDOW_SPAN} spans")
    order = sorted(planes)[:n_chips]
    return Trace(ops=[planes[c][0] for c in order],
                 modules=[planes[c][1] for c in order],
                 spans=sorted(spans, key=lambda s: s.start_ns),
                 window=(windows[0].start_ns, windows[0].end_ns))


# --------------------------------------------------------------- intervals


def clip(events, window) -> list:
    """``(start, end)`` of each event, cut to the window; empty ones go."""
    lo, hi = window
    out = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append((s, t))
    return out


def union(intervals) -> list:
    """Merge overlapping ``(start, end)`` intervals, sorted."""
    out = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            if t > out[-1][1]:
                out[-1] = (out[-1][0], t)
        else:
            out.append((s, t))
    return out


def total(intervals) -> float:
    return sum(t - s for s, t in intervals)


def subtract(a, b) -> list:
    """The parts of the merged intervals ``a`` that the merged intervals
    ``b`` do not cover."""
    out, j = [], 0
    for s, t in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < t:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < t:
            out.append((cur, t))
    return out


def gaps(busy, window) -> list:
    """The idle intervals of the window between merged busy intervals."""
    return subtract([window], busy)


# ------------------------------------------------------------------ events


def matching(events, patterns) -> list:
    """Events whose name contains any of ``patterns``."""
    return [e for e in events if any(p in e.name for p in patterns)]


def busy(ops, window) -> list:
    """Merged intervals in which some op ran on one chip."""
    return union(clip(ops, window))


def is_collective(e: Event) -> bool:
    return e.op.startswith(COLLECTIVES)


def collective_intervals(ops, window) -> list:
    """Merged intervals in which a collective was in flight on one chip:
    each synchronous collective op, and each async one from the start of
    its ``-start`` op to the end of its ``-done`` op (paired by name, in
    time order), whatever runs between them."""
    starts, ivals = {}, []
    for e in sorted(filter(is_collective, ops), key=lambda e: e.start_ns):
        if e.op.endswith("-start"):
            starts[e.name.replace("-start", "", 1)] = e.start_ns
        elif e.op.endswith("-done"):
            s = starts.pop(e.name.replace("-done", "", 1), e.start_ns)
            ivals.append(Event(e.name, s, e.end_ns))
        else:
            ivals.append(e)
    return union(clip(ivals, window))


def exposed(collective, ops, window) -> list:
    """The parts of the collective intervals during which no other op (no
    compute, no copy) runs on that chip."""
    others = union(clip([e for e in ops if not is_collective(e)], window))
    return subtract(collective, others)


def top_ops(ops_by_chip, window, n=10) -> list:
    """``[name, seconds]`` of the ops that took most device time, summed
    over chips and averaged per chip, names without their ``.N`` suffix."""
    acc = collections.Counter()
    for ops in ops_by_chip:
        for e in ops:
            s, t = max(e.start_ns, window[0]), min(e.end_ns, window[1])
            if t > s:
                acc[re.sub(r"\.\d+$", "", e.name)] += t - s
    chips = max(len(ops_by_chip), 1)
    return [[k, v / chips / 1e9] for k, v in acc.most_common(n)]


def innermost(spans) -> list:
    """``(start, end, name)`` segments, in time order, of the innermost
    span open at each instant; the spans must nest, as one thread's do."""
    marks = []
    for sp in spans:
        marks.append((sp.start_ns, 1, -sp.end_ns, id(sp), sp))
        marks.append((sp.end_ns, 0, 0, id(sp), sp))
    marks.sort(key=lambda m: m[:4])
    segs, stack, last = [], [], None
    for t, opening, _, _, sp in marks:
        if stack and t > last:
            segs.append((last, t, stack[-1].name))
        if opening:
            stack.append(sp)
        else:
            stack.remove(sp)
        last = t
    return segs


def idle_by_host(ops_by_chip, spans, window, n=10) -> list:
    """``[host activity, seconds]``: each idle gap of each chip is charged
    to the innermost benchmark span the host was in at the gap's middle
    (``host.outside_spans`` when none), averaged per chip."""
    segs = innermost([s for s in spans if s.name != WINDOW_SPAN])
    starts = [g[0] for g in segs]
    acc = collections.Counter()
    for ops in ops_by_chip:
        for s, t in gaps(busy(ops, window), window):
            mid = (s + t) / 2
            i = bisect.bisect_right(starts, mid) - 1
            name = (segs[i][2] if i >= 0 and mid < segs[i][1]
                    else "host.outside_spans")
            acc[name] += t - s
    chips = max(len(ops_by_chip), 1)
    return [[k, v / chips / 1e9] for k, v in acc.most_common(n)]


def describe(path: str, n: int = 25) -> str:
    """Planes, lines, event counts and the commonest event names."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for ln in plane.lines:
            names = collections.Counter()
            first = {}
            for e in ln.events:
                names[e.name] += 1
                first.setdefault(e.name, dict(e.stats))
            out.append(f"  line {ln.name!r}: {sum(names.values())} events")
            for name, k in names.most_common(n):
                out.append(f"    {k:6d} {name}  {first[name]}")
    return "\n".join(out)


if __name__ == "__main__":
    print(describe(sys.argv[1]))
