"""What the program's own instrumentation gives the benchmark.

The store compiles each program under the name of what it does (e.g.
``kv_tick_launch``), and the "XLA Modules" line names each run of it
``jit_<program>(<id>)``: the per-layer metrics read those. The store and
its front end also open ``repro.*`` host spans, which nest as one thread's
spans do; the reduced trace keeps only the benchmark's ``bench.*`` spans,
so ``bench/chip/scopes.py`` reads the program's from the trace file.
"""

from __future__ import annotations

import bisect

from bench.trace import clip, total


def runs_of(modules, program: str) -> list:
    """The module events of one chip that are runs of ``program``; the
    name must match up to the ``(``, so ``kv_tick_land`` is not
    ``kv_tick_land_launch``."""
    name = f"jit_{program}"
    return [e for e in modules if e.name.partition("(")[0] == name]


def program_ms(trace, program: str):
    """Device milliseconds per run of ``program``: the runs' time, each
    clipped to the window, over their count, averaged over the chips that
    ran it; ``None`` when none did."""
    if trace is None:
        return None
    per_chip = []
    for modules in trace.modules:
        runs = clip(runs_of(modules, program), trace.window)
        if runs:
            per_chip.append(total(runs) / len(runs))
    if not per_chip:
        return None
    return sum(per_chip) / len(per_chip) / 1e6


def nested(children, parents) -> list:
    """The ``children`` spans that lie inside one of ``parents``. The
    parents do not overlap one another (one thread opens them, never one
    inside another), so a child can lie only in the last parent that
    opened before it."""
    parents = sorted(parents, key=lambda s: s.start_ns)
    starts = [p.start_ns for p in parents]
    out = []
    for c in children:
        i = bisect.bisect_right(starts, c.start_ns) - 1
        if i >= 0 and c.end_ns <= parents[i].end_ns:
            out.append(c)
    return out


def host_ms_less_children(trace, parent: str, child: str):
    """Host milliseconds per ``parent`` span, less the time in the
    ``child`` spans inside them; ``None`` when there is no ``parent``."""
    if trace is None:
        return None
    parents = trace.spans_named(parent)
    if not parents:
        return None
    inner = nested(trace.spans_named(child), parents)
    ns = sum(s.dur_ns for s in parents) - sum(s.dur_ns for s in inner)
    return ns / len(parents) / 1e6
