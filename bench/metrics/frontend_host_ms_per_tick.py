"""Front end (``serve/frontend.py``): host milliseconds per tick spent in
the client's ``add`` calls and in ``BatchedFrontend.step``, less the time
inside ``ShardedKV.tick``. Read from the benchmark's host spans."""


def read(run):
    t, ticks = run.trace, run.counters["ticks"]
    if t is None or not ticks:
        return None
    ns = sum(s.dur_ns for s in t.spans_named("bench.client"))
    ns += sum(s.dur_ns for s in t.spans_named("bench.frontend.step"))
    ns -= sum(s.dur_ns for s in t.spans_named("bench.store.tick"))
    return ns / ticks / 1e6
