"""What reads the program's own instrumentation, on hand-built events: the
metrics that read program names on the modules line, the host times that
``bench/chip/scopes.py`` reads from the program's ``repro.*`` spans, and
the existing metrics and idle breakdown on a trace that holds those spans
beside the benchmark's (as the reduced trace will, once ``bench/trace.py``
keeps the ``repro.`` prefix)."""

import pathlib

import pytest

from bench import harness
from bench import trace as tr
from bench.programs import host_ms_less_children, runs_of
from bench.trace import Event, Trace

W = (0.0, 1000.0)
PEAKS = {"hbm_bytes_per_s": 819e9}
COUNTERS = {"ticks": 8, "real_updates": 3, "slots": 4, "least_bytes": 819}
# the per-layer metrics the benchmark had before the program's spans were
# kept in the reduced trace
EXISTING = ("frontend_host_ms_per_tick", "tick_fill", "tick_device_ms",
            "tick_roofline", "cscatter_roofline",
            "commit_collective_ms_per_tick", "commit_exposed_ms_per_tick",
            "device_idle")
PROGRAM_METRICS = {"launch_tick_device_ms": "kv_tick_launch",
                   "land_tick_device_ms": "kv_tick_land",
                   "ring_tick_device_ms": "kv_tick_ring"}


def ev(name, s, e, op=""):
    return Event(name, float(s), float(e), op)


def read(name, t, counters=COUNTERS):
    run = harness.Run(trace=t, counters=dict(counters), peaks=PEAKS)
    return harness.load_module("metrics", name).read(run)


def cycle(base, chip):
    """One commit cycle of the overlapped partitioned store on one chip:
    launch, land, six ring appends; the module ids differ by chip."""
    mods = [ev(f"jit_kv_tick_launch({10 + chip})", base, base + 40),
            ev(f"jit_kv_tick_land({20 + chip})", base + 40, base + 52)]
    for i in range(6):
        s = base + 52 + 2 * i
        mods.append(ev(f"jit_kv_tick_ring({30 + chip})", s, s + 1))
    return mods


def kv4_trace(chips=2):
    return Trace(ops=[[] for _ in range(chips)],
                 modules=[cycle(0, c) + cycle(100, c) for c in range(chips)],
                 spans=[ev("bench.window", 0, 1000)], window=W)


def test_each_program_metric_reads_its_own_runs():
    t = kv4_trace()
    assert read("launch_tick_device_ms", t) == pytest.approx(40e-6)
    assert read("land_tick_device_ms", t) == pytest.approx(12e-6)
    assert read("ring_tick_device_ms", t) == pytest.approx(1e-6)


def test_program_metrics_weighted_by_runs_make_the_tick_time():
    t = kv4_trace()
    counts = {p: len(runs_of(t.modules[0], p))
              for p in PROGRAM_METRICS.values()}
    assert counts == {"kv_tick_launch": 2, "kv_tick_land": 2,
                      "kv_tick_ring": 12}
    ticks = sum(counts.values())
    weighted = sum(read(m, t) * counts[p]
                   for m, p in PROGRAM_METRICS.items()) / ticks
    counters = dict(COUNTERS, ticks=ticks)
    assert weighted == pytest.approx(read("tick_device_ms", t, counters))


def test_a_program_is_matched_by_its_whole_name():
    # kv_tick_land_launch (the K=1 store) is not a landing tick
    t = Trace(ops=[[]], modules=[[ev("jit_kv_tick_land_launch(5)", 0, 90),
                                  ev("jit_kv_tick_land(6)", 100, 110),
                                  ev("jit_kv_tick_landx(7)", 200, 290)]],
              spans=[], window=W)
    assert read("land_tick_device_ms", t) == pytest.approx(10e-6)
    assert read("launch_tick_device_ms", t) is None


def test_program_runs_are_clipped_to_the_window_and_averaged_over_chips():
    t = Trace(ops=[[], []],
              modules=[[ev("jit_kv_tick_ring(1)", -5, 5),
                        ev("jit_kv_tick_ring(1)", 10, 14)],
                       [ev("jit_kv_tick_ring(2)", 10, 20)]],
              spans=[], window=W)
    # chip 0: (5 + 4) / 2 runs; chip 1: 10 / 1 run
    assert read("ring_tick_device_ms", t) == pytest.approx(7.25e-6)


@pytest.mark.parametrize("name", sorted(PROGRAM_METRICS))
def test_program_metrics_find_nothing_without_their_program(name):
    t = Trace(ops=[[]], modules=[[ev("jit_region(92016)", 0, 50)]],
              spans=[], window=W)
    assert read(name, t) is None
    assert read(name, None) is None


def spans_kv4():
    """The kv4 driver's loop: the benchmark's tick span around the
    store's, which holds stage and dispatch; then a dispatch span outside
    any tick, which is no tick's child."""
    out = [ev("bench.window", 0, 1000)]
    for base in (0, 100):
        out += [ev("bench.store.tick", base, base + 50),
                ev("repro.kv.tick", base + 1, base + 49),
                ev("repro.kv.stage", base + 2, base + 6),
                ev("repro.kv.dispatch", base + 7, base + 47)]
    out.append(ev("repro.kv.dispatch", 500, 600))
    return out


def store_host_ms(t):
    return host_ms_less_children(t, "repro.kv.tick", "repro.kv.dispatch")


def frontend_step_ms(t):
    return host_ms_less_children(t, "repro.frontend.step", "repro.kv.tick")


def test_store_host_time_is_the_tick_less_its_dispatch():
    t = Trace(ops=[[]], modules=[[]], spans=spans_kv4(), window=W)
    # each tick span is 48 ns, its dispatch 40 ns
    assert store_host_ms(t) == pytest.approx(8e-6)


def spans_kv1():
    """The kv1 driver's loop: the client's adds, then the benchmark's
    step span around the front end's, whose tick passes through the
    benchmark's tick span into the store's."""
    out = [ev("bench.window", 0, 1000)]
    for base in (0, 200):
        out += [ev("bench.client", base, base + 60),
                ev("bench.frontend.step", base + 60, base + 180),
                ev("repro.frontend.step", base + 61, base + 179),
                ev("repro.frontend.pack", base + 62, base + 70),
                ev("bench.store.tick", base + 71, base + 170),
                ev("repro.kv.tick", base + 72, base + 169),
                ev("repro.kv.stage", base + 73, base + 80),
                ev("repro.kv.dispatch", base + 81, base + 168)]
    return out


def test_front_end_step_time_less_the_store_tick():
    t = Trace(ops=[[]], modules=[[]], spans=spans_kv1(), window=W)
    # a step span is 118 ns, the store's tick inside it 97
    assert frontend_step_ms(t) == pytest.approx(21e-6)
    # below the benchmark's own front-end time, which keeps the client's
    # adds: (60 + 120 - 99) x 2 over 2 ticks
    assert read("frontend_host_ms_per_tick", t,
                dict(COUNTERS, ticks=2)) == pytest.approx(81e-6)
    # the store's own: a tick of 97 ns less its dispatch of 87
    assert store_host_ms(t) == pytest.approx(10e-6)


@pytest.mark.parametrize("host_ms", [store_host_ms, frontend_step_ms])
def test_host_times_find_nothing_without_the_program_spans(host_ms):
    bench_only = [s for s in spans_kv1() if not s.name.startswith("repro.")]
    t = Trace(ops=[[]], modules=[[]], spans=bench_only, window=W)
    assert host_ms(t) is None
    assert host_ms(None) is None


def kv1_fixture(with_program_spans: bool):
    """A traced window of two front-end steps on one chip, the device idle
    while the host is inside the store's staging, with or without the
    program's spans."""
    spans = spans_kv1()
    if not with_program_spans:
        spans = [s for s in spans if not s.name.startswith("repro.")]
    ops = [[ev("cscatter.1", 90, 150, "custom-call"),
            ev("fusion.2", 150, 160, "fusion"),
            ev("psum.1", 160, 170, "all-reduce"),
            ev("cscatter.1", 290, 350, "custom-call")]]
    mods = [[ev("jit_kv_tick_sync(3)", 90, 170),
             ev("jit_kv_tick_sync(3)", 290, 350)]]
    return Trace(ops=ops, modules=mods,
                 spans=sorted(spans, key=lambda s: s.start_ns), window=W)


@pytest.mark.parametrize("name", EXISTING)
def test_existing_metrics_read_the_same_with_the_program_spans(name):
    counters = dict(COUNTERS, ticks=2)
    before = read(name, kv1_fixture(False), counters)
    after = read(name, kv1_fixture(True), counters)
    assert before is not None
    assert after == before


def test_idle_gaps_go_to_the_innermost_program_span():
    window = (0.0, 400.0)
    ops = [[ev("k", 0, 70), ev("k", 82, 400)]]
    # the gap 70..82 has its middle (76) in the store's staging
    t = kv1_fixture(True)
    assert tr.idle_by_host(ops, t.spans, window) == [
        ["repro.kv.stage", 12e-9]]
    # without the program's spans it goes to the benchmark's tick span
    t = kv1_fixture(False)
    assert tr.idle_by_host(ops, t.spans, window) == [
        ["bench.store.tick", 12e-9]]


def test_the_tool_reads_the_program_spans_the_reduction_drops(tmp_path):
    import jax
    import jax.numpy as jnp
    from bench.chip.scopes import host_view
    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.store.tick"):
                with jax.profiler.TraceAnnotation("repro.kv.tick"):
                    with jax.profiler.TraceAnnotation("repro.kv.dispatch"):
                        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = str(next(pathlib.Path(tmp_path).rglob("*.xplane.pb")))
    names = [s.name for s in tr.load(path).spans]
    assert names.count("bench.store.tick") == 3
    assert "repro.kv.tick" not in names
    view = host_view(path, 1)
    assert view["store_host_ms_per_tick"] > 0
    assert view["frontend_step_host_ms_per_tick"] is None
    assert view["idle_gaps"] == []     # no TPU plane on a CPU
