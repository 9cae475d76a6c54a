"""The trace reduction on hand-built events, and the parser on a recorded
(CPU) trace."""

import pathlib

import pytest

from bench import trace as tr
from bench.trace import Event, Trace

W = (0.0, 100.0)


def ev(name, s, e):
    return Event(name, float(s), float(e))


def test_union_merges_overlaps_and_touching():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9.5)]) == [
        (0, 4), (5, 7), (9, 9.5)]


def test_busy_clips_to_the_window_and_idle_share():
    ops = [ev("a", -10, 10), ev("b", 5, 20), ev("c", 50, 60),
           ev("d", 95, 130)]
    busy = tr.busy(ops, W)
    assert busy == [(0, 20), (50, 60), (95, 100)]
    assert tr.total(busy) == 35
    assert tr.gaps(busy, W) == [(20, 50), (60, 95)]


def test_subtract():
    a = [(0, 10), (20, 30)]
    b = [(2, 4), (8, 22), (25, 26)]
    assert tr.subtract(a, b) == [(0, 2), (4, 8), (22, 25), (26, 30)]
    assert tr.subtract(a, []) == a
    assert tr.subtract([], b) == []


def test_selection_by_pattern():
    ops = [ev("fusion.1", 0, 1), ev("cscatter_kernel.3", 1, 2),
           ev("all-reduce.2", 2, 3)]
    assert [e.name for e in tr.matching(ops, ("cscatter",))] == [
        "cscatter_kernel.3"]
    assert tr.matching(ops, ("nothing",)) == []


# op events of the two cells' ticks, as the TPU profiler names them
KERNEL = ("%cscatter.1 = s32[8388608,4]{1,0:T(8,128)} custom-call("
          "s32[1,1024]{1,0:T(1,128)S(1)} %copy-done.1, s32[1024,4]"
          "{1,0:T(8,128)S(1)} %bitcast.7, s32[8388608,4]{1,0:T(8,128)} "
          "%broadcast_in_dim.6), custom_call_target=\"tpu_custom_call\"")
ADD = ("%fusion = s32[1,8388608,4]{2,1,0:T(8,128)} fusion(s32[8388608,4]"
       "{1,0:T(8,128)} %cscatter.1, s32[1,8388608,4]{2,1,0:T(8,128)} "
       "%copy), kind=kLoop, calls=%fused_computation.1")
PSUM = ("%psum.7 = s32[1,8388608,4]{2,1,0:T(8,128)} all-reduce(s32[1,8388608"
        ",4]{2,1,0:T(8,128)} %bitcast.6), channel_id=1, replica_groups="
        "{{0,1},{2,3}}, use_global_device_ids=true, to_apply=%region_0.1")
PERMUTE = ("%collective-permute-start = (s32[16777216]{0:T(1024)S(1)}, "
           "s32[16777216]{0:T(1024)}, u32[]{:S(2)}, u32[]{:S(2)}) "
           "collective-permute-start(s32[16777216]{0:T(1024)S(1)} "
           "%bitcast.12), channel_id=1, source_target_pairs={{0,2},{1,3}}")


def hlo(text, s, e):
    name, op = tr.parse_hlo(text)
    return Event(name, float(s), float(e), op)


def test_op_events_parse_to_name_and_opcode():
    assert tr.parse_hlo(KERNEL) == ("cscatter.1", "custom-call")
    assert tr.parse_hlo(ADD) == ("fusion", "fusion")
    assert tr.parse_hlo(PSUM) == ("psum.7", "all-reduce")
    assert tr.parse_hlo(PERMUTE) == ("collective-permute-start",
                                     "collective-permute-start")
    assert tr.parse_hlo("jit_region(92016)") == ("jit_region(92016)", "")
    # the kernel is selected by its own name, not by an operand's
    ops = [hlo(KERNEL, 0, 5), hlo(ADD, 5, 7)]
    assert [e.name for e in tr.matching(ops, ("cscatter",))] == [
        "cscatter.1"]
    assert tr.is_collective(hlo(PSUM, 0, 1))
    assert not tr.is_collective(hlo(ADD, 0, 1))


def test_collectives_exposed_versus_overlapped():
    # an async permute in flight 10..40 with compute 15..30 under it; a
    # synchronous all-reduce 60..70 with nothing beside it
    ops = [Event("collective-permute-start.1", 10, 11,
                 "collective-permute-start"),
           Event("fusion.4", 15, 30, "fusion"),
           Event("collective-permute-done.1", 39, 40,
                 "collective-permute-done"),
           Event("psum.7", 60, 70, "all-reduce")]
    coll = tr.collective_intervals(ops, W)
    assert coll == [(10, 40), (60, 70)]
    assert tr.total(coll) == 40
    exp = tr.exposed(coll, ops, W)
    assert exp == [(10, 15), (30, 40), (60, 70)]
    assert tr.total(exp) == 25


def test_async_pairs_are_matched_in_time_order():
    # the same start/done names come back every commit
    ops = []
    for base in (0, 50):
        ops += [Event("cp-start", base, base + 1, "collective-permute-start"),
                Event("cp-done", base + 9, base + 10,
                      "collective-permute-done")]
    assert tr.collective_intervals(ops, W) == [(0, 10), (50, 60)]


def test_fully_overlapped_collective_is_not_exposed():
    ops = [Event("all-gather-start.2", 10, 12, "all-gather-start"),
           Event("fusion.1", 10, 50, "fusion"),
           Event("all-gather-done.2", 48, 50, "all-gather-done")]
    coll = tr.collective_intervals(ops, W)
    assert tr.total(coll) == 40
    assert tr.exposed(coll, ops, W) == []


def test_idle_gaps_charged_to_the_innermost_host_span():
    ops = [[ev("k", 0, 20), ev("k", 50, 100)]]
    spans = [ev("bench.window", 0, 100), ev("bench.frontend.step", 10, 45),
             ev("bench.store.tick", 30, 40)]
    # the one gap 20..50 has its middle (35) inside the tick span
    assert tr.idle_by_host(ops, spans, W) == [["bench.store.tick", 30e-9]]
    spans = [ev("bench.window", 0, 100)]
    assert tr.idle_by_host(ops, spans, W) == [["host.outside_spans", 30e-9]]


def test_top_ops_strip_suffixes_and_average_over_chips():
    ops = [[ev("fusion.1", 0, 10), ev("fusion.2", 10, 30)],
           [ev("fusion.9", 0, 10), ev("copy.1", 10, 12)]]
    assert tr.top_ops(ops, W) == [["fusion", 20e-9], ["copy", 1e-9]]


def test_metric_readers_on_a_hand_built_trace():
    from bench import harness
    t = Trace(ops=[[Event("cscatter.1", 10, 30, "custom-call"),
                    Event("fusion.2", 30, 40, "fusion"),
                    Event("psum.1", 40, 50, "all-reduce")]],
              modules=[[ev("jit_tick", 10, 50)]],
              spans=[ev("bench.window", 0, 100),
                     ev("bench.client", 0, 10), ev("bench.frontend.step",
                                                   10, 30),
                     ev("bench.store.tick", 20, 25)],
              window=W)
    run = harness.Run(trace=t, counters={"ticks": 2, "real_updates": 3,
                                         "slots": 4, "least_bytes": 819},
                      peaks={"hbm_bytes_per_s": 819e9})

    def read(name):
        return harness.load_module("metrics", name).read(run)

    assert read("device_idle") == pytest.approx(60.0)
    assert read("tick_fill") == 75.0
    assert read("tick_device_ms") == pytest.approx(20e-6)
    assert read("frontend_host_ms_per_tick") == pytest.approx(12.5e-6)
    # 819 bytes at 819e9 B/s is 1 ns: over 40 ns of program, 20 of kernel
    assert read("tick_roofline") == pytest.approx(2.5)
    assert read("cscatter_roofline") == pytest.approx(5.0)
    assert read("commit_collective_ms_per_tick") == pytest.approx(5e-6)
    assert read("commit_exposed_ms_per_tick") == pytest.approx(5e-6)


def test_readers_find_nothing_without_events():
    from bench import harness
    t = Trace(ops=[[Event("fusion.2", 30, 40, "fusion")]], modules=[[]],
              spans=[ev("bench.window", 0, 100)], window=W)
    run = harness.Run(trace=t, counters={"ticks": 2, "real_updates": 3,
                                         "slots": 4, "least_bytes": 819},
                      peaks={"hbm_bytes_per_s": 819e9})
    for name in ("cscatter_roofline", "commit_collective_ms_per_tick",
                 "commit_exposed_ms_per_tick", "tick_device_ms",
                 "tick_roofline"):
        assert harness.load_module("metrics", name).read(run) is None


def test_load_reads_host_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.store.tick"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = next(pathlib.Path(tmp_path).rglob("*.xplane.pb"))
    t = tr.load(str(path))
    ticks = t.spans_named("bench.store.tick")
    assert len(ticks) == 3
    assert all(t.window[0] <= s.start_ns < s.end_ns <= t.window[1]
               for s in ticks)
    assert t.ops == t.modules == []   # no TPU planes on a CPU


def test_innermost_segments_of_nested_spans():
    spans = [ev("a", 0, 10), ev("b", 2, 5), ev("c", 3, 4), ev("d", 5, 8)]
    assert tr.innermost(spans) == [(0, 2, "a"), (2, 3, "b"), (3, 4, "c"),
                                   (4, 5, "b"), (5, 8, "d"), (8, 10, "a")]
