"""The store's tracing: program names, named scopes and host spans.

Every store program compiles under a name that says what the tick does
(``module @jit_kv_tick_launch``), its device stages carry named scopes in
the HLO ``op_name`` metadata, and ``ShardedKV``/``BatchedFrontend`` open
``repro.*`` host spans that a profiler session records on the device
trace's clock. One subprocess on a forced 4-device host mesh lowers every
program, records a profiler trace of a few front-end steps, and reports
what it found; the tests below each check one part of that report.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
ENV = dict(os.environ, PYTHONPATH=os.pathsep.join(
    [os.path.join(ROOT, "src"), os.environ.get("PYTHONPATH", "")]))
ENV.pop("XLA_FLAGS", None)

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, pathlib, re, sys, tempfile
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.apps.sharded import build_mesh, mesh_spmd
    from repro.core.ccache import deferred_stages_of
    from repro.core.defer_schedule import DeferSchedule
    from repro.core.merge_functions import ADD
    from repro.serve import BatchedFrontend, KVConfig, ShardedKV, serving_plan

    S, R, D, B = 4, 64, 4, 8
    spmd = mesh_spmd(build_mesh(S))
    plan = serving_plan(S, "all")
    levels = tuple(s.name for s in deferred_stages_of(plan, S, merge_fn=ADD))

    def store(engine="kernel", k=2, overlap=True, partitioned=True,
              consistency="read_your_writes"):
        cfg = KVConfig(n_keys=R, cols=D, engine=engine,
                       partitioned=partitioned, consistency=consistency)
        return ShardedKV(cfg, S, spmd, plan=plan, schedule=DeferSchedule.fixed(
            k, levels, overlap=overlap))

    def major(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((S,) + x.shape, x.dtype), tree)

    def lower(fn, args):
        low = spmd.lower(fn, *major(args))
        module = low.as_text().split(" ", 2)[1]
        hlo = low.compiler_ir("hlo").get_hlo_module().to_string()
        scopes = sorted({part for name in re.findall(r'op_name="([^"]*)"',
                                                     hlo)
                         for part in re.split(r"[/;]", name)})
        return {"module": module, "scopes": scopes}

    programs = {}

    def tick_programs(st, tag, land_too):
        for land in ((False, True) if land_too else (False,)):
            for due in st.supported_dues:
                fn = st.raw_tick_fn(due, land=land)
                programs[tag + fn.__name__] = lower(
                    fn, st.tick_arg_specs(B, land=land))

    over = store()
    tick_programs(over, "", True)
    tick_programs(store(k=1), "", True)
    tick_programs(store(overlap=False), "", False)
    tick_programs(store(engine="blocked"), "blocked:", True)
    tick_programs(store(engine="blocked", overlap=False), "blocked:", False)
    spec = over.tick_arg_specs(B, land=True)
    settled, ring, inflight, keys = spec[0], spec[1], spec[2], spec[3]
    for fn, args in ((over._flush_fn, (settled, ring)),
                     (over._flush_land_fn, (settled, ring, inflight)),
                     (over._read_fns["plain"], (settled, keys)),
                     (over._read_fns["ryw"], (settled, ring, keys)),
                     (over._read_fns["ryw_inflight"],
                      (settled, ring, inflight, keys))):
        programs[fn.__name__] = lower(fn, args)
    sync = ShardedKV(KVConfig(n_keys=R, cols=D), S, spmd,
                     plan=serving_plan(S, "none"))
    programs[sync.raw_tick_fn().__name__] = lower(
        sync.raw_tick_fn(), sync.tick_arg_specs(B))
    rep = store(partitioned=False, overlap=False, consistency="eventual")
    for due in rep.supported_dues:
        fn = rep.raw_tick_fn(due)
        programs[fn.__name__] = lower(fn, rep.tick_arg_specs(B))

    # a few front-end steps over a small partitioned store, one profiler
    # session open around them, then the same stream with no session. Each
    # step fills every shard's slots exactly, so the drain takes no step.
    rng = np.random.default_rng(7)
    T = 5
    adds = [(int(s + S * rng.integers(0, R // S)),
             rng.integers(1, 9, D).astype(np.int32))
            for _ in range(T * B) for s in range(S)]
    get_steps = (1, 3)

    def serve(fe):
        for t in range(T):
            for key, val in adds[t * S * B:(t + 1) * S * B]:
                fe.add(key, val)
            if t in get_steps:
                fe.get(int(adds[t * S * B][0]))
            fe.step()
        fe.drain()
        fe.store.flush()
        return fe.store.table()

    traced_fe = BatchedFrontend(store(), slots_per_shard=B)
    traced_fe.step()                       # compile outside the session
    with tempfile.TemporaryDirectory() as tdir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        traced = serve(traced_fe)
        jax.profiler.stop_trace()
        path = next(pathlib.Path(tdir).rglob("*.xplane.pb"))
        spans = [e for plane in jax.profiler.ProfileData.from_file(
                     str(path)).planes if plane.name.startswith("/host:")
                 for ln in plane.lines for e in ln.events
                 if e.name.startswith("repro.")]
    plain = serve(BatchedFrontend(store(), slots_per_shard=B))
    ref = np.zeros((R, D), np.int64)
    for key, val in adds:
        ref[key] += val

    def inside(child, parent):
        return parent.start_ns <= child.start_ns and \\
            child.end_ns <= parent.end_ns

    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    steps, ticks = by["repro.frontend.step"], by["repro.kv.tick"]
    out = {
        "programs": programs,
        "span_counts": {k: len(v) for k, v in by.items()},
        "ticks_in_a_step": [sum(inside(t, s) for s in steps)
                            for t in ticks],
        "stages_in_a_tick": [
            [sum(inside(c, t) for c in by.get(name, []))
             for name in ("repro.kv.stage", "repro.kv.dispatch")]
            for t in ticks],
        "reads_in_a_frontend_read": [
            sum(inside(r, f) for r in by.get("repro.kv.read", []))
            for f in by.get("repro.frontend.read", [])],
        "flush_spans": len(by.get("repro.kv.flush", [])),
        "traced_equals_plain": bool(np.array_equal(traced, plain)),
        "plain_equals_oracle": bool(np.array_equal(plain.astype(np.int64),
                                                   ref)),
        "steps": T,
    }
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def report():
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=ENV, cwd=ROOT,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    line = next(ln for ln in r.stdout.splitlines()
                if ln.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


# every program a store runs, by the name it compiles under
PROGRAMS = ["kv_tick_ring", "kv_tick_launch", "kv_tick_land",
            "kv_tick_land_launch", "kv_tick_commit",
            "blocked:kv_tick_ring", "blocked:kv_tick_launch",
            "blocked:kv_tick_land", "blocked:kv_tick_land_launch",
            "blocked:kv_tick_commit",
            "kv_flush", "kv_flush_land", "kv_read", "kv_read_ryw",
            "kv_read_ryw_inflight", "kv_tick_sync", "kv_tick_defer",
            "kv_tick_commit_1", "kv_tick_commit_2"]


@pytest.mark.parametrize("program", PROGRAMS)
def test_each_program_lowers_to_its_named_module(report, program):
    name = program.partition(":")[2] or program
    assert report["programs"][program]["module"] == f"@jit_{name}"


def test_no_program_is_left_unnamed(report):
    assert sorted(report["programs"]) == sorted(PROGRAMS)


# each named scope, and a program whose op_name metadata must carry it
SCOPES = [("scatter", "kv_tick_launch"), ("identity", "kv_tick_launch"),
          ("ring_append", "kv_tick_ring"), ("ring_reset", "kv_tick_launch"),
          ("launch", "kv_tick_launch"), ("land", "kv_tick_land"),
          ("settle", "blocked:kv_tick_commit"), ("settle", "kv_flush"),
          ("settle", "kv_tick_commit_2"), ("home_rows", "kv_tick_land"),
          ("apply", "blocked:kv_tick_commit"), ("apply", "kv_tick_sync"),
          ("route", "kv_tick_commit"), ("scatter", "kv_tick_commit"),
          ("read", "kv_read"), ("read", "kv_read_ryw_inflight")]


@pytest.mark.parametrize("scope,program", SCOPES)
def test_named_scopes_reach_the_hlo_metadata(report, scope, program):
    assert scope in report["programs"][program]["scopes"]


def test_a_ring_tick_runs_no_commit_stage(report):
    scopes = set(report["programs"]["kv_tick_ring"]["scopes"])
    assert not scopes & {"launch", "land", "settle", "ring_reset"}


def test_one_tick_span_per_front_end_step(report):
    counts = report["span_counts"]
    assert counts["repro.frontend.step"] == report["steps"]
    assert counts["repro.kv.tick"] == report["steps"]
    assert report["ticks_in_a_step"] == [1] * report["steps"]
    assert counts["repro.frontend.pack"] == report["steps"]


def test_each_tick_span_holds_stage_and_dispatch(report):
    assert report["stages_in_a_tick"] == [[1, 1]] * report["steps"]
    assert "repro.kv.journal" not in report["span_counts"]


def test_front_end_read_span_only_with_a_get(report):
    # two steps carry a get; the drain's step after them carries none
    assert report["reads_in_a_frontend_read"] == [1, 1]
    assert report["span_counts"]["repro.kv.read"] == 2
    assert report["flush_spans"] == 1


def test_instrumentation_leaves_the_table_bitwise(report):
    assert report["traced_equals_plain"]
    assert report["plain_equals_oracle"]
