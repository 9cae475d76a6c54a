"""Bring-up check: the store, the apps and the train step on a TPU.

    python3 chip_smoke.py [--seed N]          # one chip
    python3 chip_smoke.py --four-chips        # the 2x2 host's cross-chip paths

Runs in this one process (it holds the chip, and starts no child that
needs JAX). Each phase prints one summary line; a failed phase ends the run
with a non-zero exit. The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

One chip:
  (a) device: a TPU must be present; there is no CPU fallback.
  (b) store: a one-shard ``ShardedKV`` (kernel engine, int32, 4 columns) at
      the largest power-of-two key count whose compiled tick fits the chip,
      fed Pareto keys through ``BatchedFrontend``; gets and the flushed
      table match a numpy oracle bitwise, and the tick runs ``cscatter``.
  (c) apps: BFS (MIN kernel, bitwise) and PageRank (f32 ADD kernel) on one
      shard against their references.
  (d) train: ``launch.train`` at the full width of qwen1.5-0.5b.
With ``--four-chips``, only what runs across chips:
  - the partitioned, deferred, overlapped store at 4 shards, bitwise against
    the synchronized 4-shard store and the numpy oracle;
  - one data-parallel qwen1.5-0.5b step of the train CLI whose gradients
    merge through ``--merge-topology chip:2,host:2`` over the 4 chips,
    against the CLI's implicit XLA reduction: the merged gradient (the
    optimizer's first moment and the gradient norm) must agree.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

BATCH = 1024            # updates per shard per tick
COLS = 4
TICKS = 32
GETS = 1024
USERS = 1 << 20
MIN_KEYS_LOG2, MAX_KEYS_LOG2 = 22, 24
ARCH = "qwen1-5-0-5b"  # qwen1.5-0.5b
TRAIN_ARGV = ["--arch", ARCH, "--batch", "8", "--seq", "256"]
MERGE_PLAN = "chip:2,host:2"
# Limits on the merge plan's distance from the implicit reduction. Both
# are bf16 gradients reduced in another order and layout: a sound merge on
# 4 v5e chips read grad_norm_err 6.6e-3 and worst_leaf_err 5.6e-2 (the
# tied embedding). A dropped rank reads about 7e-2 and 4.5e-1, half the
# ranks 1e-1 and 8e-1, zeroed gradients 1 and 1; a sum in place of the
# mean triples the gradient norm (clipping hides it from mu).
MERGE_LIMITS = {"loss_err": 2**-5, "grad_norm_err": 2**-5,
                "worst_leaf_err": 2**-3}


class PhaseError(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise PhaseError(msg)


def phase(name: str, fn, *args):
    t0 = time.perf_counter()
    summary = fn(*args)
    print(f"[{name}] ok in {time.perf_counter() - t0}s: {summary}",
          flush=True)


# ---------------------------------------------------------------- (a)


def find_tpu():
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise PhaseError(
            f"no TPU found: JAX's default backend is {d.platform!r} "
            f"({len(devs)} device(s)); this check runs on a TPU only")
    return d, len(devs)


# ---------------------------------------------------------------- (b)


def _shard_major(specs, n_shards: int):
    import jax
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n_shards,) + s.shape, s.dtype), specs)


def _live_bytes(ma) -> int:
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def _oracle_add(table, keys, vals):
    """numpy int32 scatter-add, wrapping like the device's int32."""
    import numpy as np
    acc = table.astype(np.int64)
    ok = keys >= 0
    np.add.at(acc, keys[ok], vals[ok].astype(np.int64))
    return acc.astype(np.int32)


def _stream(n_keys: int, ticks: int, shards: int, seed: int):
    import numpy as np
    from benchmarks.traces import key_stream
    keys = key_stream(ticks * shards * BATCH, n_keys, "pareto",
                      n_users=USERS, seed=seed
                      ).reshape(ticks, shards, BATCH).astype(np.int32)
    rng = np.random.default_rng(seed + 1)
    # full-range int32 values: the sums wrap, as ``.at[].add`` does
    vals = rng.integers(-2**31, 2**31, (ticks, shards, BATCH, COLS),
                        dtype=np.int64).astype(np.int32)
    return keys, vals


def _get_keys(keys, n_keys: int, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed + 2)
    seen = rng.choice(keys.reshape(-1), GETS // 2)
    fresh = rng.integers(0, n_keys, GETS - GETS // 2)
    return np.concatenate([seen, fresh]).astype(np.int64)


def store_phase(dev, seed: int) -> str:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.apps.sharded import build_mesh, mesh_spmd
    from repro.serve import BatchedFrontend, KVConfig, ShardedKV

    spmd = mesh_spmd(build_mesh(1))
    stats = dev.memory_stats()
    budget = stats["bytes_limit"] - stats["bytes_in_use"]
    store = compiled = None
    for log2 in range(MAX_KEYS_LOG2, MIN_KEYS_LOG2 - 1, -1):
        cfg = KVConfig(n_keys=1 << log2, cols=COLS, dtype=jnp.int32)
        cand = ShardedKV(cfg, 1, spmd)
        lowered = spmd.lower(cand.raw_tick_fn(),
                             *_shard_major(cand.tick_arg_specs(BATCH), 1),
                             donate=cand.donate_argnums)
        try:
            cc = lowered.compile()
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            cc = None  # the compiler found it larger than the chip
        if cc is not None and _live_bytes(cc.memory_analysis()) <= 0.9 * budget:
            store, compiled = cand, cc
            break
        del cand
    check(store is not None,
          f"no tick of 2^{MIN_KEYS_LOG2}+ keys fits {budget} bytes")
    check("tpu_custom_call" in compiled.as_text(),
          "the compiled tick has no tpu_custom_call (cscatter not compiled)")
    R = store.config.n_keys
    ma = compiled.memory_analysis()
    table_device_bytes = store.settled.on_device_size_in_bytes()

    keys, vals = _stream(R, TICKS, 1, seed)
    fe = BatchedFrontend(store, slots_per_shard=BATCH)
    t0 = time.perf_counter()
    for t in range(TICKS):
        for k, v in zip(keys[t, 0], vals[t, 0]):
            fe.add(int(k), v)
        fe.step()
    jax.block_until_ready(store.settled)
    ingest_s = time.perf_counter() - t0
    oracle = _oracle_add(np.zeros((R, COLS), np.int32), keys.reshape(-1),
                         vals.reshape(-1, COLS))

    gk = _get_keys(keys, R, seed)
    rids = [fe.get(int(k)) for k in gk]
    got = fe.drain()
    check(len(got) == GETS, f"{len(got)} of {GETS} gets answered")
    answers = np.stack([got[r] for r in rids])
    check(np.array_equal(answers, oracle[gk]),
          "gets differ from the numpy oracle")
    store.flush()
    table = store.table()
    check(np.array_equal(table, oracle),
          f"flushed table differs from the oracle in "
          f"{int((table != oracle).any(axis=1).sum())} rows")
    logical = R * COLS * 4
    return (f"keys=2^{R.bit_length() - 1} logical_bytes={logical} "
            f"table_device_bytes={table_device_bytes} "
            f"tick_argument_bytes={ma.argument_size_in_bytes} "
            f"tick_temp_bytes={ma.temp_size_in_bytes} "
            f"ticks={TICKS}x{BATCH} ingest_s={ingest_s} gets={GETS} "
            f"bitwise=True tpu_custom_call=True")


# ---------------------------------------------------------------- (c)


def apps_phase(seed: int) -> str:
    from repro.apps.sharded import run_app
    n, e = 1 << 16, 1 << 18
    bfs = run_app("bfs", 1, seed=seed, n_vertices=n, n_edges=e)
    check(bfs["eager_max_err"] == 0.0,
          f"BFS differs from the reference: max_err={bfs['eager_max_err']}")
    pr = run_app("pagerank", 1, seed=seed, n_vertices=n, n_edges=e)
    tol = 1e-4 / n  # 1e-4 of the mean rank
    check(pr["eager_max_err"] <= tol,
          f"PageRank max_err={pr['eager_max_err']} > {tol}")
    return (f"vertices={n} edges={e + n} bfs_max_err={bfs['eager_max_err']} "
            f"pagerank_max_err={pr['eager_max_err']} (tol {tol})")


# ---------------------------------------------------------------- (d)


def train_phase(dev, seed: int) -> str:
    import math
    import statistics
    from repro.launch.train import main as train_main
    steps = 4
    # the peak counts since the process started: the earlier phases' too
    peak_before = dev.memory_stats().get("peak_bytes_in_use")
    with tempfile.TemporaryDirectory() as ckpt:
        events = train_main(TRAIN_ARGV + ["--steps", str(steps),
                                          "--seed", str(seed),
                                          "--ckpt-dir", ckpt])
    losses = [e["loss"] for e in events]
    check(len(losses) == steps,
          f"{len(losses)} of {steps} steps logged (a step was rolled back)")
    check(all(math.isfinite(x) for x in losses), f"losses {losses}")
    step_s = statistics.median(e["dt"] for e in events[1:])
    peak = dev.memory_stats().get("peak_bytes_in_use")
    return (f"arch={ARCH} batch=8x256 steps={steps} "
            f"first_step_s={events[0]['dt']} step_s={step_s} "
            f"peak_bytes_in_use={peak} (before this phase: {peak_before}) "
            f"losses="
            + ",".join(str(x) for x in losses))


# ------------------------------------------------------- four chips


def four_store_phase(seed: int) -> str:
    import jax.numpy as jnp
    import numpy as np
    from repro.apps.sharded import build_mesh, mesh_spmd
    from repro.core.ccache import deferred_stages_of
    from repro.core.defer_schedule import DeferSchedule
    from repro.serve import BatchedFrontend, KVConfig, ShardedKV, serving_plan

    S, R, K = 4, 1 << MIN_KEYS_LOG2, 8
    spmd = mesh_spmd(build_mesh(S))
    sync = ShardedKV(KVConfig(n_keys=R, cols=COLS, dtype=jnp.int32), S,
                     spmd, plan=serving_plan(S, "none"))
    pcfg = KVConfig(n_keys=R, cols=COLS, dtype=jnp.int32, partitioned=True)
    plan = serving_plan(S, "all")
    names = tuple(s.name for s in deferred_stages_of(plan, S,
                                                     merge_fn=pcfg.merge))
    part = ShardedKV(pcfg, S, spmd, plan=plan,
                     schedule=DeferSchedule.fixed(K, names, overlap=True))
    ticks = TICKS + 3  # ends inside a commit cycle
    keys, vals = _stream(R, ticks, S, seed)
    for t in range(ticks):
        sync.tick(keys[t], vals[t])
        part.tick(keys[t], vals[t])
    oracle = _oracle_add(np.zeros((R, COLS), np.int32), keys.reshape(-1),
                         vals.reshape(-1, COLS))
    check(np.array_equal(sync.table(), oracle),
          "synchronized 4-shard table differs from the oracle")
    part.flush()
    check(np.array_equal(part.table(), oracle),
          "partitioned store's flushed table differs from the oracle")
    fe = BatchedFrontend(part, slots_per_shard=BATCH)
    gk = _get_keys(keys, R, seed)
    rids = [fe.get(int(k)) for k in gk]
    got = fe.drain()
    check(np.array_equal(np.stack([got[r] for r in rids]), oracle[gk]),
          "routed gets differ from the oracle")
    return (f"shards={S} keys=2^{MIN_KEYS_LOG2} commit_every={K} "
            f"overlap=True ticks={ticks}x{S}x{BATCH} gets={GETS} "
            f"bitwise=True (sync, partitioned, oracle)")


def _one_step(seed: int, extra: list) -> tuple[dict, dict]:
    """One step of the train CLI: its step event and the optimizer's first
    moment. After one AdamW step mu = (1 - b1) * g * min(1, c / |g|) for
    the merged gradient g, so mu and the event's grad_norm (|g|) pin g."""
    import numpy as np
    from repro.checkpoint import load_raw
    from repro.launch.train import main as train_main
    with tempfile.TemporaryDirectory() as ckpt:
        events = train_main(TRAIN_ARGV + [
            "--steps", "1", "--ckpt-every", "1", "--seed", str(seed),
            "--ckpt-dir", ckpt] + extra)
        leaves, _ = load_raw(ckpt)
    check(len(events) == 1, "the step was rolled back")
    mu = {k: v.astype(np.float32) for k, v in leaves.items()
          if k.startswith("opt/mu/")}
    check(mu, "no optimizer first moment in the checkpoint")
    return events[0], mu


def merge_errors(ref: tuple[dict, dict], got: tuple[dict, dict]) -> dict:
    """Relative distance of ``got``'s merged gradient from ``ref``'s: of
    the loss, of the gradient norm, and of mu in its worst leaf (its L2
    error over its own L2 norm)."""
    import numpy as np
    (ev0, mu0), (ev1, mu1) = ref, got
    worst, leaf = 0.0, None
    for k, a in mu0.items():
        d, norm = float(np.linalg.norm(mu1[k] - a)), float(np.linalg.norm(a))
        err = 0.0 if d == 0 else d / norm if norm else float("inf")
        if err >= worst:
            worst, leaf = err, k
    return {"loss_err": abs(ev1["loss"] - ev0["loss"]) / abs(ev0["loss"]),
            "grad_norm_err": (abs(ev1["grad_norm"] - ev0["grad_norm"])
                              / ev0["grad_norm"]),
            "worst_leaf_err": worst, "worst_leaf": leaf}


def four_train_phase(seed: int) -> str:
    implicit = _one_step(seed, [])
    planned = _one_step(seed, ["--merge-topology", MERGE_PLAN])
    errs = merge_errors(implicit, planned)
    for name, limit in MERGE_LIMITS.items():
        check(errs[name] <= limit,
              f"merge plan {MERGE_PLAN} vs implicit: {name}={errs[name]} "
              f"> {limit} ({errs})")
    return (f"dp=4 plan={MERGE_PLAN} loss={implicit[0]['loss']} "
            f"grad_norm_implicit={implicit[0]['grad_norm']} "
            f"grad_norm_merge_plan={planned[0]['grad_norm']} "
            + " ".join(f"{k}={v}" for k, v in errs.items())
            + f" (limits {MERGE_LIMITS})")


# ---------------------------------------------------------------- main


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--four-chips", action="store_true",
                   help="run only the 4-chip paths (a 2x2 host)")
    args = p.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}; run this script "
              f"from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path[:0] = [SRC, HERE]
    from repro.launch import compile_cache
    compile_cache.enable()
    try:
        dev, count = find_tpu()
        print(f"[device] ok: platform=tpu kind={dev.device_kind} "
              f"count={count}", flush=True)
        if args.four_chips:
            check(count == 4, f"--four-chips needs 4 chips, found {count}")
            phase("store-4", four_store_phase, args.seed)
            phase("train-4", four_train_phase, args.seed)
        else:
            phase("store", store_phase, dev, args.seed)
            phase("apps", apps_phase, args.seed)
            phase("train", train_phase, dev, args.seed)
    except PhaseError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
