"""Compile a cell's store programs for a described v5e, without the chip.

    JAX_PLATFORMS=cpu python3 bench/selftest/compile_rehearsal.py \
        <cell> [n_keys ...]

For each ``n_keys`` (default: the configuration's) it compiles every tick,
flush and read program of the cell's store at the cell's batch, on a
``v5e:2x2`` topology cut to the cell's chips, and prints each program's
bytes per chip from ``memory_analysis()``: live = arguments + outputs +
temporaries - aliased. Nothing runs; no time is measured.
"""

from __future__ import annotations

import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

HBM_FIT = 0.9 * 16e9   # 90% of one v5e chip's 16 GB


def programs(store, batch: int):
    """``(name, fn, per-shard arg specs, donated positions)``."""
    import jax
    import jax.numpy as jnp
    S = store.n_shards
    keys = jax.ShapeDtypeStruct((batch,), jnp.int32)
    if store.synchronized:
        yield "sync_tick", store.raw_tick_fn(), store.tick_arg_specs(batch), (0,)
        yield "read", store._read_fn, (store.tick_arg_specs(batch)[0], keys), ()
        return
    land_variants = (False, True) if store._overlap else (False,)
    for land in land_variants:
        for due in store.supported_dues:
            name = f"tick_due{due}" + ("_land" if land else "")
            donate = store.donate_argnums + ((2,) if land else ())
            yield (name, store.raw_tick_fn(due, land),
                   store.tick_arg_specs(batch, land), donate)
    specs = store.tick_arg_specs(batch, land=store._overlap)
    settled, ring = specs[0], specs[1]
    yield "flush", store._flush_fn, (settled, ring), (0, 1)
    if store._overlap:
        yield ("flush_land", store._flush_land_fn, (settled, ring, specs[2]),
               (0, 1, 2))
    yield "read", store._read_fns["plain"], (settled, keys), ()


def main(argv) -> int:
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from bench import harness, kvstore

    cell = harness.load_cell(argv[0])
    cfg = cell.config
    sizes = [int(x) for x in argv[1:]] or [cfg["n_keys"]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    S = cfg["shards"]
    devices = list(topo.devices)[:S]
    mesh = Mesh(np.asarray(devices), ("shards",))
    shard = NamedSharding(mesh, P("shards"))
    for n_keys in sizes:
        store = kvstore.make_store(dict(cfg, n_keys=n_keys), devices)
        # the store's programs pick the Pallas kernel only on a TPU backend
        real_backend = jax.default_backend
        jax.default_backend = lambda: "tpu"
        try:
            for name, fn, specs, donate in programs(store,
                                                    cfg["slots_per_shard"]):
                args = jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct((S,) + s.shape, s.dtype,
                                                   sharding=shard), specs)
                try:
                    compiled = store.spmd.lower(fn, *args,
                                                donate=donate).compile()
                except jax.errors.JaxRuntimeError as e:
                    print(f"n_keys=2^{n_keys.bit_length() - 1} {name}: "
                          f"refused: {str(e).splitlines()[0][:200]}")
                    continue
                ma = compiled.memory_analysis()
                live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                        + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
                print(f"n_keys=2^{n_keys.bit_length() - 1} {name}: "
                      f"live={live} args={ma.argument_size_in_bytes} "
                      f"out={ma.output_size_in_bytes} "
                      f"temp={ma.temp_size_in_bytes} "
                      f"alias={ma.alias_size_in_bytes} "
                      f"fits_90pct={live <= HBM_FIT} "
                      f"kernel={'tpu_custom_call' in compiled.as_text()}",
                      flush=True)
        finally:
            jax.default_backend = real_backend
        del store
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
