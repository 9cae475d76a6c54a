"""The store's scatter kernel compiles for a described TPU v5e chip.

Nothing runs here: the TPU compiler, installed with jaxlib, compiles for a
``v5e:2x2`` topology that is described, not attached. Shapes are the
store's real widths (a 2^22 x 4 table, 1024 updates per tick) and the tile
is the one ``cscatter`` chooses by default. The topology is described
inside a fixture (never at import), and the persistent compilation cache is
off around the compiles: entries written for a chip that is not attached
cannot be read back here.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cscatter import cscatter

ROWS, COLS, BATCH = 1 << 22, 4, 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("kind,dtype", [
    ("add", jnp.int32),
    ("add", jnp.float32),
    ("max", jnp.int32),
    ("min", jnp.int32),
    ("or", jnp.int32),
])
def test_cscatter_compiles_for_v5e(one_chip, no_persistent_cache, kind,
                                   dtype):
    table = jax.ShapeDtypeStruct((ROWS, COLS), dtype, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((BATCH,), jnp.int32, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((BATCH, COLS), dtype, sharding=one_chip)
    compiled = jax.jit(
        lambda t, i, v: cscatter(t, i, v, kind=kind, interpret=False)
    ).lower(table, ids, vals).compile()
    assert "tpu_custom_call" in compiled.as_text()
