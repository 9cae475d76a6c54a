"""Blocked on-demand privatization engine vs. the serialization oracle."""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import blocked
from repro.core.merge_functions import ADD, MAX
from repro.kernels import ref


@given(seed=st.integers(0, 2**31 - 1),
       ways=st.sampled_from([2, 4, 8]),
       block_rows=st.sampled_from([2, 4]))
@settings(max_examples=15, deadline=None)
def test_cop_scatter_plus_flush_equals_oracle(seed, ways, block_rows):
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    rows_total, cols, n = 32, 4, 48
    table = jax.random.normal(k1, (rows_total, cols))
    rows = jax.random.randint(k2, (n,), 0, rows_total)
    vals = jax.random.normal(k3, (n, cols))

    cache = blocked.init_cache(ways, block_rows, cols, table.dtype)
    cache, t2 = blocked.cop_scatter(cache, table, rows, vals, ADD)
    cache, t2 = blocked.flush(cache, t2, ADD)

    gold = ref.ref_cscatter_serial(table, rows, vals, "add")
    np.testing.assert_allclose(np.asarray(t2), np.asarray(gold),
                               rtol=1e-5, atol=1e-5)
    s = blocked.stats(cache)
    assert s["total_merges"] >= 1
    assert s["evict_merges"] + s["silent_evicts"] >= 0


def test_c_read_row_sees_private_copy():
    table = jnp.zeros((8, 2))
    cache = blocked.init_cache(ways=2, block_rows=2, cols=2,
                               dtype=table.dtype)
    cache, table = blocked.cop_scatter(
        cache, table, jnp.asarray([3]), jnp.ones((1, 2)), ADD)
    # memory copy untouched before flush; private read sees the update
    assert float(table[3, 0]) == 0.0
    assert float(blocked.c_read_row(cache, table, jnp.asarray(3))[0]) == 1.0


def test_c_read_row_miss_and_post_flush():
    """Miss path: a row with no resident block reads straight from the
    memory table.  After ``flush`` the residency is drained, so the same
    read comes from the (now merged) table — and stays correct when the
    way is refilled by a different block."""
    table = jnp.asarray(np.arange(16, dtype=np.float32).reshape(8, 2))
    cache = blocked.init_cache(ways=2, block_rows=2, cols=2,
                               dtype=table.dtype)
    # miss everywhere: reads == memory rows
    for r in (0, 5, 7):
        np.testing.assert_array_equal(
            np.asarray(blocked.c_read_row(cache, table, jnp.asarray(r))),
            np.asarray(table[r]))

    cache, table = blocked.cop_scatter(
        cache, table, jnp.asarray([3]), jnp.full((1, 2), 10.0), ADD)
    # row 3 hits its private copy; row 5 (different block) still misses
    assert float(blocked.c_read_row(cache, table, jnp.asarray(3))[0]) == 16.0
    assert float(blocked.c_read_row(cache, table, jnp.asarray(5))[0]) == 10.0

    cache, table = blocked.flush(cache, table, ADD)
    # drained: the merged table now carries the update, reads agree
    assert float(table[3, 0]) == 16.0
    assert float(blocked.c_read_row(cache, table, jnp.asarray(3))[0]) == 16.0
    # refill the ways with other blocks: row 3 must read memory, not a
    # stale resident copy
    cache, table = blocked.cop_scatter(
        cache, table, jnp.asarray([0, 6]), jnp.ones((2, 2)), ADD)
    assert float(blocked.c_read_row(cache, table, jnp.asarray(3))[0]) == 16.0


def test_eviction_counters_fig9_shape():
    """More ways -> fewer evict-merges (merge-on-evict locality)."""
    table = jnp.zeros((64, 2))
    rows = jax.random.randint(jax.random.key(0), (128,), 0, 16)
    vals = jnp.ones((128, 2))

    def merges_for(ways):
        cache = blocked.init_cache(ways, 2, 2, table.dtype)
        cache, t = blocked.cop_scatter(cache, table, rows, vals, ADD)
        return blocked.stats(cache)["evict_merges"]

    assert merges_for(2) > merges_for(8)


def _ref_install_count(rows, ways, block_rows):
    """Independent model of the cache's fill policy: count block installs.

    Mirrors ``blocked.cop_scatter``'s victim selection exactly — hit way,
    else first free way, else LRU by clock (first minimum on ties) — but
    tracks only residency, no data. Every install under a write-only
    trace becomes a dirty way, and every dirty way drains through exactly
    one merge (evict or flush), so installs == total merges.
    """
    ids = [-1] * ways
    clock = [0] * ways
    installs = 0
    for tick, r in enumerate(rows):
        b = int(r) // block_rows
        if b in ids:
            way = ids.index(b)
        else:
            frees = [i for i, x in enumerate(ids) if x < 0]
            way = frees[0] if frees else min(range(ways),
                                             key=lambda i: clock[i])
            ids[way] = b
            installs += 1
        clock[way] = tick
    return installs


@given(seed=st.integers(0, 2**31 - 1),
       ways=st.sampled_from([2, 3, 4, 8]),
       block_rows=st.sampled_from([2, 4]),
       n=st.sampled_from([16, 48, 96]))
@settings(max_examples=12, deadline=None)
def test_property_counters_account_for_every_privatized_write(
        seed, ways, block_rows, n):
    """Counter conservation (Fig. 9's bookkeeping): across any access
    trace, ``n_flush_merges + n_evict_merges`` equals the number of
    privatized-block installs — every dirty block drains through exactly
    one merge, none twice, none dropped — and a write-only trace has zero
    silent evicts. The drained mass matches too: for ADD the final table
    equals the initial plus every scattered value regardless of the
    eviction pattern."""
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    rows_total, cols = 64, 2
    table = jax.random.normal(k1, (rows_total, cols))
    rows = jax.random.randint(k2, (n,), 0, rows_total)
    vals = jax.random.normal(k3, (n, cols))

    cache = blocked.init_cache(ways, block_rows, cols, table.dtype)
    cache, t2 = blocked.cop_scatter(cache, table, rows, vals, ADD)
    cache, t2 = blocked.flush(cache, t2, ADD)
    s = blocked.stats(cache)

    installs = _ref_install_count(np.asarray(rows), ways, block_rows)
    assert s["evict_merges"] + s["flush_merges"] == installs, (s, installs)
    assert s["silent_evicts"] == 0  # every access writes -> no clean ways

    # Zero update mass lost or double-counted through evict/flush merges.
    want = np.array(table)  # writable copy
    np.add.at(want, np.asarray(rows), np.asarray(vals))
    np.testing.assert_allclose(np.asarray(t2), want, rtol=1e-5, atol=1e-5)


def test_max_merge_through_cache():
    table = jnp.full((8, 1), -10.0)
    rows = jnp.asarray([1, 1, 5])
    vals = jnp.asarray([[3.0], [7.0], [-20.0]])
    cache = blocked.init_cache(2, 2, 1, table.dtype)
    cache, t = blocked.cop_scatter(cache, table, rows, vals, MAX)
    cache, t = blocked.flush(cache, t, MAX)
    assert float(t[1, 0]) == 7.0
    assert float(t[5, 0]) == -10.0  # max(-10, -20)


@given(seed=st.integers(0, 2**31 - 1),
       ways=st.sampled_from([2, 4]),
       slots=st.sampled_from([8, 16]))
@settings(max_examples=10, deadline=None)
def test_spill_scatter_plus_drain_equals_oracle(seed, ways, slots):
    """Table-less privatization: cache + spill buffer hold the whole
    pending delta; draining both into an identity table reproduces the
    serialization oracle's delta."""
    k1, k2 = jax.random.split(jax.random.key(seed), 2)
    rows_total, block_rows, cols, n = 32, 4, 3, 48
    rows = jax.random.randint(k1, (n,), 0, rows_total)
    vals = jax.random.randint(k2, (n, cols), 0, 100).astype(jnp.int32)

    # slots >= n_blocks, so coalescing-by-block-id can never overflow
    cache = blocked.init_cache(ways, block_rows, cols, jnp.int32)
    spill = blocked.init_spill(slots, block_rows, cols, jnp.int32, ADD)
    cache, spill = blocked.spill_scatter(cache, spill, rows, vals, ADD)
    assert int(spill.n_overflow) == 0

    delta = ADD.identity((rows_total, cols), jnp.int32)
    cache, delta = blocked.flush(cache, delta, ADD)
    spill, delta = blocked.spill_drain(spill, delta, ADD)

    gold = np.zeros((rows_total, cols), np.int64)
    np.add.at(gold, np.asarray(rows), np.asarray(vals, np.int64))
    np.testing.assert_array_equal(np.asarray(delta, np.int64), gold)
    # drain resets the buffer for the next commit cycle
    assert int(jnp.sum(spill.block_ids >= 0)) == 0


def test_spill_read_row_combines_resident_and_spilled_mass():
    """c_read_row semantics for the spill configuration: a row's pending
    delta is the resident way's delta plus any spilled mass, identity
    when neither holds it."""
    cache = blocked.init_cache(ways=1, block_rows=2, cols=2,
                               dtype=jnp.int32)
    spill = blocked.init_spill(4, block_rows=2, cols=2, dtype=jnp.int32,
                               merge=ADD)
    # row 0 and row 4 live in different blocks; ways=1 forces the first
    # block to spill when the second arrives
    rows = jnp.asarray([0, 0, 4])
    vals = jnp.asarray([[1, 2], [10, 20], [7, 7]], jnp.int32)
    cache, spill = blocked.spill_scatter(cache, spill, rows, vals, ADD)
    assert int(spill.n_spills) == 1

    got0 = blocked.spill_read_row(cache, spill, jnp.asarray(0), ADD)
    got4 = blocked.spill_read_row(cache, spill, jnp.asarray(4), ADD)
    got2 = blocked.spill_read_row(cache, spill, jnp.asarray(2), ADD)
    np.testing.assert_array_equal(np.asarray(got0), [11, 22])  # spilled
    np.testing.assert_array_equal(np.asarray(got4), [7, 7])    # resident
    np.testing.assert_array_equal(np.asarray(got2), [0, 0])    # identity
