"""Pallas kernel sweeps vs. the pure-jnp oracles (interpret=True on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.cscatter import (VMEM_BUDGET, choose_tile, cscatter,
                                     tile_bytes, visits)


TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kind", ["add", "max", "min", "sat_add"])
@pytest.mark.parametrize("r,d,n,br,ch", [
    (64, 8, 128, 16, 32),
    (128, 32, 256, 32, 64),
    (256, 16, 64, 256, 64),   # single table block
    (32, 128, 512, 8, 512),   # single chunk
])
def test_cscatter_sweep(dtype, kind, r, d, n, br, ch):
    table = jax.random.normal(jax.random.key(0), (r, d)).astype(dtype)
    ids = jax.random.randint(jax.random.key(1), (n,), -3, r)
    vals = jax.random.normal(jax.random.key(2), (n, d)).astype(dtype)
    out = cscatter(table, ids, vals, kind=kind, block_rows=br, chunk=ch,
                   sat_min=-2.0, sat_max=2.0)
    gold = ref.ref_cscatter(table, ids, vals, kind, -2.0, 2.0)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(gold, np.float32),
        rtol=TOL[dtype], atol=TOL[dtype] * 8)


@pytest.mark.parametrize("kind", ["add", "max", "min", "or"])
@pytest.mark.parametrize("r,n,d", [
    (1 << 22, 1024, 4), (1 << 16, 327680, 1), (48, 160, 1), (1000, 7, 4),
    (8191, 64, 2), (4096, 1024, 256)])
def test_choose_tile_fits_budget(kind, r, n, d):
    """The default tile never exceeds its VMEM budget, is the whole table
    or a multiple of 8 rows, and its chunk is lane-aligned; a tile that
    does not divide the table is the largest that fits (the table pads)."""
    br, ch = choose_tile(kind, r, n, d)
    assert ch % 128 == 0 and ch >= 128
    assert br == r or br % 8 == 0
    assert tile_bytes(kind, br, ch, d) <= VMEM_BUDGET
    if r % br:
        assert tile_bytes(kind, br + 8, ch, d) > VMEM_BUDGET


def test_cscatter_default_tile_int_add_wraps_bitwise():
    """int32 ADD through the byte-plane matmul equals ``.at[].add``
    bitwise, wrap-around included, with the default tile and a table whose
    row count has no tile divisor (8191 rows: padded internally)."""
    rng = np.random.default_rng(0)
    r, d, n = 8191, 4, 700
    table = rng.integers(-2**31, 2**31, (r, d)).astype(np.int32)
    ids = rng.integers(-3, r + 3, n).astype(np.int32)
    vals = rng.integers(-2**31, 2**31, (n, d)).astype(np.int32)
    out = cscatter(jnp.asarray(table), jnp.asarray(ids), jnp.asarray(vals))
    ok = (ids >= 0) & (ids < r)
    gold = jnp.asarray(table).at[ids[ok]].add(jnp.asarray(vals[ok]))
    assert jnp.array_equal(out, gold)


def test_cscatter_or_int():
    table = jnp.zeros((64, 8), jnp.int32)
    ids = jax.random.randint(jax.random.key(1), (128,), 0, 64)
    vals = jax.random.randint(jax.random.key(2), (128, 8), 0, 2**30)
    out = cscatter(table, ids, vals, kind="or", block_rows=16, chunk=32)
    gold = ref.ref_cscatter_serial(table, ids, vals, "or")
    assert jnp.array_equal(out, gold)


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.uint32])
def test_cscatter_min_int(dtype):
    """MIN's identity must be the dtype's max — iinfo covers unsigned,
    where a float-inf or signed sentinel would corrupt untouched rows."""
    table = jnp.full((64, 8), jnp.iinfo(dtype).max, dtype)
    ids = jax.random.randint(jax.random.key(1), (128,), -3, 64)
    vals = jax.random.randint(
        jax.random.key(2), (128, 8), 0, 2**31 - 1).astype(dtype)
    if dtype == jnp.uint32:
        vals = vals * 2  # exercise values above int32 range
    out = cscatter(table, ids, vals, kind="min", block_rows=16, chunk=32)
    gold = ref.ref_cscatter_serial(table, ids, vals, "min")
    assert jnp.array_equal(out, gold)


@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_cscatter_matches_serialization_property(seed):
    """Privatize-and-merge == *some serialization* of the COp stream (the
    paper's correctness contract), for the additive merge."""
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    table = jax.random.normal(k1, (32, 4))
    ids = jax.random.randint(k2, (64,), 0, 32)
    vals = jax.random.normal(k3, (64, 4))
    out = cscatter(table, ids, vals, kind="add", block_rows=8, chunk=16)
    gold = ref.ref_cscatter_serial(table, ids, vals, "add")
    np.testing.assert_allclose(np.asarray(out), np.asarray(gold),
                               rtol=1e-5, atol=1e-5)


def test_cscatter_untouched_rows_bit_exact():
    table = jax.random.normal(jax.random.key(0), (64, 8))
    ids = jnp.asarray([3, 3, 5], jnp.int32)
    vals = jnp.ones((3, 8))
    out = cscatter(table, ids, vals, kind="sat_add", block_rows=16,
                   chunk=3, sat_min=-0.5, sat_max=0.5)
    mask = jnp.zeros((64,), bool).at[jnp.asarray([3, 5])].set(True)
    assert jnp.array_equal(out[~mask], table[~mask])  # dirty-merge skip


# ------------------------------------------------- cscatter work list

WORK_LIST_CASES = ["all_invalid", "every_block", "one_block_many_chunks",
                   "unsorted_duplicates", "ragged_table"]


def _work_list_case(case, kind, rng):
    """``(table, ids, vals, block_rows, chunk)``: float32 for ``add`` and
    ``sat_add`` (saturating at +-2), full-range int32 for ``int_add`` (sums
    wrap) and the other kinds."""
    r, br, ch = 64, 16, 16
    if case == "all_invalid":
        ids = np.concatenate([rng.integers(-5, 0, 20),
                              rng.integers(r, r + 6, 20)])
    elif case == "every_block":
        br = 8
        ids = np.concatenate([rng.permutation(r), rng.integers(0, r, 64)])
    elif case == "one_block_many_chunks":
        # 64 updates of row 37 (block 2) among others: in the sorted
        # stream they span at least four chunks
        ids = np.concatenate([np.full(64, 37), rng.integers(0, r, 20)])
    elif case == "unsorted_duplicates":
        br = 8
        ids = rng.integers(0, 8, 50) * 8 + 3
    else:  # ragged_table: 70 rows in blocks of 16
        r = 70
        ids = rng.integers(-3, r + 3, 80)
    rng.shuffle(ids)
    n = len(ids)
    if kind in ("add", "sat_add"):
        table = rng.uniform(-1.9, 1.9, (r, 4)).astype(np.float32)
        vals = rng.normal(0, 0.5, (n, 4)).astype(np.float32)
        if case == "one_block_many_chunks":
            # row 37's updates sum to zero: +0.5 in its first 32, -0.5
            # after; a merge per chunk would saturate on the way
            table[37] = 1.5
            hits = np.flatnonzero(ids == 37)
            vals[hits[:32]], vals[hits[32:]] = 0.5, -0.5
    else:
        table = rng.integers(-2**31, 2**31, (r, 4), dtype=np.int64)
        vals = rng.integers(-2**31, 2**31, (n, 4), dtype=np.int64)
        table, vals = table.astype(np.int32), vals.astype(np.int32)
    return (jnp.asarray(table), jnp.asarray(ids.astype(np.int32)),
            jnp.asarray(vals), br, ch)


@pytest.mark.parametrize("kind", ["add", "sat_add", "int_add", "max", "min",
                                  "or"])
@pytest.mark.parametrize("case", WORK_LIST_CASES)
def test_cscatter_work_list_matches_serialization(case, kind):
    """The work-list grid equals the literal serialization, and every row
    of a block that no valid id touches is bitwise the input's (the block
    is never visited, the table updated in place)."""
    table, ids, vals, br, ch = _work_list_case(
        case, kind, np.random.default_rng(WORK_LIST_CASES.index(case)))
    kind = "add" if kind == "int_add" else kind
    out = cscatter(table, ids, vals, kind=kind, block_rows=br, chunk=ch,
                   sat_min=-2.0, sat_max=2.0)
    gold = ref.ref_cscatter_serial(table, ids, vals, kind, -2.0, 2.0)
    if jnp.issubdtype(table.dtype, jnp.floating):
        np.testing.assert_allclose(np.asarray(out), np.asarray(gold),
                                   rtol=TOL[jnp.float32],
                                   atol=TOL[jnp.float32] * 8)
    else:
        assert jnp.array_equal(out, gold)
    r = table.shape[0]
    ok = np.asarray((ids >= 0) & (ids < r))
    hit = np.zeros(-(-r // br), bool)
    hit[np.asarray(ids)[ok] // br] = True
    cold = ~np.repeat(hit, br)[:r]
    assert jnp.array_equal(out[cold], table[cold])
    if case == "all_invalid":
        assert jnp.array_equal(out, table)
    if case == "one_block_many_chunks":
        pos = np.flatnonzero(np.sort(np.asarray(ids)) // br == 37 // br)
        assert len(np.unique(pos // ch)) >= 3


@pytest.mark.parametrize("r,n,kind", [
    (1 << 16, 100, "add"),     # sparse: one chunk, ~100 blocks
    (1 << 18, 300, "add"),     # sparse: three chunks
    (1 << 16, 3000, "max"),    # dense: every block, the serial fold
    (64, 1000, "add"),         # one block: the chunk sweep
])
def test_cscatter_visits_counts_items(r, n, kind):
    """``visits`` is the number of non-empty (block, chunk) items of the
    sorted stream at the default tile (every chunk for a one-block table),
    and the kernel at that tile matches the serialization."""
    rng = np.random.default_rng(n)
    ids = rng.integers(-2, r + 2, n).astype(np.int32)
    br, ch = choose_tile(kind, r, n, 4)
    br = min(br, r)
    if br == r:
        expected = -(-n // ch)
    else:
        valid = np.sort(ids[(ids >= 0) & (ids < r)])
        pos = np.arange(len(valid))
        expected = len(set(zip(pos // ch, valid // br)))
    assert int(visits(jnp.asarray(ids), r, 4, kind)) == expected
    table = jnp.asarray(rng.integers(-2**31, 2**31, (r, 4), dtype=np.int64)
                        .astype(np.int32))
    vals = jnp.asarray(rng.integers(-2**31, 2**31, (n, 4), dtype=np.int64)
                       .astype(np.int32))
    out = cscatter(table, jnp.asarray(ids), vals, kind=kind)
    assert jnp.array_equal(
        out, ref.ref_cscatter_serial(table, jnp.asarray(ids), vals, kind))


# ---------------------------------------------------------------- cmerge


@pytest.mark.parametrize("kind", ["add", "max", "min", "sat_add"])
def test_cmerge_vs_ref(kind):
    r, d, w, br = 64, 16, 4, 8
    table = jax.random.normal(jax.random.key(0), (r, d))
    block_ids = jnp.asarray([5, -1, 0, 5 if False else 2], jnp.int32)
    dirty = jnp.asarray([1, 1, 0, 1], jnp.int32)
    src = jax.random.normal(jax.random.key(1), (w, br, d))
    upd = src + jax.random.normal(jax.random.key(2), (w, br, d))
    out = ops.merge_buffer(table, block_ids, dirty, src, upd, kind=kind,
                           sat_min=-3.0, sat_max=3.0)
    gold = ref.ref_cmerge(table, np.asarray(block_ids), np.asarray(dirty),
                          src, upd, kind, -3.0, 3.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(gold),
                               rtol=1e-5, atol=1e-5)


def test_cmerge_clean_ways_skipped():
    table = jax.random.normal(jax.random.key(0), (32, 4))
    src = jnp.zeros((2, 8, 4))
    upd = jnp.ones((2, 8, 4)) * 100        # would corrupt if merged
    out = ops.merge_buffer(table, jnp.asarray([0, 1], jnp.int32),
                           jnp.asarray([0, 0], jnp.int32), src, upd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(table))


# ------------------------------------------------------------- attention


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("h,kv", [(8, 8), (8, 2), (4, 1)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(dtype, h, kv, causal):
    b, s, d = 2, 128, 32
    q = jax.random.normal(jax.random.key(0), (b, h, s, d)).astype(dtype)
    k = jax.random.normal(jax.random.key(1), (b, kv, s, d)).astype(dtype)
    v = jax.random.normal(jax.random.key(2), (b, kv, s, d)).astype(dtype)
    out = ops.flash_attention(q, k, v, causal=causal, bq=32, bk=32)
    gold = ref.ref_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(gold, np.float32),
        rtol=TOL[dtype], atol=TOL[dtype] * 4)


@pytest.mark.parametrize("pos", [0, 1, 37, 127])
def test_decode_attention_positions(pos):
    b, h, kv, t, d = 2, 8, 2, 128, 32
    q = jax.random.normal(jax.random.key(0), (b, h, d))
    k = jax.random.normal(jax.random.key(1), (b, t, kv, d))
    v = jax.random.normal(jax.random.key(2), (b, t, kv, d))
    out = ops.decode_attention(q, k, v, jnp.asarray(pos), bk=32)
    gold = ref.ref_decode_attention(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(gold),
                               rtol=1e-5, atol=1e-5)


def test_embedding_grad_scatter_equals_autodiff():
    """The flagship use: cscatter reproduces the embedding-table gradient."""
    v, d, n = 64, 16, 256
    table = jax.random.normal(jax.random.key(0), (v, d))
    tok = jax.random.randint(jax.random.key(1), (n,), 0, v)
    tgt = jax.random.normal(jax.random.key(2), (n, d))

    def loss(tab):
        return jnp.sum((tab[tok] - tgt) ** 2)

    gold = jax.grad(loss)(table)
    out_grads = 2.0 * (table[tok] - tgt)
    got = ops.embedding_grad_scatter(jnp.zeros_like(table), tok, out_grads,
                                     block_rows=16, chunk=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(gold),
                               rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------- cscatter xor


def _numpy_xor(table, ids, vals):
    want = np.asarray(table).copy()
    i = np.asarray(ids)
    ok = (i >= 0) & (i < want.shape[0])
    np.bitwise_xor.at(want, i[ok], np.asarray(vals)[ok])
    return want


@pytest.mark.parametrize("case", WORK_LIST_CASES)
def test_cscatter_xor_matches_numpy(case):
    """The XOR kind (the serial fold over each block's own ids) and both
    jnp oracles equal ``np.bitwise_xor.at`` bit for bit; blocks no valid
    id touches are left as they were."""
    table, ids, vals, br, ch = _work_list_case(
        case, "xor", np.random.default_rng(40 + WORK_LIST_CASES.index(case)))
    out = cscatter(table, ids, vals, kind="xor", block_rows=br, chunk=ch)
    want = _numpy_xor(table, ids, vals)
    assert np.array_equal(np.asarray(out), want)
    assert np.array_equal(np.asarray(ref.ref_cscatter(table, ids, vals,
                                                      "xor")), want)
    assert np.array_equal(np.asarray(ref.ref_cscatter_serial(
        table, ids, vals, "xor")), want)
    r = table.shape[0]
    ok = np.asarray((ids >= 0) & (ids < r))
    hit = np.zeros(-(-r // br), bool)
    hit[np.asarray(ids)[ok] // br] = True
    cold = ~np.repeat(hit, br)[:r]
    assert jnp.array_equal(out[cold], table[cold])


def test_cscatter_xor_pairs_cancel_at_the_default_tile():
    """Every update sent twice cancels: the table comes back bit for bit,
    at the tile ``cscatter`` chooses for a 64-bit-word table."""
    rng = np.random.default_rng(3)
    r, n = 1 << 14, 600
    table = jnp.asarray(rng.integers(-2**31, 2**31, (r, 2)).astype(np.int32))
    ids = rng.integers(0, r, n).astype(np.int32)
    vals = rng.integers(-2**31, 2**31, (n, 2)).astype(np.int32)
    ids2, vals2 = np.concatenate([ids, ids[::-1]]), np.concatenate(
        [vals, vals[::-1]])
    out = cscatter(table, jnp.asarray(ids2), jnp.asarray(vals2), kind="xor")
    assert jnp.array_equal(out, table)
    out = cscatter(table, jnp.asarray(ids), jnp.asarray(vals), kind="xor")
    assert np.array_equal(np.asarray(out), _numpy_xor(table, ids, vals))
    br, ch = choose_tile("xor", r, n, 2)
    assert tile_bytes("xor", br, ch, 2) <= VMEM_BUDGET
    assert int(visits(jnp.asarray(ids), r, 2, "xor")) == int(
        visits(jnp.asarray(ids), r, 2, "or"))
