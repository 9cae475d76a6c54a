"""The traffic generator: deterministic by seed, routed by key or by client,
and YCSB's scrambled zipfian where the mix asks for it."""

import numpy as np
import pytest

from bench import generate as gen
from bench import harness

THETA, ZETAN, ITEMS = 0.99, 26.46902820178302, 10_000_000_000


def fnv_py(v: int) -> int:
    """YCSB's ``Utils.fnvhash64``, one Python int at a time."""
    h = 0xCBF29CE484222325
    for i in range(8):
        h ^= (v >> (8 * i)) & 0xFF
        h = (h * 1099511628211) % 2**64
    if h >= 2**63:
        h -= 2**64
    return abs(h)


def mix(name):
    return harness.load_cell(name).traffic


@pytest.mark.parametrize("cell", ["kv1.ingest-uniform", "kv4.ingest-zipf"])
def test_same_seed_same_stream_other_seed_other_stream(cell):
    traffic = dict(mix(cell), block_ticks=4)
    big = 2**31 + 12345   # seeds run past 32 signed bits
    a = gen.generate(traffic, 1 << 16, 4, 4, 64, big)
    b = gen.generate(traffic, 1 << 16, 4, 4, 64, big)
    c = gen.generate(traffic, 1 << 16, 4, 4, 64, big + 1)
    for s in range(4):
        assert np.array_equal(a.keys[s], b.keys[s])
        assert np.array_equal(a.vals[s], b.vals[s])
        assert not np.array_equal(a.keys[s], c.keys[s])
        assert a.keys[s].dtype == np.int32 and a.vals[s].dtype == np.int32
        assert a.keys[s].shape == (4 * 64,)
        assert a.vals[s].shape == (4 * 64, 4)


@pytest.mark.parametrize("cell", ["kv1.ingest-uniform", "kv4.ingest-zipf"])
def test_each_shard_gets_its_own_keys(cell):
    st = gen.generate(dict(mix(cell), block_ticks=4), 1 << 16, 4, 4, 64, 3,
                      by_key=True)
    for s in range(4):
        assert (st.keys[s] % 4 == s).all()
        assert ((st.keys[s] >= 0) & (st.keys[s] < 1 << 16)).all()


@pytest.mark.parametrize("cell", ["kv1.ingest-uniform", "kv4.ingest-zipf"])
def test_a_client_per_shard_sends_to_the_whole_key_space(cell):
    traffic = dict(mix(cell), block_ticks=4)
    st = gen.generate(traffic, 1 << 16, 4, 4, 64, 3, by_key=False)
    again = gen.generate(traffic, 1 << 16, 4, 4, 64, 3, by_key=False)
    for s in range(4):
        assert np.array_equal(st.keys[s], again.keys[s])
        assert st.keys[s].shape == (4 * 64,) and st.keys[s].dtype == np.int32
        assert set(np.unique(st.keys[s] % 4)) == {0, 1, 2, 3}
        assert ((st.keys[s] >= 0) & (st.keys[s] < 1 << 16)).all()


def test_uniform_keys_cover_the_table_evenly():
    rng = gen.rng_for(5, 1)
    keys = gen.draw_keys({"dist": "uniform"}, rng, 1 << 20, 1 << 8)
    counts = np.bincount(keys, minlength=1 << 8)
    # 4096 expected per key: every count within 6 sigma
    assert np.abs(counts - 4096).max() < 6 * 64


def test_values_use_every_bit_of_an_int32():
    v = gen.draw_vals({"dist": "int32_full"}, gen.rng_for(1, 2), 1 << 16, 4)
    assert v.min() < -2**30 and v.max() > 2**30
    assert v.dtype == np.int32


def test_fnv_hash_is_ycsbs():
    xs = [0, 1, 2, 255, 256, 12345, ITEMS - 1, 2**40 + 7]
    got = gen.fnv1a64(np.asarray(xs, np.int64))
    assert [int(g) for g in got] == [fnv_py(x) for x in xs]


def test_zipf_rank_frequencies():
    n = 4_000_000
    r = gen.zipfian_ranks(gen.rng_for(11, 1), n, ITEMS, THETA, ZETAN)
    assert r.min() == 0 and r.max() < ITEMS
    freq = np.bincount(r[r < 64], minlength=64) / n
    zipf = 1.0 / np.arange(1, 65) ** THETA / ZETAN
    # ranks 0 and 1 are exact in Gray et al.'s method
    for k in (0, 1):
        sigma = np.sqrt(zipf[k] / n)
        assert abs(freq[k] - zipf[k]) < 5 * sigma
    # later ranks follow the method's closed form, u(k+1) - u(k), with
    # u(m) = ((m / N)^(1 - theta) + eta - 1) / eta
    eta = (1 - (2 / ITEMS) ** (1 - THETA)) / (1 - (1 + 0.5 ** THETA) / ZETAN)

    def u(m):
        return max(((m / ITEMS) ** (1 - THETA) + eta - 1) / eta,
                   (1 + 0.5 ** THETA) / ZETAN)

    for k in range(2, 64):
        want = u(k + 1) - u(k)
        assert abs(freq[k] - want) < 5 * np.sqrt(want / n), k
    # and stay within a quarter of the true zipf there
    assert np.all(np.abs(freq[2:] / zipf[2:] - 1) < 0.25)


def test_scrambling_puts_rank_zero_on_its_hashed_key():
    n_keys = 1 << 23
    keys = gen.draw_keys(mix("kv4.ingest-zipf")["keys"], gen.rng_for(2, 1),
                         1 << 20, n_keys)
    hot = np.bincount(keys).argmax()
    assert hot == fnv_py(0) % n_keys
    assert (keys == hot).mean() == pytest.approx(1 / ZETAN, rel=0.03)


def test_consumed_cycles_through_the_block():
    st = gen.generate(dict(mix("kv1.ingest-uniform"), block_ticks=2),
                      1 << 10, 4, 1, 8, 1)
    L = st.length
    assert L == 16
    parts = gen.consumed(st, 0, 2 * L + 5)
    assert [p[2] for p in parts] == [2, 1]
    assert np.array_equal(parts[1][0], st.keys[0][:5])
    assert gen.consumed(st, 0, 0) == []
