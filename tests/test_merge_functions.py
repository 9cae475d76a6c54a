"""Property tests for the merge-function algebra (the paper's §4.5 contract:
combine is commutative+associative, identity is neutral, apply observes the
memory copy)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import merge_functions as mf

FLOAT_MERGES = [mf.ADD, mf.MAX, mf.MIN, mf.saturating_add(5.0, -5.0)]
INT_MERGES = [mf.BITWISE_OR, mf.BITWISE_AND]

floats = st.lists(st.floats(-100, 100, allow_nan=False, width=32),
                  min_size=4, max_size=4)
ints = st.lists(st.integers(0, 2**20), min_size=4, max_size=4)


@pytest.mark.parametrize("m", FLOAT_MERGES, ids=lambda m: m.name)
@given(a=floats, b=floats, c=floats)
@settings(max_examples=25, deadline=None)
def test_combine_commutative_associative_float(m, a, b, c):
    a, b, c = (jnp.asarray(x, jnp.float32) for x in (a, b, c))
    ab = m.combine(a, b)
    ba = m.combine(b, a)
    np.testing.assert_allclose(ab, ba, rtol=1e-6)
    abc1 = m.combine(m.combine(a, b), c)
    abc2 = m.combine(a, m.combine(b, c))
    np.testing.assert_allclose(abc1, abc2, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("m", INT_MERGES, ids=lambda m: m.name)
@given(a=ints, b=ints, c=ints)
@settings(max_examples=25, deadline=None)
def test_combine_commutative_associative_int(m, a, b, c):
    a, b, c = (jnp.asarray(x, jnp.int32) for x in (a, b, c))
    assert jnp.array_equal(m.combine(a, b), m.combine(b, a))
    assert jnp.array_equal(m.combine(m.combine(a, b), c),
                           m.combine(a, m.combine(b, c)))


@pytest.mark.parametrize("m", FLOAT_MERGES + INT_MERGES,
                         ids=lambda m: m.name)
def test_identity_neutral(m):
    dtype = jnp.int32 if m in INT_MERGES else jnp.float32
    x = jnp.asarray([1, 2, 3, -4] if dtype == jnp.int32
                    else [1.0, -2.5, 3.25, 0.0], dtype)
    e = m.identity(x.shape, x.dtype)
    np.testing.assert_allclose(np.asarray(m.combine(x, e)), np.asarray(x))


@given(src=floats, upd=floats, mem=floats)
@settings(max_examples=25, deadline=None)
def test_add_delta_apply_semantics(src, upd, mem):
    """apply(mem, delta(src, upd)) == mem + (upd - src) for ADD."""
    src, upd, mem = (jnp.asarray(x, jnp.float32) for x in (src, upd, mem))
    out = mf.ADD.apply(mem, mf.ADD.delta(src, upd))
    np.testing.assert_allclose(np.asarray(out), np.asarray(mem + upd - src),
                               rtol=1e-5, atol=1e-5)


def test_saturating_apply_observes_memory():
    """Paper §4.5: saturation thresholds must see the memory copy."""
    m = mf.saturating_add(10.0)
    mem = jnp.asarray([9.0, 3.0])
    u = jnp.asarray([5.0, 5.0])
    out = m.apply(mem, u)
    np.testing.assert_allclose(np.asarray(out), [10.0, 8.0])


def test_complex_mul_merge_roundtrip():
    m = mf.COMPLEX_MUL
    src = jnp.asarray([[1.0, 1.0]])     # 1 + i
    upd = jnp.asarray([[0.0, 2.0]])     # 2i  (core multiplied by (1+i))
    mem = jnp.asarray([[3.0, 0.0]])     # 3
    u = m.delta(src, upd)               # upd / src = (1 + i)
    out = m.apply(mem, u)               # 3 * (1+i) = 3+3i
    np.testing.assert_allclose(np.asarray(out), [[3.0, 3.0]], atol=1e-6)


def test_dropping_add_expected_fraction():
    m = mf.dropping_add(0.5)
    mem = jnp.zeros((10_000,))
    u = jnp.ones((10_000,))
    out = m.apply(mem, u, key=jax.random.key(0))
    frac = float(out.mean())
    assert 0.45 < frac < 0.55


def test_int8_codec_roundtrip_error():
    m = mf.int8_compressed_add()
    u = jnp.linspace(-3, 3, 64)
    dec = m.decode(m.encode(u))
    assert float(jnp.max(jnp.abs(dec - u))) <= 3 / 127 + 1e-6


# ------------------------------------------------------------ algebra traits


IDEMPOTENT = [mf.MAX, mf.MIN, mf.BITWISE_OR, mf.BITWISE_AND]
SCALABLE = [mf.ADD, mf.int8_compressed_add()]


@pytest.mark.parametrize("m", IDEMPOTENT, ids=lambda m: m.name)
@given(a=ints)
@settings(max_examples=25, deadline=None)
def test_idempotent_trait_holds(m, a):
    """Merges claiming ``idempotent`` must satisfy combine(a, a) == a —
    the property that licenses the re-apply settle mode."""
    assert m.idempotent
    x = jnp.asarray(a, jnp.int32)
    assert jnp.array_equal(m.combine(x, x), x)


@pytest.mark.parametrize("m", SCALABLE, ids=lambda m: m.name)
@given(a=floats, b=floats)
@settings(max_examples=25, deadline=None)
def test_scalable_trait_holds(m, a, b):
    """Merges claiming ``scalable`` must commute with scaling —
    s * (a ⊕ b) == (s * a) ⊕ (s * b) — the mean-settle contract."""
    assert m.scalable
    a, b = (jnp.asarray(x, jnp.float32) for x in (a, b))
    s = 0.125
    np.testing.assert_allclose(np.asarray(s * m.combine(a, b)),
                               np.asarray(m.combine(s * a, s * b)),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("m", [mf.ADD, mf.MUL, mf.COMPLEX_MUL],
                         ids=lambda m: m.name)
def test_invertible_trait_declared(m):
    assert m.invertible


def test_non_idempotent_merges_do_not_claim_it():
    assert not mf.ADD.idempotent
    assert not mf.saturating_add(5.0).idempotent


def test_stale_tolerant_and_settle_mode_derivation():
    assert mf.ADD.stale_tolerant and mf.ADD.settle_mode() == "mean"
    assert mf.MIN.stale_tolerant and mf.MIN.settle_mode() == "reapply"
    assert not mf.COMPLEX_MUL.stale_tolerant
    assert mf.COMPLEX_MUL.settle_mode() is None
    assert mf.saturating_add(5.0).settle_mode() is None


def test_check_deferrable_and_overlap_enforcement():
    """Every algebra-invalid defer/overlap combo raises with a clear
    message; valid combos pass."""
    for m in (mf.ADD, mf.MIN, mf.BITWISE_OR, mf.COMPLEX_MUL):
        m.check_deferrable("ctx")  # homomorphic applies may defer
    with pytest.raises(ValueError, match="sat_add"):
        mf.saturating_add(5.0).check_deferrable("ctx")
    with pytest.raises(ValueError, match="drop_add"):
        mf.dropping_add(0.5).check_deferrable("ctx")
    for m in (mf.ADD, mf.MIN, mf.BITWISE_OR):
        m.check_overlap("ctx")  # stale-tolerant merges may overlap
    for m, pat in ((mf.COMPLEX_MUL, "complex_mul"), (mf.MUL, "mul"),
                   (mf.saturating_add(5.0), "sat_add")):
        with pytest.raises(ValueError, match=pat):
            m.check_overlap("ctx")


def test_compile_plan_rejects_defer_for_non_deferrable():
    from repro.core.merge_plan import MergePlan, compile_plan
    plan = MergePlan.parse("chip:2,host:2,pod:2:defer")
    sat = mf.saturating_add(5.0)
    with pytest.raises(ValueError, match="defer"):
        compile_plan(plan, 8, merge_fn=sat)
    compile_plan(plan, 8, merge_fn=mf.ADD)           # deferrable: fine
    compile_plan(MergePlan.parse("chip:2,host:2,pod:2"), 8,
                 merge_fn=sat)                       # no :defer: fine


def test_solve_defer_schedule_rejects_invalid_merges():
    from repro.core.defer_schedule import solve_defer_schedule
    from repro.core.merge_plan import MergePlan
    plan = MergePlan.parse("chip:2,host:2,pod:2:defer")
    bytes_lv = [1e6, 1e6, 1e6]
    names = ("chip", "host", "pod")
    with pytest.raises(ValueError, match="sat_add"):
        solve_defer_schedule(plan, bytes_lv, names,
                             merge_fn=mf.saturating_add(5.0))
    with pytest.raises(ValueError, match="complex_mul"):
        solve_defer_schedule(plan, bytes_lv, names, overlap=True,
                             merge_fn=mf.COMPLEX_MUL)
    solve_defer_schedule(plan, bytes_lv, names, merge_fn=mf.COMPLEX_MUL)
    solve_defer_schedule(plan, bytes_lv, names, overlap=True,
                         merge_fn=mf.ADD)


def test_registry_mfrf():
    reg = mf.default_registry()
    assert reg.id_of("add") == 0
    assert reg["add"] is mf.ADD
    assert reg[reg.id_of("or")] is mf.BITWISE_OR
    n = len(reg)
    reg.merge_init(mf.ADD)  # idempotent
    assert len(reg) == n
    small = mf.MergeFunctionRegistry(capacity=1)
    small.merge_init(mf.ADD)
    with pytest.raises(ValueError):
        small.merge_init(mf.MAX)


# ------------------------------------------------------------ XOR (HPCC)


def test_xor_traits_and_registration():
    """XOR is self-inverse: invertible and deferrable, neither idempotent
    (a ^ a == 0) nor scalable, so a deferred commit is exact but a
    one-step-stale overlapped landing is refused."""
    x = mf.BITWISE_XOR
    assert x.invertible and x.deferrable
    assert not x.idempotent and not x.scalable
    assert not x.stale_tolerant and x.settle_mode() is None
    x.check_deferrable("t")
    with pytest.raises(ValueError, match="stale"):
        x.check_overlap("t")
    assert mf.default_registry()["xor"] is x and x in mf.standard_merges()
    rng = np.random.default_rng(0)
    a, b = (jnp.asarray(rng.integers(-2**31, 2**31, (8, 2)), jnp.int32)
            for _ in range(2))
    zero = x.identity(a.shape, a.dtype)
    assert jnp.array_equal(x.combine(a, a), zero)
    assert jnp.array_equal(x.combine(a, zero), a)
    assert jnp.array_equal(x.combine(a, x.delta(a, b)), b)


@pytest.mark.parametrize("k", [1, 3, 8])
def test_xor_k_deferred_commits_equal_k_eager_merges(k):
    """K updates combined in a pending and applied once equal K applies,
    and numpy's XOR of the same words, bit for bit."""
    x = mf.BITWISE_XOR
    rng = np.random.default_rng(k)
    mem = rng.integers(-2**31, 2**31, (16, 2)).astype(np.int32)
    ups = rng.integers(-2**31, 2**31, (k, 16, 2)).astype(np.int32)
    eager = jnp.asarray(mem)
    pending = x.identity(mem.shape, jnp.int32)
    for u in ups:
        eager = x.apply(eager, jnp.asarray(u))
        pending = x.combine(pending, jnp.asarray(u))
    want = mem.copy()
    for u in ups:
        np.bitwise_xor(want, u, out=want)
    assert np.array_equal(np.asarray(eager), want)
    assert np.array_equal(np.asarray(x.apply(jnp.asarray(mem), pending)),
                          want)
