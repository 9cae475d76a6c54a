"""Property tests for the permutation builders (`repro.core.permutes`).

Every builder must return a *full permutation* of the axis (each rank
exactly once as source and as target — vmap's ppermute contract), with
identity self-pairs only where the round intends a rank to sit out, and
must reject geometries it silently mangled before (non-power-of-two sizes
where XOR pairing is assumed, blocks that do not tile the axis).
"""

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import permutes


def assert_bijection(perm, size):
    srcs = [s for s, _ in perm]
    tgts = [t for _, t in perm]
    assert sorted(srcs) == list(range(size)), "every rank a source once"
    assert sorted(tgts) == list(range(size)), "every rank a target once"


def fixed_points(perm):
    return {s for s, t in perm if s == t}


# ---------------------------------------------------------------------------
# butterfly
# ---------------------------------------------------------------------------


@settings(max_examples=24, deadline=None)
@given(logsize=st.integers(min_value=1, max_value=6),
       logstep=st.integers(min_value=0, max_value=5))
def test_butterfly_bijection_no_fixed_points(logsize, logstep):
    size, step = 1 << logsize, 1 << logstep
    if step >= size:
        return
    perm = permutes.butterfly_perms(size, step)
    assert_bijection(perm, size)
    # XOR pairing moves every rank: a fixed point would self-combine and
    # double-count its contribution.
    assert not fixed_points(perm)
    # involution: partners pair mutually
    assert all((t, s) in set(map(tuple, perm)) for s, t in perm)


def test_butterfly_rejects_untileable_geometry():
    with pytest.raises(ValueError, match="divide"):
        permutes.butterfly_perms(6, 2)  # rank 5 ^ 2 = 7 would leave the axis
    with pytest.raises(ValueError, match="power of two"):
        permutes.butterfly_perms(8, 3)
    permutes.butterfly_perms(12, 1)  # blocks of 2 tile 12: fine


# ---------------------------------------------------------------------------
# ring
# ---------------------------------------------------------------------------


@settings(max_examples=24, deadline=None)
@given(groups=st.integers(min_value=1, max_value=5),
       group=st.integers(min_value=1, max_value=7))
def test_ring_bijection_rotates_groups(groups, group):
    size = groups * group
    perm = permutes.ring_perm(size, group)
    assert_bijection(perm, size)
    if group == 1:
        assert len(fixed_points(perm)) == size
    else:
        assert not fixed_points(perm)
        # each rank's target stays inside its aligned group
        assert all(s // group == t // group for s, t in perm)


def test_ring_rejects_partial_group():
    with pytest.raises(ValueError, match="divide"):
        permutes.ring_perm(10, 3)


# ---------------------------------------------------------------------------
# representative / lane exchanges
# ---------------------------------------------------------------------------


@settings(max_examples=24, deadline=None)
@given(stride=st.sampled_from([1, 2, 3, 4]),
       fanout=st.sampled_from([2, 3, 4, 5, 8]),
       blocks=st.integers(min_value=1, max_value=3))
def test_rep_exchange_bijection_and_rep_only_motion(stride, fanout, blocks):
    size = stride * fanout * blocks
    perms = permutes.rep_exchange_perms(size, stride, fanout)
    expected_rounds = (max(fanout.bit_length() - 1, 0)
                       if permutes.is_pow2(fanout) else 1)
    assert len(perms) == expected_rounds
    for perm in perms:
        assert_bijection(perm, size)
        for s, t in perm:
            if s % stride != 0:
                assert s == t, "non-representatives must ride self-pairs"
            else:
                assert t % stride == 0, "reps exchange only with reps"
                assert (s // (stride * fanout)) == (t // (stride * fanout)), \
                    "exchange stays inside the block"
                if fanout > 1:
                    assert s != t, "reps always move"


@settings(max_examples=24, deadline=None)
@given(stride=st.sampled_from([1, 2, 4]),
       fanout=st.sampled_from([2, 3, 4, 8]),
       blocks=st.integers(min_value=1, max_value=3))
def test_lane_exchange_bijection_same_lane_pairing(stride, fanout, blocks):
    size = stride * fanout * blocks
    perms = permutes.lane_exchange_perms(size, stride, fanout)
    for perm in perms:
        assert_bijection(perm, size)
        # every rank participates (fanout > 1 means no fixed points), always
        # with the same lane of a sibling unit in the same block
        assert not fixed_points(perm)
        for s, t in perm:
            assert s % stride == t % stride, "same-lane pairing"
            assert (s // (stride * fanout)) == (t // (stride * fanout))


def test_exchange_builders_reject_untileable_blocks():
    for builder in (permutes.rep_exchange_perms,
                    permutes.lane_exchange_perms):
        with pytest.raises(ValueError, match="divide"):
            builder(10, 2, 2)  # block of 4 does not tile 10


# ---------------------------------------------------------------------------
# broadcast / gather
# ---------------------------------------------------------------------------


@settings(max_examples=24, deadline=None)
@given(group=st.sampled_from([2, 3, 4, 5, 8]),
       blocks=st.integers(min_value=1, max_value=3))
def test_binomial_broadcast_rounds_are_bijections_and_cover(group, blocks):
    size = group * blocks
    rounds = permutes.binomial_broadcast_perms(size, group)
    ks = [k for k, _ in rounds]
    assert ks == [1 << i for i in range(len(ks))]
    # Simulate the caller's selection (lanes >= k take the received value):
    # after the last round every lane must hold lane 0's value.
    has = [i % group == 0 for i in range(size)]
    for k, perm in rounds:
        assert_bijection(perm, size)
        recv = [False] * size
        for s, t in perm:
            assert s // group == t // group, "broadcast stays in the group"
            recv[t] = has[s]
        has = [has[i] if i % group < k else recv[i] for i in range(size)]
    assert all(has), f"broadcast left lanes uncovered: {has}"


def test_binomial_broadcast_rejects_partial_group():
    with pytest.raises(ValueError, match="divide"):
        permutes.binomial_broadcast_perms(10, 4)


@settings(max_examples=16, deadline=None)
@given(logstride=st.integers(min_value=0, max_value=3),
       blocks=st.integers(min_value=1, max_value=3))
def test_lane_gather_doubling_bijections(logstride, blocks):
    stride = 1 << logstride
    size = stride * blocks
    perms = permutes.lane_gather_doubling_perms(size, stride)
    assert len(perms) == logstride
    for perm in perms:
        assert_bijection(perm, size)
        assert not fixed_points(perm)
        for s, t in perm:
            assert s // stride == t // stride, "gather stays inside the unit"


def test_lane_gather_rejects_non_pow2_stride():
    """The doubling gather assumes XOR lane pairing; non-power-of-two units
    must fail loudly (callers fall back to ring_perm)."""
    with pytest.raises(ValueError, match="power of two"):
        permutes.lane_gather_doubling_perms(12, 3)
    with pytest.raises(ValueError, match="divide"):
        permutes.lane_gather_doubling_perms(10, 4)
