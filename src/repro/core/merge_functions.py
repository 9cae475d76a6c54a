"""Merge functions and the MFRF (merge function register file) analog.

The paper's central flexibility claim (vs. COUP's fixed in-protocol op set) is
that merges are *software-defined*: ``merge(src, upd, mem) -> mem'``. We factor
every merge into four algebraic pieces so the engine can both (a) run it as a
distributed tree-reduce with an arbitrary commutative combine and (b) defer /
locally coalesce updates (soft_merge):

    delta(src, upd)      -> u      what one core contributes
    combine(u1, u2)      -> u      associative + commutative coalescing
    apply(mem, u)        -> mem'   installs the combined update into memory
    identity(shape, dt)  -> u      neutral element of ``combine``

``apply`` sees the *memory* copy — this is the paper's §4.5 requirement that
value-dependent conditionals (e.g. saturation thresholds) observe memory, not
the update copy.

``xla_reduce`` names the fixed op ``combine`` is (add/mul/min/max/or/and/xor),
which the scatter kernel and XLA's fused collectives know by that name. Where
jax has a fused collective for it (psum/pmax/pmin), the exchange rides it:
the moral equivalent of COUP, fixed ops fused into the "protocol". Anything
else (or/and/xor, whose collectives jax lacks, and every merge without a fixed
op) runs on the CCache flexible path (a ppermute butterfly whose combine is
the merge's own function).

``encode``/``decode`` optionally compress the update for the wire (gradient
compression as a delta-merge property; beyond-paper, see DESIGN.md §3).

Algebra traits
--------------

Deferral and overlap reorder *when* combined updates reach memory, and that
is only sound for some algebras. Each ``MergeFn`` therefore carries traits
the engine checks at plan-compile / schedule-solve time (instead of the old
"the docs warn you" contract):

    idempotent   combine(a, a) == a — lattice joins (max/min/or/and). A
                 deferred commit settles by re-applying the join; seeing a
                 contribution twice (stale overlap landing) is harmless.
    scalable     scaling commutes with combine: combine(c*a, c*b) ==
                 c*combine(a, b). This is what makes delayed *mean*
                 semantics exist (divide one settled sum by the number of
                 contributions) — ADD and its compressed variants.
    invertible   every update has an inverse under combine (ADD/MUL/
                 COMPLEX_MUL; XOR, whose every update is its own). Lets
                 clients subtract their own contribution from a settled
                 aggregate (e.g. remote-mass extraction in the sharded
                 PageRank app).
    deferrable   apply is a homomorphism over combine:
                 apply(apply(m, u1), u2) == apply(m, combine(u1, u2)).
                 False when apply observes memory between commits
                 (saturating_add's threshold) or randomizes per commit
                 (dropping_add) — deferring K steps then applying once
                 changes what those applies observe.

``deferrable`` gates ``:defer`` levels outright; overlapped (one-step-stale)
commits additionally need ``scalable or idempotent`` so a late/duplicated
landing cannot corrupt memory.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

Array = jax.Array
PyTree = Any


@dataclasses.dataclass(frozen=True)
class MergeFn:
    """A software-defined commutative merge (one MFRF entry)."""

    name: str
    delta: Callable[[Array, Array], Array]
    combine: Callable[[Array, Array], Array]
    apply: Callable[..., Array]  # (mem, u, *, key=None) -> mem'
    identity: Callable[[tuple, Any], Array]
    xla_reduce: Optional[str] = None  # {"add","mul","min","max","or","and","xor"}
    encode: Optional[Callable[[Array], PyTree]] = None
    decode: Optional[Callable[[PyTree], Array]] = None
    needs_key: bool = False  # apply wants a PRNG key (approximate merges)
    # Contiguous trailing elements ``combine`` treats as one value (2 for
    # complex real/imag pairs). The lane-parallel hierarchical exchange
    # splits payloads on atom boundaries so structured combines see whole
    # elements.
    wire_atom: int = 1
    # Algebra traits (see module docstring): engine-enforced validity for
    # :defer levels, schedule-solved K, and overlapped stale commits.
    idempotent: bool = False   # combine(a, a) == a
    scalable: bool = False     # combine(c*a, c*b) == c*combine(a, b)
    invertible: bool = False   # updates have inverses under combine
    deferrable: bool = True    # apply distributes over combine

    def tree_delta(self, src: PyTree, upd: PyTree) -> PyTree:
        return jax.tree.map(self.delta, src, upd)

    def tree_combine(self, u1: PyTree, u2: PyTree) -> PyTree:
        return jax.tree.map(self.combine, u1, u2)

    def tree_apply(self, mem: PyTree, u: PyTree, key=None) -> PyTree:
        if self.needs_key:
            leaves = jax.tree.leaves(mem)
            keys = list(jax.random.split(key, len(leaves)))
            kt = jax.tree.unflatten(jax.tree.structure(mem), keys)
            return jax.tree.map(lambda m, uu, k: self.apply(m, uu, key=k), mem, u, kt)
        return jax.tree.map(self.apply, mem, u)

    def tree_identity(self, like: PyTree) -> PyTree:
        return jax.tree.map(lambda x: self.identity(x.shape, x.dtype), like)

    # ---------------------------------------------------- derived validity

    @property
    def stale_tolerant(self) -> bool:
        """May a one-step-stale (overlapped) commit land against this merge?

        Scalable merges absorb the delay into the delayed-mean bookkeeping;
        idempotent merges cannot be corrupted by duplicated or late joins.
        Anything else would install a commit computed against a memory state
        that no serialization of the update stream produces.
        """
        return self.scalable or self.idempotent

    def settle_mode(self) -> Optional[str]:
        """How a K-step deferred commit reconciles with per-step semantics.

        ``"mean"``   — scalable: divide the settled sum by the contribution
                       count (delayed mean, the gradient path).
        ``"reapply"``— idempotent: the settled join is re-applied as-is;
                       scaling would be meaningless and is skipped.
        ``None``     — neither; a deferred train/commit loop has no sound
                       way to install the aggregate. Callers must raise.
        """
        if self.scalable:
            return "mean"
        if self.idempotent:
            return "reapply"
        return None

    def check_deferrable(self, context: str) -> None:
        """Raise unless ``:defer`` is algebra-sound for this merge."""
        if not self.deferrable:
            raise ValueError(
                f"{context}: merge '{self.name}' cannot defer commits — its "
                "apply is not a homomorphism over combine (it observes "
                "memory or randomizes per commit), so applying K coalesced "
                "steps at once diverges from applying each step. Drop the "
                ":defer flags or pick a deferrable merge.")
        if self.needs_key:
            raise ValueError(
                f"{context}: merge '{self.name}' draws a PRNG key per apply; "
                "deferred commits collapse K applies into one and would "
                "change the sampling distribution. Drop the :defer flags.")

    def check_overlap(self, context: str) -> None:
        """Raise unless one-step-stale commit landings are algebra-sound."""
        self.check_deferrable(context)
        if not self.stale_tolerant:
            raise ValueError(
                f"{context}: merge '{self.name}' cannot land one-step-stale "
                "overlapped commits — it is neither scalable (no delayed-"
                "mean reconciliation) nor idempotent (a late landing is not "
                "a harmless re-join). Use --merge-defer without overlap.")


def _zeros(shape, dtype):
    return jnp.zeros(shape, dtype)


def _ones(shape, dtype):
    return jnp.ones(shape, dtype)


def _neg_inf(shape, dtype):
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.full(shape, jnp.iinfo(dtype).min, dtype)
    return jnp.full(shape, -jnp.inf, dtype)


def _pos_inf(shape, dtype):
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.full(shape, jnp.iinfo(dtype).max, dtype)
    return jnp.full(shape, jnp.inf, dtype)


# ---------------------------------------------------------------------------
# Standard merges (paper §3.2 / §6.3 menu).
# ---------------------------------------------------------------------------

ADD = MergeFn(
    name="add",
    delta=lambda src, upd: upd - src,
    combine=lambda a, b: a + b,
    apply=lambda mem, u: mem + u,
    identity=_zeros,
    xla_reduce="add",
    scalable=True,
    invertible=True,
)

MUL = MergeFn(  # multiplicative updates: contribution is the factor upd/src
    name="mul",
    delta=lambda src, upd: upd / src,
    combine=lambda a, b: a * b,
    apply=lambda mem, u: mem * u,
    identity=_ones,
    xla_reduce="mul",
    invertible=True,
)

# Complex multiply (paper §6.3): represented as (..., 2) real/imag channels so
# the same merge runs in Pallas kernels and on TPU-native dtypes.
def _cmul(a: Array, b: Array) -> Array:
    ar, ai = a[..., 0], a[..., 1]
    br, bi = b[..., 0], b[..., 1]
    return jnp.stack([ar * br - ai * bi, ar * bi + ai * br], axis=-1)


def _cdiv(a: Array, b: Array) -> Array:
    br, bi = b[..., 0], b[..., 1]
    d = br * br + bi * bi
    conj = jnp.stack([br, -bi], axis=-1)
    return _cmul(a, conj) / d[..., None]


def _cones(shape, dtype):
    one = jnp.zeros(shape, dtype)
    return one.at[..., 0].set(1)


COMPLEX_MUL = MergeFn(
    name="complex_mul",
    delta=lambda src, upd: _cdiv(upd, src),
    combine=_cmul,
    apply=lambda mem, u: _cmul(mem, u),
    identity=_cones,
    wire_atom=2,
    invertible=True,
)

MAX = MergeFn(
    name="max",
    delta=lambda src, upd: upd,
    combine=jnp.maximum,
    apply=jnp.maximum,
    identity=_neg_inf,
    xla_reduce="max",
    idempotent=True,
)

MIN = MergeFn(
    name="min",
    delta=lambda src, upd: upd,
    combine=jnp.minimum,
    apply=jnp.minimum,
    identity=_pos_inf,
    xla_reduce="min",
    idempotent=True,
)

BITWISE_OR = MergeFn(  # the paper's BFS bitmap merge
    name="or",
    delta=lambda src, upd: upd | src,
    combine=lambda a, b: a | b,
    apply=lambda mem, u: mem | u,
    identity=_zeros,
    xla_reduce="or",
    idempotent=True,
)

BITWISE_AND = MergeFn(
    name="and",
    delta=lambda src, upd: upd & src,
    combine=lambda a, b: a & b,
    apply=lambda mem, u: mem & u,
    identity=lambda shape, dtype: jnp.full(shape, -1, dtype),
    xla_reduce="and",
    idempotent=True,
)


BITWISE_XOR = MergeFn(  # HPCC RandomAccess's update: word ^= value
    name="xor",
    delta=lambda src, upd: upd ^ src,
    combine=lambda a, b: a ^ b,
    apply=lambda mem, u: mem ^ u,
    identity=_zeros,
    xla_reduce="xor",
    # a ^ a == 0: neither idempotent nor scalable, so a one-step-stale
    # (overlapped) landing is refused; deferred commits are exact
    invertible=True,
)


def saturating_add(max_value: float, min_value: float | None = None) -> MergeFn:
    """Paper §4.5 / §6.3: additive merge with a memory-observed threshold."""

    def _apply(mem, u):
        out = mem + u
        out = jnp.minimum(out, jnp.asarray(max_value, out.dtype))
        if min_value is not None:
            out = jnp.maximum(out, jnp.asarray(min_value, out.dtype))
        return out

    return MergeFn(
        name=f"sat_add[{max_value}]",
        delta=ADD.delta,
        combine=ADD.combine,
        apply=_apply,
        identity=_zeros,
        xla_reduce="add",  # combine is plain add; only apply saturates
        # The threshold is observed against memory at every commit: folding
        # K commits into one changes which sums get clipped (paper §4.5).
        deferrable=False,
    )


def dropping_add(drop_prob: float) -> MergeFn:
    """Approximate merge (paper §3.2/§6.3): binomially drop updates.

    Per-element Bernoulli(drop_prob) masking of the combined update at apply
    time — the loop-perforation-style quality/performance trade-off.
    """

    def _apply(mem, u, *, key):
        keep = jax.random.bernoulli(key, 1.0 - drop_prob, shape=u.shape)
        return mem + jnp.where(keep, u, jnp.zeros_like(u))

    return MergeFn(
        name=f"drop_add[{drop_prob}]",
        delta=ADD.delta,
        combine=ADD.combine,
        apply=_apply,
        identity=_zeros,
        xla_reduce=None,  # flexible path only: COUP cannot express this
        needs_key=True,
        deferrable=False,  # one Bernoulli draw per commit, not per step
    )


def int8_compressed_add(scale_percentile: float = 100.0) -> MergeFn:
    """Beyond-paper: delta merge with int8-quantized wire format.

    ``encode`` quantizes the update with a per-tensor scale; tree-merge rounds
    exchange ~4x fewer bytes (bf16->int8 plus a scalar). Decode/requantize at
    each combine keeps the reduction commutative up to quantization noise.
    """

    def _encode(u: Array):
        amax = jnp.max(jnp.abs(u)) + 1e-12
        scale = amax / 127.0
        q = jnp.clip(jnp.round(u / scale), -127, 127).astype(jnp.int8)
        return {"q": q, "scale": scale.astype(jnp.float32)}

    def _decode(c) -> Array:
        return c["q"].astype(jnp.float32) * c["scale"]

    return MergeFn(
        name="int8_add",
        delta=ADD.delta,
        combine=ADD.combine,
        apply=lambda mem, u: mem + u.astype(mem.dtype),
        identity=_zeros,
        xla_reduce=None,
        encode=_encode,
        decode=_decode,
        scalable=True,
        invertible=True,
    )


class MergeFunctionRegistry:
    """The MFRF: maps small integer ids -> merge functions.

    The paper provisions a 4-entry register file (2 merge-type bits / line);
    ours is software so the size is a config knob, but ids stay dense so the
    blocked engine / kernels can carry per-block merge-type tags.
    """

    def __init__(self, capacity: int = 16):
        self.capacity = capacity
        self._by_name: dict[str, MergeFn] = {}
        self._by_id: list[MergeFn] = []

    def merge_init(self, fn: MergeFn) -> int:
        """Register ``fn``; returns its MFRF id (paper: merge_init(&fn, i))."""
        if fn.name in self._by_name:
            return self._by_id.index(self._by_name[fn.name])
        if len(self._by_id) >= self.capacity:
            raise ValueError(f"MFRF full (capacity={self.capacity})")
        self._by_name[fn.name] = fn
        self._by_id.append(fn)
        return len(self._by_id) - 1

    def __getitem__(self, key) -> MergeFn:
        if isinstance(key, str):
            return self._by_name[key]
        return self._by_id[key]

    def id_of(self, name: str) -> int:
        return self._by_id.index(self._by_name[name])

    def __contains__(self, name: str) -> bool:
        return name in self._by_name

    def __len__(self) -> int:
        return len(self._by_id)

    def __iter__(self):
        """Registered merges in id order (the verifier sweeps these)."""
        return iter(self._by_id)


def default_registry() -> MergeFunctionRegistry:
    reg = MergeFunctionRegistry()
    for fn in (ADD, MAX, MIN, BITWISE_OR, MUL, COMPLEX_MUL, BITWISE_XOR):
        reg.merge_init(fn)
    return reg


def standard_merges() -> tuple[MergeFn, ...]:
    """Every merge the repo ships, including the parameterized families at
    representative parameters — the trait-certification sweep surface."""
    return tuple(default_registry()) + (
        saturating_add(8.0, min_value=-8.0),
        dropping_add(0.25),
        int8_compressed_add(),
    )
