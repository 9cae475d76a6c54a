"""Trait certification: do a merge fn's declared algebra traits hold?

The engine trusts ``MergeFn`` trait declarations at plan-compile time
(``check_deferrable`` / ``check_overlap``) — a mislabeled merge silently
buys scheduling freedom its algebra cannot pay for (a ``sat_add`` declared
scalable would be granted the delayed-mean settle path and clip different
sums than the per-step program). This module probes the declarations:

* **randomized algebraic probes** — concrete identities evaluated on
  deterministic random inputs across a magnitude sweep (x1/x10/x100, so
  value-dependent thresholds actually get crossed):

    idempotent   combine(a, a) == a
    scalable     combine(c*a, c*b) == c*combine(a, b)  AND the delayed-mean
                 installation apply(apply(m, c*a), c*b) ==
                 apply(m, c*combine(a, b)) — scalable is what licenses
                 installing one scaled aggregate in place of the per-step
                 applies, so the identity must hold *through* apply
    invertible   combine(a, delta(a, b)) == b
    deferrable   apply(apply(m, u1), u2) == apply(m, combine(u1, u2))

* **jaxpr primitive classification** — ``apply`` traced next to ``combine``;
  a deferrable-declared merge whose apply uses comparison/clamp/select
  primitives that combine does not (memory-observed thresholds) is flagged
  even when the random probes happened to miss the threshold.

Probes are refutation-only: a passing probe certifies nothing beyond "not
provably mislabeled" (the probes are sound, not complete).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.diagnostics import Diagnostic
from repro.core.merge_functions import MergeFn

_SCALES = (1.0, 10.0, 100.0)
_PROBE_SCALARS = (2.0, 0.5, 3.0)
_N_TRIALS = 3

# merges over bit patterns: probed on int32 words, not floats
_BITWISE = ("or", "and", "xor")

# apply-side primitives that read memory through a value-dependent branch.
_MEMORY_OBSERVING = {"min", "max", "clamp", "select_n",
                     "lt", "gt", "le", "ge"}


def _sample(fn: MergeFn, rng: np.random.Generator, shape: tuple,
            scale: float):
    """A random update drawn from the merge's value domain.

    Bitwise merges (or/and/xor) get int32 bit patterns; everything else gets
    floats bounded away from zero (MUL/COMPLEX_MUL deltas divide) with
    random signs, scaled by the magnitude-sweep factor.
    """
    if fn.xla_reduce in _BITWISE:
        return jnp.asarray(rng.integers(0, 1 << 20, size=shape), jnp.int32)
    mag = rng.uniform(0.5, 1.5, size=shape) * scale
    sign = rng.choice([-1.0, 1.0], size=shape)
    return jnp.asarray(mag * sign, jnp.float32)


def _probe_shape(fn: MergeFn) -> tuple:
    # structured combines (COMPLEX_MUL) need a whole trailing atom
    return (4, 3) if fn.wire_atom == 1 else (4, fn.wire_atom)


def _close(a, b) -> bool:
    a, b = jnp.asarray(a), jnp.asarray(b)
    if jnp.issubdtype(a.dtype, jnp.integer):
        return bool(jnp.array_equal(a, b))
    scale = float(jnp.max(jnp.abs(a)) + jnp.max(jnp.abs(b)) + 1.0)
    return bool(jnp.allclose(a, b, rtol=1e-3, atol=1e-4 * scale))


def _scale_update(u, c: float):
    if jnp.issubdtype(u.dtype, jnp.integer):
        return (u * int(c)) if float(c) == int(c) else u
    return u * jnp.asarray(c, u.dtype)


def _primitive_names(fn, *avals) -> set[str]:
    names: set[str] = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            names.add(eqn.primitive.name)
            for v in eqn.params.values():
                sub = getattr(v, "jaxpr", None)
                if sub is not None:
                    walk(sub)
                elif hasattr(v, "eqns"):
                    walk(v)

    walk(jax.make_jaxpr(fn)(*avals).jaxpr)
    return names


def certify_merge_fn(fn: MergeFn, site: Optional[str] = None,
                     seed: int = 0) -> list[Diagnostic]:
    """Probe ``fn``'s declared traits; returns the refutations found."""
    site = site or f"merge:{fn.name}"
    shape = _probe_shape(fn)
    rng = np.random.default_rng(seed)
    diags: list[Diagnostic] = []

    def refute(code: str, what: str, lhs, rhs, detail: str) -> None:
        diags.append(Diagnostic(
            code=code, site=site,
            message=f"merge {fn.name!r} is declared {what} but {detail}: "
                    f"probe lhs={np.asarray(lhs).ravel()[:4]} != "
                    f"rhs={np.asarray(rhs).ravel()[:4]}"))

    samples = [(scale, _sample(fn, rng, shape, scale),
                _sample(fn, rng, shape, scale),
                _sample(fn, rng, shape, scale))
               for scale in _SCALES for _ in range(_N_TRIALS)]

    if fn.idempotent:
        for _, a, _b, _m in samples:
            got = fn.combine(a, a)
            if not _close(got, a):
                refute("CC001", "idempotent", got, a,
                       "combine(a, a) != a")
                break

    if fn.scalable:
        done = False
        for _, a, b, m in samples:
            for c in _PROBE_SCALARS:
                ca, cb = _scale_update(a, c), _scale_update(b, c)
                lhs = fn.combine(ca, cb)
                rhs = _scale_update(fn.combine(a, b), c)
                if not _close(lhs, rhs):
                    refute("CC002", "scalable", lhs, rhs,
                           "combine(c*a, c*b) != c*combine(a, b)")
                    done = True
                    break
                # The delayed-mean settle installs ONE scaled aggregate in
                # place of the per-step applies — the identity must survive
                # apply, or the scalable trait licenses a commit path that
                # observes memory differently (sat_add's clipped sums).
                if fn.needs_key:
                    continue
                lhs = fn.apply(fn.apply(m, ca), cb)
                rhs = fn.apply(m, fn.combine(ca, cb))
                if not _close(lhs, rhs):
                    refute("CC002", "scalable", lhs, rhs,
                           "installing the scaled aggregate diverges from "
                           "the per-step applies "
                           "(apply(apply(m, c*a), c*b) != "
                           "apply(m, c*combine(a, b)))")
                    done = True
                    break
            if done:
                break

    if fn.invertible:
        for _, a, b, _m in samples:
            got = fn.combine(a, fn.delta(a, b))
            if not _close(got, b):
                refute("CC003", "invertible", got, b,
                       "combine(a, delta(a, b)) != b")
                break

    if fn.needs_key and fn.deferrable:
        diags.append(Diagnostic(
            code="CC006", site=site,
            message=f"merge {fn.name!r} draws a PRNG key per apply but is "
                    f"declared deferrable; collapsing K applies into one "
                    f"changes the sampling distribution"))
    elif fn.deferrable:
        for _, u1, u2, m in samples:
            lhs = fn.apply(fn.apply(m, u1), u2)
            rhs = fn.apply(m, fn.combine(u1, u2))
            if not _close(lhs, rhs):
                refute("CC004", "deferrable", lhs, rhs,
                       "apply(apply(m, u1), u2) != "
                       "apply(m, combine(u1, u2))")
                break
        # Structural corroboration: a memory-observing apply (clamp /
        # comparison primitives combine never uses) contradicts the
        # homomorphism even when the probes missed the threshold.
        spec = jax.ShapeDtypeStruct(
            shape, jnp.int32 if fn.xla_reduce in _BITWISE
            else jnp.float32)
        try:
            apply_prims = _primitive_names(
                lambda m2, u: fn.apply(m2, u), spec, spec)
            combine_prims = _primitive_names(fn.combine, spec, spec)
        except Exception:
            apply_prims = combine_prims = set()
        observing = (apply_prims - combine_prims) & _MEMORY_OBSERVING
        if observing and not any(d.code == "CC004" for d in diags):
            diags.append(Diagnostic(
                code="CC005", site=site,
                message=f"merge {fn.name!r} is declared deferrable but its "
                        f"apply uses memory-observing primitives "
                        f"{sorted(observing)} that combine does not — a "
                        f"value-dependent threshold observed against "
                        f"memory at every commit"))

    return diags
