"""Jaxpr-level privatization lint: collectives + taint in non-commit regions.

CCache's contract is that between privatize and merge a program touches only
private state — the compiled region has zero coherence traffic and settled
(shared) memory is neither read into nor written from the pending buffers
except at explicit merge points. This module abstract-interprets per-shard
update bodies (traced with a bound axis environment, so collectives stay
collectives instead of being vmapped away) and checks exactly that:

* :func:`collective_primitives` / :func:`check_noncommit_region` — any
  ``psum``/``ppermute``/``all_gather``/... equation inside a non-commit
  tick is CC010 (the jaxpr twin of the HLO-level CC020);
* :func:`check_kv_tick_taint` — input->output dependency sets over the
  jaxpr: on a due=0 tick the settled output may depend only on the settled
  input (CC012 otherwise — pending mass escaped the cascade) and no pending
  output may depend on the settled input (CC011 — a settled read leaked
  into the privatized update path);
* :func:`audit_plan` — the plan/trait audit (CC013/CC014): re-runs
  ``compile_plan``'s validity checks without raising, and catches
  stage lists whose ``:defer`` levels a non-deferrable merge reached by
  bypassing ``compile_plan``.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
from jax.extend import core as jax_core

from repro.analysis.diagnostics import Diagnostic
from repro.core.merge_functions import MergeFn
from repro.core.merge_plan import MergePlan, validate_plan_merge

COLLECTIVE_PRIMITIVES = {
    "psum", "pmax", "pmin", "ppermute", "pbroadcast", "all_gather",
    "all_to_all", "reduce_scatter", "psum_scatter", "pgather",
}


def _subjaxprs(eqn):
    for v in eqn.params.values():
        if isinstance(v, jax_core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jax_core.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                if isinstance(x, jax_core.ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, jax_core.Jaxpr):
                    yield x


def trace_with_axis(fn, axis_name, axis_size: int, *avals):
    """``make_jaxpr`` with the merge axis bound, so ``psum(x, axis)`` traces
    to a psum equation instead of failing (or being batched away)."""
    return jax.make_jaxpr(fn, axis_env=[(axis_name, axis_size)])(*avals)


def collective_primitives(closed) -> list[str]:
    """Names of collective equations anywhere in ``closed`` (recursive)."""
    found: list[str] = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in COLLECTIVE_PRIMITIVES:
                found.append(eqn.primitive.name)
            for sub in _subjaxprs(eqn):
                walk(sub)

    walk(closed.jaxpr if hasattr(closed, "jaxpr") else closed)
    return found


def check_noncommit_region(fn, axis_name, axis_size: int, avals,
                           site: str) -> list[Diagnostic]:
    """CC010: a non-commit region must trace to zero collective equations."""
    closed = trace_with_axis(fn, axis_name, axis_size, *avals)
    prims = collective_primitives(closed)
    if prims:
        return [Diagnostic(
            code="CC010", site=site,
            message=f"non-commit region traces {len(prims)} collective "
                    f"equation(s) {sorted(set(prims))}; the privatized "
                    f"window must have zero coherence traffic")]
    return []


# -- taint: which inputs does each output depend on? ------------------------


def _out_deps(jaxpr) -> list[set]:
    """Per-outvar sets of input indices (conservative; precise through
    single-subjaxpr call equations like pjit/remat)."""
    env: dict[Any, set] = {}
    for i, v in enumerate(jaxpr.invars):
        env[v] = {i}
    for v in jaxpr.constvars:
        env[v] = set()

    def deps_of(atom) -> set:
        if isinstance(atom, jax_core.Literal):
            return set()
        return env.get(atom, set())

    for eqn in jaxpr.eqns:
        in_deps = [deps_of(x) for x in eqn.invars]
        subs = list(_subjaxprs(eqn))
        if len(subs) == 1 and len(subs[0].invars) == len(eqn.invars):
            sub_deps = _out_deps(subs[0])
            out_deps = [set().union(*(in_deps[i] for i in d)) if d else set()
                        for d in sub_deps]
            if len(out_deps) != len(eqn.outvars):
                u = set().union(*in_deps) if in_deps else set()
                out_deps = [u] * len(eqn.outvars)
        else:
            u = set().union(*in_deps) if in_deps else set()
            out_deps = [u] * len(eqn.outvars)
        for v, d in zip(eqn.outvars, out_deps):
            env[v] = d
    return [deps_of(v) for v in jaxpr.outvars]


def check_kv_tick_taint(tick_fn, axis_name, axis_size: int,
                        settled_aval, pending_avals: Sequence,
                        key_aval, val_aval, site: str) -> list[Diagnostic]:
    """Taint lint of a due=0 KV tick ``(settled, pendings, keys, vals) ->
    (settled', pendings')``.

    Flat input/output index 0 is the settled table; 1..n_pending the
    cascade. CC011: a pending output tainted by the settled input (the
    update path read shared state). CC012: the settled output tainted by
    pendings/keys/vals (pending mass reached shared state without a
    commit).
    """
    closed = trace_with_axis(tick_fn, axis_name, axis_size, settled_aval,
                             tuple(pending_avals), key_aval, val_aval)
    deps = _out_deps(closed.jaxpr)
    n_pend = len(pending_avals)
    diags: list[Diagnostic] = []
    if len(deps) != 1 + n_pend:
        return [Diagnostic(
            code="CC012", site=site,
            message=f"due=0 tick returns {len(deps)} arrays, expected "
                    f"settled + {n_pend} pendings; cannot prove the "
                    f"settled table stayed untouched")]
    settled_deps, pending_deps = deps[0], deps[1:]
    if settled_deps - {0}:
        diags.append(Diagnostic(
            code="CC012", site=site,
            message=f"settled output depends on non-settled inputs "
                    f"{sorted(settled_deps - {0})} (0=settled, "
                    f"1..{n_pend}=pendings, {n_pend + 1}=keys, "
                    f"{n_pend + 2}=vals) on a due=0 tick; pending mass "
                    f"escaped the cascade"))
    tainted = [i for i, d in enumerate(pending_deps) if 0 in d]
    if tainted:
        diags.append(Diagnostic(
            code="CC011", site=site,
            message=f"pending output(s) {tainted} depend on the settled "
                    f"table inside a non-commit tick; the privatized "
                    f"update path read shared state"))
    return diags


# -- plan/trait audits -------------------------------------------------------


def audit_plan(plan: MergePlan, axis_size: int,
               merge_fn: Optional[MergeFn] = None,
               site: Optional[str] = None) -> list[Diagnostic]:
    """Non-raising twin of ``compile_plan``'s validity gate (CC013/CC014)."""
    site = site or f"plan:{','.join(plan.level_names())}"
    diags = []
    for kind, level, msg in validate_plan_merge(plan, axis_size, merge_fn):
        diags.append(Diagnostic(
            code="CC013" if kind == "defer-trait" else "CC014",
            site=site, level=level, message=msg))
    return diags


def audit_stages(stages, merge_fn: MergeFn,
                 site: str) -> list[Diagnostic]:
    """CC013 for compiled stage lists that bypassed ``compile_plan``: a
    ``:defer`` stage reached by a merge whose apply is not a homomorphism
    (or draws a key per apply) was never validated."""
    diags = []
    for st in stages:
        if st.defer and st.fanout > 1 and (not merge_fn.deferrable
                                           or merge_fn.needs_key):
            why = ("draws a PRNG key per apply" if merge_fn.needs_key
                   else "apply is not a homomorphism over combine")
            diags.append(Diagnostic(
                code="CC013", site=site, level=st.name,
                message=f"deferred stage {st.name!r} is reached by merge "
                        f"{merge_fn.name!r}, which {why}; this stage list "
                        f"bypassed compile_plan's trait gate"))
    return diags
