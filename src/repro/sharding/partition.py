"""Logical-axis sharding: rules mapping logical axes -> mesh axes.

Parameters and activations carry *logical* axis names (module.py). At launch
we install a rule set (a context) mapping logical names to mesh axes;
``spec_for`` resolves a tuple of logical names into a ``PartitionSpec``,
degrading gracefully (axis dropped) when a dim is not divisible by the mesh
axis size — e.g. 8 KV heads on a 16-way model axis stay replicated.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Default production rules (DESIGN.md §5).
DEFAULT_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),
    "seq": None,
    "seq_res": None,   # residual-stream seq dim (sequence-parallel lever)
    "embed": "data",        # FSDP: params/optimizer reduce-scattered over data
    "embed_act": None,      # activation d_model dim stays unsharded
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "expert": "model",
    "expert_mlp": None,
    "capacity": None,
    "layers": None,
    "conv": None,
    "state": None,
}


class _Ctx(threading.local):
    def __init__(self):
        self.rules: Optional[dict] = None
        self.mesh: Optional[Mesh] = None
        self.manual: frozenset = frozenset()


_CTX = _Ctx()


@contextlib.contextmanager
def sharding_rules(mesh: Mesh, rules: Optional[dict] = None):
    """Install mesh + logical rules for model code (logical_constraint)."""
    prev = (_CTX.rules, _CTX.mesh)
    _CTX.rules = dict(DEFAULT_RULES, **(rules or {}))
    _CTX.mesh = mesh
    try:
        yield
    finally:
        _CTX.rules, _CTX.mesh = prev


def active_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def _mesh_axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def spec_for(shape: tuple[int, ...], axes: tuple, mesh: Mesh,
             rules: Optional[dict] = None) -> P:
    """Logical axes -> PartitionSpec.

    Degrades gracefully: assignments are dropped when the dim is not
    divisible by the mesh axis, and a mesh axis already consumed by an
    earlier dim of the same spec is never reused (cross-dim conflict guard).
    """
    rules = dict(DEFAULT_RULES, **(rules or {}))
    out = []
    used: set = set()
    for dim, name in zip(shape, axes):
        mesh_axis = rules.get(name) if name is not None else None
        if mesh_axis is None:
            out.append(None)
            continue
        # Filter a composite assignment down to the divisible, unused prefix.
        if isinstance(mesh_axis, (tuple, list)):
            kept = []
            rem = dim
            for a in mesh_axis:
                if a in mesh.shape and a not in used and rem % mesh.shape[a] == 0:
                    kept.append(a)
                    rem //= mesh.shape[a]
            mesh_axis = tuple(kept) if kept else None
        else:
            if (mesh_axis not in mesh.shape or mesh_axis in used
                    or dim % mesh.shape[mesh_axis] != 0):
                mesh_axis = None
        if mesh_axis is not None:
            used.update(mesh_axis if isinstance(mesh_axis, tuple)
                        else (mesh_axis,))
        out.append(mesh_axis)
    return P(*out)


def sharding_for(shape, axes, mesh: Mesh, rules=None) -> NamedSharding:
    return NamedSharding(mesh, spec_for(shape, axes, mesh, rules))


def params_shardings(param_axes: Any, param_shapes: Any, mesh: Mesh,
                     rules=None) -> Any:
    """Tree of NamedShardings for a params tree (axes tree + shapes tree)."""
    return jax.tree.map(
        lambda ax, shp: sharding_for(tuple(shp.shape) if hasattr(shp, "shape") else tuple(shp),
                                     ax, mesh, rules),
        param_axes, param_shapes,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x))


@contextlib.contextmanager
def manual_axes(axes):
    """Mark mesh axes as shard_map-manual for the enclosed trace.

    Inside a shard_map manual region, per-shard values are *local* along
    the manual axes: a with_sharding_constraint naming them is rejected by
    jax. Model code doesn't know which axes the launch layer went manual
    over, so the explicit-merge train step installs this context and
    ``logical_constraint`` suppresses every constraint while it is active.
    """
    prev = _CTX.manual
    _CTX.manual = prev | frozenset(axes)
    try:
        yield
    finally:
        _CTX.manual = prev


def logical_constraint(x: jax.Array, axes: tuple) -> jax.Array:
    """with_sharding_constraint by logical axes; no-op outside a rules ctx."""
    if _CTX.mesh is None or _CTX.rules is None:
        return x
    if _CTX.manual:
        # No constraints inside a shard_map manual region: naming a manual
        # axis is rejected outright, and the remaining axes are trivial
        # there (launch.steps refuses partial-auto merges), so their layout
        # follows the operand shardings.
        return x
    spec = spec_for(x.shape, axes, _CTX.mesh, _CTX.rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(_CTX.mesh, spec))


def count_params(params: Any) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
