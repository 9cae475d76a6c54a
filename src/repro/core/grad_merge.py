"""Gradient accumulation and reduction as CCache merges.

Microbatch gradient accumulation *is* privatize-and-merge: each microbatch's
gradient is a COp contribution on a privatized replica; ``soft_merge``
coalesces them locally (one ``combine`` per microbatch, zero collectives), and
the single cross-device ``commit`` at the step boundary is the evict-time
merge. Beyond-paper: the delta formulation makes compressed (int8) and
approximate (update-dropping) gradient exchange drop-in merge functions.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import ccache
from repro.core.merge_functions import ADD, MergeFn

PyTree = Any


def split_microbatches(batch: PyTree, num_microbatches: int) -> PyTree:
    """[B, ...] -> [num_microbatches, B/num_microbatches, ...] per leaf."""

    def _split(x):
        b = x.shape[0]
        assert b % num_microbatches == 0, (b, num_microbatches)
        return x.reshape((num_microbatches, b // num_microbatches) + x.shape[1:])

    return jax.tree.map(_split, batch)


def microbatched_value_and_grad(
    loss_fn: Callable[[PyTree, PyTree], jax.Array],
    num_microbatches: int,
    merge_fn: MergeFn = ADD,
    mean: bool = True,
) -> Callable[[PyTree, PyTree], tuple[jax.Array, PyTree]]:
    """Returns step(params, batch) -> (loss, grads) with soft-merge accumulation.

    The scan carries a ``PendingUpdate`` (privatized gradient replica); no
    cross-device traffic occurs inside the loop. The caller (or the sharding
    of the output) performs the final commit/reduction.
    """
    grad_fn = jax.value_and_grad(loss_fn)

    def step(params: PyTree, batch: PyTree):
        micro = split_microbatches(batch, num_microbatches)

        def body(carry, mb):
            pending, loss_sum = carry
            loss, grads = grad_fn(params, mb)
            # soft_merge: coalesce locally, defer the expensive merge.
            pending = merge_fn.tree_combine(pending, grads)
            return (pending, loss_sum + loss), None

        init = (merge_fn.tree_identity(params), jnp.zeros((), jnp.float32))
        (grads, loss_sum), _ = lax.scan(body, init, micro)
        if mean and merge_fn.scalable:
            scale = 1.0 / num_microbatches
            grads = jax.tree.map(lambda g: g * jnp.asarray(scale, g.dtype), grads)
        loss = loss_sum / num_microbatches
        return loss, grads

    return step


def merge_gradients(
    grads: PyTree,
    axis_name,
    merge_fn: MergeFn = ADD,
    compress: bool = False,
    mean: bool = True,
    topology: Optional[ccache.Topology] = None,
) -> PyTree:
    """Explicit cross-device gradient merge (inside shard_map).

    ``compress=True`` with a merge defining encode/decode exchanges the int8
    wire format in every butterfly round (≈4x fewer collective bytes).
    ``topology`` (a two-level ``MergeTopology`` or an N-level ``MergePlan``)
    routes through the hierarchical engine: fused reduction on the cheap
    innermost level, representative-only or lane-parallel exchange at the
    upper levels (where compression, if any, is applied).
    """
    if topology is not None:
        # A topology pinned to an axis overrides the argument — resolve
        # before both the reduction and the mean so they can't disagree
        # (a mismatch would silently mis-scale every gradient).
        axis_name = topology.resolve_axis(axis_name)
    merged = ccache.reduce_update(grads, axis_name, merge_fn,
                                  compress=compress, topology=topology)
    # Mean semantics exist exactly for scalable merges (the delayed-mean
    # algebra trait); idempotent/multiplicative merges pass through.
    if mean and merge_fn.scalable:
        n = lax.axis_size(axis_name)
        merged = jax.tree.map(lambda g: g / n, merged)
    return merged
