"""Pipeline parallelism: a GPipe-style circular schedule over a mesh axis.

Each rank of the ``stage`` axis owns one stage's parameters. Microbatches
stream through: at tick t, stage s processes microbatch (t - s) — a bubble
when out of range — and activations hop stage s -> s+1 with one
``ppermute`` per tick (the TPU-native point-to-point; no gather).

Total ticks = n_micro + S - 1; bubble fraction = (S-1)/(n_micro+S-1),
the standard GPipe pipeline efficiency. Used under ``shard_map`` on a real
mesh, or under ``vmap(axis_name=...)`` in tests.

The CCache view of this (DESIGN.md §3): each stage's activations are
privatized per-stage state; the ppermute handoff is the merge boundary —
ordered, not commutative, so it rides point-to-point transfer rather than
the commutative tree-merge engine.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax


PyTree = Any


def pipeline_apply(stage_fn: Callable[[PyTree, jax.Array], jax.Array],
                   stage_params: PyTree, microbatches: jax.Array,
                   axis_name: str = "stage") -> jax.Array:
    """Run ``stage_fn`` as a pipeline over ``axis_name``.

    Per-rank arguments (inside shard_map / vmap over the stage axis):
      stage_params  this rank's stage parameters
      microbatches  [n_micro, mb, ...] — the *input* stream; only stage 0's
                    copy is consumed (other ranks may pass zeros)
    Returns [n_micro, mb, ...] — only stage S-1's copy holds the outputs.
    """
    s_idx = lax.axis_index(axis_name)
    n_stages = lax.axis_size(axis_name)
    n_micro = microbatches.shape[0]
    ticks = n_micro + n_stages - 1
    mb_shape = microbatches.shape[1:]

    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    out0 = jnp.zeros((n_micro,) + mb_shape, microbatches.dtype)
    carry_in0 = jnp.zeros(mb_shape, microbatches.dtype)

    def tick(state, t):
        carry_in, outputs = state
        mb_idx = t - s_idx                       # microbatch at this stage
        active = (mb_idx >= 0) & (mb_idx < n_micro)
        # Stage 0 reads from the input stream; others take the handoff.
        src = lax.cond(
            s_idx == 0,
            lambda: lax.dynamic_index_in_dim(
                microbatches, jnp.clip(mb_idx, 0, n_micro - 1), 0,
                keepdims=False),
            lambda: carry_in)
        y = stage_fn(stage_params, src)
        # Last stage banks its result; everyone forwards (bubbles too —
        # static schedule keeps the compiled step shape-stable).
        outputs = lax.cond(
            active & (s_idx == n_stages - 1),
            lambda o: lax.dynamic_update_index_in_dim(
                o, y, jnp.clip(mb_idx, 0, n_micro - 1), 0),
            lambda o: o, outputs)
        carry_out = lax.ppermute(y, axis_name, perm)
        return (carry_out, outputs), None

    (_, outputs), _ = lax.scan(tick, (carry_in0, out0),
                               jnp.arange(ticks, dtype=jnp.int32))
    return outputs


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
