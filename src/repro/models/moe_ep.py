"""Expert-parallel MoE via shard_map: local dispatch + one commutative merge.

The GShard-style sort dispatch (moe.py) lets XLA partition a *global*
argsort over tokens — on a 16-way model axis that costs TBs of sort/permute
wire per step (EXPERIMENTS §Perf, qwen3 cell). Observation: the token
activations are replicated across the model axis (they are sharded over
data/pod only), so expert parallelism needs **no all-to-all at all**:

  * every model rank already holds all of its data-shard's tokens;
  * a rank dispatches tokens only to its LOCAL experts (E/16), locally —
    the capacity discipline and sort never leave the chip;
  * each rank produces its experts' partial token outputs, and the combine
    is a single ``psum`` over the model axis — the paper's additive
    commutative merge, applied to the token-output CData.

Per layer the collective cost collapses to one [tokens, E] router gather +
one [tokens, d] output reduction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import moe as moe_base
from repro.models.mlp import swiglu

Array = jax.Array


def _local_apply(p, x, top_k: int, capacity_factor: float, n_experts: int,
                 model_axis: str, e_start: Array):
    """Runs per model-rank: x [b_loc, s, d] (all local tokens), expert
    weights are the rank's E_loc slice; returns the psum-merged output."""
    b, s, d = x.shape
    e_loc = p["wi_gate"].shape[0]
    xt = x.reshape(b * s, d)
    t = b * s

    # Router over the full expert set: gather the E_loc logit slices.
    logits_loc = (xt.astype(jnp.float32) @ p["router"]["w"])   # [T, E_loc]
    logits = jax.lax.all_gather(logits_loc, model_axis, axis=1, tiled=True)
    probs = jax.nn.softmax(logits, axis=-1)                     # [T, E]
    w, ids = jax.lax.top_k(probs, top_k)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-9)

    # Keep only assignments routed to MY experts; dispatch locally.
    n = t * top_k
    e_flat = ids.reshape(n)
    w_flat = w.reshape(n)
    token_idx = jnp.arange(n, dtype=jnp.int32) // top_k
    rel = e_flat - e_start
    mine = (rel >= 0) & (rel < e_loc)
    rel_safe = jnp.where(mine, rel, e_loc)      # e_loc = dropped row

    cap = moe_base.capacity_for(t, top_k, n_experts, capacity_factor)
    pos = moe_base.positions_in_expert(
        jnp.where(mine, rel, e_loc).astype(jnp.int32), e_loc + 1)
    keep = mine & (pos < cap)
    slot = jnp.where(keep, pos, cap)

    buf = jnp.zeros((e_loc, cap, d), x.dtype)
    buf = buf.at[rel_safe, slot].set(xt[token_idx], mode="drop")

    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wi_gate"]))
    u = jnp.einsum("ecd,edf->ecf", buf, p["wi_up"])
    out_buf = jnp.einsum("ecf,efd->ecd", g * u, p["wo"])   # [E_loc, cap, d]

    y = out_buf.at[rel_safe, slot].get(mode="fill", fill_value=0)
    y = y * (w_flat * keep)[:, None].astype(y.dtype)
    partial = jnp.zeros((t, d), x.dtype).at[token_idx].add(y)

    # The commutative merge: every rank contributed its experts' updates.
    out = jax.lax.psum(partial, model_axis)

    if "shared" in p:
        out = out + swiglu(p["shared"], xt)

    e_one = jax.nn.one_hot(ids[:, 0], n_experts, dtype=jnp.float32)
    frac_tokens = e_one.mean(axis=0)
    mean_prob = probs.mean(axis=0)
    aux = n_experts * jnp.sum(frac_tokens * mean_prob)
    dispatched = jax.lax.psum(jnp.sum(keep.astype(jnp.float32)), model_axis)
    metrics = {
        "aux_loss": aux,
        "router_z": jnp.mean(jax.nn.logsumexp(
            jnp.log(probs + 1e-9), axis=-1) ** 2),
        "drop_frac": 1.0 - dispatched / n,
        "expert_load": e_one.sum(axis=0),
    }
    return out.reshape(b, s, d), metrics


def apply_ep(p, x: Array, top_k: int, capacity_factor: float, mesh,
             batch_axes=("pod", "data"), model_axis: str = "model"
             ) -> tuple[Array, dict]:
    """shard_map wrapper. x [B, S, D]; expert weights sharded on
    ``model_axis``; batch sharded on ``batch_axes`` (present mesh axes)."""
    n_experts = p["wi_gate"].shape[0]
    model_size = mesh.shape[model_axis]
    dp = tuple(a for a in batch_axes if a in mesh.shape)
    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)

    e_loc = n_experts // model_size

    all_axes = tuple(mesh.shape.keys())

    def fn(x, router_w, wi_gate, wi_up, wo, shared):
        rank = jax.lax.axis_index(model_axis)
        pl = {"router": {"w": router_w}, "wi_gate": wi_gate,
              "wi_up": wi_up, "wo": wo}
        if shared is not None:
            pl["shared"] = shared
        out, metrics = _local_apply(pl, x, top_k, capacity_factor,
                                    n_experts, model_axis, rank * e_loc)
        # metrics fully reduced (replicated output spec).
        metrics = jax.tree.map(
            lambda m: jax.lax.pmean(m, all_axes), metrics)
        return out, metrics

    shared = p.get("shared")
    in_specs = (P(dp_spec, None, None),            # x
                P(None, "model"),                   # router [d, E]
                P("model", None, None),             # wi_gate [E, d, f]
                P("model", None, None),
                P("model", None, None),
                (None if shared is None
                 else jax.tree.map(lambda _: P(None, None), shared)))
    out_specs = (P(dp_spec, None, None),
                 {"aux_loss": P(), "router_z": P(), "drop_frac": P(),
                  "expert_load": P()})
    f = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False)
    return f(x, p["router"]["w"], p["wi_gate"], p["wi_up"], p["wo"], shared)
