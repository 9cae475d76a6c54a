"""Pure-jnp oracles for every kernel in this package.

``ref_cscatter_serial`` is the gold standard: a literal lax.scan serialization
of the COp stream (the paper's "equivalent to some serialization") — it works
for *any* commutative merge and is what the property tests check against.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------- cscatter


def _combine(kind: str, a, b):
    if kind in ("add", "sat_add"):
        return a + b
    if kind == "max":
        return jnp.maximum(a, b)
    if kind == "min":
        return jnp.minimum(a, b)
    if kind == "or":
        return a | b
    if kind == "xor":
        return a ^ b
    raise ValueError(kind)


def _identity_like(kind: str, x):
    if kind in ("add", "sat_add", "or", "xor"):
        return jnp.zeros_like(x)
    if jnp.issubdtype(x.dtype, jnp.floating):
        f = jnp.finfo(x.dtype)
        return jnp.full_like(x, f.max if kind == "min" else f.min)
    ii = jnp.iinfo(x.dtype)  # covers unsigned dtypes (min of uints needs max)
    return jnp.full_like(x, ii.max if kind == "min" else ii.min)


def _apply(kind: str, mem, u, sat_min=0.0, sat_max=0.0):
    if kind == "add":
        return mem + u.astype(mem.dtype)
    if kind == "sat_add":
        s = mem.astype(jnp.float32) + u.astype(jnp.float32)
        return jnp.clip(s, sat_min, sat_max).astype(mem.dtype)
    if kind == "max":
        return jnp.maximum(mem, u.astype(mem.dtype))
    if kind == "min":
        return jnp.minimum(mem, u.astype(mem.dtype))
    if kind == "xor":
        return mem ^ u.astype(mem.dtype)
    return mem | u.astype(mem.dtype)


def ref_cscatter(table, ids, vals, kind="add", sat_min=0.0, sat_max=0.0):
    """Vectorized privatize-and-merge oracle: fold deltas per row, apply once."""
    acc_dtype = (jnp.float32 if jnp.issubdtype(table.dtype, jnp.floating)
                 else table.dtype)
    u = _identity_like(kind, table.astype(acc_dtype))
    valid = (ids >= 0) & (ids < table.shape[0])
    safe = jnp.where(valid, ids, 0)
    v = vals.astype(acc_dtype)
    if kind in ("add", "sat_add"):
        v = jnp.where(valid[:, None], v, 0)
        u = u.at[safe].add(v)
    elif kind == "max":
        v = jnp.where(valid[:, None], v, jnp.finfo(acc_dtype).min
                      if jnp.issubdtype(acc_dtype, jnp.floating)
                      else jnp.iinfo(acc_dtype).min)
        u = u.at[safe].max(v)
    elif kind == "min":
        v = jnp.where(valid[:, None], v, jnp.finfo(acc_dtype).max
                      if jnp.issubdtype(acc_dtype, jnp.floating)
                      else jnp.iinfo(acc_dtype).max)
        u = u.at[safe].min(v)
    else:  # or, xor — no at[].or_ or at[].xor; serial fold over the stream
        def body(u, iv):
            i, val, ok = iv
            row = _combine(kind, u[i], jnp.where(ok, val, 0))
            return u.at[i].set(row), None
        u, _ = jax.lax.scan(body, u, (safe, v, valid))
    touched = jnp.zeros((table.shape[0],), bool).at[safe].max(valid)
    merged = _apply(kind, table, u, sat_min, sat_max)
    return jnp.where(touched[:, None], merged, table)


def ref_cscatter_serial(table, ids, vals, kind="add", sat_min=0.0,
                        sat_max=0.0):
    """Gold standard: literal serialization of delta-fold + single apply."""
    acc_dtype = (jnp.float32 if jnp.issubdtype(table.dtype, jnp.floating)
                 else table.dtype)
    u = _identity_like(kind, table.astype(acc_dtype))
    touched = jnp.zeros((table.shape[0],), bool)

    def body(carry, iv):
        u, touched = carry
        i, val = iv
        ok = (i >= 0) & (i < table.shape[0])
        safe = jnp.where(ok, i, 0)
        new_row = _combine(kind, u[safe], val.astype(acc_dtype))
        u = u.at[safe].set(jnp.where(ok, new_row, u[safe]))
        touched = touched.at[safe].set(touched[safe] | ok)
        return (u, touched), None

    (u, touched), _ = jax.lax.scan(body, (u, touched), (ids, vals))
    merged = _apply(kind, table, u, sat_min, sat_max)
    return jnp.where(touched[:, None], merged, table)


# ------------------------------------------------------------------ cmerge


def ref_cmerge(table, block_ids, dirty, src, upd, kind="add", sat_min=0.0,
               sat_max=0.0):
    w, br, d = src.shape
    out = table
    for i in range(w):  # static small W
        ok = (block_ids[i] >= 0) & (dirty[i] != 0)
        start = jnp.where(ok, block_ids[i], 0) * br
        mem = jax.lax.dynamic_slice_in_dim(out, start, br, axis=0)
        if kind == "add":
            new = mem + (upd[i] - src[i])
        elif kind == "sat_add":
            s = mem.astype(jnp.float32) + (upd[i].astype(jnp.float32)
                                           - src[i].astype(jnp.float32))
            new = jnp.clip(s, sat_min, sat_max).astype(mem.dtype)
        elif kind == "max":
            new = jnp.maximum(mem, upd[i])
        elif kind == "min":
            new = jnp.minimum(mem, upd[i])
        else:
            new = mem | upd[i]
        new = jnp.where(ok, new, mem)
        out = jax.lax.dynamic_update_slice_in_dim(out, new, start, axis=0)
    return out


# --------------------------------------------------------------- attention


def ref_attention(q, k, v, causal=True):
    """q [B,H,S,d]; k,v [B,KV,T,d] -> [B,H,S,d] (fp32 softmax)."""
    b, h, s, d = q.shape
    n_kv, t = k.shape[1], k.shape[2]
    g = h // n_kv
    qg = q.reshape(b, n_kv, g, s, d).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("bkgsd,bktd->bkgst", qg, kf) / d ** 0.5
    if causal:
        mask = jnp.arange(t)[None, :] <= jnp.arange(s)[:, None]
        scores = jnp.where(mask, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,bktd->bkgsd", p, vf)
    return out.reshape(b, h, s, d).astype(q.dtype)


def ref_decode_attention(q, k, v, position):
    """q [B,H,d]; k,v [B,T,KV,d]; attends to [0, position]."""
    b, h, d = q.shape
    t, n_kv = k.shape[1], k.shape[2]
    g = h // n_kv
    qg = q.reshape(b, n_kv, g, d).astype(jnp.float32)
    kf = jnp.moveaxis(k, 2, 1).astype(jnp.float32)   # [B,KV,T,d]
    vf = jnp.moveaxis(v, 2, 1).astype(jnp.float32)
    scores = jnp.einsum("bkgd,bktd->bkgt", qg, kf) / d ** 0.5
    mask = jnp.arange(t) <= position
    scores = jnp.where(mask, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgt,bktd->bkgd", p, vf)
    return out.reshape(b, h, d).astype(q.dtype)
