"""Flash attention (GQA, causal/bidirectional) — blockwise online softmax.

HBM->VMEM tiling: q tile [bq, d] stays resident across the kv grid dimension;
k/v stream through in [bk, d] tiles; the running (m, l, acc) online-softmax
state lives in VMEM scratch. Matmul dims padded/aligned to the MXU by block
size choice (multiples of 128 for real shapes). Fully-masked causal blocks
are skipped with pl.when (structural analog of the causal block-sparsity the
GPU kernel gets from early exit).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, bq: int, bk: int, n_kv: int):
    i = pl.program_id(2)   # q block
    j = pl.program_id(3)   # kv block

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = (not causal) or (j * bk <= i * bq + bq - 1)

    @pl.when(run)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)           # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)           # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, bq: int = 512, bk: int = 512,
                    interpret: bool = True) -> jax.Array:
    """q [B,H,S,d]; k,v [B,KV,T,d] (KV divides H) -> out [B,H,S,d]."""
    b, h, s, d = q.shape
    _, n_kv, t, _ = k.shape
    assert h % n_kv == 0, (h, n_kv)
    bq = min(bq, s)
    bk = min(bk, t)
    assert s % bq == 0 and t % bk == 0, (s, bq, t, bk)
    scale = 1.0 / d ** 0.5

    kernel = functools.partial(_kernel, scale=scale, causal=causal, bq=bq,
                               bk=bk, n_kv=n_kv)
    kv_idx = lambda bi, hi, i, j: (bi, hi * n_kv // h, j, 0)
    return pl.pallas_call(
        kernel,
        grid=(b, h, s // bq, t // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, i, j: (bi, hi, i, 0)),
            pl.BlockSpec((1, 1, bk, d), kv_idx),
            pl.BlockSpec((1, 1, bk, d), kv_idx),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bi, hi, i, j: (bi, hi, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # m
            pltpu.VMEM((bq, 1), jnp.float32),   # l
            pltpu.VMEM((bq, d), jnp.float32),   # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
