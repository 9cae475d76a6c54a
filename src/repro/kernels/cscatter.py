"""cscatter: commutative scatter-update with on-demand VMEM privatization.

The CCache flagship kernel. Computes, for a table ``T[R, D]`` and a stream
of COps ``(ids[N], vals[N, D])``:

    T[ids[n]] = apply(T[ids[n]], fold(combine, identity, vals where id matches))

i.e. the paper's privatize-and-merge semantics: all contributions to a row are
combined into a *private delta* first, and the delta is merged into memory
once — ``apply`` observes the memory copy (paper §4.5), which is what makes
saturating merges correct.

TPU mapping of the paper's hardware:

* grid = (table blocks, token chunks). The VMEM scratch accumulator tile
  ``acc[block_rows, D]`` is the privatized *update copy* (the L1 line); it
  persists across the token-chunk grid dimension and is **merged exactly once
  per table block, when the grid leaves the block** — merge-on-evict realized
  as proactive scheduling.
* the ADD path turns the random scatter into a dense one-hot matmul
  ``onehot(ids)ᵀ @ vals`` on the MXU. Float tables accumulate in f32. Integer
  tables split each value into four byte planes: a plane's entries (0..255)
  and the one-hot (0/1) are exact in the MXU's operand formats, a chunk's
  plane sums stay below 2**24 and so are exact in f32, and the planes
  recombine with int32 shifts — bitwise equal to ``.at[].add``, wrap-around
  included.
* MAX/MIN/OR have no MXU form: a serial fold over the chunk reads each id as
  a scalar from SMEM and updates one accumulator row at a time.
* per-row ``touched`` masks implement the paper's dirty-merge optimization:
  rows never written are merged as the identity (left bit-exact), and a block
  whose mask stays empty writes memory back unchanged.

When no tile is given, :func:`choose_tile` picks one from the shapes and
:data:`VMEM_BUDGET`. Out-of-range and negative ids are ignored (the padding
convention).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MERGE_KINDS = ("add", "sat_add", "max", "min", "or")

# VMEM the kernel's tiles may take: half of the 16 MiB scoped default on
# v5e, leaving room for Mosaic's own internal scratch.
VMEM_BUDGET = 8 << 20
_LANES = 128
# serial-fold chunks: ids per SMEM block
_SCALAR_CHUNK = 1024
# one-hot chunks: the [block_rows, chunk] one-hot is the largest tile
_MATMUL_CHUNK = 512


def _is_float(dtype) -> bool:
    return jnp.issubdtype(dtype, jnp.floating)


def _is_matmul(kind: str) -> bool:
    return kind in ("add", "sat_add")


def _identity(kind: str, dtype):
    if kind in ("add", "sat_add"):
        return jnp.zeros((), dtype)
    if kind == "max":
        return jnp.asarray(jnp.finfo(dtype).min if _is_float(dtype)
                           else jnp.iinfo(dtype).min, dtype)
    if kind == "min":
        # iinfo covers unsigned dtypes too (identity = dtype's max value).
        return jnp.asarray(jnp.finfo(dtype).max if _is_float(dtype)
                           else jnp.iinfo(dtype).max, dtype)
    if kind == "or":
        return jnp.zeros((), dtype)
    raise ValueError(kind)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def tile_bytes(kind: str, block_rows: int, chunk: int, d: int) -> int:
    """VMEM bytes one grid step holds: every row of a ``(rows, d)`` tile is
    padded to whole 128-lane words, pipelined operands are double-buffered,
    and the ADD path adds its ``[block_rows, chunk]`` one-hot (mask + f32)."""
    row = _round_up(d, _LANES) * 4
    b = 2 * 2 * block_rows * row        # table in + out blocks
    b += block_rows * row               # accumulator
    b += block_rows * _LANES * 4        # touched mask
    b += 2 * chunk * row                # vals block
    b += 2 * _round_up(chunk, _LANES) * 4  # ids block
    if _is_matmul(kind):
        b += 2 * block_rows * chunk * 4  # one-hot mask + its f32 copy
    return b


def choose_tile(kind: str, r: int, n: int, d: int) -> tuple[int, int]:
    """``(block_rows, chunk)`` for an ``[r, d]`` table and ``n`` updates.

    ``chunk`` is a multiple of 128 (the update stream is padded up to it).
    ``block_rows`` is ``r`` when the whole table fits ``VMEM_BUDGET``; else
    the largest multiple of 8 within the budget and at least half the most
    that fits that divides ``r``; else the most that fits, and the caller
    pads the table to whole tiles."""
    cap = _MATMUL_CHUNK if _is_matmul(kind) else _SCALAR_CHUNK
    chunk = min(cap, _round_up(max(n, 1), _LANES))
    if tile_bytes(kind, r, chunk, d) <= VMEM_BUDGET:
        return r, chunk
    fixed = tile_bytes(kind, 0, chunk, d)   # tile_bytes is affine in rows
    most = (VMEM_BUDGET - fixed) // (tile_bytes(kind, 1, chunk, d) - fixed)
    most -= most % 8
    if most < 8:
        raise ValueError(f"no {kind} tile of {chunk} updates x {d} columns "
                         f"fits {VMEM_BUDGET} bytes of VMEM")
    for br in range(most, most // 2, -8):
        if r % br == 0:
            return br, chunk
    return most, chunk


def _kernel(ids_ref, vals_ref, table_ref, out_ref, acc_ref, touched_ref, *,
            kind: str, block_rows: int, chunk: int, n_chunks: int,
            sat_min: float, sat_max: float, acc_dtype):
    i = pl.program_id(0)   # table block
    j = pl.program_id(1)   # token chunk
    base = i * block_rows

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, _identity(kind, acc_dtype))
        touched_ref[...] = jnp.zeros_like(touched_ref)

    if _is_matmul(kind):
        rel = ids_ref[...] - base                        # [1, chunk] i32
        in_block = (rel >= 0) & (rel < block_rows)
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_rows, chunk), 0)
        oh = (rows == jnp.where(in_block, rel, -1)
              ).astype(jnp.float32)                      # [block_rows, chunk]
        vals = vals_ref[...]                             # [chunk, D]
        if _is_float(acc_dtype):
            acc_ref[...] += jax.lax.dot(
                oh, vals.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32).astype(acc_dtype)
        else:
            v = vals.astype(jnp.int32)
            total = jnp.zeros(acc_ref.shape, jnp.int32)
            for k in range(4):
                plane = (jax.lax.shift_right_logical(v, 8 * k) & 0xFF
                         ).astype(jnp.float32)
                s = jax.lax.dot(oh, plane,
                                preferred_element_type=jnp.float32)
                total += jax.lax.shift_left(s.astype(jnp.int32), 8 * k)
            acc_ref[...] += total.astype(acc_dtype)
        touched_ref[...] = jnp.maximum(
            touched_ref[...], jnp.max(oh, axis=1, keepdims=True))
    else:
        def body(c, carry):
            r = ids_ref[c] - base

            @pl.when((r >= 0) & (r < block_rows))
            def _fold():
                cur = acc_ref[pl.ds(r, 1), :]
                v = vals_ref[pl.ds(c, 1), :].astype(acc_dtype)
                if kind == "max":
                    new = jnp.maximum(cur, v)
                elif kind == "min":
                    new = jnp.minimum(cur, v)
                else:
                    new = cur | v
                acc_ref[pl.ds(r, 1), :] = new
                touched_ref[pl.ds(r, 1), :] = jnp.ones((1, 1), jnp.float32)

            return carry

        jax.lax.fori_loop(0, chunk, body, 0)

    @pl.when(j == n_chunks - 1)
    def _evict_merge():
        mem = table_ref[...]
        u = acc_ref[...]
        if kind == "add":
            new = mem + u.astype(mem.dtype)
        elif kind == "sat_add":
            s = mem.astype(jnp.float32) + u.astype(jnp.float32)
            s = jnp.clip(s, sat_min, sat_max)
            new = s.astype(mem.dtype)
        elif kind == "max":
            new = jnp.maximum(mem, u.astype(mem.dtype))
        elif kind == "min":
            new = jnp.minimum(mem, u.astype(mem.dtype))
        else:  # or
            new = mem | u.astype(mem.dtype)
        out_ref[...] = jnp.where(touched_ref[...] > 0, new, mem)  # dirty skip


@functools.partial(
    jax.jit,
    static_argnames=("kind", "block_rows", "chunk", "sat_min", "sat_max",
                     "interpret"))
def cscatter(table: jax.Array, ids: jax.Array, vals: jax.Array, *,
             kind: str = "add", block_rows: Optional[int] = None,
             chunk: Optional[int] = None,
             sat_min: float = 0.0, sat_max: float = 0.0,
             interpret: Optional[bool] = None) -> jax.Array:
    """table [R, D]; ids i32 [N]; vals [N, D] -> updated table [R, D].

    ``block_rows``/``chunk`` default to :func:`choose_tile`. ``interpret=None``
    resolves from the backend: compile on TPU, run the Pallas interpreter
    elsewhere (CPU/host meshes), matching ``ops.py``.
    """
    assert kind in MERGE_KINDS, kind
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    r, d = table.shape
    n = ids.shape[0]
    assert vals.shape == (n, d), (vals.shape, n, d)
    if block_rows is None or chunk is None:
        br, ch = choose_tile(kind, r, n, d)
        block_rows = block_rows or br
        chunk = chunk or ch
    block_rows = min(block_rows, r)
    r_pad = _round_up(r, block_rows)    # whole tiles; padded rows untouched
    n_pad = _round_up(max(n, 1), chunk)
    ids = ids.astype(jnp.int32)
    ids = jnp.where(ids < r, ids, -1)   # padded table rows stay untouched
    if n_pad != n:
        ids = jnp.pad(ids, (0, n_pad - n), constant_values=-1)
        vals = jnp.pad(vals, ((0, n_pad - n), (0, 0)))
    mem = table if r_pad == r else jnp.pad(table, ((0, r_pad - r), (0, 0)))
    ni, nj = r_pad // block_rows, n_pad // chunk
    acc_dtype = jnp.float32 if _is_float(table.dtype) else table.dtype

    kernel = functools.partial(
        _kernel, kind=kind, block_rows=block_rows, chunk=chunk, n_chunks=nj,
        sat_min=sat_min, sat_max=sat_max, acc_dtype=acc_dtype)
    if _is_matmul(kind):
        # a lane-major [1, N] row: the one-hot compares it against sublanes
        ids_spec = pl.BlockSpec((1, chunk), lambda i, j: (0, j))
        ids = ids[None, :]
    else:
        ids_spec = pl.BlockSpec((chunk,), lambda i, j: (j,),
                                memory_space=pltpu.SMEM)

    out = pl.pallas_call(
        kernel,
        grid=(ni, nj),
        in_specs=[
            ids_spec,                                         # ids
            pl.BlockSpec((chunk, d), lambda i, j: (j, 0)),    # vals
            pl.BlockSpec((block_rows, d), lambda i, j: (i, 0)),  # table (mem)
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r_pad, d), table.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_rows, d), acc_dtype),           # update copy
            pltpu.VMEM((block_rows, 1), jnp.float32),         # dirty bits
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(ids, vals, mem)
    return out if r_pad == r else out[:r]
