"""cscatter: commutative scatter-update with on-demand VMEM privatization.

The CCache flagship kernel. Computes, for a table ``T[R, D]`` and a stream
of COps ``(ids[N], vals[N, D])``:

    T[ids[n]] = apply(T[ids[n]], fold(combine, identity, vals where id matches))

i.e. the paper's privatize-and-merge semantics: all contributions to a row are
combined into a *private delta* first, and the delta is merged into memory
once — ``apply`` observes the memory copy (paper §4.5), which is what makes
saturating merges correct.

TPU mapping of the paper's hardware:

* the grid walks a *work list* built on the device from the ids: the ids
  are sorted (with their values) and cut into aligned chunks, and one work
  item is a (table block, chunk) pair where that chunk holds at least one
  id of that block. Items of one block are consecutive, so the VMEM
  scratch accumulator ``acc[block_rows, D]`` — the privatized *update
  copy* (the L1 line) — is initialised on a block's first item and
  **merged exactly once per table block, on its last item** (merge-on-evict
  realized as proactive scheduling), however many chunks its ids span.
  The list, its length and each item's block and chunk are scalar-prefetch
  operands; the static grid is the bound ``N/chunk + min(R/block_rows,
  N)``, and steps past the list repeat the last item (no DMA) and do
  nothing. A table that is one block skips the sort: its list is the plain
  chunk sweep.
* the table is updated in place (its operand aliases the output): a block
  no update touches is never read or written, so a call costs the blocks
  it touches, not the table's size.
* the ADD path turns the random scatter into a dense one-hot matmul
  ``onehot(ids)ᵀ @ vals`` on the MXU. Float tables accumulate in f32. Integer
  tables split each value into four byte planes: a plane's entries (0..255)
  and the one-hot (0/1) are exact in the MXU's operand formats, a chunk's
  plane sums stay below 2**24 and so are exact in f32, and the planes
  recombine with int32 shifts — bitwise equal to ``.at[].add``, wrap-around
  included.
* MAX/MIN/OR/XOR have no MXU form: a serial fold over the item's own ids
  (its run of the sorted chunk) reads each id as a scalar from SMEM and
  updates one accumulator row at a time.
* per-row ``touched`` masks implement the paper's dirty-merge optimization:
  rows of a visited block that no update wrote are merged as the identity
  (left bit-exact).

When no tile is given, :func:`choose_tile` picks one from the shapes and
:data:`VMEM_BUDGET`. :func:`visits` counts a call's work items. Out-of-range
and negative ids are ignored (the padding convention).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MERGE_KINDS = ("add", "sat_add", "max", "min", "or", "xor")

# VMEM the kernel's tiles may take: half of the 16 MiB scoped default on
# v5e, leaving room for Mosaic's own internal scratch.
VMEM_BUDGET = 8 << 20
_LANES = 128
# serial-fold chunks: ids per SMEM block
_SCALAR_CHUNK = 1024
# one-hot chunks: the [block_rows, chunk] one-hot is the largest tile
_MATMUL_CHUNK = 512
# the tile of a sparse call (fewer ids than blocks of this many rows): a
# step costs its block's DMA and one-hot, whatever the table's size, and
# on v5e it took 1.6 us at 256 x 128 against 4.5 us at 1024 x 512 with
# the same 1,024 ids over 2^23 x 4 int32 rows
_SPARSE_ROWS = 256
_SPARSE_CHUNK = 128


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
    if kind in ("or", "xor"):
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
    A sparse call — fewer ids than the table has blocks of
    ``_SPARSE_ROWS`` rows, so most blocks go untouched — takes a block of a
    multiple of 8 rows in ``(_SPARSE_ROWS / 2, _SPARSE_ROWS]`` that divides
    ``r``, where there is one, and the ADD path a ``_SPARSE_CHUNK`` chunk
    (the serial fold visits only a block's own ids, whatever the chunk,
    and reads them from SMEM in blocks of ``_SCALAR_CHUNK``). Otherwise
    ``block_rows`` is ``r`` when the whole table fits ``VMEM_BUDGET``;
    else the largest multiple of 8 within the budget and at least half the
    most that fits that divides ``r``; else the most that fits, and the
    caller pads the table to whole tiles."""
    sparse = n * _SPARSE_ROWS < r
    cap = (_SCALAR_CHUNK if not _is_matmul(kind)
           else _SPARSE_CHUNK if sparse else _MATMUL_CHUNK)
    chunk = min(cap, _round_up(max(n, 1), _LANES))
    if sparse:
        for br in range(_SPARSE_ROWS, _SPARSE_ROWS // 2, -8):
            if r % br == 0 and tile_bytes(kind, br, chunk, d) <= VMEM_BUDGET:
                return br, chunk
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


def _work_list(ids: jax.Array, r: int, block_rows: int, chunk: int):
    """The sorted stream and its work list, on the device.

    ``ids`` i32 [n_pad] (``n_pad`` a multiple of ``chunk``) for a table of
    ``r`` rows in blocks of ``block_rows``. Returns ``(ids, order,
    item_block, item_at, n_items)``: the ids in the kernel's order, with
    invalid ones set to a sentinel beyond every block; the permutation that
    puts the values in that order (``None`` when the stream keeps its
    order); each item's table block and the position of its first id in
    the stream (its chunk is ``item_at // chunk``), padded to the static
    grid by repeating the last item; and the number of items with an id in
    them. A one-block table keeps the stream's order and sweeps every
    chunk."""
    n_pad = ids.shape[0]
    nb, nj = -(-r // block_rows), n_pad // chunk
    sentinel = nb * block_rows
    ids = jnp.where((ids >= 0) & (ids < r), ids, sentinel)
    if nb == 1:
        return (ids, None, jnp.zeros((nj,), jnp.int32),
                jnp.arange(0, n_pad, chunk, dtype=jnp.int32), jnp.int32(nj))
    pos = jnp.arange(n_pad, dtype=jnp.int32)
    ids, order = jax.lax.sort_key_val(ids, pos)
    blk = ids // block_rows
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), blk[:-1]])
    start = ((pos % chunk == 0) | (blk != prev)) & (blk < nb)
    n_items = jnp.sum(start, dtype=jnp.int32)
    grid = nj + min(nb, n_pad)
    (at,) = jnp.nonzero(start, size=grid, fill_value=0)
    # steps past the list repeat its last item (an empty list runs the
    # last block against chunk 0, whose ids are all sentinels: a no-op
    # merge)
    at = at[jnp.minimum(jnp.arange(grid), jnp.maximum(n_items, 1) - 1)]
    return (ids, order, jnp.minimum(blk[at], nb - 1), at.astype(jnp.int32),
            n_items)


def visits(ids: jax.Array, r: int, d: int, kind: str = "add") -> jax.Array:
    """Work items ``cscatter`` runs for ``ids`` into an ``[r, d]`` table at
    the default tile: one per (table block, chunk) pair that holds an id of
    the block, or every chunk of a one-block table."""
    n = ids.shape[0]
    block_rows, chunk = choose_tile(kind, r, n, d)
    block_rows = min(block_rows, r)
    n_pad = _round_up(max(n, 1), chunk)
    ids = jnp.pad(ids.astype(jnp.int32), (0, n_pad - n), constant_values=-1)
    return _work_list(ids, r, block_rows, chunk)[4]


def _kernel(item_block_ref, item_at_ref, n_items_ref, ids_ref, vals_ref,
            table_ref, out_ref, acc_ref, touched_ref, *, kind: str,
            block_rows: int, chunk: int, sat_min: float, sat_max: float,
            acc_dtype):
    k = pl.program_id(0)
    last_step = pl.num_programs(0) - 1
    n_items = n_items_ref[0]
    n_run = jnp.maximum(n_items, 1)   # an empty list runs one no-op item
    live = k < n_run
    blk = item_block_ref[k]
    base = blk * block_rows
    first = (k == 0) | (item_block_ref[jnp.maximum(k - 1, 0)] != blk)
    last = (k == n_run - 1) | (
        item_block_ref[jnp.minimum(k + 1, last_step)] != blk)

    @pl.when(live & first)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, _identity(kind, acc_dtype))
        touched_ref[...] = jnp.zeros_like(touched_ref)

    @pl.when(live)
    def _fold_chunk():
        if _is_matmul(kind):
            rel = ids_ref[...] - base                    # [1, chunk] i32
            in_block = (rel >= 0) & (rel < block_rows)
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_rows, chunk), 0)
            oh = (rows == jnp.where(in_block, rel, -1)
                  ).astype(jnp.float32)                  # [block_rows, chunk]
            vals = vals_ref[...]                         # [chunk, D]
            if _is_float(acc_dtype):
                acc_ref[...] += jax.lax.dot(
                    oh, vals.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32).astype(acc_dtype)
            else:
                v = vals.astype(jnp.int32)
                total = jnp.zeros(acc_ref.shape, jnp.int32)
                for p in range(4):
                    plane = (jax.lax.shift_right_logical(v, 8 * p) & 0xFF
                             ).astype(jnp.float32)
                    s = jax.lax.dot(oh, plane,
                                    preferred_element_type=jnp.float32)
                    total += jax.lax.shift_left(s.astype(jnp.int32), 8 * p)
                acc_ref[...] += total.astype(acc_dtype)
            touched_ref[...] = jnp.maximum(
                touched_ref[...], jnp.max(oh, axis=1, keepdims=True))
        else:
            # the item's ids: from its first to the next item's first, or
            # to the end of the chunk
            at = item_at_ref[k]
            c0 = at - at % chunk
            nxt = item_at_ref[jnp.minimum(k + 1, last_step)]
            hi = jnp.where((k + 1 < n_items) & (nxt < c0 + chunk), nxt,
                           c0 + chunk)

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
                    elif kind == "xor":
                        new = cur ^ v
                    else:
                        new = cur | v
                    acc_ref[pl.ds(r, 1), :] = new
                    touched_ref[pl.ds(r, 1), :] = jnp.ones((1, 1),
                                                           jnp.float32)

                return carry

            jax.lax.fori_loop(at - c0, hi - c0, body, 0)

    @pl.when(live & last)
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
        elif kind == "xor":
            new = mem ^ u.astype(mem.dtype)
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
    elsewhere (CPU/host meshes), matching ``ops.py``. The table's buffer is
    the output's: donate it, or pass a temporary, to update in place.
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
    ids = jnp.pad(ids.astype(jnp.int32), (0, n_pad - n), constant_values=-1)
    vals = jnp.pad(vals, ((0, n_pad - n), (0, 0)))
    ids, order, item_block, item_at, n_items = _work_list(
        ids, r, block_rows, chunk)
    if order is not None:
        vals = vals[order]
    mem = table if r_pad == r else jnp.pad(table, ((0, r_pad - r), (0, 0)))
    acc_dtype = jnp.float32 if _is_float(table.dtype) else table.dtype

    kernel = functools.partial(
        _kernel, kind=kind, block_rows=block_rows, chunk=chunk,
        sat_min=sat_min, sat_max=sat_max, acc_dtype=acc_dtype)
    if _is_matmul(kind):
        # a lane-major [1, N] row: the one-hot compares it against sublanes
        ids_spec = pl.BlockSpec((1, chunk),
                                lambda k, b, a, n: (0, a[k] // chunk))
        ids = ids[None, :]
    else:
        ids_spec = pl.BlockSpec((chunk,), lambda k, b, a, n: (a[k] // chunk,),
                                memory_space=pltpu.SMEM)
    block = pl.BlockSpec((block_rows, d), lambda k, b, a, n: (b[k], 0))

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(item_block.shape[0],),
            in_specs=[
                ids_spec,                                          # ids
                pl.BlockSpec((chunk, d),
                             lambda k, b, a, n: (a[k] // chunk, 0)),  # vals
                block,                                             # table
            ],
            out_specs=block,
            scratch_shapes=[
                pltpu.VMEM((block_rows, d), acc_dtype),        # update copy
                pltpu.VMEM((block_rows, 1), jnp.float32),      # dirty bits
            ]),
        out_shape=jax.ShapeDtypeStruct((r_pad, d), table.dtype),
        input_output_aliases={5: 0},    # the table, in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="cscatter",
    )(item_block, item_at, n_items[None], ids, vals, mem)
    return out if r_pad == r else out[:r]
