"""Grouped matrix products as Mosaic kernels: the routed experts' products.

``grouped_matmul(x, w, sizes)``: the rows of ``x`` (rows, k) lie in groups,
one after the other from row 0, ``sizes[g]`` rows in group ``g``; row ``r``
of group ``g`` gives ``x[r] @ w[g]`` with ``w`` (groups, k, n).  The rows
beyond ``sum(sizes)`` belong to no group: they are read into no result and
what the output holds there is undefined (``parallel/moe.py`` zeroes it).
Operands go to the MXU as they come (bf16 in the cells), sums are float32
over the whole of ``k`` and rounded once to the output's dtype: what XLA's
ragged product gives, which this replaces in the routed layer (PERF.md
PR 33: XLA's own kernels for it ran at a quarter of the MXU on groups of
512-768 rows).  The design is that of ``jax.experimental.pallas.ops.tpu.megablox``.

Two kernels, tied by one ``jax.custom_vjp``:

  * ``grouped_matmul`` (forward, and the gradient with respect to ``x``: the
    same kernel with ``w`` read transposed, no transposed copy in HBM).  The
    grid walks (column tile, row-tile VISIT, k tile).  A visit is one row
    tile under one group: a tile that two groups share is visited once a
    group and each visit stores only its own group's rows; a tile beyond
    ``sum(sizes)`` is never visited (the number of visits is a grid bound
    read on the device), so a chunk's rows beyond them cost nothing.  Which
    tile and which group a visit has comes from scalar-prefetched metadata
    computed from ``sizes`` on the device (``_visits``).  Consecutive visits
    of one group keep the same block of ``w``: with ``k`` whole in a tile an
    expert's matrix crosses HBM once a column tile.
  * ``grouped_matmul_t`` (the gradient with respect to ``w``):
    ``x[g]^T @ dy[g]`` into (groups, k, n), the visits innermost, a float32
    accumulator a (k tile, n tile) that is zeroed where a group begins and
    stored where it ends.  An empty group gets one visit that adds nothing,
    so its gradient is exactly zero.  Rows outside the visit's group are
    zeroed in BOTH operands (what lies beyond the groups may be anything).

Tile sizes follow from the shapes alone (``tiles``).  On non-TPU backends the
same kernels run in interpret mode, so the CPU tests run the code the chip
runs.  Each kernel's wrapper is a ``jax.jit`` of its own: ``model.init`` runs
a layer operation by operation, and the walk's index arithmetic would be a
dozen eager compiles a shape there; and under autodiff the ``jvp(...)`` /
``transpose(...)`` that JAX wraps around the next name of an operation's path
lands on ``jit(_gmm)``, so the kernels' instructions (and the profiler's
events) keep their own names, ``%grouped_matmul.<n>`` and
``%grouped_matmul_t.<n>``, forward and backward.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as _pltpu

# what one kernel may hold of the chip's fast memory (a v5e has 128 MiB; the
# compiler's own default is 16): the tiles below stay under half of it
_VMEM_LIMIT = 64 * 1024 * 1024
# the largest block of an expert's matrix a tile holds, in bytes; two are in
# flight (the next group's is fetched under the current one's products)
_W_BLOCK_BYTES = 6 * 1024 * 1024


class Tiles(NamedTuple):
    """Rows, contraction and columns of one tile."""

    m: int
    k: int
    n: int


def _divisor_tile(size: int, limit: int) -> int:
    """The largest multiple of 128 that divides ``size`` and is at most
    ``limit``; ``size`` itself where it fits or has no such divisor."""
    if size <= limit:
        return size
    for tile in range(limit // 128 * 128, 0, -128):
        if size % tile == 0:
            return tile
    return size


def tiles(rows: int, k: int, n: int, expected: int, dtype) -> Tiles:
    """The tile sizes of ``grouped_matmul`` at these shapes (``k`` the
    contraction, ``n`` the columns; the weight gradient runs on the same
    tiles).  Row tiles of 256 where the ``expected`` group (the rows the
    caller expects a group to hold: the routed layer's slots an expert) is at
    least two of them, else 128: a group's first and last tile are shared
    with its neighbours and computed twice.  ``k`` whole where a (k, n tile)
    block of an expert's matrix fits ``_W_BLOCK_BYTES``, so the block stays
    put while a group's row tiles go by; ``n`` whole where it fits, else the
    widest multiple of 128 that does (a last partial column tile is fine:
    columns do not mix).
    A contraction that needs tiles and has no divisor among the multiples of
    128 is refused: a partial tile of it would add what is not there."""
    itemsize = jnp.dtype(dtype).itemsize
    tm = 256 if expected >= 512 else 128
    tm = min(tm, _round_up(rows, 16))
    words = _W_BLOCK_BYTES // itemsize
    tk = _divisor_tile(k, max(words // min(n, 512), 128))
    if tk * 128 > words:
        raise ValueError(
            f"a contraction of {k} is too long for one tile and no multiple of "
            "128 divides it: pad the width to a multiple of 128")
    tn = n if tk * n <= words else max(words // tk // 128 * 128, 128)
    return Tiles(tm, tk, min(tn, n))


def _round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def visit_counts(rows: int, groups: int, tm: int, size: int) -> Tuple[int, int]:
    """Row-tile visits a product makes when every group holds ``size`` rows,
    and the row tiles of the whole ``rows``: shape arithmetic, for the
    ``moe.rows`` event."""
    visits = sum((g * size + size - 1) // tm - (g * size) // tm + 1
                 for g in range(groups) if size)
    return visits, -(-rows // tm)


def _visits(sizes, rows: int, tm: int, visit_empty: bool):
    """The walk over the row tiles in use: ``(offsets, group, tile, count)``.
    ``offsets`` (groups + 1,) where each group starts; visit ``t < count``
    computes row tile ``tile[t]`` under group ``group[t]``.  A group's visits
    are consecutive and a tile's visits are consecutive.  ``visit_empty``:
    an empty group gets one visit (of the tile its start lies in), so that
    the weight gradient stores its zeros.  The arrays have the static length
    ``row tiles + groups - 1`` (``+ groups`` with empty visits), padded
    with the last visit."""
    groups = sizes.shape[0]
    n_tiles = -(-rows // tm)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // tm, n_tiles - 1)
    per_group = jnp.where(sizes > 0, (ends - 1) // tm - first + 1,
                          1 if visit_empty else 0)
    upto = jnp.cumsum(per_group)
    count = upto[-1]
    length = n_tiles + groups - (0 if visit_empty else 1)
    t = jnp.minimum(jnp.arange(length), jnp.maximum(count - 1, 0))
    group = jnp.minimum(jnp.searchsorted(upto, t, side="right"), groups - 1)
    tile = first[group] + t - (upto - per_group)[group]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets.astype(jnp.int32), group.astype(jnp.int32),
            tile.astype(jnp.int32), count.astype(jnp.int32))


def _bounds(offsets_ref, group, tile, tm: int):
    """Where the visit's group starts and ends, and whether the row tile
    lies whole inside it."""
    start, end = offsets_ref[group], offsets_ref[group + 1]
    whole = jnp.logical_and(start <= tile * tm, (tile + 1) * tm <= end)
    return start, end, whole


def _in_group(start, end, tile, tm: int, width: int):
    """(tm, width) whether the tile's row is one of the group's."""
    row = tile * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, width), 0)
    return jnp.logical_and(row >= start, row < end)


def _gmm_kernel(offsets_ref, group_ref, tile_ref, x_ref, w_ref, o_ref, *scratch,
                tm, tn, k_tiles, transpose_w):
    t, ki = pl.program_id(1), pl.program_id(2)
    contract = (((1,), (1,)), ((), ())) if transpose_w else (((1,), (0,)), ((), ()))
    part = jax.lax.dot_general(x_ref[...], w_ref[...], contract,
                               preferred_element_type=jnp.float32)
    if k_tiles > 1:
        acc_ref, = scratch

        @pl.when(ki == 0)
        def _():
            acc_ref[...] = part

        @pl.when(ki > 0)
        def _():
            acc_ref[...] += part

    @pl.when(ki == k_tiles - 1)
    def _store():
        out = (scratch[0][...] if k_tiles > 1 else part).astype(o_ref.dtype)
        group, tile = group_ref[t], tile_ref[t]
        start, end, whole = _bounds(offsets_ref, group, tile, tm)
        rows = _in_group(start, end, tile, tm, tn)

        @pl.when(whole)
        def _():
            o_ref[...] = out

        # a tile that groups share: each visit stores its own group's rows;
        # the first leaves zeros in the others', the later keep what is there
        revisit = jnp.logical_and(t > 0, tile_ref[jnp.maximum(t - 1, 0)] == tile)

        @pl.when(jnp.logical_and(~whole, revisit))
        def _():
            o_ref[...] = jnp.where(rows, out, o_ref[...])

        @pl.when(jnp.logical_and(~whole, ~revisit))
        def _():
            o_ref[...] = jnp.where(rows, out, jnp.zeros_like(out))


def _tgmm_kernel(offsets_ref, group_ref, tile_ref, x_ref, dy_ref, o_ref, acc_ref,
                 *, tm, tk, tn):
    t = pl.program_id(2)
    last = pl.num_programs(2) - 1
    group, tile = group_ref[t], tile_ref[t]
    before = group_ref[jnp.maximum(t - 1, 0)]
    after = group_ref[jnp.minimum(t + 1, last)]

    @pl.when(jnp.logical_or(t == 0, before != group))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    contract = (((0,), (0,)), ((), ()))
    start, end, whole = _bounds(offsets_ref, group, tile, tm)

    @pl.when(whole)
    def _():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], dy_ref[...], contract, preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(~whole, end > start))
    def _():
        x_rows = _in_group(start, end, tile, tm, tk)
        dy_rows = _in_group(start, end, tile, tm, tn)
        x = jnp.where(x_rows, x_ref[...], jnp.zeros_like(x_ref[...]))
        dy = jnp.where(dy_rows, dy_ref[...], jnp.zeros_like(dy_ref[...]))
        acc_ref[...] += jax.lax.dot_general(
            x, dy, contract, preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_or(t == last, after != group))
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pad_rows(a, tm: int):
    """``a`` with whole row tiles: a partial last tile would read rows that
    are not there into the weight gradient's sums."""
    return jnp.pad(a, ((0, _round_up(a.shape[0], tm) - a.shape[0]), (0, 0)))


@functools.partial(jax.jit, static_argnames=("transpose_w", "tile", "interpret"))
def _gmm(x, w, sizes, transpose_w: bool, tile: Tiles, interpret: bool):
    rows, k = x.shape
    n = w.shape[1] if transpose_w else w.shape[2]
    tm, tk, tn = tile
    if k % tk:
        raise ValueError(
            f"the contraction {k} has to be a multiple of its tile {tk}")
    k_tiles = k // tk
    xp = _pad_rows(x, tm)
    offsets, group, row_tile, count = _visits(sizes, xp.shape[0], tm, False)
    if transpose_w:
        w_spec = pl.BlockSpec((None, tn, tk), lambda j, t, ki, o, g, r: (g[t], j, ki))
    else:
        w_spec = pl.BlockSpec((None, tk, tn), lambda j, t, ki, o, g, r: (g[t], ki, j))
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tn=tn, k_tiles=k_tiles,
                          transpose_w=transpose_w),
        name="grouped_matmul",
        grid_spec=_pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(n, tn), count, k_tiles),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda j, t, ki, o, g, r: (r[t], ki)),
                w_spec,
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, t, ki, o, g, r: (r[t], j)),
            scratch_shapes=(
                [_pltpu.VMEM((tm, tn), jnp.float32)] if k_tiles > 1 else []),
        ),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], n), x.dtype),
        compiler_params=_pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(offsets, group, row_tile, xp, w)
    return out[:rows]


@functools.partial(jax.jit, static_argnames=("groups", "dtype", "tile", "interpret"))
def _tgmm(x, dy, sizes, groups: int, dtype, tile: Tiles, interpret: bool):
    k, n = x.shape[1], dy.shape[1]
    tm, tk, tn = tile
    xp, dyp = _pad_rows(x, tm), _pad_rows(dy, tm)
    offsets, group, row_tile, count = _visits(sizes, xp.shape[0], tm, True)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, tk=tk, tn=tn),
        name="grouped_matmul_t",
        grid_spec=_pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(k, tk), pl.cdiv(n, tn), count),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda i, j, t, o, g, r: (r[t], i)),
                pl.BlockSpec((tm, tn), lambda i, j, t, o, g, r: (r[t], j)),
            ],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda i, j, t, o, g, r: (g[t], i, j)),
            scratch_shapes=[_pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), dtype),
        compiler_params=_pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(offsets, group, row_tile, xp, dyp)


def _product(x, w, sizes, transpose_w, expected, interpret):
    n = w.shape[1] if transpose_w else w.shape[2]
    tile = tiles(x.shape[0], x.shape[1], n, expected, x.dtype)
    return _gmm(x, w, sizes, transpose_w, tile, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _grouped(x, w, sizes, transpose_w, expected, interpret):
    return _product(x, w, sizes, transpose_w, expected, interpret)


def _grouped_fwd(x, w, sizes, transpose_w, expected, interpret):
    return _product(x, w, sizes, transpose_w, expected, interpret), (x, w, sizes)


def _grouped_bwd(transpose_w, expected, interpret, residuals, dy):
    x, w, sizes = residuals
    dx = _product(dy, w, sizes, not transpose_w, expected, interpret)
    # w is (groups, k, n), or transposed (groups, n, k): dy[g]^T @ x[g] then
    rows, cols = (dy, x) if transpose_w else (x, dy)
    tile = tiles(x.shape[0], rows.shape[1], cols.shape[1], expected, x.dtype)
    dw = _tgmm(rows, cols, sizes, w.shape[0], w.dtype, tile, interpret)
    return dx, dw, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(x, w, sizes, *, expected: Optional[int] = None,
                   transpose_w: bool = False, interpret: Optional[bool] = None):
    """``x[r] @ w[g]`` for every row ``r`` of group ``g``: ``x`` (rows, k),
    ``w`` (groups, k, n) or, with ``transpose_w``, (groups, n, k) read
    transposed, ``sizes`` (groups,) int32 with ``sum(sizes) <= rows``;
    ``expected``: the rows a group is expected to hold, for the tiles
    (``tiles``; the rows over the groups where none is given).
    Returns (rows, n) in ``x``'s dtype; undefined beyond ``sum(sizes)``.
    Differentiable in ``x`` and ``w`` (the module's text)."""
    if x.ndim != 2 or w.ndim != 3 or sizes.shape != (w.shape[0],):
        raise ValueError(
            f"grouped_matmul takes x (rows, k), w (groups, k, n) and sizes "
            f"(groups,), got {x.shape}, {w.shape}, {sizes.shape}")
    if x.dtype != w.dtype:
        raise ValueError(f"x is {x.dtype} and w is {w.dtype}: one dtype")
    if w.shape[2 if transpose_w else 1] != x.shape[1]:
        raise ValueError(f"x {x.shape} and w {w.shape} (read transposed: "
                         f"{transpose_w}) do not contract")
    if interpret is None:   # here, not under the wrappers' jit: a trace is kept
        interpret = jax.default_backend() != "tpu"
    if expected is None:
        expected = x.shape[0] // w.shape[0]
    return _grouped(x, w, sizes.astype(jnp.int32), transpose_w, expected, interpret)
