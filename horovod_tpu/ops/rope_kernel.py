"""The rotary step of q and k as one Mosaic kernel pair: ONE pass over HBM a
direction, on whole heads, in the operand's own dtype and in the flash kernels'
own layout.

``models.transformer.rope`` rotates the split-half pairs of a head's first
``rot`` columns in float32 and rounds once:

    x1, x2 = x[..., :rot/2], x[..., rot/2:rot]
    out    = [x1 cos - x2 sin | x2 cos + x1 sin | x[..., rot:]]

Written in ``jnp`` that is a float32 copy of q, two ``rot / 2``-wide halves
(each padded to 128 lanes on the chip) and a concatenate through HBM: 0.9 GB
moved for a q of 134 MB (PERF.md section 6, PR 40).  The same arithmetic on a
WHOLE head, 128 lanes at a time, needs neither the split nor the concatenate:

    out = x * C + partner(x) * S       float32, rounded to x's dtype
    C   = [cos | cos | 1 ...],  S = [-sin | sin | 0 ...]      (B, S, blk) float32
    partner(x)[j] = x[j + rot/2]  (j < rot/2),  x[j - rot/2]  (rot/2 <= j < rot)

``partner`` is a lane rotation inside the head's first ``blk = 128
ceil(rot / 128)`` columns (one ``pltpu.roll`` where ``rot == blk``, two and a
lane mask where the rotation is partial); columns past ``blk`` are copied.  The
two tables stay XLA's (a few MB, made once a layer type, YaRN's factor folded
in): ``tables``.

  ``rope_fwd``   q or k HEAD-MAJOR in and out: a program takes an ``(H, rows,
      D)`` block of the ``(B H, S, D)`` array and writes the same block rotated,
      a head at a time.  Head-major is what ``ops/flash_attention.py`` ``_fold``
      makes of q and k before every kernel, and what XLA's projection product
      writes (and its backward reads) at no cost: ``rotate`` takes and returns
      the logical ``(B, S, H, D)`` and XLA cancels the transposes, so nothing
      is relaid between the projection, this pass and the flash kernels.
      (Token-major ``(B, S, H D)`` rows on the projection's side were tried
      first: their 4-D view is another tiling on the chip, and the backward paid
      a relayout of q's size: PERF.md section 6, PR 40.)
  ``rope_bwd``   the rotation is linear, so the cotangent is the same formula
      with ``S`` negated: from the flash kernels' dq, dk to the projections'
      backward.  Residuals: the two tables.  Positions take no gradient.

**Where it engages** (``engages``, the one statement of it): heads a multiple
of 128 lanes wide, ``rot`` even and no wider than the head, a row count that
16-row sublane tiles divide (no padded copy of q).  Everything else (64-wide
heads, latent attention's 64-wide rotary slice of a 192-wide head, a decode
step's one row) keeps ``rope``; so do 'dot' models, the tests' oracle.  On
non-TPU backends the kernels run in interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as _pltpu

_LANES = 128
# rows of a bf16 sublane tile: what a row tile is a multiple of
_SUBLANES = 16
# a tile of rows, in bytes of the operand, and in rows at most: a program moves
# a head's rows as one piece of ``rows x D`` elements, and pieces under 64 KB cost
# more than their bytes (q and k of 64 heads alone: 0.44 ms at 256 rows where 128
# take 0.48; of 48 heads 0.73 at 64 rows and 1.35 at 32; PERF.md section 6, PR 40)
_TILE_BYTES = 4 * 1024 * 1024
_TILE_ROWS = 512
# beside the pipeline's buffers: the float32 values of a head, Mosaic's own
_VMEM_SLACK = 4 * 1024 * 1024


def _block(rot: int) -> int:
    """Columns of a head that the rotation touches: whole lane tiles."""
    return -(-rot // _LANES) * _LANES


def engages(shape, rot: int) -> bool:
    """Whether x ``(B, S, H, D)`` rotated on its first ``rot`` columns takes
    the kernels: the lane arithmetic's needs (the module's text)."""
    if len(shape) != 4:
        return False
    _, s, _, d = shape
    return d % _LANES == 0 and rot % 2 == 0 and 0 < rot <= d and s % _SUBLANES == 0


def tile_rows(s: int, width: int, itemsize: int) -> int:
    """Rows a program: the largest multiple of 16 that divides ``s`` (itself one)
    within ``_TILE_BYTES`` of ``width`` columns and ``_TILE_ROWS``."""
    most = min(s, _TILE_ROWS, _TILE_BYTES // (width * itemsize))
    return next((rows for rows in range(most - most % _SUBLANES, _SUBLANES, -_SUBLANES)
                 if s % rows == 0), _SUBLANES)


def counts(shape, kv_heads: int, rot: int, itemsize: int) -> dict:
    """``rope.rotate``'s shape arithmetic for q of ``shape`` (B, S, H, D) and k at
    ``kv_heads``: q's rows a program, both tensors' programs and what a pass
    moves (each read and written once, the two tables read once a tensor)."""
    b, s, h, d = shape
    rows = [tile_rows(s, n * d, itemsize) for n in (h, kv_heads)]
    return {"row_tile": rows[0], "programs": sum(b * s // r for r in rows),
            "hbm_bytes": 2 * b * s * (h + kv_heads) * d * itemsize
                         + 2 * 2 * b * s * _block(rot) * 4}


def tables(positions, freqs, rot: int, scale: Optional[float] = None):
    """``C``, ``S`` (B, S, blk) float32 from ``positions`` (B, S) and the ``rot
    / 2`` frequencies, as ``rope`` makes cos and sin (``scale``: YaRN's factor
    on both)."""
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    rest = (*angles.shape[:-1], _block(rot) - rot)
    return (jnp.concatenate([cos, cos, jnp.ones(rest, jnp.float32)], axis=-1),
            jnp.concatenate([-sin, sin, jnp.zeros(rest, jnp.float32)], axis=-1))


def _kernel(x_ref, c_ref, s_ref, o_ref, *, rot, backward):
    """``x_ref``, ``o_ref`` (H, rows, D), a head at a time; the cotangent's pass
    (``backward``) negates ``S``."""
    f32 = jnp.float32
    heads, _, d = x_ref.shape
    blk, half = _block(rot), rot // 2
    c, s = c_ref[0], s_ref[0]
    if backward:
        s = -s
    first = None
    if rot < blk:
        first = jax.lax.broadcasted_iota(jnp.int32, c.shape, 1) < half

    def head(h, carry):
        x = x_ref[h, :, :blk].astype(f32)
        partner = _pltpu.roll(x, half, 1)
        if first is not None:
            partner = jnp.where(first, _pltpu.roll(x, blk - half, 1), partner)
        o_ref[h, :, :blk] = (x * c + partner * s).astype(o_ref.dtype)
        if blk < d:
            o_ref[h, :, blk:] = x_ref[h, :, blk:]
        return carry

    jax.lax.fori_loop(0, heads, head, 0)


@functools.partial(jax.jit, static_argnames=("heads", "rot", "rows", "interpret", "backward"))
def _call(x, c, s, heads, rot, rows, interpret, backward):
    """``x`` (B H, S, D) -> the same shape, a program a (sequence, row tile)."""
    _, t, d = x.shape
    blk = c.shape[-1]
    tile = pl.BlockSpec((heads, rows, d), lambda b, i: (b, i, 0))
    table = pl.BlockSpec((1, rows, blk), lambda b, i: (b, i, 0))
    tile_bytes = rows * heads * d * x.dtype.itemsize
    kernel = functools.partial(_kernel, rot=rot, backward=backward)
    spec = dict(
        grid=(x.shape[0] // heads, t // rows),
        in_specs=[tile, table, table],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=_pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # both tiles and both tables twice (the pipeline's two buffers)
            vmem_limit_bytes=4 * tile_bytes + 16 * rows * blk + _VMEM_SLACK),
        interpret=interpret)
    if backward:
        return pl.pallas_call(kernel, name="rope_bwd", **spec)(x, c, s)
    return pl.pallas_call(kernel, name="rope_fwd", **spec)(x, c, s)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rotated(x, c, s, static):
    return _call(x, c, s, *static, backward=False)


def _rotated_fwd(x, c, s, static):
    return _call(x, c, s, *static, backward=False), (c, s)


def _rotated_bwd(static, residuals, g):
    c, s = residuals
    return _call(g, c, s, *static, backward=True), jnp.zeros_like(c), jnp.zeros_like(s)


_rotated.defvjp(_rotated_fwd, _rotated_bwd)


def rotate(x, c, s, rot: int, *, row_tile: Optional[int] = None,
           interpret: Optional[bool] = None):
    """``x`` (B, S, H, D) rotated on each head's first ``rot`` columns by the
    tables ``c``, ``s`` (``tables``) -> (B, S, H, D) in ``x``'s dtype, held
    head-major (the module's text).  Differentiable in ``x``.  ``row_tile``:
    rows a program, a multiple of 16 that divides S (``tile_rows``)."""
    if not engages(x.shape, rot):
        raise ValueError(
            f"rope_kernel.rotate takes (B, S, H, D) with D a multiple of {_LANES}, "
            f"S of {_SUBLANES} and an even rot <= D, got {x.shape}, rot {rot}")
    b, t, h, d = x.shape
    if c.shape != (b, t, _block(rot)) or s.shape != c.shape:
        raise ValueError(f"tables of {(b, t, _block(rot))}, got {c.shape}, {s.shape}")
    rows = tile_rows(t, h * d, x.dtype.itemsize) if row_tile is None else row_tile
    if rows % _SUBLANES or t % rows:
        raise ValueError(f"row_tile is a multiple of {_SUBLANES} that divides {t}, got {rows}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    fold = x.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    out = _rotated(fold, c, s, (h, rot, rows, bool(interpret)))
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
