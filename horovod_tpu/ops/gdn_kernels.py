"""Gated DeltaNet's elementwise passes on either side of the rule, each one
Mosaic kernel pair on token-major rows: no 4-D ``(B, T, H, d)`` tensor, no
slice, relayout or repeat of XLA's between the input projection, the rule's
kernels (``ops/gated_delta.py``) and the output projection.

**The input side**: the causal depthwise convolution, SiLU, the L2 norm a key
head and q's scale.  ``gdn_conv_norm(qkv, conv_kernel, ...)`` takes the input projection's
token-major rows ``qkv`` (B, T, >= C), ``C = 2 Hk dk + H dv`` columns ``[q | k |
v]`` first (further columns, the projection's ``z``, are never read), and the
taps ``conv_kernel`` (K, C) float32, and returns q, k (B, T, Hk dk) and v (B, T,
H dv) as the gated delta rule's kernels read them (``ops/gated_delta.py``:
token-major rows, q and k at the KEY heads).  A channel at a time

    acc[t] = sum_j conv_kernel[j] * qkv[t - (K - 1) + j]     float32, zeros before the sequence
    y      = silu(acc)            rounded to ``qkv``'s dtype  (``causal_depthwise_conv``)
    q, k   = y / sqrt(sum_head(y^2) + eps) [* scale]   float32, rounded again  (``unit``)
    v      = y

which is what ``models.transformer.causal_depthwise_conv`` and ``l2_unit``
compute through a dozen XLA passes and two relayouts (PERF.md section 6, PR 38);
they stay as the 'dot' models' path and the tests' oracle.

  ``gdn_conv_norm_fwd``   a program a (sequence, tile of ``rows`` tokens), all
      channels wide, a head's columns at a time; the K - 1 rows before the tile
      come from a second, 16-row block of the same operand.
  ``gdn_conv_norm_bwd``   from dq, dk, dv and the same operands: makes ``acc``,
      ``y`` and the norms again in VMEM (cheaper than reading them), walks the
      tiles last to first with the K - 1 rows of ``acc``'s cotangent that the
      tile before needs carried in VMEM, writes ``dqkv`` and accumulates
      ``d conv_kernel`` over the tiles in float32.  Cotangents stay float32
      from dq to ``dqkv`` (autodiff of the ``jnp`` form rounds them at ``y``).

**The output side**: ``norm(o) * silu(z)``.  ``gdn_gated_norm(o, gate, scale,
...)`` takes the rule's output ``o`` (B, T, H dv) as its kernel writes it, the
gate ``z`` as the LAST ``H dv`` columns of ``gate`` (B, T, n H dv) (the input
projection's own rows, read in place) and the norm's ``scale`` (dv,), and
returns (B, T, H dv) rows for the output projection:

    n   = o * (rsqrt(mean_head(o^2) + eps) * scale)    float32, rounded  (``nn.RMSNorm``)
    out = n * silu(z)                                  float32, rounded again

``gdn_gated_norm_fwd`` and ``gdn_gated_norm_bwd`` (``do``, ``dz``, and ``d
scale`` accumulated over the tiles in float32; ``n`` made again, cotangents
float32 throughout), a program a (sequence, tile of tokens) as above.

**Mamba-2's forms of the two passes** (the same tiles, halo, carry and
accumulators): ``conv_bias_silu(u, conv_kernel, bias)``, the input side with a
bias a channel and no norm, ``silu(acc + bias)`` over all of ``u``'s columns, a
lane tile of channels at a time where the delta rule's pass goes a head at a
time (kernels ``conv_bias_silu_fwd`` / ``_bwd``); and ``gated_group_norm(y, z,
scale, groups=)``, the output side with the gate BEFORE the norm and the norm
over a GROUP's columns with a scale a column: ``rmsnorm_group(y * silu(z)) *
scale`` (``gated_group_norm_fwd`` / ``_bwd``).

A length that is no multiple of the tile is padded with zero rows.  On non-TPU
backends the kernels run in interpret mode.  Traced into a program each pass
leaves one event, ``gdn.conv_norm`` or ``gdn.gated_norm`` (``horovod_tpu.trace``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as _pltpu

from .. import trace as _trace

# tokens a program: a tile of 64 x 8,192 bf16 is 1 MB each way
_ROWS = 64
# rows of the block that holds the K - 1 rows before a tile (a bf16 tile's)
_HALO = 16
_VMEM_BYTES = 32 * 1024 * 1024


def _slabs(hk, dk, hv, dv):
    """(columns of qkv, which output, its columns, normalised?) a head."""
    key = hk * dk
    return ([(slice(j * dk, (j + 1) * dk), 0, slice(j * dk, (j + 1) * dk))
             for j in range(hk)]
            + [(slice(key + j * dk, key + (j + 1) * dk), 1, slice(j * dk, (j + 1) * dk))
               for j in range(hk)]
            + [(slice(2 * key + j * dv, 2 * key + (j + 1) * dv), 2,
                slice(j * dv, (j + 1) * dv)) for j in range(hv)])


def _taps(x, halo, taps):
    """``x`` (rows, W) float32 moved down by K - 1, ..., 0 rows, the rows that
    come in from above taken from the end of ``halo``: tap j's operand."""
    ext = jnp.concatenate([halo, x], axis=0)
    return [_pltpu.roll(ext, taps - 1 - j, 0)[halo.shape[0]:] for j in range(taps - 1)] + [x]


def _conv(shifted, w):
    acc = shifted[0] * w[0:1]
    for j in range(1, len(shifted)):
        acc = acc + shifted[j] * w[j:j + 1]
    return acc


def _inverse_norm(y32, eps):
    return jax.lax.rsqrt(jnp.sum(y32 * y32, axis=1, keepdims=True) + eps)


def _halo_rows(halo_ref, cols, top):
    """The 16 rows before the tile, float32: zeros before the sequence."""
    return jnp.where(top, 0.0, halo_ref[0, :, cols].astype(jnp.float32))


def _fwd_kernel(u_ref, halo_ref, w_ref, q_ref, k_ref, v_ref, *, taps, heads, scale, eps):
    f32, dtype = jnp.float32, q_ref.dtype
    top = pl.program_id(1) == 0
    outs = (q_ref, k_ref, v_ref)
    for cols, which, out_cols in _slabs(*heads):
        shifted = _taps(u_ref[0, :, cols].astype(f32), _halo_rows(halo_ref, cols, top), taps)
        acc = _conv(shifted, w_ref[:, cols])
        y = (acc * jax.nn.sigmoid(acc)).astype(dtype)
        if which < 2:
            y32 = y.astype(f32)
            y = (y32 * (_inverse_norm(y32, eps) * (scale if which == 0 else 1.0))).astype(dtype)
        outs[which][0, :, out_cols] = y


def _bwd_kernel(u_ref, halo_ref, w_ref, dq_ref, dk_ref, dv_ref, du_ref, dw_ref, carry,
                *, taps, heads, scale, eps, steps):
    f32, dtype = jnp.float32, u_ref.dtype
    i = pl.program_id(1)                     # the tiles last to first

    @pl.when(i == 0)
    def _():
        carry[...] = jnp.zeros_like(carry)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    top = i == steps - 1
    douts = (dq_ref, dk_ref, dv_ref)
    for cols, which, out_cols in _slabs(*heads):
        w = w_ref[:, cols]
        shifted = _taps(u_ref[0, :, cols].astype(f32), _halo_rows(halo_ref, cols, top), taps)
        acc = _conv(shifted, w)
        sig = jax.nn.sigmoid(acc)
        dy = douts[which][0, :, out_cols].astype(f32)
        if which < 2:
            y32 = (acc * sig).astype(dtype).astype(f32)
            inv = _inverse_norm(y32, eps)
            along = jnp.sum(dy * y32, axis=1, keepdims=True)
            dy = (inv * (scale if which == 0 else 1.0)) * (dy - y32 * (inv * inv * along))
        dacc = dy * (sig * (1.0 + acc * (1.0 - sig)))
        _conv_cotangents(dacc, w, shifted, carry, cols, du_ref, dw_ref, taps)


def _conv_cotangents(dacc, w, shifted, carry, cols, du_ref, dw_ref, taps):
    """From ``acc``'s cotangent of a tile's columns: ``du`` written, the taps'
    gradient added to, the first rows kept for the tile before.  du[t] = sum_j
    w[j] dacc[t + K - 1 - j]: the rows past the tile's end are the first rows
    of the tile after it, visited just before."""
    rows = dacc.shape[0]
    ext = jnp.concatenate([dacc, carry[:, cols]], axis=0)
    du = dacc * w[taps - 1:taps]
    for j in range(taps - 1):
        du = du + _pltpu.roll(ext, ext.shape[0] - (taps - 1 - j), 0)[:rows] * w[j:j + 1]
    du_ref[0, :, cols] = du.astype(du_ref.dtype)
    carry[:, cols] = dacc[:carry.shape[0]]
    for j in range(taps):
        dw_ref[0, j:j + 1, cols] += jnp.sum(dacc * shifted[j], axis=0, keepdims=True)


def _params(carried: bool):
    return _pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary" if carried else "parallel"),
        vmem_limit_bytes=_VMEM_BYTES)


def _in_specs(rows, width, taps, at):
    """A tile of ``rows`` tokens of the first ``width`` columns, the 16 rows
    before it (the first tile's are masked) and all the taps."""
    per = rows // _HALO
    return [pl.BlockSpec((1, rows, width), lambda b, i: (b, at(i), 0)),
            pl.BlockSpec((1, _HALO, width),
                         lambda b, i: (b, jnp.maximum(at(i) * per - 1, 0), 0)),
            pl.BlockSpec((taps, width), lambda b, i: (0, 0))]


def _tile_and_mode(row_tile, interpret, **widths):
    """Tokens a program and whether the kernels are interpreted, from the
    caller's arguments; on the chip a head's columns are whole lane tiles."""
    rows = _ROWS if row_tile is None else row_tile
    if rows < _HALO or rows % _HALO:
        raise ValueError(f"row_tile is a multiple of {_HALO} tokens, got {rows}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if not interpret and any(w % 128 for w in widths.values()):
        raise ValueError(
            "on the chip a head's columns are a multiple of 128 lanes: got "
            + ", ".join(f"{name} {w}" for name, w in widths.items()))
    return rows, bool(interpret)


_STATIC = ("heads", "scale", "eps", "rows", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _fwd_call(qkv, w, heads, scale, eps, rows, interpret):
    b, t, _ = qkv.shape
    hk, dk, hv, dv = heads
    taps, width = w.shape
    tile = lambda n: pl.BlockSpec((1, rows, n), lambda b, i: (b, i, 0))
    out = lambda n: jax.ShapeDtypeStruct((b, t, n), qkv.dtype)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, taps=taps, heads=heads, scale=scale, eps=eps),
        name="gdn_conv_norm_fwd",
        grid=(b, t // rows),
        in_specs=_in_specs(rows, width, taps, lambda i: i),
        out_specs=[tile(hk * dk), tile(hk * dk), tile(hv * dv)],
        out_shape=[out(hk * dk), out(hk * dk), out(hv * dv)],
        compiler_params=_params(carried=False),
        interpret=interpret,
    )(qkv, qkv, w)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _bwd_call(qkv, w, dq, dk_, dv_, heads, scale, eps, rows, interpret):
    b, t, _ = qkv.shape
    hk, dk, hv, dv = heads
    taps, width = w.shape
    steps = t // rows
    at = lambda i: steps - 1 - i
    tile = lambda n: pl.BlockSpec((1, rows, n), lambda b, i: (b, at(i), 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps, heads=heads, scale=scale, eps=eps,
                          steps=steps),
        name="gdn_conv_norm_bwd",
        grid=(b, steps),
        in_specs=_in_specs(rows, width, taps, at) + [tile(hk * dk), tile(hk * dk),
                                                    tile(hv * dv)],
        out_specs=[tile(width), pl.BlockSpec((1, taps, width), lambda b, i: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, t, width), qkv.dtype),
                   jax.ShapeDtypeStruct((b, taps, width), jnp.float32)],
        scratch_shapes=[_pltpu.VMEM((8, width), jnp.float32)],
        compiler_params=_params(carried=True),
        interpret=interpret,
    )(qkv, qkv, w, dq, dk_, dv_)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv_norm(qkv, w, static):
    return tuple(_fwd_call(qkv, w, *static))


def _conv_norm_fwd(qkv, w, static):
    return tuple(_fwd_call(qkv, w, *static)), (qkv, w)


def _conv_norm_bwd(static, residuals, cotangents):
    qkv, w = residuals
    dqkv, dw = _bwd_call(qkv, w, *cotangents, *static)
    # columns past the taps' (the projection's z) were never read
    dqkv = jnp.pad(dqkv, ((0, 0), (0, 0), (0, qkv.shape[-1] - dqkv.shape[-1])))
    return dqkv, jnp.sum(dw, axis=0)


_conv_norm.defvjp(_conv_norm_fwd, _conv_norm_bwd)


@functools.partial(jax.jit, static_argnames=("static",))
def _padded(qkv, w, static):
    """Whole tiles of zero-padded rows -> q, k, v.  A ``jit`` of its own for
    ``model.init``'s sake, as the rule's ``_rule``."""
    t, rows = qkv.shape[1], static[3]
    pad = -t % rows
    if pad:
        qkv = jnp.pad(qkv, ((0, 0), (0, pad), (0, 0)))
    return tuple(x[:, :t] for x in _conv_norm(qkv, w, static))


def gdn_conv_norm(qkv, conv_kernel, *, key_heads: int, key_head_dim: int,
                  value_heads: int, value_head_dim: int, eps: float = 1e-6,
                  row_tile: Optional[int] = None, interpret: Optional[bool] = None):
    """q, k (B, T, Hk dk) and v (B, T, H dv) from ``qkv`` (B, T, >= C) and
    ``conv_kernel`` (K, C) float32, ``C = 2 Hk dk + H dv`` (the module's text):
    q scaled by ``dk ** -0.5``.  Differentiable in both; ``qkv``'s cotangent is
    zero in the columns past ``C``.  ``row_tile``: tokens a program, a multiple
    of 16 (64)."""
    hk, dk, hv, dv = heads = (key_heads, key_head_dim, value_heads, value_head_dim)
    width = 2 * hk * dk + hv * dv
    if (qkv.ndim != 3 or qkv.shape[-1] < width or conv_kernel.ndim != 2
            or conv_kernel.shape[1] != width or not 1 <= conv_kernel.shape[0] <= 9):
        raise ValueError(
            f"gdn_conv_norm takes qkv (B, T, >= {width}) and conv_kernel (K <= 9, "
            f"{width}), got {qkv.shape}, {conv_kernel.shape}")
    rows, interpret = _tile_and_mode(row_tile, interpret, dk=dk, dv=dv)
    b, t, _ = qkv.shape
    if _trace.enabled():
        tiles = -(-t // rows)
        # a pass reads the rows (and 16 above each tile) and writes q, k, v
        _trace.event(
            "gdn.conv_norm", rows=b * t, channels=width, taps=conv_kernel.shape[0],
            key_heads=hk, value_heads=hv, row_tile=rows, programs=b * tiles,
            hbm_bytes=b * tiles * (2 * rows + _HALO) * width * jnp.dtype(qkv.dtype).itemsize)
    return _padded(qkv, conv_kernel.astype(jnp.float32),
                   (heads, float(dk) ** -0.5, float(eps), rows, interpret))


# -- the output side: norm(o) * silu(z) -----------------------------------------


def _gated_fwd_kernel(o_ref, z_ref, scale_ref, out_ref, *, heads, dv, eps):
    f32, dtype = jnp.float32, out_ref.dtype
    scale = scale_ref[...]
    for j in range(heads):
        cols = slice(j * dv, (j + 1) * dv)
        x, z = o_ref[0, :, cols].astype(f32), z_ref[0, :, cols].astype(f32)
        inv = jax.lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) / dv + eps)
        n = (x * (inv * scale)).astype(dtype)
        out_ref[0, :, cols] = (n.astype(f32) * (z * jax.nn.sigmoid(z))).astype(dtype)


def _gated_bwd_kernel(o_ref, z_ref, scale_ref, dout_ref, do_ref, dz_ref, dscale_ref,
                      *, heads, dv, eps):
    f32, dtype = jnp.float32, o_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        dscale_ref[...] = jnp.zeros_like(dscale_ref)

    scale = scale_ref[...]
    dscale = jnp.zeros(scale.shape, f32)
    for j in range(heads):
        cols = slice(j * dv, (j + 1) * dv)
        x, z = o_ref[0, :, cols].astype(f32), z_ref[0, :, cols].astype(f32)
        g = dout_ref[0, :, cols].astype(f32)
        inv = jax.lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) / dv + eps)
        n32 = (x * (inv * scale)).astype(dtype).astype(f32)
        sig = jax.nn.sigmoid(z)
        dz_ref[0, :, cols] = ((g * n32) * (sig * (1.0 + z * (1.0 - sig)))).astype(dtype)
        dn = g * (z * sig)
        dscale = dscale + jnp.sum(dn * x * inv, axis=0, keepdims=True)
        along = jnp.sum(dn * scale * x, axis=1, keepdims=True)
        do_ref[0, :, cols] = (inv * (dn * scale - x * (inv * inv / dv * along))).astype(dtype)
    dscale_ref[0] += dscale


_GATED_STATIC = ("heads", "eps", "rows", "interpret")


def _gated_specs(rows, width, dv, gate_width):
    tile = pl.BlockSpec((1, rows, width), lambda b, i: (b, i, 0))
    last = gate_width // width - 1                     # the gate's own columns
    return tile, [tile, pl.BlockSpec((1, rows, width), lambda b, i: (b, i, last)),
                  pl.BlockSpec((1, dv), lambda b, i: (0, 0))]


@functools.partial(jax.jit, static_argnames=_GATED_STATIC)
def _gated_fwd_call(o, gate, scale, heads, eps, rows, interpret):
    b, t, width = o.shape
    dv = width // heads
    tile, in_specs = _gated_specs(rows, width, dv, gate.shape[-1])
    return pl.pallas_call(
        functools.partial(_gated_fwd_kernel, heads=heads, dv=dv, eps=eps),
        name="gdn_gated_norm_fwd",
        grid=(b, t // rows),
        in_specs=in_specs,
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
        compiler_params=_params(carried=False),
        interpret=interpret,
    )(o, gate, scale)


@functools.partial(jax.jit, static_argnames=_GATED_STATIC)
def _gated_bwd_call(o, gate, scale, dout, heads, eps, rows, interpret):
    b, t, width = o.shape
    dv = width // heads
    tile, in_specs = _gated_specs(rows, width, dv, gate.shape[-1])
    return pl.pallas_call(
        functools.partial(_gated_bwd_kernel, heads=heads, dv=dv, eps=eps),
        name="gdn_gated_norm_bwd",
        grid=(b, t // rows),
        in_specs=in_specs + [tile],
        out_specs=[tile, tile, pl.BlockSpec((1, 1, dv), lambda b, i: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(o.shape, o.dtype),
                   jax.ShapeDtypeStruct(o.shape, o.dtype),
                   jax.ShapeDtypeStruct((b, 1, dv), jnp.float32)],
        compiler_params=_params(carried=True),
        interpret=interpret,
    )(o, gate, scale, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gated_norm(o, gate, scale, static):
    return _gated_fwd_call(o, gate, scale, *static)


def _gated_norm_fwd(o, gate, scale, static):
    return _gated_fwd_call(o, gate, scale, *static), (o, gate, scale)


def _gated_norm_bwd(static, residuals, dout):
    o, gate, scale = residuals
    do, dz, dscale = _gated_bwd_call(o, gate, scale, dout, *static)
    # the columns before the gate's were never read
    dgate = jnp.pad(dz, ((0, 0), (0, 0), (gate.shape[-1] - dz.shape[-1], 0)))
    return do, dgate, jnp.sum(dscale, axis=0)


_gated_norm.defvjp(_gated_norm_fwd, _gated_norm_bwd)


@functools.partial(jax.jit, static_argnames=("static",))
def _gated_padded(o, gate, scale, static):
    t, rows = o.shape[1], static[2]
    pad = -t % rows
    if pad:
        o, gate = (jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in (o, gate))
    return _gated_norm(o, gate, scale, static)[:, :t]


def gdn_gated_norm(o, gate, scale, *, heads: int, eps: float = 1e-6,
                   row_tile: Optional[int] = None, interpret: Optional[bool] = None):
    """``norm(o) * silu(z)`` (the module's text): ``o`` (B, T, H dv), ``z`` the
    last ``H dv`` columns of ``gate`` (B, T, >= H dv; a width that is no
    multiple of ``H dv`` is sliced first), ``scale`` (dv,) float32 -> (B, T, H
    dv) in ``o``'s dtype.  Differentiable in all three; ``gate``'s cotangent is
    zero in the columns before ``z``."""
    width = o.shape[-1] if o.ndim == 3 else 0
    if (o.ndim != 3 or gate.ndim != 3 or width % heads or gate.shape[:2] != o.shape[:2]
            or gate.shape[-1] < width or scale.shape != (width // heads,)
            or gate.dtype != o.dtype):
        raise ValueError(
            f"gdn_gated_norm takes o (B, T, H dv), gate (B, T, >= H dv) in one dtype "
            f"and scale (dv,) with H = {heads}, got {o.shape} {o.dtype}, {gate.shape} "
            f"{gate.dtype}, {scale.shape}")
    dv = width // heads
    rows, interpret = _tile_and_mode(row_tile, interpret, dv=dv)
    if gate.shape[-1] % width:
        gate = gate[..., -width:]
    b, t, _ = o.shape
    if _trace.enabled():
        tiles = -(-t // rows)
        # a pass reads o and z and writes the product
        _trace.event(
            "gdn.gated_norm", rows=b * t, channels=width, value_heads=heads, row_tile=rows,
            programs=b * tiles,
            hbm_bytes=b * tiles * 3 * rows * width * jnp.dtype(o.dtype).itemsize)
    return _gated_padded(o, gate, scale.astype(jnp.float32).reshape(1, dv),
                         (heads, float(eps), rows, interpret))


# -- Mamba-2's input side: silu(conv(u) + bias), no norm -------------------------


def _lane_slabs(width):
    """The columns a lane tile at a time; all of them where they are not whole
    tiles (interpret mode's small shapes)."""
    if width % 128:
        return [slice(0, width)]
    return [slice(lo, lo + 128) for lo in range(0, width, 128)]


def _plain_fwd_kernel(u_ref, halo_ref, w_ref, bias_ref, out_ref, *, taps):
    f32 = jnp.float32
    top = pl.program_id(1) == 0
    for cols in _lane_slabs(u_ref.shape[2]):
        shifted = _taps(u_ref[0, :, cols].astype(f32), _halo_rows(halo_ref, cols, top), taps)
        acc = _conv(shifted, w_ref[:, cols]) + bias_ref[:, cols]
        out_ref[0, :, cols] = (acc * jax.nn.sigmoid(acc)).astype(out_ref.dtype)


def _plain_bwd_kernel(u_ref, halo_ref, w_ref, bias_ref, dout_ref, du_ref, dw_ref,
                      dbias_ref, carry, *, taps, steps):
    f32 = jnp.float32
    i = pl.program_id(1)                     # the tiles last to first

    @pl.when(i == 0)
    def _():
        carry[...] = jnp.zeros_like(carry)
        dw_ref[...] = jnp.zeros_like(dw_ref)
        dbias_ref[...] = jnp.zeros_like(dbias_ref)

    top = i == steps - 1
    for cols in _lane_slabs(u_ref.shape[2]):
        w = w_ref[:, cols]
        shifted = _taps(u_ref[0, :, cols].astype(f32), _halo_rows(halo_ref, cols, top), taps)
        acc = _conv(shifted, w) + bias_ref[:, cols]
        sig = jax.nn.sigmoid(acc)
        dacc = dout_ref[0, :, cols].astype(f32) * (sig * (1.0 + acc * (1.0 - sig)))
        _conv_cotangents(dacc, w, shifted, carry, cols, du_ref, dw_ref, taps)
        dbias_ref[0, :, cols] += jnp.sum(dacc, axis=0, keepdims=True)


_PLAIN_STATIC = ("rows", "interpret")


@functools.partial(jax.jit, static_argnames=_PLAIN_STATIC)
def _plain_fwd_call(u, w, bias, rows, interpret):
    b, t, width = u.shape
    taps = w.shape[0]
    return pl.pallas_call(
        functools.partial(_plain_fwd_kernel, taps=taps),
        name="conv_bias_silu_fwd",
        grid=(b, t // rows),
        in_specs=_in_specs(rows, width, taps, lambda i: i) + [
            pl.BlockSpec((1, width), lambda b, i: (0, 0))],
        out_specs=pl.BlockSpec((1, rows, width), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        compiler_params=_params(carried=False),
        interpret=interpret,
    )(u, u, w, bias)


@functools.partial(jax.jit, static_argnames=_PLAIN_STATIC)
def _plain_bwd_call(u, w, bias, dout, rows, interpret):
    b, t, width = u.shape
    taps = w.shape[0]
    steps = t // rows
    at = lambda i: steps - 1 - i
    tile = pl.BlockSpec((1, rows, width), lambda b, i: (b, at(i), 0))
    return pl.pallas_call(
        functools.partial(_plain_bwd_kernel, taps=taps, steps=steps),
        name="conv_bias_silu_bwd",
        grid=(b, steps),
        in_specs=_in_specs(rows, width, taps, at) + [
            pl.BlockSpec((1, width), lambda b, i: (0, 0)), tile],
        out_specs=[tile, pl.BlockSpec((1, taps, width), lambda b, i: (b, 0, 0)),
                   pl.BlockSpec((1, 1, width), lambda b, i: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct((b, taps, width), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, width), jnp.float32)],
        scratch_shapes=[_pltpu.VMEM((8, width), jnp.float32)],
        compiler_params=_params(carried=True),
        interpret=interpret,
    )(u, u, w, bias, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _plain(u, w, bias, static):
    return _plain_fwd_call(u, w, bias, *static)


def _plain_fwd(u, w, bias, static):
    return _plain_fwd_call(u, w, bias, *static), (u, w, bias)


def _plain_bwd(static, residuals, dout):
    du, dw, dbias = _plain_bwd_call(*residuals, dout, *static)
    return du, jnp.sum(dw, axis=0), jnp.sum(dbias, axis=0)


_plain.defvjp(_plain_fwd, _plain_bwd)


@functools.partial(jax.jit, static_argnames=("static",))
def _plain_padded(u, w, bias, static):
    t, rows = u.shape[1], static[0]
    pad = -t % rows
    if pad:
        u = jnp.pad(u, ((0, 0), (0, pad), (0, 0)))
    return _plain(u, w, bias, static)[:, :t]


def conv_bias_silu(u, conv_kernel, bias, *, row_tile: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """``silu(sum_j conv_kernel[j] * u[t - (K - 1) + j] + bias)`` a channel, zeros
    before the sequence, the sum in float32: ``u`` (B, T, C), ``conv_kernel`` (K,
    C) and ``bias`` (C,) float32 -> (B, T, C) in ``u``'s dtype.  Differentiable
    in all three (cotangents float32 from the output's to ``du``)."""
    if (u.ndim != 3 or conv_kernel.ndim != 2 or conv_kernel.shape[1] != u.shape[-1]
            or bias.shape != u.shape[-1:] or not 1 <= conv_kernel.shape[0] <= 9):
        raise ValueError(
            f"conv_bias_silu takes u (B, T, C), conv_kernel (K <= 9, C) and bias (C,), "
            f"got {u.shape}, {conv_kernel.shape}, {bias.shape}")
    b, t, width = u.shape
    rows, interpret = _tile_and_mode(row_tile, interpret, channels=width)
    if _trace.enabled():
        tiles = -(-t // rows)
        _trace.event(
            "gdn.conv_norm", rows=b * t, channels=width, taps=conv_kernel.shape[0],
            key_heads=0, value_heads=0, row_tile=rows, programs=b * tiles,
            hbm_bytes=b * tiles * (2 * rows + _HALO) * width * jnp.dtype(u.dtype).itemsize)
    f32 = jnp.float32
    return _plain_padded(u, conv_kernel.astype(f32), bias.astype(f32).reshape(1, width),
                         (rows, interpret))


# -- Mamba-2's output side: rmsnorm over a group of (y * silu(z)) -----------------


def _group_fwd_kernel(y_ref, z_ref, scale_ref, out_ref, *, groups, eps):
    f32 = jnp.float32
    width = y_ref.shape[2] // groups
    for j in range(groups):
        cols = slice(j * width, (j + 1) * width)
        y, z = y_ref[0, :, cols].astype(f32), z_ref[0, :, cols].astype(f32)
        u = y * (z * jax.nn.sigmoid(z))
        inv = jax.lax.rsqrt(jnp.sum(u * u, axis=1, keepdims=True) / width + eps)
        out_ref[0, :, cols] = (u * (inv * scale_ref[:, cols])).astype(out_ref.dtype)


def _group_bwd_kernel(y_ref, z_ref, scale_ref, dout_ref, dy_ref, dz_ref, dscale_ref,
                      *, groups, eps):
    f32 = jnp.float32

    @pl.when(pl.program_id(1) == 0)
    def _():
        dscale_ref[...] = jnp.zeros_like(dscale_ref)

    width = y_ref.shape[2] // groups
    for j in range(groups):
        cols = slice(j * width, (j + 1) * width)
        y, z = y_ref[0, :, cols].astype(f32), z_ref[0, :, cols].astype(f32)
        g, scale = dout_ref[0, :, cols].astype(f32), scale_ref[:, cols]
        sig = jax.nn.sigmoid(z)
        gate = z * sig
        u = y * gate
        inv = jax.lax.rsqrt(jnp.sum(u * u, axis=1, keepdims=True) / width + eps)
        dscale_ref[0, :, cols] += jnp.sum(g * u * inv, axis=0, keepdims=True)
        along = jnp.sum(g * scale * u, axis=1, keepdims=True)
        du = inv * (g * scale - u * (inv * inv / width * along))
        dy_ref[0, :, cols] = (du * gate).astype(dy_ref.dtype)
        dz_ref[0, :, cols] = (du * y * (sig * (1.0 + z * (1.0 - sig)))).astype(dz_ref.dtype)


_GROUP_STATIC = ("groups", "eps", "rows", "interpret")


def _group_specs(rows, width):
    tile = pl.BlockSpec((1, rows, width), lambda b, i: (b, i, 0))
    return tile, [tile, tile, pl.BlockSpec((1, width), lambda b, i: (0, 0))]


@functools.partial(jax.jit, static_argnames=_GROUP_STATIC)
def _group_fwd_call(y, z, scale, groups, eps, rows, interpret):
    b, t, width = y.shape
    tile, in_specs = _group_specs(rows, width)
    return pl.pallas_call(
        functools.partial(_group_fwd_kernel, groups=groups, eps=eps),
        name="gated_group_norm_fwd",
        grid=(b, t // rows),
        in_specs=in_specs,
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        compiler_params=_params(carried=False),
        interpret=interpret,
    )(y, z, scale)


@functools.partial(jax.jit, static_argnames=_GROUP_STATIC)
def _group_bwd_call(y, z, scale, dout, groups, eps, rows, interpret):
    b, t, width = y.shape
    tile, in_specs = _group_specs(rows, width)
    return pl.pallas_call(
        functools.partial(_group_bwd_kernel, groups=groups, eps=eps),
        name="gated_group_norm_bwd",
        grid=(b, t // rows),
        in_specs=in_specs + [tile],
        out_specs=[tile, tile, pl.BlockSpec((1, 1, width), lambda b, i: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct((b, 1, width), jnp.float32)],
        compiler_params=_params(carried=True),
        interpret=interpret,
    )(y, z, scale, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _group_norm(y, z, scale, static):
    return _group_fwd_call(y, z, scale, *static)


def _group_norm_fwd(y, z, scale, static):
    return _group_fwd_call(y, z, scale, *static), (y, z, scale)


def _group_norm_bwd(static, residuals, dout):
    dy, dz, dscale = _group_bwd_call(*residuals, dout, *static)
    return dy, dz, jnp.sum(dscale, axis=0)


_group_norm.defvjp(_group_norm_fwd, _group_norm_bwd)


@functools.partial(jax.jit, static_argnames=("static",))
def _group_padded(y, z, scale, static):
    t, rows = y.shape[1], static[2]
    pad = -t % rows
    if pad:
        y, z = (jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in (y, z))
    return _group_norm(y, z, scale, static)[:, :t]


def gated_group_norm(y, z, scale, *, groups: int, eps: float = 1e-5,
                     row_tile: Optional[int] = None, interpret: Optional[bool] = None):
    """``u = y * silu(z)``; ``u * rsqrt(mean over a group's columns of u^2 + eps) *
    scale``, float32, rounded once: ``y``, ``z`` (B, T, W) in one dtype, ``scale``
    (W,) float32, ``groups`` a divisor of ``W`` -> (B, T, W) in ``y``'s dtype.
    Differentiable in all three."""
    width = y.shape[-1] if y.ndim == 3 else 0
    if (y.ndim != 3 or z.shape != y.shape or z.dtype != y.dtype or groups < 1
            or width % groups or scale.shape != (width,)):
        raise ValueError(
            f"gated_group_norm takes y, z (B, T, W) in one dtype and scale (W,) with "
            f"{groups} groups a divisor of W, got {y.shape} {y.dtype}, {z.shape} "
            f"{z.dtype}, {scale.shape}")
    rows, interpret = _tile_and_mode(row_tile, interpret, group=width // groups)
    b, t, _ = y.shape
    if _trace.enabled():
        tiles = -(-t // rows)
        _trace.event(
            "gdn.gated_norm", rows=b * t, channels=width, value_heads=groups, row_tile=rows,
            programs=b * tiles,
            hbm_bytes=b * tiles * 3 * rows * width * jnp.dtype(y.dtype).itemsize)
    return _group_padded(y, z, scale.astype(jnp.float32).reshape(1, width),
                         (groups, float(eps), rows, interpret))
