"""Mamba-2's state-space scan (the SSD of Dao and Gu, arXiv:2405.21060) in
chunked form, forward and backward as two Mosaic kernels.

A head keeps a state ``S`` (state size x head width, ``N x P``) along the
sequence, ``S_0 = 0``, and at every token

    S <- exp(dt_t A) S + dt_t B_t x_t^T;        y_t = S^T C_t + D x_t

(``A < 0`` and ``D`` one number a head, ``dt_t > 0`` one a head and token,
``B_t`` and ``C_t`` (``N`` wide) shared by the ``H / G`` heads of a group).
``ssd_recurrent`` is that, token by token: the oracle of the tests.

It is the gated delta rule's carry (``ops/gated_delta.py``) without the delta:
no ``(I + L)^-1`` and no ``W``; the rule's ``q, k`` are ``C, B``, its new values
``D`` are ``X = dt x`` as they come, its gate ``g_t`` is ``dt_t A``.  With
``gamma_i`` the running sum of ``g`` inside a chunk and ``S`` the state the chunk
is handed:

    ``M = lower(C_i . B_j  exp(gamma_i - gamma_j))``      (the diagonal included)
    ``y = M X + exp(gamma) (C S) + D x``
    ``S <- exp(gamma_C) S + B^T (exp(gamma_C - gamma) X)``

Every exponent is of a sum of ``g <= 0`` over a stretch of tokens, so at most 1.
Products take their operands in ``x``'s dtype (bf16 in the benchmark's cell) and
sum in float32; the gates, the decays and the state are float32, the state
rounded where a product reads it.

``impl="kernel"``: x goes in token-major ``(B, T, H P)``, B and C as ``(B, T, G
N)``, ``g`` and ``dt`` as rows a chunk ``(B, T / C, H, C)``, and a program is a
(sequence, group) with the grid's last axis walking the sequence ``block`` chunks
a step: a program carries the ``H / G`` heads of ONE group side by side, so B
and C are read once a group through the index map (as the delta rule reads its
key heads since PR 38), ``C B^T`` is one product a chunk and group, and the two
products with the state are one each for all the group's heads (``C [S_1 | ... |
S_r]`` and ``B^T [X_1 | ... | X_r]``); only ``M X`` is a head's own.  The gates'
rows are spread over a head's ``P`` columns (and cotangents summed back over
them) by products with a 0/1 pattern, float32-exact in three bf16 pieces
(``gated_delta._running_sums``' way), so no value is ever sliced below a lane
tile: heads narrower than 128 lanes share a tile under a lane mask.

  ``ssd_scan_fwd``   x, B, C, g, dt, D -> y and, for the backward, the state each
      chunk was HANDED (in the operands' dtype: what the forward's own products
      read), each group's state in VMEM from chunk to chunk.
  ``ssd_scan_bwd``   the same and ``dy`` -> dx, dB, dC, dg, ddt, dD: walks the
      chunks last to first with the state's cotangent in VMEM and makes the
      chunk-local tensors again.  dB and dC are summed over a group's heads in
      VMEM.  No residual but the kernels' operands and the states.

The running sums, the decays, the products' forms and the compiler parameters
are ``ops/gated_delta.py``'s own helpers.  A length that is no multiple of the
kernel's step is padded with ``dt = 0``: the state passes through.  On non-TPU
backends the kernels run in interpret mode.  ``impl="jnp"`` is the second
oracle, and what 'dot' models run: the same chunked form as batched XLA
products and a ``lax.scan`` over the chunks, differentiated by autodiff.  Traced
into a program the scan leaves one ``ssd.chunks`` event (``horovod_tpu.trace``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as _pltpu

from .. import trace as _trace
from .gated_delta import (_BLOCK_CHUNKS, _HIGHEST, _NT, _TN, _dot, _gates, _iotas,
                          _params, _running_sums)


def ssd_recurrent(x, dt, a, b, c, d=None):
    """The scan token by token, float32: ``x`` (B, T, H, P), ``dt`` (B, T, H),
    ``a`` (H,), ``b``, ``c`` (B, T, G, N), ``d`` (H,) or None -> ``y`` (B, T, H,
    P) float32.  Head ``h`` reads group ``h // (H / G)``."""
    f32 = lambda v: v.astype(jnp.float32)
    x, dt, a, b, c = map(f32, (x, dt, a, b, c))
    bsz, _, h, p = x.shape
    ratio = h // b.shape[2]
    b, c = (jnp.repeat(v, ratio, axis=2) for v in (b, c))

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs                                   # (B, H, .)
        s = s * jnp.exp(dt_t * a)[..., None, None] + (
            (dt_t[..., None] * b_t)[..., :, None] * x_t[..., None, :])
        return s, jnp.einsum("bhnp,bhn->bhp", s, c_t, precision=_HIGHEST)

    by_token = lambda v: jnp.moveaxis(v, 1, 0)
    s0 = jnp.zeros((bsz, h, b.shape[-1], p), jnp.float32)
    _, y = jax.lax.scan(step, s0, tuple(map(by_token, (x, dt, b, c))))
    y = jnp.moveaxis(y, 0, 1)
    return y if d is None else y + f32(d)[:, None] * x


# -- the chunked form: jax.numpy -------------------------------------------------


def _chunked(x, dt, g, b, c, chunk):
    """The module's text on whole chunks: ``x`` (B, T, H, P), ``dt``, ``g`` (B, T,
    H) float32, ``b``, ``c`` (B, T, G, N) -> (B, T, H, P) float32, rounded where
    the kernels round."""
    bsz, t, h, p = x.shape
    groups, n = b.shape[2:]
    ratio, steps = h // groups, t // chunk
    f32, dtype = jnp.float32, x.dtype
    # (steps, B, chunk, G, r, .): the heads by group
    by_chunk = lambda v, *tail: jnp.moveaxis(
        v.reshape(bsz, steps, chunk, groups, *tail), 1, 0)
    x32 = by_chunk(x.astype(f32), ratio, p)
    dt_, g_ = by_chunk(dt, ratio), by_chunk(g, ratio)
    b_, c_ = by_chunk(b, n), by_chunk(c, n)
    gamma = jnp.cumsum(g_, axis=2)                                # (s, B, C, G, r)
    total = gamma[:, :, -1:]
    row, col = _iotas(chunk)
    diff = gamma[:, :, :, None] - gamma[:, :, None, :]            # (s, B, i, j, G, r)
    decay = jnp.exp(jnp.where((row >= col)[:, :, None, None], diff, -jnp.inf))
    scores = jnp.einsum("sbign,sbjgn->sbijg", c_, b_, preferred_element_type=f32)
    m = (scores[..., None] * decay).astype(dtype)
    xd = x32 * dt_[..., None]
    intra = jnp.einsum("sbijgr,sbjgrp->sbigrp", m, xd.astype(dtype),
                       preferred_element_type=f32)
    xf = (xd * jnp.exp(total - gamma)[..., None]).astype(dtype)
    rise, a = jnp.exp(gamma)[..., None], jnp.exp(total[:, :, 0])[..., None, None]

    def step(s, xs):
        c_c, b_c, xf_c, rise_c, a_c = xs
        read = jnp.einsum("bign,bgrnp->bigrp", c_c, s.astype(dtype),
                          preferred_element_type=f32)
        s = a_c * s + jnp.einsum("bign,bigrp->bgrnp", b_c, xf_c,
                                 preferred_element_type=f32)
        return s, rise_c * read

    s0 = jnp.zeros((bsz, groups, ratio, n, p), f32)
    _, inter = jax.lax.scan(step, s0, (c_, b_, xf, rise, a))
    return jnp.moveaxis(intra + inter, 0, 1).reshape(bsz, t, h, p)


# -- the scan's kernels: Mosaic ---------------------------------------------------


def _pattern(heads, p):
    """(H, H P) of zeros and ones: head ``h`` owns the columns ``[h P, (h + 1)
    P)``."""
    head = jax.lax.broadcasted_iota(jnp.int32, (heads, heads * p), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (heads, heads * p), 1)
    return ((lane >= head * p) & (lane < (head + 1) * p)).astype(jnp.bfloat16)


def _pieces(x):
    """``x`` float32 in three bfloat16 pieces (3 x 8 bits) that sum to it."""
    out = []
    for _ in range(3):
        piece = x.astype(jnp.bfloat16)
        out.append(piece)
        x = x - piece.astype(jnp.float32)
    return out


def _spread(rows, pattern):
    """Rows a head (H, C) float32 -> (C, H P): each head's row down its ``P``
    columns, exactly."""
    return sum(_dot(piece, pattern, _TN) for piece in _pieces(rows))


def _gathered(lanes, pattern):
    """(C, H P) float32 -> rows a head (H, C): each head's sum over its ``P``
    columns, to float32 accuracy."""
    return sum(_dot(pattern, piece, _NT) for piece in _pieces(lanes))


def _lane_tiles(heads, p):
    """The lane tiles of a program's ``H P`` columns and the heads in each: 128
    lanes where heads of ``P`` fill them evenly, else a head a tile."""
    pack = 128 // p if p < 128 and 128 % p == 0 and heads % (128 // p) == 0 else 1
    return [(slice(i * p, (i + pack) * p), list(range(i, i + pack)))
            for i in range(0, heads, pack)]


def _own_lanes(members, p):
    """For each head of a lane tile, the (1, tile) mask of its own lanes; None
    for a tile of one head."""
    if len(members) == 1:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, len(members) * p), 1)
    return [(lane >= k * p) & (lane < (k + 1) * p) for k in range(len(members))]


def _chunk_gates(g_ref, dt_ref, c, pattern, chunk):
    """A chunk's gates: ``gamma`` as rows a head (H, C), and over the program's
    columns (C, H P) ``dt``, ``e^gamma``, ``e^(gamma_C - gamma)`` and (1, H P)
    ``e^gamma_C``."""
    row, col = _iotas(chunk)
    gamma = _running_sums(g_ref[0, c], (row <= col).astype(jnp.bfloat16))
    wide = _spread(gamma, pattern)
    total = wide[chunk - 1:chunk]
    return (gamma, _spread(dt_ref[0, c], pattern), jnp.exp(wide),
            jnp.exp(total - wide), jnp.exp(total))


def _decay(gamma, j, chunk):
    """Head ``j``'s ``e^(gamma_i - gamma_j)`` (C, C), 0 above the diagonal."""
    return _gates(gamma[j:j + 1], gamma[j:j + 1], chunk)[1]


def _fwd_kernel(x_ref, b_ref, c_ref, g_ref, dt_ref, skip_ref, y_ref, s_ref, state,
                *, chunk, block, heads, n, p):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    f32, dtype = jnp.float32, x_ref.dtype
    pattern, tiles = _pattern(heads, p), _lane_tiles(heads, p)
    skip = skip_ref[...]
    for c in range(block):
        rows = slice(c * chunk, (c + 1) * chunk)
        gamma, dt, rise, fall, a = _chunk_gates(g_ref, dt_ref, c, pattern, chunk)
        bm, cm = b_ref[0, rows, :], c_ref[0, rows, :]
        x32 = x_ref[0, rows, :].astype(f32)
        xd = x32 * dt
        xb = xd.astype(dtype)
        sb = state[...].astype(dtype)
        s_ref[0, c * n:(c + 1) * n, :] = sb                # the state the chunk is handed
        y = rise * _dot(cm, sb) + skip * x32
        state[...] = a * state[...] + _dot(bm, (xd * fall).astype(dtype), _TN)
        scores = _dot(cm, bm, _NT)
        for lanes, members in tiles:
            xt, own = xb[:, lanes], None
            for j, mask in zip(members, _own_lanes(members, p)):
                part = _dot((scores * _decay(gamma, j, chunk)).astype(dtype), xt)
                own = part if mask is None or own is None else jnp.where(mask, part, own)
            y_ref[0, rows, lanes] = (y[:, lanes] + own).astype(y_ref.dtype)


def _bwd_kernel(x_ref, b_ref, c_ref, g_ref, dt_ref, skip_ref, s_ref, dy_ref,
                dx_ref, db_ref, dc_ref, dg_ref, ddt_ref, dskip_ref, dstate,
                *, chunk, block, heads, n, p):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        dskip_ref[...] = jnp.zeros_like(dskip_ref)

    f32, dtype = jnp.float32, x_ref.dtype
    cast = lambda v: v.astype(dtype)
    pattern, tiles = _pattern(heads, p), _lane_tiles(heads, p)
    skip = skip_ref[...]
    row, col = _iotas(chunk)
    as_row = lambda v: jnp.sum(jnp.where(row == col, v, 0.0), axis=0, keepdims=True)
    last = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    dskip = jnp.zeros(skip.shape, f32)
    for c in reversed(range(block)):
        rows = slice(c * chunk, (c + 1) * chunk)
        gamma, dt, rise, fall, a = _chunk_gates(g_ref, dt_ref, c, pattern, chunk)
        bm, cm = b_ref[0, rows, :], c_ref[0, rows, :]
        x32 = x_ref[0, rows, :].astype(f32)
        xd = x32 * dt
        xb, xf32 = cast(xd), xd * fall
        sb = s_ref[0, c * n:(c + 1) * n, :]
        dy = dy_ref[0, rows, :]
        dy32 = dy.astype(f32)
        ds = dstate[...]                                   # of the state the chunk leaves
        dsb = cast(ds)
        read = _dot(cm, sb)                                # C S
        back = _dot(bm, dsb)                               # B dS
        dyr32 = dy32 * rise
        dyr = cast(dyr32)
        dstate[...] = a * ds + _dot(cm, dyr, _TN)
        dc = _dot(dyr, sb, _NT)
        db = _dot(cast(xf32), dsb, _NT)
        dtotal = (a * jnp.sum(sb.astype(f32) * ds, axis=0, keepdims=True)
                  + jnp.sum(xf32 * back, axis=0, keepdims=True))
        # gamma through e^gamma, e^(gamma_C - gamma) and e^gamma_C, a column each
        dgamma = dyr32 * read - xf32 * back + jnp.where(last, dtotal, 0.0)
        dxd, passed = fall * back, skip * dy32
        scores = _dot(cm, bm, _NT)
        dscores = jnp.zeros((chunk, chunk), f32)
        own_rows, dxs = [], []
        for lanes, members in tiles:
            xt, dyt, dxt = xb[:, lanes], dy[:, lanes], None
            for j, mask in zip(members, _own_lanes(members, p)):
                decay = _decay(gamma, j, chunk)
                m32 = scores * decay
                dm = _dot(dyt if mask is None else jnp.where(mask, dyt, 0), xt, _NT)
                part = _dot(cast(m32), dyt, _TN)
                dxt = part if mask is None or dxt is None else jnp.where(mask, part, dxt)
                dscores = dscores + dm * decay
                # the decays' cotangent times themselves: + along rows, - along columns
                both = dm * m32
                own_rows.append(as_row(jnp.sum(both, axis=1, keepdims=True))
                                - jnp.sum(both, axis=0, keepdims=True))
            dxt = dxt + dxd[:, lanes]
            dx_ref[0, rows, lanes] = cast(dxt * dt[:, lanes] + passed[:, lanes])
            dxs.append(dxt * x32[:, lanes])
        dsc = cast(dscores)
        dc_ref[0, rows, :] = cast(dc + _dot(dsc, bm))
        db_ref[0, rows, :] = cast(db + _dot(dsc, cm, _TN))
        ddt_ref[0, c] = _gathered(jnp.concatenate(dxs, axis=1), pattern)
        by_head = _gathered(dgamma, pattern)
        for j in range(heads):
            dg_ref[0, c, j:j + 1, :] = by_head[j:j + 1] + own_rows[j]
        dskip = dskip + jnp.sum(dy32 * x32, axis=0, keepdims=True)
    dskip_ref[0] += dskip
    # g's cotangent: gamma's summed from each token to its chunk's end
    lower = (row >= col).astype(jnp.bfloat16)
    for c in range(block):
        dg_ref[0, c] = _running_sums(dg_ref[0, c], lower)


def _specs(chunk, block, heads, n, p, steps, reverse=False):
    """The blocks of a grid step ``(b, group, i)``: a step's rows of the
    token-major tensors (x at the group's ``heads`` heads, B and C at the group:
    the repeat to its heads is this index map), of the gates' rows (B, T / C, H,
    C), of the states (B, T / C x N, H P) and of ``D`` spread (1, H P).
    ``reverse``: the backward walks the steps last to first."""
    at = (lambda i: steps - 1 - i) if reverse else (lambda i: i)
    tokens = lambda rows, width: pl.BlockSpec(
        (1, rows, width), lambda b, h, i: (b, at(i), h))
    return {"x": tokens(block * chunk, heads * p), "group": tokens(block * chunk, n),
            "gate": pl.BlockSpec((1, block, heads, chunk),
                                 lambda b, h, i: (b, at(i), h, 0)),
            "s": tokens(block * n, heads * p),
            "skip": pl.BlockSpec((1, heads * p), lambda b, h, i: (0, h)),
            "dskip": pl.BlockSpec((1, 1, heads * p), lambda b, h, i: (b, 0, h))}


_STATIC = ("chunk", "block", "heads", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _fwd_call(x, b, c, g, dt, skip, chunk, block, heads, interpret):
    bsz, steps, total, _ = g.shape
    groups = total // heads
    n, p = b.shape[-1] // groups, x.shape[-1] // total
    sp = _specs(chunk, block, heads, n, p, steps // block)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, block=block, heads=heads, n=n, p=p),
        name="ssd_scan_fwd",
        grid=(bsz, groups, steps // block),
        in_specs=[sp["x"], sp["group"], sp["group"], sp["gate"], sp["gate"], sp["skip"]],
        out_specs=[sp["x"], sp["s"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((bsz, steps * n, total * p), x.dtype)],
        scratch_shapes=[_pltpu.VMEM((n, heads * p), jnp.float32)],
        compiler_params=_params(carried=True),
        interpret=interpret,
    )(x, b, c, g, dt, skip)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _bwd_call(x, b, c, g, dt, skip, states, dy, chunk, block, heads, interpret):
    bsz, steps, total, _ = g.shape
    groups = total // heads
    n, p = b.shape[-1] // groups, x.shape[-1] // total
    sp = _specs(chunk, block, heads, n, p, steps // block, reverse=True)
    like = lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, block=block, heads=heads, n=n, p=p),
        name="ssd_scan_bwd",
        grid=(bsz, groups, steps // block),
        in_specs=[sp["x"], sp["group"], sp["group"], sp["gate"], sp["gate"], sp["skip"],
                  sp["s"], sp["x"]],
        out_specs=[sp["x"], sp["group"], sp["group"], sp["gate"], sp["gate"], sp["dskip"]],
        out_shape=[like(x), like(b), like(c), like(g), like(dt),
                   jax.ShapeDtypeStruct((bsz, 1, total * p), jnp.float32)],
        scratch_shapes=[_pltpu.VMEM((n, heads * p), jnp.float32)],
        compiler_params=_params(carried=True),
        interpret=interpret,
    )(x, b, c, g, dt, skip, states, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _fused(x, b, c, g, dt, skip, static):
    """The scan on token-major x (B, T, H P), B and C (B, T, G N), the gates as
    rows (B, T / C, H, C) float32 and ``D`` spread (1, H P) -> y (B, T, H P).
    ``static``: (chunk, block, heads a group, interpret)."""
    return _fwd_call(x, b, c, g, dt, skip, *static)[0]


def _fused_fwd(x, b, c, g, dt, skip, static):
    y, states = _fwd_call(x, b, c, g, dt, skip, *static)
    return y, (x, b, c, g, dt, skip, states)


def _fused_bwd(static, residuals, dy):
    dx, db, dc, dg, ddt, dskip = _bwd_call(*residuals, dy, *static)
    return dx, db, dc, dg, ddt, jnp.sum(dskip, axis=0)


_fused.defvjp(_fused_fwd, _fused_bwd)


# -- the scan ---------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("chunk", "block", "impl", "interpret"))
def _scan(x, dt, a, b, c, d, chunk, block, impl, interpret):
    """Whole kernel steps of padded tensors -> ``y`` (B, T, H, P).  A ``jit`` of
    its own for ``model.init``'s sake, as the delta rule's ``_rule``."""
    bsz, t, h, p = x.shape
    f32 = jnp.float32
    dt = dt.astype(f32)
    g = dt * a.astype(f32)
    if impl == "jnp":
        y = _chunked(x, dt, g, b, c, chunk)
        return (y + d.astype(f32)[:, None] * x.astype(f32)).astype(x.dtype)
    tokens = lambda v: v.reshape(bsz, t, -1)
    rows = lambda v: jnp.swapaxes(v.reshape(bsz, t // chunk, chunk, h), 2, 3)
    skip = jnp.repeat(d.astype(f32), p).reshape(1, h * p)
    y = _fused(tokens(x), tokens(b), tokens(c), rows(g), rows(dt), skip,
               (chunk, block, h // b.shape[2], interpret))
    return y.reshape(bsz, t, h, p)


def ssd_scan(x, dt, a, b, c, d=None, chunk: int = 128, *, impl: str = "kernel",
             interpret: Optional[bool] = None):
    """Mamba-2's scan in chunks of ``chunk`` tokens (the module's text).

    ``x`` (B, T, H, P), ``dt`` (B, T, H) (after its softplus, > 0), ``a`` (H,)
    (``-exp(A_log)``, < 0), ``b`` and ``c`` (B, T, G, N) in ``x``'s dtype with
    ``G`` a divisor of ``H`` (head ``h`` reads group ``h // (H / G)``; ``db``,
    ``dc`` come back at the groups), ``d`` (H,) the skip or None.  Returns ``y``
    (B, T, H, P) in ``x``'s dtype; differentiable in all six.  ``impl``:
    ``"kernel"`` (the Mosaic kernels, forward and backward) or ``"jnp"`` (XLA's
    products and a ``lax.scan`` over the chunks).  What a backward pass keeps
    under ``"kernel"``: the operands and a state a chunk and head in ``x``'s
    dtype (17 MB a layer of 8,192 tokens x 16 heads of 128 x 64 at chunk
    128)."""
    if impl not in ("kernel", "jnp"):
        raise ValueError(f"impl is 'kernel' or 'jnp', got {impl!r}")
    if (x.ndim != 4 or b.ndim != 4 or b.shape != c.shape or b.shape[:2] != x.shape[:2]
            or x.shape[2] % b.shape[2] or dt.shape != x.shape[:3]
            or a.shape != x.shape[2:3] or (d is not None and d.shape != a.shape)):
        raise ValueError(
            "ssd_scan takes x (B, T, H, P), dt (B, T, H), a (H,), b, c (B, T, G, N), G "
            f"a divisor of H, and d (H,) or None, got {x.shape}, {dt.shape}, {a.shape}, "
            f"{b.shape}, {c.shape}, {None if d is None else d.shape}")
    if not (x.dtype == b.dtype == c.dtype):
        raise ValueError(f"x, b, c are {x.dtype}, {b.dtype}, {c.dtype}: one dtype")
    if chunk < 1:
        raise ValueError(f"chunk is a number of tokens >= 1, got {chunk}")
    bsz, t, h, p = x.shape
    groups, n = b.shape[2:]
    steps = -(-t // chunk)
    block = _BLOCK_CHUNKS if steps >= _BLOCK_CHUNKS else steps
    steps = -(-steps // block) * block
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    ratio = h // groups
    fills = p % 128 == 0 or (128 % p == 0 and ratio % (128 // p) == 0)
    blocks = groups == 1 or ((ratio * p) % 128 == 0 and n % 128 == 0)
    if impl == "kernel" and not interpret and not (fills and blocks and chunk % 128 == 0):
        raise ValueError(
            f"on the chip a program's rows are a group's {ratio} heads of {p} and its "
            f"{n}-wide B and C: multiples of 128 lanes (or one group), heads that fill "
            f"their lane tiles, and chunks of a multiple of 128 tokens, got chunk {chunk}")
    if _trace.enabled():
        _trace.event(
            "ssd.chunks", rows=bsz * t, heads=h, groups=groups, chunk=chunk, chunks=steps,
            head_dim=p, state=n, impl=impl, block=block, heads_a_program=ratio,
            programs=bsz * groups * (steps // block),
            state_bytes=ratio * n * p * 4)
    if d is None:
        d = jnp.zeros((h,), jnp.float32)
    pad = steps * chunk - t
    if pad:
        # dt = 0: nothing is written and nothing forgotten, the state passes through
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                       for v in (x, dt, b, c))
    return _scan(x, dt, a, b, c, d, chunk, block, impl, interpret)[:, :t]
