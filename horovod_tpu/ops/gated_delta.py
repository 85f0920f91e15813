"""The gated delta rule (Gated DeltaNet, Yang et al., arXiv:2412.06464) in
chunked form, its carry over the chunks a Mosaic kernel.

A value head keeps a state ``S`` (keys x values) along the sequence, ``S_0 =
0``, and at every token

    S <- exp(g_t) S;   d_t = beta_t (v_t - S^T k_t);   S <- S + k_t d_t^T;
    o_t = S^T q_t

(``g_t <= 0`` the log of the forget gate, ``beta_t`` in (0, 1) how much of the
old value at ``k_t`` is overwritten).  ``gated_delta_recurrent`` is that, token
by token: the oracle of the tests.

``gated_delta_rule(q, k, v, g, beta, chunk=64)`` computes the same in chunks of
``chunk`` tokens (the WY form of the paper, section 3.3).  With ``gamma_i`` the
running sum of ``g`` inside a chunk and ``S`` the state the chunk is handed:

  chunk-local, every chunk at once (batched XLA products, ``_prepare``):
    ``L = strictly lower(beta_i (k_i . k_j) exp(gamma_i - gamma_j))``
    ``T = (I + L)^-1``                   (unit lower triangular, ``_unit_lower_inverse``)
    ``W = T (beta k exp(gamma))``,  ``U = T (beta v)``
    ``A = lower(q_i . k_j exp(gamma_i - gamma_j))``   (the diagonal included)
    ``Qg = q exp(gamma)``,  ``Kd = k exp(gamma_C - gamma)``,  ``a = exp(gamma_C)``
  across the chunks, one after the other (the carry):
    ``D = U - W S``      (the rows are the ``d_t`` of the chunk)
    ``o = Qg S + A D``
    ``S <- a S + Kd^T D``

Every exponent above is of a sum of ``g`` over a stretch of tokens, so at most
1: nothing overflows however long the chunk.

The carry is ``gated_delta_fwd`` / ``gated_delta_bwd``: a program a (sequence,
group of ``_BLOCK_HEADS`` value heads), the grid's last axis walks the sequence
``block`` chunks a step and each head's state stays in VMEM from chunk to chunk (float32; the products take
their operands as they come, bf16 in the cells, and sum in float32).  The
forward keeps, for the backward, each chunk's ``D`` and the state each chunk
was HANDED (in the operands' dtype: what the forward's own products read): the
backward walks the chunks last to first with the state's cotangent in VMEM and
recomputes nothing.  What is chunk-local stays XLA's, differentiated by
autodiff; ``T``'s transpose is written out (``-T^T dT T^T``: autodiff would keep
every factor of the inverse).  A sequence whose length is no multiple of the
kernel's step is padded with ``g = 0, beta = 0, k = 0``: the state passes
through.

On non-TPU backends the kernels run in interpret mode, so the CPU tests run the
code the chip runs; ``impl="jnp"`` is the same carry as a ``lax.scan`` in
``jax.numpy``.  Traced into a program the rule leaves one ``gdn.chunks`` event
(``horovod_tpu.trace``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as _pltpu

from .. import trace as _trace

# chunks a grid step of the carry: 8 x 64 rows
_BLOCK_CHUNKS = 8
# value heads a program of the carry: a chunk's products wait on each other
# (the state, then the new values, then the state again), and one head's chain
# leaves the MXU idle most of the time; the heads of a program are independent,
# so the scheduler fills one head's waits with another's products
_BLOCK_HEADS = 4
# the widest diagonal block inverted by its own power series (see
# ``_block_inverse``)
_SERIES_BLOCK = 16

_HIGHEST = jax.lax.Precision.HIGHEST


def gated_delta_recurrent(q, k, v, g, beta):
    """The rule token by token, float32: ``q``, ``k`` (B, T, H, dk), ``v`` (B,
    T, H, dv), ``g``, ``beta`` (B, T, H) -> ``o`` (B, T, H, dv)."""
    f32 = lambda x: x.astype(jnp.float32)
    q, k, v, g, beta = map(f32, (q, k, v, g, beta))
    b, _, h, dk = q.shape

    def step(s, xs):
        q_t, k_t, v_t, g_t, beta_t = xs                       # (B, H, .)
        s = s * jnp.exp(g_t)[..., None, None]
        d = beta_t[..., None] * (v_t - jnp.einsum(
            "bhkv,bhk->bhv", s, k_t, precision=_HIGHEST))
        s = s + k_t[..., :, None] * d[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=_HIGHEST)

    by_token = lambda x: jnp.moveaxis(x, 1, 0)
    s0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(step, s0, tuple(map(by_token, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1)


# -- chunk-local ----------------------------------------------------------------


def _series_inverse(lower):
    """``(I + lower)^-1`` of a small strictly lower block by its power series,
    summed by doubling: ``(I + N)(I + N^2)(I + N^4)...`` with ``N = -lower``,
    exact once the power passes the block's size."""
    n = lower.shape[-1]
    power = -lower
    out = jnp.eye(n, dtype=lower.dtype) + power
    reach = 2
    while reach < n:
        power = jnp.matmul(power, power, precision=_HIGHEST)
        out = out + jnp.matmul(out, power, precision=_HIGHEST)
        reach *= 2
    return out


def _block_inverse(lower):
    """``(I + lower)^-1`` for strictly lower ``lower`` (..., n, n), float32.
    Blocks of at most ``_SERIES_BLOCK`` by their power series; larger ones by
    halves, ``[[A, 0], [C, D]]^-1 = [[A^-1, 0], [-D^-1 C A^-1, D^-1]]``: a block
    forward substitution, so the powers of a whole chunk's ``lower`` (which grow
    like binomials when neighbouring keys are alike) are never formed.  The
    blocks are cut out and joined again: the same steps on whole (n, n)
    matrices under masks, which spare the chip the eight-fold padding of a
    16-wide minor axis, took twice the time there (ten 64 x 64 x 64 float32
    products at ``highest`` a chunk and head: 47 ms a step against 20; PERF.md
    section 6, PR 35)."""
    n = lower.shape[-1]
    if n <= _SERIES_BLOCK:
        return _series_inverse(lower)
    h = n // 2
    a = _block_inverse(lower[..., :h, :h])
    d = _block_inverse(lower[..., h:, h:])
    c = -jnp.matmul(jnp.matmul(d, lower[..., h:, :h], precision=_HIGHEST), a,
                    precision=_HIGHEST)
    top = jnp.concatenate([a, jnp.zeros_like(lower[..., :h, h:])], axis=-1)
    return jnp.concatenate([top, jnp.concatenate([c, d], axis=-1)], axis=-2)


@jax.custom_vjp
def _unit_lower_inverse(lower):
    """``T = (I + lower)^-1``; its transpose is ``-T^T dT T^T``."""
    return _block_inverse(lower)


def _unit_lower_inverse_fwd(lower):
    t = _block_inverse(lower)
    return t, t


def _unit_lower_inverse_bwd(t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    return (-jnp.matmul(jnp.matmul(tt, dt, precision=_HIGHEST), tt,
                        precision=_HIGHEST),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _prepare(q, k, v, g, beta, chunk):
    """What the carry takes, from the layer's tensors padded to whole chunks,
    all HEAD-MAJOR (the chunk-local products are batched over (sequence, head,
    chunk), and with the heads inside the rows every one of them would be a
    transpose first): ``W``, ``Qg``, ``Kd`` (B, H, T, dk), ``U`` (B, H, T, dv),
    ``A`` (B, H, T, chunk) in ``q``'s dtype and ``a`` (B, H, N, dv) float32, a
    chunk's number written along its ``dv`` columns (the kernel multiplies the
    state's rows by it)."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    n = t // chunk
    dtype = q.dtype
    f32 = jnp.float32
    chunks = lambda x: jnp.moveaxis(x.reshape(b, n, chunk, *x.shape[2:]), 3, 1)
    q, k, v, g, beta = map(chunks, (q, k, v, g.astype(f32), beta.astype(f32)))
    gamma = jnp.cumsum(g, axis=-1)                              # (B, H, N, C)
    total = gamma[..., -1:]                                     # gamma_C
    diff = gamma[..., :, None] - gamma[..., None, :]            # gamma_i - gamma_j
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.exp(jnp.where(row >= col, diff, -jnp.inf))      # 0 above the diagonal
    k_beta = (k * beta[..., None]).astype(dtype)
    kk = jnp.einsum("bhnid,bhnjd->bhnij", k_beta, k, preferred_element_type=f32)
    t_inv = _unit_lower_inverse(jnp.where(row > col, kk * decay, 0.0)).astype(dtype)
    rise = jnp.exp(gamma)[..., None]
    w = jnp.einsum("bhnij,bhnjd->bhnid", t_inv, (k_beta * rise).astype(dtype),
                   preferred_element_type=f32)
    u = jnp.einsum("bhnij,bhnjd->bhnid", t_inv, (v * beta[..., None]).astype(dtype),
                   preferred_element_type=f32)
    a_qk = jnp.einsum("bhnid,bhnjd->bhnij", q, k, preferred_element_type=f32) * decay
    qg = q * rise
    kd = k * jnp.exp(total - gamma)[..., None]
    a = jnp.broadcast_to(jnp.exp(total), (b, h, n, dv))
    flat = lambda x: x.astype(dtype).reshape(b, h, t, -1)
    return flat(w), flat(u), flat(qg), flat(kd), flat(a_qk), a


# -- the carry: jax.numpy -------------------------------------------------------


def _carry_scan(w, u, qg, kd, a_qk, a, chunk):
    """The carry as a ``lax.scan`` over the chunks, every head at once."""
    b, h, t, dk = w.shape
    n = t // chunk
    f32 = jnp.float32
    by_chunk = lambda x: jnp.moveaxis(x.reshape(b, h, n, chunk, -1), 2, 0)

    def step(s, xs):
        w_c, u_c, qg_c, kd_c, a_c, decay = xs
        sb = s.astype(w.dtype)
        d = u_c.astype(f32) - jnp.einsum("bhik,bhkv->bhiv", w_c, sb,
                                         preferred_element_type=f32)
        db = d.astype(w.dtype)
        o = (jnp.einsum("bhik,bhkv->bhiv", qg_c, sb, preferred_element_type=f32)
             + jnp.einsum("bhij,bhjv->bhiv", a_c, db, preferred_element_type=f32))
        s = decay[:, :, None, :] * s + jnp.einsum(
            "bhik,bhiv->bhkv", kd_c, db, preferred_element_type=f32)
        return s, o.astype(w.dtype)

    s0 = jnp.zeros((b, h, dk, u.shape[-1]), f32)
    _, o = jax.lax.scan(step, s0, (by_chunk(w), by_chunk(u), by_chunk(qg), by_chunk(kd),
                                   by_chunk(a_qk), jnp.moveaxis(a, 2, 0)))
    return jnp.moveaxis(o, 0, 2).reshape(b, h, t, -1)


# -- the carry: Mosaic ----------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))    # x @ y^T
_TN = (((0,), (0,)), ((), ()))    # x^T @ y


def _dot(x, y, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(x, y, dims, preferred_element_type=jnp.float32)


def _fwd_kernel(w_ref, u_ref, qg_ref, kd_ref, aqk_ref, a_ref, o_ref, d_ref,
                s_ref, state, *, chunk, block, heads, dk, dv):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    dtype = w_ref.dtype
    for c in range(block):
        rows = slice(c * chunk, (c + 1) * chunk)
        for j in range(heads):
            s = state[j]
            sb = s.astype(dtype)
            s_ref[0, j, c * dk:(c + 1) * dk, :] = sb       # the state the chunk is handed
            d = u_ref[0, j, rows, :].astype(jnp.float32) - _dot(w_ref[0, j, rows, :], sb)
            db = d.astype(dtype)
            d_ref[0, j, rows, :] = db
            o = _dot(qg_ref[0, j, rows, :], sb) + _dot(aqk_ref[0, j, rows, :], db)
            o_ref[0, j, rows, :] = o.astype(o_ref.dtype)
            state[j] = a_ref[0, j, c:c + 1, :] * s + _dot(kd_ref[0, j, rows, :], db, _TN)


def _bwd_kernel(w_ref, qg_ref, kd_ref, aqk_ref, a_ref, d_ref, s_ref, do_ref,
                dw_ref, du_ref, dqg_ref, dkd_ref, daqk_ref, da_ref, dstate,
                *, chunk, block, heads, dk, dv):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    dtype = w_ref.dtype
    for c in reversed(range(block)):
        rows = slice(c * chunk, (c + 1) * chunk)
        for j in range(heads):
            ds = dstate[j]                                 # of the state the chunk leaves
            dsb = ds.astype(dtype)
            sb = s_ref[0, j, c * dk:(c + 1) * dk, :]
            do, d = do_ref[0, j, rows, :], d_ref[0, j, rows, :]
            dd = (_dot(aqk_ref[0, j, rows, :], do, _TN)
                  + _dot(kd_ref[0, j, rows, :], dsb))
            ddb = dd.astype(dtype)
            daqk_ref[0, j, rows, :] = _dot(do, d, _NT).astype(daqk_ref.dtype)
            dqg_ref[0, j, rows, :] = _dot(do, sb, _NT).astype(dqg_ref.dtype)
            dkd_ref[0, j, rows, :] = _dot(d, dsb, _NT).astype(dkd_ref.dtype)
            da_ref[0, j, c:c + 1, :] = jnp.sum(sb.astype(jnp.float32) * ds, axis=0,
                                               keepdims=True)
            du_ref[0, j, rows, :] = ddb
            dw_ref[0, j, rows, :] = (-_dot(ddb, sb, _NT)).astype(dw_ref.dtype)
            dstate[j] = (a_ref[0, j, c:c + 1, :] * ds
                         + _dot(qg_ref[0, j, rows, :], do, _TN)
                         - _dot(w_ref[0, j, rows, :], ddb, _TN))


def _specs(chunk, block, heads, dk, dv, steps, reverse):
    """The blocks of a grid step ``(b, h, i)``, ``h`` a group of ``heads`` value
    heads, by the rows' width: a step's rows of the (B, H, T, .) tensors,
    ``a``'s (B, H, N, dv), the states' (B, H, N dk, dv).  ``reverse``: the
    backward walks the steps last to first."""
    at = (lambda i: steps - 1 - i) if reverse else (lambda i: i)
    rows = lambda size, width: pl.BlockSpec(
        (1, heads, size, width), lambda b, h, i: (b, h, at(i), 0))
    return {"k": rows(block * chunk, dk), "v": rows(block * chunk, dv),
            "aqk": rows(block * chunk, chunk), "a": rows(block, dv),
            "s": rows(block * dk, dv)}


def _head_group(heads: int) -> int:
    """Value heads a program: ``_BLOCK_HEADS`` where that divides them."""
    return _BLOCK_HEADS if heads % _BLOCK_HEADS == 0 else 1


_PARAMS = dict(dimension_semantics=("parallel", "parallel", "arbitrary"))


@functools.partial(jax.jit, static_argnames=("chunk", "block", "group", "interpret"))
def _carry_fwd_call(w, u, qg, kd, a_qk, a, chunk, block, group, interpret):
    b, heads, t, dk = w.shape
    dv = u.shape[-1]
    n = t // chunk
    steps = n // block
    sp = _specs(chunk, block, group, dk, dv, steps, False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, block=block, heads=group,
                          dk=dk, dv=dv),
        name="gated_delta_fwd",
        grid=(b, heads // group, steps),
        in_specs=[sp["k"], sp["v"], sp["k"], sp["k"], sp["aqk"], sp["a"]],
        out_specs=[sp["v"], sp["v"], sp["s"]],
        out_shape=[jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct(u.shape, u.dtype),
                   jax.ShapeDtypeStruct((b, heads, n * dk, dv), u.dtype)],
        scratch_shapes=[_pltpu.VMEM((group, dk, dv), jnp.float32)],
        compiler_params=_pltpu.CompilerParams(**_PARAMS),
        interpret=interpret,
    )(w, u, qg, kd, a_qk, a)


@functools.partial(jax.jit, static_argnames=("chunk", "block", "group", "interpret"))
def _carry_bwd_call(w, qg, kd, a_qk, a, d, states, do, chunk, block, group, interpret):
    b, heads, t, dk = w.shape
    dv = d.shape[-1]
    steps = t // chunk // block
    sp = _specs(chunk, block, group, dk, dv, steps, True)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, block=block, heads=group,
                          dk=dk, dv=dv),
        name="gated_delta_bwd",
        grid=(b, heads // group, steps),
        in_specs=[sp["k"], sp["k"], sp["k"], sp["aqk"], sp["a"], sp["v"], sp["s"],
                  sp["v"]],
        out_specs=[sp["k"], sp["v"], sp["k"], sp["k"], sp["aqk"], sp["a"]],
        out_shape=[like(w), like(d), like(qg), like(kd), like(a_qk), like(a)],
        scratch_shapes=[_pltpu.VMEM((group, dk, dv), jnp.float32)],
        compiler_params=_pltpu.CompilerParams(**_PARAMS),
        interpret=interpret,
    )(w, qg, kd, a_qk, a, d, states, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _carry(w, u, qg, kd, a_qk, a, static):
    """``static``: (chunk, block, heads a program, interpret)."""
    return _carry_fwd_call(w, u, qg, kd, a_qk, a, *static)[0]


def _carry_fwd(w, u, qg, kd, a_qk, a, static):
    o, d, states = _carry_fwd_call(w, u, qg, kd, a_qk, a, *static)
    return o, (w, qg, kd, a_qk, a, d, states)


def _carry_bwd(static, residuals, do):
    return _carry_bwd_call(*residuals, do, *static)


_carry.defvjp(_carry_fwd, _carry_bwd)


# -- the rule -------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("chunk", "block", "group", "impl",
                                             "interpret"))
def _rule(q, k, v, g, beta, chunk, block, group, impl, interpret):
    """Whole kernel steps of padded tensors -> ``o`` (B, H, T, dv).  A ``jit`` of
    its own for ``model.init``'s sake, which runs a layer operation by
    operation: one program there, not the hundred the chunk-local part is."""
    prepared = _prepare(q, k, v, g, beta, chunk)
    if impl == "jnp":
        return _carry_scan(*prepared, chunk)
    return _carry(*prepared, (chunk, block, group, interpret))


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64, *, impl: str = "kernel",
                     interpret: Optional[bool] = None):
    """The gated delta rule in chunks of ``chunk`` tokens (the module's text).

    ``q``, ``k`` (B, T, H, dk) and ``v`` (B, T, H, dv) in one dtype (``q``
    already scaled, ``q`` and ``k`` already normalised and repeated to the value
    heads), ``g`` (B, T, H) the log of the forget gate (<= 0), ``beta`` (B, T,
    H).  Returns ``o`` (B, T, H, dv) in ``q``'s dtype; differentiable in all
    five.  ``impl``: ``"kernel"`` (the carry a Mosaic kernel, forward and
    backward) or ``"jnp"`` (the carry a ``lax.scan``).  What a backward
    pass keeps: the chunk-local tensors' residuals and the kernel's (``W``,
    ``Qg``, ``Kd``, ``A``, ``a``, ``D`` and a state a chunk), 1.1 GB a layer of
    8,192 tokens x 32 heads in bf16; a caller short of memory wraps the call in
    ``jax.checkpoint`` (``models.transformer.GatedDeltaNet`` does)."""
    if impl not in ("kernel", "jnp"):
        raise ValueError(f"impl is 'kernel' or 'jnp', got {impl!r}")
    if (q.ndim != 4 or q.shape != k.shape or v.shape[:3] != q.shape[:3]
            or g.shape != q.shape[:3] or beta.shape != q.shape[:3]):
        raise ValueError(
            "gated_delta_rule takes q, k (B, T, H, dk), v (B, T, H, dv) and g, "
            f"beta (B, T, H), got {q.shape}, {k.shape}, {v.shape}, {g.shape}, "
            f"{beta.shape}")
    if not (q.dtype == k.dtype == v.dtype):
        raise ValueError(f"q, k, v are {q.dtype}, {k.dtype}, {v.dtype}: one dtype")
    if chunk < 1:
        raise ValueError(f"chunk is a number of tokens >= 1, got {chunk}")
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    n = -(-t // chunk)
    # whole kernel steps: a step's chunks are 8 (the tiling of ``a``'s block) or
    # all there are
    block = _BLOCK_CHUNKS if n >= _BLOCK_CHUNKS else n
    n = -(-n // block) * block
    group = _head_group(h)
    if _trace.enabled():
        _trace.event("gdn.chunks", rows=b * t, value_heads=h, chunk=chunk,
                     chunks=n, d_k=dk, d_v=dv, impl=impl,
                     programs=b * (h // group) * (n // block), block=block,
                     heads_a_program=group)
    pad = n * chunk - t
    if pad:
        # g = 0, beta = 0, k = 0: the state passes through
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                            for x in (q, k, v, g, beta))
    if interpret is None:   # here, not under the jit: a trace is kept
        interpret = jax.default_backend() != "tpu"
    o = _rule(q, k, v, g, beta, chunk, block, group, impl, interpret)
    return jnp.moveaxis(o, 1, 2)[:, :t]
