"""The gated delta rule (Gated DeltaNet, Yang et al., arXiv:2412.06464) in
chunked form, as three Mosaic kernels.

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

  chunk-local, every chunk independent of the others:
    ``L = strictly lower(beta_i (k_i . k_j) exp(gamma_i - gamma_j))``
    ``T = (I + L)^-1``                   (unit lower triangular, float32)
    ``W = T (beta k exp(gamma))``,  ``U = T (beta v)``
    ``A = lower(q_i . k_j exp(gamma_i - gamma_j))``   (the diagonal included)
    ``Qg = q exp(gamma)``,  ``Kd = k exp(gamma_C - gamma)``,  ``a = exp(gamma_C)``
  across the chunks, one after the other (the carry):
    ``D = U - W S``      (the rows are the ``d_t`` of the chunk)
    ``o = Qg S + A D``
    ``S <- a S + Kd^T D``

Every exponent above is of a sum of ``g`` over a stretch of tokens, so at most
1: nothing overflows however long the chunk.  Products take their operands as
they come (bf16 in the benchmark's cell) and sum in float32; ``T``, the gates
and the state are float32; each chunk-local tensor is rounded to the operands'
dtype once, where ``_prepare`` rounds it.

``impl="kernel"`` (since PR 36 nothing of the rule is XLA's but ``T``'s inverse
and the layouts in and out): q, k, v go in token-major ``(B, T, H d)``, ``g`` and ``beta`` as rows a
chunk ``(B, H, N, C)``, and a program is a (sequence, group of ``_BLOCK_HEADS``
value heads) with the grid's last axis walking the sequence ``block`` chunks a
step.  q and k may come at fewer heads than v (``Hk`` key heads, a divisor of
the ``H`` value heads: Gated DeltaNet's 16 and 32): value head ``j`` reads key
head ``j // (H / Hk)``.  Since PR 38 that repeat is the kernels' index map: a
program's q and k blocks are its value heads' ``_BLOCK_HEADS / (H / Hk)`` key
heads (``_key_heads_a_program``: whole key heads, a multiple of 128 lanes or
all of them), the backward sums the value heads' dq, dk a key head in VMEM in
float32 and writes them at ``Hk`` heads; any other shape, and ``impl="jnp"``,
repeats q and k first (``jnp.repeat``, XLA's).  Equal head counts are the
program they were.  (``models.transformer.GatedDeltaNet`` hands q, k, v over as
the token-major rows ``ops/gdn_kernels.py`` writes: the reshapes to ``(B, T, H,
d)`` and back fold away, and no layout of XLA's is left between them.)

  ``gated_delta_kkt``      k, g, beta -> ``L`` (float32), one product a chunk and
      head; ``T = (I + L)^-1`` is then XLA's (``_block_inverse``: 16-wide power
      series and block substitution, float32 at ``highest``).  The inverse by
      forward substitution on the VPU inside this kernel was 0.9 ms a call faster
      and cost the cell's step 0.6 GB and all but 1 ms of that by what XLA then
      made of the layer's gated norm (PERF.md section 6, PR 36): not kept.
  ``gated_delta_fwd``      q, k, v, g, beta, ``T`` -> ``o``, and for the backward
      each chunk's ``D`` and the state each chunk was HANDED (in the operands'
      dtype: what the forward's own products read).  A chunk and head at a time
      it makes ``gamma``, the decays, ``W``, ``U``, ``A``, ``Qg``, ``Kd``, ``a`` in
      VMEM (``_chunk_local``) and runs the carry, each head's state in VMEM from
      chunk to chunk; a step's chunks and heads are independent until the
      carry, so their products fill the waits of the carry's chain.
  ``gated_delta_bwd``      the same and ``D``, the states, ``dO`` -> dq, dk, dv,
      dg, dbeta: walks the chunks last to first with the state's cotangent in
      VMEM, makes the chunk-local tensors again (a handful of 64-wide products:
      cheaper than reading them) and writes their backward out, ``L``'s too: the
      inverse's transpose ``-T^T dT T^T`` (float32 at ``highest``), the decays'
      and the gates' cotangents, the running sum's.  No residual but the
      kernels' operands.

A sequence whose length is no multiple of the kernel's step is padded with ``g
= 0, beta = 0, k = 0``: the state passes through.  On non-TPU backends the
kernels run in interpret mode, so the CPU tests run the code the chip runs.  On
the chip a program's rows are ``_BLOCK_HEADS`` heads wide: that has to be a
multiple of 128 lanes, or all the heads.

``impl="jnp"`` is the second oracle, and what 'dot' models run: the chunk-local
tensors as batched XLA products (``_prepare``; ``T`` by 16-wide power series and
block substitution, ``_unit_lower_inverse``, its transpose written out; the rest
differentiated by autodiff) and the carry as a ``lax.scan``.  Traced into a
program the rule leaves one ``gdn.chunks`` event (``horovod_tpu.trace``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as _pltpu

from .. import trace as _trace

# chunks a grid step of the kernels: 8 x 64 rows
_BLOCK_CHUNKS = 8
# value heads a program of the kernels: a chunk's products wait on each other
# (the state, then the new values, then the state again), and one head's chain
# leaves the MXU idle most of the time; the heads of a program are independent,
# so the scheduler fills one head's waits with another's products
_BLOCK_HEADS = 4
# what a program of the backward holds of VMEM at the cell's widths (eight
# chunks of four 128-wide heads: 13 blocks, two in flight) passes Mosaic's 16 MiB
_VMEM_BYTES = 64 * 1024 * 1024
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


# -- the rule's kernels: Mosaic -------------------------------------------------

_NT = (((1,), (1,)), ((), ()))    # x @ y^T
_TN = (((0,), (0,)), ((), ()))    # x^T @ y


def _dot(x, y, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(x, y, dims, preferred_element_type=jnp.float32)


def _dot_f32(x, y, dims):
    """float32 operands to float32 accuracy (``T``'s transpose, as XLA's at
    ``highest``)."""
    return jax.lax.dot_general(x, y, dims, precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _iotas(chunk):
    return (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0),
            jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))


def _running_sums(x, ones):
    """``x`` (rows, C) float32 times a (C, C) matrix of zeros and ones to
    float32 accuracy whatever the MXU makes of a float32 operand: ``x`` in three
    bfloat16 pieces (3 x 8 bits), each product exact, summed in float32.  The
    running sums of a grid step's ``g`` (``gamma``) and, transposed, of
    ``gamma``'s cotangent."""
    out = jnp.zeros(x.shape, jnp.float32)
    for _ in range(3):
        piece = x.astype(jnp.bfloat16)
        out = out + _dot(piece, ones)
        x = x - piece.astype(jnp.float32)
    return out


def _gammas(g_ref, heads, chunk):
    """``gamma`` (block, C) of each of a grid step's heads: the running sums of
    its rows of ``g`` along their chunks."""
    row, col = _iotas(chunk)
    upper = (row <= col).astype(jnp.bfloat16)
    return [_running_sums(g_ref[0, j], upper) for j in range(heads)]


def _sum_all(x):
    return jnp.sum(jnp.sum(x, axis=0, keepdims=True), axis=1, keepdims=True)


def _gates(gamma_row, beta_row, chunk):
    """A chunk and head's gates from its ``gamma`` and ``beta`` as rows (1, C):
    ``beta`` down a column (C, 1), the decays ``e^(gamma_i - gamma_j)`` (C, C; 0
    above the diagonal), ``e^gamma`` and ``e^(gamma_C - gamma)`` as columns, and
    ``e^gamma_C`` (1, 1)."""
    row, col = _iotas(chunk)
    # a row's numbers down a column, exactly: the one of its lanes on the diagonal
    column = lambda x: jnp.sum(jnp.where(row == col, x, 0.0), axis=1, keepdims=True)
    gamma = column(gamma_row)
    # gamma_C by a sum over the lanes, not a slice: Mosaic then holds it in every
    # lane, and spreads it over a state's rows too
    total = jnp.sum(jnp.where(col[:1] == chunk - 1, gamma_row, 0.0), axis=1,
                    keepdims=True)
    decay = jnp.exp(jnp.where(row >= col, gamma - gamma_row, -jnp.inf))
    return (column(beta_row), decay, jnp.exp(gamma), jnp.exp(total - gamma),
            jnp.exp(total))


def _chunk_local(q, k, v, t, gates):
    """A chunk and head's tensors in VMEM from its rows of q, k (C, dk), v (C,
    dv), ``T`` (C, C) in their dtype and its ``_gates``: the module's text,
    rounded to the operands' dtype exactly where ``_prepare`` rounds.  A name
    ending in ``32`` is the float32 product before its rounding (the backward's
    gate cotangents read those)."""
    f32, dtype = jnp.float32, k.dtype
    beta, decay, rise, fall, _ = gates
    k32 = k.astype(f32)
    kb = (k32 * beta).astype(dtype)
    kbr32 = kb.astype(f32) * rise
    aqk32 = _dot(q, k, _NT) * decay
    qg32, kd32 = q.astype(f32) * rise, k32 * fall
    kbr = kbr32.astype(dtype)
    return dict(k32=k32, kb=kb, kbr32=kbr32, kbr=kbr, w=_dot(t, kbr).astype(dtype),
                vb=(v.astype(f32) * beta).astype(dtype), aqk32=aqk32,
                aqk=aqk32.astype(dtype), qg32=qg32, qg=qg32.astype(dtype),
                kd32=kd32, kd=kd32.astype(dtype))


def _strictly_lower(kb, k, decay):
    """``L = strictly lower(beta_i (k_i . k_j) e^(gamma_i - gamma_j))``, float32,
    from ``kb = beta k`` in k's dtype."""
    row, col = _iotas(decay.shape[0])
    return jnp.where(row > col, _dot(kb, k, _NT) * decay, 0.0)


def _kkt_kernel(k_ref, g_ref, beta_ref, l_ref, *, chunk, block, heads, ratio, dk):
    for j, gammas in enumerate(_gammas(g_ref, heads, chunk)):
        for c in range(block):
            rows = slice(c * chunk, (c + 1) * chunk)
            k = k_ref[0, rows, _key_columns(j, ratio, dk)]
            beta, decay, *_ = _gates(gammas[c:c + 1], beta_ref[0, j, c:c + 1, :], chunk)
            kb = (k.astype(jnp.float32) * beta).astype(k.dtype)
            l_ref[0, j, rows, :] = _strictly_lower(kb, k, decay)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, t_ref, o_ref, d_ref, s_ref,
                state, *, chunk, block, heads, ratio, dk, dv):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    dtype = q_ref.dtype
    gammas = _gammas(g_ref, heads, chunk)
    for c in range(block):
        rows = slice(c * chunk, (c + 1) * chunk)
        for j in range(heads):
            keys, values = _key_columns(j, ratio, dk), slice(j * dv, (j + 1) * dv)
            t = t_ref[0, j, rows, :].astype(dtype)
            gates = _gates(gammas[j][c:c + 1], beta_ref[0, j, c:c + 1, :], chunk)
            x = _chunk_local(q_ref[0, rows, keys], k_ref[0, rows, keys],
                             v_ref[0, rows, values], t, gates)
            u = _dot(t, x["vb"]).astype(dtype)
            s = state[j]
            sb = s.astype(dtype)
            s_ref[0, j, c * dk:(c + 1) * dk, :] = sb       # the state the chunk is handed
            d = u.astype(jnp.float32) - _dot(x["w"], sb)
            db = d.astype(dtype)
            d_ref[0, rows, values] = db
            o = _dot(x["qg"], sb) + _dot(x["aqk"], db)
            o_ref[0, rows, values] = o.astype(o_ref.dtype)
            state[j] = gates[-1] * s + _dot(x["kd"], db, _TN)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, t_ref, d_ref, s_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate,
                *, chunk, block, heads, ratio, dk, dv):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    f32, dtype = jnp.float32, q_ref.dtype
    cast = lambda x: x.astype(dtype)
    row, col = _iotas(chunk)
    # a column's numbers along a row, exactly
    as_row = lambda x: jnp.sum(jnp.where(row == col, x, 0.0), axis=0, keepdims=True)
    by_row = lambda x: jnp.sum(x, axis=1, keepdims=True)
    sums = {}

    def shared(ref, name, value, j, rows, keys):
        """dq or dk of value head ``j``: the value heads of one key head summed
        in float32 and written once, at the key head."""
        if j % ratio:
            value = sums[name] + value
        if (j + 1) % ratio:
            sums[name] = value
        else:
            ref[0, rows, keys] = cast(value)

    gammas = _gammas(g_ref, heads, chunk)
    for c in reversed(range(block)):
        rows = slice(c * chunk, (c + 1) * chunk)
        for j in range(heads):
            keys, values = _key_columns(j, ratio, dk), slice(j * dv, (j + 1) * dv)
            q, k, v = q_ref[0, rows, keys], k_ref[0, rows, keys], v_ref[0, rows, values]
            t32 = t_ref[0, j, rows, :]
            t = cast(t32)
            gates = _gates(gammas[j][c:c + 1], beta_ref[0, j, c:c + 1, :], chunk)
            beta, decay, rise, fall, a = gates
            x = _chunk_local(q, k, v, t, gates)
            lower32 = _strictly_lower(x["kb"], k, decay)
            # the carry, last chunk to first
            ds = dstate[j]                                 # of the state the chunk leaves
            dsb = cast(ds)
            sb = s_ref[0, j, c * dk:(c + 1) * dk, :]
            do, d = do_ref[0, rows, values], d_ref[0, rows, values]
            dd = _dot(x["aqk"], do, _TN) + _dot(x["kd"], dsb)     # of D, and of U
            ddb = cast(dd)
            daqk = _dot(do, d, _NT)
            dqg = _dot(do, sb, _NT)
            dkd = _dot(d, dsb, _NT)
            dwb = cast(-_dot(ddb, sb, _NT))
            dtotal = a * _sum_all(sb.astype(f32) * ds) + _sum_all(dkd * x["kd32"])
            dstate[j] = a * ds + _dot(x["qg"], do, _TN) - _dot(x["w"], ddb, _TN)
            # chunk-local: W = T (beta k e^gamma), U = T (beta v), T = (I + L)^-1
            dt = _dot(dwb, x["kbr"], _NT) + _dot(ddb, x["vb"], _NT)
            dkbr = _dot(t, dwb, _TN)
            dvb = _dot(t, ddb, _TN)
            dlower = jnp.where(
                row > col, -_dot_f32(_dot_f32(t32, dt, _TN), t32, _NT), 0.0)
            dkk, dqk = cast(dlower * decay), cast(daqk * decay)
            dkb = _dot(dkk, k) + dkbr * rise
            shared(dq_ref, "dq", _dot(dqk, k) + dqg * rise, j, rows, keys)
            shared(dk_ref, "dk", _dot(dkk, x["kb"], _TN) + _dot(dqk, q, _TN)
                   + dkd * fall + dkb * beta, j, rows, keys)
            dv_ref[0, rows, values] = cast(dvb * beta)
            dbeta_ref[0, j, c:c + 1, :] = as_row(
                by_row(dkb * x["k32"]) + by_row(dvb * v.astype(f32)))
            # gamma: through e^gamma, e^(gamma_C - gamma), e^gamma_C and the
            # decays e^(gamma_i - gamma_j), whose cotangent times themselves is
            # ``both``: + along its rows, - along its columns
            both = dlower * lower32 + daqk * x["aqk32"]
            dgamma = by_row(both) + by_row(dqg * x["qg32"] + dkbr * x["kbr32"]
                                           - dkd * x["kd32"])
            dg_ref[0, j, c:c + 1, :] = (
                as_row(dgamma) - jnp.sum(both, axis=0, keepdims=True)
                + jnp.where(col[:1] == chunk - 1, dtotal, 0.0))
    # g's cotangent: gamma's summed from each token to its chunk's end
    lower = (row >= col).astype(jnp.bfloat16)
    for j in range(heads):
        dg_ref[0, j] = _running_sums(dg_ref[0, j], lower)


def _key_columns(j, ratio, dk):
    """A program's value head ``j`` reads (and its dq, dk are written at) the
    key head ``j // ratio`` of the program's key block."""
    return slice(j // ratio * dk, (j // ratio + 1) * dk)


def _specs(chunk, block, heads, ratio, dk, dv, steps, reverse=False):
    """The blocks of a grid step ``(b, h, i)``, ``h`` a group of ``heads`` value
    heads: a step's rows of the token-major (B, T, H d) tensors by a head's
    width (q and k by the group's ``heads // ratio`` KEY heads: the repeat to
    the value heads is this index map), of the gates' rows (B, H, N, C), of
    ``L`` and ``T`` (B, H, T, C) and of the states (B, H, N dk, dv).
    ``reverse``: the backward walks the steps last to first."""
    at = (lambda i: steps - 1 - i) if reverse else (lambda i: i)
    tokens = lambda width: pl.BlockSpec(
        (1, block * chunk, width), lambda b, h, i: (b, at(i), h))
    by_head = lambda size, width: pl.BlockSpec(
        (1, heads, size, width), lambda b, h, i: (b, h, at(i), 0))
    return {"k": tokens(heads // ratio * dk), "v": tokens(heads * dv),
            "gate": by_head(block, chunk), "t": by_head(block * chunk, chunk),
            "s": by_head(block * dk, dv)}


def _head_group(heads: int) -> int:
    """Value heads a program: ``_BLOCK_HEADS`` where that divides them."""
    return _BLOCK_HEADS if heads % _BLOCK_HEADS == 0 else 1


def _key_heads_a_program(group: int, key_heads: int, ratio: int, dk: int) -> int:
    """Key heads a program of ``group`` value heads reads where q and k come at
    ``key_heads`` = value heads / ``ratio``: the group's own, when they are
    whole heads and a block Mosaic takes (a multiple of 128 lanes, or all the
    key heads); else 0, and the rule repeats q and k to the value heads."""
    if group % ratio:
        return 0
    held = group // ratio
    return held if held == key_heads or held * dk % 128 == 0 else 0


def _params(carried: bool):
    return _pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel",
                             "arbitrary" if carried else "parallel"),
        vmem_limit_bytes=_VMEM_BYTES)


_STATIC = ("chunk", "block", "group", "ratio", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _kkt_call(k, g, beta, chunk, block, group, ratio, interpret):
    """``L`` (B, H, T, C) float32 from k (B, T, H / ratio dk) and the gates'
    rows."""
    b, heads, n, _ = g.shape
    dk = k.shape[-1] * ratio // heads
    sp = _specs(chunk, block, group, ratio, dk, dk, n // block)
    return pl.pallas_call(
        functools.partial(_kkt_kernel, chunk=chunk, block=block, heads=group,
                          ratio=ratio, dk=dk),
        name="gated_delta_kkt",
        grid=(b, heads // group, n // block),
        in_specs=[sp["k"], sp["gate"], sp["gate"]],
        out_specs=sp["t"],
        out_shape=jax.ShapeDtypeStruct((b, heads, n * chunk, chunk), jnp.float32),
        compiler_params=_params(carried=False),
        interpret=interpret,
    )(k, g, beta)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _fwd_call(q, k, v, g, beta, t_inv, chunk, block, group, ratio, interpret):
    b, heads, n, _ = g.shape
    dk, dv = q.shape[-1] * ratio // heads, v.shape[-1] // heads
    sp = _specs(chunk, block, group, ratio, dk, dv, n // block)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, block=block, heads=group,
                          ratio=ratio, dk=dk, dv=dv),
        name="gated_delta_fwd",
        grid=(b, heads // group, n // block),
        in_specs=[sp["k"], sp["k"], sp["v"], sp["gate"], sp["gate"], sp["t"]],
        out_specs=[sp["v"], sp["v"], sp["s"]],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((b, heads, n * dk, dv), v.dtype)],
        scratch_shapes=[_pltpu.VMEM((group, dk, dv), jnp.float32)],
        compiler_params=_params(carried=True),
        interpret=interpret,
    )(q, k, v, g, beta, t_inv)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _bwd_call(q, k, v, g, beta, t_inv, d, states, do, chunk, block, group, ratio,
              interpret):
    b, heads, n, _ = g.shape
    dk, dv = q.shape[-1] * ratio // heads, v.shape[-1] // heads
    sp = _specs(chunk, block, group, ratio, dk, dv, n // block, reverse=True)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, block=block, heads=group,
                          ratio=ratio, dk=dk, dv=dv),
        name="gated_delta_bwd",
        grid=(b, heads // group, n // block),
        in_specs=[sp["k"], sp["k"], sp["v"], sp["gate"], sp["gate"], sp["t"],
                  sp["v"], sp["s"], sp["v"]],
        out_specs=[sp["k"], sp["k"], sp["v"], sp["gate"], sp["gate"]],
        out_shape=[like(q), like(k), like(v), like(g), like(beta)],
        scratch_shapes=[_pltpu.VMEM((group, dk, dv), jnp.float32)],
        compiler_params=_params(carried=True),
        interpret=interpret,
    )(q, k, v, g, beta, t_inv, d, states, do)


# the inverse stays XLA's, under a name of its own: the compiled text tells its
# products from any other left in the rule
_inverse = jax.jit(_block_inverse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _fused(q, k, v, g, beta, static):
    """The rule on token-major q, k (B, T, H / ratio dk), v (B, T, H dv) and the
    gates as rows (B, H, N, C) float32 -> o (B, T, H dv).  ``static``: (chunk,
    block, value heads a program, value heads a key head, interpret)."""
    return _fused_fwd(q, k, v, g, beta, static)[0]


def _fused_fwd(q, k, v, g, beta, static):
    lower = _kkt_call(k, g, beta, *static)
    t_inv = _inverse(lower.reshape(*g.shape, static[0])).reshape(lower.shape)
    o, d, states = _fwd_call(q, k, v, g, beta, t_inv, *static)
    return o, (q, k, v, g, beta, t_inv, d, states)


def _fused_bwd(static, residuals, do):
    return tuple(_bwd_call(*residuals, do, *static))


_fused.defvjp(_fused_fwd, _fused_bwd)


# -- the rule -------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("chunk", "block", "group", "impl",
                                             "interpret"))
def _rule(q, k, v, g, beta, chunk, block, group, impl, interpret):
    """Whole kernel steps of padded tensors -> ``o`` (B, T, H, dv).  A ``jit`` of
    its own for ``model.init``'s sake, which runs a layer operation by
    operation: one program there, not the hundred the inverse is."""
    b, t, h, _ = v.shape
    if impl == "jnp":
        return jnp.moveaxis(_carry_scan(*_prepare(q, k, v, g, beta, chunk), chunk), 1, 2)
    tokens = lambda x: x.reshape(b, t, -1)
    rows = lambda x: jnp.moveaxis(x.astype(jnp.float32), 2, 1).reshape(
        b, h, t // chunk, chunk)
    o = _fused(tokens(q), tokens(k), tokens(v), rows(g), rows(beta),
               (chunk, block, group, h // q.shape[2], interpret))
    return o.reshape(b, t, h, -1)


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64, *, impl: str = "kernel",
                     interpret: Optional[bool] = None):
    """The gated delta rule in chunks of ``chunk`` tokens (the module's text).

    ``q``, ``k`` (B, T, Hk, dk) and ``v`` (B, T, H, dv) in one dtype (``q``
    already scaled, ``q`` and ``k`` already normalised), ``g`` (B, T, H) the log
    of the forget gate (<= 0), ``beta`` (B, T, H).  ``Hk`` divides ``H``: value
    head ``j`` reads key head ``j // (H / Hk)``, as if q and k were repeated to
    the value heads, and ``dq``, ``dk`` come back at the key heads.  Returns
    ``o`` (B, T, H, dv) in ``q``'s dtype; differentiable in all five.
    ``impl``: ``"kernel"`` (the rule's Mosaic kernels, forward and backward) or
    ``"jnp"`` (XLA's chunk-local products and a ``lax.scan``).  What a backward
    pass keeps under ``"kernel"``: the operands, ``T`` (float32), ``D`` and a
    state a chunk, 0.47 GB a layer of 8,192 tokens x 32 heads in bf16
    (``models.transformer.GatedDeltaNet`` keeps them since PR 41: made again,
    the inverse alone was 4 ms a layer); a caller short of memory wraps the
    call, or its block (``remat_policy``), in ``jax.checkpoint``."""
    if impl not in ("kernel", "jnp"):
        raise ValueError(f"impl is 'kernel' or 'jnp', got {impl!r}")
    if (q.ndim != 4 or v.ndim != 4 or q.shape != k.shape or v.shape[:2] != q.shape[:2]
            or v.shape[2] % q.shape[2] or g.shape != v.shape[:3]
            or beta.shape != v.shape[:3]):
        raise ValueError(
            "gated_delta_rule takes q, k (B, T, Hk, dk), v (B, T, H, dv), Hk a "
            f"divisor of H, and g, beta (B, T, H), got {q.shape}, {k.shape}, "
            f"{v.shape}, {g.shape}, {beta.shape}")
    if not (q.dtype == k.dtype == v.dtype):
        raise ValueError(f"q, k, v are {q.dtype}, {k.dtype}, {v.dtype}: one dtype")
    if chunk < 1:
        raise ValueError(f"chunk is a number of tokens >= 1, got {chunk}")
    b, t, hk, dk = q.shape
    h, dv = v.shape[2:]
    n = -(-t // chunk)
    # whole kernel steps: a step's chunks are 8 (the tiling of ``a``'s block) or
    # all there are
    block = _BLOCK_CHUNKS if n >= _BLOCK_CHUNKS else n
    n = -(-n // block) * block
    group = _head_group(h)
    if hk != h and (impl == "jnp" or not _key_heads_a_program(group, hk, h // hk, dk)):
        # no block of whole key heads for a program's value heads: repeat
        q, k = (jnp.repeat(x, h // hk, axis=2) for x in (q, k))
        hk = h
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if impl == "kernel" and not interpret and any(
            group != h and (group * width) % 128 for width in (dk, dv)):
        raise ValueError(
            f"on the chip a program's rows are {group} heads wide, a multiple of "
            f"128 lanes or all {h} heads: got dk {dk}, dv {dv}")
    if _trace.enabled():
        fields = dict(rows=b * t, value_heads=h, key_heads=hk, chunk=chunk, chunks=n,
                      d_k=dk, d_v=dv, impl=impl,
                      programs=b * (h // group) * (n // block), block=block,
                      heads_a_program=group)
        if impl == "kernel":
            # what XLA hands the forward through HBM a layer and pass: q, k (at
            # the heads they come at), v, g, beta and T (float32)
            fields["hbm_operand_bytes"] = b * n * chunk * (
                jnp.dtype(q.dtype).itemsize * (2 * hk * dk + h * dv)
                + h * (4 * 2 + 4 * chunk))
        _trace.event("gdn.chunks", **fields)
    pad = n * chunk - t
    if pad:
        # g = 0, beta = 0, k = 0: the state passes through
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                            for x in (q, k, v, g, beta))
    return _rule(q, k, v, g, beta, chunk, block, group, impl, interpret)[:, :t]
