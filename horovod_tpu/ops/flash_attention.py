"""Pallas TPU flash-attention kernels (forward + backward) — the
framework's hot op.

No reference analog (the reference is a communication framework), but the
build mandate is TPU-first: the attention inner loop is where transformer
FLOPs live, and this kernel keeps the whole online-softmax accumulation
in VMEM next to the MXU instead of materializing the (S x S) logits in
HBM.  Used by ``models.transformer`` (``attention_impl="flash"``) and as
the local block of ring attention; numerically validated against
``causal_dot_attention`` (tests/test_flash_attention.py,
tests/test_gqa_flash.py).

Kernel shape (the standard TPU flash forward, per pallas_guide.md):
grid = (batch*heads, Sq/(Q*block_q)); each program holds Q query blocks of one
head in VMEM (``_query_tiles_a_program``), K/V for the whole (padded) sequence
stream through VMEM block-by-block inside ``fori_loop``s with running (max,
sum, accumulator) statistics in float32; causal programs stop at the diagonal
block.

Operand dtypes: the kernel's inputs (``q``, ``k``, ``v``, ``dO``) are
widened to float32 in front of every product and the products run on the MXU
with ``preferred_element_type=float32``.  On the v5e such a product is ONE
bfloat16 pass: the MXU rounds its float32 operands to bfloat16 itself, to
nearest even, and sums in float32 (probed bit for bit, PERF.md PR 29), so
the widening costs no pass and an explicit cast to bfloat16 in front of the
product buys nothing (it was slower: the cast is vector work, the MXU's
rounding is not).  float32 inputs (the tests) go the same way.

Several tiles a loop iteration: within one tile the second product waits
for the tile's own softmax and the softmax for the first product, and one
loop iteration is one scheduling region, so a loop of one tile an iteration
leaves the MXU idle for most of it.  Every kernel therefore walks its tiles
eight an iteration, then the rest four, two and one (``_walk``): the tiles
of an iteration are independent but for the running sums, so the scheduler
fills one tile's waits with the next one's products.  Tiles are visited in
the same order: the result is the same bit for bit.

One walk a program: a Mosaic program pays for every loop it holds, run or
not, and for its own start.  A forward or dQ program therefore holds several
consecutive query tiles of one head where their ranges are short
(``_query_tiles_a_program``: four under a window of two tiles, one where a
query tile has eight visits or more) and walks all their visits, range after
range and query tile after query tile, as ONE sequence in one set of loops
(``_run_query_tiles``).  Each query tile has its own running sums, in VMEM
scratch at the tile's number, the forward's row sums ``l`` and ``m`` as wide
as a vector register (a row's number on all 128 lanes: reading and writing
them moves whole registers, where a 1-D carry cost a lane broadcast a visit).
``tile_counts`` gives the tile visits and loop iterations by kernel (136
visits in 34 iterations a head under the causal mask at 4,096 in 256-tiles),
and tracing a kernel records them as a ``flash.tiles`` event
(``horovod_tpu.trace``).  Every mask kind's loop bounds come from
``_tile_ranges``; tiles are masked element by element as before.

Two dK/dV kernels, chosen by bytes and by nothing else (``_backward_folded``):
a program owns one key tile of one kv head and sums over the query heads that
read it.  While the group's q and dO fit VMEM twice buffered
(``_DKV_GROUP_BYTES``) the WHOLE GROUP is one program's operand, at a block
index that does not move along the key tiles, so it is fetched once a kv head
(``_bwd_dkv_kernel``); beyond that, one query head a program with the sums in
VMEM scratch across the grid's last axis (``_bwd_dkv_head_kernel``), which
fetches a head's q and dO for every key tile.  Both take every mask kind
through one tile body (``_dkv_tile``); under the block-diffusion mask the call
is named ``flash_attention_bwd_dkv_bd`` in either form.  Both walk the tiles
of the heads a program holds as ONE sequence (``_run_group_tiles``: head
after head, range after range, so the sums are the same bit for bit): a loop
nest a head was 48 loops a program at a group of 8 under two ranges.

Grouped-query attention (GQA — Ainslie et al., 2023) is KERNEL-NATIVE:
``k``/``v`` may carry ``num_kv_heads < num_heads`` heads and are folded
per *kv* head; the BlockSpec index maps point each query-head program at
``kv_head = q_head // group``, so K/V are fetched from HBM once per kv
head and shared by the whole query-head group — K/V HBM reads and the
dK/dV accumulation shrink by ``num_heads/num_kv_heads`` with no
materialized repeat.

Rows packed of several documents: all three kernels (both dK/dV forms) take
a row's document ids as DATA beside the causal mask and the window
(``flash_attention(..., documents=)``) and keep a query to the keys of its
own document.  The ids are laid out outside the kernels so that a tile's
compare moves no lane (``_document_ids``, ``_same_document``).  The tiles no
document reaches are skipped: once a call, on the device, the ids become each
tile's first and one-past-last tile of the other side that can hold one of its
documents (``_document_bounds``: safe for any ids, exact for a packed row),
small int32 arrays in SMEM beside ``kv_offset``, and every program joins its
own with the static mask's range (``_tile_ranges(..., doc=)``): the same
walk under tighter bounds, the same sums bit for bit (a tile in which
everything is masked added zeros).  A call without ids is the call it was.

All three kernels also take a traced ``kv_offset`` scalar (SMEM): the
global position of the K block's first key minus the global position of
the Q block's first query.  Ring attention passes the per-step shard
offset so causal/sliding-window masks AND the block-skip bounds act on
GLOBAL positions — this is what makes the windowed ring-flash merge
exact (parallel/ring_attention.py).

On non-TPU backends the same kernel runs in interpret mode (slow but
exact), so the CPU test mesh exercises identical code.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as _pltpu

from .. import trace as _trace

# scalar params belong in SMEM on TPU; interpret mode accepts it too
_SCALAR_SPEC = pl.BlockSpec(memory_space=_pltpu.SMEM)

_NEG_INF = -1e30

# the dK/dV kernel, under every mask kind, holds a whole query-head group's q
# and dO in VMEM (of the chip's 128 MiB) while they are at most this, twice
# buffered, and states what it needs (_VMEM_HEADROOM beside them); beyond it,
# one query head a program (_bwd_dkv_head_kernel), which fetches that head's q
# and dO anew for every key tile.  The accepted cells' are 8 MiB (2 x 4,096 x
# 128 + 128) and 10 MiB (8,192 x 192 + 128); 8,192 rows of 128 + 128 are 48 MiB
# at a group of 6 and 64 MiB at a group of 8, where the group form ran the
# causal mask 1.10 x and the 512 window 2.27 x as fast as the head form
# (PERF.md PR 39), and the block-diffusion mask 1.07 x (7.60 -> 7.08 ms a layer
# in the step, PERF.md PR 42: the fetch was the smaller cost there, the loops
# the larger: _run_group_tiles)
_DKV_GROUP_BYTES = 64 * 1024 * 1024
# what a kernel that states its VMEM asks for beside its resident operands
# (a dK/dV iteration of eight 256-tiles is 8-10 MiB of it)
_VMEM_HEADROOM = 16 * 1024 * 1024
# the forward and dQ kernels hold a head's whole keys and values, twice
# buffered; beyond this they state what they need (the compiler's own scoped
# limit is 16 MiB; the accepted cells' are at most 10 MiB: 8,192 x 192 + 128)
_KV_RESIDENT_BYTES = 12 * 1024 * 1024


def _kv_params(s_k, d, dv, dtype, q_side=0):
    """``compiler_params`` for a kernel that holds (s_k, d) keys and (s_k, dv)
    values in VMEM, twice buffered, beside ``q_side`` bytes of query-side
    blocks and scratch (``_query_side_bytes``): none while they fit the
    compiler's own limit, else what it needs, stated."""
    resident = 2 * s_k * (d + dv) * jnp.dtype(dtype).itemsize + q_side
    if resident <= _KV_RESIDENT_BYTES:
        return {}
    return {"compiler_params": _pltpu.CompilerParams(
        vmem_limit_bytes=resident + _VMEM_HEADROOM)}


# tiles a loop iteration, every kernel's: a program's walk runs eight at a
# time, then what is left four, two and one at a time (_walk).  One set of
# loops a program whatever it walks, so starting at 8 costs little to compile
# (Mosaic 2.6 s against 1.4); it was 9 % of the dK/dV kernel's time (PERF.md
# PR 42) and 3-10 % of the forward's and dQ's (PR 43)
_TILES_AN_ITERATION = (8, 4, 2, 1)
# a forward or dQ program walks consecutive query tiles of a head as one until
# it has this many tile visits, at most so many tiles (_query_tiles_a_program):
# a window of two tiles gets four query tiles a program (3.50 against 4.46 ms
# a layer at one, 3.42 at eight), and a mask of nine visits a query tile one:
# beyond a tile a program the tile's number is a traced index and the mask's
# query-side columns are made at every visit, which cost the block-diffusion
# mask 15 % and gave the causal one 4-7 % (PERF.md PR 43)
_VISITS_A_PROGRAM = 8
_QUERY_TILES_MOST = 8


def _tile_mask(q_pos, k_pos, causal, window, seq_len, kv_off=0):
    """(block_q, block_k) bool mask — padding, causality, sliding window.
    ``kv_off`` shifts the K positions into the Q block's frame (global
    K start − global Q start); 0 for self-attention.  Must stay identical
    between the forward kernel and _recompute_p (the backward recomputes
    the same probabilities from the saved lse)."""
    mask = k_pos < seq_len  # padding beyond the true (local) sequence
    rel = q_pos - k_pos - kv_off  # GLOBAL q_pos − k_pos
    if causal:
        mask = jnp.logical_and(mask, rel >= 0)
    if window is not None:
        mask = jnp.logical_and(mask, rel < window)
        if not causal:
            mask = jnp.logical_and(mask, rel > -window)
    return mask


def _kb_range(q_off, block_q, block_k, padded_kb, causal, window, kv_off=0,
              xp=jnp):
    """K-block loop bounds for one Q block: skip blocks entirely outside
    the causal diagonal / sliding window (this skip is where the windowed
    kernel's compute drops from O(S²) to O(S·W)).  ``kv_off`` is the
    global K−Q offset (see _tile_mask); bounds may be traced and may
    satisfy lo >= hi (an empty, fully-masked range — fori_loop runs zero
    iterations and the caller's l==0 guard takes over).  ``xp=numpy``
    computes the same bounds on the host (``tile_counts``)."""
    hi = padded_kb
    if causal:
        # last K block holding any k <= q for the block's last row
        hi = xp.minimum(
            hi, xp.floor_divide(q_off + block_q - 1 - kv_off, block_k) + 1)
    elif window is not None:
        # bidirectional: the forward reach k < q + window also bounds hi
        hi = xp.minimum(
            hi,
            xp.floor_divide(
                q_off + block_q - 1 + window - 1 - kv_off, block_k) + 1)
    if window is None:
        lo = 0
    else:  # first K block any row of this Q block can reach back to
        lo = xp.maximum(
            0, xp.floor_divide(q_off - (window - 1) - kv_off, block_k))
    hi = xp.maximum(hi, 0)
    return lo, hi


def _bd_tile_mask(row_off, col_off, rows, cols, seq_len, bd, cols_are_keys):
    """(rows, cols) bool mask of the block-diffusion kind (Arriola et al.,
    BD3-LM, arXiv:2503.09573, the vectorised training input): the sequence
    is ``[noisy || clean]``, two copies of ``L`` positions each in blocks of
    ``B``; with ``b(i) = i // B`` a query sees, noisy -> noisy its own block,
    noisy -> clean the blocks before its own, clean -> clean the blocks up to
    its own, clean -> noisy nothing.  ``bd = (L, B)``, ``seq_len = 2 L``.
    Block ids are taken on a column and on a row vector and only compared
    at tile size.  ``cols_are_keys=False`` is the transposed tile (keys on
    the rows) of the dK/dV kernel.  Rows and columns past ``seq_len`` (the
    padding) are masked on both sides."""
    half, blk = bd
    r = row_off + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    c = col_off + jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
    q, k = (r, c) if cols_are_keys else (c, r)
    q_clean, k_clean = q >= half, k >= half
    qb = jnp.floor_divide(jnp.where(q_clean, q - half, q), blk)
    kb = jnp.floor_divide(jnp.where(k_clean, k - half, k), blk)
    q_real, k_real = q < seq_len, k < seq_len
    # two integer comparisons at tile size (Mosaic selects no booleans):
    # a clean key up to the query's bound (its own block for a clean query,
    # the one before for a noisy one), or a noisy key of a noisy query's own
    # block; padding and the other kinds get codes that never compare true
    bound = jnp.where(q_real, qb - 1 + q_clean.astype(jnp.int32), -1)
    own = jnp.where(jnp.logical_and(q_real, jnp.logical_not(q_clean)), qb, -2)
    k_as_clean = jnp.where(jnp.logical_and(k_real, k_clean), kb, 2 ** 30)
    k_as_noisy = jnp.where(jnp.logical_or(k_clean, jnp.logical_not(k_real)),
                           -1, kb)
    return jnp.logical_or(k_as_clean <= bound, k_as_noisy == own)


def _bd_ranges(off, rows, other_block, n_other, seq_len, bd, rows_are_queries,
               xp=jnp):
    """Loop bounds, in tiles of ``other_block`` positions, over the other
    side of the block-diffusion mask for the tile of ``rows`` positions at
    ``off``: two half-open tile ranges ``((lo1, hi1), (lo2, hi2))``, the
    first inside the noisy half and the second inside the clean half, the
    second starting no earlier than the first ends (a tile is never visited
    twice).  They cover every allowed pair and little else: of the (2L)^2
    score tiles only about L^2 + L B entries are allowed, and the tiles
    outside these ranges are never computed.  Either range may be empty."""
    half, blk = bd
    first = off
    last = xp.minimum(off + rows, seq_len) - 1   # last real position
    has_noisy = first < half
    has_clean = last >= half
    n0 = first                                    # noisy positions [n0, n1]
    n1 = xp.minimum(last, half - 1)
    c0 = xp.maximum(first, half) - half          # clean positions [c0, c1]
    c1 = last - half
    own_lo = xp.floor_divide(n0, blk) * blk      # own blocks of the noisy rows
    own_hi = xp.minimum(half, xp.floor_divide(n1, blk) * blk + blk)
    if rows_are_queries:
        # noisy keys: the noisy queries' own blocks
        a_lo, a_hi = own_lo, xp.where(has_noisy, own_hi, own_lo)
        # clean keys: before the last noisy query's block, up to and with
        # the last clean query's block
        upto = xp.maximum(
            xp.where(has_noisy, xp.floor_divide(n1, blk) * blk, 0),
            xp.where(has_clean, xp.floor_divide(c1, blk) * blk + blk, 0))
        b_lo, b_hi = half, half + xp.minimum(upto, half)
    else:
        # noisy queries: the noisy keys' own blocks, and for clean keys
        # every block after the first clean key's
        after = xp.minimum(half, xp.floor_divide(c0, blk) * blk + blk)
        a_lo = xp.where(
            has_noisy,
            xp.where(has_clean, xp.minimum(own_lo, after), own_lo), after)
        a_hi = xp.where(has_clean, half, own_hi)
        # clean queries: from the first clean key's block on
        b_lo = half + xp.floor_divide(c0, blk) * blk
        b_hi = xp.where(has_clean, seq_len, b_lo)

    def tiles(lo, hi):
        t_lo = xp.floor_divide(lo, other_block)
        t_hi = xp.minimum(
            xp.floor_divide(hi + other_block - 1, other_block), n_other)
        some = hi > lo      # an empty range is (0, 0)
        return xp.where(some, t_lo, 0), xp.where(some, t_hi, 0)

    lo1, hi1 = tiles(a_lo, a_hi)
    lo2, hi2 = tiles(b_lo, b_hi)
    lo2 = xp.maximum(lo2, hi1)
    return (lo1, hi1), (lo2, xp.maximum(hi2, lo2))


def _tile_ranges(off, rows, other_block, n_other, seq_len, *, causal, window,
                 kv_off, bd, rows_are_queries, doc=None, xp=jnp):
    """One program's loop bounds, whatever the mask kind and the side: the
    tile of ``rows`` positions at ``off`` (queries in the forward and dQ
    kernels, keys in the dK/dV kernels) against the other side's tiles of
    ``other_block`` positions, as half-open tile ranges visited in rising
    order.  ``doc``: in a call that took document ids, the tile's own
    ``(first, one past last)`` tile of the other side that can hold one of its
    documents (``_document_bounds``), joined with the one range a causal or
    window mask has."""
    if bd is not None:
        return _bd_ranges(off, rows, other_block, n_other, seq_len, bd,
                          rows_are_queries, xp)
    if rows_are_queries:
        lo, hi = _kb_range(off, rows, other_block, n_other, causal, window,
                           kv_off, xp)
    else:
        # Which Q blocks can see this K block = _kb_range with the q/k roles
        # transposed (the offset flips sign, the window reach is symmetric).
        # Causality is NOT symmetric: it becomes a LOWER bound here (the
        # first Q block at or after the shifted diagonal), joined by max.
        lo, hi = _kb_range(off, rows, other_block, n_other, False, window,
                           -kv_off, xp)
        if causal:
            lo = xp.maximum(lo, xp.maximum(
                0, xp.floor_divide(off + kv_off, other_block)))
    if doc is not None:
        lo, hi = xp.maximum(lo, doc[0]), xp.minimum(hi, doc[1])
    return ((lo, hi),)


def _walk(total, visit, state):
    """``state = visit(state)`` ``total`` times, ``_TILES_AN_ITERATION`` visits
    a loop iteration (its first entry, then what is left by the next ones):
    one iteration is one region for the scheduler, which overlaps its tiles
    (module docstring), and a program holds one loop an entry whatever it
    walks.  ``total`` may be traced, and 0 runs nothing."""
    left = total
    for m in _TILES_AN_ITERATION:
        steps = left // m

        def several(_, state, m=m):
            for _ in range(m):
                state = visit(state)
            return state

        state = jax.lax.fori_loop(0, steps, several, state)
        left = left - steps * m
    return state


def _query_tiles_ranges(first, tiles, block_q, block_k, n_k, seq_len,
                        docs=None, **mask):
    """``_run_query_tiles``' ranges for the ``tiles`` consecutive query tiles
    of ``block_q`` rows from row ``first`` on: each tile's own
    (``_tile_ranges`` at its offset, and with ``docs`` under its own document
    bounds: tile ``first // block_q + j`` of the program's sequence)."""
    return [_tile_ranges(
        first + j * block_q, block_q, block_k, n_k, seq_len,
        rows_are_queries=True,
        doc=None if docs is None else docs[2](first // block_q + j), **mask)
        for j in range(tiles)]


def _run_query_tiles(ranges, body, carry):
    """``carry = body(j, t, carry)`` for every query tile ``j`` of those a
    program holds (``ranges[j]``: its half-open tile ranges) and every tile
    ``t`` of every range: query tile after query tile and, within one, range
    after range in rising order, as ONE walk of all their visits,
    ``_TILES_AN_ITERATION`` a loop iteration.  The ranges differ from query
    tile to query tile (they follow its offset), so the walk keeps its place
    ``P`` alone and reads the query tile and the tile off it: place ``P`` is
    tile ``P + shift`` of query tile ``j`` while ``P`` is before the range's
    end, range after range, so an empty range (``lo >= hi``, bounds may be
    traced) is passed over and an iteration's tiles may be of two query
    tiles.  The forward and dQ kernels' driver (the chunk and decode kernels',
    the ring's); one query tile a program is a walk of one."""
    shifts, ends, n = [], [], 0
    for tile_ranges in ranges:
        for lo, hi in tile_ranges:
            shifts.append((lo - n, n + jnp.maximum(hi - lo, 0)))
            n = shifts[-1][1]
        ends.append(n)  # where the walk leaves this query tile

    def visit(state):
        p, carry = state
        j, shift = len(ranges) - 1, shifts[-1][0]
        for earlier, end in shifts[-2::-1]:
            shift = jnp.where(p < end, earlier, shift)
        for earlier, end in reversed(list(enumerate(ends[:-1]))):
            j = jnp.where(p < end, earlier, j)
        return p + 1, body(j, p + shift, carry)

    return _walk(n, visit, (jnp.int32(0), carry))[1]


def _run_group_tiles(ranges, group, body, carry):
    """``carry = body(g, t, carry)`` for every query head ``g`` of a group and
    every tile ``t`` of every range: head after head and, within a head, range
    after range in rising order (so the sums come out the same bit for bit
    as a loop nest a head would give them), but as ONE walk of ``group x
    tiles`` visits, ``_TILES_AN_ITERATION`` a loop iteration.  The head
    and the place in its walk are carried beside the sums (the heads' ranges
    are equal), so an iteration's tiles may be of two heads: a head's walk of
    one tile (a noisy key tile under the block-diffusion mask) or of three (a
    window) still fills whole iterations, and a program holds one set of
    loops whatever the group.  The dK/dV kernels' driver."""
    shifts, n = [], 0       # place p of a head's walk is tile p + shift
    for lo, hi in ranges:   # while p < end, range after range
        shifts.append((lo - n, n + jnp.maximum(hi - lo, 0)))
        n = shifts[-1][1]

    def tile_at(p):
        shift = shifts[-1][0]
        for earlier, end in shifts[-2::-1]:
            shift = jnp.where(p < end, earlier, shift)
        return p + shift

    def visit(state):
        g, p, carry = state
        carry = body(g, tile_at(p), carry)
        last = p + 1 == n       # the head's walk is done
        return jnp.where(last, g + 1, g), jnp.where(last, 0, p + 1), carry

    return _walk(group * n, visit, (jnp.int32(0), jnp.int32(0), carry))[2]


def _tile_visits(tiles, block, other_block, n_other, seq_len, **ranges):
    """The visits of each of one head's ``tiles`` tiles of ``block`` positions
    to the other side's tiles, an array of ``tiles`` (of ``(rows, tiles)``
    under ``doc`` bounds of several rows): the kernels' own bounds
    (``_tile_ranges`` on numpy)."""
    return sum(np.maximum(hi - lo, 0) + np.zeros(tiles, np.int64)
               for lo, hi in _tile_ranges(
                   np.arange(tiles) * block, block, other_block, n_other,
                   seq_len, xp=np, **ranges))


def _query_side_bytes(tiles, block_q, d, dv, itemsize):
    """What a forward or dQ program holds in VMEM for ``tiles`` query tiles:
    its query-side blocks twice buffered and its scratch, the larger of the
    two kernels' (the forward: q, o, the ``lse`` column, the scaled q and the
    running sums; dQ: q, dO, dq, the ``lse`` and ``delta`` columns and the
    sum).  A column of float32 is lane-padded: 128 KiB a 256-row tile."""
    column = 128 * 4
    fwd = 2 * ((d + dv) * itemsize + column) + (d + dv) * 4 + 2 * column
    dq = 2 * ((2 * d + dv) * itemsize + 2 * column) + d * 4
    return tiles * block_q * max(fwd, dq)


def _query_tiles_a_program(s_q, s_k, block_q, block_k, seq_len, causal=True,
                           window=None, kv_off=0, bd=None):
    """The consecutive query tiles of a head that one forward or dQ program
    holds and walks as one (``_run_query_tiles``): the smallest power of two
    that gives a program ``_VISITS_A_PROGRAM`` tile visits at the mask's mean
    visits a query tile, at most ``_QUERY_TILES_MOST``, and a divisor of the
    head's query tiles (no operand is padded for it).  The one place that
    decides it, from the call's shapes and mask alone (document ids are data:
    they cut a program's walk and do not move this); a traced ``kv_off`` (a
    ring step) counts as 0."""
    if not isinstance(kv_off, int):
        kv_off = 0
    tiles = s_q // block_q
    visits = _tile_visits(
        tiles, block_q, block_k, s_k // block_k, seq_len, causal=causal,
        window=window, kv_off=kv_off, bd=bd, rows_are_queries=True).mean()
    held = 1
    while (held < _QUERY_TILES_MOST and tiles % (2 * held) == 0
           and held * visits < _VISITS_A_PROGRAM):
        held *= 2
    return held


def tile_counts(s_q, s_k, block_q, block_k, seq_len, causal=True,
                window=None, kv_off=0, bd=None, heads_a_program=1,
                query_tiles_a_program=1, documents=None):
    """Tile visits and the loop iterations they take, by kernel: ``{"fwd":
    (visited, iterations), "bwd_dq": ..., "bwd_dkv": ...}`` for padded
    lengths ``s_q``, ``s_k`` in tiles of ``block_q`` x ``block_k``: one query
    head's in the forward and dQ kernels, whose programs each walk
    ``query_tiles_a_program`` consecutive query tiles as one
    (``_query_tiles_a_program``, ``_run_query_tiles``), and in the dK/dV
    kernel those of the ``heads_a_program`` query heads that one of its
    programs holds (``_dkv_heads_a_program``) and walks as one
    (``_run_group_tiles``).  Host arithmetic on the kernels' own bounds
    (``_tile_ranges`` on numpy) and the loops' steps: where ``visited /
    iterations`` is near 1 (a sequence of two tiles) walking several tiles an
    iteration wins nothing.  ``documents``: concrete ids (numpy) of the
    ``seq_len`` positions of one packed row, or of several rows ``(B,
    seq_len)`` whose counts add up: the visits the kernels make of a call that
    takes these ids (``_document_bounds`` on numpy, joined as the kernels join
    them); without, the static masks' own, which bound them."""
    doc_q = doc_k = None
    if documents is not None:
        doc_q, doc_k = _document_bounds(
            np.atleast_2d(np.asarray(documents)), block_q, block_k, xp=np)
    mask = dict(causal=causal, window=window, kv_off=kv_off, bd=bd)
    n_q, n_k = s_q // block_q, s_k // block_k
    by_query = _tile_visits(n_q, block_q, block_k, n_k, seq_len, doc=doc_q,
                            rows_are_queries=True, **mask)
    by_key = _tile_visits(n_k, block_k, block_q, n_q, seq_len, doc=doc_k,
                          rows_are_queries=False, **mask)

    def count(walks):
        """``walks``: the visits of each program's one walk."""
        visited, iterations = int(walks.sum()), 0
        for n in _TILES_AN_ITERATION:
            iterations += int((walks // n).sum())
            walks = walks % n
        return visited, iterations

    fwd = count(by_query.reshape(-1, query_tiles_a_program).sum(axis=1))
    dkv = count(heads_a_program * by_key.reshape(-1))
    return {"fwd": fwd, "bwd_dq": fwd, "bwd_dkv": dkv}


def _note_tiles(kernels, kv_offset, d_qk, d_v, documents=False, **shape):
    """One ``flash.tiles`` instant a kernel as it is traced: its name, the
    ``visited`` tiles and loop ``iterations`` (``tile_counts``: a query
    head's at the ``query_tiles_a_program`` a forward or dQ program walks as
    one, and for a dK/dV kernel those of the ``heads_a_program`` query heads a
    program holds, the whole group or 1; either is given beside them), the
    ``window`` of its mask (None: none) and the widths of a tile's products
    (``d_qk`` of queries and keys, ``d_v`` of values) and whether the call took
    ``documents``.  A call with ids cuts every program's walk to the tiles its
    documents reach, on the device, from data no trace sees: its event's counts
    are the other masks' own, ``at_most`` says that they only bound the visits
    made, and ``tile_counts(..., documents=ids)`` gives a layout's.
    ``kernels`` maps a kernel's name to its key in ``tile_counts``.  Host
    bookkeeping at trace time; a traced ``kv_offset`` (a ring step) has no
    count to give."""
    if kv_offset is None:
        kv_offset = 0
    if not _trace.enabled() or not isinstance(kv_offset, int):
        return
    held = {k: shape[k] for k in ("heads_a_program", "query_tiles_a_program")
            if k in shape}
    counts = tile_counts(kv_off=kv_offset, **shape)
    for name, key in kernels.items():
        visited, iterations = counts[key]
        _trace.event("flash.tiles", kernel=name, visited=visited,
                     iterations=iterations, d_qk=d_qk, d_v=d_v,
                     window=shape.get("window"), documents=documents,
                     **({"at_most": True} if documents else {}), **held)


_LANES = 128


def _lanes(x, width):
    """``x`` (rows, 128), every lane of a row alike (a running sum a row, kept
    as wide as a vector register so that reading and writing it moves whole
    registers and no lane), as (rows, width)."""
    if width % _LANES == 0:
        return jnp.concatenate([x] * (width // _LANES), axis=-1)
    if width < _LANES:
        return x[:, :width]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], width))


def _document_ids(documents, n_rows, n_lanes):
    """A packed row's document ids (B, S) as the kernels take them, laid out so
    that no visit moves a lane: for the side on a tile's rows ``(B, n_rows,
    128)``, a position's id on all 128 lanes (as the forward's running sums: a
    (rows, 1) column cost a lane broadcast a visit), and for the side on its
    lanes ``(B, 1, n_lanes)``, the ids along the lanes.  Both padded to the
    padded lengths (the padding's ids are masked as its positions are)."""
    with jax.named_scope("attn_docmask"):
        ids = jnp.asarray(documents, jnp.int32)
        pad = lambda n: jnp.pad(ids, ((0, 0), (0, n - ids.shape[1])))
        on_rows = jnp.broadcast_to(pad(n_rows)[:, :, None],
                                   (ids.shape[0], n_rows, _LANES))
        return on_rows, pad(n_lanes)[:, None, :]


def _document_bounds(documents, block_q, block_k, xp=jnp):
    """The tiles a packed row's ids (B, S) let meet, as loop bounds: ``((lo,
    hi) of each query tile over the key tiles, (lo, hi) of each key tile over
    the query tiles)``, int32 ``(B, tiles)`` each, half-open.  A tile's
    documents lie between its smallest and its largest id; two tiles can hold
    a pair of equal ids only where those two intervals overlap, and a tile's
    bound is the first and one past the last tile of the other side whose
    interval overlaps its own.  SAFE for any ids (equal ids in two tiles put
    each tile's interval over the other's, and a range from the first such tile
    to the last leaves none out; a tile it keeps for nothing is masked inside
    as before) and exact for a packed row (ids that rise run by run: the tiles
    between a tile's first document's start and its last document's end).  The
    last tile's padding counts as its last position's document.  At square
    tiles the two sides' bounds are one pair.  ``xp=numpy``: the same on the
    host (``tile_counts``)."""
    rows, s = documents.shape

    def spans(block):
        ids = xp.pad(documents, ((0, 0), (0, (-s) % block)), mode="edge")
        ids = ids.reshape(rows, -1, block)
        return ids.min(axis=-1), ids.max(axis=-1)

    (q_min, q_max), (k_min, k_max) = spans(block_q), spans(block_k)
    meet = xp.logical_and(q_min[:, :, None] <= k_max[:, None, :],
                          k_min[:, None, :] <= q_max[:, :, None])

    def ends(meet):
        n = meet.shape[-1]
        some = meet.any(axis=-1)
        lo = xp.argmax(meet, axis=-1)
        hi = n - xp.argmax(meet[..., ::-1], axis=-1)
        return (xp.where(some, lo, 0).astype(xp.int32),
                xp.where(some, hi, 0).astype(xp.int32))

    by_query = ends(meet)
    if block_q == block_k:  # the intervals meet or do not, whichever side asks
        return by_query, by_query
    return by_query, ends(xp.swapaxes(meet, 1, 2))


def _document_operands(documents, block_q, block_k):
    """What a call's kernels take of a packed row's ids (B, S), made once a
    call under the ``attn_docmask`` scope: ``(ids, bounds by query tile, bounds
    by key tile)`` (``_document_bounds`` at the call's tiles: the forward and
    dQ kernels walk under the first, both dK/dV forms under the second)."""
    with jax.named_scope("attn_docmask"):
        ids = jnp.asarray(documents, jnp.int32)
        return (ids, *_document_bounds(ids, block_q, block_k))


def _document_bytes(rows, lanes):
    """What a program holds in VMEM of ``_document_ids``' two blocks, twice
    buffered: ``rows`` positions on 128 lanes, ``lanes`` on 8 sublanes."""
    return 2 * 4 * (rows * _LANES + 8 * lanes)


def _same_document(docs, row_off, rows, col_off, cols):
    """(rows, cols) bool: whether the tile's row and column positions are of
    one document.  ``docs``: the refs of ``_document_ids``' two operands, the
    first a block of the side on the tile's rows (``row_off`` inside it), the
    second the whole other side."""
    on_rows, on_lanes = docs[:2]
    r = on_rows[0, pl.ds(row_off, rows), :]      # (rows, 128)
    c = on_lanes[0, :, pl.ds(col_off, cols)]     # (1, cols)
    return _lanes(r, cols) == c


def _document_call(kernel, n_in, documents, bounds, folded, block_rows, n_rows,
                   n_lanes, **static):
    """What a packed row's ids add to a kernel's call, as ``(kernel, operands,
    block specs, VMEM bytes)``: four operands after the call's ``n_in``
    inputs, which reach ``kernel`` as ``docs``: ``_document_ids``' two (the
    side on a tile's rows ``block_rows`` a program along the grid's second
    axis, the other side whole; a sequence's for all its heads along the first,
    whose ``folded`` entries are sequences x heads) and, in SMEM beside
    ``kv_offset``, the ``bounds`` ``(lo, hi)`` of that side's tiles
    (``_document_bounds``), as a function of a tile's number that reads the
    program's sequence's pair.  Without ``documents`` the kernel with its
    ``static`` arguments and nothing else: the call it was, refs and all."""
    if documents is None:
        return functools.partial(kernel, **static), (), [], 0
    heads = folded // documents.shape[0]

    def with_documents(*refs):
        on_rows, on_lanes, lo, hi = refs[n_in:n_in + 4]
        row = pl.program_id(0) // heads
        return kernel(
            *refs[:n_in], *refs[n_in + 4:],
            docs=(on_rows, on_lanes, lambda tile: (lo[row, tile], hi[row, tile])),
            **static)

    specs = [pl.BlockSpec((1, block_rows, _LANES),
                          lambda b, i, *_: (b // heads, i, 0)),
             pl.BlockSpec((1, 1, n_lanes), lambda b, i, *_: (b // heads, 0, 0)),
             _SCALAR_SPEC, _SCALAR_SPEC]
    return (with_documents,
            (*_document_ids(documents, n_rows, n_lanes), *bounds), specs,
            _document_bytes(block_rows, n_lanes))


def _fwd_kernel(kvoff_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, q_s, acc_s,
                l_s, m_s, *, sm_scale, causal, block_q, block_k, seq_len,
                window=None, off_div=None, bd=None, docs=None):
    """The forward of the ``acc_s.shape[0]`` consecutive query tiles of one
    head that a program holds (``_query_tiles_a_program``), walked as one
    sequence of tile visits (``_run_query_tiles``).  Each query tile has its
    own running ``(acc, l, m)``, in VMEM scratch at the tile's number (``l``
    and ``m`` a row's number on all 128 lanes: ``_lanes``): a visit reads and
    writes its tile's, and every output is written once, after the walk."""
    tiles = acc_s.shape[0]
    first = pl.program_id(1) * (tiles * block_q)
    # off_div=None: one kv_offset for the whole grid (self/ring blocks).
    # off_div=H: kvoff_ref holds one offset PER BATCH ROW and grid row bh
    # reads entry bh // H — the paged-decode path, where every sequence
    # sits at its own global position (serving/kv_cache.py).
    if off_div is None:
        kv_off = kvoff_ref[0]
    else:
        kv_off = kvoff_ref[pl.program_id(0) // off_div]
    dv = acc_s.shape[-1]  # the accumulator is as wide as the values
    for j in range(tiles):
        q_s[j] = q_ref[0, j * block_q:(j + 1) * block_q, :].astype(
            jnp.float32) * sm_scale  # (block_q, D)
    acc_s[...] = jnp.zeros_like(acc_s)
    l_s[...] = jnp.zeros_like(l_s)
    m_s[...] = jnp.full_like(m_s, _NEG_INF)

    def body(j, kb, carry):
        acc, l, m = acc_s[j], l_s[j], m_s[j]
        q_off = first + j * block_q
        k_off = kb * block_k
        k = k_ref[0, pl.ds(k_off, block_k), :]  # (block_k, D)
        v = v_ref[0, pl.ds(k_off, block_k), :]
        s = jax.lax.dot_general(
            q_s[j], k.astype(jnp.float32),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_k)
        if bd is None:
            q_pos = q_off + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = k_off + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            mask = _tile_mask(q_pos, k_pos, causal, window, seq_len, kv_off)
            if docs is not None:
                mask = jnp.logical_and(mask, _same_document(
                    docs, j * block_q, block_q, k_off, block_k))
        else:
            mask = _bd_tile_mask(q_off, k_off, block_q, block_k, seq_len,
                                 bd, True)
        s = jnp.where(mask, s, _NEG_INF)
        new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # explicit zeroing: a fully-masked row keeps new_m at the -inf
        # sentinel, where exp(s - new_m) would be exp(0) = 1
        p = jnp.where(mask, jnp.exp(s - _lanes(new_m, block_k)), 0.0)
        corr = jnp.exp(m - new_m)
        l_s[j] = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[j] = acc * _lanes(corr, dv) + jax.lax.dot_general(
            p, v.astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_s[j] = new_m
        return carry

    _run_query_tiles(
        _query_tiles_ranges(first, tiles, block_q, block_k,
                            k_ref.shape[1] // block_k, seq_len, docs,
                            causal=causal, window=window, kv_off=kv_off,
                            bd=bd), body, ())
    for j in range(tiles):
        rows = slice(j * block_q, (j + 1) * block_q)
        l, m = l_s[j], m_s[j]
        # rows past the true sequence (or wholly out of window) are
        # all-masked (l == 0): emit zeros
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[0, rows, :] = (acc_s[j] / _lanes(safe_l, dv)).astype(o_ref.dtype)
        # per-row logsumexp of the SCALED logits, for the backward's exact
        # softmax recomputation and the ring merge; all-masked rows get the
        # -inf sentinel so a logaddexp merge leaves them inert (the backward
        # is protected by _recompute_p's explicit mask, not the sentinel)
        lse_ref[0, rows, :] = jnp.where(
            l > 0, m + jnp.log(safe_l), _NEG_INF)[:, :1]


def _fwd_scratch(tiles, block_q, d, dv):
    """The forward kernel's VMEM scratch at ``tiles`` query tiles a program:
    the scaled queries, and each tile's running sums (``l`` and ``m`` on all
    lanes of a row: 128 KiB a 256-row tile each)."""
    return [_pltpu.VMEM((tiles, block_q, d), jnp.float32),
            _pltpu.VMEM((tiles, block_q, dv), jnp.float32),
            _pltpu.VMEM((tiles, block_q, _LANES), jnp.float32),
            _pltpu.VMEM((tiles, block_q, _LANES), jnp.float32)]


def _pad_to(x, multiple, axis):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _fold(x, b, h, d):
    """(B, S, H, D) -> (B*H, S, D): one grid row per (batch, head)."""
    return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)


def _unfold(x, b, h, s, d):
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _clamp_blocks(s, block_q, block_k):
    s128 = s + (-s) % 128  # shortest padded length the tiling allows
    return min(block_q, s128), min(block_k, s128)


def _group_of(q, k):
    """Query-heads-per-kv-head group size; validates the GQA layout
    (query head h reads kv head h // group — the repeat-expansion order)."""
    h, h_kv = q.shape[2], k.shape[2]
    if h_kv <= 0 or h % h_kv:
        raise ValueError(
            f"query heads ({h}) must be a multiple of kv heads ({h_kv})"
        )
    return h // h_kv


def _off_arr(kv_offset):
    """kv_offset scalar (possibly traced, possibly None) -> (1,) int32
    array for the kernels' SMEM input."""
    if kv_offset is None:
        return jnp.zeros((1,), jnp.int32)
    return jnp.asarray(kv_offset, jnp.int32).reshape(1)


def _forward_impl(q, k, v, causal, block_q, block_k, interpret,
                  with_lse=False, window=None, kv_offset=None, bd=None,
                  documents=None):
    b, s, h, d = q.shape
    dv = v.shape[-1]  # the values' own width (latent attention: 192 / 128)
    group = _group_of(q, k)
    h_kv = h // group
    orig_s = s
    block_q, block_k = _clamp_blocks(s, block_q, block_k)
    qp = _pad_to(q, block_q, axis=1)
    kp = _pad_to(k, block_k, axis=1)
    vp = _pad_to(v, block_k, axis=1)
    s_q, s_k = qp.shape[1], kp.shape[1]
    qf = _fold(qp, b, h, d)
    kf = _fold(kp, b, h_kv, d)
    vf = _fold(vp, b, h_kv, dv)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    static = dict(
        sm_scale=1.0 / (d ** 0.5),
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        seq_len=orig_s,
        window=window,
        bd=bd,
    )
    shape = dict(s_q=s_q, s_k=s_k, block_q=block_q, block_k=block_k,
                 seq_len=orig_s, causal=causal, window=window, bd=bd)
    tiles = _query_tiles_a_program(kv_off=kv_offset, **shape)
    _note_tiles({"flash_attention_fwd": "fwd"}, kv_offset, d_qk=d, d_v=dv,
                documents=documents is not None,
                query_tiles_a_program=tiles, **shape)
    rows = tiles * block_q  # a program's: its query tiles, walked as one
    # with ids: the queries' a program's rows, the keys' whole, and each query
    # tile's bounds over the key tiles
    doc_ids, by_query, _ = documents or (None,) * 3
    kernel, ids, id_specs, id_bytes = _document_call(
        _fwd_kernel, 4, doc_ids, by_query, b * h, rows, s_q, s_k, **static)
    out, lse = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=(b * h, s_q // rows),
        in_specs=[
            _SCALAR_SPEC,
            pl.BlockSpec((1, rows, d), lambda bh, qi: (bh, qi, 0)),
            # GQA: the whole query-head group reads ONE kv head's K/V —
            # consecutive programs share the block, so it is fetched from
            # HBM once per kv head, not once per query head
            pl.BlockSpec((1, s_k, d),
                         lambda bh, qi: (bh // group, 0, 0)),
            pl.BlockSpec((1, s_k, dv),
                         lambda bh, qi: (bh // group, 0, 0)),
        ] + id_specs,
        out_specs=[
            pl.BlockSpec((1, rows, dv), lambda bh, qi: (bh, qi, 0)),
            # trailing singleton: TPU block tiling requires the last two
            # block dims divisible by (8, 128) or equal to the array's
            pl.BlockSpec((1, rows, 1), lambda bh, qi: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s_q, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, s_q, 1), jnp.float32),
        ],
        scratch_shapes=_fwd_scratch(tiles, block_q, d, dv),
        interpret=interpret,
        **_kv_params(s_k, d, dv, k.dtype, id_bytes + _query_side_bytes(
            tiles, block_q, d, dv, q.dtype.itemsize)),
    )(_off_arr(kv_offset), qf, kf, vf, *ids)
    out = _unfold(out, b, h, s_q, dv)[:, :orig_s]
    if with_lse:
        return out, lse  # lse stays folded+padded: (B*H, S_q_padded)
    return out


def _recompute_p(q_blk, k_blk, lse_blk, q_off, k_off, *, sm_scale, causal,
                 seq_len, block_q, block_k, window=None, kv_off=0, bd=None,
                 same=None):
    """Exact softmax probabilities of one (block_q, block_k) tile from
    the saved logsumexp (the dQ kernel's; the dK/dV kernels compute the
    same tile transposed).  Masked entries are zeroed EXPLICITLY (not via
    the lse sentinel), so padded rows and wholly-out-of-window rows stay
    inert whatever their lse.  ``same``: the tile's ``_same_document``, where
    the call took documents."""
    s = jax.lax.dot_general(
        q_blk.astype(jnp.float32) * sm_scale, k_blk.astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if bd is not None:
        mask = _bd_tile_mask(q_off, k_off, block_q, block_k, seq_len, bd,
                             True)
        return jnp.where(mask, jnp.exp(s - lse_blk[:, None]), 0.0)
    q_pos = q_off + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = k_off + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    mask = jnp.logical_and(
        _tile_mask(q_pos, k_pos, causal, window, seq_len, kv_off),
        q_pos < seq_len,
    )
    if same is not None:
        mask = jnp.logical_and(mask, same)
    return jnp.where(mask, jnp.exp(s - lse_blk[:, None]), 0.0)


def _bwd_dq_kernel(kvoff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_s, *, sm_scale, causal, block_q,
                   block_k, seq_len, window=None, bd=None, docs=None):
    """dQ of the ``dq_s.shape[0]`` consecutive query tiles of one head that a
    program holds, walked as one sequence of tile visits as the forward's
    (``_run_query_tiles``); each tile's sum in VMEM scratch at its number."""
    tiles = dq_s.shape[0]
    first = pl.program_id(1) * (tiles * block_q)
    kv_off = kvoff_ref[0]
    dq_s[...] = jnp.zeros_like(dq_s)

    def body(j, kb, carry):
        rows = pl.ds(j * block_q, block_q)
        do = do_ref[0, rows, :].astype(jnp.float32)
        k_off = kb * block_k
        k_blk = k_ref[0, pl.ds(k_off, block_k), :]
        v_blk = v_ref[0, pl.ds(k_off, block_k), :]
        p = _recompute_p(
            q_ref[0, rows, :], k_blk, lse_ref[0, rows, 0],
            first + j * block_q, k_off, sm_scale=sm_scale, causal=causal,
            seq_len=seq_len, block_q=block_q, block_k=block_k,
            window=window, kv_off=kv_off, bd=bd,
            same=None if docs is None else _same_document(
                docs, j * block_q, block_q, k_off, block_k),
        )
        dp = jax.lax.dot_general(
            do, v_blk.astype(jnp.float32),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0, rows, 0][:, None])
        dq_s[j] = dq_s[j] + jax.lax.dot_general(
            ds, k_blk.astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return carry

    _run_query_tiles(
        _query_tiles_ranges(first, tiles, block_q, block_k,
                            k_ref.shape[1] // block_k, seq_len, docs,
                            causal=causal, window=window, kv_off=kv_off,
                            bd=bd), body, ())
    for j in range(tiles):
        dq_ref[0, j * block_q:(j + 1) * block_q, :] = (
            dq_s[j] * sm_scale).astype(dq_ref.dtype)


def _dkv_tile(q_ref, do_ref, lse_ref, delta_ref, k_blk, v_blk, k_off, kv_off,
              s_q, *, sm_scale, causal, block_q, block_k, seq_len, window,
              bd=None, docs=None):
    """One tile visit of the dK/dV kernels, as a body for
    ``_run_group_tiles``: query head ``g`` of those the program holds has its
    ``s_q`` rows at ``g * s_q`` of the q-side blocks; ``(dk, dv)`` is the
    carry.  The tile is computed transposed, keys on the rows (``k q^T``):
    ``p^T`` and ``dS^T`` are what the two sums take, and computing ``p``
    first meant transposing two (block_q, block_k) float32 tiles a visit.
    ``bd`` chooses the mask (``_bd_tile_mask`` on the transposed tile; else
    ``_tile_mask``, and with ``docs`` ``_same_document``: the key tile's ids on
    the rows, a sequence's query ids, alike for its heads, on the lanes)."""

    def body(g, qb, carry):
        dk, dv = carry
        base = g * s_q
        q_off = qb * block_q
        q_blk = q_ref[0, pl.ds(base + q_off, block_q), :].astype(
            jnp.float32)
        do_blk = do_ref[0, pl.ds(base + q_off, block_q), :].astype(
            jnp.float32)
        lse_blk = lse_ref[0, :, pl.ds(base + q_off, block_q)]  # (1, block_q)
        delta_blk = delta_ref[0, :, pl.ds(base + q_off, block_q)]
        st = jax.lax.dot_general(               # (block_k, block_q)
            k_blk, q_blk * sm_scale,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if bd is None:
            k_pos = k_off + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            q_pos = q_off + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            mask = jnp.logical_and(
                _tile_mask(q_pos, k_pos, causal, window, seq_len, kv_off),
                q_pos < seq_len,
            )
            if docs is not None:
                mask = jnp.logical_and(mask, _same_document(
                    docs, 0, block_k, q_off, block_q))
        else:
            mask = _bd_tile_mask(k_off, q_off, block_k, block_q, seq_len, bd,
                                 False)
        pt = jnp.where(mask, jnp.exp(st - lse_blk), 0.0)
        dv = dv + jax.lax.dot_general(
            pt, do_blk,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dpt = jax.lax.dot_general(
            v_blk, do_blk,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dst = pt * (dpt - delta_blk)
        dk = dk + jax.lax.dot_general(
            dst, q_blk,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk, dv

    return body


def _bwd_dkv_kernel(kvoff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, *, sm_scale, causal,
                    block_q, block_k, seq_len, window=None, bd=None, group=1,
                    docs=None):
    """dK/dV for ONE kv head's K block: the q-side operands arrive with
    the whole query-head group concatenated on the row axis
    ((1, group*s_q, d) blocks, constant along the key-tile axis and so
    fetched once a kv head), and the group's contributions accumulate
    into the same (block_k, d) dK/dV — this is the GQA dK/dV reduction
    done in VMEM, with K/V loaded once per kv head.  The per-query ``lse``
    and ``delta`` arrive as row vectors ((1, 1, group*s_q) blocks, not
    lane-padded columns).  Every mask kind's (``_dkv_tile``)."""
    ki = pl.program_id(1)
    kv_off = kvoff_ref[0]
    k_off = ki * block_k
    k_blk = k_ref[0].astype(jnp.float32)
    v_blk = v_ref[0].astype(jnp.float32)
    d, dv = k_blk.shape[-1], v_blk.shape[-1]
    s_q = q_ref.shape[1] // group  # per-query-head padded length
    ranges = _tile_ranges(k_off, block_k, block_q, s_q // block_q, seq_len,
                          causal=causal, window=window, kv_off=kv_off,
                          bd=bd, rows_are_queries=False,
                          doc=None if docs is None else docs[2](ki))

    body = _dkv_tile(q_ref, do_ref, lse_ref, delta_ref, k_blk, v_blk, k_off,
                     kv_off, s_q, sm_scale=sm_scale, causal=causal,
                     block_q=block_q, block_k=block_k, seq_len=seq_len,
                     window=window, bd=bd, docs=docs)
    dk, dv = _run_group_tiles(
        ranges, group, body, (jnp.zeros((block_k, d), jnp.float32),
                              jnp.zeros((block_k, dv), jnp.float32)))
    dk_ref[0] = (dk * sm_scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_dkv_head_kernel(kvoff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                         delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                         sm_scale, causal, block_q, block_k, seq_len,
                         window=None, bd=None, group=1, docs=None):
    """``_bwd_dkv_kernel`` with ONE query head of the group a program: the
    grid's last axis walks the group and the (block_k, d) sums live in VMEM
    scratch across it, so a program fetches that head's whole q and dO anew
    for every key tile.  Taken where the whole group's queries and output
    gradients do not fit VMEM (``_DKV_GROUP_BYTES``: 8 query heads of 256 a
    key/value head at 8,192 rows are 128 MiB twice buffered).  The tile's
    body is the grouped kernel's (``_dkv_tile``)."""
    ki, g = pl.program_id(1), pl.program_id(2)
    kv_off = kvoff_ref[0]
    k_off = ki * block_k

    @pl.when(g == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    s_q = q_ref.shape[1]
    body = _dkv_tile(
        q_ref, do_ref, lse_ref, delta_ref, k_ref[0].astype(jnp.float32),
        v_ref[0].astype(jnp.float32), k_off, kv_off, s_q, sm_scale=sm_scale,
        causal=causal, block_q=block_q, block_k=block_k, seq_len=seq_len,
        window=window, bd=bd, docs=docs)
    dk_acc[...], dv_acc[...] = _run_group_tiles(
        _tile_ranges(k_off, block_k, block_q, s_q // block_q, seq_len,
                     causal=causal, window=window, kv_off=kv_off, bd=bd,
                     rows_are_queries=False,
                     doc=None if docs is None else docs[2](ki)),
        1, body, (dk_acc[...], dv_acc[...]))

    @pl.when(g == group - 1)
    def _():
        dk_ref[0] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _dkv_heads_a_program(group, s_q, d, dv, itemsize):
    """The query heads one dK/dV program holds, and a head's bytes of q and
    dO twice buffered: the whole ``group`` while it fits ``_DKV_GROUP_BYTES``,
    else one.  The one place that decides the form, from bytes alone."""
    head_bytes = 2 * s_q * (d + dv) * itemsize
    return (group if group * head_bytes <= _DKV_GROUP_BYTES else 1), head_bytes


def _backward_folded(qf, kf, vf, gf, lse_f, delta_f, *, orig_s, causal,
                     block_q, block_k, interpret, window=None,
                     kv_offset=None, bd=None, documents=None):
    """Backward kernels over already folded+padded operands — the ring
    calls this directly so the fold/pad of the step-invariant q/g/lse/
    delta happens once, not once per ring step.  Shapes: qf/gf
    (B*H, s_q, d), kf/vf (B*H_kv, s_k, d) with H_kv | H (GQA),
    lse_f/delta_f (B*H, s_q, 1).  Returns folded (dq, dk, dv) with
    dk/dv per KV head.  ``vf`` and ``gf`` may be of another width than
    ``qf`` and ``kf`` (latent attention), under the causal mask only; the
    scale is that of the query-key width.  ``documents``: a packed row's ids
    (B, S) of self-attention with their tile bounds by query and by key tile
    (``_document_operands``); the ids are laid out for each kernel's tiles here
    (``_document_ids``)."""
    bh, s_q, d = qf.shape
    dv_w = vf.shape[-1]   # the values' width: that of gf and of dv too
    bh_kv = kf.shape[0]
    if bh_kv <= 0 or bh % bh_kv:
        raise ValueError(f"folded q heads ({bh}) must be a multiple of "
                         f"folded kv heads ({bh_kv})")
    group = bh // bh_kv
    s_k = kf.shape[1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    off = _off_arr(kv_offset)
    kw = dict(sm_scale=1.0 / (d ** 0.5), causal=causal, block_q=block_q,
              block_k=block_k, seq_len=orig_s, window=window)
    mask = dict(s_q=s_q, s_k=s_k, block_q=block_q, block_k=block_k,
                seq_len=orig_s, causal=causal, window=window, bd=bd)
    shape = dict(d_qk=d, d_v=dv_w, documents=documents is not None, **mask)
    tiles = _query_tiles_a_program(kv_off=kv_offset, **mask)
    _note_tiles({"flash_attention_bwd_dq": "bwd_dq"}, kv_offset,
                query_tiles_a_program=tiles, **shape)
    q_rows = tiles * block_q  # a program's: its query tiles, walked as one
    doc_ids, by_query, by_key = documents or (None,) * 3
    dq_kernel, ids, id_specs, id_bytes = _document_call(
        _bwd_dq_kernel, 7, doc_ids, by_query, bh, q_rows, s_q, s_k, bd=bd, **kw)
    dq = pl.pallas_call(
        dq_kernel,
        name="flash_attention_bwd_dq",
        grid=(bh, s_q // q_rows),
        in_specs=[
            _SCALAR_SPEC,
            pl.BlockSpec((1, q_rows, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, s_k, d), lambda bh, qi: (bh // group, 0, 0)),
            pl.BlockSpec((1, s_k, dv_w), lambda bh, qi: (bh // group, 0, 0)),
            pl.BlockSpec((1, q_rows, dv_w), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, q_rows, 1), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, q_rows, 1), lambda bh, qi: (bh, qi, 0)),
        ] + id_specs,
        out_specs=pl.BlockSpec((1, q_rows, d), lambda bh, qi: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s_q, d), qf.dtype),
        scratch_shapes=[_pltpu.VMEM((tiles, block_q, d), jnp.float32)],
        interpret=interpret,
        **_kv_params(s_k, d, dv_w, kf.dtype, id_bytes + _query_side_bytes(
            tiles, block_q, d, dv_w, qf.dtype.itemsize)),
    )(off, qf, kf, vf, gf, lse_f, delta_f, *ids)
    heads, head_bytes = _dkv_heads_a_program(
        group, s_q, d, dv_w, qf.dtype.itemsize)
    if heads == group:
        # the whole group a program: each kv-head program sees its query
        # heads on the row axis — a free reshape of the head-major fold
        # (B, H_kv, G, s_q, d contiguity) — at a block index that does not
        # move along the key tiles
        kernel, scratch = _bwd_dkv_kernel, []
        grid = (bh_kv, s_k // block_k)
        q_at = lambda b, ki: (b, 0, 0)
        kv_at = lambda b, ki: (b, ki, 0)
    else:
        # the group's q and dO do not fit VMEM: one query head a program,
        # the group on the grid's last axis, the sums in scratch across it
        kernel = _bwd_dkv_head_kernel
        scratch = [_pltpu.VMEM((block_k, d), jnp.float32),
                   _pltpu.VMEM((block_k, dv_w), jnp.float32)]
        grid = (bh_kv, s_k // block_k, group)
        q_at = lambda b, ki, g: (b * group + g, 0, 0)
        kv_at = lambda b, ki, g: (b, ki, 0)
    rows = heads * s_q
    _note_tiles({"flash_attention_bwd_dkv" + ("" if bd is None else "_bd"):
                 "bwd_dkv"}, kv_offset, heads_a_program=heads, **shape)
    # with ids: the key tile's on the tile's rows, the sequence's query ids,
    # alike for every head of the group, on its lanes, and each key tile's
    # bounds over the query tiles, alike for every head too
    kernel, ids, id_specs, id_bytes = _document_call(
        kernel, 7, doc_ids, by_key, bh_kv, block_k, s_k, s_q, group=group,
        bd=bd, **kw)
    call = dict(
        grid=grid,
        in_specs=[
            _SCALAR_SPEC,
            pl.BlockSpec((1, rows, d), q_at),
            pl.BlockSpec((1, block_k, d), kv_at),
            pl.BlockSpec((1, block_k, dv_w), kv_at),
            pl.BlockSpec((1, rows, dv_w), q_at),
            pl.BlockSpec((1, 1, rows), q_at),
            pl.BlockSpec((1, 1, rows), q_at),
        ] + id_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), kv_at),
            pl.BlockSpec((1, block_k, dv_w), kv_at),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh_kv, s_k, d), kf.dtype),
            jax.ShapeDtypeStruct((bh_kv, s_k, dv_w), vf.dtype),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        # stated always: beside its resident q and dO a program holds an
        # iteration's eight tiles in float32, 8-10 MiB at 256-tiles, which
        # at 10 MiB resident (192 / 128 at 8,192 rows) is past the
        # compiler's own 16 MiB
        compiler_params=_pltpu.CompilerParams(
            vmem_limit_bytes=heads * head_bytes + _VMEM_HEADROOM + id_bytes),
    )
    # one name a mask kind, whichever form (the traces read it)
    if bd is None:
        dkv = pl.pallas_call(kernel, name="flash_attention_bwd_dkv", **call)
    else:
        dkv = pl.pallas_call(kernel, name="flash_attention_bwd_dkv_bd", **call)
    dk, dv = dkv(off, qf.reshape(bh // heads, rows, d), kf, vf,
                 gf.reshape(bh // heads, rows, dv_w),
                 lse_f.reshape(bh // heads, 1, rows),
                 delta_f.reshape(bh // heads, 1, rows), *ids)
    return dq, dk, dv


def _fold_bwd_invariants(q, out, lse, g, block_q):
    """Fold+pad the step-invariant backward operands (q, g, lse, and
    delta = rowsum(dO·O)) once; shared by self-attention backward and the
    ring (which reuses them across every ring step)."""
    b, s, h, d = q.shape
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # (B, S, H)
    delta = delta.transpose(0, 2, 1).reshape(b * h, s, 1)
    qf = _fold(_pad_to(q, block_q, axis=1), b, h, d)
    gf = _fold(_pad_to(g, block_q, axis=1), b, h, g.shape[-1])
    delta_f = _pad_to(delta, block_q, axis=1)
    lse_f = _pad_to(lse, block_q, axis=1)
    return qf, gf, lse_f, delta_f


def _backward_impl(q, k, v, out, lse, g, causal, block_q, block_k,
                   interpret, window=None, bd=None, documents=None):
    b, s, h, d = q.shape
    h_kv, dv_w = k.shape[2], v.shape[-1]
    orig_s = s
    block_q, block_k = _clamp_blocks(s, block_q, block_k)
    # lse arrives from the forward already folded and padded to the same
    # s_q (identical block clamp on identical shapes) — _fold_bwd_
    # invariants' pad is then a no-op on it
    qf, gf, lse_f, delta_f = _fold_bwd_invariants(q, out, lse, g, block_q)
    kf = _fold(_pad_to(k, block_k, axis=1), b, h_kv, d)
    vf = _fold(_pad_to(v, block_k, axis=1), b, h_kv, dv_w)
    s_q, s_k = qf.shape[1], kf.shape[1]
    dq, dk, dv = _backward_folded(
        qf, kf, vf, gf, lse_f, delta_f, orig_s=orig_s, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
        window=window, bd=bd, documents=documents,
    )
    dq = _unfold(dq, b, h, s_q, d)[:, :orig_s]
    dk = _unfold(dk, b, h_kv, s_k, d)[:, :orig_s]
    dv = _unfold(dv, b, h_kv, s_k, dv_w)[:, :orig_s]
    return dq, dk, dv


# -- block-level entry points (ring attention building blocks) --------------
#
# Ring attention combines per-KV-block partial attentions across mesh
# steps, so it needs (a) the normalized block output TOGETHER with its
# logsumexp (to rescale when merging blocks) and (b) block backward passes
# driven by the GLOBAL lse/out (FlashAttention-2 decomposes exactly this
# way: each (Q block, KV block) pair's dq/dk/dv depends only on the final
# per-row logsumexp and delta).


def flash_block_forward(q, k, v, causal, block_q=256, block_k=256,
                        interpret=None, window=None, kv_offset=None):
    """Returns (out, lse) with out (B,S,H,D) normalized within this KV
    block and lse (B,S,H) float32 = log-sum-exp of this block's logits
    (the -inf sentinel for rows this block cannot reach, so a logaddexp
    merge leaves them untouched).  ``kv_offset`` is the global position
    of k[0] minus the global position of q[0] — the ring passes the
    per-step shard offset so ``window`` masks global positions."""
    b, s, h, d = q.shape
    out, lse_f = _forward_impl(
        q, k, v, causal, block_q, block_k, interpret, with_lse=True,
        window=window, kv_offset=kv_offset,
    )
    lse = lse_f[:, :, 0].reshape(b, h, -1)[:, :, :s].transpose(0, 2, 1)
    return out, lse


# -- per-row-offset serving entries (the paged-KV-cache path) ----------------
#
# Autoregressive decode is one query row attending a long cached K/V
# stream — exactly the forward kernel at block_q rows with a PER-SEQUENCE
# kv_offset: each sequence sits at its own global position, so the SMEM
# offset input carries one entry per batch row and grid row bh reads
# entry bh // H.  The causal term of _tile_mask then masks everything at
# or beyond the sequence's length (stale pool garbage, trash-block
# gathers, unwritten tail positions) and _kb_range skips the K blocks
# the sequence doesn't own — the block-granular read reduction the paged
# cache (serving/kv_cache.py) is built on.  GQA grouping and sliding-
# window truncation compose exactly as in the training kernels.
#
# A chunked-prefill row is the SAME program shape with q_len > 1: row
# i's queries sit at global positions q_starts[i] .. q_starts[i]+C-1,
# so a prefill chunk at offset k is just another batch row of the mixed
# step (Sarathi-Serve's insight, docs/SERVING.md) — decode rows are
# chunks of length 1 and flash_decode_attention delegates here.
#
# TENSOR SHARDING (docs/SERVING.md sharding section): the per-kv-head
# folding makes the head dimension a free partition axis — under a
# shard_map'ped serving step each chip calls these same entries with
# its LOCAL slice (H/N query heads, H_kv/N kv heads, the pool gather's
# matching head slice).  The grid simply shrinks to b*(H/N) rows, the
# GQA group ratio H/H_kv is shard-invariant, and per-chip K/V HBM
# reads drop by the shard factor (kv_cache.modeled_decode_read_bytes
# shards= models it; comm_model.serve_gather_read_bytes measures it on
# the lowered program).  Nothing head-global exists in the kernels, so
# no kernel change is needed to shard — that is the seam's point.


def flash_chunk_attention(q, k, v, q_starts, *, window=None, kv_start=None,
                          block_q=32, block_k=128, interpret=None):
    """Per-row-offset attention over gathered KV-cache pages: the mixed
    chunked-prefill + decode step's kernel.

    q: (B, C, H, D) — row i's C queries sit at global positions
    ``q_starts[i] + 0 .. q_starts[i] + C - 1`` (C is the padded chunk
    tier; columns beyond a row's true chunk are pad whose outputs the
    engine discards).
    k, v: (B, S_kv, H_kv, D) with ``H_kv | H`` (GQA) — each sequence's
    cache pages gathered contiguous (serving's block-table gather),
    INCLUDING this chunk's own just-written K/V; rows beyond a
    sequence's written length may hold arbitrary garbage, the causal
    mask never attends them from a real query row.
    q_starts: (B,) int32 — each row's first query's global position
    (= tokens already in the cache before this chunk).
    kv_start: optional (B,) int32 global position of ``k[:, 0]`` (0 when
    the gather starts at the sequence head; the windowed gather passes
    the trailing-page start so masks stay global).
    window: sliding window, composing exactly as in decode — per-step
    reads stay O(window + C), not O(context).

    Output: (B, C, H, D) in q's dtype.  Causality INSIDE the chunk is
    the same global causal term (query j attends keys ≤ its own global
    position), so no separate intra-chunk mask exists to drift.
    """
    b, c, h, d = q.shape
    if k.shape != v.shape:
        raise ValueError(
            f"k/v shapes differ: {k.shape} vs {v.shape} (the serving kernels "
            "take keys and values of one width: no latent cache yet)")
    group = _group_of(q, k)
    h_kv = h // group
    s_k = k.shape[1]
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    q_starts = jnp.asarray(q_starts, jnp.int32).reshape(b)
    if kv_start is None:
        starts = jnp.zeros((b,), jnp.int32)
    else:
        starts = jnp.asarray(kv_start, jnp.int32).reshape(b)
    # global K start − global Q start, per sequence: the causal term
    # rel >= 0 then reads k_global <= q_global — the per-row length
    # mask (a real query's global position is < its row's written end).
    offs = starts - q_starts
    block_k = min(block_k, s_k + (-s_k) % 128)
    kp = _pad_to(k, block_k, axis=1)
    vp = _pad_to(v, block_k, axis=1)
    s_k_pad = kp.shape[1]
    block_q = min(block_q, c + (-c) % 8)  # tiny chunks: one 8-row tile
    qp = _pad_to(q, block_q, axis=1)
    s_q_pad = qp.shape[1]
    qf = _fold(qp, b, h, d)
    kf = _fold(kp, b, h_kv, d)
    vf = _fold(vp, b, h_kv, d)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=1.0 / (d ** 0.5),
        causal=True,  # the per-row global length mask IS the causal term
        block_q=block_q,
        block_k=block_k,
        seq_len=s_k,
        window=window,
        off_div=h,
    )
    out, _ = pl.pallas_call(
        kernel,
        name="flash_attention_chunk",
        grid=(b * h, s_q_pad // block_q),
        in_specs=[
            _SCALAR_SPEC,
            pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, s_k_pad, d),
                         lambda bh, qi: (bh // group, 0, 0)),
            pl.BlockSpec((1, s_k_pad, d),
                         lambda bh, qi: (bh // group, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi: (bh, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s_q_pad, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, s_q_pad, 1), jnp.float32),
        ],
        # each row at its own offset: a walk of one query tile a program
        scratch_shapes=_fwd_scratch(1, block_q, d, d),
        interpret=interpret,
    )(offs, qf, kf, vf)
    return _unfold(out, b, h, s_q_pad, d)[:, :c]


def flash_decode_attention(q, k, v, kv_lens, *, window=None, kv_start=None,
                           block_q=8, block_k=128, interpret=None):
    """Single-token decode attention over gathered KV-cache pages — the
    q_len=1 case of :func:`flash_chunk_attention` (one query row per
    sequence, sitting at global position ``kv_lens - 1``).

    kv_lens: (B,) int32 — keys the query may attend, PER SEQUENCE: the
    query sits at global position ``kv_lens - 1`` and attends keys
    ``0..kv_lens-1`` (itself included, i.e. its own K/V must already be
    present in ``k``/``v``).

    Output: (B, 1, H, D) in q's dtype.  Rows with ``kv_lens <= 0`` (pad
    slots of a partially filled decode batch) come back all-zero.
    """
    b, s_q = q.shape[0], q.shape[1]
    if s_q != 1:
        raise ValueError(f"decode expects q_len=1, got {s_q}")
    kv_lens = jnp.asarray(kv_lens, jnp.int32).reshape(b)
    return flash_chunk_attention(
        q, k, v, kv_lens - 1, window=window, kv_start=kv_start,
        block_q=block_q, block_k=block_k, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, documents, causal, block_q, block_k, interpret, window,
           bd=None):
    return _forward_impl(q, k, v, causal, block_q, block_k, interpret,
                         window=window, bd=bd, documents=documents)


def _flash_fwd(q, k, v, documents, causal, block_q, block_k, interpret,
               window, bd):
    out, lse = _forward_impl(
        q, k, v, causal, block_q, block_k, interpret, with_lse=True,
        window=window, bd=bd, documents=documents,
    )
    return out, (q, k, v, documents, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, window, bd, residuals,
               g):
    # FlashAttention-2-style backward: two pallas kernels (dq; dk+dv)
    # recompute the probability tiles from the forward's saved logsumexp
    # — no (S x S) materialization, so training keeps the memory win too.
    # causal_dot_attention is the numerics oracle in the tests.
    q, k, v, documents, out, lse = residuals
    # the ids are data and have no gradient (None: none, with ids or without)
    return (*_backward_impl(
        q, k, v, out, lse, g, causal, block_q, block_k, interpret,
        window=window, bd=bd, documents=documents,
    ), None)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret", "window",
                     "block_diffusion"),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    block_q: int = 256,
    block_k: int = 256,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
    block_diffusion: Optional[tuple] = None,
    documents: Optional[jax.Array] = None,
) -> jax.Array:
    """Flash attention over (B, S, H, D) tensors (same layout and
    numerics contract as ``models.transformer.causal_dot_attention``:
    softmax statistics in float32, output in the input dtype).

    GQA: ``k``/``v`` may carry ``H_kv`` heads with ``H_kv | H`` (query
    head ``h`` reads kv head ``h // (H/H_kv)``, the Llama-3 layout) —
    the kernels share each K/V head across its query-head group, so K/V
    HBM reads and the dK/dV accumulation shrink by ``H/H_kv``; never
    materialize a repeat.  Gradients for k/v come back in their
    own (B, S, H_kv, D) shape.

    Sequences that don't divide the block sizes are zero-padded and the
    pad keys masked out, so any S works.  Default 256-blocks are the
    robust v5e choice across chip-load conditions (tools/flash_bench.py;
    512 sometimes wins, sometimes regresses 2x under pool contention);
    blocks clamp down for short sequences.  Fully differentiable with an
    O(S)-memory FlashAttention-2-style pallas backward (see _flash_bwd;
    fwd+bwd 1.84x over dense at S=4096 on v5e).

    ``window``: Mistral-style sliding window — each token attends the
    last ``window`` positions, itself included (symmetric reach when
    bidirectional).  Blocks wholly outside the window are SKIPPED, so
    compute drops from O(S²) to O(S·window) — unlike the mask-level
    window on the dot path, which still does the full-matrix work.

    ``block_diffusion=(L, B)``: the block-diffusion mask kind (see
    ``_bd_tile_mask``) over a sequence of ``S = 2 L`` rows, ``[noisy ||
    clean]`` in blocks of ``B``; takes the place of ``causal`` and
    ``window``.  The three kernels visit only the tiles the mask reaches
    (``_bd_ranges``): about ``L^2 + L B`` of the ``4 L^2`` entries.

    ``documents``: a packed row's document ids, (B, S) integers, DATA of the
    call (one compiled program serves every layout of the same shapes): a
    query sees a key only of its own document, under ``causal`` and
    ``window`` as they are.  Any ids do: two positions are of one document
    where their ids are equal.  All three kernels take them
    (``_document_ids``, ``_same_document``), and skip the tiles that no
    document of theirs reaches: every program walks the static masks' range
    cut to its tile's document bounds (``_document_bounds``, made once a
    call on the device: never a tile dropped in which equal ids meet,
    whatever the ids, and none kept for nothing at a range's ends where the
    ids rise run by run), so the time follows the layout and the result does
    not.  Keys and values of one width only, and no ``block_diffusion``; the
    ring and the serving kernels take none.
    """
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if k.shape[:-1] != v.shape[:-1] or q.shape[-1] != k.shape[-1]:
        raise ValueError(
            f"q/k/v shapes do not fit: {q.shape}, {k.shape}, {v.shape} "
            "(k and v differ in their last axis at most, q and k not there)")
    if k.shape[-1] != v.shape[-1] and (
            window is not None or block_diffusion is not None):
        raise ValueError(
            f"keys {k.shape[-1]} wide and values {v.shape[-1]} wide (latent "
            "attention) take no window and no block_diffusion: those "
            "kernels are not widened")
    _group_of(q, k)  # validate the GQA head split early
    if documents is not None:
        if k.shape[-1] != v.shape[-1]:
            raise ValueError(
                f"keys {k.shape[-1]} wide and values {v.shape[-1]} wide (latent "
                "attention) take no documents: those kernels are not widened")
        if block_diffusion is not None:
            raise ValueError(
                "block_diffusion takes no documents: its mask is of one "
                "document's [noisy || clean] rows")
        if (documents.shape != q.shape[:2] or k.shape[1] != q.shape[1]
                or not jnp.issubdtype(documents.dtype, jnp.integer)):
            raise ValueError(
                f"documents are a row's integer ids, one a position of q and "
                f"k alike {q.shape[:2]}, got {documents.dtype}"
                f"{documents.shape} and {k.shape[1]} keys")
    if block_diffusion is None:
        if documents is not None:
            documents = _document_operands(
                documents, *_clamp_blocks(q.shape[1], block_q, block_k))
        return _flash(q, k, v, documents, causal, block_q, block_k, interpret,
                      window)
    half, blk = (int(x) for x in block_diffusion)
    if window is not None:
        raise ValueError("block_diffusion takes no window")
    if half < 1 or blk < 1 or q.shape[1] != 2 * half or k.shape[1] != 2 * half:
        raise ValueError(
            f"block_diffusion=(L, B) needs L, B >= 1 and 2 L = {2 * half} "
            f"rows of queries and keys, got {q.shape[1]} and {k.shape[1]}")
    return _flash(q, k, v, None, False, block_q, block_k, interpret, None,
                  (half, blk))
