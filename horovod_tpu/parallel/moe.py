"""Mixture-of-Experts with expert parallelism over an ``ep`` mesh axis.

No reference analog — the reference ships ``alltoall`` largely *for* MoE
users (SURVEY.md §5.7) but no MoE layer; here the layer itself is
first-class.  (Lepikhin et al., "GShard", 2020 — PAPERS.md.)

Design (top-1 switch routing, Fedus et al. 2021, capacity-factor
dropping):

  * each chip holds ``num_experts / ep`` experts' weights;
  * tokens are routed by a learned gate; a chip packs its tokens into
    per-expert capacity buffers (static shapes — XLA-friendly: dropped
    tokens pass through the residual);
  * ONE ``all_to_all`` sends buffers to the experts' owners, the expert
    MLPs run as a batched einsum over the local experts (MXU-dense), and
    a second ``all_to_all`` returns outputs.

Everything is static-shaped: scatter/gather by one-hot matmuls, the
standard TPU MoE formulation.

Two layers live here, and they are different mechanisms:

  * :class:`ExpertParallelMoe` (above): top-1, capacity buffers that DROP
    what overflows, a dense ``(T, E, C)`` one-hot dispatch, gelu experts,
    the ``all_to_all`` exchange over ``ep``.  Small expert counts; not
    reachable from ``models/transformer.py``.
  * :class:`RoutedExperts`: top-k over ALL experts of the model with the
    chosen weights renormalised, SwiGLU experts, of which this chip holds
    a share (a range of expert ids) and computes only that share's part
    of the sum (or, ``gated=False``, squared-ReLU experts of two matrices, and
    ``latent``: in a latent narrower than the stream); DROPLESS (assignments sorted by expert, grouped matrix
    products by the layer's own Mosaic kernels, ``ops/grouped_matmul.py``,
    no capacity), the router in float32.  This is the feed-forward
    ``models/transformer.py`` builds when
    ``TransformerConfig.num_experts`` is set.  It has no exchange
    yet: on one chip it runs on the tokens it is given and what the
    absent experts would add is left out; the ``all_to_all`` that sends
    every chip's assignments to their owners is a later change.

How ``RoutedExperts`` moves rows (PR 31; the chip's timings are in PERF.md
§6).  Between token order and expert order a row moves by GATHERS alone, in
the layer's dtype: into expert order by the sorted row's token
(``_dispatch``), back by each token slot's rank in the sort, ``top_k``
gathers of all the rows, weighted and summed in float32 (``_combine``).
Each is the other's transpose, written so under ``jax.custom_vjp``:
autodiff's own transpose of a gather is a scatter-add, which the chip runs
as a sort, a gather and a serial pass, 1.5 ms for 16,384 rows where the
gathers of four times the rows take 0.9.  Single numbers move by sorts
(the weights ride the sort by expert, their cotangent a sort back; a
slot's rank is the sort of the order) or not at all (the chosen gates and
the counts are one dense comparison): a gather or a scatter of 65,536
scalars takes 0.57 ms, a sort of them 0.05.

The grouped products (PR 33).  A chunk's rows times their experts'
matrices, three products forward and six backward, are
``ops.grouped_matmul.grouped_matmul``: Mosaic kernels whose grid walks only
the row tiles the groups cover, so the rows of a chunk that hold no
assignment cost the products nothing, and a tile that two experts share is
computed once an expert under a row mask.  XLA's own kernels for the ragged
product, which the layer called before, ran at a quarter of the MXU on
groups of 512-768 rows (PERF.md §6).

The chunks' sizes (PR 47).  What is no grouped product (the gathers, the
masks, SwiGLU, their backward) is XLA over a chunk's static rows, assigned or
not: so the first chunk is nine eighths of the assignments balanced routing
gives the held share and a later one a quarter of them (``_Chunks``).
"""

from __future__ import annotations

import functools
import math
from typing import Callable, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp


from .. import trace as _trace
from ..ops.grouped_matmul import grouped_matmul, tiles as _tiles, visit_counts
from ._mesh_utils import axis_size_or_1 as _axis_size


class ExpertParallelMoe(nn.Module):
    """Switch-style top-1 MoE layer, experts sharded over ``axis``.

    Input/output: (B, S, d_model) — the local batch/sequence shard.
    Returns (output, aux_loss); add ``aux_loss`` (load-balancing, Fedus et
    al. eq. 4) to the training loss.
    """

    num_experts: int  # GLOBAL expert count
    d_model: int
    d_ff: int
    axis: Optional[str] = "ep"
    capacity_factor: float = 1.25
    activation: Callable = nn.gelu
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        ep = _axis_size(self.axis)
        if self.num_experts % ep:
            raise ValueError(
                f"experts {self.num_experts} not divisible by ep={ep}"
            )
        local_e = self.num_experts // ep
        b, s, d = x.shape
        tokens = x.reshape(b * s, d)
        n_tok = b * s
        capacity = max(
            1, int(self.capacity_factor * n_tok / self.num_experts)
        )

        # -- gate (computed in f32 for routing stability) ------------------
        gate_w = self.param("gate", nn.initializers.lecun_normal(),
                            (d, self.num_experts), jnp.float32)
        logits = jnp.dot(tokens.astype(jnp.float32), gate_w)
        probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
        expert_idx = jnp.argmax(probs, axis=-1)  # (T,)
        gate_val = jnp.max(probs, axis=-1)  # (T,)

        # load-balancing aux loss: E * sum_e fraction_tokens_e * mean_prob_e
        one_hot = jax.nn.one_hot(expert_idx, self.num_experts)  # (T, E)
        frac = one_hot.mean(axis=0)
        mean_prob = probs.mean(axis=0)
        aux_loss = self.num_experts * jnp.sum(frac * mean_prob)

        # -- capacity assignment: position of each token within its expert
        pos_in_expert = (jnp.cumsum(one_hot, axis=0) - 1.0) * one_hot
        pos = jnp.sum(pos_in_expert, axis=-1)  # (T,)
        keep = pos < capacity
        one_hot = one_hot * keep[:, None]
        gate_val = gate_val * keep

        # dispatch tensor: (T, E, C) one-hot of (expert, slot)
        slot_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity) \
            * keep[:, None]
        dispatch = one_hot[:, :, None] * slot_oh[:, None, :]
        # (E, C, d): per-expert capacity buffers
        buffers = jnp.einsum("tec,td->ecd", dispatch, tokens.astype(
            jnp.float32)).astype(self.dtype)

        # -- all_to_all to expert owners -----------------------------------
        if ep > 1:
            # (E, C, d): dim0 chunk o (this chip's tokens for owner o's
            # experts) goes to chip o; received buffers concatenate along
            # the capacity dim -> (local_E, ep*C, d), columns ordered by
            # source chip
            buffers = jax.lax.all_to_all(
                buffers, self.axis, split_axis=0, concat_axis=1, tiled=True
            )
        else:
            buffers = buffers.reshape(local_e, capacity, d)

        # -- local experts: batched einsum over local_E (MXU) --------------
        wi = self.param("wi", nn.initializers.lecun_normal(),
                        (local_e, d, self.d_ff), jnp.float32)
        wo = self.param("wo", nn.initializers.lecun_normal(),
                        (local_e, self.d_ff, d), jnp.float32)
        h = jnp.einsum("ecd,edf->ecf", buffers, wi.astype(self.dtype))
        h = self.activation(h)
        out = jnp.einsum("ecf,efd->ecd", h, wo.astype(self.dtype))

        # -- return trip ----------------------------------------------------
        if ep > 1:
            # (local_E, ep, C, d): dim1 chunk c (outputs for chip c's
            # tokens) returns to chip c; received chunks stack along dim0
            # in owner order == global expert order -> (E, 1, C, d)
            out = out.reshape(local_e, ep, capacity, d)
            out = jax.lax.all_to_all(
                out, self.axis, split_axis=1, concat_axis=0, tiled=True
            )
            out = out.reshape(self.num_experts, capacity, d)
        else:
            out = out.reshape(self.num_experts, capacity, d)

        # gather back to token order, weighted by the gate value
        combined = jnp.einsum(
            "tec,ecd->td", dispatch.astype(self.dtype), out
        )
        combined = combined * gate_val[:, None].astype(self.dtype)
        return combined.reshape(b, s, d), aux_loss


def _round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


@jax.custom_vjp
def _sorted_with(key, value):
    """The stable sort of the slots by ``key`` with ``value`` carried along:
    ``(order, value[order])``, one sort of three operands.  On the chip a
    sort moves single numbers ten times faster than a gather or a scatter
    does, so the transpose is a sort too: back by ``order``."""
    _, order, value = jax.lax.sort(
        (key, jnp.arange(key.shape[0]), value), num_keys=1, is_stable=True)
    return order, value


def _sorted_with_fwd(key, value):
    order, value = _sorted_with(key, value)
    return (order, value), order


def _sorted_with_bwd(order, cotangents):
    return None, jax.lax.sort((order, cotangents[1]), num_keys=1)[1]


_sorted_with.defvjp(_sorted_with_fwd, _sorted_with_bwd)


def _chosen_and_counts(gates, index, sequences=None):
    """Each row's gates at its ``index`` (T, k) and how many slots chose
    each expert (E,), from one (rows, k, experts) comparison: dense forward
    and backward.  ``take_along_axis``'s transpose and ``bincount`` are
    scatters of single numbers, which the chip does one at a time.  With
    ``sequences`` the counts are by sequence, (sequences, E): the rows are
    that many sequences of equal length, one after the other."""
    onehot = index[..., None] == jnp.arange(gates.shape[-1])
    chosen = jnp.sum(jnp.where(onehot, gates[:, None, :], 0), axis=-1)
    if sequences is None:
        return chosen, jnp.sum(onehot, axis=(0, 1), dtype=jnp.int32)
    by_sequence = onehot.reshape(sequences, -1, *onehot.shape[1:])
    return chosen, jnp.sum(by_sequence, axis=(1, 2), dtype=jnp.int32)


class _Sort(NamedTuple):
    """The layer's index work, once a layer: the (row, slot) assignments
    sorted by held expert, the others last."""

    order: jax.Array     # (slots, padded to whole chunks) the slot of each sorted row
    rank: jax.Array      # (T, k) each slot's place in the sort: the inverse
    chosen: jax.Array    # (T, k) the combine weights, as the tokens hold them
    starts: jax.Array    # (held,) each held expert's run [start, end) of the sort
    ends: jax.Array
    assigned: jax.Array  # () how many assignments are held at all


class _Route(NamedTuple):
    """How one chunk's rows move between token order and expert order."""

    token: jax.Array   # (C,) the row of ``z`` each sorted row reads
    at: jax.Array      # (T, k) the chunk's row each token's slot owns, or 0
    held: jax.Array    # (T, k) whether the slot owns a row of the chunk
    weight: jax.Array  # (T, k) the combine weights, as the tokens hold them


@jax.jit
def _to_tokens(src, route, scale=None):
    """Expert order to token order, float32 ``(rows, D)``: each token's sum
    over its ``k`` slots of the chunk's row that the slot's assignment has,
    times ``scale`` (rows, k).  ``k`` gathers of ``rows`` rows in ``src``'s
    own dtype and no scatter: a slot finds its row by its rank in the sort,
    a slot with no row in the chunk reads row 0 and adds nothing.  Under a
    ``jit`` of its own for ``model.init``'s sake, which runs the layer
    operation by operation: one program there, not one a slice of the loop
    (18 compiles of 118, 2.7 s of ``init_s`` on the chip)."""
    out = jnp.zeros((route.at.shape[0], src.shape[1]), jnp.float32)
    for j in range(route.at.shape[1]):
        rows = src[route.at[:, j]].astype(jnp.float32)
        if scale is not None:
            rows = rows * scale[:, j, None]
        out = out + jnp.where(route.held[:, j, None], rows, 0)
    return out


@jax.custom_vjp
def _dispatch(z, route):
    """Token order to expert order: the row of ``z`` each of the chunk's
    assignments reads.  Its transpose is the combine without weights, so
    autodiff never writes the scatter-add of a gather."""
    return z[route.token]


def _dispatch_fwd(z, route):
    return _dispatch(z, route), route


def _dispatch_bwd(route, dx):
    return _to_tokens(dx, route).astype(dx.dtype), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(y, weight, route):
    """Expert order to token order: each token's sum of its assignments'
    rows of ``y`` times their weights, float32.  ``weight`` (C,) is in
    expert order, as the backward pass wants it; the forward pass reads the
    same numbers where the tokens hold them (``route``'s copy), so neither
    moves a weight.  Its transpose is the dispatch, of the cotangent in
    ``y``'s dtype, times the weight."""
    return _to_tokens(y, route, route.weight)


def _combine_fwd(y, weight, route):
    return _combine(y, weight, route), (y, weight, route)


def _combine_bwd(residuals, g):
    y, weight, route = residuals
    # the cast on the (rows, D) side, once, then one gather; exact where g is
    # the cotangent of a cast to that dtype, which is all the layer makes
    g = g.astype(y.dtype)[route.token].astype(jnp.float32)
    return ((g * weight[:, None]).astype(y.dtype),
            jnp.sum(g * y.astype(jnp.float32), axis=-1), None)


_combine.defvjp(_combine_fwd, _combine_bwd)


def _expert_chunk(z, w_gate, w_up, w_down, weight, valid, sizes, route, group):
    """One chunk of the sorted assignments through the held experts.

    ``weight`` (C,) each assignment's combine weight, ``valid`` (C,) whether
    the slot holds an assignment at all, ``sizes`` (held,) how many of the
    chunk's rows each held expert owns, in order, ``route`` how the chunk's
    rows move between token order and expert order (``_chunk_out``).  Rows
    that hold no assignment are zeroed on the way in, between the products
    and on the way out: the grouped product leaves what lies beyond its
    groups undefined.  ``group``: the rows balanced routing gives an expert,
    for the products' tiles.  Returns the chunk's part of the output, (T, D)
    float32."""
    keep = valid[:, None]
    x = jnp.where(keep, _dispatch(z, route), 0)
    gate = grouped_matmul(x, w_gate, sizes, expected=group)
    up = grouped_matmul(x, w_up, sizes, expected=group)
    h = jnp.where(keep, nn.silu(gate) * up, 0)
    y = jnp.where(keep, grouped_matmul(h, w_down, sizes, expected=group), 0)
    return _combine(y, weight, route)


def _expert_chunk_relu2(z, w_up, w_down, weight, valid, sizes, route, group):
    """``_expert_chunk`` for experts without a gate matrix: ``down_e(relu(up_e
    z)^2)``, two grouped products forward."""
    keep = valid[:, None]
    x = jnp.where(keep, _dispatch(z, route), 0)
    up = grouped_matmul(x, w_up, sizes, expected=group)
    h = jnp.where(keep, jnp.square(nn.relu(up)), 0)
    y = jnp.where(keep, grouped_matmul(h, w_down, sizes, expected=group), 0)
    return _combine(y, weight, route)


def _chunk_sizes(lo, starts, ends, chunk):
    """How many of the sorted rows ``[lo, lo + chunk)`` each held expert
    owns: its run ``[start, end)`` of the sort, clipped to the chunk."""
    return jnp.clip(ends - lo, 0, chunk) - jnp.clip(starts - lo, 0, chunk)


class _Chunks(NamedTuple):
    """The static sizes of a layer's chunks of sorted rows: chunk 0 holds
    ``first`` rows and chunk ``c >= 1`` the ``later`` rows from ``first +
    (c - 1) * later``.  ``group``: the rows balanced routing gives an expert."""

    first: int
    later: int
    group: int

    def start(self, c):
        return self.first + (c - 1) * self.later


def _active_chunks(index, chunks):
    """The chunks the sort reached: the first, which always runs, and the
    later ones that hold an assignment."""
    over = jnp.maximum(index.assigned - chunks.first, 0)
    return 1 + (over + chunks.later - 1) // chunks.later


@functools.partial(jax.jit, static_argnums=(1, 2))
def _counted(index, chunks, n_later):
    """``(chunks in use, assignments they computed)``, from the chunks' own
    group sizes; one program, for ``model.init``'s sake (``_to_tokens``)."""
    active = _active_chunks(index, chunks)
    c = jnp.arange(n_later + 1)[:, None]
    per_chunk = _chunk_sizes(jnp.where(c > 0, chunks.start(c), 0), index.starts,
                             index.ends, jnp.where(c > 0, chunks.later, chunks.first))
    return active, jnp.sum(jnp.where(c < active, per_chunk, 0))


def _chunk_out(lo, z, mats, weight, index, k, chunk, group):
    """The part of the output that the sorted rows ``[lo, lo + chunk)``
    give.  ``weight``: the combine weights in the order of the sort;
    ``index``: the layer's ``_Sort``."""
    sel = jax.lax.dynamic_slice_in_dim(index.order, lo, chunk)
    valid = lo + jnp.arange(chunk) < index.assigned
    at = index.rank - lo
    held = (at >= 0) & (at < chunk) & (index.rank < index.assigned)
    route = _Route(sel // k, jnp.where(held, at, 0), held, index.chosen)
    # three matrices an expert (SwiGLU) or two (squared ReLU, no gate)
    chunk_fn = _expert_chunk if len(mats) == 3 else _expert_chunk_relu2
    return chunk_fn(z, *mats, jax.lax.dynamic_slice_in_dim(weight, lo, chunk),
                         valid, _chunk_sizes(lo, index.starts, index.ends, chunk),
                         route, group)


def _later_chunks(out, z, mats, weight, index, k, chunks):
    """``out`` plus the parts of the chunks after the first that the sort
    reached: a loop whose trip count is read on the device."""
    return jax.lax.fori_loop(
        1, _active_chunks(index, chunks),
        lambda c, out: out + _chunk_out(chunks.start(c), z, mats, weight, index,
                                        k, chunks.later, chunks.group), out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _routed_sum(z, mats, weight, index, k, chunks):
    """All chunks' parts of the output, summed.  The first chunk always
    runs, as ordinary code with an ordinary backward pass.  A later chunk
    runs only if the sort reached it, so a chunk that holds nothing costs
    nothing, forward or backward (a ``lax.cond`` a chunk would still fill
    and add a zero gradient for every expert matrix).  The backward pass
    of a later chunk recomputes it."""
    out = _chunk_out(0, z, mats, weight, index, k, chunks.first, chunks.group)
    return _later_chunks(out, z, mats, weight, index, k, chunks)


def _routed_sum_fwd(z, mats, weight, index, k, chunks):
    out, first_vjp = jax.vjp(
        lambda z, mats, weight: _chunk_out(0, z, mats, weight, index, k,
                                           chunks.first, chunks.group),
        z, mats, weight)
    out = _later_chunks(out, z, mats, weight, index, k, chunks)
    return out, (first_vjp, z, mats, weight, index)


def _routed_sum_bwd(k, chunks, residuals, g):
    first_vjp, z, mats, weight, index = residuals

    def later(c, grads):
        _, vjp = jax.vjp(
            lambda z, mats, weight: _chunk_out(
                chunks.start(c), z, mats, weight, index, k, chunks.later,
                chunks.group), z, mats, weight)
        return jax.tree_util.tree_map(jnp.add, grads, vjp(g))

    grads = jax.lax.fori_loop(1, _active_chunks(index, chunks), later,
                              first_vjp(g))
    return (*grads, None)   # the index: integers, and a copy of the weights


_routed_sum.defvjp(_routed_sum_fwd, _routed_sum_bwd)


class RoutedExperts(nn.Module):
    """Top-k routed experts (SwiGLU, or squared ReLU without a gate matrix) of
    which this chip holds a share.

    ``g = softmax(W_r z)`` over all ``num_experts`` in float32; the
    ``top_k`` largest are chosen and their weights renormalised over the
    chosen (held here or not); the output is
    ``sum over chosen AND held e of w_e * down_e(silu(gate_e z) * up_e
    z)``.  ``held = (first, count)`` names the expert ids this chip holds
    (``None``: all of them); the stacked expert matrices have ``count``
    leading entries.  What the absent experts would add is left out.

    ``gated=False``: an expert is ``down_e(relu(up_e z)^2)``, two stacked
    matrices and no ``w_gate``.  ``latent``: the experts work in a latent
    narrower than the stream the router reads (LatentMoE): ``l = z W_down``
    (``latent_down``, ``d_model -> latent``) before the gather, the experts'
    matrices ``latent x d_ff``, and ``W_up`` (``latent_up``) after the weighted
    sum, so the gathers, the grouped products and the sums move ``latent``
    columns, not ``d_model``; the router still reads ``z``.  Each projection
    traces under a scope of its own name, outside ``experts``.

    The router's other forms (DeepSeek-V3's; all inside the ``router``
    scope, everything after the chosen ids and weights is the same code):
    ``scoring="sigmoid"``: ``g = sigmoid(W_r z)``, the weights ``g_e / (sum
    over the chosen of g + 1e-20)``; ``selection_bias``: the ``top_k`` are
    taken of ``g + b`` and weighed by ``g`` without it.  ``b``
    (``e_score_correction_bias``, one number an expert, zeros at init) is
    no parameter: it lives in the ``batch_stats`` collection, which the
    train steps carry beside the parameters, takes no gradient and is not
    the optimizer's to move (its update rule between steps is not here
    yet); ``scaling_factor``: the renormalised weights times it;
    ``seq_aux``: the auxiliary loss a SEQUENCE (the input's leading axis),
    ``sum_e f_e P_e`` with ``f_e = E / (k S) x n_e`` of that sequence's
    ``S`` rows (no gradient through the counts) and ``P_e`` the sequence's
    mean of ``g_e / sum g``; mean over the sequences.

    Dropless: the (token, expert) assignments are sorted by held expert
    and go through the grouped products' kernels
    (``ops.grouped_matmul``) in chunks of ``chunk_rows`` sorted rows.  A
    chunk's rows come by one gather of the tokens' rows and go back by
    ``top_k`` gathers of the chunk's, one a slot, found by the slot's rank
    in the sort (no scatter, forward or backward: the module's text).  The
    first chunk always runs; a later one runs only if
    the sort reached it (``_routed_sum``: a loop over the chunks in use,
    a later chunk recomputed in the backward pass, so an idle one costs
    neither time nor memory).  By default the first chunk holds nine
    eighths of the assignments balanced routing gives the held share and a
    later one a quarter of them: balanced routing is one chunk with little
    padding, an overflow costs its quarters and a router that sends everything
    to one expert still drops nothing; ``chunk_rows``: every chunk that size.

    Returns ``(y, stats)``: ``aux_loss`` (Switch / Hugging Face form:
    ``E * sum_e (n_e / (k T)) * mean_T g_e``, no gradient through the
    counts; or the sequence-wise form above), and the counters ``assigned`` (assignments to held experts),
    ``load_max_over_mean`` (largest held expert's load over the mean held
    load), ``dropped`` (assignments to held experts no chunk computed: 0 by
    construction, counted from the chunks' own group sizes), ``chunks`` (the
    chunks in use: 1 without overflow) and ``expert_index`` (T, k), the
    chosen ids.

    Traced into a program (never in a step) it leaves one ``moe.rows``
    event: the rows, slots and chunk rows (``first``, ``later``), the held
    assignments balanced routing gives, the row gathers a chunk makes,
    forward and backward, and the grouped products' tile sizes with the
    row-tile visits one product makes at balanced sizes against the row
    tiles of the whole first chunk; ``width``, the columns of a row the
    gathers move (``latent`` where the experts have one, else ``d_model``), and
    ``gated``.
    """

    num_experts: int
    top_k: int
    d_model: int
    d_ff: int
    held: Optional[Tuple[int, int]] = None
    chunk_rows: Optional[int] = None
    dtype: jnp.dtype = jnp.bfloat16
    scoring: str = "softmax"
    scaling_factor: float = 1.0
    selection_bias: bool = False
    seq_aux: bool = False
    latent: Optional[int] = None
    gated: bool = True

    @nn.compact
    def __call__(self, x):
        n_exp, k, d = self.num_experts, self.top_k, self.d_model
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(
                f"scoring is 'softmax' or 'sigmoid', got {self.scoring!r}")
        first, n_held = self.held if self.held is not None else (0, n_exp)
        if not (0 <= first and n_held >= 1 and first + n_held <= n_exp
                and 1 <= k <= n_exp):
            raise ValueError(
                f"held experts ({first}, {n_held}) and top_k {k} do not fit "
                f"{n_exp} experts")
        z = x.reshape(-1, d)
        rows = z.shape[0]
        slots = rows * k

        with jax.named_scope("router"):
            logits = nn.Dense(
                n_exp, use_bias=False, dtype=jnp.float32,
                precision=jax.lax.Precision.HIGHEST, name="router",
            )(z.astype(jnp.float32))
            if self.scoring == "softmax":
                gates = jax.nn.softmax(logits, axis=-1)
                select_by = logits
            else:
                gates = select_by = jax.nn.sigmoid(logits)
            if self.selection_bias:
                bias = self.variable(
                    "batch_stats", "e_score_correction_bias",
                    lambda: jnp.zeros((n_exp,), jnp.float32))
                select_by = gates + jax.lax.stop_gradient(bias.value)
            _, index = jax.lax.top_k(select_by, k)
            chosen, counts = _chosen_and_counts(
                gates, index, x.shape[0] if self.seq_aux else None)
            if self.scoring == "softmax":
                chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
            else:
                chosen = chosen / (
                    jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
            if self.scaling_factor != 1.0:
                chosen = chosen * self.scaling_factor
            if self.seq_aux:
                # a sequence's own counts and its own mean of the
                # normalised scores; the layer's counts are their sum
                share = jax.lax.stop_gradient(
                    counts * (n_exp / (slots // x.shape[0])))
                mean_gate = jnp.mean(
                    (gates / jnp.sum(gates, axis=-1, keepdims=True)).reshape(
                        x.shape[0], -1, n_exp), axis=1)
                aux_loss = jnp.mean(jnp.sum(share * mean_gate, axis=-1))
                counts = jnp.sum(counts, axis=0)
            else:
                share = jax.lax.stop_gradient(counts / slots)
                aux_loss = n_exp * jnp.sum(share * jnp.mean(gates, axis=0))
            # sort the assignments by held expert; the others go last
            local = index.reshape(-1) - first
            key = jnp.where((local >= 0) & (local < n_held), local, n_held)
            order, weight = _sorted_with(key, chosen.reshape(-1))
            # each slot's place in the sort: the inverse permutation, a sort
            rank = jax.lax.sort((order, jnp.arange(slots)), num_keys=1)[1]
            sizes = jax.lax.dynamic_slice_in_dim(counts, first, n_held)
            sizes = sizes.astype(jnp.int32)
            assigned = jnp.sum(sizes)
            ends = jnp.cumsum(sizes)
            starts = ends - sizes

        # the columns of a row the experts read and write
        width = d if self.latent is None else self.latent
        expected = slots * n_held / n_exp
        first, later = (self.chunk_rows,) * 2 if self.chunk_rows is not None else (
            math.ceil(9 * expected / 8), math.ceil(expected / 4))
        first, later = (min(_round_up(max(n, 1), 8), _round_up(slots, 8))
                        for n in (first, later))
        chunks = _Chunks(first, later, slots // n_exp)
        n_later = -(-max(slots - first, 0) // later)
        padding = chunks.start(n_later + 1) - slots
        if _trace.enabled():
            # shape arithmetic: a chunk gathers its own rows twice (x; g in
            # the backward) and every token's slots twice (y; dx); a grouped
            # product visits the row tiles that balanced groups cover, not
            # the chunk's
            tile = _tiles(first, width, self.d_ff, chunks.group, self.dtype)
            visits, chunk_tiles = visit_counts(
                first, n_held, tile.m, int(min(expected, first)) // n_held)
            _trace.event(
                "moe.rows", rows=rows, slots=slots, chunk=first, first=first,
                later=later, expected=expected, dtype=jnp.dtype(self.dtype).name,
                gathered=2 * first + 2 * slots, scoring=self.scoring,
                tiles=list(tile), visits=visits, chunk_tiles=chunk_tiles,
                width=width, gated=self.gated)
        order = jnp.pad(order, (0, padding))
        weight = jnp.pad(weight, (0, padding))
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=(0,))
        names = ("w_gate",) * self.gated + ("w_up", "w_down")
        mats = tuple(self.param(
            name, init, (n_held, self.d_ff, width) if name == "w_down"
            else (n_held, width, self.d_ff)) for name in names)
        if self.latent is not None:
            with jax.named_scope("latent_down"):
                z = nn.Dense(width, use_bias=False, dtype=self.dtype,
                             name="latent_down")(z.astype(self.dtype))

        with jax.named_scope("experts"):
            mats = tuple(w.astype(self.dtype) for w in mats)
            sort = _Sort(order, rank.reshape(rows, k), jax.lax.stop_gradient(chosen),
                         starts, ends, assigned)
            out = _routed_sum(z.astype(self.dtype), mats, weight, sort, k, chunks)
            y = out.astype(self.dtype)
            if self.latent is None:
                y = y.reshape(x.shape)
        if self.latent is not None:
            with jax.named_scope("latent_up"):
                y = nn.Dense(d, use_bias=False, dtype=self.dtype,
                             name="latent_up")(y).reshape(x.shape)
        active, computed = _counted(sort, chunks, n_later)

        mean_load = jnp.maximum(assigned, 1) / n_held
        stats = {
            "aux_loss": aux_loss,
            "assigned": assigned,
            "load_max_over_mean": jnp.max(sizes) / mean_load,
            "dropped": assigned - computed,
            "chunks": active,
            "expert_index": index,
        }
        return y, stats
