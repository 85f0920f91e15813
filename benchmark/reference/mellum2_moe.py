"""Plain reference: a causal decoder of sliding-window and full attention layers
whose every feed-forward is a softmax router over SwiGLU experts, trained on
rows packed of several documents; float32.

Mellum2-12B-A2.5B (``model_type`` ``mellum``; JetBrains/Mellum2-12B-A2.5B-Instruct
``config.json``).  The equations, from the config's keys; ``x`` is the residual
stream, ``z = RMSNorm(x)`` (``x / rms(x) * w``), layer ``l``:

Packed rows.  The input is ``(rows, 2, S)`` integers: the token ids and each
position's document id.  A document is a run of equal ids.  Position ``i`` of
the row has the rotary position ``p_i = i - (the index where its document
begins)``: positions restart at each document.  Query ``i`` sees key ``j`` iff
they are of the SAME DOCUMENT and ``j <= i`` and, on a ``sliding_attention``
layer, ``i - j < sliding_window`` (itself included), written on the row's own
indices ``i``, ``j`` and the ids.

Attention.  ``num_attention_heads`` query heads over ``num_key_value_heads``
key/value heads of ``head_dim``, no bias, alike in both kinds of layer: ``q = z
W_q``, ``k = z W_k``, ``v = z W_v``; query head ``h`` reads key/value head ``h //
(H / H_kv)``.  RoPE on the whole of q and k by
``rope_parameters[layer_types[l]]``, split-half pairs (column ``i`` with column
``i + head_dim / 2``): ``rope_type`` ``default``: ``inv_freq_i = theta^(-2i /
head_dim)``; ``yarn`` (arXiv:2309.00071, Hugging Face's
``_compute_yarn_parameters``): ``extra_i = theta^(-2i / d)``, ``inter_i = extra_i
/ factor``, ``low = floor(d ln(original_max_position_embeddings / (beta_fast 2
pi)) / (2 ln theta))``, ``high = ceil(the same with beta_slow)``, ``ramp_i =
clip((i - low) / (high - low), 0, 1)``, ``inv_freq_i = inter_i ramp_i + extra_i (1
- ramp_i)``, and cos and sin times ``attention_factor``.  Scores ``q k^T /
sqrt(head_dim)``, softmax over the keys seen, times v, then ``W_o``.

Feed-forward, every layer (``mlp_layer_types`` all ``sparse``).  ``s =
softmax(W_r z)`` over all the router's experts in float32; chosen = the
``num_experts_per_tok`` largest; ``w_e = s_e / (sum over the chosen of s)``
(``norm_topk_prob``); ``y = sum over chosen AND held e of w_e W_d,e (silu(W_g,e
z) * W_u,e z)``.  No shared expert, no dense layer.  What the absent experts
would add is left out.  Auxiliary loss a layer (Switch form): ``E sum_e (n_e /
(k T)) mean_T s_e`` over the T rows of one chip's batch, no gradient through the
counts ``n_e``; mean over the layers.

Head and loss.  ``logits = W_head n_f(x)``; loss = ``sum_i w_i ce_i / sum_i w_i``
over the row's positions, ``ce_i`` the cross-entropy against the next token,
``w_i`` the labels' weights (0 at a document's last position, whose next token
is another document's), plus ``router_aux_loss_coef`` x the auxiliary loss.

Departures from the published model, each in the configuration's file: the cut
(``reduced``: 4 of 28 layers, 16 of 64 experts held, a quarter of the
vocabulary), what the config leaves open (``assumed``: softmax over all experts
renormalised over the chosen, no q / k norms, split-half pairs, the auxiliary
loss, documents that see themselves alone with positions that restart), and the
multi-token-prediction head the model is described with, which has no key.

Laid out to fit: attention one sequence and one query head at a time, a head's
8,192 x 8,192 scores in blocks of ``SCORE_ROWS`` query rows; the experts one at
a time (a masked dense product over the held experts: no sort, no kernel); the
loss one block of ``LOSS_ROWS`` positions at a time.  Between stages goes a
``Carry``: the activations, the auxiliary loss summed so far and the rows'
document ids (as float32, exact: the chain differentiates what it carries, and
an integer has no gradient to hand back).  The parameter tree is addressed by
the names of the program's (``embed``, ``layer_<i>/{ln1, attn/{q, k, v, o}, ln2,
moe/{router, w_gate, w_up, w_down}}``, ``ln_f``, ``head``).

Nothing of the program is imported: the mask, the positions and YaRN's
frequencies are this file's own, and the reference routes by its own float32
router, and prints what it chose at its first step.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import chain
from .chain import Ops, Stage

LOSS_ROWS = 1024
SCORE_ROWS = 1024

# the reference's own chosen experts at its first step, by layer: (chips, T, k)
REFERENCE_ROUTING = {}

_ACTIVATIONS = {"silu": jax.nn.silu}


@jax.tree_util.register_pytree_node_class
class Carry:
    """What goes from stage to stage: the activations (R, S, D), the auxiliary
    loss summed over the layers so far, and the rows' document ids (R, S)."""

    def __init__(self, h, aux, documents):
        self.h, self.aux, self.documents = h, aux, documents

    dtype = property(lambda self: self.h.dtype)

    def tree_flatten(self):
        return (self.h, self.aux, self.documents), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def restarted_positions(documents):
    """(S,) ids -> (S,) positions: index i minus the index where i's run of
    equal ids began."""
    index = jnp.arange(documents.shape[0])
    begins = jnp.concatenate([jnp.ones((1,), bool), documents[1:] != documents[:-1]])
    return index - jax.lax.cummax(jnp.where(begins, index, 0))


def inverse_frequencies(width: int, rope: tuple) -> np.ndarray:
    """The ``width // 2`` frequencies of a layer type's ``rope_parameters`` (the
    tuple ``rope_static`` makes of them); float32."""
    theta, yarn = rope
    extra = np.float32(theta) ** (-np.arange(0, width, 2, dtype=np.float32) / np.float32(width))
    if yarn is None:
        return extra
    factor, original, beta_fast, beta_slow, _ = yarn
    inter = extra / np.float32(factor)

    def pair_of(turns):
        return width * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), width - 1)
    span = (high - low) or 0.001                        # Hugging Face's guard
    ramp = np.clip((np.arange(width // 2, dtype=np.float32) - low) / np.float32(span), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rotate(x, positions, rope: tuple):
    """x: (S, H, D); the row's position ``positions[s]`` turns pair (x[i], x[i +
    D / 2]) by ``positions[s] * inv_freq_i``; cos and sin times YaRN's factor."""
    width = x.shape[-1]
    angles = (positions.astype(jnp.float32)[:, None]
              * jnp.asarray(inverse_frequencies(width, rope)))
    scale = 1.0 if rope[1] is None else rope[1][4]
    cos, sin = jnp.cos(angles)[:, None, :] * scale, jnp.sin(angles)[:, None, :] * scale
    x1, x2 = x[..., : width // 2], x[..., width // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def allowed(q_index, k_index, q_documents, k_documents, window):
    """(Q, K) bool on the row's indices and ids: key j for query i iff same
    document and ``j <= i`` and, under a window, ``i - j < window``."""
    back = q_index[:, None] - k_index[None, :]
    seen = (q_documents[:, None] == k_documents[None, :]) & (back >= 0)
    return seen if window is None else seen & (back < window)


def embed(ops, ps, inputs):
    (p,) = ps
    return Carry(p["embedding"][inputs[:, 0]], jnp.zeros((), jnp.float32),
                 inputs[:, 1].astype(jnp.float32))


def attention(ops, a, z, documents, shape, window, rope):
    """One sequence: z (S, D), normed, and its ids (S,) -> the attention
    sublayer's output.  ``shape``: (query heads, key/value heads, head width)
    as the config states them; parameters of another shape are refused."""
    s = z.shape[0]
    heads, kv_heads, width = shape
    got = tuple(a[n]["kernel"].shape[1:] for n in "qkv") + (a["o"]["kernel"].shape[:2],)
    if got != ((heads, width), (kv_heads, width), (kv_heads, width), (heads, width)):
        raise ValueError(f"a layer of {heads} query heads over {kv_heads} of {width} got "
                         f"q, k, v, o of {got}")
    positions = restarted_positions(documents)
    q = rotate(ops.einsum("sd,dhk->shk", z, a["q"]["kernel"]), positions, rope)
    k = rotate(ops.einsum("sd,dhk->shk", z, a["k"]["kernel"]), positions, rope)
    v = ops.einsum("sd,dhk->shk", z, a["v"]["kernel"])
    group = heads // kv_heads
    scale = 1.0 / jnp.sqrt(jnp.float32(width))
    rows = SCORE_ROWS if s % SCORE_ROWS == 0 else s
    index = jnp.arange(s)

    @jax.checkpoint  # keep a head's scores only while its own gradient is taken
    def head(parts):
        qh, kh, vh = parts                                      # (S, D) each

        def block(start):
            qb = jax.lax.dynamic_slice_in_dim(qh, start, rows)
            mask = allowed(start + jnp.arange(rows), index,
                           jax.lax.dynamic_slice_in_dim(documents, start, rows),
                           documents, window)
            scores = ops.einsum("qd,kd->qk", qb, kh) * scale
            probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
            return ops.einsum("qk,kd->qd", probs, vh)

        return jax.lax.map(block, jnp.arange(0, s, rows)).reshape(s, -1)

    per_head = lambda x: jnp.repeat(jnp.moveaxis(x, 1, 0), group, axis=0)
    out = jax.lax.map(head, (jnp.moveaxis(q, 1, 0), per_head(k), per_head(v)))
    return ops.einsum("hsk,hkd->sd", out, a["o"]["kernel"])


def route(ops, m, z, top_k):
    """z (T, D) -> scores (T, E), chosen weights (T, k), chosen ids (T, k)."""
    scores = jax.nn.softmax(ops.einsum("td,de->te", z, m["router"]["kernel"]), axis=-1)
    chosen, index = jax.lax.top_k(scores, top_k)
    return scores, chosen / jnp.sum(chosen, axis=-1, keepdims=True), index


def routed_feed_forward(ops, m, z, top_k, first, act):
    """One chip's batch: z (T, D) -> (the held experts' part of the sum, the
    layer's auxiliary loss)."""
    scores, weight, index = route(ops, m, z, top_k)
    n_router = scores.shape[-1]
    counts = jnp.sum(jax.nn.one_hot(index, n_router, dtype=jnp.float32), axis=(0, 1))
    share = jax.lax.stop_gradient(counts / (top_k * z.shape[0]))
    aux = n_router * jnp.sum(share * jnp.mean(scores, axis=0))

    @jax.checkpoint
    def one(y, expert):
        e, w_gate, w_up, w_down = expert
        w = jnp.sum(jnp.where(index == first + e, weight, 0.0), axis=-1)
        hidden = (act(ops.einsum("td,df->tf", z, w_gate))
                  * ops.einsum("td,df->tf", z, w_up))
        return y + w[:, None] * ops.einsum("tf,fd->td", hidden, w_down), None

    held = m["w_gate"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(z),
                        (jnp.arange(held), m["w_gate"], m["w_up"], m["w_down"]))
    return y, aux


def _attend(ops, p, carry, eps, shape, window, rope):
    """x (R, S, D) -> x + attention, one sequence at a time."""
    documents = jax.lax.stop_gradient(carry.documents)
    one = jax.checkpoint(lambda row: attention(
        ops, p["attn"], rms_norm(row[0], p["ln1"]["scale"], eps), row[1], shape, window, rope))
    return carry.h + jax.lax.map(one, (carry.h, documents))


def _by_chip(p, x, eps, rows_per_chip):
    """n2(x) by chip: (R / rows_per_chip, rows_per_chip x S, D)."""
    z = rms_norm(x, p["ln2"]["scale"], eps)
    return z.reshape(x.shape[0] // rows_per_chip, -1, x.shape[-1])


def layer(ops, ps, carry, eps, shape, window, rope, act, top_k, first, rows_per_chip):
    """One layer over a ``Carry``.  Rows meet only in the router's counts, and
    there only the rows of one chip's batch."""
    (p,) = ps
    x = _attend(ops, p, carry, eps, shape, window, rope)
    y, aux = jax.lax.map(
        lambda zc: routed_feed_forward(ops, p["moe"], zc, top_k, first, _ACTIVATIONS[act]),
        _by_chip(p, x, eps, rows_per_chip))
    return Carry(x + y.reshape(x.shape), carry.aux + jnp.mean(aux), carry.documents)


def layer_chosen(ops, ps, carry, eps, shape, window, rope, act, top_k, first, rows_per_chip):
    """The experts the layer's router chooses: (chips, T, k)."""
    (p,) = ps
    x = _attend(ops, p, carry, eps, shape, window, rope)
    return jax.lax.map(lambda zc: route(ops, p["moe"], zc, top_k)[2],
                       _by_chip(p, x, eps, rows_per_chip))


class Layer(Stage):
    """A layer stage that also keeps, at its first forward pass, the experts
    its router chose (a second, forward-only pass of the layer)."""

    def __init__(self, index: int, static: tuple):
        super().__init__((f"layer_{index}",), layer, static)
        self.index = index

    def forward(self, ops: Ops, ps, x):
        if self.index not in REFERENCE_ROUTING:
            REFERENCE_ROUTING[self.index] = np.asarray(
                chain._forward(layer_chosen, self.static, ops.precision)(ps, x))
        return super().forward(ops, ps, x)


def _block_loss(ops, ps, x, targets, weights, eps):
    """Weighted, summed cross-entropy of a block of rows: x (R, D)."""
    ln_f, head = ps
    logits = ops.einsum("rd,dv->rv", rms_norm(x, ln_f["scale"], eps), head["kernel"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(weights * jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0])


@functools.lru_cache(maxsize=None)
def _block_loss_backward(precision, eps):
    ops = Ops(precision)

    def run(ps, x, targets, weights):
        loss, (dps, dx) = jax.value_and_grad(
            lambda p, a: _block_loss(ops, p, a, targets, weights, eps), argnums=(0, 1))(ps, x)
        return loss, dps, dx

    return jax.jit(run)


_add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b), donate_argnums=(0,))


def routing_report(first: int, held: int) -> str:
    """What the reference's own router chose at its first step."""
    if not REFERENCE_ROUTING:
        return "# routing: the reference kept none"
    per_layer, loads = [], []
    for index in REFERENCE_ROUTING.values():       # (chips, T, k) a layer
        total = 0
        for chip in index:
            counts = np.array([(chip == first + e).sum() for e in range(held)])
            total += int(counts.sum())
            loads.append(counts.max() / max(counts.mean(), 1e-9))
        per_layer.append(total // len(index))
    return (f"# routing (the reference's own, first step): assignments to held experts a chip "
            f"and layer {per_layer} over {len(per_layer)} layers, largest held expert's "
            f"load over the mean {max(loads):.3f}")


class LossBackward:
    keys = ("ln_f", "head")

    def __init__(self, eps, coef, layers, first, held):
        self.eps, self.coef, self.layers = eps, coef, layers
        self.first, self.held = first, held
        self.reported = False

    def __call__(self, ops, ps, carry, labels):
        if not self.reported:
            self.reported = True
            print(routing_report(self.first, self.held), flush=True)
        targets, weights = labels
        b, s, d = carry.h.shape
        rows = carry.h.reshape(b * s, d)
        targets, weights = targets.reshape(b * s), weights.reshape(b * s).astype(jnp.float32)
        fn = _block_loss_backward(ops.precision, self.eps)
        total, dps, dxs = 0.0, None, []
        for lo in range(0, b * s, LOSS_ROWS):
            hi = lo + LOSS_ROWS
            loss, dp, dx = fn(ps, rows[lo:hi], targets[lo:hi], weights[lo:hi])
            total = total + loss
            dps = dp if dps is None else _add(dps, dp)
            dxs.append(dx)
        scale = 1.0 / jnp.sum(weights)      # the mean over the positions that count
        dps = jax.tree_util.tree_map(lambda g: g * scale, dps)
        dh = (jnp.concatenate(dxs) * scale).reshape(b, s, d)
        aux_scale = self.coef / self.layers
        return (total * scale + aux_scale * carry.aux, dps,
                Carry(dh, jnp.full((), aux_scale, jnp.float32),
                      jnp.zeros_like(carry.documents)))


def rope_static(parameters: dict) -> tuple:
    """A layer type's ``rope_parameters`` as a hashable ``(theta, None | (factor,
    original_max_position_embeddings, beta_fast, beta_slow, attention_factor))``;
    the whole head is rotated."""
    kind = parameters.get("rope_type", "default")
    if kind not in ("default", "yarn"):
        raise ValueError(f"rope_type {kind!r}: this reference has 'default' and 'yarn'")
    if float(parameters.get("partial_rotary_factor", 1.0)) != 1.0:
        raise ValueError("partial_rotary_factor: this reference rotates whole heads")
    yarn = None if kind == "default" else (
        float(parameters["factor"]), int(parameters["original_max_position_embeddings"]),
        float(parameters["beta_fast"]), float(parameters["beta_slow"]),
        float(parameters["attention_factor"]))
    return float(parameters["rope_theta"]), yarn


def build(config: dict, traffic: dict):
    """(stages, loss_backward) for the configuration: the first
    ``num_hidden_layers`` entries of its per-layer lists."""
    eps, layers = float(config["rms_norm_eps"]), config["num_hidden_layers"]
    if config["router_scoring"] != "softmax" or not config["norm_topk_prob"]:
        raise ValueError("this reference routes by a softmax renormalised over the chosen")
    shape = (config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"])
    REFERENCE_ROUTING.clear()
    stages = [Stage(("embed",), embed)]
    for i in range(layers):
        kind = config["layer_types"][i]
        if kind not in ("full_attention", "sliding_attention"):
            raise ValueError(f"layer_types[{i}] {kind!r}: this reference has full and sliding")
        if config["mlp_layer_types"][i] != "sparse":
            raise ValueError(f"mlp_layer_types[{i}]: this reference's layers are all sparse")
        window = int(config["sliding_window"]) if kind == "sliding_attention" else None
        stages.append(Layer(i, (
            eps, shape, window, rope_static(config["rope_parameters"][kind]),
            config["hidden_act"], config["num_experts_per_tok"], config["held_experts_first"],
            int(traffic["samples_per_chip"]))))
    return stages, LossBackward(eps, float(config["router_aux_loss_coef"]), layers,
                                config["held_experts_first"], config["num_experts"])
