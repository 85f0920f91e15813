"""Plain reference: a causal decoder whose attention differs by layer (sliding
window or full, each kind with its own head count and its own rotary positions,
a sigmoid gate a head on the output), a leading dense layer, then layers whose
feed-forward is a sigmoid router over SwiGLU experts beside one shared expert;
float32.

Laguna-XS.2 (``model_type`` ``laguna``; poolside/Laguna-XS.2 ``config.json``).
The equations, from the config's keys; ``x`` is the residual stream, ``z =
RMSNorm(x)`` (``x / rms(x) * w``), layer ``l``:

Attention.  ``H_l = num_attention_heads_per_layer[l]`` query heads over
``num_key_value_heads`` key/value heads of ``head_dim``, no bias: ``q = z W_q``,
``k = z W_k``, ``v = z W_v``; query head ``h`` reads key/value head ``h // (H_l
/ H_kv)``.  RoPE on q and k by ``rope_parameters[layer_types[l]]``, split-half
pairs (column ``i`` with column ``i + rot / 2``), on the first ``rot = head_dim x
partial_rotary_factor`` columns, the others pass: ``rope_type`` ``default``:
``inv_freq_i = theta^(-2i / rot)``; ``yarn`` (arXiv:2309.00071, Hugging Face's
``_compute_yarn_parameters``): ``extra_i = theta^(-2i / rot)``, ``inter_i =
extra_i / factor``, ``low = floor(rot ln(original_max_position_embeddings /
(beta_fast 2 pi)) / (2 ln theta))``, ``high = ceil(the same with beta_slow)``,
``ramp_i = clip((i - low) / (high - low), 0, 1)``, ``inv_freq_i = inter_i ramp_i
+ extra_i (1 - ramp_i)``, and cos and sin times ``attention_factor``.  Scores ``q
k^T / sqrt(head_dim)``; a query at position ``i`` sees key ``j`` iff ``j <= i``
and, on a ``sliding_attention`` layer, ``i - j < sliding_window`` (itself
included); softmax; times v.  Gate (``gating``): ``g = sigmoid(z W_g)``, ``W_g``
(hidden, H_l): one number a head and token, times the head's ``head_dim``
outputs, before ``W_o``.

Feed-forward.  ``mlp_layer_types[l]`` ``dense``: SwiGLU of ``intermediate_size``.
``sparse``: ``s = score(W_r z)`` over all the router's experts in float32
(``router_scoring``: sigmoid); chosen = the ``num_experts_per_tok`` largest;
``w_e = s_e / (sum over the chosen of s + 1e-20) x moe_routed_scaling_factor``,
applied to the expert's OUTPUT (``moe_apply_router_weight_on_input`` false);
``y = sum over chosen AND held e of w_e W_d,e (silu(W_g,e z) * W_u,e z) + W_d,s
(silu(W_g,s z) * W_u,s z)``, the last the shared expert
(``shared_expert_intermediate_size``), ungated, whole here.  What the absent
experts would add is left out.  Auxiliary loss a layer (Switch form): ``E
sum_e (n_e / (k T)) mean_T s_e`` over the T rows of one chip's batch, no
gradient through the counts ``n_e``; mean over the routed layers.

Head and loss.  ``logits = W_head n_f(x)``; loss = mean over every position of
the cross-entropy against the next token (the labels) plus
``router_aux_loss_coef`` x the auxiliary loss.

Departures from the published model, each in the configuration's file: the cut
(``reduced``: 5 of 40 layers, 16 of 256 experts held, an eighth of the
vocabulary) and what the config leaves open (``assumed``: the gate a head and
its place, sigmoid scores renormalised over the chosen, no selection bias,
``silu``, no q / k norms, split-half pairs, the auxiliary loss).  The assumed
forms are arguments here (``gating_type``, ``router_scoring``, ``hidden_act``
of the configuration), so that a correction is a change of data.

Laid out to fit: attention one sequence and one query head at a time, the
8,192 x 8,192 scores of a head in blocks of ``SCORE_ROWS`` query rows; the
experts one at a time (a masked dense product over the held experts: no sort,
no kernel); the loss one block of ``LOSS_ROWS`` positions at a time.  Between
stages goes a ``Carry``: the activations and the auxiliary loss summed so far.
The parameter tree is addressed by the names of the program's (``embed``,
``layer_<i>/{ln1, attn/{q, k, v, gate, o}, ln2, mlp/{gate, up, down} |
moe/{router, w_gate, w_up, w_down}, shared_experts/{gate, up, down}}``, ``ln_f``,
``head``).

Nothing of the program is imported: the mask is this file's own, from
positions; YaRN's frequencies are its own; the head counts are read off the
config a layer; and the reference routes by its own float32 router, and prints
what it chose at its first step (assignments to held experts a layer, the
largest held expert's load over the mean).
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
_SCORES = {"sigmoid": jax.nn.sigmoid, "softmax": functools.partial(jax.nn.softmax, axis=-1)}


@jax.tree_util.register_pytree_node_class
class Carry:
    """What goes from stage to stage: the activations (R, S, D) and the
    auxiliary loss summed over the routed layers so far."""

    def __init__(self, h, aux):
        self.h, self.aux = h, aux

    dtype = property(lambda self: self.h.dtype)

    def tree_flatten(self):
        return (self.h, self.aux), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def inverse_frequencies(rot: int, rope: tuple) -> np.ndarray:
    """The ``rot // 2`` frequencies of a layer type's ``rope_parameters``, as the
    tuple ``rope_static`` makes of them; float32."""
    theta, _, yarn = rope
    extra = np.float32(theta) ** (-np.arange(0, rot, 2, dtype=np.float32) / np.float32(rot))
    if yarn is None:
        return extra
    factor, original, beta_fast, beta_slow, _ = yarn
    inter = extra / np.float32(factor)

    def pair_of(turns):
        return rot * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(pair_of(beta_fast)), 0), min(math.ceil(pair_of(beta_slow)), rot - 1)
    span = (high - low) or 0.001                        # Hugging Face's guard
    ramp = np.clip((np.arange(rot // 2, dtype=np.float32) - low) / np.float32(span), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rotate(x, rope: tuple):
    """x: (S, H, D); position s turns pair (x[i], x[i + rot / 2]) of the first
    ``rot`` columns by ``s * inv_freq_i``; cos and sin times YaRN's factor."""
    _, partial, yarn = rope
    rot = int(x.shape[-1] * partial)
    angles = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
              * jnp.asarray(inverse_frequencies(rot, rope)))
    scale = 1.0 if yarn is None else yarn[4]
    cos, sin = jnp.cos(angles)[:, None, :] * scale, jnp.sin(angles)[:, None, :] * scale
    x1, x2, rest = x[..., : rot // 2], x[..., rot // 2: rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def allowed(q_positions, k_positions, window):
    """(Q, K) bool from positions: key j for query i iff ``j <= i`` and, under a
    window, ``i - j < window``."""
    back = q_positions[:, None] - k_positions[None, :]
    seen = back >= 0
    return seen if window is None else seen & (back < window)


def swiglu(ops, m, z, act):
    hidden = (act(ops.einsum("td,df->tf", z, m["gate"]["kernel"]))
              * ops.einsum("td,df->tf", z, m["up"]["kernel"]))
    return ops.einsum("tf,fd->td", hidden, m["down"]["kernel"])


def embed(ops, ps, tokens):
    (p,) = ps
    return Carry(p["embedding"][tokens], jnp.zeros((), jnp.float32))


def attention(ops, a, z, shape, window, rope, gating):
    """One sequence: z (S, D), normed -> the attention sublayer's output.
    ``shape``: the layer's (query heads, key/value heads, head width) as the
    config states them; parameters of another shape are refused."""
    s = z.shape[0]
    heads, kv_heads, width = shape
    got = tuple(a[n]["kernel"].shape[1:] for n in "qkv") + (a["o"]["kernel"].shape[:2],)
    if got != ((heads, width), (kv_heads, width), (kv_heads, width), (heads, width)):
        raise ValueError(f"a layer of {heads} query heads over {kv_heads} of {width} got "
                         f"q, k, v, o of {got}")
    q = rotate(ops.einsum("sd,dhk->shk", z, a["q"]["kernel"]), rope)
    k = rotate(ops.einsum("sd,dhk->shk", z, a["k"]["kernel"]), rope)
    v = ops.einsum("sd,dhk->shk", z, a["v"]["kernel"])
    group = heads // kv_heads
    scale = 1.0 / jnp.sqrt(jnp.float32(width))
    rows = SCORE_ROWS if s % SCORE_ROWS == 0 else s
    k_positions = jnp.arange(s)

    @jax.checkpoint  # keep a head's scores only while its own gradient is taken
    def head(parts):
        qh, kh, vh = parts                                      # (S, D) each

        def block(start):
            qb = jax.lax.dynamic_slice_in_dim(qh, start, rows)
            mask = allowed(start + jnp.arange(rows), k_positions, window)
            scores = ops.einsum("qd,kd->qk", qb, kh) * scale
            probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
            return ops.einsum("qk,kd->qd", probs, vh)

        return jax.lax.map(block, jnp.arange(0, s, rows)).reshape(s, -1)

    per_head = lambda x: jnp.repeat(jnp.moveaxis(x, 1, 0), group, axis=0)
    out = jax.lax.map(head, (jnp.moveaxis(q, 1, 0), per_head(k), per_head(v)))
    if gating == "per_head":
        gate = jax.nn.sigmoid(ops.einsum("sd,dh->sh", z, a["gate"]["kernel"]))
        out = out * gate.T[:, :, None]
    elif gating is not None:
        raise ValueError(f"gating_type {gating!r}: this reference has 'per_head' and null")
    return ops.einsum("hsk,hkd->sd", out, a["o"]["kernel"])


def route(ops, m, z, top_k, scale, scoring):
    """z (T, D) -> scores (T, E), chosen weights (T, k), chosen ids (T, k)."""
    scores = _SCORES[scoring](ops.einsum("td,de->te", z, m["router"]["kernel"]))
    _, index = jax.lax.top_k(scores, top_k)
    chosen = jnp.take_along_axis(scores, index, axis=-1)
    weight = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20) * scale
    return scores, weight, index


def routed_feed_forward(ops, m, shared, z, top_k, first, scale, scoring, act):
    """One chip's batch: z (T, D) -> (the held routed experts' part of the sum
    plus the shared expert, the layer's auxiliary loss)."""
    scores, weight, index = route(ops, m, z, top_k, scale, scoring)
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
    if shared is not None:
        y = y + swiglu(ops, shared, z, act)
    return y, aux


def _attend(ops, p, x, eps, shape, window, rope, gating):
    """x (R, S, D) -> x + attention, one sequence at a time."""
    one = jax.checkpoint(lambda r: attention(
        ops, p["attn"], rms_norm(r, p["ln1"]["scale"], eps), shape, window, rope, gating))
    return x + jax.lax.map(one, x)


def dense_layer(ops, ps, carry, eps, shape, window, rope, gating, act):
    (p,) = ps
    x = _attend(ops, p, carry.h, eps, shape, window, rope, gating)
    feed = jax.checkpoint(lambda r: swiglu(
        ops, p["mlp"], rms_norm(r, p["ln2"]["scale"], eps), _ACTIVATIONS[act]))
    return Carry(x + jax.lax.map(feed, x), carry.aux)


def _by_chip(p, x, eps, rows_per_chip):
    """n2(x) by chip: (R / rows_per_chip, rows_per_chip x S, D)."""
    z = rms_norm(x, p["ln2"]["scale"], eps)
    return z.reshape(x.shape[0] // rows_per_chip, -1, x.shape[-1])


def routed_layer(ops, ps, carry, eps, shape, window, rope, gating, act, top_k, first, scale,
                 scoring, rows_per_chip):
    """One routed layer over a ``Carry``.  Rows meet only in the router's
    counts, and there only the rows of one chip's batch."""
    (p,) = ps
    x = _attend(ops, p, carry.h, eps, shape, window, rope, gating)
    y, aux = jax.lax.map(
        lambda zc: routed_feed_forward(ops, p["moe"], p.get("shared_experts"), zc, top_k,
                                       first, scale, scoring, _ACTIVATIONS[act]),
        _by_chip(p, x, eps, rows_per_chip))
    return Carry(x + y.reshape(x.shape), carry.aux + jnp.mean(aux))


def routed_layer_chosen(ops, ps, carry, eps, shape, window, rope, gating, act, top_k, first,
                        scale, scoring, rows_per_chip):
    """The experts the layer's router chooses: (chips, T, k)."""
    (p,) = ps
    x = _attend(ops, p, carry.h, eps, shape, window, rope, gating)
    return jax.lax.map(lambda zc: route(ops, p["moe"], zc, top_k, scale, scoring)[2],
                       _by_chip(p, x, eps, rows_per_chip))


class RoutedLayer(Stage):
    """A layer stage that also keeps, at its first forward pass, the experts
    its router chose (a second, forward-only pass of the layer)."""

    def __init__(self, index: int, static: tuple):
        super().__init__((f"layer_{index}",), routed_layer, static)
        self.index = index

    def forward(self, ops: Ops, ps, x):
        if self.index not in REFERENCE_ROUTING:
            REFERENCE_ROUTING[self.index] = np.asarray(
                chain._forward(routed_layer_chosen, self.static, ops.precision)(ps, x))
        return super().forward(ops, ps, x)


def _block_loss(ops, ps, x, labels, eps):
    """Summed cross-entropy of a block of rows: x (R, D), labels (R,)."""
    ln_f, head = ps
    logits = ops.einsum("rd,dv->rv", rms_norm(x, ln_f["scale"], eps), head["kernel"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))


@functools.lru_cache(maxsize=None)
def _block_loss_backward(precision, eps):
    ops = Ops(precision)

    def run(ps, x, labels):
        loss, (dps, dx) = jax.value_and_grad(
            lambda p, a: _block_loss(ops, p, a, labels, eps), argnums=(0, 1))(ps, x)
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
            f"and layer {per_layer} over {len(per_layer)} routed layers, largest held expert's "
            f"load over the mean {max(loads):.3f}")


class LossBackward:
    keys = ("ln_f", "head")

    def __init__(self, eps, coef, routed_layers, first, held):
        self.eps, self.coef, self.routed_layers = eps, coef, routed_layers
        self.first, self.held = first, held
        self.reported = False

    def __call__(self, ops, ps, carry, labels):
        if not self.reported:
            self.reported = True
            print(routing_report(self.first, self.held), flush=True)
        b, s, d = carry.h.shape
        rows, flat = carry.h.reshape(b * s, d), labels.reshape(b * s)
        fn = _block_loss_backward(ops.precision, self.eps)
        n = b * s
        total, dps, dxs = 0.0, None, []
        for lo in range(0, n, LOSS_ROWS):
            loss, dp, dx = fn(ps, rows[lo:lo + LOSS_ROWS], flat[lo:lo + LOSS_ROWS])
            total = total + loss
            dps = dp if dps is None else _add(dps, dp)
            dxs.append(dx)
        scale = 1.0 / n
        dps = jax.tree_util.tree_map(lambda g: g * scale, dps)
        dh = (jnp.concatenate(dxs) * scale).reshape(b, s, d)
        aux_scale = self.coef / max(self.routed_layers, 1)
        return (total * scale + aux_scale * carry.aux, dps,
                Carry(dh, jnp.full((), aux_scale, jnp.float32)))


def rope_static(parameters: dict) -> tuple:
    """A layer type's ``rope_parameters`` as a hashable ``(theta, partial rotary
    factor, None | (factor, original_max_position_embeddings, beta_fast,
    beta_slow, attention_factor))``."""
    kind = parameters.get("rope_type", "default")
    if kind not in ("default", "yarn"):
        raise ValueError(f"rope_type {kind!r}: this reference has 'default' and 'yarn'")
    yarn = None if kind == "default" else (
        float(parameters["factor"]), int(parameters["original_max_position_embeddings"]),
        float(parameters["beta_fast"]), float(parameters["beta_slow"]),
        float(parameters["attention_factor"]))
    return (float(parameters["rope_theta"]),
            float(parameters.get("partial_rotary_factor", 1.0)), yarn)


def build(config: dict, traffic: dict):
    """(stages, loss_backward) for the configuration: the first
    ``num_hidden_layers`` entries of its per-layer lists."""
    eps, layers = float(config["rms_norm_eps"]), config["num_hidden_layers"]
    gating = config["gating_type"] if config["gating"] else None
    act = config["hidden_act"]
    routed_static = (
        config["num_experts_per_tok"], config["held_experts_first"],
        float(config["moe_routed_scaling_factor"]), config["router_scoring"],
        int(traffic["samples_per_chip"]))
    REFERENCE_ROUTING.clear()
    stages = [Stage(("embed",), embed)]
    routed = 0
    for i in range(layers):
        kind = config["layer_types"][i]
        if kind not in ("full_attention", "sliding_attention"):
            raise ValueError(f"layer_types[{i}] {kind!r}: this reference has full and sliding")
        window = int(config["sliding_window"]) if kind == "sliding_attention" else None
        shape = (config["num_attention_heads_per_layer"][i], config["num_key_value_heads"],
                 config["head_dim"])
        static = (eps, shape, window, rope_static(config["rope_parameters"][kind]), gating, act)
        if config["mlp_layer_types"][i] == "dense":
            stages.append(Stage((f"layer_{i}",), dense_layer, static))
        else:
            stages.append(RoutedLayer(i, static + routed_static))
            routed += 1
    return stages, LossBackward(eps, float(config["router_aux_loss_coef"]), routed,
                                config["held_experts_first"], config["num_experts"])
