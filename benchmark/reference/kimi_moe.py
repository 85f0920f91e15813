"""Plain reference: a causal decoder with latent attention (MLA), a leading
dense layer, then layers whose feed-forward is a sigmoid router over SwiGLU
experts beside shared experts; float32.

Kimi-VL-A3B-Instruct's language decoder (``model_type`` of the DeepSeek-V3
family; DeepSeek-V2, arXiv:2405.04434, for the attention; DeepSeek-V3,
arXiv:2412.19437, for the router).  The equations, which the program computes
too; ``x`` is the residual stream, ``z = RMSNorm(x)``:

Attention.  ``q = z W_q`` -> H heads of ``[q_nope (nope) | q_rope (rope)]``;
``[c (rank) | k_r (rope)] = z W_kva``; ``c' = RMSNorm_rank(c)``; ``[k_nope_h
(nope) | v_h (v)] = c' W_kvb`` for each head h; ``q_rope <- RoPE(q_rope)``,
``k_r <- RoPE(k_r)``: ONE rotary key for all heads; ``score_h(i, j) =
(q_nope_h,i . k_nope_h,j + q_rope_h,i . k_r,j) / sqrt(nope + rope)``, causal,
softmax; ``out = concat_h(softmax(score_h) v_h) W_o``.  RoPE in the split-half
convention (the first and second half of the rotary part are the pairs);
``q_lora_rank`` null: the query has no low-rank step; no ``rope_scaling``.

Feed-forward.  The first ``first_k_dense_replace`` layers: SwiGLU of
``intermediate_size``.  The others: ``s = sigmoid(W_r z)`` over all the
router's experts; chosen = the ``num_experts_per_tok`` largest of ``s + b``
(``b``: the selection bias, which weighs nothing and takes no gradient; zero
in a cell, as at the start of a run); ``w_e = s_e / (sum over the chosen of s +
1e-20) x routed_scaling_factor``; ``y = sum over chosen AND held e of w_e
W_d,e (silu(W_g,e z) * W_u,e z) + W_d,s (silu(W_g,s z) * W_u,s z)``, the last
the shared experts, one SwiGLU of ``n_shared_experts x
moe_intermediate_size``, whole here.  What the absent experts would add is
left out.  Auxiliary loss a SEQUENCE a layer (``seq_aux``): ``sum_e f_e P_e``,
``f_e = E / (k S) x n_e`` (no gradient through the counts ``n_e`` of that
sequence's S rows), ``P_e`` the sequence's mean of ``s_e / sum s``; mean over
the sequences, mean over the routed layers.

Head and loss.  ``logits = W_head n_f(x)``; loss = mean over every position of
the cross-entropy against the next token (the labels) plus
``router_aux_loss_coef`` x the auxiliary loss.

Laid out to fit: attention one sequence and one head at a time (the 8,192 x
8,192 scores of 16 heads are 4.3 GB whole), the experts one at a time (a
masked dense product over the held experts: no sort, no kernel), the loss one
block of ``LOSS_ROWS`` positions at a time.  Between stages goes a ``Carry``:
the activations and the auxiliary loss summed so far.  The parameter tree is
addressed by the names of the program's (``embed``, ``layer_<i>/{ln1, attn/{q,
kv_a, kv_a_norm, kv_b, o}, ln2, mlp/{gate, up, down} | moe/{router, w_gate,
w_up, w_down}, shared_experts/{gate, up, down}}``, ``ln_f``, ``head``).

Nothing of the program is imported and its routing is never used: the
reference routes by its own float32 router, and prints what it chose at its
first step (assignments to held experts a layer, the largest held expert's
load over the mean).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import chain
from .chain import Ops, Stage

LOSS_ROWS = 1024

# the reference's own chosen experts at its first step, by layer: (R, S, k)
REFERENCE_ROUTING = {}


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


def rope(x, theta):
    """x: (S, ..., D); position s rotates pair (x[i], x[i + D/2]) by
    s * theta^(-2i/D)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    angles = angles.reshape(x.shape[0], *([1] * (x.ndim - 2)), d // 2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def swiglu(ops, m, z):
    hidden = (jax.nn.silu(ops.einsum("td,df->tf", z, m["gate"]["kernel"]))
              * ops.einsum("td,df->tf", z, m["up"]["kernel"]))
    return ops.einsum("tf,fd->td", hidden, m["down"]["kernel"])


def embed(ops, ps, tokens):
    (p,) = ps
    return Carry(p["embedding"][tokens], jnp.zeros((), jnp.float32))


def attention(ops, a, z, eps, theta, nope):
    """One sequence: z (S, D), normed -> the attention sublayer's output."""
    s = z.shape[0]
    q = ops.einsum("sd,dhk->shk", z, a["q"]["kernel"])
    rank = a["kv_a_norm"]["scale"].shape[0]
    kv_a = ops.einsum("sd,dr->sr", z, a["kv_a"]["kernel"])
    latent = rms_norm(kv_a[:, :rank], a["kv_a_norm"]["scale"], eps)
    kv_b = ops.einsum("sr,rhk->shk", latent, a["kv_b"]["kernel"])
    k_rot = rope(kv_a[:, rank:], theta)                        # (S, rope): every head's
    q_nope, q_rot = q[..., :nope], rope(q[..., nope:], theta)
    k_nope, v = kv_b[..., :nope], kv_b[..., nope:]
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint  # keep a head's scores only while its own gradient is taken
    def head(parts):
        qn, qr, kn, vh = parts                                  # (S, .) each
        scores = (ops.einsum("qd,kd->qk", qn, kn)
                  + ops.einsum("qd,kd->qk", qr, k_rot)) * scale
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return ops.einsum("qk,kd->qd", probs, vh)

    by_head = lambda x: jnp.moveaxis(x, 1, 0)
    out = jax.lax.map(head, (by_head(q_nope), by_head(q_rot), by_head(k_nope), by_head(v)))
    return ops.einsum("hsk,hkd->sd", out, a["o"]["kernel"])


def route(ops, m, z, top_k, scale, bias=None):
    """z (S, D) -> scores (S, E), chosen weights (S, k), chosen ids (S, k)."""
    scores = jax.nn.sigmoid(ops.einsum("td,de->te", z, m["router"]["kernel"]))
    _, index = jax.lax.top_k(scores if bias is None else scores + bias, top_k)
    chosen = jnp.take_along_axis(scores, index, axis=-1)
    weight = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20) * scale
    return scores, weight, index


def routed_feed_forward(ops, m, shared, z, top_k, first, scale, bias=None):
    """One sequence: z (S, D) -> (the held routed experts' part of the sum
    plus the shared experts, the sequence's auxiliary loss)."""
    scores, weight, index = route(ops, m, z, top_k, scale, bias)
    n_router = scores.shape[-1]
    counts = jnp.sum(jax.nn.one_hot(index, n_router, dtype=jnp.float32), axis=(0, 1))
    share = jax.lax.stop_gradient(counts * (n_router / (top_k * z.shape[0])))
    normed = scores / jnp.sum(scores, axis=-1, keepdims=True)
    aux = jnp.sum(share * jnp.mean(normed, axis=0))

    @jax.checkpoint
    def one(y, expert):
        e, w_gate, w_up, w_down = expert
        w = jnp.sum(jnp.where(index == first + e, weight, 0.0), axis=-1)
        hidden = (jax.nn.silu(ops.einsum("td,df->tf", z, w_gate))
                  * ops.einsum("td,df->tf", z, w_up))
        return y + w[:, None] * ops.einsum("tf,fd->td", hidden, w_down), None

    held = m["w_gate"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(z),
                        (jnp.arange(held), m["w_gate"], m["w_up"], m["w_down"]))
    if shared is not None:
        y = y + swiglu(ops, shared, z)
    return y, aux


def _attend(ops, p, x, eps, theta, nope):
    """x (R, S, D) -> x + attention, one sequence at a time."""
    one = jax.checkpoint(lambda r: attention(
        ops, p["attn"], rms_norm(r, p["ln1"]["scale"], eps), eps, theta, nope))
    return x + jax.lax.map(one, x)


def dense_layer(ops, ps, carry, eps, theta, nope):
    (p,) = ps
    x = _attend(ops, p, carry.h, eps, theta, nope)
    feed = jax.checkpoint(lambda r: swiglu(ops, p["mlp"], rms_norm(r, p["ln2"]["scale"], eps)))
    return Carry(x + jax.lax.map(feed, x), carry.aux)


def routed_layer(ops, ps, carry, eps, theta, nope, top_k, first, scale):
    """One routed layer over a ``Carry``.  Sequences meet nowhere: the
    auxiliary loss is a sequence's own."""
    (p,) = ps
    x = _attend(ops, p, carry.h, eps, theta, nope)
    y, aux = jax.lax.map(
        lambda r: routed_feed_forward(
            ops, p["moe"], p.get("shared_experts"), rms_norm(r, p["ln2"]["scale"], eps),
            top_k, first, scale), x)
    return Carry(x + y, carry.aux + jnp.mean(aux))


def routed_layer_chosen(ops, ps, carry, eps, theta, nope, top_k, first, scale):
    """The experts the layer's router chooses: (R, S, k)."""
    (p,) = ps
    x = _attend(ops, p, carry.h, eps, theta, nope)
    return jax.lax.map(
        lambda r: route(ops, p["moe"], rms_norm(r, p["ln2"]["scale"], eps), top_k, scale)[2], x)


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
    for index in REFERENCE_ROUTING.values():       # (R, S, k) a layer
        counts = np.array([(index == first + e).sum() for e in range(held)])
        per_layer.append(int(counts.sum()))
        loads.append(counts.max() / max(counts.mean(), 1e-9))
    return (f"# routing (the reference's own, first step): assignments to held experts a layer "
            f"{per_layer} over {len(per_layer)} routed layers, largest held expert's load "
            f"over the mean {max(loads):.3f}")


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


def build(config: dict, traffic: dict):
    """(stages, loss_backward) for the configuration."""
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    layers, dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    attention_static = (eps, theta, config["qk_nope_head_dim"])
    routed_static = attention_static + (
        config["num_experts_per_tok"], config["held_experts_first"],
        float(config["routed_scaling_factor"]))
    REFERENCE_ROUTING.clear()
    stages = [Stage(("embed",), embed)]
    stages += [Stage((f"layer_{i}",), dense_layer, attention_static) for i in range(dense)]
    stages += [RoutedLayer(i, routed_static) for i in range(dense, layers)]
    return stages, LossBackward(eps, float(config["router_aux_loss_coef"]), layers - dense,
                                config["held_experts_first"], config["n_routed_experts"])
