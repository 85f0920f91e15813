"""Plain reference: a decoder whose feed-forward is a router over SwiGLU
experts, trained by diffusion over blocks; float32.

SDAR (``sdar_moe``; JetLM/SDAR-30B-A3B-Chat) with the vectorised training of
BD3-LM (arXiv:2503.09573).  The equations, which the program computes too:

Input.  A row is ``[xt || x0]``: L noisy ids, then the L clean ones;
positions run ``[0..L) || [0..L)``.  With blocks of B positions, ``b(i) = i //
B``, a query ``(copy_q, i)`` sees a key ``(copy_k, j)``: noisy -> noisy iff
``b(j) = b(i)``; noisy -> clean iff ``b(j) < b(i)``; clean -> clean iff ``b(j)
<= b(i)``; clean -> noisy never.

Layer.  ``h' = h + W_o Attn(RoPE(norm_q(W_q n1(h))), RoPE(norm_k(W_k n1(h))),
W_v n1(h))``: grouped key/value heads (query head h reads key/value head h //
(H / H_kv)), scale 1/sqrt(head_dim), ``norm_q`` / ``norm_k`` an RMSNorm over
the head with a learned scale, RoPE in the split-half convention.  Then with
``z = n2(h')``: ``g = softmax(W_r z)`` over all the router's experts; S = the
``num_experts_per_tok`` largest; ``w_e = g_e / sum_{j in S} g_j``; ``y = sum
over e in S that are held here of w_e W_d,e (silu(W_g,e z) * W_u,e z)``; ``h''
= h' + y``.  What the absent experts would add is left out.  Auxiliary loss a
layer: ``E sum_e (n_e / (k T)) mean_T g_e`` over the T rows of one chip's
batch, no gradient through the counts ``n_e``; mean over layers.

Head and loss.  ``logits = W_head n_f(h)`` on the noisy rows only; loss = mean
over the L positions of ``weight x cross-entropy(logits, x0)`` (the labels are
``(x0, weight)``, weight = masked / t) plus ``router_aux_loss_coef`` x the
auxiliary loss.

Laid out to fit: attention one row and one query head at a time (the 8,192 x
8,192 scores of 32 heads are 8.6 GB whole), the experts one at a time (a
masked dense product over the held experts: no sort, no kernel), the loss one
block of ``LOSS_ROWS`` positions at a time.  Between stages goes a ``Carry``:
the activations and the auxiliary loss summed so far.  The parameter tree is
addressed by the names of the program's (``embed``, ``layer_<i>/{ln1, attn/{q,
k, v, o, q_norm, k_norm}, ln2, moe/{router, w_gate, w_up, w_down}}``, ``ln_f``,
``head``).

Nothing of the program is imported and its routing is never used: the
reference routes by its own float32 router, and prints what it chose at its
first step (assignments to held experts, the largest held expert's load over
the mean).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import chain
from .chain import Ops, Stage

LOSS_ROWS = 1024

# the reference's own chosen experts at its first step, by layer: (chips, T, k)
REFERENCE_ROUTING = {}


@jax.tree_util.register_pytree_node_class
class Carry:
    """What goes from stage to stage: the activations (R, 2L, D) and the
    auxiliary loss summed over the layers so far."""

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


def rope(x, positions, theta):
    """x: (S, H, D); position p rotates pair (x[i], x[i + D/2]) by
    p * theta^(-2i/D)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def allowed(length, block):
    """(2L, 2L) bool: the four rules, written on index arrays."""
    index = jnp.arange(2 * length)
    clean = index >= length
    blk = jnp.where(clean, index - length, index) // block
    q_clean, k_clean = clean[:, None], clean[None, :]
    q_blk, k_blk = blk[:, None], blk[None, :]
    return ((~q_clean & ~k_clean & (k_blk == q_blk))
            | (~q_clean & k_clean & (k_blk < q_blk))
            | (q_clean & k_clean & (k_blk <= q_blk)))


def embed(ops, ps, tokens):
    (p,) = ps
    return Carry(p["embedding"][tokens], jnp.zeros((), jnp.float32))


def _attention_row(ops, a, h, eps, theta, block):
    """One row: h (2L, D) normed -> the attention sublayer's output (2L, D)."""
    s = h.shape[0]
    length = s // 2
    positions = jnp.concatenate([jnp.arange(length), jnp.arange(length)])
    q = ops.einsum("sd,dhk->shk", h, a["q"]["kernel"])
    k = ops.einsum("sd,dhk->shk", h, a["k"]["kernel"])
    v = ops.einsum("sd,dhk->shk", h, a["v"]["kernel"])
    q = rope(rms_norm(q, a["q_norm"]["scale"], eps), positions, theta)
    k = rope(rms_norm(k, a["k_norm"]["scale"], eps), positions, theta)
    heads, hd = q.shape[1], q.shape[2]
    group = heads // k.shape[1]
    mask = allowed(length, block)

    @jax.checkpoint  # keep a head's scores only while its own gradient is taken
    def head(qkv):
        qh, kh, vh = qkv                                   # (S, D) each
        scores = ops.einsum("qd,kd->qk", qh, kh) / jnp.sqrt(jnp.float32(hd))
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return ops.einsum("qk,kd->qd", probs, vh)

    per_head = lambda x: jnp.repeat(jnp.moveaxis(x, 1, 0), group, axis=0)
    out = jax.lax.map(head, (jnp.moveaxis(q, 1, 0), per_head(k), per_head(v)))
    return ops.einsum("hsk,hkd->sd", out, a["o"]["kernel"])


def _route(ops, m, z, top_k):
    """z (T, D) -> gates (T, E), chosen weights (T, k), chosen ids (T, k)."""
    logits = ops.einsum("td,de->te", z, m["router"]["kernel"])
    gates = jax.nn.softmax(logits, axis=-1)
    _, index = jax.lax.top_k(logits, top_k)
    chosen = jnp.take_along_axis(gates, index, axis=-1)
    return gates, chosen / jnp.sum(chosen, axis=-1, keepdims=True), index


def _experts(ops, m, z, top_k, first):
    """One chip's batch: z (T, D) -> (the held experts' part of the routed
    sum, the layer's auxiliary loss)."""
    gates, weight, index = _route(ops, m, z, top_k)
    n_router = gates.shape[-1]
    counts = jnp.sum(jax.nn.one_hot(index, n_router, dtype=jnp.float32), axis=(0, 1))
    share = jax.lax.stop_gradient(counts / (top_k * z.shape[0]))
    aux = n_router * jnp.sum(share * jnp.mean(gates, axis=0))

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
    return y, aux


def _sublayers(ops, p, x, eps, theta, block, rows_per_chip):
    """x (R, 2L, D) -> (h' after attention, z = n2(h') by chip: (R / rows_per_
    chip, rows_per_chip x 2L, D))."""
    attend = jax.checkpoint(lambda r: _attention_row(
        ops, p["attn"], rms_norm(r, p["ln1"]["scale"], eps), eps, theta, block))
    x = x + jax.lax.map(attend, x)
    z = rms_norm(x, p["ln2"]["scale"], eps)
    return x, z.reshape(x.shape[0] // rows_per_chip, -1, x.shape[-1])


def layer(ops, ps, carry, eps, theta, block, top_k, first, rows_per_chip):
    """One layer over a ``Carry``.  Rows meet only in the router's counts,
    and there only the rows of one chip's batch."""
    (p,) = ps
    x, z = _sublayers(ops, p, carry.h, eps, theta, block, rows_per_chip)
    y, aux = jax.lax.map(lambda zc: _experts(ops, p["moe"], zc, top_k, first), z)
    return Carry(x + y.reshape(x.shape), carry.aux + jnp.mean(aux))


def layer_chosen(ops, ps, carry, eps, theta, block, top_k, first, rows_per_chip):
    """The experts the layer's router chooses: (chips, T, k)."""
    (p,) = ps
    _, z = _sublayers(ops, p, carry.h, eps, theta, block, rows_per_chip)
    return jax.lax.map(lambda zc: _route(ops, p["moe"], zc, top_k)[2], z)


class RoutedLayer(Stage):
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
    """Summed weighted cross-entropy of a block of rows: x (R, D)."""
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
    assigned, loads = 0, []
    for index in REFERENCE_ROUTING.values():       # (chips, T, k) a layer
        for chip in index:
            counts = np.array([(chip == first + e).sum() for e in range(held)])
            assigned += int(counts.sum())
            loads.append(counts.max() / max(counts.mean(), 1e-9))
    chips = next(iter(REFERENCE_ROUTING.values())).shape[0]
    return (f"# routing (the reference's own, first step): assignments to held experts a chip "
            f"{assigned / chips:.0f} over {len(REFERENCE_ROUTING)} layers, largest held "
            f"expert's load over the mean {max(loads):.3f}")


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
        b, length = targets.shape
        d = carry.h.shape[-1]
        rows = carry.h[:, :length].reshape(b * length, d)     # the noisy half only
        targets, weights = targets.reshape(-1), weights.reshape(-1).astype(jnp.float32)
        fn = _block_loss_backward(ops.precision, self.eps)
        n = b * length
        total, dps, dxs = 0.0, None, []
        for lo in range(0, n, LOSS_ROWS):
            hi = lo + LOSS_ROWS
            loss, dp, dx = fn(ps, rows[lo:hi], targets[lo:hi], weights[lo:hi])
            total = total + loss
            dps = dp if dps is None else _add(dps, dp)
            dxs.append(dx)
        scale = 1.0 / n
        dps = jax.tree_util.tree_map(lambda g: g * scale, dps)
        dh = (jnp.concatenate(dxs) * scale).reshape(b, length, d)
        dh = jnp.concatenate([dh, jnp.zeros_like(dh)], axis=1)
        aux_scale = self.coef / self.layers
        return (total * scale + aux_scale * carry.aux, dps,
                Carry(dh, jnp.full((), aux_scale, jnp.float32)))


def build(config: dict, traffic: dict):
    """(stages, loss_backward) for the configuration."""
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    layers = config["num_hidden_layers"]
    static = (eps, theta, int(traffic["block_length"]), config["num_experts_per_tok"],
              config["held_experts_first"], int(traffic["samples_per_chip"]))
    REFERENCE_ROUTING.clear()
    stages = [Stage(("embed",), embed)]
    stages += [RoutedLayer(i, static) for i in range(layers)]
    return stages, LossBackward(eps, float(config["router_aux_loss_coef"]), layers,
                                config["held_experts_first"], config["num_experts"])
