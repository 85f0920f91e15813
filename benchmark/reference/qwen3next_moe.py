"""Plain reference: a causal decoder whose layers mix tokens by Gated DeltaNet
(three of four) or by gated softmax attention (the fourth), each followed by a
softmax router over SwiGLU experts beside one gated shared expert; float32.

Qwen3-Next-80B-A3B-Instruct's language model (Hugging Face ``qwen3_next``;
Gated DeltaNet: Yang et al., arXiv:2412.06464).  The equations, which the
program computes too; ``x`` (T, D) is one sequence of the residual stream.

Norms.  ``n(x) = x / rms(x) * (1 + w)`` (zero-centred) for ``ln1``, ``ln2``,
``ln_f`` and the q / k norms; Gated DeltaNet's own norm is ``x / rms(x) * w``.

Layer ``i`` (from 0) is ``full`` when ``(i + 1) % full_attention_interval == 0``, else
``linear``; ``x += mixer(n1(x))``, then ``x += moe(n2(x))``.

Gated DeltaNet, ``z = n1(x)``:  ``[q | k | v | gate] = z W_qkvz`` (q, k: ``Hk``
heads of ``dk``; v, gate: ``Hv`` heads of ``dv``), ``[b | a] = z W_ba`` (``Hv``
each);  ``c_t = silu(sum_{j<K} w_conv[j] * u_{t-(K-1)+j})`` a channel of ``u =
[q | k | v]``, zeros before the sequence (four shifted multiply-adds);  q, k
divided by ``sqrt(sum of squares + 1e-6)`` a head, q times ``dk ** -0.5``, each
key head repeated to its ``Hv / Hk`` value heads;  ``beta_t = sigmoid(b_t)``,
``g_t = -exp(A_log) softplus(a_t + dt_bias)``;  then TOKEN BY TOKEN, a value
head's state ``S`` (dk x dv) from zero:

    S <- exp(g_t) S;  d_t = beta_t (v_t - S^T k_t);  S <- S + k_t d_t^T;
    o_t = S^T q_t

(``lax.scan`` over the tokens, in segments kept by ``jax.checkpoint`` so that
the backward holds one segment's states: no chunked form, no triangular solve);
``out = (o / rms(o) * w_norm * silu(gate)) W_out``.

Gated attention, ``z = n1(x)``:  ``[query | gate]_h = z W_q`` a head (``hd``
each), ``k, v = z W_k, z W_v`` (``Hkv`` heads); ``query, k <- n(query), n(k)``
over the head; RoPE (split-half pairs) on the first ``partial_rotary_factor x
hd`` columns, the rest pass; causal softmax of ``query . k / sqrt(hd)``, query
head ``h`` reading key/value head ``h // (H / Hkv)``; ``out = (attn *
sigmoid(gate)) W_o``.

Feed-forward, ``z = n2(x)``:  ``p = softmax(z W_r)`` over all the router's
experts; the ``num_experts_per_tok`` largest chosen and renormalised over the
chosen; ``y = sum over chosen AND held e of w_e SwiGLU_e(z) + sigmoid(z w_sg)
SwiGLU_shared(z)``.  What the absent experts would add is left out.  Auxiliary
loss a layer: ``E sum_e (n_e / (k T)) mean_T p_e`` over the T rows of one chip's
batch, no gradient through the counts; mean over the layers.

Head and loss.  ``logits = n_f(x) W_head``; loss = mean over every position of
the cross-entropy against the next token (the labels) plus
``router_aux_loss_coef`` x the auxiliary loss.

Laid out to fit: a sequence at a time; attention one query head at a time (the
8,192 x 8,192 scores of 16 heads are 4.3 GB whole); the experts one at a time (a
masked dense product over the held experts: no sort, no kernel); the loss one
block of ``LOSS_ROWS`` positions at a time.  Between stages goes a ``Carry``:
the activations and the auxiliary loss summed so far.  The parameter tree is
addressed by the names of the program's (``embed``, ``layer_<i>/{ln1,
linear_attn/{in_proj_qkvz, in_proj_ba, conv_kernel, A_log, dt_bias, norm,
out_proj} | attn/{q, k, v, o, q_norm, k_norm}, ln2, moe/{router, w_gate, w_up,
w_down}, shared_experts/{gate, up, down}, shared_expert_gate}``, ``ln_f``,
``head``).

Nothing of the program is imported and its routing is never used: the reference
routes by its own float32 router, and prints what it chose at its first step
and how slowly its heads forget (``exp(g_t)``: a state gone within one of the
program's chunks would let a wrong carry pass).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import chain
from .chain import Ops, Stage

LOSS_ROWS = 1024
# tokens a checkpointed segment of the recurrence (only what the backward keeps)
SEGMENT = 128

# the reference's own readings at its first step, by layer
REFERENCE_ROUTING = {}    # the chosen experts: (chips, T, k)
REFERENCE_DECAYS = {}     # exp(g_t) of a linear layer: (R, T, Hv)


@jax.tree_util.register_pytree_node_class
class Carry:
    """What goes from stage to stage: the activations (R, T, D) and the
    auxiliary loss summed over the layers so far."""

    def __init__(self, h, aux):
        self.h, self.aux = h, aux

    dtype = property(lambda self: self.h.dtype)

    def tree_flatten(self):
        return (self.h, self.aux), None

    @classmethod
    def tree_unflatten(cls, _, children):
        return cls(*children)


def _unit_rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def norm(x, w, eps):
    """The zero-centred form: ``w`` is the distance from one."""
    return _unit_rms(x, eps) * (1.0 + w)


def rope(x, theta):
    """x: (T, ..., R); position t rotates pair (x[i], x[i + R/2]) by
    t * theta^(-2i/R)."""
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


# -- Gated DeltaNet -------------------------------------------------------------


def causal_conv(u, w):
    """u (T, C), w (K, C): ``silu(sum_j w[j] * u[t - (K - 1) + j])``."""
    taps, t = w.shape[0], u.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1]), u.dtype), u])
    out = w[0] * padded[:t]
    for j in range(1, taps):
        out = out + w[j] * padded[j:j + t]
    return jax.nn.silu(out)


def delta_rule(ops, q, k, v, g, beta):
    """The recurrence, token by token: q, k (T, H, dk), v (T, H, dv), g, beta
    (T, H) -> o (T, H, dv).  Segments of ``SEGMENT`` tokens under
    ``jax.checkpoint``: the backward keeps a state a segment and one segment's
    states, not a state a token."""
    t, h, dk = q.shape
    dv = v.shape[-1]
    pad = (-t) % SEGMENT
    if pad:  # g = 0, beta = 0: the state passes through; the outputs are cut
        q, k, v, g, beta = (jnp.concatenate(
            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)]) for x in (q, k, v, g, beta))

    def token(s, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        s = s * jnp.exp(g_t)[:, None, None]
        d = beta_t[:, None] * (v_t - ops.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * d[:, None, :]
        return s, ops.einsum("hkv,hk->hv", s, q_t)

    @jax.checkpoint
    def segment(s, xs):
        return jax.lax.scan(token, s, xs)

    split = lambda x: x.reshape(-1, SEGMENT, *x.shape[1:])
    _, o = jax.lax.scan(segment, jnp.zeros((h, dk, dv), jnp.float32),
                        tuple(map(split, (q, k, v, g, beta))))
    return o.reshape(-1, h, dv)[:t]


def gate_values(a, ba, hv):
    """[b | a] (T, 2 Hv) -> beta, g (T, Hv)."""
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(a["A_log"]) * jax.nn.softplus(ba[:, hv:] + a["dt_bias"])
    return beta, g


def linear_mixer(ops, a, z, eps, hk, dk, hv, dv):
    """One sequence: z (T, D), normed -> Gated DeltaNet's output."""
    t = z.shape[0]
    key_dim, value_dim = hk * dk, hv * dv
    qkvz = ops.einsum("td,df->tf", z, a["in_proj_qkvz"]["kernel"])
    ba = ops.einsum("td,df->tf", z, a["in_proj_ba"]["kernel"])
    mixed = causal_conv(qkvz[:, :2 * key_dim + value_dim], a["conv_kernel"])
    gate = qkvz[:, 2 * key_dim + value_dim:].reshape(t, hv, dv)
    q = mixed[:, :key_dim].reshape(t, hk, dk)
    k = mixed[:, key_dim:2 * key_dim].reshape(t, hk, dk)
    v = mixed[:, 2 * key_dim:].reshape(t, hv, dv)
    unit = lambda y: y * jax.lax.rsqrt(jnp.sum(jnp.square(y), axis=-1, keepdims=True) + 1e-6)
    q = jnp.repeat(unit(q) * dk ** -0.5, hv // hk, axis=1)
    k = jnp.repeat(unit(k), hv // hk, axis=1)
    beta, g = gate_values(a, ba, hv)
    o = delta_rule(ops, q, k, v, g, beta)
    o = _unit_rms(o, eps) * a["norm"]["scale"] * jax.nn.silu(gate)
    return ops.einsum("tf,fd->td", o.reshape(t, value_dim), a["out_proj"]["kernel"])


def linear_decays(ops, a, z, hv):
    """exp(g_t) (T, Hv) of one sequence: what the layer's heads keep a token."""
    ba = ops.einsum("td,df->tf", z, a["in_proj_ba"]["kernel"])
    return jnp.exp(gate_values(a, ba, hv)[1])


# -- gated attention ------------------------------------------------------------


def attention(ops, a, z, eps, theta, rotary):
    """One sequence: z (T, D), normed -> the gated attention's output."""
    t = z.shape[0]
    qg = ops.einsum("td,dhk->thk", z, a["q"]["kernel"])          # (T, H, 2 hd)
    hd = qg.shape[-1] // 2
    q, gate = qg[..., :hd], qg[..., hd:]
    k = ops.einsum("td,dhk->thk", z, a["k"]["kernel"])           # (T, Hkv, hd)
    v = ops.einsum("td,dhk->thk", z, a["v"]["kernel"])
    q = norm(q, a["q_norm"]["scale"], eps)
    k = norm(k, a["k_norm"]["scale"], eps)
    turn = lambda y: jnp.concatenate([rope(y[..., :rotary], theta), y[..., rotary:]], axis=-1)
    q, k = turn(q), turn(k)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint  # keep a head's scores only while its own gradient is taken
    def head(parts):
        qh, kh, vh = parts                                        # (T, hd) each
        scores = ops.einsum("qd,kd->qk", qh, kh) * scale
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return ops.einsum("qk,kd->qd", probs, vh)

    by_head = lambda x: jnp.moveaxis(x, 1, 0)
    out = jax.lax.map(head, (by_head(q), by_head(k), by_head(v)))  # (H, T, hd)
    out = out * jax.nn.sigmoid(by_head(gate))
    return ops.einsum("htk,hkd->td", out, a["o"]["kernel"])


# -- the routed feed-forward ----------------------------------------------------


def route(ops, m, z, top_k):
    """z (T, D) -> gates (T, E), chosen weights (T, k), chosen ids (T, k)."""
    logits = ops.einsum("td,de->te", z, m["router"]["kernel"])
    probs = jax.nn.softmax(logits, axis=-1)
    _, index = jax.lax.top_k(logits, top_k)
    chosen = jnp.take_along_axis(probs, index, axis=-1)
    return probs, chosen / jnp.sum(chosen, axis=-1, keepdims=True), index


def feed_forward(ops, p, z, top_k, first):
    """One chip's rows: z (T, D) -> (the held experts' part of the routed sum
    plus the gated shared expert, the layer's auxiliary loss)."""
    m = p["moe"]
    probs, weight, index = route(ops, m, z, top_k)
    n_router = probs.shape[-1]
    counts = jnp.zeros((n_router,), jnp.float32).at[index.reshape(-1)].add(1.0)
    share = jax.lax.stop_gradient(counts / (top_k * z.shape[0]))
    aux = n_router * jnp.sum(share * jnp.mean(probs, axis=0))

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
    shared = swiglu(ops, p["shared_experts"], z)
    if "shared_expert_gate" in p:
        shared = shared * jax.nn.sigmoid(
            ops.einsum("td,do->to", z, p["shared_expert_gate"]["kernel"]))
    return y + shared, aux


# -- layers ---------------------------------------------------------------------


def _mix(ops, p, x, linear, eps, theta, rotary, sizes):
    """x (R, T, D) -> x + the layer's mixer, one sequence at a time."""
    if linear:
        one = lambda r: linear_mixer(ops, p["linear_attn"],
                                     norm(r, p["ln1"]["scale"], eps), eps, *sizes)
    else:
        one = lambda r: attention(ops, p["attn"], norm(r, p["ln1"]["scale"], eps),
                                  eps, theta, rotary)
    return x + jax.lax.map(jax.checkpoint(one), x)


def _by_chip(z, rows_per_chip):
    return z.reshape(z.shape[0] // rows_per_chip, -1, z.shape[-1])


def layer(ops, ps, carry, linear, eps, theta, rotary, sizes, top_k, first, rows_per_chip):
    """One layer over a ``Carry``.  Rows meet only in the router's counts, and
    there only the rows of one chip's batch."""
    (p,) = ps
    x = _mix(ops, p, carry.h, linear, eps, theta, rotary, sizes)
    z = _by_chip(norm(x, p["ln2"]["scale"], eps), rows_per_chip)
    y, aux = jax.lax.map(lambda zc: feed_forward(ops, p, zc, top_k, first), z)
    return Carry(x + y.reshape(x.shape), carry.aux + jnp.mean(aux))


def layer_readings(ops, ps, carry, linear, eps, theta, rotary, sizes, top_k, first,
                   rows_per_chip):
    """(the experts the layer's router chooses (chips, T, k); a linear layer's
    exp(g_t) (R, T, Hv), else an empty array)."""
    (p,) = ps
    if linear:
        decays = jax.lax.map(lambda r: linear_decays(
            ops, p["linear_attn"], norm(r, p["ln1"]["scale"], eps), sizes[2]), carry.h)
    else:
        decays = jnp.zeros((0,), jnp.float32)
    x = _mix(ops, p, carry.h, linear, eps, theta, rotary, sizes)
    z = _by_chip(norm(x, p["ln2"]["scale"], eps), rows_per_chip)
    return jax.lax.map(lambda zc: route(ops, p["moe"], zc, top_k)[2], z), decays


class Layer(Stage):
    """A layer stage that also keeps, at its first forward pass, what its
    router chose and how its heads forget (a second, forward-only pass)."""

    def __init__(self, index: int, static: tuple):
        super().__init__((f"layer_{index}",), layer, static)
        self.index = index

    def forward(self, ops: Ops, ps, x):
        if self.index not in REFERENCE_ROUTING:
            chosen, decays = chain._forward(layer_readings, self.static, ops.precision)(ps, x)
            REFERENCE_ROUTING[self.index] = np.asarray(chosen)
            if decays.size:
                REFERENCE_DECAYS[self.index] = np.asarray(decays)
        return super().forward(ops, ps, x)


# -- head, loss -------------------------------------------------------------------


def _block_loss(ops, ps, x, labels, eps):
    """Summed cross-entropy of a block of rows: x (R, D), labels (R,)."""
    ln_f, head = ps
    logits = ops.einsum("rd,dv->rv", norm(x, ln_f["scale"], eps), head["kernel"])
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


def readings_report(first: int, held: int) -> str:
    """What the reference's own router chose and its heads kept at its first
    step."""
    if not REFERENCE_ROUTING:
        return "# routing: the reference kept none"
    per_layer, loads = [], []
    for index in REFERENCE_ROUTING.values():       # (chips, T, k) a layer
        counts = np.array([(index == first + e).sum() for e in range(held)])
        per_layer.append(int(counts.sum()))
        loads.append(counts.max() / max(counts.mean(), 1e-9))
    text = (f"# routing (the reference's own, first step): assignments to held experts a "
            f"layer {per_layer} over {len(per_layer)} layers, largest held expert's load "
            f"over the mean {max(loads):.3f}")
    for i, decays in REFERENCE_DECAYS.items():     # (R, T, Hv)
        by_head = decays.reshape(-1, decays.shape[-1]).mean(axis=0)
        slow = int(((by_head >= 0.9) & (by_head <= 0.9999)).sum())
        text += (f"\n# decays (layer {i}, exp(g_t), mean over tokens a head): smallest "
                 f"{by_head.min():.5f}, median {np.median(by_head):.5f}, largest "
                 f"{by_head.max():.5f}; {slow} of {by_head.size} heads in [0.9, 0.9999]; "
                 f"a token's smallest {decays.min():.5f}")
    return text


class LossBackward:
    keys = ("ln_f", "head")

    def __init__(self, eps, coef, layers, first, held):
        self.eps, self.coef, self.layers = eps, coef, layers
        self.first, self.held = first, held
        self.reported = False

    def __call__(self, ops, ps, carry, labels):
        if not self.reported:
            self.reported = True
            print(readings_report(self.first, self.held), flush=True)
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
        aux_scale = self.coef / self.layers
        return (total * scale + aux_scale * carry.aux, dps,
                Carry(dh, jnp.full((), aux_scale, jnp.float32)))


def build(config: dict, traffic: dict):
    """(stages, loss_backward) for the configuration."""
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    layers = config["num_hidden_layers"]
    rotary = int(config["head_dim"] * config["partial_rotary_factor"])
    sizes = (config["linear_num_key_heads"], config["linear_key_head_dim"],
             config["linear_num_value_heads"], config["linear_value_head_dim"])
    static = (eps, theta, rotary, sizes, config["num_experts_per_tok"],
              config["held_experts_first"], traffic["samples_per_chip"])
    interval = config["full_attention_interval"]
    REFERENCE_ROUTING.clear()
    REFERENCE_DECAYS.clear()
    stages = [Stage(("embed",), embed)] + [
        Layer(i, ((i + 1) % interval != 0,) + static) for i in range(layers)]
    return stages, LossBackward(eps, float(config["router_aux_loss_coef"]), layers,
                                config["held_experts_first"], config["num_experts"])
