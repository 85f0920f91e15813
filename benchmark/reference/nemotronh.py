"""Plain reference: a causal decoder whose every layer is ONE sublayer under one
norm: a Mamba-2 mixer, a router over squared-ReLU experts in a latent beside a
shared expert, or softmax attention without positions; float32.

NVIDIA-Nemotron-3-Super-120B-A12B (Hugging Face ``nemotron_h``; Mamba-2: Dao
and Gu, arXiv:2405.21060).  The equations, which the program computes too; ``x``
(T, D) is one sequence of the residual stream, ``D`` 4,096.

Every layer ``i``:  ``x <- x + f_i(n_i(x))``, ``n(x) = x / rms(x) * w`` (eps
``norm_eps``), ``f_i`` the one sublayer that ``hybrid_override_pattern[i]``
names (the configuration's layers are the slice ``hybrid_override_layers`` of the
published pattern).

``M`` (Mamba-2), ``u = n(x)``, ``H`` heads of ``P``, ``G`` groups, state ``N``,
``K`` taps:  ``[z | xBC | dt] = u W_in`` (``H P | H P + 2 G N | H`` columns, no
bias);  ``xBC <- silu(sum_{j<K} w_conv[j] * xBC_{t-(K-1)+j} + b_conv)`` a
channel, zeros before the sequence; split ``x (H, P)``, ``B (G, N)``, ``C (G,
N)``;  ``dt <- softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head;  then TOKEN
BY TOKEN, a head's state ``S`` (N x P) from zero, head ``h`` reading group ``h
// (H / G)``:

    S <- exp(dt_t A) S + dt_t B_t x_t^T;        y_t = S^T C_t + D x_t

(``lax.scan`` over the tokens, in segments kept by ``jax.checkpoint`` so that
the backward holds one segment's states: no chunked form);  ``y <- (y * silu(z))
/ rms over each group's H P / G columns * w_norm``;  ``out = y W_out``.

``E`` (LatentMoE), ``u = n(x)``:  ``g = sigmoid(u W_r)`` over all the router's
experts (it reads the D-wide ``u``);  the ``num_experts_per_tok`` largest of
``g + b`` chosen (``b``, the selection bias, is no parameter and zero here: the
program keeps it in ``batch_stats``, zeros at the first steps, and has no update
rule for it yet), weights ``g / (sum over the chosen of g + 1e-20) x
routed_scaling_factor``; ``n_group`` 1, ``topk_group`` 1: no group limit;  ``l
= u W_down`` (D -> ``moe_latent_size``);  an expert is ``down_e(relu(up_e
l)^2)``, no gate matrix;  ``out = (sum over chosen AND held e of w_e
expert_e(l)) W_up + down_s(relu(up_s u)^2)``, the shared expert whole.  What
the absent experts would add is left out.  Auxiliary loss a layer: ``E sum_e
(n_e / (k T)) mean_T g_e`` over the T rows of one chip's batch, no gradient
through the counts; mean over the ``E`` layers.

``*`` (attention), ``u = n(x)``:  ``q, k, v = u W_q, u W_k, u W_v`` (``H`` query
heads over ``Hkv`` key/value heads of ``hd``), NO rotation (``assumed.rope``),
causal softmax of ``q . k / sqrt(hd)``, query head ``h`` reading key/value head
``h // (H / Hkv)``;  ``out = attn W_o``.

``-``: ``down(relu(up u)^2)``.

Head and loss.  ``logits = n_f(x) W_head``; loss = mean over every position of
the cross-entropy against the next token (the labels) plus
``router_aux_loss_coef`` x the auxiliary loss.

**The share.**  The configuration is one head-parallel rank of eight inside one
expert-parallel rank: the mixers are built at the rank's heads and groups (a
Mamba-2 layer at ``mamba_num_heads`` heads of ``n_groups`` groups, attention at
``num_attention_heads`` over ``num_key_value_heads``), ``out_proj`` / ``o`` give
the rank's summand of the uncut layer's output, and that partial result goes on
to the next layer, here as in the program: nothing stands in for the absent
ranks (``tests/test_nemotronh.py`` holds the eight shares' sum to the uncut
layer).

Laid out to fit: a sequence at a time; attention one query head at a time; the
experts one at a time (a masked dense product over the held experts: no sort,
no kernel); the loss one block of ``LOSS_ROWS`` positions at a time.  Between
stages goes a ``Carry``: the activations and the auxiliary loss summed so far.
The parameter tree is addressed by the names of the program's (``embed``,
``layer_<i>/{norm, mixer/{in_proj, conv_kernel, conv_bias, A_log, D, dt_bias,
norm, out_proj} | attn/{q, k, v, o} | moe/{router, latent_down, latent_up, w_up,
w_down}, shared_experts/{up, down} | mlp/{up, down}}``, ``ln_f``, ``head``).

Nothing of the program is imported and its routing is never used: the reference
routes by its own float32 router, and prints what it chose at its first step
and how slowly its heads forget (``exp(dt_t A)``: a state gone within one of the
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
REFERENCE_DECAYS = {}     # exp(dt_t A) of a Mamba-2 layer: (R, T, H)


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
    return _unit_rms(x, eps) * w


def relu2(ops, m, z):
    """``down(relu(up z)^2)``: the feed-forward without a gate matrix."""
    hidden = jnp.square(jax.nn.relu(ops.einsum("td,df->tf", z, m["up"]["kernel"])))
    return ops.einsum("tf,fd->td", hidden, m["down"]["kernel"])


def embed(ops, ps, tokens):
    (p,) = ps
    return Carry(p["embedding"][tokens], jnp.zeros((), jnp.float32))


# -- Mamba-2 ----------------------------------------------------------------------


def causal_conv(u, w, bias):
    """u (T, C), w (K, C), bias (C,): ``silu(sum_j w[j] * u[t - (K - 1) + j] +
    bias)``."""
    taps, t = w.shape[0], u.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1]), u.dtype), u])
    out = w[0] * padded[:t]
    for j in range(1, taps):
        out = out + w[j] * padded[j:j + t]
    return jax.nn.silu(out + bias)


def state_scan(ops, x, dt, a, b, c):
    """The recurrence, token by token: x (T, H, P), dt (T, H), a (H,), b, c (T,
    H, N) -> y (T, H, P).  Segments of ``SEGMENT`` tokens under
    ``jax.checkpoint``: the backward keeps a state a segment and one segment's
    states, not a state a token."""
    t, h, p = x.shape
    n = b.shape[-1]
    pad = (-t) % SEGMENT
    if pad:  # dt = 0: the state passes through; the outputs are cut
        x, dt, b, c = (jnp.concatenate(
            [v, jnp.zeros((pad,) + v.shape[1:], v.dtype)]) for v in (x, dt, b, c))

    def token(s, xs):
        x_t, dt_t, b_t, c_t = xs
        s = (s * jnp.exp(dt_t * a)[:, None, None]
             + (dt_t[:, None] * b_t)[:, :, None] * x_t[:, None, :])
        return s, ops.einsum("hnp,hn->hp", s, c_t)

    @jax.checkpoint
    def segment(s, xs):
        return jax.lax.scan(token, s, xs)

    split = lambda v: v.reshape(-1, SEGMENT, *v.shape[1:])
    _, y = jax.lax.scan(segment, jnp.zeros((h, n, p), jnp.float32),
                        tuple(map(split, (x, dt, b, c))))
    return y.reshape(-1, h, p)[:t]


def _steps(a, raw):
    """dt (T, H) and A (H,) from the projection's last ``H`` columns."""
    return jax.nn.softplus(raw + a["dt_bias"]), -jnp.exp(a["A_log"])


def mamba_mixer(ops, a, u, eps, h, p, g, n):
    """One sequence: u (T, D), normed -> the Mamba-2 mixer's output."""
    t = u.shape[0]
    inner = h * p
    proj = ops.einsum("td,df->tf", u, a["in_proj"]["kernel"])
    z, mixed, raw = proj[:, :inner], proj[:, inner:2 * inner + 2 * g * n], proj[:, -h:]
    mixed = causal_conv(mixed, a["conv_kernel"], a["conv_bias"])
    x = mixed[:, :inner].reshape(t, h, p)
    to_heads = lambda v: jnp.repeat(v.reshape(t, g, n), h // g, axis=1)
    b = to_heads(mixed[:, inner:inner + g * n])
    c = to_heads(mixed[:, inner + g * n:])
    dt, a_neg = _steps(a, raw)
    y = state_scan(ops, x, dt, a_neg, b, c) + a["D"][:, None] * x
    gated = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(t, g, inner // g)
    y = _unit_rms(gated, eps).reshape(t, inner) * a["norm"]["scale"]
    return ops.einsum("tf,fd->td", y, a["out_proj"]["kernel"])


def mamba_decays(ops, a, u, h):
    """exp(dt_t A) (T, H) of one sequence: what the layer's heads keep a token."""
    raw = ops.einsum("td,df->tf", u, a["in_proj"]["kernel"][:, -h:])
    dt, a_neg = _steps(a, raw)
    return jnp.exp(dt * a_neg)


# -- attention --------------------------------------------------------------------


def attention(ops, a, u):
    """One sequence: u (T, D), normed -> the attention's output; no rotation."""
    t = u.shape[0]
    q = ops.einsum("td,dhk->thk", u, a["q"]["kernel"])           # (T, H, hd)
    k = ops.einsum("td,dhk->thk", u, a["k"]["kernel"])           # (T, Hkv, hd)
    v = ops.einsum("td,dhk->thk", u, a["v"]["kernel"])
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint  # keep a head's scores only while its own gradient is taken
    def head(parts):
        qh, kh, vh = parts                                        # (T, hd) each
        scores = ops.einsum("qd,kd->qk", qh, kh) * scale
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return ops.einsum("qk,kd->qd", probs, vh)

    by_head = lambda x: jnp.moveaxis(x, 1, 0)
    out = jax.lax.map(head, (by_head(q), by_head(k), by_head(v)))  # (H, T, hd)
    return ops.einsum("htk,hkd->td", out, a["o"]["kernel"])


# -- the routed feed-forward ------------------------------------------------------


def route(ops, m, u, top_k, scaling):
    """u (T, D) -> gates (T, E), chosen weights (T, k), chosen ids (T, k)."""
    gates = jax.nn.sigmoid(ops.einsum("td,de->te", u, m["router"]["kernel"]))
    _, index = jax.lax.top_k(gates, top_k)
    chosen = jnp.take_along_axis(gates, index, axis=-1)
    weight = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20) * scaling
    return gates, weight, index


def feed_forward(ops, p, u, top_k, first, scaling):
    """One chip's rows: u (T, D) -> (the held experts' part of the routed sum
    through the latent plus the shared expert, the layer's auxiliary loss)."""
    m = p["moe"]
    gates, weight, index = route(ops, m, u, top_k, scaling)
    n_router = gates.shape[-1]
    counts = jnp.zeros((n_router,), jnp.float32).at[index.reshape(-1)].add(1.0)
    share = jax.lax.stop_gradient(counts / (top_k * u.shape[0]))
    aux = n_router * jnp.sum(share * jnp.mean(gates, axis=0))
    latent = ops.einsum("td,dl->tl", u, m["latent_down"]["kernel"])

    @jax.checkpoint
    def one(y, expert):
        e, w_up, w_down = expert
        w = jnp.sum(jnp.where(index == first + e, weight, 0.0), axis=-1)
        hidden = jnp.square(jax.nn.relu(ops.einsum("tl,lf->tf", latent, w_up)))
        return y + w[:, None] * ops.einsum("tf,fl->tl", hidden, w_down), None

    held = m["w_up"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(latent),
                        (jnp.arange(held), m["w_up"], m["w_down"]))
    y = ops.einsum("tl,ld->td", y, m["latent_up"]["kernel"])
    return y + relu2(ops, p["shared_experts"], u), aux


# -- layers -----------------------------------------------------------------------


def _by_chip(z, rows_per_chip):
    return z.reshape(z.shape[0] // rows_per_chip, -1, z.shape[-1])


def layer(ops, ps, carry, kind, eps, sizes, top_k, first, scaling, rows_per_chip):
    """One layer over a ``Carry``: ``x + f(n(x))`` with the one ``f`` that
    ``kind`` (the pattern's letter) names.  Rows meet only in the router's
    counts, and there only the rows of one chip's batch."""
    (p,) = ps
    x = carry.h
    normed = lambda r: norm(r, p["norm"]["scale"], eps)
    if kind == "E":
        u = _by_chip(normed(x), rows_per_chip)
        y, aux = jax.lax.map(
            lambda uc: feed_forward(ops, p, uc, top_k, first, scaling), u)
        return Carry(x + y.reshape(x.shape), carry.aux + jnp.mean(aux))
    if kind == "M":
        one = lambda r: mamba_mixer(ops, p["mixer"], normed(r), eps, *sizes)
    elif kind == "*":
        one = lambda r: attention(ops, p["attn"], normed(r))
    else:
        one = lambda r: relu2(ops, p["mlp"], normed(r))
    return Carry(x + jax.lax.map(jax.checkpoint(one), x), carry.aux)


def layer_readings(ops, ps, carry, kind, eps, sizes, top_k, first, scaling,
                   rows_per_chip):
    """The experts an ``E`` layer's router chooses (chips, T, k), or an ``M``
    layer's exp(dt_t A) (R, T, H)."""
    (p,) = ps
    normed = lambda r: norm(r, p["norm"]["scale"], eps)
    if kind == "E":
        u = _by_chip(normed(carry.h), rows_per_chip)
        return jax.lax.map(lambda uc: route(ops, p["moe"], uc, top_k, scaling)[2], u)
    return jax.lax.map(lambda r: mamba_decays(ops, p["mixer"], normed(r), sizes[0]),
                       carry.h)


class Layer(Stage):
    """A layer stage that also keeps, at its first forward pass, what its
    router chose or how its heads forget (a second, forward-only pass)."""

    def __init__(self, index: int, static: tuple):
        super().__init__((f"layer_{index}",), layer, static)
        self.index, self.kind = index, static[0]

    def forward(self, ops: Ops, ps, x):
        kept = {"E": REFERENCE_ROUTING, "M": REFERENCE_DECAYS}.get(self.kind)
        if kept is not None and self.index not in kept:
            read = chain._forward(layer_readings, self.static, ops.precision)(ps, x)
            kept[self.index] = np.asarray(read)
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
    """What the reference's own routers chose and its heads kept at its first
    step."""
    if not REFERENCE_ROUTING and not REFERENCE_DECAYS:
        return "# routing: the reference kept none"
    per_layer, loads = [], []
    for index in REFERENCE_ROUTING.values():       # (chips, T, k) a layer
        counts = np.array([(index == first + e).sum() for e in range(held)])
        per_layer.append(int(counts.sum()))
        loads.append(counts.max() / max(counts.mean(), 1e-9))
    text = (f"# routing (the reference's own, first step): assignments to held experts a "
            f"layer {per_layer} over {len(per_layer)} layers, largest held expert's load "
            f"over the mean {max(loads, default=0.0):.3f}")
    for i, decays in REFERENCE_DECAYS.items():     # (R, T, H)
        by_head = decays.reshape(-1, decays.shape[-1]).mean(axis=0)
        slow = int(((by_head >= 0.9) & (by_head <= 0.9999)).sum())
        text += (f"\n# decays (layer {i}, exp(dt_t A), mean over tokens a head): smallest "
                 f"{by_head.min():.5f}, median {np.median(by_head):.5f}, largest "
                 f"{by_head.max():.5f}; {slow} of {by_head.size} heads in [0.9, 0.9999]; "
                 f"a token's smallest {decays.min():.5f}")
    return text


class LossBackward:
    keys = ("ln_f", "head")

    def __init__(self, eps, coef, routed_layers, first, held):
        self.eps, self.coef, self.routed_layers = eps, coef, routed_layers
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
        aux_scale = self.coef / max(self.routed_layers, 1)
        return (total * scale + aux_scale * carry.aux, dps,
                Carry(dh, jnp.full((), aux_scale, jnp.float32)))


def build(config: dict, traffic: dict):
    """(stages, loss_backward) for the configuration."""
    eps = float(config["norm_eps"])
    lo, hi = config.get("hybrid_override_layers", (0, None))
    pattern = config["hybrid_override_pattern"][lo:hi]     # this stage's layers
    sizes = (config["mamba_num_heads"], config["mamba_head_dim"], config["n_groups"],
             config["ssm_state_size"])
    static = (eps, sizes, config["num_experts_per_tok"], config["held_experts_first"],
              float(config["routed_scaling_factor"]), traffic["samples_per_chip"])
    REFERENCE_ROUTING.clear()
    REFERENCE_DECAYS.clear()
    stages = [Stage(("embed",), embed)] + [
        Layer(i, (kind,) + static) for i, kind in enumerate(pattern)]
    return stages, LossBackward(eps, float(config["router_aux_loss_coef"]),
                                pattern.count("E"), config["held_experts_first"],
                                config["n_routed_experts"])
