"""Plain reference: pre-norm decoder-only language model, float32.

The InternLM2 / Llama block (InternLM2 technical report, arXiv:2403.17297):
token embedding; layers of ``x + attention(rmsnorm(x))`` then
``x + swiglu(rmsnorm(x))``; a final RMSNorm; logits against the output
matrix; mean softmax cross-entropy over every position.  Attention is causal
with grouped key/value heads (query head h reads key/value head
h // (H / H_kv)), scaled by 1/sqrt(head_dim), with rotary position
embedding in the split-half convention (the first and second half of a head
are the pairs) on queries and keys.  RMSNorm: x * rsqrt(mean(x^2) + eps) *
scale.  SwiGLU: down(silu(gate(x)) * up(x)).

As the configuration's file lists: the output matrix is the embedding (tied),
and the rotary base is ``rope_theta`` from the file.

Laid out to fit: a layer takes one sequence at a time and its attention one
key/value group at a time, and the loss one block of ``LOSS_ROWS`` positions
at a time.  The parameter tree
is addressed by the names the configuration's checkpoint uses (``embed``,
``layer_<i>/{ln1,attn/{q,k,v,o},ln2,mlp/{gate,up,down}}``, ``ln_f``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .chain import Ops, Stage

LOSS_ROWS = 1024


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: (B, S, H, D); position s rotates pair (x[i], x[i + D/2]) by
    s * theta^(-2i/D)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def embed(ops, ps, tokens):
    (p,) = ps
    return p["embedding"][tokens]


def layer(ops, ps, x, eps, theta):
    """One decoder layer over (B, S, D).  Rows do not meet inside a layer, so
    they go through it one at a time: a step of four sequences then needs no
    more memory than a step of one."""
    if x.shape[0] == 1:
        return _layer_row(ops, ps, x, eps, theta)
    row = jax.checkpoint(lambda r: _layer_row(ops, ps, r[None], eps, theta)[0])
    return jax.lax.map(row, x)


def _layer_row(ops, ps, x, eps, theta):
    (p,) = ps
    a = p["attn"]
    h = rms_norm(x, p["ln1"]["scale"], eps)
    q = rope(ops.einsum("bsd,dhk->bshk", h, a["q"]["kernel"]), theta)
    k = rope(ops.einsum("bsd,dhk->bshk", h, a["k"]["kernel"]), theta)
    v = ops.einsum("bsd,dhk->bshk", h, a["v"]["kernel"])
    b, s, heads, hd = q.shape
    kv = k.shape[2]
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint  # keep a group's scores only while its own gradient is taken
    def group(qkv):
        # one key/value head and its query heads: qg (B, S, G, D), kg/vg (B, S, D)
        qg, kg, vg = qkv
        scores = ops.einsum("bqgd,bkd->bgqk", qg, kg) / jnp.sqrt(jnp.float32(hd))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return ops.einsum("bgqk,bkd->bqgd", probs, vg)

    qg = jnp.moveaxis(q.reshape(b, s, kv, heads // kv, hd), 2, 0)
    out = jax.lax.map(group, (qg, jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
    out = jnp.moveaxis(out, 0, 2).reshape(b, s, heads, hd)
    x = x + ops.einsum("bshk,hkd->bsd", out, a["o"]["kernel"])
    h = rms_norm(x, p["ln2"]["scale"], eps)
    m = p["mlp"]
    gate = ops.einsum("bsd,df->bsf", h, m["gate"]["kernel"])
    up = ops.einsum("bsd,df->bsf", h, m["up"]["kernel"])
    return x + ops.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up, m["down"]["kernel"])


def _block_loss(ops, ps, x, labels, eps):
    """Summed cross-entropy of a block of rows: x (R, D), labels (R,)."""
    ln_f, emb = ps
    h = rms_norm(x, ln_f["scale"], eps)
    logits = ops.einsum("rd,vd->rv", h, emb["embedding"])
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


class LossBackward:
    keys = ("ln_f", "embed")

    def __init__(self, eps):
        self.eps = eps

    def __call__(self, ops, ps, x, labels):
        b, s, d = x.shape
        rows, flat = x.reshape(b * s, d), labels.reshape(b * s)
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
        dx = (jnp.concatenate(dxs) * scale).reshape(b, s, d)
        return total * scale, dps, dx


def build(config: dict, traffic: dict):
    """(stages, loss_backward) for the configuration."""
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    stages = [Stage(("embed",), embed)]
    stages += [Stage((f"layer_{i}",), layer, (eps, theta))
               for i in range(config["num_hidden_layers"])]
    return stages, LossBackward(eps)
