"""Plain reference of the worked example: the same decoder in ``jax.numpy``,
float32, no flax, nothing of the program.

Token embedding; layers of ``x + attention(rmsnorm(x))`` then ``x +
moe(rmsnorm(x))``; a final RMSNorm; logits against an untied head; mean
cross-entropy plus ``z_loss`` times the mean squared log-partition.
Attention is causal multi-head, scaled by 1/sqrt(head_dim), no positions.
The feed-forward: router logits ``h @ W_r`` in float32 at every precision
(the router is not a matrix multiplication a lower precision is tried on), a
softmax over each token's ``num_experts_per_tok`` largest logits as its gates,
and the gated sum of those experts' ``silu(h W_in[e]) W_out[e]``.

Stages for ``chain.train_steps``: the embedding, one stage a layer, and the
loss with the final norm and the head.  At this size nothing needs laying out
in blocks; a reference at a cell's real size does (``reference/decoder_lm.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference.chain import Ops, Stage


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def embed(ops, ps, tokens):
    (p,) = ps
    return p["embedding"][tokens]


def gates_of(logits, top_k):
    top, index = jax.lax.top_k(logits, top_k)
    weights = jax.nn.softmax(top, axis=-1)
    return jnp.sum(jax.nn.one_hot(index, logits.shape[-1]) * weights[..., None], axis=-2)


def layer(ops, ps, x, eps, heads, top_k):
    (p,) = ps
    b, s, d = x.shape
    a = p["attn"]
    h = rms_norm(x, p["ln1"]["scale"], eps)
    q, k, v = (ops.einsum("bsd,df->bsf", h, a[n]["kernel"]).reshape(b, s, heads, -1)
               for n in "qkv")
    scores = ops.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = ops.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, -1)
    x = x + ops.einsum("bsf,fd->bsd", out, a["o"]["kernel"])
    h = rms_norm(x, p["ln2"]["scale"], eps)
    logits = jnp.einsum("bsd,de->bse", h, p["router"]["kernel"],
                        precision=jax.lax.Precision.HIGHEST)
    gates = gates_of(logits, top_k)
    hidden = ops.einsum("bsd,edf->bsef", h, p["moe"]["w_in"])
    expert_out = ops.einsum("bsef,efd->bsed", jax.nn.silu(hidden), p["moe"]["w_out"])
    return x + jnp.einsum("bsed,bse->bsd", expert_out, gates,
                          precision=jax.lax.Precision.HIGHEST)


def _loss(ops, ps, x, labels, eps, z):
    ln_f, head = ps
    logits = ops.einsum("bsd,dv->bsv", rms_norm(x, ln_f["scale"], eps), head["kernel"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked) + z * jnp.mean(jnp.square(lse))


@functools.lru_cache(maxsize=None)
def _loss_backward(precision, eps, z):
    ops = Ops(precision)

    def run(ps, x, labels):
        loss, (dps, dx) = jax.value_and_grad(
            lambda p, a: _loss(ops, p, a, labels, eps, z), argnums=(0, 1))(ps, x)
        return loss, dps, dx

    return jax.jit(run)


class LossBackward:
    keys = ("ln_f", "head")

    def __init__(self, eps, z):
        self.eps, self.z = eps, z

    def __call__(self, ops, ps, x, labels):
        return _loss_backward(ops.precision, self.eps, self.z)(ps, x, labels)


def build(config: dict, traffic: dict):
    """(stages, loss_backward) for the configuration."""
    eps = float(config["rms_norm_eps"])
    static = (eps, config["num_attention_heads"], config["num_experts_per_tok"])
    stages = [Stage(("embed",), embed)]
    stages += [Stage((f"layer_{i}",), layer, static)
               for i in range(config["num_hidden_layers"])]
    return stages, LossBackward(eps, float(config["z_loss"]))
