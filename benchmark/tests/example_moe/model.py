"""The program's side of the worked example: a small flax decoder whose
feed-forward is a router over stacked experts.

It stands where a real family's ``horovod_tpu.models`` class stands; it lives
here because this example adds no model to the program.  Every expert is held
whole and computed for every token, and a token's two gates weigh its two
experts' outputs (the rest get a gate of 0): the mathematics of top-2 routing
without the dispatch.  Leaf names are the reference's: ``embed/embedding``,
``layer_<i>/{ln1,ln2}/scale``, ``layer_<i>/attn/{q,k,v,o}/kernel``,
``layer_<i>/router/kernel``, ``layer_<i>/moe/{w_in,w_out}`` (stacked:
experts first), ``ln_f/scale``, ``head/kernel``.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp


class RmsNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (y * scale).astype(x.dtype)


class Dense(nn.Module):
    features: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.features), jnp.float32)
        return x.astype(self.dtype) @ kernel.astype(self.dtype)


class Attention(nn.Module):
    heads: int
    head_dim: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, h):
        b, s, d = h.shape
        q, k, v = (Dense(self.heads * self.head_dim, self.dtype, name=n)(h).reshape(
            b, s, self.heads, self.head_dim) for n in "qkv")
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        scores = scores / jnp.sqrt(jnp.float32(self.head_dim))
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1).astype(self.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, -1)
        return Dense(d, self.dtype, name="o")(out)


def gates_of(logits, top_k: int):
    """(B, S, E) router logits -> (B, S, E) gates: a softmax over each token's
    ``top_k`` largest logits, 0 elsewhere."""
    top, index = jax.lax.top_k(logits, top_k)
    weights = jax.nn.softmax(top, axis=-1)
    return jnp.sum(jax.nn.one_hot(index, logits.shape[-1], dtype=weights.dtype)
                   * weights[..., None], axis=-2)


class Experts(nn.Module):
    experts: int
    features: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, h, gates):
        d = h.shape[-1]
        init = nn.initializers.normal(0.02)
        w_in = self.param("w_in", init, (self.experts, d, self.features), jnp.float32)
        w_out = self.param("w_out", init, (self.experts, self.features, d), jnp.float32)
        hidden = jnp.einsum("bsd,edf->bsef", h.astype(self.dtype), w_in.astype(self.dtype))
        out = jnp.einsum("bsef,efd->bsed", jax.nn.silu(hidden), w_out.astype(self.dtype))
        return jnp.einsum("bsed,bse->bsd", out, gates.astype(self.dtype))


class Layer(nn.Module):
    config: dict
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        c = self.config
        eps = c["rms_norm_eps"]
        x = x + Attention(c["num_attention_heads"], c["head_dim"], self.dtype, name="attn")(
            RmsNorm(eps, name="ln1")(x))
        h = RmsNorm(eps, name="ln2")(x)
        # the router in float32, as routed models run it
        logits = Dense(c["num_experts"], jnp.float32, name="router")(h.astype(jnp.float32))
        gates = gates_of(logits, c["num_experts_per_tok"])
        return x + Experts(c["num_experts"], c["moe_intermediate_size"], self.dtype,
                           name="moe")(h, gates)


class MoeDecoder(nn.Module):
    config: dict
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, tokens):
        c = self.config
        x = nn.Embed(c["vocab_size"], c["hidden_size"], param_dtype=jnp.float32,
                     name="embed")(tokens).astype(self.dtype)
        for i in range(c["num_hidden_layers"]):
            x = Layer(c, self.dtype, name=f"layer_{i}")(x)
        x = RmsNorm(c["rms_norm_eps"], name="ln_f")(x)
        return Dense(c["vocab_size"], self.dtype, name="head")(x).astype(jnp.float32)


def z_loss_cross_entropy(logits, labels, z: float):
    """Mean next-token cross-entropy plus ``z`` times the mean squared
    log-partition (the z-loss of routed and large-vocabulary models)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked) + z * jnp.mean(jnp.square(lse))
