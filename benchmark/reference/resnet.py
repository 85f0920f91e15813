"""Plain reference: bottleneck ResNet v1.5, training mode, float32.

He et al., arXiv:1512.03385, Table 1: a stem, a 3x3/2 max-pool, stages of
bottleneck blocks (1x1, 3x3, 1x1 with 4x expansion, a 1x1 projection on the
shortcut where the shape changes), global average pool, a linear classifier,
softmax cross-entropy.  Every convolution is followed by batch normalisation
over the batch and both spatial axes, with the batch's own statistics (the
biased variance, epsilon 1e-5) -- this is training.

Departures from the paper, the same as the configuration's: the stride of 2
sits on the 3x3 (v1.5); and the stem's weights come in the space-to-depth
layout the configuration stores them in, (4, 4, 12, C): a 2x2 space-to-depth
of the image followed by a 4x4 convolution padded (2, 1), which is the linear
map of a 7x7/2 (8x8/2 for weights drawn at random in that layout).  Padding
follows XLA's ``SAME`` rule, as the configuration does.

The parameter tree is addressed by the names the configuration's checkpoint
uses (``conv_init``, ``bn_init``, ``BottleneckBlock_<i>``, ``head``); nothing
of the program is imported.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .chain import Ops, Stage

EPS = 1e-5


def batch_norm(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + EPS) * p["scale"] + p["bias"]


def stem(ops, ps, x):
    conv, bn = ps
    n, h, w, c = x.shape
    x = x.reshape(n, h // 2, 2, w // 2, 2, c).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(n, h // 2, w // 2, 4 * c)
    x = ops.conv(x, conv["kernel"], 1, [(2, 1), (2, 1)])
    x = jax.nn.relu(batch_norm(x, bn))
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        ((0, 0), (1, 1), (1, 1), (0, 0)))


def bottleneck(ops, ps, x, stride):
    (p,) = ps
    y = jax.nn.relu(batch_norm(ops.conv(x, p["Conv_0"]["kernel"], 1, "SAME"),
                               p["BatchNorm_0"]))
    y = jax.nn.relu(batch_norm(ops.conv(y, p["Conv_1"]["kernel"], stride, "SAME"),
                               p["BatchNorm_1"]))
    y = batch_norm(ops.conv(y, p["Conv_2"]["kernel"], 1, "SAME"), p["BatchNorm_2"])
    if "conv_proj" in p:
        x = batch_norm(ops.conv(x, p["conv_proj"]["kernel"], stride, "SAME"),
                       p["norm_proj"])
    return jax.nn.relu(x + y)


def _loss(ops, ps, x, labels):
    (head,) = ps
    pooled = jnp.mean(x, axis=(1, 2))
    logits = ops.einsum("nc,ck->nk", pooled, head["kernel"]) + head["bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


class LossBackward:
    keys = ("head",)

    def __call__(self, ops, ps, x, labels):
        return _loss_backward(ops.precision)(ps, x, labels)


@functools.lru_cache(maxsize=None)
def _loss_backward(precision):
    ops = Ops(precision)

    def run(ps, x, labels):
        loss, (dps, dx) = jax.value_and_grad(
            lambda p, a: _loss(ops, p, a, labels), argnums=(0, 1))(ps, x)
        return loss, dps, dx

    return jax.jit(run)


def build(config: dict, traffic: dict):
    """(stages, loss_backward) for the configuration."""
    stages = [Stage(("conv_init", "bn_init"), stem)]
    index = 0
    for i, blocks in enumerate(config["stage_sizes"]):
        for j in range(blocks):
            stride = 2 if (i > 0 and j == 0) else 1
            stages.append(Stage((f"BottleneckBlock_{index}",), bottleneck, (stride,)))
            index += 1
    return stages, LossBackward()
