"""Weights and batches from ``--seed``, made on the device in one jitted call.

The benchmark owns the numbers that go into both the program and the plain
reference: the reference takes nothing the program has made.  The program
supplies only the shapes of its parameter tree (``jax.eval_shape`` of its
own state); a leaf's values follow from its name, its shape, the
configuration's ``init`` block and the seed:

  ``scale``      -> ``norm_scale`` (1); 0 where the leaf's path matches
                   ``zero_scale`` (the last norm of a residual branch)
  ``bias``       -> 0
  ``embedding``  -> normal(0, ``embed_std``)
  ``kernel``, 4-D (H, W, I, O) -> He normal on the fan out, sqrt(2 / (H W O))
  ``kernel``, other -> normal(0, ``dense_std``), or 1/sqrt(fan in) when that
                   is null (fan in: every axis but the last)

A family whose leaves these rules do not cover (another name, or a fan in that
is not "every axis but the last": a stack of experts) states its own, as data,
under ``init.rules``: a list of ``{"match": <regex on the leaf's path>, ...}``
tried in order before the rules above, the first match giving

  ``"constant": c``            -> c everywhere
  ``"std": s``                 -> normal(0, s)
  ``"fan_in_axes": [axes]``    -> normal(0, ``gain`` / sqrt(product of those
                                 axes' sizes)); ``gain`` defaults to 1.  A stacked
                                 (experts, d, f) tensor's fan in is axis 1.

Each leaf's key is the seed's key folded with the leaf's index, so a leaf
can be made again alone; with no ``rules`` a leaf's values are what they
were before rules existed.
"""

from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key from any whole number up to 2**63: the low 31 bits seed it, the
    rest are folded in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must not be negative, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _ruled(key, shape, dtype, rule: dict):
    if "constant" in rule:
        return jnp.full(shape, rule["constant"], dtype)
    if "std" in rule:
        std = rule["std"]
    elif "fan_in_axes" in rule:
        std = rule.get("gain", 1.0) / math.sqrt(math.prod(shape[a] for a in rule["fan_in_axes"]))
    else:
        raise ValueError(f"init rule {rule} gives no constant, std or fan_in_axes")
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _leaf(key, path: str, shape, dtype, init: dict):
    for rule in init.get("rules", ()):
        if re.search(rule["match"], path):
            return _ruled(key, shape, dtype, rule)
    name = path.rsplit("/", 1)[-1]
    if name == "scale":
        zero = init.get("zero_scale") and re.search(init["zero_scale"], path)
        return jnp.full(shape, 0.0 if zero else init.get("norm_scale", 1.0), dtype)
    if name == "bias":
        return jnp.zeros(shape, dtype)
    if name == "embedding":
        std = init["embed_std"]
    elif name == "kernel" and len(shape) == 4:
        std = math.sqrt(2.0 / (shape[0] * shape[1] * shape[3]))
    elif name == "kernel":
        std = init.get("dense_std") or 1.0 / math.sqrt(math.prod(shape[:-1]))
    else:
        raise ValueError(f"no rule for a parameter leaf named {name!r} ({path}); state "
                         f"one under init.rules in the configuration's file")
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def make_params(shapes, key, init: dict, sharding=None):
    """The parameter tree for ``shapes`` (a tree of ShapeDtypeStruct)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        leaves = [
            _leaf(jax.random.fold_in(key, i),
                  "/".join(str(getattr(k, "key", k)) for k in path), s.shape, s.dtype, init)
            for i, (path, s) in enumerate(flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(make, out_shardings=sharding)(key)
