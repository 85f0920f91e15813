"""What the plain references share: precision, a chain of stages, the optimizer.

Nothing here imports the program.  A reference model is a list of stages,
each a plain ``jax.numpy`` function of its own parameters and its input.  A
training step runs the stages forward keeping only the activations between
them, then backward one stage at a time (``jax.vjp`` of that stage alone, so
a stage's internals live only while its own gradient is taken), and applies
the optimizer to a stage's parameters as soon as their gradient is complete.
That is ordinary back-propagation in float32, laid out so that a step of the
timed size fits on the chip beside nothing else: the full set of gradients
never exists at once.

``precision`` names the arithmetic of every matrix multiplication and
convolution: ``float32`` is the reference (operands untouched, TPU passes at
``highest``); ``bfloat16`` and ``fp8`` round both operands first (float32
accumulation, straight-through gradient) and are the controls that the
output check has to fail.
"""

from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "bfloat16", "fp8")
_FP8_MAX = 448.0  # float8_e4m3fn


def _round_operand(a, precision: str):
    if precision == "float32":
        return a
    if precision == "bfloat16":
        rounded = a.astype(jnp.bfloat16).astype(jnp.float32)
    elif precision == "fp8":
        # per-tensor scaling to the format's range, as fp8 recipes do
        scale = _FP8_MAX / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
        rounded = (a * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    else:
        raise ValueError(f"unknown precision {precision!r}; have {PRECISIONS}")
    return a + jax.lax.stop_gradient(rounded - a)


class Ops:
    """Matrix multiplication and convolution at a named precision."""

    def __init__(self, precision: str):
        if precision not in PRECISIONS:
            raise ValueError(f"unknown precision {precision!r}; have {PRECISIONS}")
        self.precision = precision

    def einsum(self, spec: str, a, b):
        return jnp.einsum(spec, _round_operand(a, self.precision),
                          _round_operand(b, self.precision),
                          precision=jax.lax.Precision.HIGHEST)

    def conv(self, x, w, stride: int, padding):
        return jax.lax.conv_general_dilated(
            _round_operand(x, self.precision), _round_operand(w, self.precision),
            (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST)


class Stage:
    """``fn(ops, params, x, *static) -> y`` over the sub-trees ``keys`` of
    the parameter tree.  Stages with the same ``fn``, ``static`` and shapes
    share one compiled forward and one compiled backward."""

    def __init__(self, keys: Sequence[str], fn: Callable, static: tuple = ()):
        self.keys = tuple(keys)
        self.fn = fn
        self.static = tuple(static)

    def forward(self, ops: Ops, ps, x):
        return _forward(self.fn, self.static, ops.precision)(ps, x)

    def backward(self, ops: Ops, ps, x, dy):
        """(gradient of the parameters, gradient of the input or None)."""
        wrt_x = jnp.issubdtype(x.dtype, jnp.floating)
        return _backward(self.fn, self.static, ops.precision, wrt_x)(ps, x, dy)


@functools.lru_cache(maxsize=None)
def _forward(fn, static, precision):
    ops = Ops(precision)
    return jax.jit(lambda ps, x: fn(ops, ps, x, *static))


@functools.lru_cache(maxsize=None)
def _backward(fn, static, precision, wrt_x):
    ops = Ops(precision)

    def run(ps, x, dy):
        if wrt_x:
            _, vjp = jax.vjp(lambda p, a: fn(ops, p, a, *static), ps, x)
            return vjp(dy)
        _, vjp = jax.vjp(lambda p: fn(ops, p, x, *static), ps)
        return vjp(dy)[0], None

    return jax.jit(run)


# -- the optimizers, written out ---------------------------------------------


@functools.partial(jax.jit, static_argnames=("lr", "momentum"), donate_argnums=(0, 2))
def _sgd_leaf(p, g, trace, *, lr, momentum):
    trace = g + momentum * trace
    return p - lr * trace, trace


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps", "wd"),
                   donate_argnums=(0, 2, 3))
def _adamw_leaf(p, g, mu, nu, t, *, lr, b1, b2, eps, wd):
    mu = b1 * mu + (1.0 - b1) * g
    nu = b2 * nu + (1.0 - b2) * g * g
    mu_hat = mu / (1.0 - b1 ** t)
    nu_hat = nu / (1.0 - b2 ** t)
    return p - lr * (mu_hat / (jnp.sqrt(nu_hat) + eps) + wd * p), mu, nu


class Optimizer:
    """SGD with momentum, or AdamW (decoupled decay, bias-corrected, the
    epsilon outside the root), one leaf at a time."""

    def __init__(self, spec: dict):
        self.spec = spec
        if spec["name"] not in ("sgd", "adamw"):
            raise ValueError(f"unknown optimizer {spec['name']!r}")

    def slots(self, p):
        n = 1 if self.spec["name"] == "sgd" else 2
        return tuple(jnp.zeros_like(p) for _ in range(n))

    def update(self, p, g, slots, t: int):
        s = self.spec
        if s["name"] == "sgd":
            p, trace = _sgd_leaf(p, g, slots[0], lr=s["learning_rate"],
                                 momentum=s["momentum"])
            return p, (trace,)
        p, mu, nu = _adamw_leaf(
            p, g, slots[0], slots[1], jnp.float32(t), lr=s["learning_rate"],
            b1=s["b1"], b2=s["b2"], eps=s["eps"], wd=s["weight_decay"])
        return p, (mu, nu)


# -- training steps -----------------------------------------------------------


def leaf_paths(tree) -> list:
    """``a/b/c`` for every leaf, in ``jax.tree_util`` order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", k)) for k in path) for path, _ in flat]


@jax.jit
def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


@jax.jit
def _delta_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32))))


def tree_norms(tree) -> dict:
    flat = jax.tree_util.tree_leaves(tree)
    return dict(zip(leaf_paths(tree), [float(_norm(x)) for x in flat]))


def tree_delta_norms(a, b) -> dict:
    norms = [float(_delta_norm(x, y)) for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))]
    return dict(zip(leaf_paths(a), norms))


def train_steps(stages: Sequence[Stage], loss_backward: Callable, params: dict,
                make_params: Callable, inputs, labels, optimizer_spec: dict,
                steps: int, precision: str = "float32",
                other_first_gradient: dict | None = None,
                keep_first_gradient: bool = False) -> dict:
    """Train ``params`` for ``steps`` steps on the one batch.

    ``stages`` run in order; ``loss_backward(ops, ps, x, labels)`` is the
    last stage with the loss: it returns ``(loss, dps, dx)``.  A parameter
    sub-tree may belong to several stages (a tied embedding): its gradients
    add, and it is updated when the earliest stage that uses it is done.
    ``params`` is consumed.  ``make_params()`` makes the initial parameters
    again, for the change after the last step.

    Returns the loss of every step, the norm of every leaf's first gradient,
    and the norm of every leaf's change over the steps.  Given another first
    gradient (leaf path -> array, on the host), also the norm of each leaf's
    difference from it, taken as each leaf's own gradient is complete; and on
    request its own first gradient, on the host.
    """
    ops = Ops(precision)
    opt = Optimizer(optimizer_spec)
    loss_keys = loss_backward.keys
    first_use = {}
    for i, keys in enumerate([st.keys for st in stages] + [loss_keys]):
        for k in keys:
            first_use.setdefault(k, i)
    slots = {}
    losses, grad_norms, diff_norms, kept = [], {}, {}, {}

    def apply(key, grad, t):
        leaves, treedef = jax.tree_util.tree_flatten(params[key])
        gleaves = jax.tree_util.tree_leaves(grad)
        if t == 1:
            paths = [f"{key}/{p}" if p else key for p in leaf_paths(grad)]
            for path, g in zip(paths, gleaves):
                grad_norms[path] = float(_norm(g))
                if other_first_gradient is not None:
                    diff_norms[path] = float(_delta_norm(g, other_first_gradient[path]))
                if keep_first_gradient:
                    kept[path] = jax.device_get(g)
            slots[key] = [opt.slots(p) for p in leaves]
        new = []
        for i, (p, g) in enumerate(zip(leaves, gleaves)):
            p, slots[key][i] = opt.update(p, g, slots[key][i], t)
            new.append(p)
        params[key] = jax.tree_util.tree_unflatten(treedef, new)

    for t in range(1, steps + 1):
        acts = [inputs]
        for st in stages:
            acts.append(st.forward(ops, tuple(params[k] for k in st.keys), acts[-1]))
        pending = {}

        def collect(keys, dps, index, t=t, pending=pending):
            for k, dp in zip(keys, dps):
                pending[k] = dp if k not in pending else jax.tree_util.tree_map(
                    jnp.add, pending[k], dp)
                if first_use[k] == index:
                    apply(k, pending.pop(k), t)

        loss, dps, dx = loss_backward(
            ops, tuple(params[k] for k in loss_keys), acts.pop(), labels)
        losses.append(float(loss))
        collect(loss_keys, dps, len(stages))
        for index in range(len(stages) - 1, -1, -1):
            st = stages[index]
            dps, dx = st.backward(ops, tuple(params[k] for k in st.keys), acts.pop(), dx)
            collect(st.keys, dps, index)
    slots.clear()
    delta_norms = tree_delta_norms(params, make_params())
    out = {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta_norms}
    if other_first_gradient is not None:
        out["grad_diff_norms"] = diff_norms
    if keep_first_gradient:
        out["first_gradient"] = kept
    return out
