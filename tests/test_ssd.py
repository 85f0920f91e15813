"""Mamba-2's state-space scan (PR 48, ``ops/ssd.py``): the Mosaic kernels
(interpret mode on the CPU), the ``jnp`` chunked form and the token-by-token
recurrence agree on the outputs and on every gradient; Mamba-2's forms of
Gated DeltaNet's two elementwise passes (``ops/gdn_kernels.py``) against plain
``jax.numpy``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import trace
from horovod_tpu.ops import gated_delta, ssd
from horovod_tpu.ops.gdn_kernels import conv_bias_silu, gated_group_norm
from horovod_tpu.ops.ssd import ssd_recurrent, ssd_scan


def _operands(shape, dtype, seed=0):
    """x, dt, a, b, c, d and a cotangent: steps small enough that a state
    outlives a chunk."""
    b, t, h, p, g, n = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (b, t, h, p)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)) - 1.0)
    a = -jnp.exp(0.5 * jax.random.normal(ks[2], (h,)))
    bm = (0.5 * jax.random.normal(ks[3], (b, t, g, n))).astype(dtype)
    cm = (0.5 * jax.random.normal(ks[4], (b, t, g, n))).astype(dtype)
    d = jax.random.normal(ks[5], (h,))
    return (x, dt, a, bm, cm, d), jax.random.normal(ks[6], (b, t, h, p))


def _gap(got, want):
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


# two heads that share ONE group over 37 tokens (two whole chunks of 16 and a
# partial one); four heads in two groups, two sequences
@pytest.mark.parametrize("shape", [(1, 37, 2, 64, 1, 16), (2, 40, 4, 8, 2, 16)],
                         ids=["a_group_of_two", "two_groups"])
@pytest.mark.parametrize("dtype,limit", [(jnp.float32, 2e-5), (jnp.bfloat16, 1e-2)],
                         ids=["float32", "bfloat16"])
def test_kernel_chunked_form_and_recurrence_agree(shape, dtype, limit):
    args, w = _operands(shape, dtype)
    names = ("x", "dt", "a", "b", "c", "d")
    loss = lambda fn: (lambda *v: jnp.sum(fn(*v).astype(jnp.float32) * w))
    want_y = ssd_recurrent(*args)
    want = jax.grad(loss(ssd_recurrent), range(6))(*args)
    for impl in ("jnp", "kernel"):
        fn = lambda *v: ssd_scan(*v, chunk=16, impl=impl)
        y = fn(*args)
        assert y.dtype == dtype and y.shape == args[0].shape
        assert _gap(y, want_y) < limit, impl
        for name, got, ref in zip(names, jax.grad(loss(fn), range(6))(*args), want):
            assert got.shape == ref.shape and _gap(got, ref) < limit, (impl, name)


def test_the_scan_without_a_skip_and_its_event():
    args, _ = _operands((1, 20, 2, 8, 1, 16), jnp.float32)
    t0 = trace.now()
    y = ssd_scan(*args[:5], chunk=16)
    np.testing.assert_allclose(y, ssd_recurrent(*args[:5]), atol=2e-5)
    (event,) = [r[3] for r in trace.snapshot(t0) if r[0] == "ssd.chunks"]
    assert event == {"rows": 20, "heads": 2, "groups": 1, "chunk": 16, "chunks": 2,
                     "head_dim": 8, "state": 16, "impl": "kernel", "block": 2,
                     "heads_a_program": 2, "programs": 1, "state_bytes": 2 * 16 * 8 * 4}


def test_the_scan_shares_the_delta_rule_s_helpers_and_refuses_what_it_cannot_take():
    for name in ("_running_sums", "_gates", "_iotas", "_dot", "_params"):
        assert getattr(ssd, name) is getattr(gated_delta, name)
    (x, dt, a, b, c, d), _ = _operands((1, 20, 4, 8, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="impl is 'kernel' or 'jnp'"):
        ssd_scan(x, dt, a, b, c, d, impl="scan")
    with pytest.raises(ValueError, match="G a divisor of H"):
        ssd_scan(x, dt, a, b[:, :, :1].repeat(3, axis=2), c[:, :, :1].repeat(3, axis=2), d)
    with pytest.raises(ValueError, match="one dtype"):
        ssd_scan(x, dt, a, b.astype(jnp.bfloat16), c, d)
    with pytest.raises(ValueError, match="chunk is a number of tokens"):
        ssd_scan(x, dt, a, b, c, d, chunk=0)
    # on the chip: groups whose heads are no whole lane tiles
    with pytest.raises(ValueError, match="multiples of 128 lanes"):
        ssd_scan(x, dt, a, b, c, d, chunk=128, interpret=False)


# -- Mamba-2's forms of the two elementwise passes ---------------------------------


def _conv_plain(u, w, bias):
    taps, t = w.shape[0], u.shape[1]
    padded = jnp.pad(u.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + t] * w[j] for j in range(taps)) + bias)


def _norm_plain(y, z, scale, groups, eps=1e-5):
    u = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    by_group = u.reshape(*u.shape[:-1], groups, -1)
    by_group = by_group * jax.lax.rsqrt(
        jnp.mean(jnp.square(by_group), axis=-1, keepdims=True) + eps)
    return by_group.reshape(u.shape) * scale


@pytest.mark.parametrize("dtype,limit", [(jnp.float32, 1e-5), (jnp.bfloat16, 1e-2)],
                         ids=["float32", "bfloat16"])
def test_bias_convolution_and_group_norm_kernels_match_jax_numpy(dtype, limit):
    """100 tokens in tiles of 32 (the last one partial, the taps across every
    tile's edge); the norm over two groups of 12 columns with a scale a column."""
    ks = jax.random.split(jax.random.PRNGKey(1), 8)
    u = jax.random.normal(ks[0], (2, 100, 24)).astype(dtype)
    w, bias = 0.5 * jax.random.normal(ks[1], (4, 24)), jax.random.normal(ks[2], (24,))
    cot = jax.random.normal(ks[3], (2, 100, 24))
    loss = lambda fn: (lambda *v: jnp.sum(fn(*v).astype(jnp.float32) * cot))
    conv = lambda *v: conv_bias_silu(*v, row_tile=32)
    assert _gap(conv(u, w, bias), _conv_plain(u, w, bias)) < limit
    for got, want in zip(jax.grad(loss(conv), (0, 1, 2))(u, w, bias),
                         jax.grad(loss(_conv_plain), (0, 1, 2))(u, w, bias)):
        assert _gap(got, want) < limit
    y, z = (jax.random.normal(k, (2, 100, 24)).astype(dtype) for k in ks[4:6])
    scale = 1.0 + 0.1 * jax.random.normal(ks[6], (24,))
    gated = lambda *v: gated_group_norm(*v, groups=2, row_tile=32)
    plain = lambda *v: _norm_plain(*v, 2)
    assert _gap(gated(y, z, scale), plain(y, z, scale)) < limit
    for got, want in zip(jax.grad(loss(gated), (0, 1, 2))(y, z, scale),
                         jax.grad(loss(plain), (0, 1, 2))(y, z, scale)):
        assert _gap(got, want) < limit
    with pytest.raises(ValueError, match="conv_bias_silu takes u"):
        conv_bias_silu(u, w, bias[:3])
    with pytest.raises(ValueError, match="groups a divisor of W"):
        gated_group_norm(y, z, scale, groups=5)
