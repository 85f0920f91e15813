"""The gated delta rule (``ops/gated_delta.py``, PR 35): the chunked form, as
the Mosaic kernels (interpreted here; since PR 36 they make the chunk-local
tensors themselves, forward and backward) and as XLA's chunk-local products
with a ``jax.numpy`` scan, against the token-by-token recurrence, values and all
five gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import trace
from horovod_tpu.ops import gated_delta
from horovod_tpu.ops.gated_delta import gated_delta_recurrent, gated_delta_rule


def _inputs(seed, b, t, h, dk, dv, slow, dtype=jnp.float32, alike=0.0):
    """q, k normalised a head and q scaled, as the layer hands them; ``slow``
    decays keep the state over many chunks (exp(g) 0.99-0.999 a token), fast
    ones lose it within one (0.1-0.7); ``alike``: the share of every key that
    is one direction a head (neighbouring keys alike: ``T``'s entries grow)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5
    k = unit(alike * jax.random.normal(jax.random.fold_in(ks[1], 1), (b, 1, h, dk))
             + (1 - alike) * jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h)))
    g = -jax.nn.softplus(jax.random.normal(ks[4], (b, t, h))) * (0.005 if slow else 1.5)
    return tuple(x.astype(dtype) for x in (q, k, v)) + (g, beta)


def _value_and_grads(fn, args, seed=9):
    co = jax.random.normal(jax.random.PRNGKey(seed), args[2].shape)
    return jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * co), argnums=(0, 1, 2, 3, 4))(*args)


def _against_the_recurrence(args, chunk, names="q k v g beta", rtol=2e-4, impl="kernel"):
    want, want_grads = _value_and_grads(gated_delta_recurrent, args)
    got, grads = _value_and_grads(
        lambda *a: gated_delta_rule(*a, chunk=chunk, impl=impl), args)
    assert abs(float(got - want)) <= 2e-5 * abs(float(want)) + 1e-5
    for name, a, b in zip("q k v g beta".split(), grads, want_grads):
        if name in names.split():
            scale = float(jnp.max(jnp.abs(b)))
            assert float(jnp.max(jnp.abs(a - b))) <= rtol * scale + 1e-7, name


@pytest.mark.parametrize("impl", ["kernel", "jnp"])
@pytest.mark.parametrize("slow", [True, False], ids=["slow_decay", "fast_decay"])
@pytest.mark.parametrize("t,chunk", [(64, 16), (50, 16), (192, 64), (200, 64), (1100, 64)])
def test_chunked_is_the_recurrence_values_and_all_five_gradients(t, chunk, slow, impl):
    """Lengths that are and are not multiples of the chunk (and of the kernel's
    step of eight chunks: 1,100 tokens are 18 chunks, padded to 24), at two
    chunk sizes."""
    args = _inputs(t, 2, t, 4 if chunk == 64 else 3, 16, 24, slow)   # 4: a program of four heads
    _against_the_recurrence(args, chunk, impl=impl)


def _neighbouring_keys_alike():
    """``dg`` and ``dbeta`` where ``T``'s entries are large and alternate: they
    pass through ``T``'s transpose ``-T^T dT T^T`` and the decays' cotangent
    inside the backward kernel.  (The shapes of a case above: interpret mode
    compiles a step's 32 unrolled chunk-heads once a shape.)"""
    _against_the_recurrence(_inputs(11, 2, 192, 4, 16, 24, True, alike=0.5), 64,
                            names="g beta", rtol=5e-4)


def _bfloat16_against_the_jnp_path():
    """bf16 operands: the kernels round where ``_prepare`` rounds, so the values
    are the ``jnp`` path's to the bit but for a rare last place (a moved rounding
    point reads 2e-3 of the mean here); the gradients, which the kernel keeps in
    float32 where autodiff rounds each cotangent to bf16, to a bf16 place."""
    args = _inputs(6, 1, 256, 4, 32, 32, True, jnp.bfloat16)
    f32 = lambda x: x.astype(jnp.float32)
    co = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

    def run(impl):
        def loss(*a):
            o = f32(gated_delta_rule(*a, impl=impl))
            return jnp.sum(o * co), o
        (_, o), grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
        return o, grads

    (o, grads), (want, want_grads) = run("kernel"), run("jnp")
    assert float(jnp.mean(jnp.abs(o - want))) <= 2e-4 * float(jnp.mean(jnp.abs(want)))
    for name, a, b in zip("q k v g beta".split(), grads, want_grads):
        assert a.dtype == b.dtype, name
        gap = float(jnp.max(jnp.abs(f32(a) - f32(b))))
        assert gap <= 0.02 * float(jnp.max(jnp.abs(f32(b)))), (name, gap)


def _four_heads_eight_chunks_and_a_padded_tail():
    """A program of four heads, three grid steps of eight chunks, the last with
    six chunks of padding behind a chunk that is itself part padding, the keys
    part alike so that every step's ``T`` is far from the identity."""
    _against_the_recurrence(_inputs(12, 2, 1100, 4, 16, 24, True, alike=0.3), 64)


def _gdn_chunks_says_what_crossed_hbm():
    """``hbm_operand_bytes``: what XLA hands the forward kernels through HBM a
    layer and pass, q, k, v, g, beta and ``T``; the ``jnp`` path, whose
    chunk-local tensors are XLA's own to place, says nothing."""
    q, k, v, g, beta = _inputs(9, 2, 170, 4, 16, 24, True, jnp.bfloat16)
    events = {}
    for impl in ("kernel", "jnp"):
        t0 = trace.now()
        jax.eval_shape(lambda *a: gated_delta_rule(*a, chunk=16, impl=impl), q, k, v, g, beta)
        (events[impl],) = [r[3] for r in trace.snapshot(t0) if r[0] == "gdn.chunks"]
    rows = 2 * 16 * 16 * 4           # sequences x chunks x chunk x heads, padded
    assert events["kernel"]["chunks"] == 16 and events["kernel"]["heads_a_program"] == 4
    assert events["kernel"]["hbm_operand_bytes"] == rows * (
        2 * (16 + 16 + 24) + 4 * 2 + 4 * 16)
    assert "hbm_operand_bytes" not in events["jnp"]


@pytest.mark.parametrize("case", [
    _neighbouring_keys_alike, _bfloat16_against_the_jnp_path,
    _four_heads_eight_chunks_and_a_padded_tail, _gdn_chunks_says_what_crossed_hbm,
], ids=lambda case: case.__name__.lstrip("_"))
def test_the_kernels_make_the_chunk_local_tensors_themselves(case):
    """What only the fused path (PR 36) can get wrong."""
    case()


def test_output_itself_matches_token_for_token():
    args = _inputs(3, 1, 150, 2, 8, 8, True)
    want = gated_delta_recurrent(*args)
    for impl in ("kernel", "jnp"):
        got = gated_delta_rule(*args, chunk=32, impl=impl)
        assert got.shape == want.shape == (1, 150, 2, 8) and got.dtype == jnp.float32
        np.testing.assert_allclose(got, want, atol=2e-6)


def test_the_state_is_carried_across_chunks():
    """With slow decays a chunk's output depends on the chunks before it: a
    carry that starts every chunk from zero is far off."""
    args = _inputs(4, 1, 128, 2, 16, 16, True)
    whole = gated_delta_rule(*args, chunk=32)
    alone = gated_delta_rule(*(x[:, 96:] for x in args), chunk=32)
    first = gated_delta_rule(*(x[:, :32] for x in args), chunk=32)
    np.testing.assert_allclose(whole[:, :32], first, atol=2e-6)      # causal
    assert float(jnp.max(jnp.abs(whole[:, 96:] - alone))) > 0.05


def test_padding_passes_the_state_through():
    """g = 0, beta = 0, k = 0 beyond the sequence: the tokens before are what
    they were, whatever the kernel's step pads to."""
    args = _inputs(5, 1, 70, 2, 16, 16, True)
    padded = tuple(jnp.pad(x, ((0, 0), (0, 58)) + ((0, 0),) * (x.ndim - 2)) for x in args)
    np.testing.assert_allclose(gated_delta_rule(*padded, chunk=16)[:, :70],
                               gated_delta_rule(*args, chunk=16), atol=2e-6)


@pytest.mark.parametrize("alike", [0.0, 0.5], ids=["random_keys", "neighbouring_keys_alike"])
def test_the_unit_triangular_inverse_and_its_transpose(alike):
    """(I + L)^-1 by 16-wide power series and block substitution: the inverse,
    also where neighbouring keys are alike (the whole chunk's power series would
    cancel catastrophically there) and its hand-written transpose."""
    n = 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    base = jax.random.normal(ks[0], (1, 16))
    keys = alike * base + (1 - alike) * jax.random.normal(ks[1], (3, n, 16))
    keys = keys / jnp.linalg.norm(keys, axis=-1, keepdims=True)
    lower = jnp.tril(jnp.einsum("bid,bjd->bij", keys, keys), -1)
    got = gated_delta._unit_lower_inverse(lower)
    want = np.linalg.inv(np.eye(n) + np.asarray(lower, np.float64))
    np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max())
    # the cotangent of what lies strictly under the diagonal (all the rule reads)
    co = jax.random.normal(ks[2], lower.shape)
    masked = lambda inverse: jax.grad(
        lambda x: jnp.sum(inverse(jnp.tril(x, -1)) * co))(lower)
    by_hand, by_autodiff = masked(gated_delta._unit_lower_inverse), masked(gated_delta._block_inverse)
    np.testing.assert_allclose(by_hand, by_autodiff,
                               atol=1e-4 * float(jnp.max(jnp.abs(by_autodiff))))


def test_bfloat16_operands_give_bfloat16_and_stay_near_the_recurrence():
    args = _inputs(6, 1, 256, 4, 32, 32, True, jnp.bfloat16)
    want = gated_delta_recurrent(*args)
    for impl in ("kernel", "jnp"):
        got = gated_delta_rule(*args, impl=impl)
        assert got.dtype == jnp.bfloat16
        gap = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
        assert gap < 0.03 * float(jnp.max(jnp.abs(want)))
    grads = jax.grad(lambda *a: jnp.sum(gated_delta_rule(*a).astype(jnp.float32)),
                     argnums=(0, 1, 2, 3, 4))(*args)
    assert [g.dtype for g in grads] == [jnp.bfloat16] * 3 + [jnp.float32] * 2
    assert all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32)))) for g in grads)


def test_kernel_and_scan_carry_agree_under_jit_and_vmap_free_batches():
    args = _inputs(7, 3, 96, 2, 16, 16, False)
    kernel = jax.jit(lambda *a: gated_delta_rule(*a, chunk=16))(*args)
    scan = jax.jit(lambda *a: gated_delta_rule(*a, chunk=16, impl="jnp"))(*args)
    np.testing.assert_allclose(kernel, scan, atol=2e-6)


@pytest.mark.parametrize("bad,message", [
    (dict(impl="pallas"), "impl is 'kernel' or 'jnp'"),
    (dict(chunk=0), "chunk is a number of tokens"),
    # the chip's blocks, told before Mosaic: one head of 8 lanes a program of two
    (dict(interpret=False), "a multiple of 128 lanes or all 2 heads"),
])
def test_arguments_are_refused_by_name(bad, message):
    args = _inputs(8, 1, 32, 2, 8, 8, True)
    with pytest.raises(ValueError, match=message):
        gated_delta_rule(*args, **bad)


def test_shapes_and_dtypes_are_refused():
    q, k, v, g, beta = _inputs(8, 1, 32, 2, 8, 8, True)
    with pytest.raises(ValueError, match=r"takes q, k \(B, T, H, dk\)"):
        gated_delta_rule(q, k[:, :16], v, g, beta)
    with pytest.raises(ValueError, match=r"takes q, k \(B, T, H, dk\)"):
        gated_delta_rule(q, k, v, g[..., :1], beta)
    with pytest.raises(ValueError, match="one dtype"):
        gated_delta_rule(q.astype(jnp.bfloat16), k, v, g, beta)


def test_gdn_chunks_event_carries_the_shape_arithmetic():
    """Rows, value heads, chunk, chunks a sequence (padded to whole kernel
    steps), both head widths and the kernel's programs, at trace time."""
    q, k, v, g, beta = _inputs(9, 2, 1100, 3, 16, 24, True)
    t0 = trace.now()
    jax.eval_shape(lambda *a: gated_delta_rule(*a), q, k, v, g, beta)
    (event,) = [r[3] for r in trace.snapshot(t0) if r[0] == "gdn.chunks"]
    assert event == dict(rows=2200, value_heads=3, chunk=64, chunks=24, d_k=16, d_v=24,
                         impl="kernel", programs=2 * 3 * 3, block=8, heads_a_program=1,
                         hbm_operand_bytes=2 * 24 * 64 * 3 * (4 * (16 + 16 + 24) + 8 + 4 * 64))
    t0 = trace.now()      # the benchmark's cell: 8,192 tokens, 32 value heads of 128
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
    jax.eval_shape(lambda *a: gated_delta_rule(*a), shape(1, 8192, 32, 128),
                   shape(1, 8192, 32, 128), shape(1, 8192, 32, 128),
                   jax.ShapeDtypeStruct((1, 8192, 32), jnp.float32),
                   jax.ShapeDtypeStruct((1, 8192, 32), jnp.float32))
    (event,) = [r[3] for r in trace.snapshot(t0) if r[0] == "gdn.chunks"]
    # four value heads a program: 1 sequence x 8 head groups x 16 steps of eight chunks
    assert (event["chunks"], event["programs"], event["heads_a_program"], event["d_k"],
            event["d_v"]) == (128, 128, 4, 128, 128)
