"""The gated delta rule (``ops/gated_delta.py``, PR 35): the chunked form, as
the Mosaic kernels (interpreted here; since PR 36 they make the chunk-local
tensors themselves, forward and backward; since PR 38 they read q and k at the
key heads) and as XLA's chunk-local products with a ``jax.numpy`` scan, against
the token-by-token recurrence, values and all five gradients.  And the pass
that hands the rule its q, k, v and the one that takes its ``o``
(``ops/gdn_kernels.py``, PR 38): the convolution, SiLU and L2 norms, and the
gated RMSNorm, each a Mosaic kernel pair, against the ``jnp`` functions 'dot'
models run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import trace
from horovod_tpu.models.transformer import causal_depthwise_conv, l2_unit
from horovod_tpu.ops import gated_delta
from horovod_tpu.ops.gated_delta import gated_delta_recurrent, gated_delta_rule
from horovod_tpu.ops.gdn_kernels import gdn_conv_norm, gdn_gated_norm


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


@pytest.mark.parametrize("impl", ["kernel", "jnp"])
@pytest.mark.parametrize("heads,key_heads,dk,held", [
    (4, 2, 16, 2),      # two value heads a key head: the program's four read both key heads
    (4, 1, 16, 1),      # four a key head: all of them read the one
    (8, 4, 64, 2),      # a program's block of two key heads, 128 lanes of the four's 256
    (8, 4, 16, 0),      # ... 32 lanes: no block Mosaic takes, the rule repeats
    (6, 3, 16, 0),      # one value head a program: none of a key head's pair, the same
], ids=["ratio_2", "ratio_4", "ratio_2_lane_aligned_block", "ratio_2_falls_back",
        "one_head_a_program_falls_back"])
def test_q_and_k_at_the_key_heads_are_the_repeated_call(heads, key_heads, dk, held, impl):
    """q, k at fewer heads than v (PR 38): the kernels read a value head's key
    head through their index map and sum the value heads' dq, dk a key head in
    VMEM; values and all five gradients equal the call on q, k repeated to the
    value heads, whichever way the shapes send it."""
    ratio = heads // key_heads
    assert gated_delta._key_heads_a_program(
        gated_delta._head_group(heads), key_heads, ratio, dk) == held
    q, k, v, g, beta = _inputs(3, 2, 40, heads, dk, 24, True)
    args = (q[:, :, ::ratio], k[:, :, ::ratio], v, g, beta)
    rule = lambda *a: gated_delta_rule(*a, chunk=16, impl=impl)
    repeat = lambda x: jnp.repeat(x, ratio, axis=2)
    t0 = trace.now()
    got, grads = _value_and_grads(rule, args)
    (event,) = [r[3] for r in trace.snapshot(t0) if r[0] == "gdn.chunks"]
    assert event["key_heads"] == (key_heads if held and impl == "kernel" else heads)
    want, want_grads = _value_and_grads(lambda q, k, *a: rule(repeat(q), repeat(k), *a), args)
    assert abs(float(got - want)) <= 1e-6 * abs(float(want))
    for name, a, b in zip("q k v g beta".split(), grads, want_grads):
        assert a.shape == b.shape, name
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-6 * float(jnp.max(jnp.abs(b))), name


def test_key_heads_in_bfloat16_sum_their_gradients_in_float32():
    """bf16: the key heads' dq, dk are each value head's summed in float32 and
    rounded once; the repeated call rounds each and sums in bf16.  A bf16
    place apart, and the values the same to the bit."""
    q, k, v, g, beta = _inputs(6, 1, 128, 4, 32, 32, True, jnp.bfloat16)
    args = (q[:, :, ::2], k[:, :, ::2], v, g, beta)
    f32 = lambda x: x.astype(jnp.float32)
    co = jax.random.normal(jax.random.PRNGKey(9), v.shape)
    repeat = lambda x: jnp.repeat(x, 2, axis=2)

    def run(fn):
        def loss(*a):
            o = f32(fn(*a))
            return jnp.sum(o * co), o
        (_, o), grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
        return o, grads

    (o, grads) = run(lambda *a: gated_delta_rule(*a))
    (want, want_grads) = run(lambda q, k, *a: gated_delta_rule(repeat(q), repeat(k), *a))
    np.testing.assert_array_equal(o, want)
    for name, a, b in zip("q k v g beta".split(), grads, want_grads):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        gap = float(jnp.max(jnp.abs(f32(a) - f32(b))))
        assert gap <= 0.01 * float(jnp.max(jnp.abs(f32(b)))), (name, gap)


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
    with pytest.raises(ValueError, match=r"takes q, k \(B, T, Hk, dk\)"):
        gated_delta_rule(q, k[:, :16], v, g, beta)
    with pytest.raises(ValueError, match=r"takes q, k \(B, T, Hk, dk\)"):
        gated_delta_rule(q, k, v, g[..., :1], beta)
    three = jnp.concatenate([v, v[:, :, :1]], axis=2)      # 2 key heads, 3 value heads
    with pytest.raises(ValueError, match="Hk a divisor of H"):
        gated_delta_rule(q, k, three, jnp.pad(g, ((0, 0), (0, 0), (0, 1))),
                         jnp.pad(beta, ((0, 0), (0, 0), (0, 1))))
    with pytest.raises(ValueError, match="one dtype"):
        gated_delta_rule(q.astype(jnp.bfloat16), k, v, g, beta)


def test_gdn_chunks_event_carries_the_shape_arithmetic():
    """Rows, value heads, chunk, chunks a sequence (padded to whole kernel
    steps), both head widths and the kernel's programs, at trace time."""
    q, k, v, g, beta = _inputs(9, 2, 1100, 3, 16, 24, True)
    t0 = trace.now()
    jax.eval_shape(lambda *a: gated_delta_rule(*a), q, k, v, g, beta)
    (event,) = [r[3] for r in trace.snapshot(t0) if r[0] == "gdn.chunks"]
    assert event == dict(rows=2200, value_heads=3, key_heads=3, chunk=64, chunks=24, d_k=16,
                         d_v=24, impl="kernel", programs=2 * 3 * 3, block=8, heads_a_program=1,
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
    assert event["key_heads"] == 32 and event["hbm_operand_bytes"] == 8192 * 32 * (
        2 * 3 * 128 + 8 + 4 * 64)
    t0 = trace.now()      # q, k as the cell's layer hands them since PR 38: 16 key heads
    jax.eval_shape(lambda *a: gated_delta_rule(*a), shape(1, 8192, 16, 128),
                   shape(1, 8192, 16, 128), shape(1, 8192, 32, 128),
                   jax.ShapeDtypeStruct((1, 8192, 32), jnp.float32),
                   jax.ShapeDtypeStruct((1, 8192, 32), jnp.float32))
    (event,) = [r[3] for r in trace.snapshot(t0) if r[0] == "gdn.chunks"]
    # a program's four value heads read two key heads: q and k cross HBM once a
    # key head, 33.5 MB less a layer and pass than repeated
    assert (event["key_heads"], event["value_heads"], event["programs"]) == (16, 32, 128)
    assert event["hbm_operand_bytes"] == 8192 * (
        2 * (2 * 16 * 128 + 32 * 128) + 32 * (8 + 4 * 64)) == 203_423_744


@pytest.mark.parametrize("key_heads,total", [(16, 404_750_336), (32, 471_859_200)],
                         ids=["the_cell_s_16_key_heads", "equal_head_counts"])
def test_the_backward_keeps_the_kernels_operands_the_inverse_and_a_state_a_chunk(key_heads, total):
    """What ``jax.vjp`` holds of the rule under ``"kernel"`` at the cell's
    shapes (8,192 tokens, 32 value heads of 128, bf16): q, k, v as rows, the
    gates' rows, ``T`` in float32, each chunk's ``D`` and the state it was
    handed, and nothing else: 0.40 GB a layer at Gated DeltaNet's 16 key heads,
    the docstring's 0.47 at equal head counts.  Since PR 41 the layer keeps
    exactly these (no ``jax.checkpoint`` around the call)."""
    from jax._src.ad_checkpoint import saved_residuals

    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
    gate = jax.ShapeDtypeStruct((1, 8192, 32), jnp.float32)
    kept = saved_residuals(lambda *a: gated_delta_rule(*a, interpret=True),
                           shape(1, 8192, key_heads, 128), shape(1, 8192, key_heads, 128),
                           shape(1, 8192, 32, 128), gate, gate)
    assert [(a.shape, a.dtype.name) for a, _ in kept] == [
        ((1, 8192, key_heads * 128), "bfloat16"), ((1, 8192, key_heads * 128), "bfloat16"),
        ((1, 8192, 4096), "bfloat16"),                                    # q, k, v
        ((1, 32, 128, 64), "float32"), ((1, 32, 128, 64), "float32"),     # g, beta
        ((1, 32, 8192, 64), "float32"),                                   # T
        ((1, 8192, 4096), "bfloat16"),                                    # D
        ((1, 32, 128 * 128, 128), "bfloat16")]                            # the states
    assert sum(a.size * a.dtype.itemsize for a, _ in kept) == total


# -- the pass that makes q, k, v: ops/gdn_kernels.py (PR 38) ------------------


def _conv_norm_oracle(qkv, w, hk, dk, hv, dv):
    """What 'dot' models run: ``causal_depthwise_conv``, the slices, ``l2_unit``
    a key head, q scaled."""
    b, t, _ = qkv.shape
    key = hk * dk
    mixed = causal_depthwise_conv(qkv[..., :2 * key + hv * dv], w)
    heads = lambda x: x.reshape(b, t, hk, dk)
    return (l2_unit(heads(mixed[..., :key]), dk ** -0.5).reshape(b, t, key),
            l2_unit(heads(mixed[..., key:2 * key])).reshape(b, t, key), mixed[..., 2 * key:])


def _conv_norm_both(dtype, t, rows, extra=0, seed=0, heads=(2, 16, 4, 24)):
    """Outputs and gradients (of a random cotangent a output) of the kernel pair
    and of the oracle: ``((q, k, v), (dqkv, dw))`` each, float32 numpy."""
    hk, dk, hv, dv = heads
    width = 2 * hk * dk + hv * dv
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    qkv = jax.random.normal(ks[0], (2, t, width + extra)).astype(dtype)
    w = jax.random.uniform(ks[1], (4, width), jnp.float32, -0.5, 0.5)
    cos = [jax.random.normal(key, (2, t, n)) for key, n in
           zip(ks[2:], (hk * dk, hk * dk, hv * dv))]

    def run(fn):
        def loss(qkv, w):
            outs = fn(qkv, w)
            return sum(jnp.sum(o.astype(jnp.float32) * c) for o, c in zip(outs, cos)), outs
        (_, outs), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(qkv, w)
        assert [o.dtype for o in outs] == [dtype] * 3
        assert (grads[0].dtype, grads[1].dtype) == (dtype, jnp.float32)
        assert grads[0].shape == qkv.shape and grads[1].shape == w.shape
        return tuple(np.asarray(x, np.float32) for x in outs), tuple(
            np.asarray(x, np.float32) for x in grads)

    kernel = run(lambda qkv, w: gdn_conv_norm(
        qkv, w, key_heads=hk, key_head_dim=dk, value_heads=hv, value_head_dim=dv,
        row_tile=rows))
    return kernel, run(lambda qkv, w: _conv_norm_oracle(qkv, w, hk, dk, hv, dv)), width


@pytest.mark.parametrize("t,rows,extra", [
    (32, 32, 0),      # one tile: the rows before the sequence are zeros
    (64, 16, 0),      # four tiles: every boundary hands three rows on, and back
    (50, 16, 0),      # no multiple of the tile: padded with zero rows
    (50, 32, 24),     # the projection's further columns (z) ride along unread
], ids=["one_tile", "tile_boundaries", "padded_tail", "columns_past_the_taps"])
def test_conv_norm_pass_is_the_jnp_functions_in_float32(t, rows, extra):
    """``gdn_conv_norm`` against ``causal_depthwise_conv`` + ``l2_unit``: q, k,
    v, ``dqkv`` and ``d conv_kernel`` to 1e-5 in float32."""
    (outs, grads), (want, want_grads), width = _conv_norm_both(jnp.float32, t, rows, extra)
    for name, a, b in zip("q k v dqkv dw".split(), outs + grads, want + want_grads):
        np.testing.assert_allclose(a, b, atol=1e-5 * np.abs(b).max(), err_msg=name)
    # the first rows see zeros before the sequence, not the tile's own last rows
    assert np.abs(outs[2][:, :3]).max() > 0 and np.isfinite(grads[0]).all()
    if extra:
        assert not grads[0][..., width:].any()      # what is never read has no gradient


@pytest.mark.parametrize("t,rows", [(48, 16), (50, 32)], ids=["tile_boundaries", "padded_tail"])
def test_conv_norm_pass_rounds_where_the_jnp_functions_round(t, rows):
    """bf16: the float32 sum of the taps and SiLU rounded once, the norm in
    float32 rounded again: q, k, v are the ``jnp`` functions' to the bit, but
    for a rare last place (XLA:CPU contracts the oracle's multiply-adds its own
    way; a moved or missing rounding point moves every tenth number).  The
    gradients, which the kernel keeps in float32 where autodiff rounds the
    cotangent at each of the two points, to a bf16 place."""
    (outs, grads), (want, want_grads), _ = _conv_norm_both(jnp.bfloat16, t, rows, seed=1)
    for name, a, b in zip("q k v".split(), outs, want):
        assert np.mean(a != b) <= 1e-3, name
        assert np.all(np.abs(a - b) <= 2.0 ** -7 * np.abs(b)), name
    for name, a, b in zip("dqkv dw".split(), grads, want_grads):
        assert np.abs(a - b).max() <= 0.02 * np.abs(b).max(), name


def test_conv_norm_pass_crosses_a_tile_boundary_as_it_crosses_any_row():
    """The three-row halo: the same sequence in tiles of 16 and in one tile of
    64 gives the same rows, values and gradients, on both sides of every
    boundary (a boundary that dropped or doubled a row's tap would be off by
    the tap: tenths)."""
    (a, ga), _, _ = _conv_norm_both(jnp.float32, 64, 16, seed=2)
    (b, gb), _, _ = _conv_norm_both(jnp.float32, 64, 64, seed=2)
    for name, x, y in zip("q k v dqkv dw".split(), a + ga, b + gb):
        np.testing.assert_allclose(x, y, atol=2e-6 * np.abs(y).max(), err_msg=name)


def test_conv_norm_pass_leaves_its_event_and_refuses_by_name():
    """``gdn.conv_norm``: rows, channels, taps, heads, the row tile, its programs
    and the bytes a pass moves; shapes and tiles refused by name."""
    qkv = jax.ShapeDtypeStruct((1, 8192, 12288), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((4, 8192), jnp.float32)
    call = lambda qkv, w, **kw: gdn_conv_norm(
        qkv, w, key_heads=16, key_head_dim=128, value_heads=32, value_head_dim=128, **kw)
    t0 = trace.now()
    q, k, v = jax.eval_shape(call, qkv, w)
    (event,) = [r[3] for r in trace.snapshot(t0) if r[0] == "gdn.conv_norm"]
    assert (q.shape, k.shape, v.shape) == ((1, 8192, 2048), (1, 8192, 2048), (1, 8192, 4096))
    assert event == dict(rows=8192, channels=8192, taps=4, key_heads=16, value_heads=32,
                         row_tile=64, programs=128, hbm_bytes=128 * (2 * 64 + 16) * 8192 * 2)
    with pytest.raises(ValueError, match=r"takes qkv \(B, T, >= 8192\)"):
        call(jax.ShapeDtypeStruct((1, 8192, 4096), jnp.bfloat16), w)
    with pytest.raises(ValueError, match="row_tile is a multiple of 16"):
        call(qkv, w, row_tile=24)
    with pytest.raises(ValueError, match="a multiple of 128 lanes"):
        gdn_conv_norm(jnp.zeros((1, 32, 160)), jnp.zeros((4, 160)), key_heads=2,
                      key_head_dim=16, value_heads=4, value_head_dim=24, interpret=False)


# -- the pass that takes o: norm(o) * silu(z) (PR 38) ---------------------------


def _gated_norm_oracle(o, gate, scale, heads, eps):
    """What 'dot' models run: flax's ``nn.RMSNorm`` a head, times ``silu(z)``
    in float32, ``z`` the gate's last columns."""
    import flax.linen as nn

    b, t, width = o.shape
    by_head = lambda x: x.reshape(b, t, heads, width // heads)
    n = nn.RMSNorm(dtype=o.dtype, epsilon=eps).apply(
        {"params": {"scale": scale}}, by_head(o))
    gated = n.astype(jnp.float32) * nn.silu(by_head(gate[..., -width:]).astype(jnp.float32))
    return gated.astype(o.dtype).reshape(b, t, width)


def _gated_norm_both(dtype, t, rows, gate_width, dv=24, heads=4, seed=0):
    width = heads * dv
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    o = jax.random.normal(ks[0], (2, t, width)).astype(dtype)
    gate = jax.random.normal(ks[1], (2, t, gate_width)).astype(dtype)
    scale = 1.0 + 0.3 * jax.random.normal(ks[2], (dv,))
    co = jax.random.normal(ks[3], (2, t, width))

    def run(fn):
        def loss(o, gate, scale):
            out = fn(o, gate, scale)
            return jnp.sum(out.astype(jnp.float32) * co), out
        (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
            o, gate, scale)
        assert out.dtype == dtype and [g.dtype for g in grads] == [dtype, dtype, jnp.float32]
        assert [g.shape for g in grads] == [o.shape, gate.shape, scale.shape]
        return tuple(np.asarray(x, np.float32) for x in (out,) + grads)

    return (run(lambda o, g, s: gdn_gated_norm(o, g, s, heads=heads, eps=1e-6, row_tile=rows)),
            run(lambda o, g, s: _gated_norm_oracle(o, g, s, heads, 1e-6)), width)


@pytest.mark.parametrize("t,rows,gate_width", [
    (32, 32, 96),      # the gate alone
    (50, 16, 288),     # three times as wide: z read in place by column block; a padded tail
    (48, 16, 136),     # no multiple of the gate's width: sliced first
], ids=["gate_alone", "gate_in_the_projection_s_rows", "gate_sliced_first"])
def test_gated_norm_pass_is_the_jnp_functions_in_float32(t, rows, gate_width):
    """``gdn_gated_norm`` against ``nn.RMSNorm`` x ``silu``: the product, ``do``,
    ``dz`` and ``d scale`` to 1e-5 in float32; what is not the gate has no
    gradient."""
    got, want, width = _gated_norm_both(jnp.float32, t, rows, gate_width)
    for name, a, b in zip("out do dgate dscale".split(), got, want):
        np.testing.assert_allclose(a, b, atol=1e-5 * np.abs(b).max(), err_msg=name)
    assert not got[2][..., :gate_width - width].any()


def test_gated_norm_pass_rounds_where_the_jnp_functions_round():
    """bf16: the norm in float32 rounded once, the gate's product in float32
    rounded again: the ``jnp`` functions' to the bit but for a rare last place;
    the gradients, float32 throughout in the kernel, to a bf16 place."""
    got, want, _ = _gated_norm_both(jnp.bfloat16, 50, 32, 256, dv=32, seed=1)
    assert np.mean(got[0] != want[0]) <= 1e-3
    assert np.all(np.abs(got[0] - want[0]) <= 2.0 ** -7 * np.abs(want[0]))
    for name, a, b in zip("do dgate dscale".split(), got[1:], want[1:]):
        assert np.abs(a - b).max() <= 0.02 * np.abs(b).max(), name


def test_gated_norm_pass_leaves_its_event_and_refuses_by_name():
    o = jax.ShapeDtypeStruct((1, 8192, 4096), jnp.bfloat16)
    qkvz = jax.ShapeDtypeStruct((1, 8192, 12288), jnp.bfloat16)
    scale = jax.ShapeDtypeStruct((128,), jnp.float32)
    t0 = trace.now()
    out = jax.eval_shape(lambda *a: gdn_gated_norm(*a, heads=32), o, qkvz, scale)
    (event,) = [r[3] for r in trace.snapshot(t0) if r[0] == "gdn.gated_norm"]
    assert (out.shape, out.dtype) == ((1, 8192, 4096), jnp.bfloat16)
    assert event == dict(rows=8192, channels=4096, value_heads=32, row_tile=64,
                         programs=128, hbm_bytes=3 * 8192 * 4096 * 2)
    with pytest.raises(ValueError, match=r"takes o \(B, T, H dv\)"):
        gdn_gated_norm(jnp.zeros((1, 32, 96)), jnp.zeros((1, 32, 64)), jnp.ones((24,)), heads=4)
    with pytest.raises(ValueError, match=r"takes o \(B, T, H dv\)"):
        gdn_gated_norm(jnp.zeros((1, 32, 96)), jnp.zeros((1, 32, 96)), jnp.ones((96,)), heads=4)
    with pytest.raises(ValueError, match="a multiple of 128 lanes"):
        gdn_gated_norm(jnp.zeros((1, 32, 96)), jnp.zeros((1, 32, 96)), jnp.ones((24,)),
                       heads=4, interpret=False)
