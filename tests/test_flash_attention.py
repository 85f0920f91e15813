"""Pallas flash-attention parity tests (interpret mode on the CPU mesh;
the identical kernel compiles for real on TPU — tools/flash_bench.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.transformer import causal_dot_attention
from horovod_tpu.ops.flash_attention import flash_attention, tile_counts


def _qkv(b, s, h, d, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, s, h, d)
    return tuple(
        jax.random.normal(kk, shape, jnp.float32).astype(dtype) for kk in ks
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s", [256, 384, 1024])
def test_flash_matches_dense_causal(dtype, s):
    # 1024: walks of one to eight 128-tiles, in iterations of eight, four, two, one
    q, k, v = _qkv(2, s, 2, 64, dtype)
    ref = causal_dot_attention(q, k, v)
    out = flash_attention(q, k, v, block_q=128, block_k=128)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol,
    )


def test_flash_unpadded_sequence():
    # S=200 pads to 256; pad keys must be masked and pad rows dropped
    q, k, v = _qkv(1, 200, 2, 64, jnp.float32, seed=1)
    ref = causal_dot_attention(q, k, v)
    out = flash_attention(q, k, v, block_q=128, block_k=128)
    assert out.shape == (1, 200, 2, 64)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_transformer_flash_impl_matches_dot():
    """attention_impl='flash' must produce the same transformer forward
    as the dense default (same params, same logits)."""
    from horovod_tpu.models.transformer import Transformer, TransformerConfig

    cfg = dict(vocab_size=64, num_heads=2, head_dim=16,
               num_layers=2, dtype=jnp.float32)
    m_dot = Transformer(TransformerConfig(**cfg, attention_impl="dot"))
    m_flash = Transformer(TransformerConfig(**cfg, attention_impl="flash"))
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 96), 0, 64)
    variables = m_dot.init(jax.random.PRNGKey(1), tokens)
    out_dot = m_dot.apply(variables, tokens)
    out_flash = m_flash.apply(variables, tokens)
    np.testing.assert_allclose(
        np.asarray(out_flash), np.asarray(out_dot), rtol=2e-4, atol=2e-4
    )


@pytest.mark.parametrize("s,block", [(256, 256), (1024, 128)],
                         ids=["one_tile", "eight_tiles"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-3),
                                       (jnp.bfloat16, 1e-1)])
def test_flash_gradients_match_dense(dtype, tol, s, block):
    """Training through the kernel: custom_vjp gradients must match the
    dense path's (backward recomputes with the kernel's upcast numerics;
    bf16 compares loosely against the model's dense reference).  Eight
    tiles a side: dQ's and dK/dV's loops run one to eight tiles, in iterations
    of eight first (``tile_counts``: 36 visits in 13)."""
    counts = tile_counts(1024, 1024, 128, 128, 1024)
    assert (counts["bwd_dq"], counts["bwd_dkv"]) == ((36, 13), (36, 13))
    q, k, v = _qkv(1, s, 2, 32, dtype, seed=3)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, block_q=block, block_k=block
                                ).astype(jnp.float32) ** 2).sum()

    def loss_dense(q, k, v):
        return (
            causal_dot_attention(q, k, v).astype(jnp.float32) ** 2
        ).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=tol, atol=tol,
        )


def test_flash_gradients_unpadded_sequence():
    """Backward over a padded sequence: pad rows/keys must contribute
    zero gradient (S=200 pads to 256 inside the kernels)."""
    q, k, v = _qkv(1, 200, 2, 32, jnp.float32, seed=5)

    gf = jax.grad(
        lambda a, b, c: (flash_attention(a, b, c) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    gd = jax.grad(
        lambda a, b, c: (causal_dot_attention(a, b, c) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3
        )


def test_flash_non_causal():
    q, k, v = _qkv(1, 256, 2, 64, jnp.float32, seed=2)
    d = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(d))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    ref = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), v)
    out = flash_attention(q, k, v, causal=False)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("causal", [True, False])
def test_flash_sliding_window_matches_dot(causal):
    """Windowed block-skip parity: same values as the windowed dot
    oracle, with the window crossing block boundaries (S=384, 256-blocks,
    W=200) so the skip ranges and tile masks both matter."""
    q, k, v = _qkv(1, 384, 2, 32, jnp.float32, seed=5)
    out = flash_attention(q, k, v, causal=causal, window=200)
    ref = causal_dot_attention(q, k, v, causal=causal, window=200)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_sliding_window_gradients():
    """Windowed backward (skip ranges in both bwd kernels) matches
    autodiff through the windowed dot oracle."""
    q, k, v = _qkv(1, 320, 2, 32, jnp.float32, seed=6)
    gf = jax.grad(
        lambda a, b, c: (
            flash_attention(a, b, c, window=150) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    gd = jax.grad(
        lambda a, b, c: (
            causal_dot_attention(a, b, c, window=150) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3
        )


def test_flash_window_small_blocks():
    """Window much smaller than a block plus a window smaller than the
    sequence tail: every skip-bound edge case in one sweep."""
    for s, w in ((256, 17), (300, 64), (128, 1)):
        q, k, v = _qkv(1, s, 1, 32, jnp.float32, seed=s)
        out = flash_attention(q, k, v, window=w, block_q=128, block_k=128)
        ref = causal_dot_attention(q, k, v, window=w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5, err_msg=f"s={s} w={w}")


def test_flash_non_causal_gradients():
    """Encoder-mode backward through the pallas kernels matches autodiff
    through the dot oracle."""
    q, k, v = _qkv(1, 192, 2, 32, jnp.float32, seed=3)
    gf = jax.grad(
        lambda a, b, c: (flash_attention(a, b, c, causal=False) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    gd = jax.grad(
        lambda a, b, c: (
            causal_dot_attention(a, b, c, causal=False) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3
        )


def test_flash_tiles_event_names_each_kernel_traced():
    """Tracing a kernel leaves one ``flash.tiles`` instant with its name and
    the tile visits and loop iterations (``tile_counts``): a head's, at the
    ``query_tiles_a_program`` consecutive query tiles a forward or dQ program
    walks as one, and for a dK/dV kernel those of the query heads a program
    holds and walks as one, ``heads_a_program`` beside them; the
    block-diffusion kind names its own dK/dV kernel."""
    from horovod_tpu import trace

    def traced(kv_heads=2, **kw):
        t0 = trace.now()
        q = jnp.ones((1, 512, 2, 16), jnp.float32)
        jax.make_jaxpr(jax.grad(lambda a: flash_attention(
            a, a[:, :, :kv_heads], a[:, :, :kv_heads], block_q=128, block_k=128,
            **kw).sum()))(q)
        held = lambda r: {k: r[k] for k in ("heads_a_program", "query_tiles_a_program")
                          if k in r}
        return {r[3]["kernel"]: (r[3]["visited"], r[3]["iterations"], held(r[3]))
                for r in trace.snapshot(t0) if r[0] == "flash.tiles"}

    # each kernel says what a program of its holds: the head's four query tiles
    # (10 visits under the window: one walk of 8 + 2), or its query heads
    a_head = (10, 2, {"query_tiles_a_program": 4})
    assert traced(window=300) == {
        "flash_attention_fwd": a_head, "flash_attention_bwd_dq": a_head,
        "flash_attention_bwd_dkv": (10, 5, {"heads_a_program": 1})}
    # two heads a program: twice the visits in as many iterations
    assert traced(window=300, kv_heads=1)["flash_attention_bwd_dkv"] == (
        20, 5, {"heads_a_program": 2})
    a_head = (8, 1, {"query_tiles_a_program": 4})
    assert traced(block_diffusion=(256, 4)) == {
        "flash_attention_fwd": a_head, "flash_attention_bwd_dq": a_head,
        "flash_attention_bwd_dkv_bd": (8, 4, {"heads_a_program": 1})}
    assert traced(block_diffusion=(256, 4), kv_heads=1) == {
        "flash_attention_fwd": a_head, "flash_attention_bwd_dq": a_head,
        "flash_attention_bwd_dkv_bd": (16, 4, {"heads_a_program": 2})}


# -- 256-wide heads, eight query heads a key/value head (PR 35) ----------------


@pytest.mark.parametrize("one_head", [False, True],
                         ids=["dkv_whole_group", "dkv_one_head_a_program"])
@pytest.mark.parametrize("dtype,s,tol", [(jnp.float32, 300, 2e-4),
                                         (jnp.float32, 1024, 2e-4),
                                         (jnp.bfloat16, 512, 6e-2)])
def test_flash_256_wide_heads_match_dense(monkeypatch, dtype, s, tol, one_head):
    """Forward, dQ and dK/dV at ``d_qk = d_v = 256`` against
    ``causal_dot_attention``, 8 query heads over 1 key/value head (the gated
    attention of the ``qwen3_next`` family), in 128-tiles so that the loops run
    several tiles; dK/dV by both kernels: the whole group a program, and one
    query head a program with the sums in VMEM scratch (what 8 x 8,192 rows of
    256 take: ``_DKV_GROUP_BYTES``)."""
    from horovod_tpu.ops import flash_attention as fa

    if one_head:
        monkeypatch.setattr(fa, "_DKV_GROUP_BYTES", 0)
    ks = jax.random.split(jax.random.PRNGKey(s), 4)
    q = jax.random.normal(ks[0], (1, s, 8, 256), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (1, s, 1, 256), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (1, s, 1, 256), jnp.float32).astype(dtype)
    co = jax.random.normal(ks[3], (1, s, 8, 256), jnp.float32)

    def run(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * co),
            argnums=(0, 1, 2))(q, k, v)

    got = run(lambda q, k, v: flash_attention(q, k, v, block_q=128, block_k=128))
    want = run(causal_dot_attention)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, atol=tol * max(1.0, np.abs(b).max()))


def test_the_one_head_dkv_kernel_is_chosen_by_the_group_s_bytes():
    """Twice-buffered q and dO of a whole query-head group: the accepted cells'
    8 and 10 MiB keep the grouped kernel, the 256-wide cell's 128 MiB do not."""
    from horovod_tpu.ops import flash_attention as fa

    group_bytes = lambda group, s, d, dv: group * 2 * s * (d + dv) * 2
    assert group_bytes(2, 4096, 128, 128) == 8 * 2 ** 20 < fa._DKV_GROUP_BYTES
    assert group_bytes(1, 8192, 192, 128) == 10 * 2 ** 20 < fa._DKV_GROUP_BYTES
    assert group_bytes(8, 8192, 256, 256) == 128 * 2 ** 20 > fa._DKV_GROUP_BYTES
    # the block-diffusion mask goes by the same bytes: the SDAR cell's group of
    # 8 x 8,192 rows of 128 + 128 is AT the threshold and held whole (as
    # Laguna's sliding layers' is), 256-wide heads under the mask are not
    assert group_bytes(8, 8192, 128, 128) == 64 * 2 ** 20 == fa._DKV_GROUP_BYTES
    # the one rule that decides (the kernel's and tools/flash_bench.py's): the
    # heads a program holds, and a head's bytes
    assert fa._dkv_heads_a_program(8, 8192, 128, 128, 2) == (8, 8 * 2 ** 20)
    assert fa._dkv_heads_a_program(8, 8192, 256, 256, 2) == (1, 16 * 2 ** 20)
    assert fa._dkv_heads_a_program(1, 8192, 192, 128, 2) == (1, 10 * 2 ** 20)
    from horovod_tpu import trace

    def heads_a_program(heads, kv_heads, width):
        """What the dK/dV kernel traced at 8,192 rows under the mask says."""
        t0 = trace.now()
        q = jax.ShapeDtypeStruct((1, 8192, heads, width), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((1, 8192, kv_heads, width), jnp.bfloat16)
        jax.eval_shape(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, block_diffusion=(4096, 4), interpret=True).astype(jnp.float32))),
            q, kv, kv)
        (event,) = [r[3] for r in trace.snapshot(t0) if r[0] == "flash.tiles"
                    and r[3]["kernel"] == "flash_attention_bwd_dkv_bd"]
        return event["heads_a_program"]

    assert heads_a_program(32, 4, 128) == 8
    assert heads_a_program(16, 2, 256) == 1
    # and the forward / dQ kernels state their VMEM only beyond the compiler's own
    assert fa._kv_params(8192, 192, 128, jnp.bfloat16) == {}
    assert fa._kv_params(8192, 128, 128, jnp.bfloat16) == {}
    stated = fa._kv_params(8192, 256, 256, jnp.bfloat16)["compiler_params"]
    assert stated.vmem_limit_bytes == 16 * 2 ** 20 + fa._VMEM_HEADROOM
