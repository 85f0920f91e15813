"""The rotary step as a Mosaic kernel pair (``ops/rope_kernel.py``, PR 40;
interpreted here): q and k rotated on whole 128-wide heads in one pass a
direction, against ``rope`` / ``_rotary``, the ``jnp`` functions 'dot' models
run: values and the gradient of a scalar of both, at the cells' head counts and
rotary forms; and the shapes that must keep ``rope``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import trace
from horovod_tpu.models import transformer
from horovod_tpu.models.transformer import TransformerConfig
from horovod_tpu.ops import rope_kernel

YARN = dict(theta=5e5, partial_rotary_factor=0.5, factor=64.0,
            original_max_position_embeddings=16, beta_fast=64.0, beta_slow=1.0,
            attention_factor=1.4158883083359672)


def _cfg(heads, kv_heads, head_dim, impl="flash", **kw):
    return TransformerConfig(num_heads=heads, num_kv_heads=kv_heads, head_dim=head_dim,
                             hidden_size=64, attention_impl=impl, **kw)


def _arange(b, s):
    return jnp.broadcast_to(jnp.arange(s), (b, s))


def _two_halves(b, s):
    """SDAR's: ``[0..L) || [0..L)``."""
    return jnp.broadcast_to(jnp.tile(jnp.arange(s // 2), 2), (b, s))


def _scattered(b, s):
    """Given positions that are no ``arange``: each row its own, unordered."""
    return jax.random.randint(jax.random.PRNGKey(5), (b, s), 0, 5000)


# heads, key/value heads, head width, rows, positions, the model's RoPE
CASES = {
    "a_64_over_8_whole_head_plain": (64, 8, 128, 32, _arange, {}),
    "b_48_over_8_yarn_on_64_of_128": (
        48, 8, 128, 32, _arange, dict(rope_parameters={"full_attention": YARN})),
    "c_16_over_2_at_256_wide_rot_64": (
        16, 2, 256, 32, _arange, dict(rope_theta=1e7, partial_rotary_factor=0.25)),
    "d_32_over_4_two_halves_of_positions": (32, 4, 128, 64, _two_halves, dict(rope_theta=1e6)),
    "e_positions_no_arange": (4, 2, 128, 48, _scattered, {}),
    # 208 = 13 x 16 rows: no tile but 16 and 208 divides them
    "f_rows_no_multiple_of_a_larger_tile": (4, 2, 128, 208, _arange, {}),
}


def _both(case, dtype, batch=2):
    heads, kv_heads, d, s, positions, kw = CASES[case]
    cfg = _cfg(heads, kv_heads, d, **kw)
    own = cfg.layer_rope("full_attention")
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 4)
    q = jax.random.normal(keys[0], (batch, s, heads, d)).astype(dtype)
    k = jax.random.normal(keys[1], (batch, s, kv_heads, d)).astype(dtype)
    co = (jax.random.normal(keys[2], q.shape), jax.random.normal(keys[3], k.shape))
    pos = positions(batch, s)

    def scalar(fn):
        def of(q, k):
            out = fn(q, k)
            return sum(jnp.sum(o.astype(jnp.float32) * c) for o, c in zip(out, co)), out
        return jax.value_and_grad(of, argnums=(0, 1), has_aux=True)(q, k)

    want = scalar(lambda q, k: tuple(transformer._rotary(cfg, x, pos, own) for x in (q, k)))
    got = scalar(lambda q, k: transformer._rotary_qk(cfg, q, k, pos, own))
    return cfg, want, got


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_is_rope_in_float32_values_and_gradients(case, monkeypatch):
    if case.startswith("f_"):
        # a tile's bytes allow 64 rows of these 512 columns: 16 is what divides 208
        monkeypatch.setattr(rope_kernel, "_TILE_BYTES", 64 * 512 * 4)
        assert rope_kernel.tile_rows(208, 512, 4) == 16
    _, ((_, want), want_grads), ((_, got), grads) = _both(case, jnp.float32, batch=1)
    for a, b in zip(got + grads, want + want_grads):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, atol=1e-6 * float(jnp.max(jnp.abs(b))))


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_rounds_to_bfloat16_where_rope_rounds(case):
    """bf16 in and bf16 out: equal to ``rope``'s to the last place or one (the
    same float32 arithmetic; a compiler may fuse a multiply and an add)."""
    _, ((_, want), want_grads), ((_, got), grads) = _both(case, jnp.bfloat16)
    for a, b in zip(got + grads, want + want_grads):
        assert a.dtype == jnp.bfloat16 == b.dtype
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        # one bf16 place of b at most (a sum that cancels keeps float32's own error)
        assert bool(jnp.all(jnp.abs(a - b) <= 2.0 ** -7 * jnp.abs(b) + 1e-6))
        assert float(jnp.mean(a != b)) < 0.01


def test_partial_rotation_passes_the_other_columns_bit_for_bit():
    heads, kv_heads, d, s, positions, kw = CASES["b_48_over_8_yarn_on_64_of_128"]
    cfg = _cfg(heads, kv_heads, d, **kw)
    q = jax.random.normal(jax.random.PRNGKey(0), (1, s, heads, d)).astype(jnp.bfloat16)
    k = q[:, :, :kv_heads]
    out_q, out_k = transformer._rotary_qk(cfg, q, k, positions(1, s),
                                          cfg.layer_rope("full_attention"))
    np.testing.assert_array_equal(out_q[..., 64:], q[..., 64:])
    np.testing.assert_array_equal(out_k[..., 64:], k[..., 64:])
    assert not bool(jnp.all(out_q[..., :64] == q[..., :64]))


def _traced(cfg, s):
    """The rotary step's jaxpr, as text: a kernel shows as ``pallas_call`` with its
    name (the lowered text of an interpreted kernel holds neither)."""
    own = cfg.layer_rope("full_attention")
    shape = lambda heads: jax.ShapeDtypeStruct((1, s, heads, cfg.head_dim), jnp.bfloat16)
    return str(jax.make_jaxpr(
        lambda q, k, pos: transformer._rotary_qk(cfg, q, k, pos, own))(
            shape(cfg.num_heads), shape(cfg.num_kv_heads),
            jax.ShapeDtypeStruct((1, s), jnp.int32)))


@pytest.mark.parametrize("why,cfg,s", [
    ("a_192_wide_head", _cfg(4, 2, 192), 32),
    ("a_64_wide_head", _cfg(4, 2, 64), 32),
    ("attention_impl_dot", _cfg(4, 2, 128, impl="dot"), 32),
    ("the_ring", _cfg(4, 2, 128, impl="ring"), 32),
    ("a_decode_step_s_one_row", _cfg(4, 2, 128), 1),
    ("rows_no_multiple_of_16", _cfg(4, 2, 128), 40),
], ids=lambda x: x if isinstance(x, str) else "")
def test_shapes_that_must_not_engage_keep_rope(why, cfg, s):
    text = _traced(cfg, s)
    assert "rope_fwd" not in text and "pallas_call" not in text, why


def test_shapes_that_engage_trace_one_call_a_tensor():
    text = _traced(_cfg(4, 2, 128), 32)
    assert text.count("pallas_call") == 2 == text.count("name=rope_fwd")


def test_engages_is_the_lane_arithmetic_s_needs_and_rotate_refuses_by_name():
    assert rope_kernel.engages((1, 8192, 64, 128), 128)
    assert rope_kernel.engages((1, 8192, 16, 256), 64)
    assert not rope_kernel.engages((1, 8192, 16, 192), 64)      # Kimi's heads
    assert not rope_kernel.engages((1, 8192, 16, 128), 63)
    assert not rope_kernel.engages((1, 8192, 16, 128), 256)
    assert not rope_kernel.engages((1, 1, 16, 128), 128)
    assert not rope_kernel.engages((8192, 16, 128), 128)
    x = jnp.zeros((1, 32, 2, 128))
    c, s = rope_kernel.tables(_arange(1, 32), transformer.rope_frequencies(128, 1e4), 128)
    assert c.shape == s.shape == (1, 32, 128)
    with pytest.raises(ValueError, match="multiple of 128"):
        rope_kernel.rotate(jnp.zeros((1, 32, 2, 64)), c, s, 64)
    with pytest.raises(ValueError, match="tables of"):
        rope_kernel.rotate(x, c[:, :16], s[:, :16], 128)
    with pytest.raises(ValueError, match="row_tile is a multiple of 16 that divides 32"):
        rope_kernel.rotate(x, c, s, 128, row_tile=24)
    # the tile: within 4 MiB of the rows' bytes and 512 rows, dividing S
    assert rope_kernel.tile_rows(8192, 64 * 128, 2) == 256
    assert rope_kernel.tile_rows(8192, 48 * 128, 2) == 256
    assert rope_kernel.tile_rows(8192, 8 * 128, 2) == 512
    assert rope_kernel.tile_rows(48, 8 * 128, 2) == 48


def test_rope_rotate_event_says_where_the_kernel_engaged():
    """``rope.rotate`` at the cell's full layer (48 heads over 8, YaRN on 64 of
    128, 8,192 rows), once with the kernels and once as a 'dot' model."""
    heads, kv_heads, d, _, _, kw = CASES["b_48_over_8_yarn_on_64_of_128"]
    q = jax.ShapeDtypeStruct((1, 8192, heads, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 8192, kv_heads, d), jnp.bfloat16)
    t0 = trace.now()
    for impl in ("flash", "dot"):
        cfg = _cfg(heads, kv_heads, d, impl=impl, **kw)
        jax.eval_shape(lambda q, k: transformer._rotary_qk(
            cfg, q, k, _arange(1, 8192), cfg.layer_rope("full_attention")), q, k)
    on, off = [r[3] for r in trace.snapshot(t0) if r[0] == "rope.rotate"]
    # q and k read and written once in bf16; the two 128-wide float32 tables read
    # once a tensor; q in tiles of 256 rows (3 MiB), k in tiles of 512
    assert on == dict(rows=8192, heads=48, kv_heads=8, head_dim=128, rot=64, rope_type="yarn",
                      kernel=True, row_tile=256, programs=32 + 16,
                      hbm_bytes=2 * 8192 * 56 * 128 * 2 + 2 * 2 * 8192 * 128 * 4)
    assert off == dict(on, kernel=False, row_tile=0, programs=0, hbm_bytes=0)
