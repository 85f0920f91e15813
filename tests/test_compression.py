"""compression.py unit tests — the module's first (ISSUE 7 satellite).

Covers the whole-tensor :class:`Compression` contract (reference:
horovod/torch/compression.py) and the new DCN-hop
:class:`DcnCompression` shard contract: pytree roundtrips, mixed
float/int leaves, fp64 leaves, the fp16 finite-range clamp, and the
error-feedback residual algebra.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.compression import (
    Compression,
    DcnCompression,
    dcn_compression_from_name,
)


def _tree():
    return {
        "w": jnp.asarray(np.linspace(-2.0, 2.0, 12, dtype=np.float32)),
        "b": (jnp.asarray([1.5, -0.25], jnp.float32),
              jnp.asarray([3, -7], jnp.int32)),
        "step": jnp.asarray(11, jnp.int32),
    }


class TestCompression:
    @pytest.mark.parametrize("comp,wire", [
        (Compression.fp16, jnp.float16),
        (Compression.bf16, jnp.bfloat16),
    ])
    def test_pytree_roundtrip_casts_only_wide_floats(self, comp, wire):
        tree = _tree()
        wired, ctx = comp.compress(tree)
        assert wired["w"].dtype == wire
        assert wired["b"][0].dtype == wire
        # non-float leaves ride through untouched
        assert wired["b"][1].dtype == jnp.int32
        assert wired["step"].dtype == jnp.int32
        out = comp.decompress(wired, ctx)
        assert jax.tree_util.tree_structure(out) == \
            jax.tree_util.tree_structure(tree)
        assert out["w"].dtype == jnp.float32
        np.testing.assert_allclose(
            np.asarray(out["w"]), np.asarray(tree["w"]), rtol=1e-2)
        np.testing.assert_array_equal(
            np.asarray(out["b"][1]), np.asarray(tree["b"][1]))

    def test_none_compressor_is_identity(self):
        tree = _tree()
        wired, ctx = Compression.none.compress(tree)
        assert wired is tree and ctx is None
        assert Compression.none.decompress(wired, ctx) is tree

    def test_fp64_leaves_compress_and_restore(self):
        with jax.enable_x64(True):
            x = {"p": jnp.asarray([1.0, -2.5], jnp.float64)}
            assert x["p"].dtype == jnp.float64
            wired, ctx = Compression.bf16.compress(x)
            assert wired["p"].dtype == jnp.bfloat16
            out = Compression.bf16.decompress(wired, ctx)
            assert out["p"].dtype == jnp.float64

    def test_fp16_overflow_clamps_to_finite(self):
        # fp16 max finite is 65504: a large fp32 gradient must saturate,
        # not become inf and poison the whole reduction (ISSUE 7)
        big = jnp.asarray([1e6, -1e6, 3.0], jnp.float32)
        wired, ctx = Compression.fp16.compress(big)
        w = np.asarray(wired, np.float32)
        assert np.isfinite(w).all(), w
        lim = float(np.finfo(np.float16).max)
        np.testing.assert_allclose(w[:2], [lim, -lim])
        out = np.asarray(Compression.fp16.decompress(wired, ctx))
        assert np.isfinite(out).all()

    def test_bf16_keeps_fp32_range(self):
        # bf16 shares fp32's exponent: the same magnitudes stay exact in
        # range — the documented recommendation over fp16
        big = jnp.asarray([1e6, -3e38], jnp.float32)
        wired, _ = Compression.bf16.compress(big)
        assert np.isfinite(np.asarray(wired, np.float32)).all()


class TestDcnCompression:
    def test_shard_roundtrip(self):
        comp = DcnCompression("bfloat16")
        shard = jnp.asarray(np.linspace(-4, 4, 64, dtype=np.float32))
        wire, residual = comp.compress_shard(shard)
        assert wire.dtype == jnp.bfloat16
        assert residual is None  # error feedback off
        back = comp.decompress_shard(wire, shard.dtype)
        assert back.dtype == jnp.float32
        np.testing.assert_allclose(
            np.asarray(back), np.asarray(shard), rtol=1e-2)

    def test_narrow_and_int_shards_pass_through(self):
        comp = DcnCompression("bfloat16")
        for shard in (jnp.asarray([1, 2], jnp.int32),
                      jnp.asarray([1.0, 2.0], jnp.bfloat16),
                      jnp.asarray([1.0], jnp.float16)):
            wire, _ = comp.compress_shard(shard)
            assert wire.dtype == shard.dtype

    def test_fp16_wire_clamps(self):
        comp = DcnCompression("float16")
        wire, _ = comp.compress_shard(jnp.asarray([1e9, -1e9], jnp.float32))
        assert np.isfinite(np.asarray(wire, np.float32)).all()

    def test_error_feedback_residual_algebra(self):
        comp = DcnCompression("bfloat16", error_feedback=True)
        shard = jnp.asarray(
            np.random.RandomState(0).randn(128).astype(np.float32))
        wire, res = comp.compress_shard(shard, None)
        # residual IS the quantization error of this step
        np.testing.assert_allclose(
            np.asarray(res),
            np.asarray(shard) - np.asarray(wire, np.float32),
            rtol=0, atol=0,
        )
        # next step: the carried residual is added back before the cast,
        # so the two-step wire sum tracks the two-step true sum to within
        # ONE quantization error, not two (the EF-SGD invariant:
        # sum(wire_i) == sum(shard_i) - res_final)
        wire2, res2 = comp.compress_shard(shard, res)
        total_wire = np.asarray(wire, np.float64) + np.asarray(
            wire2, np.float64)
        total_true = 2.0 * np.asarray(shard, np.float64)
        np.testing.assert_allclose(
            total_wire + np.asarray(res2, np.float64), total_true,
            rtol=1e-6,
        )

    def test_rejects_non_float_wire(self):
        with pytest.raises(ValueError):
            DcnCompression("int8")

    def test_from_name(self):
        assert dcn_compression_from_name(None) is None
        assert dcn_compression_from_name("") is None
        assert dcn_compression_from_name("none") is None
        assert dcn_compression_from_name("off") is None
        c = dcn_compression_from_name("bf16")
        assert c is not None and c.wire_dtype == jnp.bfloat16
        assert not c.error_feedback  # routed path is stateless
        assert dcn_compression_from_name("fp16").wire_dtype == jnp.float16
        assert dcn_compression_from_name("float16").wire_dtype == jnp.float16

    def test_from_name_garbled_warns_and_disables(self):
        # env convention (env_float): a typo'd knob falls back instead
        # of killing the first routed collective of a long job
        from horovod_tpu import compression as C

        assert dcn_compression_from_name("bf61") is None  # typo of bf16
        assert dcn_compression_from_name("int8") is None  # non-float
        # wider-or-equal wires are silent no-ops, not compression
        assert dcn_compression_from_name("float32") is None
        assert dcn_compression_from_name("float64") is None
        # warned once per spelling, not per collective (the resolver
        # runs on every routed call)
        assert {"bf61", "int8", "float32"} <= C._warned_wire_dtypes
