"""Eager collective API tests (single-process degenerate semantics +
handle/async machinery + fusion).

Reference analog: the np=1 cases of test/parallel/test_torch.py plus the
handle tests (allreduce_async/synchronize/poll).  Multi-process eager paths
get exercised by the tpurun integration tests.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.ops.fusion import FusionPlan, fuse, unfuse


def test_allreduce_identity_single():
    x = jnp.arange(6, dtype=jnp.float32).reshape(2, 3)
    np.testing.assert_allclose(np.asarray(hvd.allreduce(x)), np.asarray(x))
    np.testing.assert_allclose(
        np.asarray(hvd.allreduce(x, op=hvd.Sum)), np.asarray(x)
    )


def test_allreduce_scaling():
    x = jnp.ones((4,), jnp.float32)
    out = hvd.allreduce(x, op=hvd.Sum, prescale_factor=0.5,
                        postscale_factor=4.0)
    np.testing.assert_allclose(np.asarray(out), 2.0 * np.ones(4))


def test_allreduce_pytree():
    tree = {"a": jnp.ones((3,)), "b": {"c": jnp.zeros((2, 2))}}
    out = hvd.allreduce(tree)
    assert set(out) == {"a", "b"}
    np.testing.assert_allclose(np.asarray(out["b"]["c"]), np.zeros((2, 2)))


def test_async_handle():
    x = jnp.ones((8,), jnp.float32)
    h = hvd.allreduce_async(x)
    assert isinstance(h, hvd.Handle)
    out = hvd.synchronize(h)
    np.testing.assert_allclose(np.asarray(out), np.ones(8))
    assert hvd.poll(h)


def test_grouped_allreduce():
    ts = [jnp.ones((2,)), jnp.full((3,), 2.0)]
    outs = hvd.grouped_allreduce(ts, op=hvd.Sum)
    assert len(outs) == 2
    np.testing.assert_allclose(np.asarray(outs[1]), [2.0, 2.0, 2.0])


def test_allgather_single():
    x = jnp.arange(4).reshape(2, 2)
    np.testing.assert_array_equal(np.asarray(hvd.allgather(x)), np.asarray(x))


def test_broadcast_single():
    x = jnp.arange(3.0)
    np.testing.assert_allclose(np.asarray(hvd.broadcast(x, 0)), np.asarray(x))
    with pytest.raises(ValueError):
        hvd.broadcast(x, root_rank=99)


def test_alltoall_single():
    x = jnp.arange(8.0)
    out, splits = hvd.alltoall(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))
    assert int(np.asarray(splits)[0]) == 8


def test_reducescatter_single():
    x = jnp.arange(8.0)
    np.testing.assert_allclose(np.asarray(hvd.reducescatter(x)),
                               np.asarray(x))


def test_barrier_and_join_single():
    hvd.barrier()
    assert hvd.join() == hvd.rank()


def test_broadcast_parameters_and_object():
    params = {"w": jnp.ones((3, 3)), "b": jnp.zeros((3,))}
    out = hvd.broadcast_parameters(params, root_rank=0)
    np.testing.assert_allclose(np.asarray(out["w"]), np.ones((3, 3)))
    obj = {"epoch": 3, "name": "x"}
    assert hvd.broadcast_object(obj, 0) == obj
    assert hvd.allgather_object(obj) == [obj]


def test_op_average_conflict():
    with pytest.raises(ValueError):
        hvd.allreduce(jnp.ones(2), average=True, op=hvd.Sum)


def test_fusion_roundtrip():
    leaves = [
        jnp.arange(5, dtype=jnp.float32),
        jnp.ones((2, 3), jnp.float32),
        jnp.arange(4, dtype=jnp.int32),
        jnp.zeros((1,), jnp.float32),
    ]
    plan = FusionPlan(leaves, threshold_bytes=1 << 20)
    fused = fuse(leaves, plan)
    # one f32 bucket + one i32 bucket
    assert len(fused) == 2
    out = unfuse(fused, plan)
    for a, b in zip(leaves, out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.dtype == b.dtype


def test_fusion_threshold_splits_buckets():
    leaves = [jnp.ones((1024,), jnp.float32) for _ in range(4)]
    plan = FusionPlan(leaves, threshold_bytes=4096)  # one tensor per bucket
    assert len(plan.buckets) == 4
    fused = fuse(leaves, plan)
    out = unfuse(fused, plan)
    assert len(out) == 4


def test_fusion_deterministic_signature():
    leaves = [jnp.ones((3,)), jnp.ones((4,), jnp.int32)]
    p1 = FusionPlan(leaves, 64)
    p2 = FusionPlan(leaves, 64)
    assert p1.signature() == p2.signature()
    assert [b[1] for b in p1.buckets] == [b[1] for b in p2.buckets]


def test_prescale_rejected_for_min():
    with pytest.raises(ValueError):
        hvd.allreduce(jnp.ones(2), op=hvd.Min, prescale_factor=2.0)


def test_fusion_threshold_zero_disables_fusion():
    leaves = [jnp.ones((4,), jnp.float32), jnp.ones((4,), jnp.float32)]
    plan = FusionPlan(leaves, threshold_bytes=0)
    assert len(plan.buckets) == 2  # one bucket per tensor
    out = unfuse(fuse(leaves, plan), plan)
    np.testing.assert_array_equal(np.asarray(out[0]), np.ones(4))


def test_scalar_allreduce_preserves_zero_d_shape():
    """0-d inputs round-trip as 0-d through the native fused path
    (regression: ascontiguousarray promotes 0-d to 1-d; the unpack
    reshape must use the original shape)."""
    out = hvd.allreduce(jnp.asarray(3.0), name="scalar_rt", op=hvd.Sum)
    assert np.asarray(out).shape == ()
    assert float(out) == 3.0


def test_profiler_bridge_spans_in_xplane_capture(tmp_path):
    """The jax.profiler bridge (the controller's trace.span) puts
    ENQUEUE/XLA_COMM spans into an XPlane capture with the same names the Chrome timeline
    uses — SURVEY.md §5.1's 'framework spans next to XLA ops' view."""
    import glob
    import gzip
    import json

    logdir = str(tmp_path / "trace")
    x = jnp.arange(1024, dtype=jnp.float32)
    hvd.allreduce(x, name="bridge_warm")  # compile outside the capture
    jax.profiler.start_trace(logdir)
    try:
        out = hvd.allreduce(x, name="bridge_probe", op=hvd.Sum)
        jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    traces = glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.trace.json.gz")
    )
    assert traces, "no trace file produced"
    with gzip.open(traces[0]) as f:
        events = json.load(f)["traceEvents"]
    names = {str(e.get("name", "")) for e in events}
    assert any("hvd_tpu::bridge_probe" in n and "ENQUEUE" in n
               for n in names), sorted(n for n in names if "hvd" in n)
    assert any("hvd_tpu::bridge_probe" in n and "XLA_COMM" in n
               for n in names), sorted(n for n in names if "hvd" in n)


def test_grouped_reducescatter_single():
    """np=1 degenerate: each entry's full reduction is its own chunk
    (reference: torch grouped_reducescatter surface)."""
    a, b = jnp.arange(6.0), jnp.ones((4,)) * 3.0
    ra, rb = hvd.grouped_reducescatter([a, b], op=hvd.Sum, name="grs1")
    np.testing.assert_allclose(np.asarray(ra), np.asarray(a))
    np.testing.assert_allclose(np.asarray(rb), np.asarray(b))


def test_build_capability_flags():
    """Reference: horovod/common/basics.py capability probes — scripts
    branch on these; every backend the reference can report is answered
    honestly (XLA yes, everything else no)."""
    assert hvd.xla_built()
    for probe in (hvd.nccl_built, hvd.mpi_built, hvd.mpi_enabled,
                  hvd.mpi_threads_supported, hvd.gloo_built,
                  hvd.gloo_enabled, hvd.ccl_built, hvd.cuda_built,
                  hvd.rocm_built, hvd.ddl_built):
        assert probe() is False, probe
