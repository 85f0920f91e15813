"""Backward/collective overlap scheduler (ISSUE 11, docs/tensor-fusion.md).

Covers, against the 8-virt-device session mesh:

* ``BucketSchedule`` determinism (permuted-but-equal leaf lists build the
  identical layout), reverse-production launch order, and the
  threshold-sensitive ``signature()`` (executable-cache collision guard);
* strict env validation of ``HVD_TPU_FUSION_THRESHOLD`` and the overlap/
  autotune knobs;
* the overlap oracle — gradients and optimizer updates bit-equal between
  overlapped and unoverlapped steps at fp32, ZeRO on and off, replicated
  and multi-axis (tp-sharded) alike;
* the StableHLO interleave check: each bucket's collective pinned between
  segment computations (``overlap_inventory``), with the unoverlapped
  program as the trailing negative control;
* the PR-7 ``measured_tier_bytes`` inventory idiom on the hierarchical
  (2-slice) overlapped program: modeled == measured per tier;
* ``BucketAutotuner`` convergence, default-never-regresses, budget
  exhaustion, and metric side effects;
* the torch bridge's deterministic bucket-ordered submission.
"""

import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import training
from horovod_tpu.common.topology import DCN_AXIS, ICI_AXIS, WORLD_AXIS
from horovod_tpu.metrics import instruments as _metrics
from horovod_tpu.models.transformer import (
    Transformer, gpt_tiny, overlap_segments,
)
from horovod_tpu.ops import spmd_ops
from horovod_tpu.ops.comm_model import (
    compiled_collective_counts,
    measured_tier_bytes, mesh_slice_ids, modeled_collective_bytes,
    modeled_overlap_exposed, overlap_inventory,
)
from horovod_tpu.ops.fusion import BucketSchedule, FusionPlan
from horovod_tpu.ops.overlap import (
    BucketAutotuner, Candidate, Segment, overlapped_value_and_grad,
    record_overlap_metrics, used_leaf_mask,
)


def _leaves(specs):
    return [jnp.zeros(s, d) for s, d in specs]


def _tree_max_diff(a, b):
    return max(
        jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            lambda x, y: float(np.abs(np.asarray(x) - np.asarray(y)).max()),
            a, b,
        ))
    )


def _tree_bit_equal(a, b):
    return all(
        (np.asarray(x) == np.asarray(y)).all()
        for x, y in zip(
            jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
        )
    )


# -- BucketSchedule ----------------------------------------------------------


class TestBucketSchedule:
    SPECS = [
        ((64, 64), jnp.float32),   # 16 KiB
        ((32,), jnp.float32),
        ((64, 64), jnp.bfloat16),  # 8 KiB
        ((128, 64), jnp.float32),  # 32 KiB
        ((16, 16), jnp.float32),
    ]

    def test_permuted_but_equal_lists_build_identical_layout(self):
        leaves = _leaves(self.SPECS)
        order = list(range(len(leaves)))[::-1]  # explicit production order
        a = BucketSchedule(leaves, 20 * 1024, production_order=order)
        perm = [3, 0, 4, 1, 2]
        b = BucketSchedule(
            [leaves[i] for i in perm], 20 * 1024,
            production_order=[order[i] for i in perm],
        )
        assert a.layout() == b.layout()
        assert a.ready_at == b.ready_at
        assert a.bucket_nbytes == b.bucket_nbytes

    def test_reverse_production_launch_order(self):
        # default production order: reversed list order -> the LAST leaf
        # completes first and its bucket launches first
        leaves = _leaves([((8, 8), jnp.float32)] * 4)
        sched = BucketSchedule(leaves, 8 * 8 * 4)  # one leaf per bucket
        launch_leaves = [idxs[0] for _, idxs in sched.buckets]
        assert launch_leaves == [3, 2, 1, 0]
        assert sched.ready_at == [0, 1, 2, 3]

    def test_buckets_pack_consecutive_production_under_threshold(self):
        leaves = _leaves([((8, 8), jnp.float32)] * 6)  # 256 B each
        sched = BucketSchedule(leaves, 512)
        assert sched.num_buckets == 3
        assert all(n == 512 for n in sched.bucket_nbytes)
        # members of one bucket are consecutively produced
        for _, idxs in sched.buckets:
            prods = sorted(sched.production_order[i] for i in idxs)
            assert prods == list(range(prods[0], prods[0] + len(prods)))

    def test_zero_threshold_one_bucket_per_leaf(self):
        leaves = _leaves(self.SPECS)
        sched = BucketSchedule(leaves, 0)
        assert sched.num_buckets == len(leaves)

    def test_signature_distinguishes_thresholds(self):
        leaves = _leaves(self.SPECS)
        # the executable-cache collision guard: same leaves, different
        # HVD_TPU_FUSION_THRESHOLD -> different signature, for the plan
        # AND the schedule
        assert FusionPlan(leaves, 1 << 20).signature() != \
            FusionPlan(leaves, 1 << 10).signature()
        assert BucketSchedule(leaves, 1 << 20).signature() != \
            BucketSchedule(leaves, 1 << 10).signature()
        # and stays deterministic for equal inputs
        assert FusionPlan(leaves, 64).signature() == \
            FusionPlan(leaves, 64).signature()
        assert BucketSchedule(leaves, 64).signature() == \
            BucketSchedule(leaves, 64).signature()

    def test_from_specs_matches_array_build(self):
        leaves = _leaves(self.SPECS)
        a = BucketSchedule(leaves, 20 * 1024)
        b = BucketSchedule.from_specs(
            [(s, str(jnp.dtype(d))) for s, d in self.SPECS], 20 * 1024
        )
        assert a.layout() == b.layout()


# -- env validation ----------------------------------------------------------


class TestEnvValidation:
    def _from_env(self, monkeypatch, name, value):
        from horovod_tpu.utils.env_parser import Config

        monkeypatch.setenv(name, value)
        return Config.from_env()

    def test_garbage_fusion_threshold_rejected(self, monkeypatch):
        with pytest.raises(ValueError, match="FUSION_THRESHOLD"):
            self._from_env(monkeypatch, "HVD_TPU_FUSION_THRESHOLD", "64MB")

    def test_negative_fusion_threshold_rejected(self, monkeypatch):
        with pytest.raises(ValueError, match="FUSION_THRESHOLD"):
            self._from_env(monkeypatch, "HVD_TPU_FUSION_THRESHOLD", "-1")

    def test_zero_threshold_still_disables_fusion(self, monkeypatch):
        cfg = self._from_env(monkeypatch, "HVD_TPU_FUSION_THRESHOLD", "0")
        assert cfg.fusion_threshold_bytes == 0

    def test_overlap_bucket_bytes_validated(self, monkeypatch):
        with pytest.raises(ValueError, match="OVERLAP_BUCKET_BYTES"):
            self._from_env(
                monkeypatch, "HVD_TPU_OVERLAP_BUCKET_BYTES", "4MiB")
        cfg = self._from_env(
            monkeypatch, "HVD_TPU_OVERLAP_BUCKET_BYTES", "1048576")
        assert cfg.overlap_bucket_bytes == 1 << 20

    def test_autotune_trials_must_be_positive(self, monkeypatch):
        with pytest.raises(ValueError, match="OVERLAP_AUTOTUNE_TRIALS"):
            self._from_env(
                monkeypatch, "HVD_TPU_OVERLAP_AUTOTUNE_TRIALS", "0")


# -- the overlapped chain ----------------------------------------------------


def _mlp_chain(n_seg=4, d=16):
    rs = np.random.RandomState(0)
    params = {
        f"w{k}": jnp.asarray(np.round(rs.randn(d, d) * 8) / 8, jnp.float32)
        for k in range(n_seg)
    }

    def make(k):
        def seg(p, x):
            return jax.nn.relu(x @ p[f"w{k}"])

        return Segment(seg, keys=(f"w{k}",))

    def head(p, x):
        return jnp.mean((x @ p[f"w{n_seg - 1}"]) ** 2)

    segments = [make(k) for k in range(n_seg - 1)] + [
        Segment(head, keys=(f"w{n_seg - 1}",))
    ]
    x = jnp.asarray(
        np.round(rs.randn(hvd.size() * 2, d) * 8) / 8, jnp.float32
    )
    return segments, params, x


def _chain_fn(segments, world, bucket_bytes, overlap):
    def f(p, x):
        loss, grads, _ = overlapped_value_and_grad(
            segments, p, x,
            bucket_reduce=lambda b: jax.lax.psum(b, WORLD_AXIS)
            / jnp.asarray(world, b.dtype),
            bucket_bytes=bucket_bytes, overlap=overlap,
        )
        return loss, grads

    return jax.jit(jax.shard_map(
        f, mesh=hvd.world_mesh(), in_specs=(P(), P(WORLD_AXIS)),
        out_specs=(P(), P()), check_vma=False,
    ))


class TestOverlappedChain:
    def test_used_leaf_mask_detects_reads(self):
        params = {"a": jnp.ones((3,)), "b": jnp.ones((3,))}
        mask = used_leaf_mask(lambda p, x: p["a"] * x, params,
                              jnp.ones((3,)))
        # leaves flatten alphabetically: a, b
        assert mask == [True, False]

    def test_bare_callables_auto_detect(self):
        # segments WITHOUT declared keys take the jaxpr-analysis path
        segments, params, x = _mlp_chain()
        bare = [Segment(s.fn) for s in segments]
        world = hvd.size()
        f_decl = _chain_fn(segments, world, 1 << 10, True)
        f_auto = _chain_fn(bare, world, 1 << 10, True)
        l1, g1 = f_decl(params, x)
        l2, g2 = f_auto(params, x)
        assert float(l1) == float(l2)
        assert _tree_bit_equal(g1, g2)

    def test_grads_bit_equal_and_match_plain_grad(self):
        segments, params, x = _mlp_chain()
        world = hvd.size()
        f_ov = _chain_fn(segments, world, 1 << 10, True)
        f_un = _chain_fn(segments, world, 1 << 10, False)
        l1, g1 = f_ov(params, x)
        l2, g2 = f_un(params, x)
        assert float(l1) == float(l2)
        assert _tree_bit_equal(g1, g2)

        def plain(p, xx):
            def loss_fn(pp):
                h = xx
                for k in range(3):
                    h = jax.nn.relu(h @ pp[f"w{k}"])
                return jnp.mean((h @ pp["w3"]) ** 2)

            loss, grads = jax.value_and_grad(loss_fn)(p)
            return loss, jax.tree_util.tree_map(
                lambda t: jax.lax.psum(t, WORLD_AXIS)
                / jnp.asarray(world, t.dtype),
                grads,
            )

        f_plain = jax.jit(jax.shard_map(
            plain, mesh=hvd.world_mesh(), in_specs=(P(), P(WORLD_AXIS)),
            out_specs=(P(), P()), check_vma=False,
        ))
        l3, g3 = f_plain(params, x)
        assert float(l1) == float(l3)
        assert _tree_bit_equal(g1, g3)

    def test_stablehlo_interleave_and_negative_control(self):
        segments, params, x = _mlp_chain()
        world = hvd.size()
        f_ov = _chain_fn(segments, world, 1 << 10, True)
        f_un = _chain_fn(segments, world, 1 << 10, False)
        inv_ov = overlap_inventory(f_ov.lower(params, x).as_text())
        inv_un = overlap_inventory(f_un.lower(params, x).as_text())
        # every non-final bucket's collective has compute after it...
        assert inv_ov["interleaved"]
        assert all(
            op["compute_after"] > 0 for op in inv_ov["collectives"][:-1]
        )
        assert inv_ov["exposed_fraction"] < 1.0
        # ...while the unoverlapped control trails everything
        assert not inv_un["interleaved"]
        assert inv_un["exposed_fraction"] == 1.0
        assert all(
            op["compute_after"] == 0 for op in inv_un["collectives"]
        )

    def test_record_overlap_metrics_sets_gauge(self):
        segments, params, x = _mlp_chain()
        f_ov = _chain_fn(segments, hvd.size(), 1 << 10, True)
        inv = record_overlap_metrics(f_ov.lower(params, x).as_text())
        assert _metrics.OVERLAP_EXPOSED_FRACTION.get() == pytest.approx(
            inv["exposed_fraction"]
        )

    def test_scalar_loss_enforced(self):
        segments, params, x = _mlp_chain()
        bad = segments[:-1]  # chain now ends with a (B, d) activation
        with pytest.raises(ValueError, match="scalar loss"):
            overlapped_value_and_grad(
                bad, params, x, bucket_reduce=lambda b: b,
                bucket_bytes=1 << 10,
            )


class TestHierarchicalOverlapInventory:
    """The PR-7 measured_tier_bytes idiom on the OVERLAPPED program:
    each bucket's two-level reduction, launched at its bucket boundary,
    must show up in the lowered module with modeled == measured bytes
    per fabric tier."""

    def test_modeled_equals_measured_per_tier(self, monkeypatch):
        from horovod_tpu.ops import spmd_ops

        monkeypatch.setenv("HVD_TPU_SLICE_SIZE", "4")
        topo = hvd.common.basics._require_init().topology
        hmesh = topo.hierarchical_mesh()
        n_dcn, n_ici = hmesh.devices.shape
        world = n_dcn * n_ici
        segments, params, x = _mlp_chain(n_seg=4, d=16)

        def bucket_reduce(buf):
            red, _ = spmd_ops._two_level_sum_leaf(
                buf, ICI_AXIS, DCN_AXIS, None, None
            )
            return red / jnp.asarray(world, buf.dtype)

        def f(p, xx):
            loss, grads, _ = overlapped_value_and_grad(
                segments, p, xx, bucket_reduce=bucket_reduce,
                bucket_bytes=2 * 16 * 16 * 4,
            )
            return loss, grads

        fj = jax.jit(jax.shard_map(
            f, mesh=hmesh,
            in_specs=(P(), P((DCN_AXIS, ICI_AXIS))),
            out_specs=(P(), P()), check_vma=False,
        ))
        measured = measured_tier_bytes(
            fj.lower(params, x).as_text(), mesh_slice_ids(hmesh)
        )
        sched = BucketSchedule(
            jax.tree_util.tree_leaves(params), 2 * 16 * 16 * 4
        )
        want_ici = want_dcn = 0
        for nbytes in sched.bucket_nbytes:
            m = modeled_collective_bytes(
                (nbytes // 4,), world, n_ici, dtype="float32"
            )
            want_ici += m["ici_bytes"]
            want_dcn += m["dcn_bytes"]
        assert measured["ici_bytes"] == want_ici
        assert measured["dcn_bytes"] == want_dcn
        # and the interleave holds on the hierarchical program too
        inv = overlap_inventory(fj.lower(params, x).as_text())
        assert inv["interleaved"]


# -- train-step oracles ------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = gpt_tiny(dtype=jnp.float32)
    model = Transformer(cfg)
    rng = jax.random.PRNGKey(0)
    tokens = jax.random.randint(rng, (8, 16), 0, cfg.vocab_size)
    targets = jax.random.randint(
        jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size
    )
    return model, rng, tokens, targets


# Overlapped vs unoverlapped updates: gradients and an elementwise-exact
# inner (sgd) are bit-equal; adamw's moment updates are fma-bearing and
# XLA:CPU contracts them differently between globally-different programs
# (first seen under ZeRO, PR 11; the replicated and multi-axis programs
# show the same 1-ulp moment drift on jax 0.9's XLA, from the second step
# on, while sgd stays exact through both steps).  The adamw bound is the
# ZeRO test's.
_INNER_BOUNDS = [
    pytest.param(lambda: optax.sgd(0.1), 0.0, id="sgd"),
    pytest.param(lambda: optax.adamw(1e-2), 4e-7, id="adamw"),
]


class TestTrainStepOracles:
    @pytest.mark.parametrize("make_opt,bound", _INNER_BOUNDS)
    def test_replicated_overlap_bit_equal(self, tiny_lm, make_opt, bound):
        model, rng, tokens, targets = tiny_lm
        opt = make_opt()
        st_a = training.replicate_state(
            training.create_train_state(model, opt, rng, tokens[:1])
        )
        st_b = jax.tree_util.tree_map(jnp.copy, st_a)
        step_a = training.data_parallel_train_step(model, opt)
        step_b = training.data_parallel_train_step(
            model, opt, overlap=True, bucket_bytes=1 << 15
        )
        for _ in range(2):
            st_a, la = step_a(st_a, tokens, targets)
            st_b, lb = step_b(st_b, tokens, targets)
            assert float(la) == float(lb)
            assert _tree_max_diff(st_a.params, st_b.params) <= bound

    def test_zero_overlap_bit_equal_sgd(self, tiny_lm):
        # the ISSUE-11 oracle: updates bit-equal, ZeRO ON, overlapped vs
        # unoverlapped (elementwise-exact inner; see the adamw test for
        # the FMA caveat)
        model, rng, tokens, targets = tiny_lm
        opt = optax.sgd(0.1)
        st_a, step_a, _ = training.zero_train_setup(
            model, opt, rng, tokens[:1]
        )
        st_b, step_b, _ = training.zero_train_setup(
            model, opt, rng, tokens[:1], overlap=True,
            bucket_bytes=1 << 15,
        )
        for _ in range(2):
            st_a, la = step_a(st_a, tokens, targets)
            st_b, lb = step_b(st_b, tokens, targets)
            assert float(la) == float(lb)
            assert _tree_max_diff(st_a.params, st_b.params) == 0.0

    def test_zero_overlap_adamw_fma_bound(self, tiny_lm):
        # XLA:CPU contracts adamw's nu update (g*g fma) differently
        # across globally-different programs: gradients stay bit-equal
        # (pinned below) but nu — and through it the params — may drift
        # by 1-2 ulp.  Pin the bound tightly so a real numerics
        # regression (not contraction noise) still fails loudly.
        model, rng, tokens, targets = tiny_lm
        opt = optax.adamw(1e-2)
        st_a, step_a, _ = training.zero_train_setup(
            model, opt, rng, tokens[:1]
        )
        st_b, step_b, _ = training.zero_train_setup(
            model, opt, rng, tokens[:1], overlap=True,
            bucket_bytes=1 << 15,
        )
        for _ in range(2):
            st_a, la = step_a(st_a, tokens, targets)
            st_b, lb = step_b(st_b, tokens, targets)
        assert float(la) == float(lb)
        assert _tree_max_diff(st_a.params, st_b.params) <= 4e-7

    def test_zero_overlap_grads_bit_equal(self, tiny_lm):
        # gradients (as opposed to fma-contracted updates) are bit-equal
        # under the ZeRO bucket exchange too: run one sgd step (update =
        # params - lr*grad, exact) and an identity-lr probe
        model, rng, tokens, targets = tiny_lm
        opt = optax.sgd(1.0)
        st_a, step_a, _ = training.zero_train_setup(
            model, opt, rng, tokens[:1]
        )
        st_b, step_b, _ = training.zero_train_setup(
            model, opt, rng, tokens[:1], overlap=True,
            bucket_bytes=1 << 15,
        )
        st_a, _ = step_a(st_a, tokens, targets)
        st_b, _ = step_b(st_b, tokens, targets)
        assert _tree_max_diff(st_a.params, st_b.params) == 0.0

    def test_zero_hierarchical_overlap_parity(self, monkeypatch, tiny_lm):
        # the two-level (2 slices x 4 chips) ZeRO exchange on the bucket
        # schedule: sgd updates bit-equal overlapped vs unoverlapped,
        # and with STATELESS bf16 wire compression the overlap
        # composition must not add quantization the unoverlapped path
        # doesn't have (the gradient gather runs full-precision — only
        # the RS hop and the update allgather carry the wire dtype)
        from horovod_tpu.compression import DcnCompression

        monkeypatch.setenv("HVD_TPU_SLICE_SIZE", "4")
        topo = hvd.common.basics._require_init().topology
        hmesh = topo.hierarchical_mesh()
        model, rng, tokens, targets = tiny_lm
        for comp in (None, DcnCompression("bfloat16")):
            opt = optax.sgd(0.1)
            st_a, step_a, _ = training.zero_train_setup(
                model, opt, rng, tokens[:1], hierarchical=True,
                mesh=hmesh, dcn_compression=comp,
            )
            st_b, step_b, _ = training.zero_train_setup(
                model, opt, rng, tokens[:1], hierarchical=True,
                mesh=hmesh, dcn_compression=comp, overlap=True,
                bucket_bytes=1 << 15,
            )
            for _ in range(2):
                st_a, la = step_a(st_a, tokens, targets)
                st_b, lb = step_b(st_b, tokens, targets)
            assert float(la) == float(lb)
            # the wire cast is elementwise, so even the compressed legs
            # agree bit-for-bit under an elementwise-exact inner
            assert _tree_max_diff(st_a.params, st_b.params) == 0.0

    def test_zero_overlap_rejects_error_feedback(self, tiny_lm):
        from horovod_tpu.compression import DcnCompression

        model, rng, tokens, _ = tiny_lm
        with pytest.raises(ValueError, match="error_feedback"):
            training.zero_train_setup(
                model, optax.sgd(0.1), rng, tokens[:1],
                hierarchical=True,
                dcn_compression=DcnCompression(
                    "bfloat16", error_feedback=True),
                overlap=True,
            )

    def test_overlap_rejects_batch_stats_models(self, tiny_lm):
        model, rng, tokens, targets = tiny_lm
        opt = optax.sgd(0.1)
        st = training.replicate_state(
            training.create_train_state(model, opt, rng, tokens[:1])
        )
        st = st.replace(batch_stats={"mean": jnp.zeros((2,))})
        step = training.data_parallel_train_step(
            model, opt, overlap=True
        )
        with pytest.raises(Exception, match="batch_stats"):
            step(st, tokens, targets)

    def test_overlap_requires_segmenter_for_unknown_models(self):
        import flax.linen as nn

        class Mlp(nn.Module):
            @nn.compact
            def __call__(self, x):
                return nn.Dense(4)(x)

        with pytest.raises(ValueError, match="segment chain"):
            training.data_parallel_train_step(
                Mlp(), optax.sgd(0.1), overlap=True
            )


class TestMultiAxisOverlap:
    @pytest.mark.parametrize("make_opt,bound", _INNER_BOUNDS)
    def test_sharded_step_bit_equal(self, make_opt, bound):
        from horovod_tpu.parallel import sharded as sh

        mesh = sh.multi_axis_mesh(dp=2, sp=1, tp=2,
                                  devices=jax.devices()[:4])
        model = sh.MultiAxisTransformer(
            vocab=64, d_model=32, num_heads=4, num_layers=2,
            seq_len=16, dtype=jnp.float32,
        )
        rng = jax.random.PRNGKey(0)
        variables, pspecs = sh.init_sharded(model, mesh, rng)
        opt = make_opt()
        opt_state, ospecs = sh.init_opt_sharded(
            opt, variables, mesh, pspecs
        )
        tok = jax.random.randint(rng, (4, 16), 0, 64)
        tgt = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, 64)
        step_a = sh.make_sharded_train_step(
            model, opt, mesh, pspecs, ospecs
        )
        step_b = sh.make_sharded_train_step(
            model, opt, mesh, pspecs, ospecs, overlap=True,
            bucket_bytes=1 << 13,
        )
        cp = lambda t: jax.tree_util.tree_map(jnp.copy, t)  # noqa: E731
        pa, oa, tokc, tgtc = cp(variables), cp(opt_state), tok, tgt
        pb, ob = cp(variables), cp(opt_state)
        for _ in range(2):
            pa, oa, la = step_a(pa, oa, tokc, tgtc)
            pb, ob, lb = step_b(pb, ob, tokc, tgtc)
        assert float(la) == float(lb)
        assert _tree_max_diff(pa, pb) <= bound

    def test_sharded_overlap_interleaves(self):
        from horovod_tpu.parallel import sharded as sh

        mesh = sh.multi_axis_mesh(dp=2, sp=1, tp=2,
                                  devices=jax.devices()[:4])
        model = sh.MultiAxisTransformer(
            vocab=64, d_model=32, num_heads=4, num_layers=2,
            seq_len=16, dtype=jnp.float32,
        )
        rng = jax.random.PRNGKey(0)
        variables, pspecs = sh.init_sharded(model, mesh, rng)
        opt = optax.sgd(0.1)
        opt_state, ospecs = sh.init_opt_sharded(
            opt, variables, mesh, pspecs
        )
        tok = jax.random.randint(rng, (4, 16), 0, 64)
        tgt = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, 64)
        step = sh.make_sharded_train_step(
            model, opt, mesh, pspecs, ospecs, overlap=True,
            bucket_bytes=1 << 13,
        )
        txt = step.lower(
            variables, opt_state, tok, tgt
        ).as_text()
        # scalar loss pmean filtered out: buckets are >= 1 KiB here
        inv = overlap_inventory(txt, min_payload_bytes=1024)
        assert len(inv["collectives"]) >= 2
        assert inv["interleaved"]
        assert inv["exposed_fraction"] < 1.0


# -- autotuner ---------------------------------------------------------------


class TestBucketAutotuner:
    CANDS = [Candidate(1 << 20), Candidate(4 << 20), Candidate(16 << 20)]

    def _drive(self, tuner, time_of):
        while not tuner.converged:
            cand = tuner.propose()
            tuner.observe(time_of(cand))
        return tuner

    def test_converges_to_argmin_within_budget(self):
        tuner = BucketAutotuner(
            candidates=self.CANDS, default=Candidate(8 << 20),
            trial_budget=8, steps_per_trial=3,
        )
        times = {1 << 20: 0.9, 4 << 20: 0.3, 8 << 20: 0.5, 16 << 20: 0.7}
        self._drive(tuner, lambda c: times[c.bucket_bytes])
        assert tuner.converged
        assert tuner.pinned.bucket_bytes == 4 << 20
        assert len(tuner.scores) <= 8
        # once pinned, propose() is stable and observe() is a no-op
        assert tuner.propose() == tuner.pinned
        tuner.observe(0.0001)
        assert tuner.pinned.bucket_bytes == 4 << 20

    def test_never_regresses_vs_default(self):
        # the default is the global best -> it must win (it is trial 0)
        tuner = BucketAutotuner(
            candidates=self.CANDS, default=Candidate(8 << 20),
            trial_budget=8, steps_per_trial=2,
        )
        times = {1 << 20: 0.9, 4 << 20: 0.8, 8 << 20: 0.1, 16 << 20: 0.7}
        self._drive(tuner, lambda c: times[c.bucket_bytes])
        assert tuner.pinned.bucket_bytes == 8 << 20

    def test_budget_exhaustion_pins_best_so_far(self):
        tuner = BucketAutotuner(
            candidates=self.CANDS, default=Candidate(8 << 20),
            trial_budget=2, steps_per_trial=1,
        )
        times = {1 << 20: 0.2, 4 << 20: 0.05, 8 << 20: 0.5, 16 << 20: 0.7}
        self._drive(tuner, lambda c: times[c.bucket_bytes])
        # only default + first candidate scored; best of those pinned
        assert len(tuner.scores) == 2
        assert tuner.pinned.bucket_bytes == 1 << 20

    def test_trial_counter_increments(self):
        before = _metrics.OVERLAP_AUTOTUNE_TRIALS.get()
        tuner = BucketAutotuner(
            candidates=self.CANDS[:1], default=Candidate(8 << 20),
            trial_budget=4, steps_per_trial=1,
        )
        self._drive(tuner, lambda c: 0.1)
        assert _metrics.OVERLAP_AUTOTUNE_TRIALS.get() == before + 2
        assert _metrics.OVERLAP_AUTOTUNE_PINNED_BYTES.get() == \
            tuner.pinned.bucket_bytes

    def test_first_step_of_trial_discarded(self):
        # the first observation pays the recompile; the median must
        # ignore it
        tuner = BucketAutotuner(
            candidates=[], default=Candidate(8 << 20),
            trial_budget=1, steps_per_trial=3,
        )
        for t in (9.0, 0.1, 0.1):  # compile spike first
            tuner.observe(t)
        assert tuner.converged
        assert tuner.scores[0][1] == pytest.approx(0.1)


# -- torch bridge ------------------------------------------------------------


class TestTorchBucketedSubmission:
    def test_bucket_ordered_drain_matches_local_sgd(self):
        torch = pytest.importorskip("torch")
        from horovod_tpu.common import basics
        from horovod_tpu.torch.optimizer import DistributedOptimizer

        cfg = basics._require_init().config
        old = cfg.overlap_bucket_bytes
        cfg.overlap_bucket_bytes = 64  # force several tiny buckets
        try:
            torch.manual_seed(0)
            model = torch.nn.Sequential(
                torch.nn.Linear(8, 16), torch.nn.ReLU(),
                torch.nn.Linear(16, 8), torch.nn.Linear(8, 4),
            )
            ref = torch.nn.Sequential(
                torch.nn.Linear(8, 16), torch.nn.ReLU(),
                torch.nn.Linear(16, 8), torch.nn.Linear(8, 4),
            )
            ref.load_state_dict(model.state_dict())
            opt = DistributedOptimizer(
                torch.optim.SGD(model.parameters(), lr=0.1),
                named_parameters=model.named_parameters(),
            )
            ref_opt = torch.optim.SGD(ref.parameters(), lr=0.1)
            xb = torch.randn(4, 8)
            try:
                for _ in range(2):
                    opt.zero_grad()
                    model(xb).pow(2).mean().backward()
                    opt.step()
                    ref_opt.zero_grad()
                    ref(xb).pow(2).mean().backward()
                    ref_opt.step()
                # single-process world: distributed average == local grad,
                # so the bucketed submission must reproduce plain SGD
                for p, q in zip(model.parameters(), ref.parameters()):
                    assert torch.equal(p, q)
                # the deterministic schedule split the params into
                # several buckets
                assert len(set(opt._bucket_of.values())) >= 2
            finally:
                opt.close()
        finally:
            cfg.overlap_bucket_bytes = old


# -- modeled exposure --------------------------------------------------------


class TestModeledOverlap:
    def test_r4_point_drops_2x(self):
        # PERF.md round-4 measured inputs (tools/scaling_model.py):
        # ResNet-50, 47.6 ms step, 51.2 MB bf16 wire, ~200 GB/s ICI
        wire = int(25.6e6 * 2)
        bucket = 4 << 20
        n = -(-wire // bucket)
        sizes = [bucket] * (n - 1) + [wire - bucket * (n - 1)]
        m = modeled_overlap_exposed(sizes, 0.0476, 200e9, 256)
        assert m["exposed_fraction"] * 2 <= 1.0  # the >=2x bar
        assert m["t_step_s"] < 0.0476 + m["t_comm_s"]

    def test_unbucketed_exposes_nothing_hidden(self):
        # one bucket produced at the very end == the unoverlapped step
        m = modeled_overlap_exposed([1 << 20], 0.01, 1e9, 8)
        assert m["exposed_fraction"] == pytest.approx(1.0)

    def test_world_one_is_free(self):
        m = modeled_overlap_exposed([1 << 20], 0.01, 1e9, 1)
        assert m["t_comm_s"] == 0.0 and m["exposed_fraction"] == 0.0


# -- asynchronous all-reduces: what the step asks of the compiler (ISSUE 25) --


def _fake_mesh(platforms, axis=WORLD_AXIS):
    """What ``exchange_compile_options`` reads of a mesh (its shape and
    its devices' platforms), for backends this suite cannot attach; the
    described v5e is in tests/test_chip_compile.py."""
    devices = np.empty(len(platforms), dtype=object)
    devices[:] = [types.SimpleNamespace(platform=p) for p in platforms]
    return types.SimpleNamespace(shape={axis: len(platforms)}, devices=devices)


def _tiny_lm(n_devices):
    from jax.sharding import Mesh

    hvd.init()
    mesh = Mesh(np.array(jax.devices()[:n_devices]), (WORLD_AXIS,))
    model, optimizer = Transformer(gpt_tiny()), optax.adamw(1e-3)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (n_devices, 32), 0, gpt_tiny().vocab_size)

    def state():
        return training.replicate_state(training.create_train_state(
            model, optimizer, jax.random.PRNGKey(0), tokens[:1]), mesh)

    return mesh, model, optimizer, tokens, state


class TestExchangeCompileOptions:
    def test_cpu_mesh_of_four_gets_none_and_the_step_is_the_plain_jit(self):
        mesh, model, optimizer, tokens, state = _tiny_lm(4)
        assert spmd_ops.exchange_compile_options(mesh) == {}
        step = training.data_parallel_train_step(model, optimizer, mesh=mesh)
        plain = jax.jit(step.__wrapped__, donate_argnums=(0,))
        got_state, got_loss = step(state(), tokens, tokens)
        want_state, want_loss = plain(state(), tokens, tokens)
        assert np.asarray(got_loss) == np.asarray(want_loss)
        assert _tree_bit_equal(got_state.params, want_state.params)
        # nothing asynchronous on this backend, and the counter says so
        counts = compiled_collective_counts(
            step.lower(state(), tokens, tokens).compile().as_text())
        assert counts["async_pairs"] == 0 and counts["sync_all_reduces"] >= 1

    @pytest.mark.parametrize("mesh", [
        pytest.param(lambda: _tiny_lm(1)[0], id="one_cpu_device"),
        pytest.param(lambda: _fake_mesh(["tpu"]), id="one_tpu"),
        pytest.param(lambda: _fake_mesh(["gpu"] * 4), id="four_gpus"),
        pytest.param(lambda: _fake_mesh(["tpu", "tpu", "cpu", "tpu"]),
                     id="mixed"),
    ])
    def test_none_without_an_exchange_or_off_the_tpu(self, mesh):
        assert spmd_ops.exchange_compile_options(mesh()) == {}

    def test_tpu_axis_of_four_gets_the_asynchronous_set(self):
        mesh = _fake_mesh(["tpu"] * 4)
        options = spmd_ops.exchange_compile_options(mesh)
        assert options["xla_enable_async_all_reduce"] is True
        assert options["xla_tpu_enable_async_collective_fusion"] is True
        assert options[
            "xla_tpu_enable_async_collective_fusion_fuse_all_reduce"] is True
        # a fresh dict each call: a caller may add to it
        options["x"] = 1
        assert "x" not in spmd_ops.exchange_compile_options(mesh)
        # the axis that is reduced over decides, not the mesh's size
        two_axes = types.SimpleNamespace(
            shape={"data": 4, "model": 1}, devices=mesh.devices)
        assert (spmd_ops.exchange_compile_options(two_axes, "data")
                == spmd_ops.exchange_compile_options(mesh))
        assert spmd_ops.exchange_compile_options(two_axes, "model") == {}


_COMPILED_TEXT = """HloModule jit__step, is_scheduled=true
%region_1.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%a, %b)
}
%fused_computation.1 (param_0.1: f32[8]) -> (f32[8], u32[]) {
  %param_0.1 = f32[8]{0} parameter(0)
  %all-reduce.7 = f32[8]{0} all-reduce(%param_0.1), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_1.1
  ROOT %custom-call.1 = (f32[8]{0}, u32[]) custom-call(%all-reduce.7), custom_call_target="AsyncCollectiveStart"
}
%fused_computation.2 (param_0.2: f32[8], param_1.2: f32[8,8]) -> f32[8,8] {
  %param_0.2 = f32[8]{0} parameter(0)
  %param_1.2 = f32[8,8]{1,0} parameter(1)
  %all-reduce.8 = f32[8]{0} all-reduce(%param_0.2), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_1.1
  ROOT %convolution.1 = f32[8,8]{1,0} convolution(%param_1.2, %param_1.2), dim_labels=bf_io->bf
}
%fused_computation.3 (param_0.3: f32[8]) -> f32[8] {
  %param_0.3 = f32[8]{0} parameter(0)
  %all-reduce.9 = f32[8]{0} all-reduce(%param_0.3), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_1.1
  ROOT %custom-call.2 = f32[8]{0} custom-call(%all-reduce.9), custom_call_target="AsyncCollectiveDone"
}
ENTRY %main (p0: f32[8], p1: f32[8,8], p2: f32[]) -> (f32[8], f32[8,8], f32[]) {
  %p0 = f32[8]{0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  %p2 = f32[] parameter(2)
  %async-collective-start = (f32[8]{0}, u32[]) fusion(%p0), kind=kCustom, calls=%fused_computation.1
  %fusion.5 = f32[8,8]{1,0} fusion(%p0, %p1), kind=kOutput, calls=%fused_computation.2
  %async-collective-done = f32[8]{0} fusion(%p0), kind=kCustom, calls=%fused_computation.3
  %psum.3 = f32[] all-reduce(%p2), channel_id=2, replica_groups={{0,1,2,3}}, to_apply=%region_1.1
  SYNC_LINE
  ROOT %tuple.1 = (f32[8]{0}, f32[8,8]{1,0}, f32[]) tuple(%async-collective-done, %fusion.5, %psum.3)
}
"""


@pytest.mark.parametrize("extra,want", [
    pytest.param("", {"async_pairs": 1, "sync_all_reduces": 1},
                 id="one_fused_pair_and_the_scalar"),
    pytest.param(
        "%all-reduce.3 = f32[8]{0} all-reduce(%p0), channel_id=3, "
        "replica_groups={{0,1,2,3}}, to_apply=%region_1.1, "
        'frontend_attributes={async_collective_name="all-reduce-start.1"}',
        {"async_pairs": 1, "sync_all_reduces": 2}, id="one_turned_back"),
    pytest.param(
        "%all-reduce-start.1 = f32[8]{0} all-reduce-start(%p0), "
        "channel_id=3, replica_groups={{0,1,2,3}}, to_apply=%region_1.1\n"
        "  %all-reduce-done.1 = f32[8]{0} all-reduce-done("
        "%all-reduce-start.1)",
        {"async_pairs": 2, "sync_all_reduces": 1}, id="the_generic_pair"),
])
def test_compiled_collective_counts_reads_the_schedule(extra, want):
    """The all-reduce repeated inside the fusions of an asynchronous
    collective is not a synchronous one; one in ENTRY is, whatever
    name it still carries."""
    text = _COMPILED_TEXT.replace("SYNC_LINE", extra)
    assert compiled_collective_counts(text) == want
