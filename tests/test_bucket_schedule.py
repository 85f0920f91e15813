"""The bucket schedule and what is built on it (docs/tensor-fusion.md).

* ``BucketSchedule`` determinism (permuted-but-equal leaf lists build the
  identical layout), reverse-production launch order, and the
  threshold-sensitive ``signature()`` (executable-cache collision guard);
* strict env validation of ``HVD_TPU_FUSION_THRESHOLD`` and
  ``HVD_TPU_OVERLAP_BUCKET_BYTES``;
* the torch bridge's deterministic bucket-ordered submission.
"""

import pytest

import jax.numpy as jnp

from horovod_tpu.ops.fusion import BucketSchedule, FusionPlan


def _leaves(specs):
    return [jnp.zeros(s, d) for s, d in specs]


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
