"""RayExecutor / spark-run contract tests.

Reference analog: test/single/test_ray.py (SURVEY.md §4) — executor
start/run/shutdown semantics with per-rank results.  Ray itself is not
in this image, so the local backend (same contract) is what runs; the
spark module's no-pyspark guidance is asserted too.
"""

import os
import sys

import pytest


import horovod_tpu.ray as hvd_ray
import horovod_tpu.spark as hvd_spark
from tests.executor_fns import rank_report


@pytest.mark.integration
def test_ray_executor_local_backend(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    executor = hvd_ray.RayExecutor(num_workers=2)
    assert executor._backend == "local"  # ray absent in this image
    executor.start()
    results = executor.run(rank_report, args=[7])
    executor.shutdown()
    assert len(results) == 2
    # rank order preserved; collective result agrees everywhere
    assert [r["rank"] for r in results] == [0, 1]
    assert all(r["world"] == 2 for r in results)
    assert all(abs(r["allreduce_sum"] - 2.0) < 1e-6 for r in results)
    assert all(r["arg"] == 7 for r in results)


@pytest.mark.integration
def test_elastic_ray_executor_local_backend(monkeypatch):
    """ElasticRayExecutor contract on the subprocess backend: callable
    discovery feeds the same ElasticDriver as tpurun --host-discovery-
    script; per-rank results of the final world come back in rank order
    (reference: horovod/ray/elastic.py ElasticRayExecutor)."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    executor = hvd_ray.ElasticRayExecutor(
        min_workers=2, max_workers=2,
        discovery=lambda: [("localhost", 2)],
    )
    executor.start()
    results = executor.run(rank_report, args=[3])
    executor.shutdown()
    assert len(results) == 2
    assert [r["rank"] for r in results] == [0, 1]
    assert all(r["world"] == 2 for r in results)
    assert all(abs(r["allreduce_sum"] - 2.0) < 1e-6 for r in results)


def test_ray_executor_requires_start():
    executor = hvd_ray.RayExecutor(num_workers=1)
    with pytest.raises(RuntimeError):
        executor.run(rank_report, args=[0])


def test_spark_run_without_pyspark_raises_helpfully():
    with pytest.raises(ImportError) as e:
        hvd_spark.run(rank_report, args=(0,), num_proc=2)
    assert "RayExecutor" in str(e.value)
