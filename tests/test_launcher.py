"""Launcher + multi-process integration tests.

Reference analog: test/integration/test_static_run.py (end-to-end
horovodrun on localhost) and the multi-node-without-a-cluster technique of
SURVEY.md §4: N real processes on one box, rendezvous over loopback — here
the JAX coordination service instead of the Gloo HTTP store.
"""

import os
import subprocess
import sys

import pytest


import horovod_tpu.runner.launch as launch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "integration", "launcher_worker.py")


def _run_tpurun(np_, extra=None, timeout=180, target=None,
                target_args=None):
    """Launch ``tpurun -np N`` on a per-rank script with the suite's
    standard child environment (CPU backend, repo on PYTHONPATH, one
    device per process).  Defaults to the collective-asserting WORKER."""
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env["TF_CPP_MIN_LOG_LEVEL"] = "3"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)  # one CPU device per process
    if target is None:
        assert target_args is None, "target_args requires an explicit target"
        target, target_args = WORKER, [str(np_)]
    cmd = [
        sys.executable, "-m", "horovod_tpu.runner",
        "-np", str(np_), *(extra or []), "--",
        sys.executable, target, *(target_args or []),
    ]
    return subprocess.run(
        cmd, env=env, capture_output=True, text=True, timeout=timeout,
        cwd=REPO,
    )


def test_host_parsing():
    assert launch.parse_host_spec("h1:4,h2:2") == [("h1", 4), ("h2", 2)]
    assert launch.parse_host_spec("h1") == [("h1", 1)]


def test_hostfile_parsing(tmp_path):
    f = tmp_path / "hosts"
    f.write_text("# comment\nnode1 slots=8\nnode2 slots=4\n")
    assert launch.parse_hostfile(str(f)) == [("node1", 8), ("node2", 4)]


def test_check_build():
    out = launch.check_build()
    assert "XLA" in out and "horovod_tpu" in out


def test_config_file_to_env(tmp_path):
    import yaml

    from horovod_tpu.runner.config_parser import (
        config_to_env, load_config_file,
    )

    cfg = tmp_path / "cfg.yaml"
    cfg.write_text(yaml.safe_dump({
        "fusion_threshold": 1234, "autotune": True, "log_level": "debug",
    }))
    args = launch.build_parser().parse_args(
        ["--cycle-time-ms", "2.5", "--", "true"]
    )
    env = config_to_env(args, load_config_file(str(cfg)))
    assert env["HVD_TPU_FUSION_THRESHOLD"] == "1234"
    assert env["HVD_TPU_AUTOTUNE"] == "1"
    assert env["HVD_TPU_CYCLE_TIME"] == "2.5"  # CLI wins layering intact
    assert env["HVD_TPU_LOG_LEVEL"] == "debug"


def test_np_exceeding_slots_rejected(capsys):
    rc = launch.run_commandline(["-np", "4", "-H", "localhost:2", "--",
                                 "true"])
    assert rc == 2


@pytest.mark.parametrize("np_", [2])
def test_tpurun_multiprocess_collectives(np_):
    """The big one: np real processes, jax.distributed rendezvous, every
    eager collective checked cross-process (python fallback controller)."""
    res = _run_tpurun(np_, extra=["--disable-native"])
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    assert res.stdout.count("WORKER_OK") == np_


def test_tpurun_failure_propagates():
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-m", "horovod_tpu.runner", "-np", "2", "--",
           sys.executable, "-c", "import sys; sys.exit(3)"]
    res = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=120, cwd=REPO)
    assert res.returncode == 3


_SHUTDOWN_WITH_LIVE_PEERS = """
import os, time
import jax.numpy as jnp
import horovod_tpu as hvd
hvd.init()
hvd.allreduce(jnp.ones(8), op=hvd.Sum, name="warm")
time.sleep(0.2 * hvd.rank())   # ranks leave the cycle at different times
hvd.shutdown()                 # peers are alive and not exiting yet
os.write(1, f"SHUTDOWN_RETURNED {hvd.rank() if hvd.is_initialized() else -1}\\n".encode())
time.sleep(1.0)
"""


@pytest.mark.integration
def test_explicit_shutdown_returns_while_peers_live():
    """hvd.shutdown() must not need the peers to die: the negotiation
    cycle is lockstep with no goodbye message, and before the native
    shutdown interrupted its own connections a rank sat in
    hvdtpu_shutdown() until some peer's sockets closed — four ranks each
    waiting for another to die first is how a four-chip job hung at exit
    (PERF.md, PR 21)."""
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    cmd = [sys.executable, "-m", "horovod_tpu.runner", "-np", "4", "--",
           sys.executable, "-c", _SHUTDOWN_WITH_LIVE_PEERS]
    res = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=120, cwd=REPO)
    assert res.returncode == 0, res.stderr[-3000:]
    assert res.stdout.count("SHUTDOWN_RETURNED") == 4, res.stdout


@pytest.mark.parametrize("np_", [2, 3])
def test_tpurun_multiprocess_native_controller(np_):
    """Same per-rank assertions with the C++ controller negotiating over
    its TCP star (reference analog: the gloo-controller path of
    test_static_run).  np=3 additionally exercises eager cross-process
    process-set collectives and ragged join fills."""
    res = _run_tpurun(np_)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    assert res.stdout.count("WORKER_OK") == np_
    assert "native=True" in res.stdout


@pytest.mark.integration
def test_tpurun_tensorflow_adapter():
    """TF/Keras adapter under 2 real processes: tf.Tensor bridge, graph
    mode, DistributedGradientTape averaging, Keras optimizer lockstep
    (reference analog: test/parallel/test_tensorflow.py under
    horovodrun -np 2)."""
    tf_worker = os.path.join(REPO, "tests", "integration", "tf_worker.py")
    res = _run_tpurun(2, timeout=420, target=tf_worker, target_args=["2"])
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr[-4000:]}"
    assert res.stdout.count("TF_WORKER_OK") == 2
    # the jit_compile=True leg must have RUN (bridge builds under g++,
    # which this image has) — a silent skip would mask a regression
    assert res.stdout.count("TF_WORKER_XLA_OK") == 2, res.stdout


@pytest.mark.integration
def test_tpurun_keras_mnist_example():
    """The Keras example trains to high accuracy under 2 real processes —
    pins the full model.fit + DistributedOptimizer + callbacks path
    (reference analog: test/integration style end-to-end runs)."""
    example = os.path.join(REPO, "examples", "tensorflow2",
                           "tensorflow2_keras_mnist.py")
    res = _run_tpurun(2, timeout=420, target=example,
                      target_args=["--epochs", "1"])
    assert res.returncode == 0, \
        f"stdout:\n{res.stdout[-2000:]}\nstderr:\n{res.stderr[-3000:]}"
    # rank-0 accuracy assertion ran inside the child
    assert "final accuracy" in res.stdout, res.stdout[-2000:]


@pytest.mark.integration
def test_tpurun_keras_elastic_example():
    """The elastic Keras example (reference:
    tensorflow2_keras_mnist_elastic.py) trains under 2 real processes:
    KerasState sync, commit/epoch callbacks inside model.fit, resume via
    initial_epoch; the script asserts final accuracy."""
    example = os.path.join(REPO, "examples", "tensorflow2",
                           "tensorflow2_keras_mnist_elastic.py")
    res = _run_tpurun(2, timeout=420, target=example,
                      target_args=["--epochs", "2"])
    assert res.returncode == 0, \
        f"stdout:\n{res.stdout[-2000:]}\nstderr:\n{res.stderr[-3000:]}"
    assert "KERAS_ELASTIC_OK" in res.stdout, res.stdout[-2000:]


@pytest.mark.integration
def test_tpurun_negotiation_stress():
    """Randomized mixed-collective schedule, submitted async in a
    DIFFERENT order on every rank with timing jitter (the cross-rank
    readiness skew of SURVEY §3.2/§5.2).  Caught a real deadlock: the
    coordinator's group-atomicity check keyed on per-process group ids,
    which diverge under out-of-order submission (see group_table.h)."""
    worker = os.path.join(REPO, "tests", "integration", "stress_worker.py")
    res = _run_tpurun(3, timeout=300, target=worker, target_args=["3"])
    assert res.returncode == 0, \
        f"stdout:\n{res.stdout[-2000:]}\nstderr:\n{res.stderr[-3000:]}"
    assert res.stdout.count("STRESS_OK") == 3


@pytest.mark.integration
def test_tpurun_negotiation_stress_np8_soak():
    """np=8 + a longer seeded schedule (120 ops, different seed): more
    ranks means more cross-rank submission-order divergence and more
    partial-readiness cycles at the coordinator — the regime where the
    round-4 grouped deadlock and the round-5 wire-name mismatch both
    lived.  The batched-enqueue + CV-wake paths get their widest
    exercise here."""
    worker = os.path.join(REPO, "tests", "integration", "stress_worker.py")
    os.environ["HVD_TPU_STRESS_OPS"] = "120"
    os.environ["HVD_TPU_STRESS_SEED"] = "77"
    try:
        res = _run_tpurun(8, timeout=600, target=worker, target_args=["8"])
    finally:
        os.environ.pop("HVD_TPU_STRESS_OPS", None)
        os.environ.pop("HVD_TPU_STRESS_SEED", None)
    assert res.returncode == 0, \
        f"stdout:\n{res.stdout[-2000:]}\nstderr:\n{res.stderr[-3000:]}"
    assert res.stdout.count("STRESS_OK") == 8


@pytest.mark.integration
def test_tpurun_elastic_pretrain_example():
    """The elastic LM-pretrain example (BASELINE's elastic-Llama-pretrain
    analog at toy scale) trains under 2 real processes: elastic
    commit/restore wrapper + ElasticSampler + DistributedOptimizer grad
    averaging on the negotiated path; the script asserts the loss fell."""
    example = os.path.join(REPO, "examples", "jax",
                           "jax_elastic_pretrain.py")
    res = _run_tpurun(2, timeout=420, target=example,
                      target_args=["--epochs", "2", "--docs", "128"])
    assert res.returncode == 0, \
        f"stdout:\n{res.stdout[-2000:]}\nstderr:\n{res.stderr[-3000:]}"
    assert "ELASTIC_PRETRAIN_OK" in res.stdout, res.stdout[-2000:]


@pytest.mark.integration
def test_tpurun_pytorch_synthetic_example():
    """The torch synthetic benchmark example runs under 2 real processes
    (grad-hook DistributedOptimizer + state broadcasts end to end)."""
    example = os.path.join(REPO, "examples", "pytorch",
                           "pytorch_synthetic_benchmark.py")
    res = _run_tpurun(2, timeout=420, target=example,
                      target_args=["--num-iters", "3", "--num-warmup", "1"])
    assert res.returncode == 0, \
        f"stdout:\n{res.stdout[-2000:]}\nstderr:\n{res.stderr[-3000:]}"
    assert "Total img/sec on 2 worker(s)" in res.stdout, res.stdout[-2000:]


@pytest.mark.integration
def test_jax_pipeline_example():
    """The GPipe example trains (8 virtual devices, loss halves — the
    script asserts it) with grad-outside-shard_map over the pp axis."""
    example = os.path.join(REPO, "examples", "jax", "jax_pipeline_mlp.py")
    env = os.environ.copy()
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
    })
    res = subprocess.run(
        [sys.executable, example, "--steps", "20"],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO,
    )
    assert res.returncode == 0, \
        f"stdout:\n{res.stdout[-2000:]}\nstderr:\n{res.stderr[-3000:]}"
    assert "pp=8 stages" in res.stdout


@pytest.mark.integration
def test_tpurun_mxnet_adapter():
    """MXNet adapter under 2 real processes (faked-mxnet NDArray storage,
    real cross-process collectives): in-place/grouped ops, default-op
    reducescatter, broadcast_parameters, DistributedTrainer/Optimizer
    averaging (reference analog: test/parallel/test_mxnet.py)."""
    worker = os.path.join(REPO, "tests", "integration", "mxnet_worker.py")
    res = _run_tpurun(2, timeout=420, target=worker, target_args=["2"])
    assert res.returncode == 0, \
        f"stdout:\n{res.stdout[-2000:]}\nstderr:\n{res.stderr[-3000:]}"
    assert res.stdout.count("MXNET_WORKER_OK") == 2


@pytest.mark.integration
def test_tpurun_torch_adapter():
    """Torch adapter under 2 real processes: grouped ops, uneven
    alltoall, SyncBatchNorm global stats + gradient flow (reference
    analog: test/parallel/test_torch.py under horovodrun -np 2)."""
    worker = os.path.join(REPO, "tests", "integration", "torch_worker.py")
    res = _run_tpurun(2, timeout=420, target=worker, target_args=["2"])
    assert res.returncode == 0, \
        f"stdout:\n{res.stdout[-2000:]}\nstderr:\n{res.stderr[-3000:]}"
    assert res.stdout.count("TORCH_WORKER_OK") == 2
