"""chip_smoke.py's phases at tiny sizes on the CPU mesh, and the rules of the
chip path: no TPU means a non-zero exit (chip_smoke.py, bench.py), peaks are
keyed by device_kind, the compile cache has one fixed home."""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402
import chip_smoke  # noqa: E402
from horovod_tpu.models.transformer import gpt_tiny  # noqa: E402
from horovod_tpu.utils import compile_cache  # noqa: E402


def _cpu_env(**extra):
    env = os.environ.copy()
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **extra)
    env.pop("XLA_FLAGS", None)
    return env


def test_phase_init_native_controller():
    rec = chip_smoke.phase_init(rebuild=False)
    assert rec["native_controller"] and rec["size"] == 8
    assert rec["device"] == {"platform": "cpu", "kind": "cpu", "count": 8}
    assert rec["compile_cache_dir"] == compile_cache.cache_dir()


def test_phase_train_resnet_tiny():
    rec = chip_smoke.phase_train_resnet(
        "ResNetTiny", batch=16, image_size=32, warmup=2, steps=3)
    assert rec["loss_last"] < rec["loss_first"]
    assert rec["compiles_after_warmup"] == 0 and rec["compiles"] >= 1


def test_phase_train_transformer_tiny():
    cfg = gpt_tiny(attention_impl="flash", dtype=jnp.float32)
    rec = chip_smoke.phase_train_transformer(
        cfg, batch=8, seq=64, warmup=2, steps=3)
    assert rec["loss_last"] < rec["loss_first"]
    assert rec["attention_impl"] == "flash"
    assert rec["compiles_after_warmup"] == 0


def test_phase_kernels_interpreted():
    rec = chip_smoke.phase_kernels(
        batch=1, seq=128, heads=4, kv_heads=2, head_dim=32, interpret=True)
    assert rec["fwd_err"] <= rec["fwd_tol"]
    assert max(rec["grad_err"].values()) <= rec["grad_tol"]


def test_phase_kernels_refuses_the_cpu():
    # interpret=False names the Mosaic kernel: no silent interpreter
    with pytest.raises(Exception):
        chip_smoke.phase_kernels(
            batch=1, seq=128, heads=4, kv_heads=2, head_dim=32,
            interpret=False)


def test_phase_eager_native():
    rec = chip_smoke.phase_eager(elements=1 << 12)
    assert rec["native"] and rec["bytes"] == 4 << 12


def test_phase_multichip_cpu_mesh():
    rec = chip_smoke.phase_multichip(
        "ResNetTiny", batch=16, image_size=32, steps=3)
    assert rec["world"] == 8 and rec["shard_devices"] == 8
    assert rec["loss_rel_err"] <= rec["loss_rtol"]
    assert rec["param_err"] <= rec["param_tol"]


def test_chip_smoke_exits_nonzero_without_tpu():
    res = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         env=_cpu_env(), capture_output=True, text=True,
                         timeout=120)
    assert res.returncode != 0
    assert res.stdout == "", "no phase may run, no result may print"
    assert "no TPU" in res.stderr


def test_bench_exits_nonzero_without_tpu():
    res = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                         env=_cpu_env(), capture_output=True, text=True,
                         timeout=120)
    assert res.returncode != 0
    assert res.stdout == ""


def test_bench_runs_on_cpu_when_told():
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--worker", "cpu"],
        env=_cpu_env(), capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    rec = json.loads(res.stdout.strip().splitlines()[-1])
    assert rec["platform"] == "cpu" and rec["device_count"] == 1
    assert rec["metric"] == "resnet50_tiny_cpu_train_throughput"
    assert "mfu" not in rec


def test_peaks_table_keyed_by_device_kind():
    peaks = bench.device_peaks("TPU v5 lite")
    assert peaks["bf16_flops"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="TPU v9"):
        bench.device_peaks("TPU v9")


def test_compile_cache_honours_the_variable(monkeypatch, tmp_path):
    # JAX_COMPILATION_CACHE_DIR set: used as it is, nothing set in code
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)
    assert compile_cache.enable() == str(tmp_path)


def test_compile_cache_default_is_the_checkout(tmp_path):
    code = ("from horovod_tpu.utils import compile_cache as c; "
            "print(c.cache_dir()); print(c.enable())")
    env = _cpu_env()
    env.pop(compile_cache.ENV, None)
    outs = []
    for cwd in (REPO, str(tmp_path)):
        res = subprocess.run([sys.executable, "-c", code], env=env, cwd=cwd,
                             capture_output=True, text=True, timeout=120)
        assert res.returncode == 0, res.stderr[-2000:]
        outs.append(res.stdout.split())
    want = os.path.join(REPO, ".jax_cache")
    assert outs == [[want, want], [want, want]]


def test_tpurun_exports_the_cache_to_its_children(monkeypatch):
    import horovod_tpu.runner.launch as launch

    monkeypatch.delenv(compile_cache.ENV, raising=False)
    env = launch._worker_env({}, {}, "127.0.0.1:1", 2, 2, 0, False)
    assert env[compile_cache.ENV] == os.path.join(REPO, ".jax_cache")
    env = launch._worker_env({compile_cache.ENV: "/x"}, {}, "127.0.0.1:1",
                             2, 2, 0, False)
    assert env[compile_cache.ENV] == "/x"


def test_tpurun_deals_one_chip_to_each_rank(monkeypatch):
    import horovod_tpu.runner.launch as launch

    monkeypatch.setattr(launch, "_local_tpu_chips", lambda: 4)
    assert launch._tpu_process_ports({"JAX_PLATFORMS": "cpu"}, 4) is None
    assert launch._tpu_process_ports({}, 1) is None
    ports = launch._tpu_process_ports({}, 4)
    assert len(set(ports)) == 4
    envs = [launch._worker_env({}, {}, "127.0.0.1:1", 2, 4, r, False,
                               local_rank=r, local_size=4, tpu_ports=ports)
            for r in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert {e["TPU_PROCESS_ADDRESSES"] for e in envs} == {
        ",".join(f"localhost:{p}" for p in ports)}
    assert [e["TPU_PROCESS_PORT"] for e in envs] == [str(p) for p in ports]
    assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"2,2,1"}
    assert {e["TPU_CHIPS_PER_PROCESS_BOUNDS"] for e in envs} == {"1,1,1"}
    with pytest.raises(SystemExit, match="2 processes on a host with 4 TPU"):
        launch._tpu_process_ports({}, 2)


def test_tpurun_binds_nothing_without_tpu_chips(monkeypatch):
    import horovod_tpu.runner.launch as launch

    monkeypatch.setattr(launch, "_local_tpu_chips", lambda: 0)
    assert launch._tpu_process_ports({}, 4) is None
    env = launch._worker_env({}, {}, "127.0.0.1:1", 2, 4, 0, False)
    assert not [k for k in env if k.startswith("TPU_")]
