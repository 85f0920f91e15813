#!/usr/bin/env python
"""Chip smoke: the framework's main path, once, on the TPU that is attached.

    python chip_smoke.py              # one chip: phases A-D below
    python chip_smoke.py --multichip  # one host with four chips (see below)

This is the quickest proof that the system still starts on the chip.  It is
one process, it needs an accelerator (with none it exits non-zero before any
phase), and the first phase that fails ends the run with a traceback and a
non-zero exit: nothing here catches a failure to keep going.  Every phase
prints one JSON line (name, compile seconds, run seconds, what it checked);
the last line of stdout is ``{"ok": true, "device": {...}}`` and nothing else.
Step times are information, not a benchmark.

One chip:
  A init     build the native core from the sources git carries, hvd.init(),
             native controller asserted
  B train    ResNet-50 bf16 space_to_depth b128@224 SGD+momentum (bench.py's
             configuration) and gpt_small 12L S2048 b4 bf16 flash AdamW
             (tools/transformer_bench.py's replicated leg), through
             create_train_state -> replicate_state ->
             data_parallel_train_step: loss finite and falling on a fixed
             batch, no compile after warm-up
  C kernels  flash_attention forward and gradients against the float32
             dense reference, program holds the Mosaic kernel
  D eager    allreduce / allreduce_async+synchronize / allgather / broadcast
             of a few MB through the native controller

--multichip runs only the four-chip path and what it is compared with:
``tpurun -np 4`` (one process per chip: eager allreduce of rank-dependent
values, then the synthetic benchmark example) from this parent before it
touches the backend, then, in this process over all four chips, three
ResNet-50 sync-BN steps over the world mesh against the same steps on one
chip.

The phases are plain functions of their sizes; tests/test_chip_smoke.py calls
them at tiny sizes on the CPU mesh.  The script itself takes no size options.
"""

import argparse
import json
import os
import re
import signal
import statistics
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
NATIVE_SRC = os.path.join(REPO, "horovod_tpu", "native", "src")

# bf16 kernel against the float32 reference, both normalised by the
# reference's largest magnitude: bf16 keeps 8 bits of mantissa (2^-8 = 0.4%)
# and the kernel rounds the probabilities to bf16 before the PV product
FLASH_FWD_TOL = 2e-2
FLASH_GRAD_TOL = 4e-2
# four chips against one, float32 model: the same arithmetic in another
# summation order (per-chip partial sums then psum), three steps deep
MULTICHIP_LOSS_RTOL = 1e-2
MULTICHIP_PARAM_TOL = 1e-2

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def emit(record: dict) -> dict:
    print(json.dumps(record), flush=True)
    return record


class CompileMeter:
    """Counts backend compiles and their seconds through jax.monitoring
    (a persistent-cache hit is reported under the same event, as the time
    it took to load)."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, duration, **_):
        if name == _COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def snapshot(self):
        return self.count, self.seconds


_meter = None


def meter() -> CompileMeter:
    global _meter
    if _meter is None:
        _meter = CompileMeter()
    return _meter


def device_record() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


# -- A: init ----------------------------------------------------------------


def phase_init(rebuild: bool = True) -> dict:
    """Native core built from source, framework up, native controller."""
    t0 = time.perf_counter()
    if rebuild:
        # -B: unconditional rebuild of what git carries; the Makefile links
        # under a private name and renames, so a loader never sees half a file
        subprocess.run(["make", "-B", "-C", NATIVE_SRC, "all"], check=True,
                       capture_output=True, text=True)
    build_s = time.perf_counter() - t0

    import importlib.metadata as md

    import jax

    import horovod_tpu as hvd
    from horovod_tpu.utils import compile_cache

    cache = compile_cache.enable()
    meter()
    hvd.init()
    assert hvd.native_built(), "hvd.init() did not load the native controller"
    assert hvd.size() == jax.device_count(), (hvd.size(), jax.device_count())
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    return emit({
        "phase": "init", "native_build_s": round(build_s, 2),
        "rebuilt": rebuild, "native_controller": True, "size": hvd.size(),
        "device": device_record(), "jax": jax.__version__,
        "jaxlib": md.version("jaxlib"), "libtpu": libtpu,
        "compile_cache_dir": cache,
    })


# -- B: train ---------------------------------------------------------------


def _train(name, model, optimizer, inputs, labels, warmup, steps,
           mesh=None, want_kernel=False) -> dict:
    """create_train_state -> replicate_state -> data_parallel_train_step on
    one fixed batch: finite falling loss, no compile after warm-up."""
    import jax
    import numpy as np

    from horovod_tpu import training

    m = meter()
    c0, s0 = m.snapshot()
    t0 = time.perf_counter()
    state = training.create_train_state(
        model, optimizer, jax.random.PRNGKey(0), inputs[:1])
    state = training.replicate_state(state, mesh)
    step = training.data_parallel_train_step(model, optimizer, mesh=mesh)
    if want_kernel:
        text = step.lower(state, inputs, labels).as_text()
        assert "tpu_custom_call" in text, (
            f"{name}: no Mosaic kernel in the step (flash ran interpreted?)")
    losses = []
    for _ in range(warmup):
        state, loss = step(state, inputs, labels)
        losses.append(float(loss))
    setup_s = time.perf_counter() - t0
    c1, s1 = m.snapshot()
    times = []
    for _ in range(steps):
        t = time.perf_counter()
        state, loss = step(state, inputs, labels)
        jax.block_until_ready(loss)
        times.append(time.perf_counter() - t)
        losses.append(float(loss))
    c2, _ = m.snapshot()
    assert all(np.isfinite(losses)), f"{name}: non-finite loss in {losses}"
    assert losses[-1] < losses[0], f"{name}: loss did not fall: {losses}"
    assert c2 == c1, f"{name}: {c2 - c1} compile(s) after warm-up"
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    return {
        "name": name, "params": int(n_params),
        "compiles": c1 - c0, "compile_s": round(s1 - s0, 2),
        "setup_s": round(setup_s, 2), "warmup": warmup, "steps": steps,
        "step_ms_median": round(statistics.median(times) * 1e3, 3),
        "step_ms_all": [round(t * 1e3, 2) for t in times],
        "loss_first": losses[0], "loss_last": losses[-1],
        "losses": [round(x, 4) for x in losses],
        "compiles_after_warmup": c2 - c1,
        "peak_bytes_in_use": _peak_bytes(),
    }


def _resnet_and_batch(model_name, batch, image_size, **kwargs):
    """The ResNet (bench.py's stem at full size) and one seeded batch."""
    import numpy as np

    from horovod_tpu import models

    if "Tiny" not in model_name:
        kwargs.update(num_classes=1000, stem="space_to_depth")
    model = getattr(models, model_name)(**kwargs)
    images = np.random.RandomState(0).randn(
        batch, image_size, image_size, 3).astype(np.float32)
    labels = np.random.RandomState(1).randint(
        0, kwargs.get("num_classes", 10), size=(batch,))
    return model, images, labels


def phase_train_resnet(model_name="ResNet50", batch=128, image_size=224,
                       warmup=3, steps=10, mesh=None) -> dict:
    """bench.py's configuration: bf16, space_to_depth stem, SGD+momentum."""
    import jax.numpy as jnp
    import optax

    model, images, labels = _resnet_and_batch(
        model_name, batch, image_size, dtype=jnp.bfloat16)
    rec = _train(model_name, model, optax.sgd(0.1, momentum=0.9),
                 jnp.asarray(images), jnp.asarray(labels), warmup, steps,
                 mesh=mesh)
    rec.update(phase="train_resnet", batch=batch, image_size=image_size,
               images_per_s=round(batch / rec["step_ms_median"] * 1e3, 1))
    return emit(rec)


def phase_train_transformer(config=None, batch=4, seq=2048, warmup=3,
                            steps=10, mesh=None) -> dict:
    """tools/transformer_bench.py's replicated leg: gpt_small, flash, AdamW."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from horovod_tpu.models.transformer import Transformer, gpt_small

    cfg = config or gpt_small(attention_impl="flash", max_seq_len=seq,
                              dtype=jnp.bfloat16)
    rs = np.random.RandomState(0)
    tok = jnp.asarray(rs.randint(0, cfg.vocab_size, (batch, seq)))
    tgt = jnp.asarray(rs.randint(0, cfg.vocab_size, (batch, seq)))
    rec = _train("transformer", Transformer(cfg), optax.adamw(1e-3), tok,
                 tgt, warmup, steps, mesh=mesh,
                 want_kernel=jax.default_backend() == "tpu")
    rec.update(phase="train_transformer", batch=batch, seq=seq,
               layers=cfg.num_layers, heads=cfg.num_heads,
               head_dim=cfg.head_dim, attention_impl=cfg.attention_impl,
               tokens_per_s=round(
                   batch * seq / rec["step_ms_median"] * 1e3, 1))
    return emit(rec)


# -- C: kernels -------------------------------------------------------------


def phase_kernels(batch=2, seq=2048, heads=32, kv_heads=8, head_dim=128,
                  interpret=False) -> dict:
    """flash_attention forward and gradients against the float32 dense
    reference.  ``interpret=False`` is the chip's path: it names the Mosaic
    kernel outright, so a backend that cannot run it fails instead of
    falling back to the interpreter."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.models.transformer import causal_dot_attention
    from horovod_tpu.ops.flash_attention import flash_attention

    m = meter()
    c0, s0 = m.snapshot()
    t0 = time.perf_counter()
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (batch, seq, heads, head_dim), jnp.bfloat16)
    k = jax.random.normal(ks[1], (batch, seq, kv_heads, head_dim),
                          jnp.bfloat16)
    v = jax.random.normal(ks[2], (batch, seq, kv_heads, head_dim),
                          jnp.bfloat16)
    w = jax.random.normal(ks[3], (batch, seq, heads, head_dim), jnp.float32)

    def flash_loss(q, k, v, w):
        out = flash_attention(q, k, v, causal=True, interpret=interpret)
        return jnp.sum(out.astype(jnp.float32) * w), out

    def dense_loss(q, k, v, w):
        out = causal_dot_attention(q, k, v, causal=True)
        return jnp.sum(out * w), out

    flash = jax.jit(jax.value_and_grad(flash_loss, argnums=(0, 1, 2),
                                       has_aux=True))
    if not interpret:
        text = flash.lower(q, k, v, w).as_text()
        assert "tpu_custom_call" in text, "flash_attention lowered no kernel"
    (_, out), grads = flash(q, k, v, w)
    jax.block_until_ready(grads)
    with jax.default_matmul_precision("highest"):
        (_, ref), ref_grads = jax.jit(jax.value_and_grad(
            dense_loss, argnums=(0, 1, 2), has_aux=True))(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), w)

    def err(a, b):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        assert a.shape == b.shape and np.isfinite(a).all(), a.shape
        return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))

    fwd_err = err(out, ref)
    grad_err = {n: err(g, r) for n, g, r in zip("qkv", grads, ref_grads)}
    assert fwd_err <= FLASH_FWD_TOL, fwd_err
    assert max(grad_err.values()) <= FLASH_GRAD_TOL, grad_err
    c1, s1 = m.snapshot()
    return emit({
        "phase": "kernels", "shape": [batch, seq, heads, kv_heads, head_dim],
        "interpret": bool(interpret), "tpu_custom_call": not interpret,
        "fwd_err": fwd_err, "fwd_tol": FLASH_FWD_TOL,
        "grad_err": grad_err, "grad_tol": FLASH_GRAD_TOL,
        "compiles": c1 - c0, "compile_s": round(s1 - s0, 2),
        "run_s": round(time.perf_counter() - t0, 2),
    })


# -- D: eager surface -------------------------------------------------------


def phase_eager(elements=1 << 20) -> dict:
    """The Horovod surface on device arrays through the native controller.
    One process is one contributor, so the reductions return their input."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvd

    assert hvd.native_built()
    m = meter()
    c0, s0 = m.snapshot()
    t0 = time.perf_counter()
    x_np = np.random.RandomState(0).randn(elements).astype(np.float32)
    x = jnp.asarray(x_np)
    rows = x.reshape(-1, 256)
    out = hvd.allreduce(x, op=hvd.Sum, name="smoke.sum")
    assert isinstance(out, jax.Array), type(out)  # stays a device array
    np.testing.assert_array_equal(np.asarray(out), x_np)
    np.testing.assert_array_equal(
        np.asarray(hvd.allreduce(x, name="smoke.avg")), x_np)
    handles = [hvd.allreduce_async(x * (i + 1), op=hvd.Sum,
                                   name=f"smoke.async.{i}") for i in range(4)]
    for i, h in enumerate(handles):
        np.testing.assert_array_equal(
            np.asarray(hvd.synchronize(h)), x_np * np.float32(i + 1))
    np.testing.assert_array_equal(
        np.asarray(hvd.allgather(rows, name="smoke.gather")),
        x_np.reshape(-1, 256))
    np.testing.assert_array_equal(
        np.asarray(hvd.broadcast(x, root_rank=0, name="smoke.bcast")), x_np)
    c1, s1 = m.snapshot()
    return emit({
        "phase": "eager", "bytes": int(x_np.nbytes), "native": True,
        "ops": ["allreduce", "allreduce_async+synchronize", "allgather",
                "broadcast"],
        "compiles": c1 - c0, "compile_s": round(s1 - s0, 2),
        "run_s": round(time.perf_counter() - t0, 2),
    })


# -- four chips -------------------------------------------------------------

_PROBE = ("import jax; d = jax.devices(); "
          "print('PROBE', d[0].platform, len(d))")

# under tpurun, one process per chip: each rank must hold exactly one local
# chip of a four-chip world, and the eager allreduce of rank-dependent
# values must equal the numpy sum
_RANK_WORKER = """
import os
import jax, jax.numpy as jnp, numpy as np
import horovod_tpu as hvd
hvd.init()
r, n = hvd.rank(), hvd.size()
local = jax.local_devices()
assert jax.devices()[0].platform == "tpu", jax.devices()
assert n == 4 and len(local) == 1 and jax.device_count() == 4, (n, local)
assert hvd.native_built()
base = np.arange(1 << 18, dtype=np.float32)
out = hvd.allreduce(jnp.asarray(base * (r + 1)), op=hvd.Sum, name="rankdep")
np.testing.assert_array_equal(np.asarray(out), base * sum(range(1, n + 1)))
ids = hvd.allgather(jnp.asarray([local[0].id], jnp.int32), name="chips")
assert len(set(np.asarray(ids).tolist())) == n, ids
os.write(1, f"RANK_OK {r} chip {local[0].id}\\n".encode())  # one write
"""


def _run_child(cmd, timeout):
    """Run a child in its own process group; on timeout the whole group is
    killed, so nothing this script starts outlives it."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    t0 = time.perf_counter()
    proc = subprocess.Popen(cmd, cwd=REPO, env=env, text=True,
                            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                            start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        out, _ = proc.communicate()
        raise RuntimeError(
            f"{' '.join(cmd[:6])}... hung >{timeout}s:\n{out[-3000:]}")
    return proc.returncode, out, time.perf_counter() - t0


def phase_tpurun(np_=4, timeout=420) -> dict:
    """``tpurun -np 4`` from a parent that has not touched the backend:
    does each process get one chip?"""
    rc, out, _ = _run_child([sys.executable, "-c", _PROBE], 120)
    assert rc == 0 and f"PROBE tpu {np_}" in out.splitlines(), (
        f"--multichip needs {np_} TPU chips; the probe said: {out[-500:]}")
    tpurun = [sys.executable, "-m", "horovod_tpu.runner", "-np", str(np_),
              "--"]
    rc, out, ranks_s = _run_child(
        tpurun + [sys.executable, "-c", _RANK_WORKER], 240)
    ok = sorted(re.findall(r"RANK_OK (\d+) chip \d+", out))
    assert rc == 0 and ok == [str(r) for r in range(np_)], (
        f"tpurun rank worker rc={rc}:\n{out[-4000:]}")
    rc, out, example_s = _run_child(
        tpurun + [sys.executable,
                  os.path.join("examples", "jax", "jax_synthetic_benchmark.py"),
                  "--model", "ResNet50", "--num-iters", "1",
                  "--num-batches-per-iter", "3"], timeout)
    rate = re.findall(r"Img/sec total: [^\n]*", out)
    assert rc == 0 and rate, f"tpurun example rc={rc}:\n{out[-4000:]}"
    return emit({
        "phase": "tpurun", "np": np_, "one_chip_per_process": True,
        "eager_rank_dependent_allreduce": "exact",
        "rank_worker_s": round(ranks_s, 1),
        "example_s": round(example_s, 1), "example": rate[0],
    })


def phase_multichip(model_name="ResNet50", batch=128, image_size=224,
                    steps=3) -> dict:
    """Sync-BN data-parallel steps over the world mesh against the same
    global batch on one of its chips (float32 model, so the comparison is
    about the sharding and the collectives, not about bf16)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu import training

    world = hvd.world_mesh()
    n = hvd.size()
    one = Mesh(np.array(jax.devices()[:1]), (hvd.WORLD_AXIS,))
    model, images_np, labels_np = _resnet_and_batch(
        model_name, batch, image_size, dtype=jnp.float32,
        bn_axis_name=hvd.WORLD_AXIS)
    optimizer = optax.sgd(0.1, momentum=0.9)

    def run(mesh):
        sharding = NamedSharding(mesh, P(hvd.WORLD_AXIS))
        images = jax.device_put(images_np, sharding)
        labels = jax.device_put(labels_np, sharding)
        state = training.create_train_state(
            model, optimizer, jax.random.PRNGKey(0), images_np[:1])
        state = training.replicate_state(state, mesh)
        step = training.data_parallel_train_step(model, optimizer, mesh=mesh)
        losses = []
        for _ in range(steps):
            state, loss = step(state, images, labels)
            losses.append(float(loss))
        return images, losses, jax.device_get(state.params)

    t0 = time.perf_counter()
    images, world_losses, world_params = run(world)
    shards = images.addressable_shards
    assert len({s.device for s in shards}) == n, shards
    assert all(s.data.shape[0] == batch // n for s in shards)
    in_use = {}
    for d in world.devices.flat:
        stats = d.memory_stats()
        if stats:  # the CPU backend reports none
            in_use[str(d.id)] = stats["bytes_in_use"]
            assert stats["bytes_in_use"] > 0, d
    _, one_losses, one_params = run(one)
    assert all(np.isfinite(world_losses)), world_losses
    loss_rel = max(abs(a - b) / abs(b)
                   for a, b in zip(world_losses, one_losses))
    scale = max(float(np.max(np.abs(x)))
                for x in jax.tree_util.tree_leaves(one_params))
    param_err = max(
        float(np.max(np.abs(a - b))) for a, b in zip(
            jax.tree_util.tree_leaves(world_params),
            jax.tree_util.tree_leaves(one_params))) / scale
    assert loss_rel <= MULTICHIP_LOSS_RTOL, (world_losses, one_losses)
    assert param_err <= MULTICHIP_PARAM_TOL, param_err

    # in-jit collective over the chips: rank-dependent values, numpy's sum
    base = np.arange(1024, dtype=np.float32)
    summed = hvd.run_per_rank(lambda r: hvd.spmd.allreduce(
        jnp.asarray(base) * (r + 1).astype(jnp.float32), op=hvd.Sum))
    np.testing.assert_array_equal(
        np.asarray(summed),
        np.broadcast_to(base * sum(range(1, n + 1)), (n, base.size)))
    return emit({
        "phase": "multichip", "model": model_name, "world": n,
        "batch": batch, "steps": steps, "shard_devices": n,
        "bytes_in_use": in_use,
        "world_losses": world_losses, "one_chip_losses": one_losses,
        "loss_rel_err": loss_rel, "loss_rtol": MULTICHIP_LOSS_RTOL,
        "param_err": param_err, "param_tol": MULTICHIP_PARAM_TOL,
        "spmd_allreduce": "exact",
        "run_s": round(time.perf_counter() - t0, 2),
    })


# -- entry ------------------------------------------------------------------


def _require_tpu() -> dict:
    import jax

    device = device_record()
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU (jax.devices()[0] is {jax.devices()[0]}); "
              "this script does not run on another backend", file=sys.stderr)
        sys.exit(1)
    return device


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--multichip", action="store_true",
                   help="the four-chip path and what it is compared with")
    args = p.parse_args(argv)
    if args.multichip:
        phase_tpurun()  # first: this parent has not touched the backend yet
        device = _require_tpu()
        assert device["count"] == 4, device
        phase_init(rebuild=False)  # the ranks above built it from source
        phase_multichip()
    else:
        device = _require_tpu()
        phase_init()
        phase_train_resnet()
        phase_train_transformer()
        phase_kernels()
        phase_eager()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
