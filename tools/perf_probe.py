#!/usr/bin/env python
"""Perf probe: honest step timing on the real chip.

Every timing below chains N dependent steps and fetches the final loss
scalar — the same protocol as bench.py.

Sweeps batch size and input dtype; prints XLA cost-analysis FLOPs so MFU
can be cross-checked against the analytic model-FLOP count.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu.models.resnet import ResNet50  # noqa: E402
from horovod_tpu import training  # noqa: E402
from bench import RESNET50_TRAIN_FLOPS_PER_IMG, device_peaks  # noqa: E402


def run(batch, img_dtype, peak, iters=30, warmup=5):
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    rng = jax.random.PRNGKey(0)
    images = jnp.asarray(
        np.random.RandomState(0).randn(batch, 224, 224, 3), dtype=img_dtype
    )
    labels = jnp.asarray(np.random.RandomState(1).randint(0, 1000, size=(batch,)))
    optimizer = optax.sgd(0.1, momentum=0.9)
    state = training.create_train_state(model, optimizer, rng, images[:2])
    state = training.replicate_state(state)
    step = training.data_parallel_train_step(model, optimizer)

    # cost_analysis() is per-device for SPMD-partitioned modules; this
    # probe is a single-chip tool, so require one device for the XLA MFU.
    flops = None
    try:
        step = step.lower(state, images, labels).compile()
        ca = step.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0] if ca else None
        if ca and jax.device_count() == 1:
            flops = float(ca.get("flops", 0)) or None
    except Exception as e:
        print(f"cost_analysis unavailable: {e}", file=sys.stderr)

    for _ in range(warmup):
        state, loss = step(state, images, labels)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, loss = step(state, images, labels)
    float(loss)
    dt = (time.perf_counter() - t0) / iters
    mfu_xla = f"{flops / dt / peak:.3f}" if flops else "n/a"
    mfu_model = f"{batch * RESNET50_TRAIN_FLOPS_PER_IMG / dt / peak:.3f}"
    print(
        f"batch={batch:4d} img={img_dtype.__name__:8s} "
        f"step={dt * 1e3:7.2f} ms  {batch / dt:8.0f} img/s  "
        f"xla_flops={flops or 0:.3e}  MFU(xla)={mfu_xla}  MFU(2*MAC)={mfu_model}"
    )
    return dt


def main():
    hvd.init()
    print("backend:", jax.default_backend(), file=sys.stderr)
    peak = device_peaks()["bf16_flops"]
    run(128, jnp.float32, peak)
    run(128, jnp.bfloat16, peak)
    run(256, jnp.bfloat16, peak)
    run(512, jnp.bfloat16, peak)
    return 0


if __name__ == "__main__":
    sys.exit(main())
