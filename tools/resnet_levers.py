#!/usr/bin/env python
"""ResNet-50 perf lever sweep on the chip (VERDICT r3 item 9).

Measures each proposed lever against the round-3 "plateau" (MFU
0.32–0.33 at batch 128, HBM-roofline-bound per PERF.md): batch-size
curve, per-block rematerialization (HBM-for-FLOPs trade), stem choice.
Same timing protocol as bench.py (chained steps, scalar fetch).

    python tools/resnet_levers.py [--iters 30]
"""
import argparse
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
from bench import device_peaks  # noqa: E402


def run(batch, stem, remat, peak, iters=30, warmup=5):
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16, stem=stem,
                     remat=remat)
    rng = jax.random.PRNGKey(0)
    images = jnp.asarray(
        np.random.RandomState(0).randn(batch, 224, 224, 3),
        dtype=jnp.float32,
    )
    labels = jnp.asarray(
        np.random.RandomState(1).randint(0, 1000, size=(batch,)))
    optimizer = optax.sgd(0.1, momentum=0.9)
    state = training.create_train_state(model, optimizer, rng, images[:2])
    state = training.replicate_state(state)
    step = training.data_parallel_train_step(model, optimizer)

    flops = bytes_accessed = None
    try:
        step = step.lower(state, images, labels).compile()
        ca = step.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0] if ca else None
        if ca and jax.device_count() == 1:
            flops = float(ca.get("flops", 0)) or None
            bytes_accessed = float(ca.get("bytes accessed", 0)) or None
    except Exception as e:
        print(f"cost_analysis unavailable: {e}", file=sys.stderr)

    for _ in range(warmup):
        state, loss = step(state, images, labels)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, loss = step(state, images, labels)
    final = float(loss)
    dt = (time.perf_counter() - t0) / iters
    assert np.isfinite(final)
    mfu = f"{flops / dt / peak:.4f}" if flops else "n/a"
    gbytes = f"{bytes_accessed / 1e9:6.1f}" if bytes_accessed else "   n/a"
    print(f"batch={batch:4d} stem={stem:14s} remat={int(remat)} "
          f"step={dt * 1e3:7.2f} ms  {batch / dt:7.0f} img/s  "
          f"mfu={mfu}  xla_GB={gbytes}", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=30)
    args = ap.parse_args()
    hvd.init()
    peak = device_peaks()["bf16_flops"]
    print(f"backend={jax.default_backend()} devices={jax.device_count()} "
          f"peak={peak}", flush=True)
    for batch, stem, remat in [
        (128, "space_to_depth", False),   # round-3/4 bench config
        (128, "space_to_depth", True),    # the HBM-for-FLOPs lever
        (256, "space_to_depth", False),   # the falling curve...
        (256, "space_to_depth", True),    # ...and whether remat fixes it
        (512, "space_to_depth", True),
        (128, "conv", False),             # stem control
    ]:
        run(batch, stem, remat, peak, iters=args.iters)
    return 0


if __name__ == "__main__":
    sys.exit(main())
