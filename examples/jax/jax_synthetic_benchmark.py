"""Synthetic-data training benchmark, the reference's headline example.

Reference parity: examples/pytorch/pytorch_synthetic_benchmark.py and
examples/tensorflow2/tensorflow2_synthetic_benchmark.py — same protocol
(synthetic ImageNet-shaped batches, warmup then timed iterations, report
img/sec per worker and total) on the TPU-native stack: the whole train
step (fwd, bwd, fused gradient allreduce, update) is ONE compiled XLA
program over the world mesh.

    python examples/jax/jax_synthetic_benchmark.py --model ResNet50
    tpurun -np 2 python examples/jax/jax_synthetic_benchmark.py  # CPU demo

``--data npy --data-path DIR`` (or ``--data folder``) feeds the step
through the ``horovod_tpu.data`` pipeline — per-rank sharded on-disk
arrays, worker-pool decode, double-buffered device prefetch — and prints
the pipeline's input-wait stats next to img/sec (docs/DATA.md).
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu import data as hvd_data, models, training


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="ResNet50",
                   help="ResNet18/34/50/101/152 or ResNetTiny")
    p.add_argument("--batch-size", type=int, default=32,
                   help="per-worker batch size (reference default)")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-iters", type=int, default=10,
                   help="timed iterations per measurement")
    p.add_argument("--num-batches-per-iter", type=int, default=10)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--stem", default="space_to_depth",
                   choices=["conv", "space_to_depth"])
    p.add_argument("--data", default="synthetic",
                   choices=["synthetic", "npy", "folder"],
                   help="synthetic = device-resident; npy/folder stream "
                        "through the horovod_tpu.data pipeline")
    p.add_argument("--data-path", default=None)
    args = p.parse_args()

    hvd.init()
    model_cls = getattr(models, args.model)
    kwargs = {"dtype": jnp.bfloat16}
    if "Tiny" not in args.model:
        kwargs.update(num_classes=1000, stem=args.stem)
    model = model_cls(**kwargs)

    # per-worker means per-chip: the compiled step shards the global
    # batch over every chip of the world mesh (training.py P(axis))
    global_batch = args.batch_size * max(hvd.size(), 1)
    loader = None
    if args.data == "synthetic":
        images = jnp.asarray(
            np.random.RandomState(0)
            .randn(global_batch, args.image_size, args.image_size, 3)
            .astype(np.float32)
        )
        labels = jnp.asarray(
            np.random.RandomState(1).randint(0, 1000, size=(global_batch,))
        )
    else:
        # the drop-in loader, prefetched to device (docs/DATA.md).  The
        # compiled step takes the GLOBAL batch, and like the resident
        # path every process supplies it whole — so the loader is pinned
        # to the un-sharded spec here (per-rank sharding pairs with
        # per-process global-array assembly, out of scope for this demo)
        loader = hvd_data.make_loader(
            args.data, args.data_path, batch_size=global_batch,
            image_size=args.image_size,
            shard=hvd_data.ShardSpec(0, 1))
        if len(loader) == 0:
            raise SystemExit(
                f"dataset too small: needs >= {global_batch} samples "
                f"for one global batch")
        images, labels = next(iter(loader))
    optimizer = optax.sgd(0.01, momentum=0.9)
    state = training.create_train_state(
        model, optimizer, jax.random.PRNGKey(0), images[:2]
    )
    state = training.replicate_state(state)
    step = training.data_parallel_train_step(model, optimizer)

    loss = jnp.zeros(())
    for _ in range(args.warmup):
        state, loss = step(state, images, labels)
    float(loss)

    if hvd.rank() == 0:
        print(f"Model: {args.model}, batch {args.batch_size}/worker, "
              f"{hvd.size()} workers")
    img_secs = []
    for i in range(args.num_iters):
        t0 = time.perf_counter()
        if loader is None:
            for _ in range(args.num_batches_per_iter):
                state, loss = step(state, images, labels)
            float(loss)
            n_batches = args.num_batches_per_iter
        else:
            state, loss = training.fit_epoch(step, state, loader, epoch=i)
            n_batches = max(len(loader), 1)
        dt = time.perf_counter() - t0
        rate = global_batch * n_batches / dt
        img_secs.append(rate)
        if hvd.rank() == 0:
            extra = (f"  (input wait "
                     f"{loader.stats().get('input_wait_ms_mean', 0)} "
                     "ms/batch)") if loader is not None else ""
            print(f"Iter #{i}: {rate:.1f} img/sec total{extra}")
    if hvd.rank() == 0:
        mean, conf = np.mean(img_secs), 1.96 * np.std(img_secs)
        print(f"Img/sec total: {mean:.1f} +- {conf:.1f}")
        print(f"Img/sec per worker: {mean / hvd.size():.1f}")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
