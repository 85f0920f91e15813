#!/usr/bin/env python
"""Input-pipeline benchmark + large-batch sweep (PERF.md round 6).

Three modes, all emitting one JSON document on stdout:

  stages   microbenchmark each pipeline stage standalone: source read,
           worker-pool scaling, prefetch-on/off A/B against a simulated
           compute step (how much overlap is worth);
  ab       end-to-end train-step comparison: device-resident synthetic
           vs pipeline-fed npy (prefetch on and off) — the bench.py
           acceptance A/B (resident vs --data npy) in one process;
  sweep    batch-size sweep of the compiled train step with pipeline
           feeding, recording step time, input wait, host produce/put
           cost, and XLA cost analysis (flops + bytes accessed) per
           batch — the instrumentation behind "name the large-batch
           limiter" (PERF.md).

CPU-host runs use ResNetTiny/64px so the numbers are about the PIPELINE
(decode, staging, overlap); chip runs use the bench.py config
(ResNet-50, 224px, space-to-depth stem) so sweep results line up with
the headline table.
"""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import _EpochFeed  # noqa: E402 — cumulative pipeline stats


def _make_npy(root, n, image_size):
    import atexit
    import shutil

    import numpy as np

    from horovod_tpu import data

    atexit.register(shutil.rmtree, root, ignore_errors=True)
    rng = np.random.RandomState(0)
    inputs = rng.randint(0, 256, size=(n, image_size, image_size, 3),
                         dtype=np.uint8)
    labels = rng.randint(0, 1000, size=(n,)).astype(np.int32)
    data.write_npy_shards(root, inputs, labels, num_shards=4)
    return root


def bench_stages(args):
    import numpy as np

    from horovod_tpu import data
    from horovod_tpu.data import workers as workers_mod

    out = {}
    bs, size = args.batch, args.image_size
    root = _make_npy(tempfile.mkdtemp(prefix="dpb_npy_"), 8 * bs, size)
    src = data.NpyShardSource(root)
    idx = np.arange(bs)

    # raw source read (mmap fancy-index + uint8->f32 decode)
    t0 = time.perf_counter()
    reps = 10
    for r in range(reps):
        x, y = src.batch((idx + r * bs) % len(src))
        x.astype(np.float32)
    out["npy_read_decode_ms_per_batch"] = round(
        (time.perf_counter() - t0) / reps * 1e3, 3)

    syn = data.SyntheticSource(8 * bs, image_size=size)
    t0 = time.perf_counter()
    for r in range(3):
        syn.batch((idx + r * bs) % len(syn))
    out["synthetic_gen_ms_per_batch"] = round(
        (time.perf_counter() - t0) / 3 * 1e3, 3)

    # worker-pool scaling on the decode workload
    def collate(indices):
        x, y = src.batch(indices)
        return x.astype(np.float32) / 255.0, y

    batches = [(idx + r * bs) % len(src) for r in range(16)]
    scaling = {}
    for w in (0, 1, 2, 4):
        t0 = time.perf_counter()
        for _ in workers_mod.map_ordered(collate, batches, num_workers=w,
                                         window=max(2 * w, 2)):
            pass
        scaling[str(w)] = round((time.perf_counter() - t0) / 16 * 1e3, 3)
    out["map_ordered_ms_per_batch_by_workers"] = scaling

    # prefetch A/B against a simulated step: with overlap the loop should
    # cost ~max(produce, step) per item, without it produce + step
    step_ms = args.sim_step_ms

    def run(depth):
        loader = data.DataLoader(src, batch_size=bs, transform=None,
                                 num_workers=2, prefetch_depth=depth,
                                 device_put=False, shuffle=False,
                                 shard=data.ShardSpec(0, 1))
        t0 = time.perf_counter()
        n = 0
        for _ in loader:
            time.sleep(step_ms / 1e3)  # the "compute"
            n += 1
        return round((time.perf_counter() - t0) / n * 1e3, 3), loader.stats()

    for depth in (0, 2):
        per, stats = run(depth)
        out[f"loop_ms_per_batch_prefetch_{depth}"] = per
        out[f"input_wait_ms_mean_prefetch_{depth}"] = stats[
            "input_wait_ms_mean"]
    out["sim_step_ms"] = step_ms
    return out


def _train_setup(on_tpu, batch, image_size):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from horovod_tpu import training
    from horovod_tpu.models.resnet import ResNet50
    from horovod_tpu.models import ResNetTiny

    if on_tpu:
        model = ResNet50(num_classes=1000, dtype=jnp.bfloat16,
                         stem="space_to_depth")
    else:
        model = ResNetTiny(dtype=jnp.bfloat16)
    optimizer = optax.sgd(0.1, momentum=0.9)
    sample = jnp.asarray(
        np.zeros((2, image_size, image_size, 3), np.float32))
    state = training.create_train_state(
        model, optimizer, jax.random.PRNGKey(0), sample)
    state = training.replicate_state(state)
    step = training.data_parallel_train_step(model, optimizer)
    return state, step


def _timed_steps(step, state, feed, iters):
    t0 = time.perf_counter()
    loss = None
    for _ in range(iters):
        state, loss = step(state, *next(feed))
    if loss is not None:
        float(loss)
    return state, time.perf_counter() - t0


def bench_ab(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu import data

    on_tpu = jax.default_backend() != "cpu"
    bs, size = args.batch, args.image_size
    warmup, iters = (5, 20) if on_tpu else (1, 3)
    out = {"backend": jax.default_backend(), "batch": bs,
           "image_size": size}

    # resident baseline
    state, step = _train_setup(on_tpu, bs, size)
    images = jnp.asarray(np.random.RandomState(0).randn(
        bs, size, size, 3).astype(np.float32))
    labels = jnp.asarray(np.random.RandomState(1).randint(
        0, 1000, size=(bs,)))

    def resident():
        while True:
            yield images, labels

    feed = resident()
    state, _ = _timed_steps(step, state, feed, warmup)
    state, dt = _timed_steps(step, state, feed, iters)
    out["resident_img_s"] = round(bs * iters / dt, 1)
    out["resident_step_ms"] = round(dt / iters * 1e3, 2)

    # pipeline-fed npy, prefetch on/off
    root = _make_npy(tempfile.mkdtemp(prefix="dpb_ab_"), 8 * bs, size)
    for depth, tag in ((None, "prefetch_on"), (0, "prefetch_off")):
        loader = data.make_loader(
            "npy", root, batch_size=bs, image_size=size,
            cast="bfloat16" if on_tpu else None, prefetch_depth=depth)
        state, step = _train_setup(on_tpu, bs, size)
        feed_obj = _EpochFeed(loader)
        feed = iter(feed_obj)
        state, _ = _timed_steps(step, state, feed, warmup)
        wait0 = feed_obj.stats().get("input_wait_ms_total", 0.0)
        state, dt = _timed_steps(step, state, feed, iters)
        stats = feed_obj.stats()
        out[f"npy_{tag}_img_s"] = round(bs * iters / dt, 1)
        out[f"npy_{tag}_step_ms"] = round(dt / iters * 1e3, 2)
        out[f"npy_{tag}_input_wait_ms"] = round(
            (stats.get("input_wait_ms_total", 0.0) - wait0) / iters, 3)
        out[f"npy_{tag}_host_produce_ms"] = stats.get(
            "host_produce_ms_mean")
    out["npy_vs_resident_pct"] = round(
        100.0 * out["npy_prefetch_on_img_s"] / out["resident_img_s"], 1)
    return out


def bench_sweep(args):
    """Batch sweep with pipeline feeding + XLA cost analysis — the
    large-batch-limiter instrumentation (PERF.md round 6)."""
    import jax
    import numpy as np

    from horovod_tpu import data

    on_tpu = jax.default_backend() != "cpu"
    size = args.image_size
    warmup, iters = (5, 20) if on_tpu else (1, 3)
    batches = args.batches or ([128, 256, 512, 1024] if on_tpu
                               else [16, 32, 64])
    rows = []
    for bs in batches:
        row = {"batch": bs}
        try:
            root = _make_npy(
                tempfile.mkdtemp(prefix=f"dpb_sweep{bs}_"),
                max(4 * bs, 256), size)
            loader = data.make_loader(
                "npy", root, batch_size=bs, image_size=size,
                cast="bfloat16" if on_tpu else None)
            state, step = _train_setup(on_tpu, bs, size)
            feed_obj = _EpochFeed(loader)
            feed = iter(feed_obj)
            first = next(feed)
            # AOT compile: one program per batch size — the sweep itself
            # proves shapes are static per config (no per-step recompile)
            try:
                compiled = step.lower(state, *first).compile()
                ca = compiled.cost_analysis()
                if isinstance(ca, list):
                    ca = ca[0] if ca else None
                if ca:
                    row["xla_flops_per_step"] = float(ca.get("flops", 0))
                    row["xla_bytes_per_step"] = float(
                        ca.get("bytes accessed", 0))
                step = compiled
            except Exception as e:  # remote backends may refuse AOT
                row["cost_analysis_error"] = str(e)[:120]
            state, loss = step(state, *first)
            state, _ = _timed_steps(step, state, feed, warmup)
            wait0 = feed_obj.stats().get("input_wait_ms_total", 0.0)
            state, dt = _timed_steps(step, state, feed, iters)
            stats = feed_obj.stats()
            row["step_ms"] = round(dt / iters * 1e3, 2)
            row["img_s"] = round(bs * iters / dt, 1)
            row["input_wait_ms"] = round(
                (stats.get("input_wait_ms_total", 0.0) - wait0) / iters, 3)
            row["input_wait_pct"] = round(
                100 * row["input_wait_ms"] / row["step_ms"], 2)
            row["host_produce_ms"] = stats.get("host_produce_ms_mean")
            row["device_put_ms"] = stats.get("device_put_ms_mean")
            if "xla_bytes_per_step" in row and row["step_ms"]:
                # effective HBM bandwidth implied by the counted bytes:
                # counted_bytes / step_time.  Rising above spec bandwidth
                # = the schedule re-reads more than the count (VMEM
                # residency loss); see PERF.md round 6.
                row["implied_gbps"] = round(
                    row["xla_bytes_per_step"] / (row["step_ms"] / 1e3)
                    / 1e9, 1)
        except Exception as e:
            row["error"] = f"{type(e).__name__}: {e}"[:200]
        rows.append(row)
        print(f"[sweep] {row}", file=sys.stderr)
    return {"backend": jax.default_backend(), "image_size": size,
            "rows": rows}


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--mode", default="stages",
                   choices=["stages", "ab", "sweep"])
    p.add_argument("--batch", type=int, default=None)
    p.add_argument("--image-size", type=int, default=None)
    p.add_argument("--batches", type=int, nargs="*", default=None,
                   help="sweep mode batch list")
    p.add_argument("--sim-step-ms", type=float, default=20.0)
    args = p.parse_args()
    from horovod_tpu.utils import compile_cache

    compile_cache.enable()

    import jax

    import horovod_tpu as hvd

    hvd.init()
    on_tpu = jax.default_backend() != "cpu"
    if args.batch is None:
        args.batch = 128 if on_tpu else 32
    if args.image_size is None:
        args.image_size = 224 if on_tpu else 64

    result = {"stages": bench_stages, "ab": bench_ab,
              "sweep": bench_sweep}[args.mode](args)
    result["mode"] = args.mode
    print(json.dumps(result, indent=2))


if __name__ == "__main__":
    main()
