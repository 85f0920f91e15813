#!/usr/bin/env python
"""Headline benchmark: ResNet-50 synthetic-data training throughput.

Reference parity: examples/pytorch/pytorch_synthetic_benchmark.py and
examples/tensorflow2/tensorflow2_synthetic_benchmark.py — the scripts the
reference's own docs point at for measuring img/sec (BASELINE.md).  Same
protocol: synthetic ImageNet-shaped data, warmup then timed steps, report
images/sec.

Baseline constant: the reference repo publishes no absolute number
(BASELINE.md: "user-measured"); the widely reported figure for its
pytorch_synthetic_benchmark on the reference-era flagship (V100, fp32,
batch 32) is ~330 img/sec, which we use as vs_baseline's denominator.

One process on the default backend, and that backend has to be a TPU: a
run that finds none exits non-zero and prints no result.  The tiny CPU
configuration (``--worker cpu``) runs only when asked for by name, and writes
its number under its own metric name, never under the chip's.

Real-data modes (round 6): ``--data npy`` / ``--data folder`` feed the
step through the ``horovod_tpu.data`` pipeline (sharded source -> worker
pool -> double-buffered device prefetch) instead of device-resident
tensors, and ``--data synthetic-stream`` pushes the same synthetic
tensors through the pipeline — the A/B that prices the host-feeding path
against the resident headline.  Every mode now reports ``input_wait_ms``
and pipeline stats in the result JSON so BENCH_*.json tracks
input-boundness across rounds alongside ``mfu``.

Output: ONE JSON line {"metric", "value", "unit", "vs_baseline", ...}.
"""

import argparse
import json
import sys
import time

BASELINE_IMG_PER_SEC = 330.0  # reference pytorch_synthetic_benchmark, 1x V100 fp32

# Published per-chip peaks, keyed by jax's ``device_kind``.  A kind that is
# not here is an error, not a missing field: an assumed denominator would
# mis-state MFU by up to ~4.7x across generations.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM at 819 GB/s per chip",
    },
}


def device_peaks(device_kind=None) -> dict:
    """Peaks of ``device_kind`` (default: the first device's)."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    if device_kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add it to "
            f"bench.DEVICE_PEAKS with its source (have {sorted(DEVICE_PEAKS)})")
    return DEVICE_PEAKS[device_kind]


# ResNet-50 fwd @224 is ~4.09 GMACs = ~8.2 GFLOP (mul+add counted
# separately, the standard MFU convention); training step ~= 3x forward.
# Round 2 used 4.1e9 here — the MAC count — which under-stated MFU by 2x.
# Cross-checked against XLA cost analysis: 3.06e12 FLOP/step at batch 128
# = 7.97e9 fwd FLOP/img (PERF.md).  The headline "mfu" field is computed
# from the compiled program's own cost_analysis() when available, with
# this analytic constant as fallback ("mfu_model").
RESNET50_TRAIN_FLOPS_PER_IMG = 3 * 2 * 4.09e9


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--worker", default="tpu", choices=["tpu", "cpu"],
                   help="tpu (default): the chip, or exit non-zero; cpu: "
                        "the tiny CPU configuration, only when named")
    p.add_argument("--data", default="synthetic",
                   choices=["synthetic", "synthetic-stream", "npy", "folder"],
                   help="synthetic = device-resident tensors (headline); "
                        "synthetic-stream/npy/folder feed the step through "
                        "the horovod_tpu.data pipeline")
    p.add_argument("--data-path", default=None,
                   help="dataset root for --data npy/folder (npy "
                        "self-seeds a temp dir when omitted)")
    p.add_argument("--batch", type=int, default=None,
                   help="override the per-backend default batch size")
    return p.parse_args(argv)


class _EpochFeed:
    """Endless batch stream over a data.DataLoader (epoch after epoch),
    keeping every epoch's prefetcher so pipeline stats aggregate across
    the whole run — the timed window subtracts a snapshot taken at its
    start, so warmup batches never pollute the reported wait."""

    def __init__(self, loader):
        self.loader = loader
        self._iters = []

    def __iter__(self):
        epoch = 0
        while True:
            self.loader.set_epoch(epoch)
            it = iter(self.loader)
            self._iters.append(it)
            for item in it:
                yield item
            epoch += 1

    def stats(self) -> dict:
        totals = {}
        for it in self._iters:
            for k, v in it.stats().items():
                if k == "prefetch_depth":
                    totals[k] = v
                elif not k.endswith("_mean"):  # totals/counts sum cleanly
                    totals[k] = round(totals.get(k, 0) + v, 3)
        n = max(totals.get("batches", 1), 1)
        for key in ("input_wait", "host_produce", "device_put"):
            totals[f"{key}_ms_mean"] = round(
                totals.get(f"{key}_ms_total", 0.0) / n, 3)
        return totals


def _build_feed(args, batch: int, image_size: int, on_tpu: bool):
    """Build the pipeline-fed batch stream for the non-resident modes."""
    import numpy as np
    from horovod_tpu import data

    kind = "synthetic" if args.data == "synthetic-stream" else args.data
    path = args.data_path
    if kind == "npy" and path is None:
        # self-seed: uint8 shards on disk (the realistic storage dtype —
        # decode is astype(float32)/255 on the worker pool), enough for
        # 8 batches; the feed loops epochs so the step count is unbounded
        import atexit
        import shutil
        import tempfile

        n = 8 * batch
        rng = np.random.RandomState(0)
        inputs = rng.randint(0, 256, size=(n, image_size, image_size, 3),
                             dtype=np.uint8)
        labels = rng.randint(0, 1000, size=(n,)).astype(np.int32)
        path = tempfile.mkdtemp(prefix="hvd_tpu_bench_npy_")
        # ~155 MB at the TPU config — always reap the seeded dir at exit
        atexit.register(shutil.rmtree, path, ignore_errors=True)
        data.write_npy_shards(path, inputs, labels, num_shards=4)
        print(f"[bench] seeded {n} uint8 samples into {path}",
              file=sys.stderr)
    loader = data.make_loader(
        kind, path, batch_size=batch, image_size=image_size,
        synthetic_samples=8 * batch,
        # bf16 host cast halves the host->device bytes; the first conv
        # consumes bf16 anyway (model dtype)
        cast="bfloat16" if on_tpu else None,
    )
    return _EpochFeed(loader)


def worker(mode: str, args) -> int:
    """The measured run itself.  mode: 'tpu' (the default backend, which
    must be a TPU) or 'cpu' (the tiny configuration, asked for by name)."""
    import jax

    if mode == "cpu":
        jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np
    import optax

    device = jax.devices()[0]
    on_tpu = device.platform == "tpu"
    if mode == "tpu" and not on_tpu:
        print(f"[bench] no TPU: the default backend is {device.platform!r}.  The "
              "benchmark does not fall back; `--worker cpu` runs the tiny "
              "CPU configuration by name.", file=sys.stderr)
        return 1
    peaks = device_peaks(device.device_kind) if on_tpu else None

    import horovod_tpu as hvd
    from horovod_tpu.models.resnet import ResNet50
    from horovod_tpu import training
    from horovod_tpu.utils import compile_cache

    compile_cache.enable()
    hvd.init()
    batch = args.batch or (128 if on_tpu else 16)
    image_size = 224 if on_tpu else 64
    warmup, iters = (5, 30) if on_tpu else (1, 2)

    # space_to_depth stem: mathematically identical to the classic 7x7/s2
    # stem (equivalence proven by test_space_to_depth_stem_equivalence)
    # but MXU-friendly — measured ~3.5% faster end-to-end (PERF.md)
    model = ResNet50(
        num_classes=1000, dtype=jnp.bfloat16, stem="space_to_depth"
    )
    rng = jax.random.PRNGKey(0)
    feed = None
    if args.data == "synthetic":
        # device-resident tensors: the headline config (input pipeline
        # exonerated as a limiter on this path — PERF.md r4 lever sweep)
        images = jnp.asarray(
            np.random.RandomState(0)
            .randn(batch, image_size, image_size, 3)
            .astype(np.float32)
        )
        labels = jnp.asarray(
            np.random.RandomState(1).randint(0, 1000, size=(batch,))
        )
    else:
        feed = _build_feed(args, batch, image_size, on_tpu)
        feed_iter = iter(feed)
        images, labels = next(feed_iter)

    optimizer = optax.sgd(0.1, momentum=0.9)
    state = training.create_train_state(model, optimizer, rng, images[:2])
    state = training.replicate_state(state)
    step = training.data_parallel_train_step(model, optimizer)

    # AOT-compile once and reuse the executable for the loops.  XLA's own
    # FLOP count is the self-verifying numerator for MFU (PERF.md documents
    # the cross-check vs the analytic count) — but cost_analysis() describes
    # the SPMD-partitioned *per-device* module, so it is only used as the
    # headline MFU when there is exactly one device (the bench's config);
    # multi-device runs use the analytic model count.  A step that does not
    # compile is a failed run.
    step = step.lower(state, images, labels).compile()
    ca = step.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0] if ca else None
    xla_flops = (float(ca.get("flops", 0)) or None) if ca else None

    if feed is None:
        for _ in range(warmup):
            state, loss = step(state, images, labels)
    else:
        state, loss = step(state, images, labels)  # the batch compile ate
        for _ in range(warmup - 1):
            state, loss = step(state, *next(feed_iter))
    float(loss)

    wait0 = feed.stats() if feed is not None else {}
    t0 = time.perf_counter()
    if feed is None:
        for _ in range(iters):
            state, loss = step(state, images, labels)
    else:
        for _ in range(iters):
            state, loss = step(state, *next(feed_iter))
    final_loss = float(loss)
    dt = time.perf_counter() - t0
    assert np.isfinite(final_loss), f"non-finite loss {final_loss}"

    img_per_sec = batch * iters / dt
    # input-boundness record (round-6 ask #3): wait accumulated over the
    # TIMED window only, so BENCH_*.json tracks it alongside mfu
    if feed is not None:
        pipeline = feed.stats()
        input_wait_ms = round(
            (pipeline.get("input_wait_ms_total", 0.0)
             - wait0.get("input_wait_ms_total", 0.0)) / iters, 3)
        pipeline["starved_batches"] = int(
            pipeline.get("starved_batches", 0)
            - wait0.get("starved_batches", 0))
        timed = max(
            int(pipeline.pop("batches", 0) - wait0.get("batches", 0)), 1)
        pipeline["timed_batches"] = timed
        # per-batch means over the TIMED window only — whole-run means
        # would fold warmup (incl. the compile step) into the record
        for key in ("host_produce", "device_put"):
            pipeline[f"{key}_ms_mean"] = round(
                (pipeline.get(f"{key}_ms_total", 0.0)
                 - wait0.get(f"{key}_ms_total", 0.0)) / timed, 3)
        for k in ("input_wait_ms_total", "input_wait_ms_mean",
                  "host_produce_ms_total", "device_put_ms_total"):
            pipeline.pop(k, None)
        from horovod_tpu.data import workers as _data_workers

        pipeline["workers"] = _data_workers.default_num_workers()
    else:
        pipeline = {"mode": "device_resident"}
        input_wait_ms = 0.0
    # memory-per-rank record (ISSUE 6): live-buffer accounting plus the
    # optimizer-state split PERF.md's capacity arithmetic reasons about —
    # `opt_state_bytes` is this run's replicated per-rank cost and
    # `opt_state_bytes_zero` the modeled ZeRO-1 shard
    # (horovod_tpu.optim.ZeroDistributedOptimizer) at this world size
    from horovod_tpu.optim import state_bytes as _state_bytes

    world = max(jax.device_count(), 1)
    memory_per_rank = {
        "params_bytes": int(_state_bytes(state.params)),
        "opt_state_bytes": int(_state_bytes(state.opt_state)),
        "opt_state_bytes_zero": int(-(-_state_bytes(state.opt_state)
                                      // world)),
        "world": world,
    }
    try:
        memory_per_rank["live_buffer_bytes"] = int(sum(
            int(getattr(a, "nbytes", 0) or 0) for a in jax.live_arrays()
        ))
    except Exception as e:  # accounting must never sink the bench line
        print(f"[bench] live-array accounting unavailable: {e}",
              file=sys.stderr)
    # per-step gradient-exchange byte record (ISSUE 7): the modeled
    # per-tier traffic of allreducing every parameter gradient once,
    # flat vs this topology's routing (ops/comm_model.py; one entry per
    # tier + the DCN wire dtype the HVD_TPU_* env selects) — what
    # hierarchical routing + DCN compression exist to shrink
    from horovod_tpu.common import basics as _basics
    from horovod_tpu.ops.comm_model import (
        modeled_collective_bytes as _comm_bytes,
    )

    _st = _basics._state
    _topo = _st.topology if _st is not None else None
    n_slices = _topo.num_slices if _topo is not None else 1
    _cfg = _st.config if _st is not None else None
    hier_on = bool(
        _cfg is not None and _cfg.hierarchical_allreduce and n_slices > 1
    )
    wire = None
    if hier_on:
        from horovod_tpu.compression import dcn_compression_from_name

        _comp = dcn_compression_from_name(_cfg.dcn_wire_dtype)
        wire = str(_comp.wire_dtype) if _comp is not None else None
        n_ici = _topo.slice_size
    else:
        n_ici = 1 if n_slices > 1 else world
    comm = {"ici": 0, "dcn": 0}
    try:
        for leaf in jax.tree_util.tree_leaves(state.params):
            m = _comm_bytes(np.shape(leaf), world, n_ici,
                            wire_dtype=wire, dtype=str(leaf.dtype))
            comm["ici"] += m["ici_bytes"]
            comm["dcn"] += m["dcn_bytes"]
    except Exception as e:  # accounting must never sink the bench line
        print(f"[bench] comm-bytes accounting unavailable: {e}",
              file=sys.stderr)
        comm = {"ici": 0, "dcn": 0}
    comm["wire_dtype"] = wire
    comm["routing"] = "hierarchical" if hier_on else "flat"

    result = {
        # a CPU number never goes under the chip's metric name
        "metric": ("resnet50_synthetic_train_throughput" if on_tpu
                   else "resnet50_tiny_cpu_train_throughput"),
        "value": round(img_per_sec, 2),
        "unit": "images/sec",
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": jax.device_count(),
        "batch": batch,
        "image_size": image_size,
        "step_time_ms": round(dt / iters * 1e3, 2),
        "data": args.data,
        "input_wait_ms": input_wait_ms,
        "input_wait_pct": round(
            100.0 * input_wait_ms / max(dt / iters * 1e3, 1e-9), 2),
        "pipeline": pipeline,
        "memory_per_rank": memory_per_rank,
        "comm_bytes": comm,
    }
    if on_tpu:
        result["vs_baseline"] = round(img_per_sec / BASELINE_IMG_PER_SEC, 3)
    if on_tpu and image_size == 224:
        # img_per_sec is aggregate across the data-parallel world, so
        # normalize to per-chip before dividing by per-chip peak.
        peak = peaks["bf16_flops"]
        step_s = dt / iters
        mfu_model = round(
            img_per_sec / jax.device_count()
            * RESNET50_TRAIN_FLOPS_PER_IMG / peak, 4
        )
        if xla_flops and jax.device_count() == 1:
            # headline MFU from XLA's measured FLOP count of the compiled
            # step — unambiguous single-chip (per-device == whole-program)
            result["mfu"] = round(xla_flops / step_s / peak, 4)
            result["mfu_model"] = mfu_model
            result["xla_flops_per_step"] = xla_flops
        else:
            result["mfu"] = mfu_model
        result["peak_bf16_flops"] = peak
    print(json.dumps(result))
    return 0


def main() -> int:
    args = parse_args()
    return worker(args.worker, args)


if __name__ == "__main__":
    sys.exit(main())
