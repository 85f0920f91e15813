#!/usr/bin/env python
"""Capture a jax.profiler (XPlane) trace with framework spans in it.

Runs a short burst of negotiated collectives inside a profiler capture
so the resulting trace shows ``hvd_tpu::<name>::ENQUEUE`` /
``hvd_tpu::<op>::XLA_COMM`` spans (utils/profiler.py bridge) next to
XLA's own op activity — the reference's NVTX-next-to-kernels view,
TPU edition (SURVEY.md §5.1).

Usage (single process; works on the virtual CPU mesh or a TPU)::

    python tools/profile_capture.py /tmp/hvd-trace
    tensorboard --logdir /tmp/hvd-trace           # Profile plugin
    # or load plugins/profile/<ts>/<host>.trace.json.gz in
    # ui.perfetto.dev

docs/example_trace.json.gz in the repo is one committed capture from
the 8-device virtual CPU mesh (see PERF.md round 4).
"""

import os
import sys


def main() -> int:
    logdir = sys.argv[1] if len(sys.argv) > 1 else "/tmp/hvd-trace"
    if os.environ.get("JAX_PLATFORMS", "") == "":
        # default to the virtual CPU mesh so the tool runs anywhere
        os.environ.setdefault("XLA_FLAGS",
                              "--xla_force_host_platform_device_count=8")
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp

    import horovod_tpu as hvd

    hvd.init()
    # timeline active => XLA_COMM spans end at data-ready (controller
    # resolve() blocks), giving the capture true collective extents
    hvd.start_timeline(os.path.join("/tmp", "hvd-chrome-timeline.json"))

    x = jnp.arange(1 << 16, dtype=jnp.float32)
    hvd.allreduce(x, name="warmup")  # compile outside the capture

    from horovod_tpu import trace
    from horovod_tpu.trace import export as trace_export

    since = trace.now()
    jax.profiler.start_trace(logdir)
    for i in range(8):
        y = hvd.allreduce(x, name=f"grad_{i % 4}")
    jax.block_until_ready(y)
    # a grouped submission so a fused XLA_COMM span appears too
    hvd.grouped_allreduce([x, x * 2, x * 3], name="bucket")
    jax.profiler.stop_trace()
    hvd.stop_timeline()
    # ONE instrumentation point, two views (docs/TRACING.md): the same
    # collective.enqueue/exec spans that just landed in the XPlane
    # capture also export as standalone Chrome trace-event JSON
    chrome = os.path.join(logdir, "hvd_framework_spans.json")
    trace_export.write_dump(chrome, since=since)
    print(f"trace written under {logdir}/plugins/profile/")
    print(f"framework spans (Chrome trace-event JSON): {chrome}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
