#!/usr/bin/env python
"""Capture a jax.profiler (XPlane) trace of the framework, or reduce one.

    python tools/profile_capture.py <dir> [--hlo <compiled step's text>]

``<dir>`` already holds a capture (a ``.xplane.pb`` anywhere beneath it,
e.g. what ``benchmark/run.py --trace 1`` leaves in
``.bench_out/trace/<cell>/``): print the device time a step by phase —
forward / backward / exchange / optimizer / unattributed
(``horovod_tpu.trace.device``; docs/TRACING.md, "Device names").  The
instructions' ``op_name`` is read from the compiled program the capture
carries; ``--hlo`` takes it from a text file instead.

``<dir>`` holds none: capture there first — a burst of negotiated
collectives (``hvd_tpu::<name>::ENQUEUE`` / ``hvd_tpu::<op>::XLA_COMM``
spans next to XLA's own op activity; SURVEY.md §5.1) and a few steps of a
small compiled train step through ``fit_epoch`` (``hvd_tpu::train.step``
as a step annotation, the phase scopes inside the program) — then print
the set-up table (start-up by the program's own spans: ``hvd.import``,
``hvd.init``, ``train.create_state`` and their children, the
``jax.compile`` records; from ``trace.snapshot()``, so only in the process
that made the capture) and the same phase table.  The phases need a TPU's device plane: on the virtual CPU
mesh (the default without ``JAX_PLATFORMS``) only the capture is written.

    tensorboard --logdir <dir>           # Profile plugin, or load
    # plugins/profile/<ts>/<host>.trace.json.gz in ui.perfetto.dev

docs/example_trace.json.gz in the repo is one committed capture from
the 8-device virtual CPU mesh (see PERF.md round 4).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def capture(logdir: str) -> None:
    if os.environ.get("JAX_PLATFORMS", "") == "":
        # default to the virtual CPU mesh so the tool runs anywhere
        os.environ.setdefault("XLA_FLAGS",
                              "--xla_force_host_platform_device_count=8")
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu import trace, training
    from horovod_tpu.models.transformer import Transformer, gpt_tiny
    from horovod_tpu.trace import export as trace_export

    hvd.init()
    # timeline active => XLA_COMM spans end at data-ready (controller
    # resolve() blocks), giving the capture true collective extents
    hvd.start_timeline(os.path.join("/tmp", "hvd-chrome-timeline.json"))

    x = jnp.arange(1 << 16, dtype=jnp.float32)
    hvd.allreduce(x, name="warmup")  # compile outside the capture

    model, optimizer = Transformer(gpt_tiny()), optax.adamw(1e-3)
    tokens = jnp.zeros((hvd.size(), 128), jnp.int32)
    state = training.replicate_state(training.create_train_state(
        model, optimizer, jax.random.PRNGKey(0), tokens[:1]))
    step = training.data_parallel_train_step(model, optimizer)
    state, _ = step(state, tokens, tokens)  # compile outside the capture

    since = trace.now()
    jax.profiler.start_trace(logdir)
    for i in range(8):
        y = hvd.allreduce(x, name=f"grad_{i % 4}")
    jax.block_until_ready(y)
    # a grouped submission so a fused XLA_COMM span appears too
    hvd.grouped_allreduce([x, x * 2, x * 3], name="bucket")
    state, _ = training.fit_epoch(step, state, [(tokens, tokens)] * 4)
    jax.profiler.stop_trace()
    hvd.stop_timeline()
    # ONE instrumentation point, two views (docs/TRACING.md): the same
    # spans that just landed in the XPlane capture also export as
    # standalone Chrome trace-event JSON
    chrome = os.path.join(logdir, "hvd_framework_spans.json")
    trace_export.write_dump(chrome, since=since)
    print(f"trace written under {logdir}/plugins/profile/")
    print(f"framework spans (Chrome trace-event JSON): {chrome}")
    # start-up by the program's own spans, beside the phase table below:
    # the same two tables the benchmark's readers_program.py reads
    print(format_startup(trace.snapshot()))


#: the start-up sites, in the order a job passes them (depth = nesting)
_STARTUP = (
    ("hvd.import", 0), ("hvd.init", 0), ("hvd.init.topology", 1),
    ("hvd.init.controller", 1), ("train.create_state", 0),
    ("train.model_init", 1), ("train.optimizer_init", 1),
    ("train.replicate", 0),
)


def format_startup(records) -> str:
    """The set-up table: a row a start-up span of ``records``
    (``trace.snapshot()`` tuples; seconds, and the compiles it paid), then every ``jax.compile`` record summed (compiles against cache
    loads) and the longest of them by ``fun``."""
    from horovod_tpu.trace.export import enclosing

    rows = ["start-up, by the program's own spans (s):"]
    for site, depth in _STARTUP:
        for rec in (r for r in records if r[0] == site):
            args = dict(rec[3] or {})
            paid = (f"  {args.pop('compiles')} compiles {args.pop('compile_s'):.3f} s, "
                    f"{args.pop('cache_hits')} from the cache"
                    if "compiles" in args else "")
            rest = ", ".join(f"{k} {v}" for k, v in sorted(args.items()))
            rows.append(f"  {'  ' * depth}{site:<{24 - 2 * depth}}{rec[2]:9.3f}"
                        f"{paid}{'  (' + rest + ')' if rest else ''}")
    compiles = [r for r in records if r[0] == "jax.compile" and r[3]]
    loads = [r for r in compiles if r[3].get("cached")]
    rows.append(
        f"  {'jax.compile':<24}{sum(r[2] for r in compiles):9.3f}  "
        f"{len(compiles)} records: {len(compiles) - len(loads)} compiled, "
        f"{len(loads)} loaded from the persistent cache in "
        f"{sum(r[2] for r in loads):.3f} s")
    for rec in sorted(compiles, key=lambda r: -r[2])[:5]:
        step = enclosing(records, rec, "train.step")
        rows.append(
            f"    {rec[3].get('fun', ''):<22}{rec[2]:9.3f}  "
            f"{'loaded' if rec[3].get('cached') else 'compiled'}"
            + (f" inside train.step {step[3].get('step')}" if step else ""))
    return "\n".join(rows)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("dir", nargs="?", default="/tmp/hvd-trace",
                   help="a capture (a directory holding an .xplane.pb, or "
                   "the file), or where to write one")
    p.add_argument("--hlo", help="text of the compiled step "
                   "(step.lower(...).compile().as_text())")
    args = p.parse_args(argv)
    from horovod_tpu.trace import device

    try:
        device.find_xplane(args.dir)
    except FileNotFoundError:
        capture(args.dir)
    table = None
    if args.hlo:
        with open(args.hlo) as f:
            table = device.phase_table(f.read())
    try:
        print(device.format_phases(device.phase_ms(args.dir, table)))
    except ValueError as e:
        print(f"no phases: {e}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
