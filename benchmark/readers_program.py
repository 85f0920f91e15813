"""Readers of the program's OWN trace (the rule for names:
``"reader": "benchmark.readers_program:<name>"``).

The program times its start-up on its span ring (``horovod_tpu.trace``,
docs/TRACING.md: ``hvd.import``, ``hvd.init``, ``train.model_init``, one
``jax.compile`` record a backend compile or cache load) and splits its
compiled step into phases with its own reducer
(``horovod_tpu.trace.device.phase_ms``, from the ``jax.named_scope`` names in
every step builder).  The two readers here hand those numbers to the
benchmark as they are: they time the same layers as ``init_s`` and
``compile_s`` do from outside, from inside.  The benchmark already imports
``horovod_tpu`` (it is the system under test); ``benchmark/trace.py`` and
``readers_scope.py`` stay the benchmark's own readers of the capture.

A program that has no such span, counter or reducer (an earlier commit under
these files) gives None, never an error: the harness then leaves the metric
out of the line.
"""

from __future__ import annotations

import functools
import re


def _records():
    """The process's ring records, ``(site, t0, dur, args, tid)``, or None
    where they are not the whole of set-up: a program without the recorder's
    ``wrapped()`` (it has none of the sites read here either), or a ring that
    has wrapped.  A ring holds 16,384 records a thread and set-up writes some
    hundreds (a record a backend compile), so ``program_span`` reads a ring
    that has NOT wrapped, and says nothing once one has."""
    try:
        from horovod_tpu import trace
    except ImportError:
        return None
    wrapped = getattr(trace, "wrapped", None)
    if wrapped is None or wrapped():
        return None
    return trace.snapshot()


def program_span(r, spec: dict):
    """The process's records of ``spec["site"]``, summed: their durations in
    seconds, or their ``spec["arg"]`` where given (a count, a flag); times
    ``spec["scale"]``.  ``spec["fun"]`` keeps the ``jax.compile`` records
    whose ``fun`` matches (a pattern; the train step's is ``jit(_step)``).
    The program's entry path runs once a run and the reference calls nothing
    of the program, so these are set-up's (a step compiled inside the window
    is already a failed check).  None when there is no such record."""
    records = _records()
    if records is None:
        return None
    rx = re.compile(spec["fun"]) if "fun" in spec else None
    found = [rec for rec in records if rec[0] == spec["site"] and (
        rx is None or rx.search(str((rec[3] or {}).get("fun", ""))))]
    if not found:
        return None
    if "arg" in spec:
        values = [(rec[3] or {}).get(spec["arg"]) for rec in found]
        if any(v is None for v in values):
            return None
    else:
        values = [rec[2] or 0.0 for rec in found]
    return float(sum(values)) * spec.get("scale", 1.0)


@functools.lru_cache(maxsize=2)
def _phases(trace_dir: str):
    """``phase_ms`` of the capture under ``trace_dir``, once a run; None where
    the program has no reducer or the capture no step on a device."""
    try:
        from horovod_tpu.trace import device
    except ImportError:
        return None
    try:
        result = device.phase_ms(trace_dir)
    except Exception as e:  # a reader gives nothing, it never fails the run
        print(f"# program_phase: no phases ({type(e).__name__}: {e})", flush=True)
        return None
    busy = result["busy_ms"] or float("nan")
    print("# phases, the program's reducer (ms a step): "
          + ", ".join(f"{k} {v:.3f}" for k, v in result["phases"].items())
          + f"; busy {result['busy_ms']:.3f}, recompute {result['recompute_ms']:.3f}; "
          f"unattributed {100 * result['phases']['unattributed'] / busy:.2f} % of busy, "
          f"largest: {[[n, round(ms, 3)] for n, ms in result['unattributed_top'][:6]]}",
          flush=True)
    return result


def program_phase(r, spec: dict):
    """Device milliseconds a traced step of one phase of the compiled step,
    by the program's own names: ``spec["phase"]`` in ``forward`` /
    ``backward`` / ``optimizer`` / ``unattributed`` (``exchange`` too), or
    ``spec["key"]`` = ``recompute_ms`` (what the backward made again under
    ``jax.checkpoint``; inside ``backward``).  The phases sum to the busy time
    (``device_step_ms``): every instant goes to the innermost operation
    covering it.  None without a capture."""
    if not r.trace_dir or not r.steps_traced:
        return None
    result = _phases(r.trace_dir)
    if result is None:
        return None
    value = result["phases"].get(spec["phase"]) if "phase" in spec else result.get(spec["key"])
    return None if value is None else float(value) * spec.get("scale", 1.0)
