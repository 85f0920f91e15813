"""Per-layer metric readers: a small set, chosen by name from a metric's file.

``benchmark/metrics/<metric>.json`` names one of these and gives it its
parameters (a pattern, a span, a FLOP function), so a new metric that reads
the trace by pattern is a data file.  A reader gets the run's ``Readings``
and the metric's spec and returns the value in the metric's unit, or None
when it finds nothing to read; the harness then leaves the metric out.  A
reader that is not in the table below is ``<module>:<attribute>`` under
``benchmark/`` (the rule in ``benchmark/__init__.py``): a new file.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from benchmark import flops, resolve, trace as tr


@dataclass
class Readings:
    """What one run offers to read."""

    config: dict
    traffic: dict
    peaks: dict
    chips: int
    rows_per_step: int                             # rows of the global batch
    spans: dict = field(default_factory=dict)       # host spans, seconds
    compile_seconds: float | None = None            # the meter, during set-up
    trace: tr.Trace | None = None
    trace_dir: str | None = None                    # the capture, for what Trace drops
    steps_traced: int = 0
    values: dict = field(default_factory=dict)      # metrics read so far


def host_span(r: Readings, spec: dict):
    value = r.spans.get(spec["span"])
    return None if value is None else value * spec.get("scale", 1.0)


def compile_meter(r: Readings, spec: dict):
    return r.compile_seconds


def _per_step(r: Readings, ns: float, spec: dict):
    if r.trace is None or not r.steps_traced or ns <= 0:
        return None
    return ns / r.steps_traced * spec.get("scale", 1.0)


def trace_busy_per_step(r: Readings, spec: dict):
    return _per_step(r, tr.busy_ns(r.trace) if r.trace else 0.0, spec)


def trace_events_per_step(r: Readings, spec: dict):
    return _per_step(r, tr.matching_ns(r.trace, spec["pattern"]) if r.trace else 0.0, spec)


def trace_exposed_per_step(r: Readings, spec: dict):
    return _per_step(r, tr.exposed_ns(r.trace, spec["pattern"]) if r.trace else 0.0, spec)


def roofline(r: Readings, spec: dict):
    """Least time the chip could take (required operations over the peak,
    or required bytes over ``hbm_bytes_per_s`` where the metric's file names a
    bytes function and that peak; per chip) over the measured time, in per
    cent."""
    ms = r.values.get(spec["time_metric"])
    if not ms:
        return None
    required = flops.function(spec["flops_function"])(
        r.config, r.traffic, r.rows_per_step // r.chips)
    return 100.0 * (required / r.peaks[spec["peak"]]) / (ms * 1e-3)


READERS = {f.__name__: f for f in (
    host_span, compile_meter, trace_busy_per_step, trace_events_per_step,
    trace_exposed_per_step, roofline)}


def reader(name: str):
    return resolve(name, READERS, "reader")
