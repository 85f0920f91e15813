"""The worked example's readers: functions of ``(Readings, spec)`` that a
metric's file names as ``"reader": "benchmark.tests.example_moe.readers:<name>"``.
A reader that finds nothing to read returns None.
"""

from __future__ import annotations

import os


def expert_assignments_per_step(r, spec: dict):
    """A counter from the run's ``Readings``: (token, expert) pairs a step
    routes, over all layers, times ``scale``."""
    tokens = r.rows_per_step * r.traffic["seq_len"]
    return (tokens * r.config["num_experts_per_tok"] * r.config["num_hidden_layers"]
            * spec.get("scale", 1.0))


def capture_bytes(r, spec: dict):
    """What ``Readings.trace_dir`` is for: the capture itself, which holds
    what the reduced ``Trace`` drops (each program's ``Hlo Proto`` with every
    operation's ``op_name``).  Here only its size; None without a capture."""
    if not r.trace_dir or not os.path.isdir(r.trace_dir):
        return None
    sizes = [os.path.getsize(os.path.join(d, f)) for d, _, fs in os.walk(r.trace_dir) for f in fs]
    return float(sum(sizes)) or None
