"""A reader a model family brought as a new file (the rule for names:
``"reader": "benchmark.readers_kernels:trace_kernels_in_scope_per_step"``).

Device time a step of the KERNELS of one scope: the operations whose own name
matches ``spec["kernel"]`` (a Mosaic call's: ``flash_attention_fwd.<n>``) and
whose ``op_name`` path matches ``spec["pattern"]`` (``/attn_window/``).  The path
alone does not do where kernels of one name run under two scopes and the
compiler's own operations around a call (a ``reduce`` of its output) carry the
call's path: ``readers_scope.trace_scope_per_step`` counts those too, and the
scopes' times would not add up to what ``^flash_attention`` reads by name.
"""

from __future__ import annotations

import re

from benchmark import readers_scope, trace as tr


def kernels_in_scope_ns(trace: tr.Trace, names: dict, kernel: str, pattern: str) -> float:
    """Time covered by the operations that match both, mean over devices."""
    if not trace.ops:
        return 0.0
    by_name, by_path = re.compile(kernel), re.compile(pattern)
    return sum(tr.union_ns(
        (s, d) for n, s, d in events
        if by_name.search(n) and by_path.search(names.get(n.split("[", 1)[0], "")))
        for events in trace.ops.values()) / len(trace.ops)


def trace_kernels_in_scope_per_step(r, spec: dict):
    """None without a capture, without the program in it, or where nothing
    matches (a program that has no such scope)."""
    if r.trace is None or not r.trace_dir or not r.steps_traced:
        return None
    ran = tuple(sorted({n for events in r.trace.modules.values() for n, _, _ in events}))
    names = readers_scope.op_names(r.trace_dir, ran)
    ns = kernels_in_scope_ns(r.trace, names, spec["kernel"], spec["pattern"]) if names else 0.0
    return ns / r.steps_traced * spec.get("scale", 1.0) if ns > 0 else None
