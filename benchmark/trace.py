"""From a profiler trace to numbers: the reduction, kept with the benchmark.

``load_xplane`` turns the ``.xplane.pb`` the JAX profiler writes into a plain
``Trace``: for each device the operation events (short name, start, duration
in nanoseconds) and for the host its annotated events.  Everything below works
on a ``Trace`` alone, so ``selftest.py`` can check the arithmetic against the
small recorded trace in ``fixtures/`` without a chip.

On a TPU the profiler writes one plane per chip, ``/device:TPU:<n>``, whose
``XLA Ops`` line holds one event per executed HLO operation (fusions, custom
calls, synchronous collectives), whose ``Async XLA Ops`` line holds the
operations that run beside them (copies, and collectives once XLA makes them
asynchronous), and whose ``XLA Modules`` line holds one event per program run;
host threads sit in ``/host:CPU``.
"""

from __future__ import annotations

import glob
import json
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


@dataclass
class Trace:
    """``ops[d]``, ``async_ops[d]`` and ``modules[d]``: (name, start_ns,
    duration_ns) on device ``d``; ``host``: the same for host events."""

    ops: dict = field(default_factory=dict)
    async_ops: dict = field(default_factory=dict)
    modules: dict = field(default_factory=dict)
    host: list = field(default_factory=list)

    def to_json(self) -> dict:
        return {"ops": self.ops, "async_ops": self.async_ops, "modules": self.modules,
                "host": self.host}

    @classmethod
    def from_json(cls, data: dict) -> "Trace":
        def events(rows):
            return [(str(n), float(s), float(d)) for n, s, d in rows]

        return cls(ops={k: events(v) for k, v in data["ops"].items()},
                   async_ops={k: events(v) for k, v in data.get("async_ops", {}).items()},
                   modules={k: events(v) for k, v in data["modules"].items()},
                   host=events(data.get("host", [])))


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def short_name(name: str) -> str:
    """An operation event is named by its whole HLO line
    (``%fusion.14 = (f32[256]...) fusion(... %fusion.107), kind=kOutput``);
    what is before the equals sign names it, and for a custom call its target
    is added.  A ``Trace`` keeps this short name, so a pattern can never match
    an operand."""
    head = name.split(" = ", 1)[0].lstrip("%")
    m = re.search(r'custom_call_target="([^"]+)"', name)
    return f"{head}[{m.group(1)}]" if m else head


def load_xplane(path: str, host_events: int = 20000) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    trace = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                target = {OPS_LINE: trace.ops, ASYNC_LINE: trace.async_ops,
                          MODULES_LINE: trace.modules}.get(line.name)
                if target is not None:
                    target[m.group(1)] = [
                        (short_name(e.name), float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0 and len(trace.host) < host_events:
                        trace.host.append((e.name, float(e.start_ns), float(e.duration_ns)))
    return trace


def describe_xplane(path: str, top: int = 12) -> dict:
    """Planes, lines, event counts and the most frequent names: what to look
    at by hand before trusting a pattern."""
    from collections import Counter

    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            names = Counter(e.name for e in line.events)
            lines[line.name] = {"events": sum(names.values()),
                                "top": names.most_common(top)}
        out[plane.name] = lines
    return out


# -- reductions ---------------------------------------------------------------


def union_ns(intervals) -> float:
    """Total length covered by (start, duration) intervals."""
    total, end = 0.0, None
    for start, dur in sorted(intervals):
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def window_ns(trace: Trace) -> float:
    """First start to last end of any device operation."""
    starts = [s for evs in trace.ops.values() for _, s, _ in evs]
    ends = [s + d for evs in trace.ops.values() for _, s, d in evs]
    return max(ends) - min(starts) if starts else 0.0


def busy_ns(trace: Trace) -> float:
    """Time in which an operation ran on a device, averaged over devices."""
    if not trace.ops:
        return 0.0
    return sum(union_ns((s, d) for _, s, d in evs) for evs in trace.ops.values()) / len(trace.ops)


def steps_traced(trace: Trace, module_pattern: str) -> int:
    """Runs of the step program on one device (the fewest, if they differ)."""
    rx = re.compile(module_pattern)
    counts = [sum(1 for n, _, _ in evs if rx.search(n)) for evs in trace.modules.values()]
    return min(counts) if counts else 0


def _matching(trace: Trace, device: str, rx) -> list:
    """(start, duration) of the matching operations on a device, from the
    operations' line and the asynchronous one."""
    both = trace.ops[device] + trace.async_ops.get(device, [])
    return [(s, d) for n, s, d in both if rx.search(n)]


def matching_ns(trace: Trace, pattern: str) -> float:
    """Time covered by the operations whose name matches (a union: an
    asynchronous collective and its start and done markers count once), mean
    over devices."""
    if not trace.ops:
        return 0.0
    rx = re.compile(pattern)
    return sum(union_ns(_matching(trace, d, rx)) for d in trace.ops) / len(trace.ops)


def exposed_ns(trace: Trace, pattern: str) -> float:
    """The part of the matching operations' time during which no other
    operation of the operations' line ran on that device, mean over devices."""
    if not trace.ops:
        return 0.0
    rx = re.compile(pattern)
    total = 0.0
    for d, evs in trace.ops.items():
        mine = _matching(trace, d, rx)
        others = [(s, dur) for n, s, dur in evs if not rx.search(n)]
        # |mine| - |mine and others| = |mine or others| - |others|
        total += union_ns(mine + others) - union_ns(others)
    return total / len(trace.ops)


def top_ops(trace: Trace, limit: int = 10) -> list:
    """[name, seconds] of the operations that took most time, summed over
    their events and averaged over devices."""
    sums = {}
    for evs in trace.ops.values():
        for n, _, d in evs:
            sums[n] = sums.get(n, 0.0) + d
    n_dev = max(len(trace.ops), 1)
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:limit]
    return [[name, ns / n_dev / 1e9] for name, ns in ranked]


def idle_gaps(trace: Trace, limit: int = 10) -> list:
    """[what the host was doing, seconds] for the longest gaps between
    operations on the first device: the host event that overlaps the gap
    most (the shortest such, so the innermost of nested ones), or
    ``(no host event)``."""
    if not trace.ops:
        return []
    evs = sorted(trace.ops[sorted(trace.ops)[0]], key=lambda e: e[1])
    gaps, end = [], None
    for _, s, d in evs:
        if end is not None and s > end:
            gaps.append((end, s - end))
        end = s + d if end is None else max(end, s + d)
    out = []
    for start, length in sorted(gaps, key=lambda g: -g[1])[:limit]:
        best, best_key = "(no host event)", (0.0, 0.0)
        for n, hs, hd in trace.host:
            overlap = min(hs + hd, start + length) - max(hs, start)
            if overlap > 0 and (overlap, -hd) > best_key:
                best, best_key = n, (overlap, -hd)
        out.append([best, length / 1e9])
    return out


def save(trace: Trace, path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace.to_json(), f)


def load(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))
