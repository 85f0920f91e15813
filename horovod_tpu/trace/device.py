"""Device time by phase: the reducer for the names the program gives its
device work (docs/TRACING.md, "Device names").

A compiled train step is one XLA program; no host span can record inside
it.  The program names its work instead — the :data:`~horovod_tpu.trace.
DEVICE_SCOPES` phases (``jax.named_scope`` in ``training.py``) and one
``name=`` a Pallas kernel — and both survive compilation as metadata:
every HLO instruction carries an ``op_name`` such as
``jit(_step)/shard_map/transpose(jvp(forward))/Transformer/layer_3/...``.
This module turns a ``jax.profiler`` capture into milliseconds a step for
``forward | backward | exchange | optimizer | unattributed``.

Where the ``op_name`` of a device event comes from.  On the v5e the
events of the ``XLA Ops`` line carry only their times (my chip runs,
PR 24: ``device_offset_ps``, ``device_duration_ps``); the instruction's
metadata is in the compiled program, which the profiler stores in the
capture itself.  So :func:`phase_ms` builds a :func:`phase_table`, keyed
by instruction name, from the ``HloModuleProto`` of the capture's
``/host:metadata`` plane (:func:`embedded_hlo`) — or from the compiled
step's text (``step.lower(...).compile().as_text()``), where a caller has
it.

The rules, stated and not hidden:

* the INNERMOST catalogued component of the path names the phase, so
  ZeRO's reduce-scatter / all-gather under ``optimizer/exchange`` is the
  exchange;
* the backward has no scope of its own: it is the transpose of the
  forward scope, ``transpose(jvp(forward))``, and everything beneath it:
  under ``jax.checkpoint`` jax writes a second ``jvp(forward)`` BENEATH
  the transpose (``transpose(jvp(forward))/jvp(forward)/checkpoint/...``:
  the block linearised again in the backward pass), which is backward
  time; of it, what lies under ``rematted_computation`` is the forward
  made again and is also reported as ``recompute`` (the rest of
  ``checkpoint/...`` is the block's own backward);
* a fusion takes the phase of the one ``op_name`` XLA leaves on it (its
  root's): a fusion that merged work of two phases counts under one;
* nested events (a ``while`` and the operations of its body) are not
  counted twice: every instant of busy time goes to the innermost event
  covering it, so the phases and ``unattributed`` sum to the busy time —
  the union of the operation intervals, as the benchmark's
  ``device_step_ms`` is taken.
* ``exchange hidden`` is the time with a reduction IN FLIGHT during
  which an operation of another phase ran on that device.  A
  synchronous all-reduce is in flight while its one event runs, with
  nothing beside it.  An asynchronous one (what the data-parallel step
  asks for on more than one TPU chip, ``spmd_ops.
  exchange_compile_options``) has no event of its own on the ``XLA Ops``
  line: it is in flight from the start of ``async-collective-start.<n>``
  to the end of ``async-collective-done.<n>`` (or ``all-reduce-start`` /
  ``-done``), and the fusions between the two are the compute it hides
  behind.  The ``exchange`` row itself is then what stayed exposed: the
  start and done operations (the done waits for what is left), the
  synchronous all-reduces and the divisions.

Reads the capture through ``jax.profiler.ProfileData`` and a few lines
of protobuf wire format (no TensorFlow, no xprof); imports nothing of
the benchmark's.
"""

from __future__ import annotations

import glob
import heapq
import os
import re
from typing import (
    Dict, Iterable, Iterator, List, NamedTuple, Optional, Tuple,
)

from . import DEVICE_SCOPES, DEVICE_SUBSCOPES

__all__ = ["PHASES", "classify", "device_events", "embedded_hlo",
           "find_xplane", "format_phases", "phase_ms", "phase_table",
           "reduce_phases", "subscope_table"]

PHASES = ("forward", "backward", "exchange", "optimizer", "unattributed")

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"

_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_CALLS_RE = re.compile(r"\bcalls=%?([\w.\-]+)")
# operands: %names not handed over as an attribute (calls=%c, to_apply=%c)
_OPERAND_RE = re.compile(r"(?<![=\w])%([\w.\-]+)")
_RECOMPUTE = "rematted_computation"
_FORWARD = DEVICE_SCOPES[0]
# the forward scope inside transforms: jvp(forward), transpose(jvp(forward))
_WRAPPED_FORWARD = re.compile(rf"^((?:[\w.]+\()+){_FORWARD}\)+$")


def classify(op_name: str) -> Tuple[str, bool]:
    """``(phase, recompute)`` of one ``op_name`` path (rules: module
    docstring)."""
    phase, recompute = "unattributed", False
    for part in op_name.split("/"):
        wrapped = _WRAPPED_FORWARD.match(part)
        if part in DEVICE_SCOPES:
            phase, recompute = part, False
        elif wrapped:
            # jvp(forward), transpose(jvp(forward)), ...; the outermost
            # transform names the pass: a jvp(forward) beneath the
            # transpose is a checkpointed block linearised again, backward
            transposed = "transpose(" in wrapped.group(1)
            if transposed or phase != "backward":
                phase, recompute = "backward" if transposed else _FORWARD, False
        elif phase == "backward" and part.startswith(_RECOMPUTE):
            recompute = True
    return phase, recompute


class _Instr(NamedTuple):
    computation: str
    name: str
    op_name: str
    callee: str          # the computation a fusion or call runs, or ""
    operands: Tuple[str, ...]
    root: bool


def _from_text(text: str) -> Iterator[_Instr]:
    """The instructions of ``compiled.as_text()``."""
    computation = ""
    for line in text.splitlines():
        m = _COMPUTATION_RE.match(line)
        if m:
            computation = m.group(1)
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        found = _OP_NAME_RE.search(line)
        called = _CALLS_RE.search(line)
        body = line.split(" = ", 1)[1].split(", metadata=", 1)[0]
        yield _Instr(computation, m.group(1),
                     found.group(1) if found else "",
                     called.group(1) if called else "",
                     tuple(_OPERAND_RE.findall(body)),
                     line.lstrip().startswith("ROOT"))


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one protobuf message: integers for
    varints, a memoryview for length-delimited and fixed-width fields."""
    buf = memoryview(buf)
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"protobuf wire type {wire}")
            value, i = buf[i:i + size], i + size
        yield number, value


def _from_proto(module: bytes) -> Iterator[_Instr]:
    """The instructions of a serialized ``HloModuleProto`` (field
    numbers: xla/service/hlo.proto)."""
    computations = []        # (id, name, root id, [instruction fields])
    for number, value in _fields(module):
        if number != 3:      # HloModuleProto.computations
            continue
        comp = {"id": 0, "name": "", "root": 0, "instrs": []}
        for n, v in _fields(value):
            if n == 1:
                comp["name"] = bytes(v).decode()
            elif n == 2:
                comp["instrs"].append(v)
            elif n == 5:
                comp["id"] = v
            elif n == 6:
                comp["root"] = v
        computations.append(comp)
    comp_names = {c["id"]: c["name"] for c in computations}
    parsed, names = [], {}
    for comp in computations:
        for raw in comp["instrs"]:
            ins = {"id": 0, "name": "", "op_name": "", "operands": [],
                   "called": []}
            for n, v in _fields(raw):
                if n == 1:
                    ins["name"] = bytes(v).decode()
                elif n == 7:       # OpMetadata; its field 2 is op_name
                    ins["op_name"] = next(
                        (bytes(x).decode() for k, x in _fields(v) if k == 2),
                        "")
                elif n == 35:
                    ins["id"] = v
                elif n in (36, 38):   # operand_ids, called_computation_ids
                    ids = [v] if isinstance(v, int) else list(_packed(v))
                    ins["operands" if n == 36 else "called"] += ids
            names[ins["id"]] = ins["name"]
            parsed.append((comp, ins))
    for comp, ins in parsed:
        yield _Instr(
            comp["name"], ins["name"], ins["op_name"],
            comp_names.get(ins["called"][0], "") if ins["called"] else "",
            tuple(names.get(i, "") for i in ins["operands"]),
            ins["id"] == comp["root"])


def _packed(buf) -> Iterator[int]:
    """The varints of a packed repeated field."""
    i = 0
    while i < len(buf):
        value, i = _varint(buf, i)
        yield value


def phase_table(compiled) -> Dict[str, Tuple[str, bool, Tuple[str, ...]]]:
    """Instruction name -> ``(phase, recompute, also)`` for every
    instruction of a compiled step, in every computation (the profiler
    names a ``while`` body's operations too).  ``compiled`` is the text
    (``step.lower(...).compile().as_text()``) or the serialized
    ``HloModuleProto`` a capture carries (:func:`embedded_hlo`).

    An instruction's own ``op_name`` decides.  The compiler leaves some
    without one, or with one of its own making outside every scope
    (``jit(_step)/shard_map/convert.126``); such an instruction takes,
    in this order, the phase of the computation it calls (a fusion: its
    root's ``op_name``, else the phase most of its instructions name),
    the one phase its attributed operands agree on (a relayout between
    two backward operations is backward) or, last, the one phase its
    users agree on (the loss's backward scatter, which the compiler
    rewrites without metadata, feeds backward products alone).  What is
    left — parameters, constants, copies of weights that the forward and
    the backward both read — stays ``unattributed``.  ``also`` lists the OTHER phases whose
    instructions a fusion holds: XLA fuses AdamW's update into the
    weight-gradient matmul that feeds it, and that fusion's time cannot
    be split."""
    nothing = ("unattributed", False)
    instrs = list(_from_text(compiled) if isinstance(compiled, str)
                  else _from_proto(compiled))
    own: Dict[str, Tuple[str, bool]] = {}
    by_computation: Dict[str, List[str]] = {}
    roots: Dict[str, str] = {}
    for ins in instrs:
        own[ins.name] = classify(ins.op_name) if ins.op_name else nothing
        by_computation.setdefault(ins.computation, []).append(ins.name)
        if ins.root:
            roots[ins.computation] = ins.name

    def inside(callee: str) -> Dict[Tuple[str, bool], int]:
        votes: Dict[Tuple[str, bool], int] = {}
        for inner in by_computation.get(callee, ()):
            if own[inner] != nothing:
                votes[own[inner]] = votes.get(own[inner], 0) + 1
        return votes

    # definitions come before uses, so one pass in order sees every
    # operand resolved
    table: Dict[str, Tuple[str, bool, Tuple[str, ...]]] = {}
    for ins in instrs:
        got = own[ins.name]
        votes = inside(ins.callee) if ins.callee else {}
        if got == nothing and votes:
            root = roots.get(ins.callee)
            got = (own[root] if root and own[root] != nothing
                   else max(votes, key=votes.get))
        if got == nothing:
            agreed = {table[o][:2] for o in ins.operands
                      if o in table and table[o][:2] != nothing}
            if len(agreed) == 1:
                (got,) = agreed
        also = tuple(sorted({phase for phase, _ in votes} - {got[0]}))
        table[ins.name] = got + (also,)
    # last, uses before definitions: what is still unnamed takes the one
    # phase its users agree on (the compiler rewrites the loss's scatter
    # and the cast of its result with no metadata at all; both feed the
    # head's backward products alone)
    users: Dict[str, List[str]] = {}
    for ins in instrs:
        for operand in ins.operands:
            users.setdefault(operand, []).append(ins.name)
    for ins in reversed(instrs):
        if table[ins.name][:2] != nothing:
            continue
        agreed = {table[u][:2] for u in users.get(ins.name, ())
                  if table[u][:2] != nothing}
        if len(agreed) == 1:
            table[ins.name] = agreed.pop() + (table[ins.name][2],)
    return table


def subscope_table(compiled) -> Dict[str, str]:
    """Instruction name -> the innermost :data:`~horovod_tpu.trace.
    DEVICE_SUBSCOPES` component of its ``op_name`` (a part of the forward
    scope or of its transpose: the routed layer's ``router``, ``experts``),
    for the instructions that have one.  An instruction without an
    ``op_name`` takes that of the root of the computation it calls, else
    that of its last operand that has one (a custom call of XLA's own, such
    as the ragged product the routed layer called before PR 33, carries only
    its bare name; its data operands carry the scope.  The package's own
    kernels carry their path themselves)."""
    instrs = list(_from_text(compiled) if isinstance(compiled, str)
                  else _from_proto(compiled))
    roots = {i.computation: i.op_name for i in instrs if i.root}
    paths: Dict[str, str] = {}
    table = {}
    for ins in instrs:   # definitions come before uses
        own = ins.op_name if "/" in ins.op_name else ""   # a bare name is no path
        path = (own or roots.get(ins.callee, "")
                or next((paths[o] for o in reversed(ins.operands)
                         if paths.get(o)), ""))
        paths[ins.name] = path
        found = [p for p in path.split("/") if p in DEVICE_SUBSCOPES]
        if found:
            table[ins.name] = found[-1]
    return table


def embedded_hlo(xplane_path: str, module: str = "step") -> Optional[bytes]:
    """The serialized ``HloModuleProto`` of the step program, which the
    profiler stores in the capture itself (plane ``/host:metadata``, an
    event metadata named after the program, ``jit__step(<id>)``, with an
    ``Hlo Proto`` stat), or None.  That plane holds EVERY program the
    process has loaded, run during the capture or not, in no order: of
    several whose name matches ``module`` the one that ran on a device
    during the capture is taken (its name is on the ``XLA Modules``
    line), else the one loaded last (the largest ``<id>``; an earlier
    ``jit_step`` of another test or tool must not stand in for the step).
    Field numbers: tsl/profiler/protobuf/xplane.proto."""
    rx = re.compile(module)
    path = find_xplane(xplane_path)
    with open(path, "rb") as f:
        space = f.read()
    found: List[Tuple[str, bytes]] = []
    for number, plane in _fields(space):
        if number != 1:                      # XSpace.planes
            continue
        fields = list(_fields(plane))
        if not any(n == 2 and bytes(v) == b"/host:metadata"
                   for n, v in fields):      # XPlane.name
            continue
        for n, entry in fields:
            if n != 4:                       # XPlane.event_metadata (a map)
                continue
            for k, metadata in _fields(entry):
                if k != 2:                   # the map entry's value
                    continue
                name, stats = "", []
                for m, v in _fields(metadata):
                    if m == 2:               # XEventMetadata.name
                        name = bytes(v).decode()
                    elif m == 5:             # XEventMetadata.stats
                        stats.append(v)
                if not rx.search(name):
                    continue
                for stat in stats:
                    for m, v in _fields(stat):
                        if m == 6:           # XStat.bytes_value: an HloProto
                            hlo = next(
                                (bytes(x) for h, x in _fields(v) if h == 1),
                                None)        # HloProto.hlo_module
                            if hlo is not None:
                                found.append((name, hlo))
    if len(found) > 1:
        ran = _modules_run(path)
        found = [f for f in found if f[0] in ran] or found
        found = [max(found, key=lambda f: _program_id(f[0]))]
    return found[0][1] if found else None


def _program_id(name: str) -> int:
    """The ``<id>`` of ``jit__step(<id>)``; -1 without one."""
    m = re.search(r"\((\d+)\)$", name)
    return int(m.group(1)) if m else -1


def _modules_run(xplane_file: str) -> set:
    """Names of the programs that ran on a device during the capture
    (the ``XLA Modules`` line of every TPU plane; none on the CPU)."""
    from jax.profiler import ProfileData

    ran = set()
    for plane in ProfileData.from_file(xplane_file).planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == _MODULES_LINE:
                    ran.update(e.name for e in line.events)
    return ran


def find_xplane(path: str) -> str:
    """``path`` itself, or the newest ``.xplane.pb`` beneath it (what
    ``jax.profiler.start_trace(dir)`` and ``benchmark/run.py --trace 1``
    leave under ``<dir>/plugins/profile/<time>/``)."""
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def _short(name: str) -> str:
    """The instruction's name off an event named by its whole HLO line
    (``%fusion.14 = (f32[256]...) fusion(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def _self_time(events: Iterable[Tuple[float, float, object]]) -> Dict[object, float]:
    """Total time by key where every instant covered by ``(start,
    duration, key)`` events goes to the innermost one: of the events
    covering it, the one that started last (the shorter on a tie).  The
    values sum to the union of the intervals."""
    evs = sorted((e for e in events if e[1] > 0), key=lambda e: (e[0], -e[1]))
    out: Dict[object, float] = {}
    active: List[tuple] = []  # heap of (-start, dur, end, n, key)
    cursor = 0.0

    def credit_until(limit: float) -> None:
        """Hand out the time from ``cursor`` to ``limit`` (or to where
        the active events run out)."""
        nonlocal cursor
        while cursor < limit:
            while active and active[0][2] <= cursor:
                heapq.heappop(active)
            if not active:
                return
            _, _, end, _, key = active[0]
            stop = min(end, limit)
            out[key] = out.get(key, 0.0) + (stop - cursor)
            cursor = stop

    for n, (start, dur, key) in enumerate(evs):
        credit_until(start)
        cursor = start
        # n breaks ties, so that keys are never compared
        heapq.heappush(active, (-start, dur, start + dur, n, key))
    credit_until(float("inf"))
    return out


def _union_ns(events) -> float:
    """Length covered by ``(start, duration, ...)`` events."""
    total, end = 0.0, None
    for start, dur, *_ in sorted(events, key=lambda e: (e[0], e[1])):
        stop = start + dur
        if end is None or start > end:
            total, end = total + dur, stop
        elif stop > end:
            total, end = total + (stop - end), stop
    return total


_ASYNC_START = re.compile(r"^(.*)-start((?:\.\d+)?)$")


def _in_flight(ops, phase_of) -> List[Tuple[float, float]]:
    """``(start, duration)`` of the stretches with an exchange operation
    in flight on one device: every ``exchange`` event's own interval,
    and for an asynchronous pair the whole stretch from ``<x>-start.<n>``
    to the end of the next ``<x>-done.<n>`` (rule: module docstring)."""
    spans, open_starts = [], {}
    for name, start, dur in sorted(ops, key=lambda e: e[1]):
        if phase_of(name) != "exchange":
            continue
        spans.append((start, dur))
        m = _ASYNC_START.match(name)
        if m:
            open_starts[f"{m.group(1)}-done{m.group(2)}"] = start
        elif name in open_starts:
            began = open_starts.pop(name)
            spans.append((began, start + dur - began))
    return spans


def device_events(xplane_path: str, module: str = "step") -> Dict[str, dict]:
    """``{device: {"steps": n, "ops": [(name, start_ns, duration_ns)]}}``
    of a capture: the operations of the ``XLA Ops`` line that started
    inside a run of the step program (a module of the ``XLA Modules``
    line whose name matches ``module``; a step is one such run)."""
    from jax.profiler import ProfileData

    rx = re.compile(module)
    devices: Dict[str, dict] = {}
    for plane in ProfileData.from_file(find_xplane(xplane_path)).planes:
        m = _DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lines = {line.name: line for line in plane.lines}
        if _OPS_LINE not in lines:
            continue
        runs = sorted(
            (e.start_ns, e.start_ns + e.duration_ns)
            for e in (lines[_MODULES_LINE].events
                      if _MODULES_LINE in lines else ())
            if rx.search(e.name))
        ops = []
        for e in lines[_OPS_LINE].events:
            if runs and not any(a <= e.start_ns < b for a, b in runs):
                continue
            ops.append((_short(e.name), float(e.start_ns),
                        float(e.duration_ns)))
        if ops:
            devices[m.group(1)] = {"steps": max(len(runs), 1), "ops": ops}
    if not devices:
        raise ValueError(
            f"no TPU device plane with an {_OPS_LINE!r} line in "
            f"{xplane_path}: phases need a capture from the chip")
    return devices


def reduce_phases(devices: Dict[str, dict], table: Optional[dict] = None,
                  subscopes: Optional[dict] = None) -> dict:
    """Milliseconds a step by phase from :func:`device_events`' shape,
    means over the devices.  An operation's phase is its instruction's
    in ``table`` (:func:`phase_table`); one the table does not name, or
    any without a table, is ``unattributed``.

    Returns ``{"steps", "devices", "busy_ms", "sum_ms", "phases":
    {phase: ms}, "recompute_ms", "shared_ms": {"<phase>+<other>": ms},
    "exchange_in_flight_ms", "exchange_hidden_ms",
    "unattributed_top": [[name, ms], ...]}``.
    ``busy_ms`` is the union of the operation intervals, taken on its
    own; ``sum_ms``, the phases' sum, equals it.  ``shared_ms`` is the
    part of a phase spent in fusions that also hold another phase's
    instructions.  ``exchange_in_flight_ms`` is the time with a
    reduction in flight and ``exchange_hidden_ms`` the part of it during
    which an operation of another phase ran (module docstring).
    ``subscopes`` (:func:`subscope_table`) adds ``"subscopes": {name:
    ms}``: the time of the operations under each part of the forward
    scope, forward and backward together (a union: a ``conditional`` and
    its body count once); they lie inside the phases, not beside them."""
    nothing = ("unattributed", False, ())
    n_dev = len(devices)
    phases = dict.fromkeys(PHASES, 0.0)
    busy = recompute_ms = in_flight_ms = hidden_ms = 0.0

    def phase_of(name: str) -> str:
        return (table or {}).get(name, nothing)[0]

    shared: Dict[str, float] = {}
    unattributed: Dict[str, float] = {}
    parts = dict.fromkeys(DEVICE_SUBSCOPES, 0.0) if subscopes else {}
    for dev in devices.values():
        for part in parts:
            parts[part] += _union_ns(
                [(start, dur) for name, start, dur in dev["ops"]
                 if subscopes.get(name) == part]) * 1e-6 / dev["steps"] / n_dev
        keyed = [(start, dur, (table or {}).get(name, nothing) + (name,))
                 for name, start, dur in dev["ops"]]
        scale = 1e-6 / dev["steps"] / n_dev
        busy += _union_ns(keyed) * scale
        flying = _in_flight(dev["ops"], phase_of)
        others = [(start, dur) for name, start, dur in dev["ops"]
                  if phase_of(name) != "exchange"]
        flying_ns = _union_ns(flying)
        in_flight_ms += flying_ns * scale
        # |flying and others| = |flying| + |others| - |flying or others|
        hidden_ms += (flying_ns + _union_ns(others)
                      - _union_ns(flying + others)) * scale
        for (phase, recompute, also, name), ns in _self_time(keyed).items():
            phases[phase] += ns * scale
            if recompute:
                recompute_ms += ns * scale
            for other in also:
                key = f"{phase}+{other}"
                shared[key] = shared.get(key, 0.0) + ns * scale
            if phase == "unattributed":
                unattributed[name] = unattributed.get(name, 0.0) + ns * scale
    top = sorted(unattributed.items(), key=lambda kv: -kv[1])[:10]
    return {
        "steps": min(d["steps"] for d in devices.values()), "devices": n_dev,
        "busy_ms": busy, "sum_ms": sum(phases.values()),
        "phases": phases, "recompute_ms": recompute_ms, "shared_ms": shared,
        "exchange_in_flight_ms": in_flight_ms,
        "exchange_hidden_ms": hidden_ms,
        "unattributed_top": [[name, ms] for name, ms in top],
        **({"subscopes": parts} if subscopes else {}),
    }


def phase_ms(xplane_path: str, table: Optional[dict] = None,
             module: str = "step") -> dict:
    """Device milliseconds a step by phase, from a profiler capture (a
    file or a directory holding one): :func:`reduce_phases` over
    :func:`device_events`, with the :func:`phase_table` of the step
    program the capture itself carries unless ``table`` is given."""
    subscopes = None
    if table is None:
        hlo = embedded_hlo(xplane_path, module)
        table = phase_table(hlo) if hlo else None
        subscopes = (subscope_table(hlo) or None) if hlo else None
    return reduce_phases(device_events(xplane_path, module), table, subscopes)


def format_phases(result: dict) -> str:
    """The table ``tools/profile_capture.py`` prints."""
    busy = result["busy_ms"] or float("nan")
    rows = [f"device time a step, by phase ({result['steps']} steps, "
            f"{result['devices']} device(s)):"]
    for phase in PHASES:
        ms = result["phases"][phase]
        fused = ", ".join(    # shares under 1 % of the step are not worth a clause
            f"{v:.3f} in fusions that also hold {k.split('+')[1]} work"
            for k, v in sorted(result["shared_ms"].items())
            if k.startswith(phase + "+") and v >= 0.01 * busy)
        rows.append(f"  {phase:<13}{ms:10.3f} ms  {100 * ms / busy:5.1f} %"
                    + (f"  ({fused})" if fused else ""))
        flying = result["exchange_in_flight_ms"]
        if phase == "exchange" and flying > 0:
            hidden = result["exchange_hidden_ms"]
            rows.append(
                f"  {'exchange hidden':<16}{hidden:7.3f} ms  "
                f"{100 * hidden / flying:5.1f} % of the {flying:.3f} ms "
                "with a reduction in flight")
    for part, ms in result.get("subscopes", {}).items():
        if ms > 0:   # a model without the part (no latent attention) has no row
            rows.append(
                f"  of which {part:<15}{ms:7.3f} ms  {100 * ms / busy:5.1f} %"
                "  (inside forward and backward)")
    rows.append(f"  {'busy':<13}{result['busy_ms']:10.3f} ms  (phases sum "
                f"{result['sum_ms']:.3f}; recompute, inside backward, "
                f"{result['recompute_ms']:.3f})")
    if result["phases"]["unattributed"] > 0.1 * busy:
        rows.append("  largest unattributed operations:")
        rows += [f"    {name:<40}{ms:9.3f} ms"
                 for name, ms in result["unattributed_top"][:5]]
    return "\n".join(rows)
