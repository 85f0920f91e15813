"""Readers a model family brought as a new file (the rule for names:
``"reader": "benchmark.readers_scope:<name>"``).

``trace_scope_per_step``: device time a step of the operations whose
``op_name`` path matches a pattern.  The program names parts of its compiled
step with ``jax.named_scope`` (``forward``, ``router``, ``experts``); the name
survives compilation as every HLO instruction's ``op_name``
(``jit(_step)/.../jvp(forward)/Transformer/layer_3/moe/experts/...``; the
backward's is the same under ``transpose(...)``).  On a v5e the profiler's
``XLA Ops`` events carry only their times, so the ``op_name`` is read from the
compiled program the capture itself stores: the ``Hlo Proto`` stat of the
``/host:metadata`` plane (PERF.md section 7).  A few lines of protobuf wire
format, copied from the way ``horovod_tpu/trace/device.py`` reads it, not
imported: the benchmark names nothing of the program as its yardstick.
"""

from __future__ import annotations

import functools
import re

from benchmark import trace as tr

_METADATA_PLANE = b"/host:metadata"


def _varint(buf, i: int):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf):
    """(field number, value) of one protobuf message: integers for varints, a
    memoryview for length-delimited and fixed-width fields."""
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


def embedded_hlo(xplane_path: str, ran=(), module: str = "step"):
    """The serialized ``HloModuleProto`` of the step program, or None.  The
    ``/host:metadata`` plane holds every program the process has loaded, in
    no order: of those whose name matches ``module`` the one whose name is in
    ``ran`` (the names on the trace's ``XLA Modules`` line) is taken, else the
    one loaded last.  Field numbers: tsl/profiler/protobuf/xplane.proto."""
    rx = re.compile(module)
    with open(xplane_path, "rb") as f:
        space = f.read()
    found = []
    for number, plane in _fields(space):
        if number != 1:                                    # XSpace.planes
            continue
        fields = list(_fields(plane))
        if not any(n == 2 and bytes(v) == _METADATA_PLANE for n, v in fields):
            continue
        for n, entry in fields:
            if n != 4:                                     # XPlane.event_metadata
                continue
            for k, metadata in _fields(entry):
                if k != 2:                                 # the map entry's value
                    continue
                name, stats = "", []
                for m, v in _fields(metadata):
                    if m == 2:                             # XEventMetadata.name
                        name = bytes(v).decode()
                    elif m == 5:                           # XEventMetadata.stats
                        stats.append(v)
                if not rx.search(name):
                    continue
                for stat in stats:
                    for m, v in _fields(stat):
                        if m == 6:                         # XStat.bytes_value: an HloProto
                            hlo = next((bytes(x) for h, x in _fields(v) if h == 1), None)
                            if hlo is not None:
                                found.append((name, hlo))
    found = [f for f in found if f[0] in ran] or found

    def loaded(entry):
        m = re.search(r"\((\d+)\)$", entry[0])
        return int(m.group(1)) if m else -1

    return max(found, key=loaded)[1] if found else None


def op_names_of(module: bytes) -> dict:
    """Instruction name -> ``op_name`` for every instruction of a serialized
    ``HloModuleProto`` (field numbers: xla/service/hlo.proto).  An instruction
    without one takes, in this order, the ``op_name`` of the root of the
    computation it calls (a fusion the compiler built) or that of its last
    operand that has one (XLA's own ``ragged-dot`` custom calls carry only
    their own bare name, which is no path; their data operands, which come
    after the group metadata, carry the scope)."""
    computations = {}          # id -> (root id, [(id, name, op_name, called, operands)])
    for number, value in _fields(module):
        if number != 3:                                    # HloModuleProto.computations
            continue
        comp_id, root, instrs = 0, 0, []
        for n, v in _fields(value):
            if n == 2:                                     # instructions
                ins_id, name, op_name, called, operands = 0, "", "", [], []
                for k, x in _fields(v):
                    if k == 1:
                        name = bytes(x).decode()
                    elif k == 7:                           # OpMetadata; field 2 is op_name
                        op_name = next((bytes(y).decode() for j, y in _fields(x) if j == 2), "")
                    elif k == 35:
                        ins_id = x
                    elif k in (36, 38):                    # operand_ids, called_computation_ids
                        (operands if k == 36 else called).extend(
                            [x] if isinstance(x, int) else _packed(x))
                instrs.append((ins_id, name, op_name, called, operands))
            elif n == 5:
                comp_id = v
            elif n == 6:
                root = v
        computations[comp_id] = (root, instrs)
    root_name = {cid: next((op for i, _, op, _, _ in instrs if i == root), "")
                 for cid, (root, instrs) in computations.items()}
    names, by_id = {}, {}
    for _, instrs in computations.values():
        for ins_id, name, op_name, called, operands in instrs:   # definitions come first
            if "/" not in op_name:     # the compiler's own, a bare name: no path
                op_name = ""
            found = (op_name
                     or next((root_name[c] for c in called if root_name.get(c)), "")
                     or next((by_id[o] for o in reversed(operands) if by_id.get(o)), ""))
            names[name] = by_id[ins_id] = found
    return names


def _packed(buf) -> list:
    out, i = [], 0
    while i < len(buf):
        value, i = _varint(buf, i)
        out.append(value)
    return out


@functools.lru_cache(maxsize=4)
def op_names(trace_dir: str, ran: tuple = ()) -> dict:
    """Instruction name -> ``op_name`` of the step program in the capture
    under ``trace_dir`` (``ran``: the programs that ran on a device); empty
    where the capture holds no such program."""
    try:
        module = embedded_hlo(tr.find_xplane(trace_dir), ran)
    except (OSError, ValueError, IndexError):
        return {}
    return op_names_of(module) if module else {}


def scope_ns(trace: tr.Trace, names: dict, pattern: str) -> float:
    """Time covered by the device operations whose ``op_name`` matches, mean
    over devices.  A union of intervals: a ``conditional`` or a ``while`` and
    the operations of its body count once."""
    if not trace.ops:
        return 0.0
    rx = re.compile(pattern)
    total = 0.0
    for events in trace.ops.values():
        total += tr.union_ns(
            (s, d) for n, s, d in events if rx.search(names.get(n.split("[", 1)[0], "")))
    return total / len(trace.ops)


def trace_scope_per_step(r, spec: dict):
    """``spec["pattern"]`` against the ``op_name`` paths, times ``scale``, a
    traced step; None without a capture, without the program in it, or where
    nothing matches (a program that has no such scope)."""
    if r.trace is None or not r.trace_dir or not r.steps_traced:
        return None
    ran = tuple(sorted({n for events in r.trace.modules.values() for n, _, _ in events}))
    names = op_names(r.trace_dir, ran)
    ns = scope_ns(r.trace, names, spec["pattern"]) if names else 0.0
    return ns / r.steps_traced * spec.get("scale", 1.0) if ns > 0 else None
