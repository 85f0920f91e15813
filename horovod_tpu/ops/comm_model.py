"""Pure byte model of allreduce traffic per fabric tier.

The ``modeled_activation_bytes`` idiom applied to the comms stack: a
dependency-free function the bench tools, the engine's byte counters and
the CI assertions all share, so "modeled DCN bytes" means one thing
everywhere (docs/COLLECTIVES.md derives the formulas).

Model (ring algorithms, per one allreduce of ``shape``):

* flat over one slice (``n_ici == world``): the classic ring —
  ``2·(w-1)/w · payload`` bytes per chip, all on ICI; zero DCN.
* flat over a DCN-spanning world (``n_ici == 1``): the same stream, but
  every ring step's bytes cross a slice-boundary link — the
  bottleneck-link view that upstream Horovod's NCCLHierarchical mode
  exists to fix ("each byte crosses the slow fabric once per intra-group
  size").  All ``2·(w-1)/w · payload`` bytes are attributed to DCN.
* hierarchical (``1 < n_ici < world``): ICI reduce-scatter + ICI
  allgather move ``2·(n_ici-1)/n_ici · padded`` bytes on ICI; only the
  1/n_ici shard crosses DCN.  Uncompressed, the DCN hop is a psum —
  ``2·(n_dcn-1)/n_dcn · shard`` bytes.  With a wire dtype the hop is a
  wire-cast all_gather plus a LOCAL full-precision sum (the
  implementation never accumulates in the wire dtype,
  ``spmd_ops._two_level_sum_leaf``), so its ring stream is
  ``(n_dcn-1) · wire_shard`` — the two coincide only at n_dcn == 2.

Figures are bytes per rank (ICI) / per slice-boundary link (DCN) and
exclude protocol framing — good to first order, which is what the
flat-vs-hierarchical and fp32-vs-bf16 ratios need.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence

import numpy as np

#: Accepted short spellings for wire dtypes (mirrors compression.py).
_DTYPE_ALIAS = {"bf16": "bfloat16", "fp16": "float16", "half": "float16"}


def _itemsize(dtype) -> int:
    name = str(dtype)
    name = _DTYPE_ALIAS.get(name, name)
    if name == "bfloat16":  # numpy has no native bfloat16
        return 2
    try:
        return np.dtype(name).itemsize
    except TypeError:
        # ml_dtypes names numpy doesn't know (float8_e4m3fn, ...)
        try:
            import ml_dtypes

            return np.dtype(getattr(ml_dtypes, name)).itemsize
        except (ImportError, AttributeError, TypeError):
            raise ValueError(
                f"unknown dtype {dtype!r} in the collective byte model"
            ) from None


def modeled_collective_bytes(
    shape: Sequence[int],
    world: int,
    n_ici: int,
    wire_dtype: Optional[str] = None,
    dtype: str = "float32",
) -> dict:
    """Modeled per-tier bytes of ONE allreduce of ``shape``.

    Args:
      shape: tensor shape (any iterable of ints; () = scalar).
      world: total participating chips.
      n_ici: chips sharing the fast fabric.  ``world`` = flat single
        slice; ``1`` = flat routing over a DCN-spanning world (the
        bottleneck-link attribution above); anything between = the
        two-level hierarchical routing.
      wire_dtype: DCN-hop wire format (None/"bfloat16"/"float16"); only
        meaningful on the hierarchical routing — the flat paths carry
        the payload dtype.
      dtype: payload dtype.

    Returns ``{"ici_bytes", "dcn_bytes", "wire_dtype", "algorithm"}``
    (ints; wire_dtype echoed as a canonical name or None).
    """
    world = int(world)
    n_ici = int(n_ici)
    if world < 1 or n_ici < 1 or (n_ici > 1 and world % n_ici):
        raise ValueError(
            f"invalid world={world} / n_ici={n_ici} (n_ici must divide)"
        )
    n = int(np.prod(np.asarray(list(shape), dtype=np.int64))) if len(
        tuple(shape)) else 1
    item = _itemsize(dtype)
    payload = n * item
    wire_name = (
        _DTYPE_ALIAS.get(str(wire_dtype), str(wire_dtype))
        if wire_dtype else None
    )
    if world == 1:
        return {"ici_bytes": 0, "dcn_bytes": 0, "wire_dtype": None,
                "algorithm": "local"}
    if n_ici == world:
        return {
            "ici_bytes": int(2 * (world - 1) * payload // world),
            "dcn_bytes": 0,
            "wire_dtype": None,
            "algorithm": "flat",
        }
    if n_ici == 1:
        return {
            "ici_bytes": 0,
            "dcn_bytes": int(2 * (world - 1) * payload // world),
            "wire_dtype": None,
            "algorithm": "flat",
        }
    n_dcn = world // n_ici
    padded = -(-n // n_ici) * n_ici  # ceil to the scatter multiple
    shard = padded // n_ici
    # the wire only engages when compress_shard would actually narrow
    # the payload (float, wider than the wire) — otherwise the program
    # takes the uncompressed psum branch (_two_level_sum_leaf) and the
    # model must follow it
    compressible = (
        wire_name is not None
        and "float" in _DTYPE_ALIAS.get(str(dtype), str(dtype))
        and _itemsize(wire_name) < item
    )
    if compressible:
        # compressed hop: wire-dtype all_gather + local full-precision
        # sum — the all_gather ring stream, NOT the psum factor (module
        # docstring)
        dcn = int((n_dcn - 1) * shard * _itemsize(wire_name))
    else:
        dcn = int(2 * (n_dcn - 1) * shard * item // n_dcn)
    return {
        "ici_bytes": int(2 * (n_ici - 1) * padded * item // n_ici),
        "dcn_bytes": dcn,
        "wire_dtype": wire_name if compressible else None,
        "algorithm": "hierarchical",
    }


def mesh_slice_ids(hmesh) -> List[int]:
    """Slice id per LOGICAL device of a 2-D ``(dcn, ici)`` hierarchical
    mesh — the id order replica groups of a program lowered over that
    mesh use (row-major device assignment, so row == slice), regardless
    of how the physical world order interleaves slices.  This is what
    :func:`measured_tier_bytes` expects for programs compiled over
    ``Topology.hierarchical_mesh()``; the world-ordered
    ``Topology.slice_ids()`` only coincides with it when slices are
    contiguous in world order (the ``HVD_TPU_SLICE_SIZE`` override)."""
    n_dcn, n_ici = hmesh.devices.shape
    return [r for r in range(n_dcn) for _ in range(n_ici)]


# -- measured bytes: the compiled program's collective inventory -------------

#: ring-stream factor per collective kind: bytes a chip moves per byte of
#: the accounted payload (operand for reduce-style ops, result for
#: gathers) over a group of size g is ``factor * (g-1)/g``.
_COLLECTIVE_FACTOR = {
    "all_reduce": 2.0,
    "all_gather": 1.0,
    "reduce_scatter": 1.0,
    "all_to_all": 1.0,
    "collective_permute": 1.0,
}

#: which side of the op is the wire payload: reduce-style ops stream
#: their operand; gathers materialize their (bigger) result on the wire.
_PAYLOAD_SIDE = {
    "all_reduce": "operand",
    "reduce_scatter": "operand",
    "all_to_all": "operand",
    "all_gather": "result",
    "collective_permute": "operand",
}

_MLIR_ITEMSIZE = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2,
    "i64": 8, "ui64": 8, "i32": 4, "ui32": 4,
    "i16": 2, "ui16": 2, "i8": 1, "ui8": 1, "i1": 1,
}

_OP_START_RE = re.compile(
    r"\"?stablehlo\.(all_reduce|all_gather|reduce_scatter|all_to_all|"
    r"collective_permute)\"?\("
)

_GROUPS_RE = re.compile(
    r"(?:replica_groups|source_target_pairs)\s*=\s*dense<(.*?)>\s*:\s*"
    r"tensor<([0-9x]+)xi64>"
)

_SIG_RE = re.compile(
    r":\s*(\((?:tensor<[^>]+>(?:,\s*)?)*\)|tensor<[^>]+>)\s*->\s*"
    r"(\((?:tensor<[^>]+>(?:,\s*)?)*\)|tensor<[^>]+>)"
)

_TENSOR_RE = re.compile(r"tensor<([0-9x]*?)x?([a-z]+[0-9]*)>")


def _tensor_bytes(types: str) -> int:
    total = 0
    for dims, elem in _TENSOR_RE.findall(types):
        n = 1
        for d in dims.split("x"):
            if d:
                n *= int(d)
        total += n * _MLIR_ITEMSIZE.get(elem, 4)
    return total


def _parse_groups(literal: str, shape: str) -> List[List[int]]:
    rows = [int(s) for s in shape.split("x") if s]
    nums = [int(s) for s in re.findall(r"-?\d+", literal)]
    n_groups = rows[0] if rows else 1
    per = rows[1] if len(rows) > 1 else max(len(nums), 1)
    if len(nums) == 1 and n_groups * per > 1:  # dense splat
        nums = nums * (n_groups * per)
    return [nums[i * per:(i + 1) * per] for i in range(n_groups)]


def _collective_records(
    lowered_text: str, default_group: int
) -> List[Dict[str, object]]:
    """Inventory every collective instruction of a lowered (StableHLO)
    module: kind, payload bytes, replica groups, and the ring-stream
    per-chip link bytes.  The parser behind
    :func:`measured_tier_bytes` (tier attribution)."""
    lines = lowered_text.splitlines()
    records: List[Dict[str, object]] = []
    for i, line in enumerate(lines):
        start = _OP_START_RE.search(line)
        if start is None:
            continue
        kind = start.group(1)
        gm = _GROUPS_RE.search(line)
        if gm is not None:
            groups = _parse_groups(gm.group(1), gm.group(2))
        else:
            groups = [list(range(default_group))]
        # region ops (all_reduce / reduce_scatter) close with a
        # separate ``}) : (types) -> types`` line; single-line ops carry
        # the signature inline
        sig = _SIG_RE.search(line)
        j = i
        while sig is None and j + 1 < len(lines):
            j += 1
            if _OP_START_RE.search(lines[j]):
                break  # never read into the next collective
            if lines[j].lstrip().startswith("})"):
                sig = _SIG_RE.search(lines[j])
                break
        if sig is None:
            continue
        in_types, out_types = sig.groups()
        side = _PAYLOAD_SIDE[kind]
        payload = _tensor_bytes(in_types if side == "operand" else out_types)
        if kind == "collective_permute":
            g = 2  # pairwise sends; each chip ships its whole buffer
            stream = payload
        else:
            g = max(len(groups[0]), 1) if groups else 1
            stream = int(_COLLECTIVE_FACTOR[kind] * (g - 1) * payload // g)
        records.append({
            "op": kind, "groups": groups,
            "payload_bytes": payload, "group_size": g,
            "stream_bytes": stream,
        })
    return records


def measured_tier_bytes(
    lowered_text: str,
    slice_ids: Sequence[int],
) -> Dict[str, object]:
    """Per-tier wire bytes of a compiled program, MEASURED from its
    lowered (StableHLO) module rather than assumed by the model: every
    collective instruction is inventoried with its real payload
    shape/dtype and replica groups, the ring-stream factor converts
    payload to per-chip link bytes, and each group is attributed to DCN
    when its members span >1 slice of ``slice_ids`` and to ICI
    otherwise.  ``slice_ids`` must map the program's LOGICAL device
    ids: :func:`mesh_slice_ids` for programs lowered over a
    hierarchical mesh (replica groups follow the mesh's row-major
    device assignment), ``Topology.slice_ids()`` for the 1-D world
    mesh (logical order == world order there).

    The lowered module is the device-agnostic program: backends may
    legalize further (XLA:CPU promotes bf16 collectives to f32 — the
    reason this reads the lowered text, not the backend-optimized HLO;
    TPU executes 16-bit collectives natively).  Returns ``{"ici_bytes",
    "dcn_bytes", "ops": [per-instruction records]}``.
    """
    slice_ids = list(slice_ids)
    ici = dcn = 0
    ops = []
    for rec in _collective_records(lowered_text, len(slice_ids)):
        crosses = any(
            len({slice_ids[d] for d in grp if 0 <= d < len(slice_ids)}) > 1
            for grp in rec["groups"]
        )
        stream = rec["stream_bytes"]
        if crosses:
            dcn += stream
        else:
            ici += stream
        ops.append({
            "op": rec["op"], "payload_bytes": rec["payload_bytes"],
            "group_size": rec["group_size"],
            "tier": "dcn" if crosses else "ici", "stream_bytes": stream,
        })
    return {"ici_bytes": int(ici), "dcn_bytes": int(dcn), "ops": ops}


# -- tensor-sharded serving: the decode program's collective inventory -------


def modeled_serve_psum_bytes(
    batch: int,
    q_len: int,
    d_model: int,
    num_layers: int,
    shards: int,
    dtype: str = "float32",
) -> dict:
    """Per-chip ICI ring-stream bytes of ONE tensor-sharded serving
    step's collectives (docs/SERVING.md sharding section): the Megatron
    schedule runs exactly TWO row-parallel psums per decoder layer
    (attention output projection, MLP down projection), each an
    all_reduce of that sublayer's ``(batch, q_len, d_model)`` output in
    the activation dtype — nothing else in the step communicates (the
    KV pool is head-sharded in place, block tables replicate, the
    embedding head is replicated).  The ring stream per chip is
    ``2*(shards-1)/shards * payload`` per psum — the same factor
    :func:`measured_tier_bytes` applies to the lowered program's
    ``all_reduce`` records, so modeled == measured holds op-for-op (the
    PR-7 idiom; tools/serve_bench.py asserts it on the MULTICHIP leg).
    """
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    if shards == 1:
        return {"psum_count": 0, "payload_bytes": 0, "stream_bytes": 0}
    payload = int(batch) * int(q_len) * int(d_model) * _itemsize(dtype)
    per = 2 * (shards - 1) * payload // shards
    return {
        "psum_count": 2 * num_layers,
        "payload_bytes": payload,
        "stream_bytes": 2 * num_layers * per,
    }


def modeled_kvsnap_bytes(
    num_blocks: int,
    block_size: int,
    num_layers: int,
    kv_heads: int,
    head_dim: int,
    dtype: str = "float32",
) -> dict:
    """Modeled wire bytes of ONE ``kvsnap/1`` paged-KV snapshot of
    ``num_blocks`` full blocks — the prefill→decode handoff (and
    replica-loss migration) payload the disaggregated fleet moves
    between replicas.  Per block the snapshot carries one K page and
    one V page of ``(num_layers, block_size, kv_heads, head_dim)``
    each, plus the block's verified int32 token run.  Pages export
    host-side from the FULL pool (``export_requests`` pulls the whole
    pool, so a sharded engine's page still carries every kv head —
    the model is shard-independent by construction, exactly like the
    measured ``nbytes`` of the exported arrays).  Returns
    ``{"page_bytes", "token_bytes", "wire_bytes"}`` (ints);
    ``tools/serve_bench.py --disagg`` asserts modeled == measured
    over the leg's handoff records (the PR-7 idiom)."""
    if num_blocks < 0 or block_size < 1:
        raise ValueError(
            f"need num_blocks >= 0 and block_size >= 1, got "
            f"{num_blocks}/{block_size}")
    page = (2 * int(num_layers) * int(block_size) * int(kv_heads)
            * int(head_dim) * _itemsize(dtype))
    toks = int(num_blocks) * int(block_size) * 4  # int32 token runs
    return {
        "page_bytes": int(num_blocks) * page,
        "token_bytes": toks,
        "wire_bytes": int(num_blocks) * page + toks,
    }


def measured_kvsnap_bytes(snap: dict) -> int:
    """MEASURED wire bytes of one ``kvsnap/1`` snapshot: the K/V page
    arrays' ``nbytes`` plus the int32 token stream as actually
    serialized — :func:`modeled_kvsnap_bytes`'s measured twin (the
    router books it into ``hvd_tpu_serve_migrated_kv_bytes_total`` on
    every warm handoff/migration)."""
    toks = snap.get("tokens")
    n = len(toks) if toks is not None else 0  # may be an ndarray:
    total = n * 4                             # never bool() it
    for kp, vp in snap.get("pages") or ():
        total += int(np.asarray(kp).nbytes) + int(np.asarray(vp).nbytes)
    return total


_GATHER_RE = re.compile(r"\"?stablehlo\.(?:dynamic_)?gather\"?\(")


def serve_gather_read_bytes(lowered_text: str, min_rank: int = 5) -> dict:
    """MEASURED per-chip bytes the compiled serving step's page-gather
    copies materialize, inventoried from the lowered (StableHLO) module
    — the measured twin of ``kv_cache.modeled_decode_read_bytes``'s
    ``gathered_bytes`` term (× batch tier), and the number that must
    drop by the shard factor under kv-head sharding (the lowered
    shard_map program carries LOCAL shapes, so the inventory reads the
    per-chip stream directly).

    The pool-page copies are identified by RESULT RANK: a page gather's
    result is ``(batch, pages, block_size, H_kv, head_dim)`` — rank 5 —
    while every other gather in the step is lower-rank (embedding
    lookup rank 3, block-table ``take_along_axis`` rank 2), so rank is
    a shape-stable discriminator where a byte threshold would not be.
    Returns ``{"gather_bytes", "ops": [{result_bytes, rank}]}``.
    """
    total = 0
    ops = []
    for line in lowered_text.splitlines():
        if not _GATHER_RE.search(line):
            continue
        sig = _SIG_RE.search(line)
        if sig is None:
            continue
        out_types = sig.group(2)
        m = _TENSOR_RE.search(out_types)
        if m is None:
            continue
        dims = [d for d in m.group(1).split("x") if d]
        if len(dims) < min_rank:
            continue
        nbytes = _tensor_bytes(out_types)
        total += nbytes
        ops.append({"result_bytes": nbytes, "rank": len(dims)})
    return {"gather_bytes": int(total), "ops": ops}


# -- what the backend scheduled: the compiled program's all-reduces -----------

_HLO_COMPUTATION_RE = re.compile(
    r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$"
)
_HLO_FUSION_CALLS_RE = re.compile(r"\bfusion\(.*\bcalls=%?([\w.\-]+)")


def compiled_collective_counts(compiled_text: str) -> Dict[str, int]:
    """How the BACKEND scheduled a step's all-reduces, counted in the
    compiled text (``step.lower(...).compile().as_text()``: scheduled HLO,
    after the compiler's passes): the lowered program cannot say what
    the TPU scheduler does with it.

    ``async_pairs``: start/done pairs of asynchronous collectives — the
    TPU's ``AsyncCollectiveStart``/``AsyncCollectiveDone`` custom calls
    (each in a fusion of its own; the collective itself is repeated
    inside the compute fusions it runs beside) and XLA's generic
    ``all-reduce-start``/``-done``.  ``sync_all_reduces``: ``all-reduce``
    instructions outside every fused computation, during which the core
    waits.  The dp4 benchmark step compiled with no option: 0 and 26."""
    fused = set(_HLO_FUSION_CALLS_RE.findall(compiled_text))
    starts = dones = sync = 0
    computation = ""
    for line in compiled_text.splitlines():
        m = _HLO_COMPUTATION_RE.match(line)
        if m:
            computation = m.group(1)
        elif 'custom_call_target="AsyncCollectiveStart"' in line \
                or " all-reduce-start(" in line:
            starts += 1
        elif 'custom_call_target="AsyncCollectiveDone"' in line \
                or " all-reduce-done(" in line:
            dones += 1
        elif " all-reduce(" in line and computation not in fused:
            sync += 1
    return {"async_pairs": min(starts, dones), "sync_all_reduces": sync}
