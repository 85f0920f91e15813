#!/usr/bin/env python
"""Collective-routing benchmark: flat vs hierarchical vs hierarchical +
DCN wire compression, with modeled AND measured per-tier bytes.

Every leg emits ONE bench-style JSON line on stdout (human summary on
stderr) — the flash_bench/transformer_bench contract.  Per leg:

  * ``modeled``  — ``ops.comm_model.modeled_collective_bytes`` (the pure
    ring model docs/COLLECTIVES.md derives);
  * ``measured`` — ``ops.comm_model.measured_tier_bytes`` over the
    lowered StableHLO of the EXACT compiled program: the real collective
    instruction inventory (shapes, wire dtypes, replica groups), each
    group attributed to ICI or DCN by the slice map.  The lowered module
    is read rather than backend-optimized HLO because XLA:CPU legalizes
    16-bit collectives to f32 (TPU executes them natively);
  * ``max_rel_err`` / ``bit_exact`` — the allreduce oracle: leg output
    vs a float64 numpy reduction of the same contributions;
  * ``time_ms`` — wall clock per step (interpret-grade on a CPU box;
    chip numbers are not measured yet).

The default configuration IS the MULTICHIP ground-truth topology: an
8-virt-device world split 2 slices x 4 chips (``HVD_TPU_SLICE_SIZE=4``
over virtual CPU devices), the acceptance harness of ISSUE 7 /
ROADMAP item 3.

``HVD_TPU_BENCH_ITERS`` / ``HVD_TPU_BENCH_WARMUP`` override iteration
counts (docs/running.md).

Usage:
  collective_bench.py                      # full sweep, 4 MiB payload
  collective_bench.py --numel 1048576      # payload size (elements)
  collective_bench.py --legs flat,hier_bf16
  collective_bench.py --smoke              # tiny CPU-safe pass (CI)
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# expose the virtual multislice world BEFORE jax can be imported: raw
# parse, same bootstrap as transformer_bench
try:  # contract-ok: env -- bootstrap runs before the package's env_int is importable
    _WORLD = max(2, int(os.environ.get("HVD_TPU_BENCH_WORLD", "") or 8))
except ValueError:
    _WORLD = 8
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + f" --xla_force_host_platform_device_count={_WORLD}"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu.common.retry import env_int  # noqa: E402
from horovod_tpu.common.topology import (  # noqa: E402
    DCN_AXIS, ICI_AXIS, WORLD_AXIS,
)
from horovod_tpu.compression import DcnCompression  # noqa: E402
from horovod_tpu.ops import spmd_ops  # noqa: E402
from horovod_tpu.ops.comm_model import (  # noqa: E402
    measured_tier_bytes, mesh_slice_ids, modeled_collective_bytes,
)

ITERS = env_int("HVD_TPU_BENCH_ITERS", 20)
WARMUP = env_int("HVD_TPU_BENCH_WARMUP", 3)

#: leg -> (hierarchical?, wire dtype or None)
LEGS = {
    "flat": (False, None),
    "hier": (True, None),
    "hier_bf16": (True, "bfloat16"),
    "hier_fp16": (True, "float16"),
}


_leg_t0 = time.time()


def begin_leg():
    """Stamp the wall-clock start of the next leg (emit() pairs it with
    t_end so bench rows correlate with trace dumps from the same run)."""
    global _leg_t0
    _leg_t0 = time.time()


def emit(rec, human=""):
    rec.setdefault("t_start", round(_leg_t0, 3))
    rec.setdefault("t_end", round(time.time(), 3))
    print(json.dumps(rec))
    if human:
        print(human, file=sys.stderr)


def _timed(fn, *args):
    out = jax.block_until_ready(fn(*args))
    for _ in range(max(WARMUP - 1, 0)):
        out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    iters = max(ITERS, 1)
    for _ in range(iters):
        out = jax.block_until_ready(fn(*args))
    return out, (time.perf_counter() - t0) / iters


def run_leg(leg, x, hmesh, wmesh, slice_ids, n_ici):
    hierarchical, wire = LEGS[leg]
    world = x.shape[0]
    comp = DcnCompression(wire) if wire else None
    if hierarchical:
        fn = jax.jit(jax.shard_map(
            lambda t: spmd_ops.hierarchical_allreduce(
                t, op=hvd.Sum, dcn_compression=comp
            ),
            mesh=hmesh, in_specs=P((DCN_AXIS, ICI_AXIS)),
            out_specs=P((DCN_AXIS, ICI_AXIS)), check_vma=False,
        ))
    else:
        fn = jax.jit(jax.shard_map(
            lambda t: spmd_ops.allreduce(t, op=hvd.Sum),
            mesh=wmesh, in_specs=P(WORLD_AXIS), out_specs=P(WORLD_AXIS),
            check_vma=False,
        ))
    out, step_s = _timed(fn, x)
    ref = np.asarray(x, np.float64).sum(axis=0)
    got = np.asarray(out, np.float64)
    err = np.abs(got - ref[None]).max()
    scale = max(np.abs(ref).max(), 1e-30)
    # hierarchical programs: replica groups use the hmesh's row-major
    # LOGICAL ids (mesh_slice_ids); the flat program runs over the 1-D
    # world mesh where logical order == world order
    measured = measured_tier_bytes(
        fn.lower(x).as_text(),
        mesh_slice_ids(hmesh) if hierarchical else slice_ids,
    )
    if hierarchical:
        n_ici_model = n_ici
    else:
        # flat routing over a slice-spanning world: every ring step's
        # bytes cross a slice-boundary link (n_ici=1 attribution —
        # comm_model's bottleneck-link view, matching measured_tier_bytes'
        # classification of the world-spanning replica group)
        n_ici_model = 1 if len(set(slice_ids)) > 1 else world
    modeled = modeled_collective_bytes(
        x.shape[1:], world, n_ici_model,
        wire_dtype=wire, dtype=str(x.dtype),
    )
    return {
        "bench": "collective",
        "leg": leg,
        "world": world,
        "n_ici": n_ici if hierarchical else world,
        "n_dcn": (world // n_ici) if hierarchical else 1,
        "numel": int(np.prod(x.shape[1:])),
        "dtype": str(x.dtype),
        "wire_dtype": wire,
        "comm_bytes": {
            "ici": modeled["ici_bytes"],
            "dcn": modeled["dcn_bytes"],
            "wire_dtype": modeled["wire_dtype"],
        },
        "measured_bytes": {
            "ici": measured["ici_bytes"],
            "dcn": measured["dcn_bytes"],
        },
        "collective_ops": [
            (o["op"], o["tier"], o["stream_bytes"]) for o in measured["ops"]
        ],
        "time_ms": round(step_s * 1e3, 3),
        "max_rel_err": float(err / scale),
        "bit_exact": bool(err == 0.0),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--legs", default=",".join(LEGS),
                    help=f"comma list of {'/'.join(LEGS)}")
    ap.add_argument("--numel", type=int, default=1 << 20,
                    help="payload elements per contribution")
    ap.add_argument("--slice-size", type=int, default=0,
                    help="chips per slice (default world/2)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CPU-safe pass of every leg (CI)")
    args = ap.parse_args(argv)
    from horovod_tpu.utils import compile_cache

    compile_cache.enable()

    numel = 4096 if args.smoke else args.numel
    hvd.init()
    world = hvd.size()
    n_ici = args.slice_size or max(world // 2, 1)
    if world % n_ici:
        ap.error(f"--slice-size {n_ici} does not divide world {world}")
    os.environ["HVD_TPU_SLICE_SIZE"] = str(n_ici)
    from horovod_tpu.common import basics
    topo = basics._require_init().topology
    slice_ids = topo.slice_ids()
    hmesh = topo.hierarchical_mesh()
    wmesh = hvd.world_mesh()

    # dyadic-friendly contributions: distinct per chip, exactly
    # representable so the fp32 Sum oracle can be bit-checked
    rs = np.random.RandomState(0)
    x = jnp.asarray(
        np.round(rs.randn(world, numel) * 8) / 8
    ).astype(jnp.float32)

    failed = False
    for leg in args.legs.split(","):
        leg = leg.strip()
        if leg not in LEGS:
            ap.error(f"unknown leg {leg!r}")
        begin_leg()
        try:
            rec = run_leg(leg, x, hmesh, wmesh, slice_ids, n_ici)
        except Exception as e:  # noqa: BLE001 - isolate legs, report at exit
            print(f"[collective_bench] leg {leg} FAILED: {e}",
                  file=sys.stderr)
            failed = True
            continue
        emit(rec, (
            f"[collective_bench] {leg:>10}: modeled dcn "
            f"{rec['comm_bytes']['dcn']}B measured dcn "
            f"{rec['measured_bytes']['dcn']}B ici "
            f"{rec['measured_bytes']['ici']}B "
            f"{rec['time_ms']}ms rel_err {rec['max_rel_err']:.2e}"
        ))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
