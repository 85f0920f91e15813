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

#: overlap legs (ops/overlap.py): handled by run_overlap_legs /
#: run_overlap_autotune_leg rather than the allreduce sweep above
OVERLAP_LEGS = ("overlap", "overlap_autotune")


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


def _overlap_chain(world, n_seg, d, batch):
    """A segment-chain training program (relu MLP) sized so the
    BucketSchedule splits it into several buckets — the overlap leg's
    workload.  Returns (segments, params, x, schedule bucket bytes)."""
    from horovod_tpu.ops.overlap import Segment

    rs = np.random.RandomState(1)
    params = {
        f"w{k}": jnp.asarray(
            np.round(rs.randn(d, d) * 8) / 8, jnp.float32
        )
        for k in range(n_seg)
    }

    def make(k):
        def seg(p, x):
            return jax.nn.relu(x @ p[f"w{k}"])

        return Segment(seg, keys=(f"w{k}",))

    def head(p, x):
        return jnp.mean((x @ p[f"w{n_seg - 1}"]) ** 2)

    segments = [make(k) for k in range(n_seg - 1)] + [
        Segment(head, keys=(f"w{n_seg - 1}",))
    ]
    x = jnp.asarray(
        np.round(rs.randn(batch, d) * 8) / 8, jnp.float32
    )
    return segments, params, x


def _overlap_step_fn(segments, wmesh, world, bucket_bytes, overlap):
    from horovod_tpu.ops.overlap import overlapped_value_and_grad

    def f(p, x):
        loss, grads, _ = overlapped_value_and_grad(
            segments, p, x,
            bucket_reduce=lambda b: jax.lax.psum(b, WORLD_AXIS)
            / jnp.asarray(world, b.dtype),
            bucket_bytes=bucket_bytes, overlap=overlap,
        )
        return loss, grads

    return jax.jit(jax.shard_map(
        f, mesh=wmesh, in_specs=(P(), P(WORLD_AXIS)),
        out_specs=(P(), P()), check_vma=False,
    ))


def run_overlap_legs(wmesh, world, smoke):
    """The backward/collective overlap leg: overlapped vs unoverlapped
    step time, static (program-inventory) exposed-comm fraction on both,
    bucket count/size columns, grads-bit-equal oracle — plus the r4
    scaling-model row (modeled exposed fraction + efficiency at the
    PERF.md round-4 measured point, cross-checked against
    tools/scaling_model.py's inline twin)."""
    from horovod_tpu.ops.fusion import BucketSchedule
    from horovod_tpu.ops.overlap import record_overlap_metrics
    from horovod_tpu.ops.comm_model import (
        modeled_overlap_exposed, overlap_inventory,
    )

    n_seg, d = (4, 32) if smoke else (8, 256)
    batch = world * (2 if smoke else 8)
    segments, params, x = _overlap_chain(world, n_seg, d, batch)
    leaf_bytes = d * d * 4
    bucket_bytes = 2 * leaf_bytes  # 2 layers per bucket -> n_seg/2 buckets
    f_ov = _overlap_step_fn(segments, wmesh, world, bucket_bytes, True)
    f_un = _overlap_step_fn(segments, wmesh, world, bucket_bytes, False)
    (l1, g1), t_ov = _timed(f_ov, params, x)
    (l2, g2), t_un = _timed(f_un, params, x)
    bit_equal = bool(np.asarray(l1) == np.asarray(l2)) and all(
        (np.asarray(a) == np.asarray(b)).all()
        for a, b in zip(
            jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)
        )
    )
    inv_ov = record_overlap_metrics(f_ov.lower(params, x).as_text())
    inv_un = overlap_inventory(f_un.lower(params, x).as_text())
    sched = BucketSchedule(
        jax.tree_util.tree_leaves(params), bucket_bytes
    )
    # r4 scaling-model point (tools/scaling_model.py constants): the
    # acceptance bar is a >=2x modeled exposed-comm drop there
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "scaling_model",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "scaling_model.py"),
    )
    sm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sm)
    n_chips = 256
    n_buckets_r4 = -(-int(sm.WIRE_BYTES) // sm.BUCKET_BYTES)
    r4 = modeled_overlap_exposed(
        [sm.BUCKET_BYTES] * (n_buckets_r4 - 1)
        + [int(sm.WIRE_BYTES) - sm.BUCKET_BYTES * (n_buckets_r4 - 1)],
        sm.T_STEP_S, sm.B_ICI, n_chips,
    )
    exp_sm, frac_sm, eff_sm = sm.overlap_model(n_chips)
    if abs(frac_sm - r4["exposed_fraction"]) > 1e-9:
        raise AssertionError(
            "scaling_model.overlap_model drifted from "
            f"comm_model.modeled_overlap_exposed: {frac_sm} vs "
            f"{r4['exposed_fraction']}"
        )
    recs = [
        {
            "bench": "collective",
            "leg": "overlap",
            "world": world,
            "segments": n_seg,
            "n_buckets": sched.num_buckets,
            "bucket_bytes": bucket_bytes,
            "bucket_nbytes": list(sched.bucket_nbytes),
            "time_ms": round(t_ov * 1e3, 3),
            "time_ms_unoverlapped": round(t_un * 1e3, 3),
            "exposed_fraction_static": round(
                inv_ov["exposed_fraction"], 4),
            "exposed_fraction_static_unoverlapped": round(
                inv_un["exposed_fraction"], 4),
            "interleaved": inv_ov["interleaved"],
            "interleaved_unoverlapped": inv_un["interleaved"],
            "collectives": len(inv_ov["collectives"]),
            "bit_exact": bit_equal,
        },
        {
            "bench": "collective",
            "leg": "overlap_r4_model",
            "chips": n_chips,
            "bucket_bytes": int(sm.BUCKET_BYTES),
            "n_buckets": r4["n_buckets"],
            "t_comm_ms": round(r4["t_comm_s"] * 1e3, 4),
            "t_exposed_ms": round(r4["t_exposed_s"] * 1e3, 4),
            "exposed_fraction": round(r4["exposed_fraction"], 4),
            "exposed_fraction_unoverlapped": 1.0,
            "exposed_drop_x": round(
                1.0 / max(r4["exposed_fraction"], 1e-9), 2),
            "efficiency_bucketed_overlap": round(eff_sm, 4),
        },
    ]
    return recs


def run_overlap_autotune_leg(wmesh, world, smoke):
    """BucketAutotuner leg: sweep bucket sizes over the overlap chain,
    pin the winner, report per-candidate step times — the bench
    acceptance is structural (the default is trial 0 and the pin is the
    argmin, so the pinned plan can never regress against it)."""
    import time as _time

    from horovod_tpu.ops.overlap import BucketAutotuner, Candidate

    n_seg, d = (4, 32) if smoke else (8, 256)
    batch = world * (2 if smoke else 8)
    segments, params, x = _overlap_chain(world, n_seg, d, batch)
    leaf_bytes = d * d * 4
    default = Candidate(2 * leaf_bytes)
    candidates = [Candidate(leaf_bytes), Candidate(4 * leaf_bytes)]
    tuner = BucketAutotuner(
        candidates=candidates, default=default,
        trial_budget=len(candidates) + 1,
        steps_per_trial=2 if smoke else max(3, WARMUP + 1),
    )

    def build(cand):
        step = _overlap_step_fn(
            segments, wmesh, world, cand.bucket_bytes, True
        )
        return lambda: step(params, x)

    def timed(thunk):
        t0 = _time.perf_counter()
        jax.block_until_ready(thunk())
        return _time.perf_counter() - t0

    pinned = tuner.run(build, timed)
    scores = {c.bucket_bytes: t for c, t in tuner.scores}
    return {
        "bench": "collective",
        "leg": "overlap_autotune",
        "world": world,
        "candidates": sorted(scores),
        "step_ms_by_bucket": {
            str(k): round(v * 1e3, 3) for k, v in sorted(scores.items())
        },
        "pinned_bucket_bytes": pinned.bucket_bytes,
        "trials": len(tuner.scores),
        "trial_budget": tuner.trial_budget,
        "pinned_step_ms": round(scores[pinned.bucket_bytes] * 1e3, 3),
        "default_step_ms": round(scores[default.bucket_bytes] * 1e3, 3),
        "regressed_vs_default": bool(
            scores[pinned.bucket_bytes] > scores[default.bucket_bytes]
        ),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    all_legs = tuple(LEGS) + OVERLAP_LEGS
    ap.add_argument("--legs", default=",".join(all_legs),
                    help=f"comma list of {'/'.join(all_legs)}")
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
        if leg not in LEGS and leg not in OVERLAP_LEGS:
            ap.error(f"unknown leg {leg!r}")
        begin_leg()
        try:
            if leg == "overlap":
                for rec in run_overlap_legs(wmesh, world, args.smoke):
                    if rec["leg"] == "overlap":
                        emit(rec, (
                            f"[collective_bench]    overlap: "
                            f"{rec['n_buckets']} buckets, static exposed "
                            f"{rec['exposed_fraction_static']} (unoverlapped "
                            f"{rec['exposed_fraction_static_unoverlapped']}), "
                            f"bit_exact {rec['bit_exact']}, "
                            f"{rec['time_ms']}ms vs "
                            f"{rec['time_ms_unoverlapped']}ms"
                        ))
                    else:
                        emit(rec, (
                            f"[collective_bench] overlap_r4: modeled exposed "
                            f"{rec['exposed_fraction']} at {rec['chips']} "
                            f"chips ({rec['exposed_drop_x']}x drop)"
                        ))
                continue
            if leg == "overlap_autotune":
                rec = run_overlap_autotune_leg(wmesh, world, args.smoke)
                emit(rec, (
                    f"[collective_bench]   autotune: pinned "
                    f"{rec['pinned_bucket_bytes']}B after {rec['trials']} "
                    f"trials, {rec['pinned_step_ms']}ms (default "
                    f"{rec['default_step_ms']}ms)"
                ))
                continue
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
