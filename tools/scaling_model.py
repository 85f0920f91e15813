#!/usr/bin/env python
"""Analytic data-parallel scaling model for the headline ResNet-50 bench.

A 256-chip pod is not reachable from this environment, so the BASELINE
north star — >=90% scaling efficiency to
256 chips — cannot be measured directly.  This tool states the model and
the measured inputs it rests on, so the efficiency claim is a checkable
calculation rather than an assertion.  It is a MODEL, labeled as such:
the real number depends on XLA's compute/communication overlap, which
this bounds from both sides.

Model (standard DP ring cost, e.g. the reference's own ring-allreduce
analysis and the scaling-book recipe):
  t_comm(n)  = 2*(n-1)/n * G / B_ici          (bf16 gradient allreduce)
  eff_worst  = t_step / (t_step + t_comm)      (zero overlap)
  eff_best   = t_step / max(t_step, t_comm)    (perfect overlap)
Cross-slice (DCN) terms only enter past one pod slice; v5e slices reach
256 chips on ICI, so the headline range never leaves ICI.

Measured inputs (an earlier round, one v5e chip, not re-measured on today's code):
  t_step = 47.6 ms  (ResNet-50, batch 128/chip, bf16, space-to-depth)
  G      = 25.6M params -> 51.2 MB bf16 on the wire (fp32 would be 102 MB)

Hardware constant (approx., public v5e spec): 1600 Gbit/s ICI per chip
=> B_ici ~= 200 GB/s aggregate; the ring uses it bidirectionally.
"""

import json

T_STEP_S = 0.0476          # measured, v5e batch 128 (PERF.md round 4)
PARAMS = 25.6e6
WIRE_BYTES = PARAMS * 2    # bf16 gradient compression on the wire
B_ICI = 200e9              # ~1600 Gbit/s per v5e chip (approx. public spec)
#: default BucketSchedule bucket (HVD_TPU_OVERLAP_BUCKET_BYTES) for the
#: bucketed-overlap row; ops/comm_model.modeled_overlap_exposed is the
#: canonical simulation — tools/collective_bench.py cross-checks this
#: file's inline twin against it on the overlap leg.
BUCKET_BYTES = 4 * 1024 * 1024


def model(n: int):
    t_comm = 2 * (n - 1) / n * WIRE_BYTES / B_ICI
    worst = T_STEP_S / (T_STEP_S + t_comm)
    best = T_STEP_S / max(T_STEP_S, t_comm)
    return t_comm, worst, best


def overlap_model(n: int, bucket_bytes: int = BUCKET_BYTES):
    """Bucketed backward/overlap row (ops/overlap.py schedule): buckets
    are produced across the backward at a byte-proportional rate, each
    bucket's ring allreduce queues on the serial link, and only what
    outlives the compute is exposed.  Inline twin of
    ``ops.comm_model.modeled_overlap_exposed`` (kept dependency-free so
    this tool stays stdlib-only); returns (t_exposed_s,
    exposed_fraction, efficiency)."""
    if n <= 1:
        return 0.0, 0.0, 1.0
    sizes = [bucket_bytes] * int(WIRE_BYTES // bucket_bytes)
    rem = WIRE_BYTES - bucket_bytes * len(sizes)
    if rem:
        sizes.append(rem)
    ring = 2 * (n - 1) / n / B_ICI
    t_comm = sum(s * ring for s in sizes)
    cum, end = 0.0, 0.0
    for s in sizes:
        cum += s
        ready = T_STEP_S * cum / WIRE_BYTES
        end = max(ready, end) + s * ring
    exposed = max(0.0, end - T_STEP_S)
    frac = exposed / t_comm if t_comm else 0.0
    return exposed, frac, T_STEP_S / (T_STEP_S + exposed)


def main():
    rows = []
    for n in (1, 8, 32, 64, 256):
        t_comm, worst, best = model(n)
        exposed, frac, eff_overlap = overlap_model(n)
        rows.append({
            "chips": n,
            "t_comm_ms": round(t_comm * 1e3, 3),
            "efficiency_no_overlap": round(worst, 4),
            "efficiency_full_overlap": round(best, 4),
            "bucketed_exposed_ms": round(exposed * 1e3, 4),
            "bucketed_exposed_fraction": round(frac, 4),
            "efficiency_bucketed_overlap": round(eff_overlap, 4),
        })
        print(f"n={n:4d}: allreduce {t_comm*1e3:6.3f} ms  "
              f"efficiency {worst:.1%} (no overlap) .. {best:.1%} (full); "
              f"bucketed schedule exposes {frac:.1%} of comm "
              f"-> {eff_overlap:.1%}")
    print()
    worst_comm_ms = max(r["t_comm_ms"] for r in rows)
    print("Even with ZERO compute/comm overlap the model stays above "
          f"{min(r['efficiency_no_overlap'] for r in rows):.1%} — the "
          f"51 MB bf16 gradient ring is ~{worst_comm_ms:.2f} ms against "
          "a 47.6 ms step, so the reference's >=90%-at-256 regime is "
          "bandwidth-trivial for this model on ICI.  The binding risks "
          "are stragglers and input pipeline, not the collective.")
    print(json.dumps({"model": "dp_ring_allreduce", "rows": rows}))


if __name__ == "__main__":
    main()
