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


def model(n: int):
    t_comm = 2 * (n - 1) / n * WIRE_BYTES / B_ICI
    worst = T_STEP_S / (T_STEP_S + t_comm)
    best = T_STEP_S / max(T_STEP_S, t_comm)
    return t_comm, worst, best


def main():
    rows = []
    for n in (1, 8, 32, 64, 256):
        t_comm, worst, best = model(n)
        rows.append({
            "chips": n,
            "t_comm_ms": round(t_comm * 1e3, 3),
            "efficiency_no_overlap": round(worst, 4),
            "efficiency_full_overlap": round(best, 4),
        })
        print(f"n={n:4d}: allreduce {t_comm*1e3:6.3f} ms  "
              f"efficiency {worst:.1%} (no overlap) .. {best:.1%} (full)")
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
