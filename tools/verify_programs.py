#!/usr/bin/env python
"""Program-contract verifier launcher (the ``programs`` analysis pass).

Lowers the canonical program menu — serving decode/mixed/speculative
tiers at shard counts 1 and 2, guarded + ZeRO train
steps, the hierarchical allreduce — and machine-checks the invariants
docs promise in prose (see ``horovod_tpu/analysis/programs.py``):

* guard/trace no-op paths lower BYTE-identical; guard on adds 0
  collectives (plain AND ZeRO steps)
* no serving-step collective's replica group spans >1 slice (the
  DCN-exclusion contract of docs/SERVING.md)
* ``ops/comm_model`` modeled bytes == the lowered inventory, per tier
  program and for the hierarchical allreduce
* every program key dispatched under a randomized request load is in
  the warmup menu (the zero-recompile lint)

This needs jax (CPU is fine — it reads StableHLO, not wall clocks), so
it is a SEPARATE front door from ``tools/check.py``: the bare-box lint
stays <10s while this runs as its own CI job on 8 virtual devices.

Usage:
  tools/verify_programs.py                  # full run (CI program-verify)
  tools/verify_programs.py --requests 64    # faster local iteration
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_WORLD = 8
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + f" --xla_force_host_platform_device_count={_WORLD}"
    ).strip()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shards", default="1,2",
                    help="comma list of serving shard counts (default 1,2)")
    ap.add_argument("--requests", type=int, default=512,
                    help="randomized load size for the zero-recompile "
                    "lint (default 512)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from horovod_tpu.analysis import programs
    from horovod_tpu.analysis._common import Suppressions

    t0 = time.perf_counter()
    shards = tuple(int(s) for s in args.shards.split(",") if s)
    findings = programs.verify(shards=shards, requests=args.requests,
                               seed=args.seed)
    findings = Suppressions(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ).filter(findings)
    for f in findings:
        print(f.render())
    dt = time.perf_counter() - t0
    verdict = (f"{len(findings)} finding(s)" if findings
               else "all program contracts hold")
    print(f"verify_programs: {verdict} ({dt:.1f} s)", file=sys.stderr)
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
