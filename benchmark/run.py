#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, on the machine it is started on; a TPU with at least the cell's
chips, or exit 1 with nothing on stdout (there is no CPU fallback).  Earlier
lines start with ``#`` and are free; the last line of stdout is the result
object.  See README.md for the files a cell is made of.
"""

import time

T_START = time.perf_counter()

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "horovod_tpu")):
        print("benchmark: the program (horovod_tpu/) is not in this checkout",
              file=sys.stderr)
        return 1
    from benchmark import harness

    cell = harness.load_cell(args.workload)

    import jax

    from horovod_tpu.utils import compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"benchmark: {args.workload} needs {cell.chips} TPU chip(s); jax.devices() "
              f"is {devices}; this benchmark does not run on another backend",
              file=sys.stderr)
        return 1
    cache = compile_cache.enable()
    harness.log(f"# {args.workload} seed {args.seed} seconds {args.seconds} "
                f"trace {args.trace}; compile cache {cache}")
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              devices, t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
