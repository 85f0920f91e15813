"""Not a test and not part of a run: the tool that reproduced the four-chip
window hang (PR 27; PERF.md section 7, first item).  Four chips, one process:

    PYTHONFAULTHANDLER=1 python3 benchmark/tests/stress_window.py <on|off> <seconds> [tiny]

Builds the dp4 cell's compiled step once through harness.prepare (the
benchmark's own set-up), then drives short windows through harness._drive
(dispatch one ahead) over and over, with a host-side read between windows, so
that the idle -> two-in-flight transition of a window's start happens many
times.  "off" compiles the step without spmd_ops.exchange_compile_options
(PR 25's asynchronous all-reduce options).  A watchdog dumps every thread and
exits 1 when a cycle does not finish in 60 s.  ``tiny`` is selftest.py's
tiny decoder, for a rehearsal on four virtual CPU devices.  Readings of PR 27
(my chip run): on, hung after 755 to about 1,000 steps; off, 1,038 steps sound.
"""

import faulthandler
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

mode, seconds = sys.argv[1], float(sys.argv[2])
tiny = len(sys.argv) > 3

from benchmark import harness  # noqa: E402

import jax  # noqa: E402

from horovod_tpu.ops import spmd_ops  # noqa: E402
from horovod_tpu.utils import compile_cache  # noqa: E402

if mode == "off":
    spmd_ops.exchange_compile_options = lambda *a, **k: {}

if tiny:
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import selftest

    cell = selftest.tiny_cell(selftest.TINY_LM, 4)
else:
    cell = harness.Cell(
        name="internlm2-1.8b-s4096-dp4", config_name="internlm2-1.8b",
        config=harness.load_json(ROOT, "benchmark/configs/internlm2-1.8b.json"),
        traffic_name="s4096-dp4",
        traffic=harness.load_json(ROOT, "benchmark/traffic/s4096-dp4.json"), chips=4)
    print("# cache", compile_cache.enable(), flush=True)

devices = jax.devices()[:4]
assert len(devices) == 4, devices
t0 = time.perf_counter()
p = harness.prepare(cell, 2700000041, devices)
print(f"# mode {mode}: prepared in {time.perf_counter() - t0:.1f} s; "
      f"first losses {p.first['losses']}", flush=True)
p.first = None

leaf = jax.tree_util.tree_leaves(p.state.params)[0]
start = time.perf_counter()
cycles = steps = 0
lengths = (2, 3, 4, 8, 2, 16, 3, 2)
while time.perf_counter() - start < seconds:
    faulthandler.dump_traceback_later(60, exit=True)
    k = lengths[cycles % len(lengths)]
    done, losses = [], []
    harness._drive(p, lambda now, n, k=k: n >= k - 1, done, losses)
    steps += len(done)
    # the idle gap of a window's start: a host read, sometimes a pause
    float(losses[-1])
    if cycles % 3 == 0:
        jax.device_get(jax.tree_util.tree_leaves(p.state.params)[0][:1])
    if cycles % 5 == 0:
        time.sleep(0.05)
    cycles += 1
    if cycles % 50 == 0:
        print(f"# {time.perf_counter() - start:7.1f} s: {cycles} window starts, "
              f"{steps} steps, loss {float(losses[-1]):.4f}", flush=True)
faulthandler.cancel_dump_traceback_later()
print(json.dumps({"mode": mode, "hung": False, "window_starts": cycles, "steps": steps,
                  "seconds": time.perf_counter() - start}), flush=True)
