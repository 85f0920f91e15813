#!/usr/bin/env python
"""Measure elastic exec-restart cost vs state size (VERDICT r3 item 3).

Runs a real elastic job (2 workers on this host), triggers a PLANNED
scale-up to 3 mid-run, then a kill -9 FAILURE recovery, and reports the
per-worker restart cost split the instrumented restart path records
(horovod_tpu/elastic/worker.py): persist (pickle → disk), reboot
(execv → wrapper re-entry: interpreter + jax import + rendezvous +
init), restore (unpickle + apply).  State size is swept via a numpy
ballast array in the elastic state.

Usage::

    python tools/elastic_restart_bench.py [--sizes 1,100,1024]  # MB

Results land in PERF.md ("Round 4: elastic restart cost").
"""

import argparse
import json
import os
import signal
import stat
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "integration", "elastic_worker.py")


def read_logs(logdir):
    events = []
    for name in os.listdir(logdir):
        with open(os.path.join(logdir, name)) as f:
            for line in f:
                ev = json.loads(line)
                ev["worker"] = name
                events.append(ev)
    return events


def wait_for(logdir, pred, timeout):
    deadline = time.time() + timeout
    while time.time() < deadline:
        evs = read_logs(logdir)
        if pred(evs):
            return evs
        time.sleep(0.5)
    raise TimeoutError("condition not reached; last events: %r" % (
        read_logs(logdir)[-5:],))


def run_one(size_bytes: int, do_kill: bool = True):
    tmp = tempfile.mkdtemp(prefix="hvd_restart_bench_")
    hosts = os.path.join(tmp, "hosts.txt")
    with open(hosts, "w") as f:
        f.write("localhost:2\n")
    script = os.path.join(tmp, "discover.sh")
    with open(script, "w") as f:
        f.write(f"#!/bin/sh\ncat {hosts}\n")
    os.chmod(script, os.stat(script).st_mode | stat.S_IEXEC)
    logdir = os.path.join(tmp, "logs")
    os.mkdir(logdir)

    env = os.environ.copy()
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "HVD_TPU_ELASTIC_TIMEOUT": "120",
    })
    env.pop("XLA_FLAGS", None)
    cmd = [sys.executable, "-m", "horovod_tpu.runner",
           "--host-discovery-script", script, "--min-np", "1",
           "--max-np", "3",
           "--", sys.executable, WORKER, logdir, "1", "400",
           str(size_bytes)]
    proc = subprocess.Popen(cmd, env=env, cwd=REPO,
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    # boot sync broadcasts the whole state; commits copy it per batch —
    # both scale with size, so the waits must too
    win = 120 + size_bytes / 10e6
    try:
        # let both workers demonstrably train, then scale up (planned)
        wait_for(logdir, lambda evs: sum(
            1 for e in evs if e["event"] == "batch" and e["batch"] >= 3
        ) >= 2, win)
        with open(hosts, "w") as f:
            f.write("localhost:3\n")
        evs = wait_for(logdir, lambda evs: any(
            e["event"] == "restart_stats" for e in evs
        ) and any(e["event"] == "batch" and e["world"] >= 2
                  and e["worker"] == "worker_2.log" for e in evs), 240 + win)
        def stat_key(e):
            return (e["worker"], e["total_s"], e["persist_s"],
                    e["reboot_s"])

        planned = [e for e in evs if e["event"] == "restart_stats"]
        killed = []
        if do_kill:
            pids = sorted({e["pid"] for e in evs if e["event"] == "init"})
            # kill the newest-init pid still alive
            for pid in reversed(pids):
                try:
                    os.kill(pid, 0)
                except OSError:
                    continue
                os.kill(pid, signal.SIGKILL)
                break
            seen = {stat_key(e) for e in planned}
            evs = wait_for(logdir, lambda evs: any(
                e["event"] == "restart_stats" and stat_key(e) not in seen
                for e in evs
            ), 180 + win)
            killed = [e for e in evs if e["event"] == "restart_stats"
                      and stat_key(e) not in seen]
        return planned, killed
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            proc.kill()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="1,100,1024",
                    help="state ballast sizes in MB, comma-separated")
    ap.add_argument("--no-kill", action="store_true")
    args = ap.parse_args()
    print(f"{'MB':>6} {'kind':>8} {'persist_s':>9} {'reboot_s':>8} "
          f"{'restore_s':>9} {'total_s':>8}")
    for mb in [float(s) for s in args.sizes.split(",")]:
        planned, killed = run_one(int(mb * 1e6), do_kill=not args.no_kill)
        for kind, stats in (("planned", planned), ("failure", killed)):
            for s in stats:
                print(f"{mb:>6.0f} {kind:>8} {s['persist_s']:>9.2f} "
                      f"{s['reboot_s']:>8.2f} {s['restore_s']:>9.2f} "
                      f"{s['total_s']:>8.2f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
