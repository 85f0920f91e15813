#!/usr/bin/env python
"""Chaos soak: prove end-to-end failure recovery under injected faults.

Runs real multi-process elastic training jobs (the same driver + worker
machinery as production ``tpurun``) with ``HVD_TPU_CHAOS`` injecting
faults mid-training, and asserts the jobs complete with EXACT final step
counts — lost or duplicated work is arithmetically visible in the
workers' weight bookkeeping.  Scenarios:

  kill-resume     world of 1 (+1 spare slot); chaos SIGKILLs the worker
                  at commit #K.  The driver blacklists the slot, spawns a
                  replacement, and the replacement — which has no
                  exec-restart snapshot — must auto-resume from the last
                  ``save_state_checkpoint`` and finish with exactly
                  ``batches`` steps.
  corrupt-recover world of 2; chaos flips one bit in a native negotiation
                  frame on rank 1.  The coordinator rejects the MAC, the
                  control plane dies on both ranks, ``commit()``'s
                  liveness poll raises, both workers exec-restart with
                  live snapshots, re-rendezvous, and finish exactly.
  autoscale       world of 2 with spare slots; the fleet autoscaler's
                  timed plan (HVD_TPU_FLEET_PLAN) scales 2 -> peak -> 2
                  through ElasticDriver.request_world_size while chaos
                  SIGKILLs a member mid-run; exact final counts, peak
                  reached, every exec-restart bounded.
  preempt         a chaos kill rule with code=-15 at the fleet.preempt
                  site SIGTERMs rank 1 (a preemption notice); the
                  fleet guard takes a planned snapshot, reports
                  'leaving' and exits 0; the driver books a scale-down
                  (not a failure), the survivor converges exactly, and
                  recovery_seconds{phase="planned"} stays bounded.
  sdc             silent-data-corruption closed loop (guard.py): chaos
                  flips one bit of rank 1's gradient at the guard.grad
                  site (a finite, materially wrong value no crash or
                  MAC can see).  Within one HVD_TPU_GUARD_CADENCE the
                  cross-rank digest exchange detects the mismatch, the
                  redundant-recompute vote attributes RANK 1 (not rank
                  0), rank 1 reports the integrity failure and
                  quarantines (its HOST leaves the driver's spawn
                  pool), and the survivor rolls back to the last
                  VERIFIED checkpoint — discarding the poisoned-window
                  checkpoints — then re-runs to the exact final count
                  with bounded recovery_seconds{phase="rollback"}.
  serve-recover   crash-surviving SERVING requests (docs/SERVING.md
                  fault tolerance): a 3-replica fleet router under a
                  templated request load loses one replica mid-burst
                  (chaos raise at serve.replica_step with
                  HVD_TPU_FLEET_REPLICA_ERRORS=1).  The router dumps a
                  replica_loss flight bundle, re-disperses the
                  victim's in-flight work — warm KV migration where
                  verified blocks exist, cold re-prefill otherwise —
                  and every request must complete with output
                  BIT-IDENTICAL to an unkilled control run: zero lost
                  requests, zero duplicated emissions, zero
                  post-warmup compiles on the survivors.
  replay          the same HVD_TPU_CHAOS_SEED must reproduce the same
                  injection trace, event for event.
  overhead        chaos OFF must cost one module-bool per injection point
                  (measured and printed; no flaky wall-clock assert).

Local-host note: with ``HVD_TPU_SOAK_LOCAL_SYNC=1`` the workers skip the
cross-worker state broadcast — the control plane under test
(rendezvous, native negotiation frames + MACs, heartbeats, chaos,
exec-restart, checkpoint auto-resume) is identical; only the cross-worker
state broadcast is skipped.  On a TPU fleet run without it.

Usage: python tools/chaos_soak.py [--batches N] [--seed S]
       [--serve-requests N]
       [--scenario all|kill-resume|corrupt-recover|autoscale|preempt
                  |sdc|serve-recover|replay|overhead]
Exit code 0 = every scenario passed.  Marked `slow` in the test suite
(tests/test_chaos.py wraps it); a full run is a few minutes of real
process churn.
"""

import argparse
import json
import os
import stat
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "integration", "chaos_worker.py")
if REPO not in sys.path:  # `python tools/chaos_soak.py` from anywhere
    sys.path.insert(0, REPO)


def _env(extra=None):
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    env["HVD_TPU_ELASTIC_TIMEOUT"] = "120"
    env["HVD_TPU_SOAK_LOCAL_SYNC"] = "1"
    env.update(extra or {})
    return env


def _discovery(tmp, slots, hosts_lines=None):
    hosts = os.path.join(tmp, "hosts.txt")
    with open(hosts, "w") as f:
        if hosts_lines is not None:
            f.write("".join(line + "\n" for line in hosts_lines))
        else:
            f.write(f"localhost:{slots}\n")
    script = os.path.join(tmp, "discover.sh")
    with open(script, "w") as f:
        f.write(f"#!/bin/sh\ncat {hosts}\n")
    os.chmod(script, os.stat(script).st_mode | stat.S_IEXEC)
    return script


def _read_events(logdir):
    events = []
    for name in sorted(os.listdir(logdir)):
        if not (name.startswith("worker_") and name.endswith(".log")):
            continue  # per-rank trace dumps share the directory
        with open(os.path.join(logdir, name)) as f:
            for line in f:
                ev = json.loads(line)
                ev["worker"] = name
                events.append(ev)
    return events


def _run_job(tmp, *, np_, min_np, max_np, slots, batches, chaos, seed,
             timeout=420, extra_env=None, hosts_lines=None):
    logdir = os.path.join(tmp, "logs")
    ckpt = os.path.join(tmp, "ckpt")
    os.makedirs(logdir)
    os.makedirs(ckpt)
    script = _discovery(tmp, slots, hosts_lines)
    cmd = [sys.executable, "-m", "horovod_tpu.runner",
           "--host-discovery-script", script,
           "--min-np", str(min_np), "-np", str(np_)]
    if max_np is not None:
        cmd += ["--max-np", str(max_np)]
    cmd += ["--", sys.executable, WORKER, logdir, str(batches), ckpt]
    env = _env({"HVD_TPU_CHAOS": chaos, "HVD_TPU_CHAOS_SEED": str(seed),
                **(extra_env or {})})
    proc = subprocess.run(cmd, env=env, cwd=REPO, timeout=timeout,
                          capture_output=True, text=True)
    return proc, _read_events(logdir)


def _read_bundles(bdir, reason):
    """Flight-recorder bundles of one trigger reason (may import the
    package: the soak driver already does for other scenarios)."""
    from horovod_tpu.trace.flight import read_bundle

    if not os.path.isdir(bdir):
        return []
    return [read_bundle(os.path.join(bdir, n))
            for n in sorted(os.listdir(bdir))
            if n.startswith(f"bundle-{reason}-")]


def _bundle_sites(bundle):
    return [(e["name"], (e.get("args") or {}).get("site"))
            for e in bundle["trace"]["traceEvents"]
            if e.get("ph") in ("X", "i")]


def scenario_kill_resume(batches, seed):
    """Worker killed at commit #K; the fresh replacement must resume from
    the checkpoint, not step 0, and finish exactly.  The dying worker's
    flight recorder must leave a crash bundle carrying its final spans —
    including the injected chaos event (the ISSUE-15 black-box drill)."""
    kill_at = max(3, batches // 3)
    with tempfile.TemporaryDirectory(prefix="chaos_soak_") as tmp:
        fuse = os.path.join(tmp, "kill.fuse")
        bdir = os.path.join(tmp, "bundles")
        proc, events = _run_job(
            tmp, np_=1, min_np=1, max_np=1, slots=2, batches=batches,
            chaos=f"elastic.commit:kill,at={kill_at},rank=0,fuse={fuse}",
            seed=seed,
            extra_env={"HVD_TPU_TRACE_BUNDLE_DIR": bdir},
        )
        assert proc.returncode == 0, (
            f"job failed rc={proc.returncode}\n{proc.stderr[-4000:]}")
        dones = [e for e in events if e["event"] == "done"]
        assert len(dones) == 1 and abs(dones[0]["weight"] - batches) < 1e-6, \
            f"wrong final count: {dones}"
        assert os.path.exists(fuse), "chaos kill never fired"
        workers = {e["worker"] for e in events if e["event"] == "init"}
        assert len(workers) == 2, f"no replacement spawned: {workers}"
        # the replacement had NO exec-restart snapshot: a boot at step > 0
        # can only come from checkpoint auto-resume
        done_worker = dones[0]["worker"]
        boots = [e for e in events
                 if e["event"] == "boot" and e["worker"] == done_worker]
        assert any(b["step"] >= kill_at - 1 and b["step"] > 0
                   for b in boots), \
            f"replacement did not auto-resume from checkpoint: {boots}"
        # flight recorder: the killed worker dumped its black box BEFORE
        # os._exit — final train.step spans + the chaos kill event at
        # the elastic.commit site, attributed to the dying rank
        bundles = _read_bundles(bdir, "chaos_kill")
        assert bundles, f"no chaos_kill crash bundle in {bdir}"
        b = bundles[0]
        assert b["rank"] == 0 and b["extra"]["site"] == "elastic.commit", b
        sites = _bundle_sites(b)
        assert ("chaos.inject", "elastic.commit") in sites, sites
        assert any(name == "train.step" for name, _ in sites), \
            f"bundle carries no final train.step spans: {sites}"
        return {"kill_at": kill_at, "boots": boots,
                "recovered_steps": dones[0]["step"],
                "bundle_events": len(b["trace"]["traceEvents"])}


def scenario_corrupt_recover(batches, seed):
    """One corrupted negotiation frame must fail the control plane
    cleanly on every rank, trigger exec-restart recovery, and still end
    with exact per-worker counts."""
    # enough runway that the failure push reaches every member while it
    # is still committing (recovery propagation is ~0.5 s; see
    # docs/FAULT_TOLERANCE.md on the end-of-job window)
    batches = max(batches, 40)
    with tempfile.TemporaryDirectory(prefix="chaos_soak_") as tmp:
        fuse = os.path.join(tmp, "corrupt.fuse")
        proc, events = _run_job(
            tmp, np_=2, min_np=2, max_np=2, slots=2, batches=batches,
            chaos=("transport.frame.send:corrupt,after=150,rank=1,"
                   f"times=1,fuse={fuse}"),
            seed=seed,
        )
        assert proc.returncode == 0, (
            f"job failed rc={proc.returncode}\n{proc.stderr[-4000:]}")
        dones = [e for e in events if e["event"] == "done"]
        assert len(dones) == 2, f"expected 2 finishers: {dones}"
        for d in dones:
            assert abs(d["weight"] - batches) < 1e-6, f"wrong count: {d}"
        assert os.path.exists(fuse), "frame corruption never fired"
        # both workers went through a reset epoch (exec-restart recovery)
        resets = [e for e in events if e["event"] == "reset"]
        assert resets, f"no reset epoch after the corrupted frame: {events}"
        assert "bad MAC" in proc.stderr or "chaos injecting" in \
            proc.stderr, "native chaos left no trace in stderr"
        # cross-rank trace merge (ISSUE-15): both finishers dumped their
        # span rings; the collector must align their train.step clocks
        # and produce one perfetto-loadable timeline with 2 rank lanes
        logdir = os.path.join(tmp, "logs")
        dumps = sorted(os.path.join(logdir, n) for n in os.listdir(logdir)
                       if n.startswith("trace_") and n.endswith(".json"))
        assert len(dumps) == 2, f"expected 2 per-rank trace dumps: {dumps}"
        merged_path = os.path.join(tmp, "merged_trace.json")
        subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "trace_collect.py")]
            + dumps + ["-o", merged_path],
            env=_env(), cwd=REPO, check=True, timeout=120,
            capture_output=True)
        with open(merged_path) as f:
            merged = json.load(f)
        pids = {e["pid"] for e in merged["traceEvents"]}
        assert pids == {0, 1}, f"merged trace missing a rank lane: {pids}"
        for ev in merged["traceEvents"]:
            assert "name" in ev and "ph" in ev, ev
        # step alignment: for steps BOTH ranks recorded, the shifted
        # start deltas must be centred (median ~0 by construction) and
        # bounded — the clocks really were put on one axis
        per_rank = {}
        for ev in merged["traceEvents"]:
            if ev.get("name") == "train.step" and ev.get("ph") == "X":
                step = (ev.get("args") or {}).get("step")
                per_rank.setdefault(ev["pid"], {}).setdefault(
                    step, ev["ts"])
        common = set(per_rank.get(0, {})) & set(per_rank.get(1, {}))
        assert common, "no common train.step anchors across ranks"
        deltas = sorted(abs(per_rank[0][s] - per_rank[1][s])
                        for s in common)
        median_delta_us = deltas[len(deltas) // 2]
        assert median_delta_us < 1e6, (
            f"ranks' steps not aligned after merge: median "
            f"|delta|={median_delta_us}us over {len(common)} steps")
        return {"resets": len(resets), "merged_events":
                len(merged["traceEvents"]),
                "aligned_steps": len(common),
                "median_step_delta_ms": round(median_delta_us / 1e3, 2)}


def scenario_autoscale(batches, seed, peak=4):
    """The PR-13 closed-loop scale drill (docs/FLEET.md): the driver's
    fleet autoscaler runs a timed plan 2 -> peak -> 2 through
    ``request_world_size`` while chaos SIGKILLs one member mid-run
    (blacklist + replacement).  Every resize lands as a planned reset
    epoch at a commit boundary; the final world's members must finish
    with EXACT counts (scale-up members auto-resume from the fleet
    checkpoint, never from step 0) and every exec-restart must stay
    bounded."""
    # the plan spans scale-up at 6 s and scale-down at 18 s of driver
    # time; the workers must still be training after both (plus the
    # injected kill's recovery), so the step count keys off the plan
    batches = max(batches, 560)  # ~28 s of 0.05 s steps
    with tempfile.TemporaryDirectory(prefix="chaos_soak_") as tmp:
        fuse = os.path.join(tmp, "kill.fuse")
        proc, events = _run_job(
            tmp, np_=2, min_np=2, max_np=peak, slots=peak + 1,
            batches=batches,
            chaos=f"elastic.commit:kill,after=60,rank=1,times=1,fuse={fuse}",
            seed=seed, timeout=560,
            extra_env={"HVD_TPU_FLEET_PLAN": f"0:2,6:{peak},18:2"},
        )
        assert proc.returncode == 0, (
            f"job failed rc={proc.returncode}\n{proc.stderr[-4000:]}")
        dones = [e for e in events if e["event"] == "done"]
        assert len(dones) == 2, f"expected the scaled-down world of 2 " \
            f"finishers: {dones}"
        for d in dones:
            assert abs(d["weight"] - batches) < 1e-6, f"wrong count: {d}"
            assert d["world"] == 2, f"final world not 2: {d}"
        peak_seen = max(e["world"] for e in events if e["event"] == "batch")
        assert peak_seen == peak, \
            f"world never reached the plan's peak {peak}: {peak_seen}"
        assert os.path.exists(fuse), "chaos kill never fired"
        # scale-up members had no snapshot: step > 0 at boot is the
        # checkpoint auto-resume (exact counts depend on it)
        boots = [e for e in events if e["event"] == "boot"]
        restarts = [e["restart_total_s"] for e in boots
                    if e.get("restart_total_s")]
        assert all(r < 120.0 for r in restarts), \
            f"unbounded exec-restart: {restarts}"
        return {"peak_world": peak_seen, "finishers": len(dones),
                "exec_restarts": len(restarts),
                "max_restart_s": round(max(restarts), 2) if restarts
                else None}


def scenario_preempt(batches, seed):
    """The preemption path (ISSUE 13 satellite): a chaos ``kill`` rule
    with a NEGATIVE code at the new ``fleet.preempt`` site delivers
    SIGTERM to rank 1 mid-training; the fleet guard takes a bounded
    planned snapshot (HVD_TPU_ELASTIC_PLANNED_SNAPSHOT_SECONDS),
    checkpoints it, reports 'leaving', and exits 0.  The driver books
    a scale-down (slot held, planned reset epoch — NOT a failure, NOT
    job completion), and the survivor converges to the exact count."""
    batches = max(batches, 160)  # ~8 s: the notice lands ~2.5 s in
    with tempfile.TemporaryDirectory(prefix="chaos_soak_") as tmp:
        fuse = os.path.join(tmp, "preempt.fuse")
        proc, events = _run_job(
            tmp, np_=2, min_np=1, max_np=2, slots=2, batches=batches,
            chaos=f"fleet.preempt:kill,code=-15,at=4,rank=1,fuse={fuse}",
            seed=seed,
        )
        assert proc.returncode == 0, (
            f"job failed rc={proc.returncode}\n{proc.stderr[-4000:]}")
        assert os.path.exists(fuse), "chaos preemption never fired"
        leaves = [e for e in events if e["event"] == "leave"]
        assert len(leaves) == 1, f"expected exactly one leave: {leaves}"
        leave = leaves[0]
        # bounded planned recovery: notice -> snapshot -> exit within
        # the snapshot budget (30 s default) + margin — the
        # hvd_tpu_recovery_seconds{phase="planned"} bound
        assert 0 <= leave["planned_s"] < 35.0, leave
        assert leave["snapshot"] in ("live", "commit"), leave
        assert leave["step"] > 0, f"preempted before any progress: {leave}"
        dones = [e for e in events if e["event"] == "done"]
        assert len(dones) == 1, f"expected 1 finisher: {dones}"
        assert abs(dones[0]["weight"] - batches) < 1e-6, dones
        assert dones[0]["world"] == 1, f"survivor world not 1: {dones}"
        # before the notice the world really was 2 (the leave shrank it)
        assert any(e["event"] == "batch" and e["world"] == 2
                   for e in events), "never trained at world 2"
        return {"leave_step": leave["step"],
                "planned_s": round(leave["planned_s"], 2),
                "snapshot": leave["snapshot"]}


def scenario_sdc(batches, seed, cadence=4):
    """The guard.py closed loop (docs/FAULT_TOLERANCE.md, silent
    corruption): detect -> attribute -> quarantine -> roll back ->
    converge, end to end on a real 2-worker elastic job.  The two
    workers sit on DISTINCT host names (localhost / 127.0.0.1 — both
    spawn locally) so the integrity quarantine blacklists only the
    lying rank's host."""
    flip_step = 3 * cadence - 2   # mid-window: detection must wait for
    # the NEXT cadence check, pinning the <= 1 cadence detection bound
    batches = max(batches, flip_step + 4 * cadence)
    with tempfile.TemporaryDirectory(prefix="chaos_soak_") as tmp:
        fuse = os.path.join(tmp, "sdc.fuse")
        board = os.path.join(tmp, "board")
        bdir = os.path.join(tmp, "bundles")
        proc, events = _run_job(
            tmp, np_=2, min_np=1, max_np=2, slots=2, batches=batches,
            hosts_lines=["localhost:1", "127.0.0.1:1"],
            # eval N of guard.grad is the step that becomes N+1
            chaos=(f"guard.grad:flipbit,at={flip_step - 1},rank=1,"
                   f"fuse={fuse}"),
            seed=seed,
            extra_env={"HVD_TPU_GUARD": "1",
                       "HVD_TPU_GUARD_CADENCE": str(cadence),
                       "HVD_TPU_GUARD_BOARD": board,
                       "HVD_TPU_TRACE_BUNDLE_DIR": bdir},
        )
        assert proc.returncode == 0, (
            f"job failed rc={proc.returncode}\n{proc.stderr[-4000:]}")
        assert os.path.exists(fuse), "chaos flipbit never fired"
        # detection: the first bad verdict, within one cadence of the flip
        bad = [e for e in events if e["event"] == "guard" and not e["ok"]]
        assert bad, f"corruption never detected: {events}"
        detect_step = min(e["step"] for e in bad)
        assert flip_step <= detect_step < flip_step + cadence, (
            f"detected at {detect_step}, flipped at {flip_step}, "
            f"cadence {cadence}")
        # attribution: rank 1 (not rank 0), on BOTH ranks' verdicts
        for e in bad:
            assert e["kind"] == "mismatch" and e["attributed"] == [1], e
            assert e["divergent_step"] == flip_step, e
            assert e["self_attributed"] == (e["rank"] == 1), e
        assert {e["rank"] for e in bad} == {0, 1}, bad
        # quarantine: the driver blacklisted rank 1's HOST, and no
        # replacement was ever spawned into it (2 workers total)
        assert "QUARANTINED" in proc.stderr, proc.stderr[-2000:]
        inits = [e for e in events if e["event"] == "init"]
        assert len({e["worker"] for e in inits}) == 2, inits
        # rollback: the survivor restarted WITHOUT its live state and
        # auto-resumed from the last VERIFIED checkpoint (the poisoned
        # window's checkpoints were discarded)
        verified = max(e["verified"] for e in bad if e["rank"] == 0)
        assert verified == ((flip_step - 1) // cadence) * cadence, bad
        done_rollbacks = [e for e in events
                          if e["event"] == "rollback_done"]
        assert done_rollbacks, f"no rollback accounting: {events}"
        assert all(0 <= e["rollback_s"] < 60 for e in done_rollbacks), \
            done_rollbacks
        boots = [e for e in events if e["event"] == "boot"
                 and 0 < e["step"] <= verified]
        assert boots, f"survivor did not resume from the verified " \
            f"checkpoint: {events}"
        # convergence: exactly the surviving world of 1, EXACT count
        dones = [e for e in events if e["event"] == "done"]
        assert len(dones) == 1, f"expected 1 finisher: {dones}"
        assert abs(dones[0]["weight"] - batches) < 1e-6, dones
        assert dones[0]["world"] == 1, dones
        # flight recorder: the QUARANTINED rank (1) dumped its black box
        # before exit 86 — final spans incl. the injected guard.grad
        # flipbit event and the guard exchange that convicted it
        bundles = _read_bundles(bdir, "quarantine")
        assert bundles, f"no quarantine crash bundle in {bdir}"
        qb = [b for b in bundles if b["rank"] == 1]
        assert qb, f"quarantine bundle not from rank 1: " \
            f"{[b['rank'] for b in bundles]}"
        sites = _bundle_sites(qb[0])
        assert ("chaos.inject", "guard.grad") in sites, sites
        assert any(name == "guard.exchange" for name, _ in sites), sites
        assert qb[0]["extra"]["step"] == detect_step, qb[0]["extra"]
        return {"flip_step": flip_step, "detect_step": detect_step,
                "verified_step": verified,
                "rollback_s": round(max(e["rollback_s"]
                                        for e in done_rollbacks), 2),
                "quarantine_bundle_events":
                len(qb[0]["trace"]["traceEvents"])}


def scenario_serve_recover(n_requests, seed):
    """The ISSUE-18 serving drill: kill a serving replica mid-burst and
    prove no request is lost, duplicated, or altered.  Two runs of
    tests/integration/serve_fleet_worker.py on the SAME seeded load:
    a fault-free control, then a chaotic run where the K-th
    serve.replica_step raises (one strike ejects).  The chaotic run's
    streams must be bit-identical to the control's, with >= 1 recorded
    migration, a replica_loss flight bundle, and compile-free
    survivors (recovery re-registers KV pages / re-prefills — it never
    compiles a new program)."""
    worker = os.path.join(REPO, "tests", "integration",
                          "serve_fleet_worker.py")
    # mid-burst: the victim has served ~kill_at steps of a load that is
    # still mostly in flight, so it holds running requests (warm
    # migrations) AND queued ones (cold re-dispatch)
    kill_at = max(24, n_requests // 8)
    with tempfile.TemporaryDirectory(prefix="chaos_soak_") as tmp:
        bdir = os.path.join(tmp, "bundles")
        fuse = os.path.join(tmp, "serve.fuse")
        ctl_path = os.path.join(tmp, "control.json")
        cha_path = os.path.join(tmp, "chaotic.json")
        subprocess.run(
            [sys.executable, worker, ctl_path, str(n_requests), str(seed)],
            env=_env(), cwd=REPO, check=True, timeout=900,
            capture_output=True)
        proc = subprocess.run(
            [sys.executable, worker, cha_path, str(n_requests), str(seed)],
            env=_env({
                "HVD_TPU_CHAOS":
                    f"serve.replica_step:raise,at={kill_at},fuse={fuse}",
                "HVD_TPU_CHAOS_SEED": str(seed),
                "HVD_TPU_FLEET_REPLICA_ERRORS": "1",
                "HVD_TPU_SERVE_SNAPSHOT_STEPS": "8",
                "HVD_TPU_SERVE_HEDGE": "1",
                "HVD_TPU_TRACE_BUNDLE_DIR": bdir,
            }), cwd=REPO, timeout=900, capture_output=True, text=True)
        assert proc.returncode == 0, (
            f"chaotic serve run failed rc={proc.returncode}\n"
            f"{proc.stderr[-4000:]}")
        assert os.path.exists(fuse), "chaos replica loss never fired"
        with open(ctl_path) as f:
            ctl = json.load(f)
        with open(cha_path) as f:
            cha = json.load(f)
        assert ctl["lost"] == [], f"control run lost requests: {ctl['lost']}"
        assert cha["lost"] == [], f"requests lost in recovery: {cha['lost']}"
        assert set(cha["results"]) == set(ctl["results"]), \
            "chaotic run's request ids diverged from control"
        mismatch = [g for g in ctl["results"]
                    if ctl["results"][g] != cha["results"][g]]
        assert not mismatch, (
            f"{len(mismatch)} of {n_requests} streams not bit-identical "
            f"after recovery: {mismatch[:5]}")
        assert cha["replicas_retired"] >= 1, "no replica was ejected"
        assert cha["recovery"], "ejection recorded no migrations"
        assert cha["migration_ms"] > 0, cha["migration_ms"]
        assert ctl["compile_free"] and cha["compile_free"], \
            "recovery compiled a new program post-warmup"
        # the black box: _eject dumps BEFORE touching any state
        bundles = _read_bundles(bdir, "replica_loss")
        assert bundles, f"no replica_loss flight bundle in {bdir}"
        warm = sum(1 for x in cha["recovery"] if x["path"] == "warm")
        return {"requests": n_requests, "kill_at": kill_at,
                "migrations": len(cha["recovery"]), "warm": warm,
                "cold": len(cha["recovery"]) - warm,
                "migration_ms": round(cha["migration_ms"], 2),
                "hedge_rate": round(cha["hedge_rate"], 4),
                "bundle_events":
                len(bundles[0]["trace"]["traceEvents"])}


def _replay_trace(tmp, tag, seed):
    trace = os.path.join(tmp, f"trace_{tag}.jsonl")
    code = (
        "from horovod_tpu import chaos\n"
        "chaos.install_from_env(rank=0)\n"
        "for _ in range(300):\n"
        "    chaos.point('elastic.commit')\n"
    )
    env = _env({
        "HVD_TPU_CHAOS": "elastic.commit:delay,delay=0,prob=0.1",
        "HVD_TPU_CHAOS_SEED": str(seed),
        "HVD_TPU_CHAOS_LOG": trace,
    })
    subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                   check=True, timeout=120, capture_output=True)
    with open(trace) as f:
        return [json.loads(line) for line in f]


def scenario_replay(seed):
    """Same seed => byte-identical injection trace; different seed =>
    different trace (the draws really are seed-driven)."""
    with tempfile.TemporaryDirectory(prefix="chaos_soak_") as tmp:
        a = _replay_trace(tmp, "a", seed)
        b = _replay_trace(tmp, "b", seed)
        c = _replay_trace(tmp, "c", seed + 1)
        assert a and a == b, "same seed did not replay the same trace"
        assert [e["eval"] for e in a] != [e["eval"] for e in c], \
            "different seeds produced identical traces (seed unused?)"
        return {"fires": len(a)}


def scenario_overhead():
    """Chaos off: point() must be a module-bool check.  Prints the
    measured per-call cost; asserts only the structural property."""
    from horovod_tpu import chaos

    chaos.clear()
    assert not chaos.active
    n = 1_000_000
    t0 = time.perf_counter()
    for _ in range(n):
        if chaos.active:
            chaos.point("training.step")
    per_call_ns = (time.perf_counter() - t0) / n * 1e9
    return {"inactive_point_ns": round(per_call_ns, 1)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--batches", type=int, default=24)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--scenario", default="all",
                    choices=["all", "kill-resume", "corrupt-recover",
                             "autoscale", "preempt", "sdc",
                             "serve-recover", "replay", "overhead"])
    ap.add_argument("--peak", type=int, default=4,
                    help="autoscale scenario's peak world (CI smoke: 3)")
    ap.add_argument("--serve-requests", type=int, default=512,
                    help="serve-recover scenario's request count "
                         "(CI smoke: 96)")
    args = ap.parse_args(argv)

    runs = {
        "kill-resume": lambda: scenario_kill_resume(args.batches, args.seed),
        "corrupt-recover": lambda: scenario_corrupt_recover(
            args.batches, args.seed),
        "autoscale": lambda: scenario_autoscale(args.batches, args.seed,
                                                peak=args.peak),
        "preempt": lambda: scenario_preempt(args.batches, args.seed),
        "sdc": lambda: scenario_sdc(args.batches, args.seed),
        "serve-recover": lambda: scenario_serve_recover(
            args.serve_requests, args.seed),
        "replay": lambda: scenario_replay(args.seed),
        "overhead": scenario_overhead,
    }
    selected = list(runs) if args.scenario == "all" else [args.scenario]
    failed = False
    for name in selected:
        t0 = time.time()
        try:
            detail = runs[name]()
            print(f"[chaos_soak] PASS {name} ({time.time() - t0:.1f}s) "
                  f"{json.dumps(detail)}")
        except (AssertionError, subprocess.TimeoutExpired,
                subprocess.CalledProcessError) as e:
            failed = True
            print(f"[chaos_soak] FAIL {name} ({time.time() - t0:.1f}s): {e}",
                  file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
