"""horovod_tpu.fleet: policy engine, resize API, router, preemption.

The closed loop's properties, each pinned where it is cheapest to pin:

* policy math (target tracking, deadband, hysteresis, cooldown,
  clamps, schedule plans, env/HTTP-settable targets) — pure unit
  tests, injected clocks;
* ``ElasticDriver.request_world_size`` — both directions through the
  real ``_reconcile`` path with stubbed processes (no fork): grow
  spawns into free slots, shrink marks the highest slots leaving
  (epoch-boundary semantics), blacklist + preemption holds respected,
  min/max clamped, ``None`` returns to capacity tracking;
* router placement — affinity routes to the replica whose published
  block-hash index holds the prompt's prefix, least-queue fallback on
  unseen templates, the max_skew balance escape, drain-before-retire,
  scale via warm spares — over REAL engines (tiny config; the oracle
  keeps holding);
* preemption guard — a real SIGTERM in a subprocess: planned snapshot,
  ``recovery_seconds{phase="planned"}``, exit 0 (the full
  multi-process drill lives in tools/chaos_soak.py preempt/autoscale);
* chaos negative-code kill — delivers a signal instead of exiting
  (the fleet.preempt drill mechanism).

The end-to-end closed loop (2→4→2 under faults, exact counts) is the
slow-marked soak in tools/chaos_soak.py.
"""

import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time
import urllib.request

import numpy as np
import pytest

from horovod_tpu.fleet.policy import (
    SchedulePolicy, Target, TargetTrackingPolicy, histogram_quantile,
    snapshot_signals,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- policy ------------------------------------------------------------------


def test_target_ratio_orientation():
    assert Target("p99_ttft", 0.5).ratio(1.0) == 2.0
    assert Target("throughput", 100.0, invert=True).ratio(50.0) == 2.0
    assert Target("throughput", 100.0, invert=True).ratio(0.0) == float(
        "inf")
    assert Target("x", 0.0).ratio(1.0) is None


def test_policy_scales_out_on_breach_and_clamps():
    p = TargetTrackingPolicy([Target("p99_ttft", 0.5)], min_size=1,
                             max_size=4, cooldown_s=10.0)
    d = p.evaluate({"p99_ttft": 1.5}, 2, now=0.0)
    assert d.direction == "out" and d.desired == 4  # ceil(2 * 3.0) -> max
    d = p.evaluate({"p99_ttft": 0.7}, 2, now=0.0)
    assert d.direction == "out" and d.desired == 3
    # at max already: hold, not a phantom resize
    d = p.evaluate({"p99_ttft": 9.9}, 4, now=0.0)
    assert d.direction == "hold"


def test_policy_deadband_holds():
    p = TargetTrackingPolicy([Target("queue_depth", 4.0)], deadband=0.25)
    assert p.evaluate({"queue_depth": 4.9}, 2, now=0.0).direction == "hold"
    assert p.evaluate({"queue_depth": 5.1}, 2, now=0.0).direction == "out"


def test_policy_scale_in_needs_hysteresis_and_cooldown():
    p = TargetTrackingPolicy([Target("queue_depth", 4.0)], min_size=1,
                             max_size=4, hysteresis=3, cooldown_s=10.0,
                             scale_in_at=0.5)
    lo = {"queue_depth": 0.5}
    assert p.evaluate(lo, 3, now=0.0).direction == "hold"
    assert p.evaluate(lo, 3, now=1.0).direction == "hold"
    d = p.evaluate(lo, 3, now=2.0)
    assert d.direction == "in" and d.desired == 2  # one step at a time
    p.note_applied(now=2.0)
    # cooling: the streak is satisfied but the window blocks action
    assert p.evaluate(lo, 2, now=5.0).direction == "hold"
    assert p.evaluate(lo, 2, now=13.0).direction == "in"
    # a single hot sample resets the streak (chaos noise can't flap it)
    p2 = TargetTrackingPolicy([Target("queue_depth", 4.0)], hysteresis=2,
                              cooldown_s=0.0)
    p2.evaluate(lo, 3, now=0.0)
    p2.evaluate({"queue_depth": 8.0}, 3, now=1.0)  # breach resets
    assert p2.evaluate(lo, 3, now=2.0).direction == "hold"


def test_policy_min_size_floor_and_missing_signals():
    p = TargetTrackingPolicy([Target("queue_depth", 4.0)], min_size=2,
                             hysteresis=1, cooldown_s=0.0)
    assert p.evaluate({"queue_depth": 0.1}, 2, now=0.0).direction == "hold"
    assert p.evaluate({}, 2, now=0.0).reason == "no watched signals"


def test_policy_set_target_and_env(monkeypatch):
    p = TargetTrackingPolicy([Target("p99_ttft", 0.5)])
    p.set_target("p99_ttft", 0.25)
    assert p.targets()["p99_ttft"].value == 0.25
    p.set_target("throughput", 10.0, invert=True)
    assert p.targets()["throughput"].invert
    with pytest.raises(ValueError):
        p.set_target("p99_ttft", -1)
    monkeypatch.setenv("HVD_TPU_FLEET_TTFT_SLO", "0.4")
    monkeypatch.setenv("HVD_TPU_FLEET_THROUGHPUT_FLOOR", "50")
    monkeypatch.setenv("HVD_TPU_FLEET_MAX", "6")
    pe = TargetTrackingPolicy.from_env()
    assert set(pe.targets()) == {"p99_ttft", "throughput"}
    assert pe.max_size == 6 and pe.targets()["throughput"].invert


def test_schedule_policy_parse_and_evaluate():
    sp = SchedulePolicy.parse("0:2, 4:4, 8:2")
    assert sp.evaluate({}, 2, now=100.0).direction == "hold"  # t0 pinned
    d = sp.evaluate({}, 2, now=104.5)
    assert d.direction == "out" and d.desired == 4
    d = sp.evaluate({}, 4, now=109.0)
    assert d.direction == "in" and d.desired == 2
    with pytest.raises(ValueError):
        SchedulePolicy.parse("4:4,2:2")  # offsets must ascend
    with pytest.raises(ValueError):
        SchedulePolicy.parse("nope")
    with pytest.raises(ValueError):
        SchedulePolicy([])


def test_histogram_quantile_interpolates_and_clamps():
    bounds = [0.1, 0.5, 1.0]
    assert histogram_quantile(bounds, [0, 10, 0, 0], 0.5) == \
        pytest.approx(0.3)
    assert histogram_quantile(bounds, [10, 0, 0, 0], 0.99) == \
        pytest.approx(0.099)
    # overflow bucket clamps to the last bound (bounded-histogram truth)
    assert histogram_quantile(bounds, [0, 0, 0, 10], 0.99) == 1.0
    assert histogram_quantile(bounds, [0, 0, 0, 0], 0.5) == 0.0
    with pytest.raises(ValueError):
        histogram_quantile(bounds, [1, 2], 0.5)


def test_snapshot_signals_extraction():
    buckets = [0.1, 0.5, 1.0]
    snap = {"metrics": {
        "hvd_tpu_serve_queue_depth": {
            "kind": "gauge", "labelnames": ["rank"],
            "series": [[["0"], 3.0], [["1"], 5.0]]},
        "hvd_tpu_serve_token_latency_seconds": {
            "kind": "histogram", "labelnames": ["kind"],
            "buckets": buckets,
            "series": [[["first"],
                        {"buckets": [0, 10, 0, 0], "sum": 3.0,
                         "count": 10}]]},
        "hvd_tpu_serve_steps_total": {
            "kind": "counter", "labelnames": [],
            "series": [[[], 120.0]]},
    }}
    prev = {"metrics": {"hvd_tpu_serve_steps_total": {
        "kind": "counter", "labelnames": [], "series": [[[], 20.0]]}}}
    sig = snapshot_signals(snap, prev, dt=10.0)
    assert sig["queue_depth"] == 8.0
    assert sig["p99_ttft"] == pytest.approx(0.496)
    assert sig["throughput"] == pytest.approx(10.0)
    assert "step_time" not in sig  # absent metric -> absent signal


# -- the autoscaler loop -----------------------------------------------------


def test_autoscaler_tick_applies_and_respects_rejection():
    from horovod_tpu.fleet.autoscaler import Autoscaler

    applied = []
    accept = [True]
    policy = TargetTrackingPolicy([Target("queue_depth", 2.0)],
                                  max_size=8, cooldown_s=100.0)
    scaler = Autoscaler(policy, lambda n: accept[0] and applied.append(n)
                        is None, current_fn=lambda: 2,
                        signals_fn=lambda: {"queue_depth": 8.0},
                        interval_s=999, kind="train")
    d = scaler.tick(now=0.0)
    assert d.direction == "out" and applied == [8]
    # cooldown armed by the applied action: the next breach holds
    assert scaler.tick(now=1.0).direction == "hold"
    # a REJECTED apply must not burn the cooldown: retry next tick
    accept[0] = False
    scaler2 = Autoscaler(
        TargetTrackingPolicy([Target("queue_depth", 2.0)], max_size=8,
                             cooldown_s=100.0),
        lambda n: False, current_fn=lambda: 2,
        signals_fn=lambda: {"queue_depth": 8.0}, interval_s=999)
    assert scaler2.tick(now=0.0).direction == "out"
    assert scaler2.tick(now=1.0).direction == "out"  # not cooling


def test_autoscaler_does_not_respam_unconverged_target():
    """A plan target already handed to the applier is sticky there
    (request_world_size persists); while the world converges — or when
    capacity is short — the autoscaler must not re-apply and re-count
    the same decision every tick (SchedulePolicy has no cooldown, so
    the tick-level guard is the only damper)."""
    from horovod_tpu.fleet.autoscaler import Autoscaler

    applied = []
    scaler = Autoscaler(SchedulePolicy([(0.0, 4)]),
                        lambda n: applied.append(n) is None,
                        current_fn=lambda: 2, interval_s=999)
    for t in (0.0, 1.0, 2.0):  # world stuck at 2 (slots short)
        scaler.tick(now=t)
    assert applied == [4], f"re-applied an unconverged target: {applied}"


def test_maybe_training_autoscaler_from_env(monkeypatch):
    from horovod_tpu.fleet.autoscaler import maybe_training_autoscaler

    monkeypatch.delenv("HVD_TPU_FLEET_PLAN", raising=False)
    assert maybe_training_autoscaler(lambda n: n, lambda: 2, min_size=1,
                                     max_size=None) is None
    monkeypatch.setenv("HVD_TPU_FLEET_PLAN", "0:2,5:4")
    sc = maybe_training_autoscaler(lambda n: n, lambda: 2, min_size=1,
                                   max_size=4)
    assert sc is not None and isinstance(sc.policy, SchedulePolicy)
    # SLO mode without a scrape source refuses to start blind
    monkeypatch.delenv("HVD_TPU_FLEET_PLAN")
    monkeypatch.setenv("HVD_TPU_FLEET_TTFT_SLO", "0.5")
    monkeypatch.delenv("HVD_TPU_FLEET_SCRAPE", raising=False)
    assert maybe_training_autoscaler(lambda n: n, lambda: 2, min_size=1,
                                     max_size=4) is None


def test_endpoint_signal_source_and_http_targets(monkeypatch):
    """The scrape loop + HTTP-settable targets, against a REAL PR-1
    exposition server: gauges/histograms in, policy signals out, and a
    GET /control/fleet/targets?set=... retunes the live policy."""
    from horovod_tpu.fleet.autoscaler import (
        EndpointSignalSource, register_targets_endpoint,
    )
    from horovod_tpu.metrics import exposition as expo
    from horovod_tpu.metrics import instruments as instr

    # the histogram is the process's: every engine an earlier test of this
    # worker drove observed its first-token latencies into it, and a loaded
    # box puts enough of them over 0.5 s to move the first scrape's p99
    first = instr.SERVE_TOKEN_LATENCY.labels("first")
    monkeypatch.setattr(first, "_counts", [0] * len(first._counts))
    monkeypatch.setattr(first, "_sum", 0.0)
    monkeypatch.setattr(first, "_count", 0)
    instr.SERVE_QUEUE_DEPTH.set(7.0)
    instr.SERVE_TOKEN_LATENCY.labels("first").observe(0.3)
    instr.SERVE_STEPS.labels("decode").inc(5)
    srv = expo.MetricsHTTPServer(0, addr="127.0.0.1")
    try:
        url = f"http://127.0.0.1:{srv.port}"
        src = EndpointSignalSource([url], clock=iter(
            [0.0, 10.0]).__next__)
        s1 = src()
        assert s1["queue_depth"] == 7.0
        assert 0.25 <= s1["p99_ttft"] <= 0.5  # bucket-interpolated
        instr.SERVE_STEPS.labels("decode").inc(20)
        s2 = src()
        assert s2["throughput"] == pytest.approx(2.0)  # 20 steps / 10 s
        # -- HTTP-settable targets ----------------------------------
        policy = TargetTrackingPolicy([Target("p99_ttft", 0.5)])
        register_targets_endpoint(policy)
        with urllib.request.urlopen(
                url + "/control/fleet/targets?set=p99_ttft:0.125") as r:
            body = json.load(r)
        assert body["targets"]["p99_ttft"]["value"] == 0.125
        assert policy.targets()["p99_ttft"].value == 0.125
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                url + "/control/fleet/targets?set=garbage")
        assert ei.value.code == 400
    finally:
        expo.unregister_control_handler("fleet/targets")
        srv.close()
        instr.SERVE_QUEUE_DEPTH.set(0)


# -- driver resize API -------------------------------------------------------


class _FakeProc:
    def __init__(self):
        self.code = None

    def poll(self):
        return self.code


def _stub_driver(slots=4, min_np=1, max_np=None):
    from horovod_tpu.runner import elastic_driver as ed

    drv = ed.ElasticDriver(
        command=["true"], discovery=None, min_np=min_np, max_np=max_np)

    def fake_spawn(host, slot, addr):
        w = ed._Worker(drv._next_worker_id, host, slot, _FakeProc())
        drv._next_worker_id += 1
        drv._workers[w.worker_id] = w
        return w

    drv._spawn = fake_spawn
    return drv, [("localhost", slots)]


def test_request_world_size_grows_and_shrinks():
    drv, hosts = _stub_driver(slots=4, min_np=1, max_np=None)
    assert drv.request_world_size(2) == 2
    assert drv._reconcile(hosts, "addr")
    assert drv.current_world() == 2
    assert {(w.host, w.slot) for w in drv._workers.values()} == \
        {("localhost", 0), ("localhost", 1)}
    # grow: spawns into the freed slots (epoch follows via the caller)
    drv.request_world_size(4)
    assert drv._reconcile(hosts, "addr")
    assert drv.current_world() == 4
    # shrink: the HIGHEST slots get leaving marks, nobody is killed —
    # they exit through the next rendezvous's shutdown reply
    drv.request_world_size(2)
    assert drv._reconcile(hosts, "addr")
    leaving = {(w.host, w.slot) for w in drv._workers.values()
               if w.alive and w.leaving}
    assert leaving == {("localhost", 2), ("localhost", 3)}
    assert drv.current_world() == 2
    assert all(w.alive for w in drv._workers.values()), \
        "resize must not kill processes directly"
    # steady state: an already-leaving worker is not re-marked (no
    # membership-epoch spin while it walks to its shutdown reply)
    assert not drv._reconcile(hosts, "addr")


def test_request_world_size_clamps_and_resets():
    drv, hosts = _stub_driver(slots=4, min_np=2, max_np=3)
    assert drv.request_world_size(1) == 2   # min_np floor
    assert drv.request_world_size(99) == 3  # max_np ceiling
    assert drv._reconcile(hosts, "addr")
    assert drv.current_world() == 3
    # None returns to capacity tracking (all slots, still max_np-capped)
    assert drv.request_world_size(None) == -1
    assert not drv._reconcile(hosts, "addr")  # max_np 3 == current
    drv.max_np = None
    assert drv._reconcile(hosts, "addr")
    assert drv.current_world() == 4


def test_resize_respects_blacklist_and_holds():
    drv, hosts = _stub_driver(slots=4)
    drv._blacklist.add(("localhost", 0))
    drv._slot_hold[("localhost", 1)] = time.monotonic() + 60  # hold
    drv.request_world_size(4)
    drv._reconcile(hosts, "addr")
    used = {(w.host, w.slot) for w in drv._workers.values() if w.alive}
    assert used == {("localhost", 2), ("localhost", 3)}, \
        "blacklisted/held slots must never be re-filled"
    # an EXPIRED hold releases the slot back to discovery's authority
    drv._slot_hold[("localhost", 1)] = time.monotonic() - 1
    drv._reconcile(hosts, "addr")
    used = {(w.host, w.slot) for w in drv._workers.values() if w.alive}
    assert ("localhost", 1) in used


def test_leaving_exit_books_scale_down_not_completion():
    drv, hosts = _stub_driver(slots=2)
    drv.request_world_size(2)
    drv._reconcile(hosts, "addr")
    w = next(iter(drv._workers.values()))
    w.leaving = True
    w.proc.code = 0
    with drv._cv:
        any_exit, any_failure = drv._observe_exits()
    assert any_exit and not any_failure
    assert not getattr(drv, "_completing", False), \
        "a planned leave must not read as job completion"
    assert drv._leaver_exited, "survivors need a planned reset epoch"


# -- router + replicas (real engines, tiny config) ---------------------------


@pytest.fixture(scope="module")
def fleet_pieces():
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import (
        Transformer, TransformerConfig,
    )
    from horovod_tpu.serving import ServeConfig, ServingEngine

    cfg = TransformerConfig(
        vocab_size=97, num_layers=1, num_heads=2, num_kv_heads=2,
        head_dim=8, max_seq_len=48, dtype=jnp.float32,
        attention_impl="dot", causal=True)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32), train=False)["params"]
    serve = ServeConfig(block_size=8, num_blocks=0, token_budget=128,
                        watermark=2, prefill_tiers=(32,),
                        decode_tiers=(1, 2), prefill_chunk=8)

    def build():
        return ServingEngine(cfg, params, serve=serve)

    return cfg, params, build


def test_router_affinity_routes_to_cached_replica(fleet_pieces):
    from horovod_tpu.fleet.router import FleetRouter

    _cfg, _params, build = fleet_pieces
    router = FleetRouter(build, replicas=2, mode="affinity")
    rs = np.random.RandomState(0)
    template = rs.randint(1, 90, size=24).astype(np.int32)
    # first sight of the template: least-queue fallback places it
    g0 = router.submit(np.concatenate([template, [3, 4]]), 2,
                       arrival=time.perf_counter())
    first = router._placed[g0].replica
    router.run_until_drained()
    assert router.route_counts["least_queue"] == 1
    assert first.cached_prefix_blocks(template) > 0, \
        "served template not published"
    # the OTHER replica never saw it
    other = next(r for r in router.replicas if r is not first)
    assert other.cached_prefix_blocks(template) == 0
    # second request with the same template must stick to `first`
    g1 = router.submit(np.concatenate([template, [9]]), 2,
                       arrival=time.perf_counter())
    assert router._placed[g1].replica is first
    assert router.route_counts["affinity"] == 1
    router.run_until_drained()
    assert router.all_compile_free()


def test_router_max_skew_balance_escape(fleet_pieces):
    from horovod_tpu.fleet.router import FleetRouter

    _cfg, _params, build = fleet_pieces
    router = FleetRouter(build, replicas=2, mode="affinity", max_skew=2)
    rs = np.random.RandomState(1)
    template = rs.randint(1, 90, size=24).astype(np.int32)
    router.submit(np.concatenate([template, [1]]), 1,
                  arrival=time.perf_counter())
    router.run_until_drained()
    hot = max(router.replicas,
              key=lambda r: r.cached_prefix_blocks(template))
    # pile queued work onto the cache-hot replica past the skew bound
    for i in range(4):
        g = router.submit(np.concatenate([template, [i + 2]]), 1,
                          arrival=time.perf_counter())
    # the 4th submit saw hot.queue >= 3 > min queue 0 + skew 2: escape
    assert router.route_counts["least_queue"] >= 2
    cold = next(r for r in router.replicas if r is not hot)
    assert cold.engine.scheduler.queue_depth() \
        + len(cold.engine.scheduler.running) > 0, \
        "skew escape never spread the hot template"
    router.run_until_drained()


def test_router_drain_semantics_and_scale(fleet_pieces):
    from horovod_tpu.fleet.policy import Target, TargetTrackingPolicy
    from horovod_tpu.fleet.router import FleetRouter

    _cfg, _params, build = fleet_pieces
    policy = TargetTrackingPolicy([Target("queue_depth", 2.0)],
                                  min_size=1, max_size=2, hysteresis=3,
                                  cooldown_s=0.0, scale_in_at=0.5)
    router = FleetRouter(build, replicas=1, mode="affinity",
                         policy=policy, spares=1)
    assert router.size == 1 and len(router.replicas) == 2  # 1 + spare
    rs = np.random.RandomState(2)
    # flood: queue past target -> the policy unparks the warm spare
    gids = [router.submit(rs.randint(1, 90, size=10).astype(np.int32), 2,
                          arrival=time.perf_counter())
            for _ in range(8)]
    deadline = time.time() + 30
    while router.size < 2 and time.time() < deadline:
        router.step()
    assert router.size == 2, "scale-out never unparked the spare"
    assert ("out", 2) in router.scale_events
    # drain the tail: empty queues scale back in; the drained replica
    # finishes its in-flight work and retires, its results intact
    deadline = time.time() + 30
    while (router.size > 1 or any(r.state == "draining"
                                  for r in router.replicas)) \
            and time.time() < deadline:
        router.step()
    router.run_until_drained()
    assert router.size == 1
    assert len(router.retired) == 1
    assert router.retired[0].state == "retired"
    assert all(g in router.results for g in gids), \
        "a drained replica dropped in-flight work"
    assert router.all_compile_free()
    # a retired replica's surface stays safe (stats survive the engine)
    hits, lookups = router.prefix_stats()
    assert lookups >= 0 and router.all_ttfts()


def test_replica_lifecycle_guards(fleet_pieces):
    from horovod_tpu.fleet.replica import ServingReplica

    _cfg, _params, build = fleet_pieces
    r = ServingReplica("t", build)
    with pytest.raises(AttributeError):
        r.queue_depth()  # not spawned: no engine
    r.spawn(park=True)
    assert r.state == "parked" and not r.accepting
    with pytest.raises(RuntimeError, match="not accepting"):
        r.submit(np.ones((4,), np.int32), 1)
    r.unpark()
    rid = r.submit(np.arange(1, 6, dtype=np.int32), 2,
                   arrival=time.perf_counter())
    with pytest.raises(RuntimeError, match="drain before retire"):
        r.drain() or r.retire()
    while r.has_work:
        r.step()
    assert r.drained and r.healthy()
    r.retire()
    assert r.state == "retired" and r.engine is None
    assert rid in [s for s, _ in r.ttft_samples()] or r.ttft_samples()
    r.retire()  # idempotent


# -- preemption: signal-kill + the guard ------------------------------------


def test_chaos_negative_code_kill_delivers_signal():
    """kill with code=-N sends signal N to self and RETURNS — the
    drill mechanism behind fleet.preempt (spec grammar, PR 13)."""
    from horovod_tpu import chaos

    got = []
    old = signal.signal(signal.SIGUSR1,
                        lambda *_: got.append(True))
    try:
        chaos.configure(
            f"training.step:kill,at=0,code=-{signal.SIGUSR1.value}",
            seed=1)
        assert chaos.point("training.step", "payload") == "payload"
        deadline = time.time() + 5
        while not got and time.time() < deadline:
            time.sleep(0.01)
        assert got, "signal never delivered"
        assert chaos.injection_trace()[-1]["action"] == "kill"
    finally:
        chaos.clear()
        signal.signal(signal.SIGUSR1, old)


_GUARD_SCRIPT = textwrap.dedent("""
    import json, os, signal, sys, time
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np
    from horovod_tpu.elastic.state import ObjectState
    from horovod_tpu.fleet.preemption import PreemptionGuard
    from horovod_tpu.metrics import instruments as instr

    out = sys.argv[1]
    state = ObjectState(step=0, weight=np.zeros(()))
    state.enable_auto_resume(sys.argv[2], step_attr="step")

    def on_leave(info):
        info["recovery_planned_s"] = instr.RECOVERY_SECONDS.labels(
            "planned").get()
        with open(out, "w") as f:
            json.dump(info, f)

    PreemptionGuard(state, on_leave=on_leave, poll_s=10.0).install()
    for i in range(1000):
        state.weight = np.asarray(state.weight) + 1.0
        state.step = int(state.step) + 1
        state.commit()
        if state.step == 5:
            os.kill(os.getpid(), signal.SIGTERM)  # the notice
        time.sleep(0.02)
    sys.exit(3)  # the guard must have exited us long before
""")


def test_preemption_guard_sigterm_snapshot_leave(tmp_path):
    """A real SIGTERM: bounded planned snapshot, checkpoint published
    (any rank), recovery_seconds{planned} set, exit 0."""
    out = tmp_path / "leave.json"
    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-c", _GUARD_SCRIPT, str(out), str(ckpt)],
        env=env, timeout=120, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-2000:]
    info = json.loads(out.read_text())
    assert info["snapshot"] in ("live", "commit")
    assert info["step"] >= 5
    assert 0 <= info["planned_s"] < 35.0
    assert info["recovery_planned_s"] == pytest.approx(
        info["planned_s"], abs=1.0)
    # the leave published a state checkpoint a replacement can resume
    from horovod_tpu import checkpoint as ckpt_mod

    peeked = ckpt_mod.peek_state_checkpoint(str(ckpt))
    assert peeked is not None and peeked[0] >= 5


# -- replica resilience: suspect ejection + re-route (ISSUE 14) --------------


def test_router_ejects_raising_replica_and_reroutes(fleet_pieces,
                                                    monkeypatch):
    """A replica whose submit() raises is marked SUSPECT after
    HVD_TPU_FLEET_REPLICA_ERRORS consecutive errors and ejected from
    placement; its in-flight requests re-route ONCE to the least-queue
    survivor and every request still completes — a raising replica can
    no longer keep winning affinity for its cached templates."""
    from horovod_tpu.fleet.router import FleetRouter

    monkeypatch.setenv("HVD_TPU_FLEET_REPLICA_ERRORS", "2")
    _cfg, _params, build = fleet_pieces
    router = FleetRouter(build, replicas=2, mode="round_robin")
    rs = np.random.RandomState(3)
    gids = [router.submit(rs.randint(1, 90, 10).astype(np.int32), 4)
            for _ in range(4)]
    victim = router.replicas[0]
    placed_on_victim = [g for g, p in router._placed.items()
                        if p.replica is victim]
    assert placed_on_victim, "round robin should have placed on both"

    def boom(*a, **k):
        raise RuntimeError("chip on fire")

    victim.engine.submit = boom
    gids += [router.submit(rs.randint(1, 90, 10).astype(np.int32), 4)
             for _ in range(4)]
    assert victim.suspect and not victim.accepting
    # the victim's in-flight requests were re-routed exactly once
    for g in placed_on_victim:
        assert router._placed[g].rerouted
        assert router._placed[g].replica is not victim
    res = router.run_until_drained()
    assert len(res) == 8 and all(res[g].size == 4 for g in gids)
    # the suspect drained empty and retired; the survivor serves alone
    assert victim.state == "retired"
    assert router.size == 1
    assert router.all_compile_free()


def test_router_step_errors_count_toward_suspect(fleet_pieces,
                                                 monkeypatch):
    from horovod_tpu.fleet.router import FleetRouter

    monkeypatch.setenv("HVD_TPU_FLEET_REPLICA_ERRORS", "2")
    _cfg, _params, build = fleet_pieces
    router = FleetRouter(build, replicas=2, mode="round_robin")
    rs = np.random.RandomState(4)
    gids = [router.submit(rs.randint(1, 90, 10).astype(np.int32), 3)
            for _ in range(2)]
    victim = next(p.replica for p in router._placed.values())

    def boom():
        raise RuntimeError("wedged step")

    victim.engine.step = boom
    res = router.run_until_drained()
    assert victim.suspect and victim.state == "retired"
    assert len(res) == 2 and all(res[g].size == 3 for g in gids)


def test_replica_stall_trip_feeds_the_error_counter(fleet_pieces,
                                                    monkeypatch):
    """The healthz stall source (has-work-but-no-progress) drives the
    same consecutive-error counter as raises do."""
    from horovod_tpu.fleet.replica import ServingReplica

    monkeypatch.setenv("HVD_TPU_FLEET_REPLICA_STALL_SECONDS", "0.5")
    _cfg, _params, build = fleet_pieces
    t = [0.0]
    r = ServingReplica("stall", build, clock=lambda: t[0])
    r.spawn()
    r.submit(np.arange(1, 9, dtype=np.int32), 2)
    assert r.healthy()
    t[0] = 10.0  # work pending, no progress for 10s > 0.5s stall bound
    assert not r.healthy()
    assert not r.note_error() and not r.note_error()
    assert r.note_error()  # default threshold 3 -> suspect transition
    assert r.suspect
    r.engine.scheduler.pending.clear()
    r.drain()
    r.retire()


def test_note_ok_resets_consecutive_errors(fleet_pieces):
    from horovod_tpu.fleet.replica import ServingReplica

    _cfg, _params, build = fleet_pieces
    r = ServingReplica("flappy", build)
    r.spawn()
    assert not r.note_error() and not r.note_error()
    r.note_ok()  # a success breaks the run
    assert not r.note_error() and not r.note_error()
    assert not r.suspect
    assert r.note_error()
    r.engine.scheduler.pending.clear()
    r.drain()
    r.retire()


def test_router_deadline_aware_placement_skips_slow_replica(fleet_pieces):
    """A replica whose estimated queue delay exceeds the request's
    remaining deadline budget is skipped — placement onto it could
    only produce a shed."""
    from horovod_tpu.fleet.router import FleetRouter

    _cfg, _params, build = fleet_pieces
    router = FleetRouter(build, replicas=2, mode="affinity")
    slow, fast = router.replicas
    slow.avg_step_s = 10.0
    for _ in range(3):  # queue depth makes slow's estimate ~30s
        slow.engine.submit(np.arange(1, 9, dtype=np.int32), 2)
    g = router.submit(np.arange(1, 9, dtype=np.int32), 2,
                      deadline_s=1.0)
    assert router._placed[g].replica is fast
    # without a deadline the same queue state is NOT skipped on a
    # cache hit: run the template through `slow` first
    router.run_until_drained()


def test_quarantine_host_blacklists_and_kills_siblings():
    """Integrity attribution quarantines the WHOLE host: its slots
    leave the spawn pool AND its sibling workers are hard-killed —
    leaving them computing would keep re-tripping the guard until the
    survivors' rollback fuse kills the job (review finding)."""
    drv, hosts = _stub_driver(slots=2)
    hosts = [("hostA", 2), ("hostB", 1)]
    with drv._cv:
        for h, n in hosts:
            for s in range(n):
                drv._spawn(h, s, "addr")
        killed = []
        for w in drv._workers.values():
            w.proc.kill = (lambda wid=w.worker_id:
                           killed.append(wid))
        liar = next(w for w in drv._workers.values()
                    if (w.host, w.slot) == ("hostA", 0))
        drv._quarantine_host(liar.worker_id)
        assert "hostA" in drv._host_blacklist
        sibling = next(w for w in drv._workers.values()
                       if (w.host, w.slot) == ("hostA", 1))
        assert killed == [sibling.worker_id]  # hostB untouched, liar
        # exits itself
        # quarantined slots never refill; hostB's survive
        assert set(drv._desired_slots(hosts)) == {("hostB", 0)}
        # idempotent: a re-report doesn't double-kill
        drv._quarantine_host(liar.worker_id)
        assert killed == [sibling.worker_id]


def test_validation_errors_never_suspect_replicas(fleet_pieces,
                                                  monkeypatch):
    """Client-input errors (over-long prompt) re-raise to the caller
    instead of booking replica health — a few bad requests must not
    eject the whole fleet (review finding)."""
    from horovod_tpu.fleet.router import FleetRouter

    monkeypatch.setenv("HVD_TPU_FLEET_REPLICA_ERRORS", "2")
    _cfg, _params, build = fleet_pieces
    router = FleetRouter(build, replicas=2, mode="round_robin")
    too_long = np.arange(1, 200, dtype=np.int32)  # > max_seq_len 48
    for _ in range(4):
        with pytest.raises(ValueError):
            router.submit(too_long, 4)
    assert not any(r.suspect for r in router.replicas)
    assert router.size == 2
    g = router.submit(np.arange(1, 9, dtype=np.int32), 3)
    assert router.run_until_drained()[g].size == 3


def test_stalled_draining_replica_still_ejects(fleet_pieces,
                                               monkeypatch):
    """A replica already DRAINING voluntarily (scale-down) that then
    wedges must STILL get the full ejection — the old state-based
    guard made the stall response a no-op and run_until_drained spun
    forever (review finding)."""
    from horovod_tpu.fleet.router import FleetRouter

    monkeypatch.setenv("HVD_TPU_FLEET_REPLICA_ERRORS", "2")
    _cfg, _params, build = fleet_pieces
    router = FleetRouter(build, replicas=2, mode="round_robin")
    rs = np.random.RandomState(5)
    gids = [router.submit(rs.randint(1, 90, 10).astype(np.int32), 3)
            for _ in range(2)]
    victim = next(p.replica for p in router._placed.values())
    victim.drain()  # voluntary scale-down with work still in flight
    assert victim.state == "draining"

    def wedged():
        raise RuntimeError("wedged mid-drain")

    victim.engine.step = wedged
    res = router.run_until_drained()
    assert victim.suspect and victim.ejected
    assert victim.state == "retired"
    assert len(res) == 2 and all(res[g].size == 3 for g in gids)


# -- crash-surviving requests: migration, hedging, chaos (ISSUE 18) ----------


def _decode_until(router, victim, n, timeout_s=60):
    """Step the fleet until every running request on ``victim`` has
    generated >= n tokens (the mid-decode interruption point)."""
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        router.step()
        seqs = list(victim.engine.scheduler.running)
        if seqs and all(len(s.generated) >= n for s in seqs):
            return
    raise AssertionError("victim never reached the interruption point")


def test_router_recovery_token_identical_and_warm(fleet_pieces,
                                                  monkeypatch, tmp_path):
    """The tentpole oracle: kill a replica mid-decode and every one of
    its requests completes on a survivor with output bit-identical to
    an unkilled control run — the already-generated prefix emitted
    exactly once, the KV snapshot re-registered (warm path), zero
    post-warmup compiles on the recovery path, and a replica_loss
    flight bundle on disk."""
    from horovod_tpu.fleet.router import FleetRouter
    from horovod_tpu.trace import flight as _flight

    monkeypatch.setenv("HVD_TPU_FLEET_REPLICA_ERRORS", "1")
    monkeypatch.setenv("HVD_TPU_TRACE_BUNDLE_DIR", str(tmp_path))
    _flight._last_dump.clear()  # another test's dump must not gate ours
    _cfg, _params, build = fleet_pieces
    rs = np.random.RandomState(18)
    # 2 per replica: both of the victim's land IN DECODE (a 3rd would
    # sit queued — no KV yet — and correctly migrate cold)
    prompts = [rs.randint(1, 90, size=10).astype(np.int32)
               for _ in range(4)]
    ctrl = FleetRouter(build, replicas=2, mode="round_robin")
    cgids = [ctrl.submit(p, 12) for p in prompts]
    cres = ctrl.run_until_drained()
    router = FleetRouter(build, replicas=2, mode="round_robin")
    gids = [router.submit(p, 12) for p in prompts]
    victim = router.replicas[0]
    on_victim = [g for g, p in router._placed.items()
                 if p.replica is victim]
    assert on_victim, "round robin should have placed on both"
    _decode_until(router, victim, 7)  # >= 1 full block generated

    def boom():
        raise RuntimeError("chip on fire")

    victim.engine.step = boom
    res = router.run_until_drained()
    assert len(res) == 4
    for g, cg in zip(gids, cgids):
        np.testing.assert_array_equal(
            res[g], cres[cg],
            err_msg=f"gid {g} diverged from the unkilled control")
    assert router.recovery, "ejection must book recovery records"
    assert {x["path"] for x in router.recovery} == {"warm"}, \
        "mid-decode requests with full blocks must migrate warm"
    assert all(x["ms"] >= 0 for x in router.recovery)
    assert router.migration_ms() > 0
    assert victim.state == "retired"
    assert router.all_compile_free(), \
        "the recovery path must not compile on the survivor"
    bundles = [p for p in os.listdir(tmp_path)
               if p.startswith("bundle-replica_loss-")]
    assert bundles, "replica loss must dump a flight-recorder bundle"


def test_serve_migrate_corrupt_degrades_to_cold(fleet_pieces,
                                                monkeypatch):
    """Corrupt injection on the serve.migrate wire: the chain-hash
    verification rejects the snapshot and recovery DEGRADES to the
    cold path (re-prefill from tokens) — outputs stay exact, never
    wrong tokens."""
    from horovod_tpu import chaos
    from horovod_tpu.fleet.router import FleetRouter

    monkeypatch.setenv("HVD_TPU_FLEET_REPLICA_ERRORS", "1")
    _cfg, _params, build = fleet_pieces
    ref_eng = build()
    rs = np.random.RandomState(19)
    prompts = [rs.randint(1, 90, size=10).astype(np.int32)
               for _ in range(4)]
    rids = [ref_eng.submit(p, 12) for p in prompts]
    ref = ref_eng.run()
    chaos.configure("serve.migrate:corrupt,prob=1", seed=7)
    try:
        router = FleetRouter(build, replicas=2, mode="round_robin")
        gids = [router.submit(p, 12) for p in prompts]
        victim = router.replicas[0]
        _decode_until(router, victim, 7)

        def boom():
            raise RuntimeError("chip on fire")

        victim.engine.step = boom
        res = router.run_until_drained()
        fired = [t["site"] for t in chaos.injection_trace()]
    finally:
        chaos.clear()
    assert "serve.migrate" in fired
    assert router.recovery
    assert {x["path"] for x in router.recovery} == {"cold"}, \
        "a corrupt snapshot must fall back to cold re-prefill"
    for g, rid in zip(gids, rids):
        np.testing.assert_array_equal(res[g], ref[rid])


def test_ejection_preserves_arrival_order(fleet_pieces, monkeypatch):
    """Fairness satellite: requests migrated off a dead replica rejoin
    the survivor's admission queue in ORIGINAL arrival order, not at
    the tail behind later arrivals."""
    from horovod_tpu.fleet.router import FleetRouter

    monkeypatch.setenv("HVD_TPU_FLEET_REPLICA_ERRORS", "1")
    _cfg, _params, build = fleet_pieces
    router = FleetRouter(build, replicas=2, mode="round_robin")
    gids = [router.submit(np.arange(1, 9, dtype=np.int32), 2,
                          arrival=float(i)) for i in range(6)]
    victim, survivor = router.replicas

    def boom(*a, **k):
        raise RuntimeError("chip on fire")

    victim.engine.submit = boom
    gids.append(router.submit(np.arange(1, 9, dtype=np.int32), 2,
                              arrival=6.0))  # trips the ejection
    assert victim.ejected
    arrivals = [s.req.arrival for s in
                survivor.engine.scheduler.pending]
    assert arrivals == sorted(arrivals), \
        f"migrated requests broke arrival order: {arrivals}"
    assert set(arrivals) == {float(i) for i in range(7)}
    res = router.run_until_drained()
    assert len(res) == 7 and all(res[g].size == 2 for g in gids)


def test_hedged_dispatch_first_wins_and_budget(fleet_pieces,
                                               monkeypatch):
    """HVD_TPU_SERVE_HEDGE: a prefill-phase request past the sliding
    p99 TTFT gets one second dispatch; first completion wins, the
    loser cancels (blocks freed, result never raced into collection);
    HVD_TPU_SERVE_HEDGE_BUDGET=0 suppresses instead."""
    from horovod_tpu.fleet.router import FleetRouter

    monkeypatch.setenv("HVD_TPU_SERVE_HEDGE", "1")
    # the default 0.1 budget would suppress the very first hedge
    # (1 > 0.1 x 1 submitted) — that conservatism is the point of the
    # budget, but here we want a hedge to actually fly
    monkeypatch.setenv("HVD_TPU_SERVE_HEDGE_BUDGET", "1")
    _cfg, _params, build = fleet_pieces
    ref_eng = build()
    prompt = np.arange(1, 9, dtype=np.int32)
    rid = ref_eng.submit(prompt, 3)
    ref = ref_eng.run()[rid]
    t = [0.0]
    router = FleetRouter(build, replicas=2, mode="round_robin",
                         clock=lambda: t[0])
    router._ttfts.extend([0.001] * 16)  # stable p99 estimate
    g = router.submit(prompt, 3)
    primary = router._placed[g].replica
    t[0] = 1.0  # way past p99, still no first token: hedgeable
    router._maybe_hedge()
    p = router._placed[g]
    assert p.hedged and p.hedge is not None
    hedge_replica = p.hedge[0]
    assert hedge_replica is not primary
    assert router.hedge_rate() == pytest.approx(1.0)
    res = router.run_until_drained()
    np.testing.assert_array_equal(res[g], ref)
    assert router.hedges["won"] + router.hedges["lost"] == 1
    # the loser was cancelled: neither engine still holds the request
    for r in router.replicas:
        assert not r.engine.scheduler.running
        assert not r.engine.scheduler.pending
    assert router.all_compile_free()
    # budget 0: the hedge decision books as suppressed, no dispatch
    monkeypatch.setenv("HVD_TPU_SERVE_HEDGE_BUDGET", "0")
    r2 = FleetRouter(build, replicas=2, mode="round_robin",
                     clock=lambda: t[0])
    r2._ttfts.extend([0.001] * 16)
    t[0] = 2.0
    g2 = r2.submit(prompt, 3)
    t[0] = 3.0
    r2._maybe_hedge()
    assert r2._placed[g2].hedge is None and r2._placed[g2].hedged
    assert r2.hedges == {"won": 0, "lost": 0, "suppressed": 1}
    assert r2.hedge_rate() == 0.0
    np.testing.assert_array_equal(r2.run_until_drained()[g2], ref)


def test_periodic_snapshot_cadence_and_chaos_skip(fleet_pieces,
                                                  monkeypatch):
    """HVD_TPU_SERVE_SNAPSHOT_STEPS: the replica snapshots its
    in-flight KV every N steps (the warm source when a dead engine
    can't export); a chaos raise on serve.snapshot skips that beat
    without failing the step."""
    from horovod_tpu import chaos
    from horovod_tpu.fleet.replica import ServingReplica

    monkeypatch.setenv("HVD_TPU_SERVE_SNAPSHOT_STEPS", "2")
    _cfg, _params, build = fleet_pieces
    r = ServingReplica("snap", build)
    r.spawn()
    r.submit(np.arange(1, 9, dtype=np.int32), 8,
             arrival=time.perf_counter())
    r.step()
    assert not r.kv_snapshots, "cadence 2 must not snapshot on step 1"
    r.step()
    assert r.kv_snapshots, "cadence 2 must snapshot on step 2"
    rid, (tokens, _snap, _arr) = next(iter(r.kv_snapshots.items()))
    assert tokens.size >= 8
    # chaos raise on the snapshot site: the beat skips, the step lives
    chaos.configure("serve.snapshot:raise,prob=1", seed=3)
    try:
        r.kv_snapshots = {}
        r.step()
        r.step()
        assert not r.kv_snapshots, "chaos raise must skip the beat"
    finally:
        chaos.clear()
    while r.has_work:
        r.step()
    r.drain()
    r.retire()
