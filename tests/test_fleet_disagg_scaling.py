"""The disaggregated fleet's per-tier scaling (docs/FLEET.md): TTFT
breaches grow the prefill tier, a decode-tokens/s floor breach grows the
decode tier, independently.
"""

import pytest

from tests.fleet_disagg_helpers import (  # noqa: F401  (disagg_pieces: fixture)
    disagg_pieces,
)


# -- per-tier scaling --------------------------------------------------------


def test_per_tier_scaling_signals_drive_their_tier(disagg_pieces,
                                                   monkeypatch):
    from horovod_tpu.fleet.policy import Target, TargetTrackingPolicy
    from horovod_tpu.fleet.router import FleetRouter

    _cfg, _params, _serve, build = disagg_pieces
    mk = dict(min_size=1, max_size=3, hysteresis=1, cooldown_s=0.0)
    router = FleetRouter(
        build, replicas=1, prefill_replicas=1,
        policy=TargetTrackingPolicy([Target("p99_ttft", 0.5)], **mk),
        decode_policy=TargetTrackingPolicy(
            [Target("decode_tokens_per_s", 100.0, invert=True)], **mk))
    # TTFT breach + decode floor met: ONLY the prefill tier grows
    monkeypatch.setattr(router, "signals", lambda: {
        "p99_ttft": 1.0, "decode_tokens_per_s": 500.0})
    router._maybe_scale()
    assert router.tier_size("prefill") == 2
    assert router.tier_size("decode") == 1
    assert ("out", 2, "prefill") in router.scale_events
    grown = router.replicas[-1]
    assert grown.tier == "prefill" and grown.engine.role == "prefill"
    # decode floor breach + TTFT healthy: ONLY the decode tier grows
    monkeypatch.setattr(router, "signals", lambda: {
        "p99_ttft": 0.2, "decode_tokens_per_s": 10.0})
    router._maybe_scale()
    assert router.tier_size("decode") >= 2
    assert any(ev[2] == "decode" and ev[0] == "out"
               for ev in router.scale_events if len(ev) == 3)
    assert router.replicas[-1].engine.role == "both"


def test_decode_tokens_rate_signal(disagg_pieces):
    from horovod_tpu.fleet.router import FleetRouter

    _cfg, _params, _serve, build = disagg_pieces
    t = [50.0]
    router = FleetRouter(build, replicas=2, prefill_replicas=1,
                         clock=lambda: t[0])
    assert "decode_tokens_per_s" not in router.signals()  # baseline pin
    router._decode_tokens += 120
    t[0] += 2.0
    s = router.signals()
    # 120 tokens / 2 s / 2 accepting decode replicas
    assert s["decode_tokens_per_s"] == pytest.approx(30.0)


def test_env_knobs_arm_disagg_and_decode_policy(disagg_pieces,
                                                monkeypatch):
    from horovod_tpu.fleet.policy import decode_policy_from_env
    from horovod_tpu.fleet.router import FleetRouter

    _cfg, _params, _serve, build = disagg_pieces
    assert decode_policy_from_env() is None
    monkeypatch.setenv("HVD_TPU_FLEET_DECODE_TPS_FLOOR", "50")
    pol = decode_policy_from_env()
    t = pol.targets()["decode_tokens_per_s"]
    assert t.value == 50.0 and t.invert
    monkeypatch.setenv("HVD_TPU_FLEET_PREFILL_REPLICAS", "1")
    router = FleetRouter(build, replicas=1)
    assert router.disagg and router.decode_policy is not None
    assert router.tier_size("prefill") == 1
    assert router.tier_size("decode") == 1
    assert {r.name for r in router.replicas} == {"decode0", "prefill1"}


def test_endpoint_signal_source_decode_rate(monkeypatch):
    """The scrape-side twin of the router's in-process signal: token
    emissions (latency histogram ``_count``) rated between scrapes,
    per endpoint."""
    from horovod_tpu.fleet.autoscaler import EndpointSignalSource

    t = [10.0]
    src = EndpointSignalSource(["http://a", "http://b"],
                               clock=lambda: t[0])
    name = src.LATENCY + "_count"
    samples = [{(name, ("first",)): 100.0},
               {(name, ("first",)): 400.0}]
    monkeypatch.setattr(src, "_fetch", lambda: dict(samples.pop(0)))
    assert "decode_tokens_per_s" not in src()
    t[0] += 3.0
    out = src()
    # (400 - 100) / 3 s / 2 endpoints
    assert out["decode_tokens_per_s"] == pytest.approx(50.0)
