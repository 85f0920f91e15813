"""The disaggregated fleet's handoff (docs/FLEET.md; the oracle it rides is
tests/test_fleet_disagg.py's):

* kvsnap ``source`` tag: import rejections name the exporting replica
  (and untagged snapshots stay importable — backward compatible);
* the two-hop deadline filter: remaining-budget checks charge prefill
  queue + handoff + decode-tier delay, not one replica's queue alone;
* edge cases: decode replica dies mid-decode post-handoff (PR-18
  replica-loss recovery, watermark semantics), prefill
  retire-while-draining holds the engine until its handoffs are
  collected, hedged dispatch resolves first-handoff-wins within the
  prefill tier;
* chaos ``serve.handoff``: a corrupted wire degrades every handoff to
  the cold path — outputs stay token-identical, never wrong — and the
  handoff span reaches the flight-recorder bundle on the chaos path.
"""

import time

import numpy as np
import pytest

from tests.fleet_disagg_helpers import (  # noqa: F401  (disagg_pieces: fixture)
    _prompts, disagg_pieces,
)


# -- satellite: the kvsnap source tag ----------------------------------------


def test_kvsnap_source_tag_names_sender(disagg_pieces):
    _cfg, _params, _serve, build = disagg_pieces
    src, dst = build(role="prefill"), build()
    src.warmup()
    dst.warmup()
    src.snap_source = "prefill7"  # what ServingReplica.spawn sets
    src.submit(np.arange(1, 18, dtype=np.int32), max_new_tokens=4)
    src.run()
    (_stream, snap, _arr), = src.handoffs.values()
    assert snap["source"] == "prefill7"
    # corrupt one verified token: the chain-hash reject names the sender
    bad = dict(snap)
    bad["tokens"] = np.array(snap["tokens"], np.int32).copy()
    bad["tokens"][3] ^= 1
    with pytest.raises(ValueError, match=r"from replica prefill7"):
        dst.import_kv(bad)
    # format reject names it too
    worse = dict(snap)
    worse["format"] = "bogus/9"
    with pytest.raises(ValueError, match=r"from replica prefill7"):
        dst.import_kv(worse)
    # the clean tagged snapshot imports fine
    assert dst.import_kv(dict(snap)) == len(snap["hashes"])


def test_kvsnap_untagged_snapshot_backward_compatible(disagg_pieces):
    _cfg, _params, _serve, build = disagg_pieces
    src, dst = build(), build()
    src.warmup()
    dst.warmup()
    assert src.snap_source is None  # no replica wrapper: untagged
    rid = src.submit(np.arange(2, 19, dtype=np.int32), max_new_tokens=9)
    while not any(s.req.id == rid and s.tokens_in_cache >= 16
                  for s in src.scheduler.running):
        src.step()
    snap = src.export_requests(rids=[rid])[rid][1]
    assert snap is not None and "source" not in snap
    assert dst.import_kv(dict(snap)) == len(snap["hashes"])
    # an untagged corrupt snapshot still rejects — just anonymously
    bad = dict(snap)
    bad["tokens"] = np.array(snap["tokens"], np.int32).copy()
    bad["tokens"][0] ^= 1
    with pytest.raises(ValueError, match=r"mismatch at block 0(?!.*from "
                                         r"replica)"):
        dst.import_kv(bad)
    src.cancel(rid)


# -- satellite: the two-hop deadline filter ----------------------------------


def test_two_hop_deadline_filter(disagg_pieces):
    """A cache-hot prefill replica whose queue ALONE fits the budget
    must still be skipped when queue + handoff + decode delay does not
    — and with no handoff cost on the books, affinity wins as before."""
    from horovod_tpu.fleet.router import FleetRouter

    _cfg, _params, _serve, build = disagg_pieces
    router = FleetRouter(build, replicas=1, prefill_replicas=2)
    template = np.arange(5, 29, dtype=np.int32)
    g0 = router.submit(np.concatenate([template, [3, 4]]), 4)
    p_hot = router._placed[g0].replica
    assert p_hot.tier == "prefill"
    router.run_until_drained()
    assert p_hot.cached_prefix_blocks(template) > 0
    p_cold = next(r for r in router.replicas
                  if r.tier == "prefill" and r is not p_hot)
    # fabricate load on the hot replica: 1 queued request x 0.5 s steps
    p_hot.avg_step_s = 0.5
    p_hot.engine.submit(np.arange(40, 60, dtype=np.int32),
                        max_new_tokens=4)
    assert p_hot.est_queue_delay() >= 0.5
    # no handoff cost booked yet: queue 0.5 fits the 1.0 s budget and
    # affinity routes to the cached replica (the pre-fix behavior)
    router._handoff_ema = None
    now = time.perf_counter()
    g1 = router.submit(np.concatenate([template, [7, 8]]), 4,
                       arrival=now, deadline_s=1.0)
    assert router._placed[g1].replica is p_hot
    # 0.6 s of handoff EMA: 0.5 + 0.6 > 1.0 — the two-hop total blows
    # the budget, so the filter must exclude the hot replica even
    # though its own queue fits
    router._handoff_ema = 0.6
    g2 = router.submit(np.concatenate([template, [9, 1]]), 4,
                       arrival=time.perf_counter(), deadline_s=1.0)
    assert router._placed[g2].replica is p_cold, \
        "deadline filter ignored the handoff + decode hop"
    assert router._two_hop_overhead() == pytest.approx(0.6)
    router.run_until_drained()


# -- satellite: handoff edge cases -------------------------------------------


def test_decode_replica_death_after_handoff(disagg_pieces, monkeypatch,
                                            tmp_path):
    """A decode replica dying mid-decode falls back to the PR-18
    replica-loss recovery: its handed-off requests re-route (watermark
    prepended exactly once), outputs stay bit-identical, and the
    bundle dumped on the chaos path carries the serve.handoff span."""
    from horovod_tpu.fleet.router import FleetRouter
    from horovod_tpu.trace import flight as _flight

    monkeypatch.setenv("HVD_TPU_FLEET_REPLICA_ERRORS", "1")
    monkeypatch.setenv("HVD_TPU_TRACE_BUNDLE_DIR", str(tmp_path))
    _flight._last_dump.clear()
    _cfg, _params, _serve, build = disagg_pieces
    prompts = _prompts(23, 4)
    ref = build()
    ref.warmup()
    rids = [ref.submit(p, max_new_tokens=12) for p in prompts]
    want = ref.run()

    router = FleetRouter(build, replicas=2, prefill_replicas=1)
    gids = [router.submit(p, 12) for p in prompts]
    # run until a decode replica is actually decoding handed-off work
    victim = None
    deadline = time.time() + 60
    while time.time() < deadline:
        router.step()
        victim = next(
            (r for r in router.replicas if r.tier == "decode"
             and r.engine is not None
             and any(len(s.generated) >= 2
                     for s in r.engine.scheduler.running)), None)
        if victim is not None:
            break
    assert victim is not None, "no decode replica reached mid-decode"

    def boom():
        raise RuntimeError("injected decode-step failure")

    victim.engine.step = boom
    got = router.run_until_drained()
    for i, (r, g) in enumerate(zip(rids, gids)):
        np.testing.assert_array_equal(want[r], got[g], err_msg=f"req {i}")
    assert router.recovery, "replica loss must book a recovery"
    assert victim.state == "retired"
    assert router.all_compile_free()
    bundles = list(tmp_path.glob("bundle-replica_loss-*.json"))
    assert bundles, "no flight bundle on the chaos path"
    names = {ev.get("name") for b in bundles
             for ev in _flight.read_bundle(str(b))["trace"]["traceEvents"]}
    assert "serve.handoff" in names, \
        "handoff span missing from the flight recorder"


def test_prefill_retire_while_draining(disagg_pieces):
    """A draining prefill replica finishes its in-flight prefill,
    hands the request off, and only THEN retires — the handoff-aware
    ``drained`` gate keeps the parked snapshot alive until the router
    collects it."""
    from horovod_tpu.fleet.router import FleetRouter

    _cfg, _params, _serve, build = disagg_pieces
    prompts = _prompts(24, 2)
    ref = build()
    ref.warmup()
    rids = [ref.submit(p, max_new_tokens=8) for p in prompts]
    want = ref.run()
    router = FleetRouter(build, replicas=1, prefill_replicas=2)
    gids = [router.submit(p, 8) for p in prompts]
    pre = [r for r in router.replicas if r.tier == "prefill"]
    loaded = next(r for r in pre if r.has_work)
    loaded.drain()
    # step the ENGINE directly (not the router) so the parked handoff
    # is observable before the router's collection pass
    for _ in range(32):
        if loaded.engine.handoffs:
            break
        loaded.engine.step()
    assert loaded.engine.handoffs, "prefill never reached the boundary"
    assert not loaded.has_work
    assert not loaded.drained, \
        "a parked handoff must count as in-flight work"
    got = router.run_until_drained()
    assert loaded.state == "retired"
    for i, (r, g) in enumerate(zip(rids, gids)):
        np.testing.assert_array_equal(want[r], got[g], err_msg=f"req {i}")


def test_hedged_dispatch_within_prefill_tier(disagg_pieces, monkeypatch):
    """Hedging in a disaggregated fleet stays tier-matched (the second
    dispatch lands on the OTHER prefill replica) and resolves
    first-handoff-wins: exactly one copy crosses into the decode tier,
    the loser's parked handoff is discarded."""
    from horovod_tpu.fleet.router import FleetRouter

    monkeypatch.setenv("HVD_TPU_SERVE_HEDGE", "1")
    _cfg, _params, _serve, build = disagg_pieces
    prompt = np.arange(3, 20, dtype=np.int32)
    ref = build()
    ref.warmup()
    rid = ref.submit(prompt, max_new_tokens=6)
    want = ref.run()[rid]

    t = [100.0]
    router = FleetRouter(build, replicas=1, prefill_replicas=2,
                         clock=lambda: t[0])
    router.hedge_budget = 1.0
    router._ttfts.extend([0.001] * 16)  # a stable, tiny p99 estimate
    gid = router.submit(prompt, 6)
    p = router._placed[gid]
    t[0] += 1.0  # stalled far past p99 TTFT, still pre-first-token
    router._maybe_hedge()
    assert p.hedge is not None and p.hedge[0].tier == "prefill"
    assert p.hedge[0] is not p.replica
    got = router.run_until_drained()
    np.testing.assert_array_equal(want, got[gid])
    assert router.hedges["won"] + router.hedges["lost"] == 1
    dec = next(r for r in router.replicas if r.tier == "decode")
    assert dec.engine._next_id == 1, \
        "both hedge copies crossed the tier boundary"


def test_handoff_chaos_corrupt_degrades_cold(disagg_pieces):
    """serve.handoff corruption: every chain-hash verification fails,
    every handoff lands cold — and outputs are STILL token-identical
    (deterministic re-prefill, never wrong tokens)."""
    from horovod_tpu import chaos
    from horovod_tpu.fleet.router import FleetRouter

    _cfg, _params, _serve, build = disagg_pieces
    prompts = _prompts(25, 5)
    ref = build()
    ref.warmup()
    rids = [ref.submit(p, max_new_tokens=8) for p in prompts]
    want = ref.run()
    chaos.configure("serve.handoff:corrupt,prob=1", seed=7)
    try:
        router = FleetRouter(build, replicas=1, prefill_replicas=1)
        gids = [router.submit(p, 8) for p in prompts]
        got = router.run_until_drained()
        fired = chaos.injection_trace()
    finally:
        chaos.clear()
    for i, (r, g) in enumerate(zip(rids, gids)):
        np.testing.assert_array_equal(want[r], got[g], err_msg=f"req {i}")
    assert router.handoffs["warm"] == 0
    assert router.handoffs["cold"] == len(prompts)
    assert router.migrated_bytes == 0
    assert any(ev["site"] == "serve.handoff" for ev in fired)
