"""Disaggregated prefill/decode fleet (ROADMAP item 2, docs/FLEET.md).

The two-tier pipeline's properties, each pinned where it is cheapest (the
handoff's wire, deadlines and edge cases are tests/test_fleet_disagg_handoff.py,
per-tier scaling tests/test_fleet_disagg_scaling.py; what they share is
tests/fleet_disagg_helpers.py):

* engine ``role="prefill"``: warmup compiles the mixed chunk menu ONLY
  (the structural proof the tier can never run a decode step) and
  every request leaves at the handoff boundary with its first token +
  ``kvsnap/1`` chain parked for the router;
* the tentpole oracle: decode on MIGRATED blocks is bit-identical to
  decode on locally-prefilled blocks, at shards 1 and 2, with zero
  post-warmup compiles on both tiers and warm handoffs observed;
* modeled == measured: ``modeled_kvsnap_bytes`` reproduces the warm
  handoffs' measured wire bytes exactly (comm_model idiom).
"""

import numpy as np
import pytest

from horovod_tpu.metrics import instruments as _instr
from tests.fleet_disagg_helpers import (  # noqa: F401  (disagg_pieces: fixture)
    _prompts, disagg_pieces,
)


# -- engine: the prefill role ------------------------------------------------


def test_prefill_role_menu_and_handoff_boundary(disagg_pieces):
    cfg, params, serve, build = disagg_pieces
    from horovod_tpu.serving import ServingEngine

    with pytest.raises(ValueError, match="role"):
        ServingEngine(cfg, params, serve=serve, role="decode")
    eng = build(role="prefill")
    menu = len(eng.decode_tiers) * len(eng.chunk_tiers)
    assert eng.warmup() == menu == eng.program_count
    assert all(k[0] == "mixed" for k in eng._progs), \
        "prefill role must never compile a decode/spec program"
    full = build()
    assert full.warmup() > menu, "the full menu is a strict superset"

    prompt = np.arange(1, 12, dtype=np.int32)
    rid = eng.submit(prompt, max_new_tokens=6)
    out = eng.run()
    # the request LEFT at the boundary: no result, one parked handoff
    assert rid not in out and set(eng.handoffs) == {rid}
    stream, snap, _arr = eng.handoffs[rid]
    # stream = prompt + exactly the boundary (first) token
    assert stream.size == prompt.size + 1
    np.testing.assert_array_equal(stream[:prompt.size], prompt)
    assert snap is not None and len(snap["hashes"]) == 1  # 11 // 8
    assert not eng.scheduler.running and not eng.scheduler.pending
    assert eng.program_count == menu, "handoff must not compile"
    # the freed chain PARKED matchable: a repeat template still hits
    assert eng.allocator.peek_prefix(prompt, max_blocks=1) == 1


def test_prefill_role_finishes_short_requests_locally(disagg_pieces):
    """max_new_tokens=1 completes AT the boundary — no handoff, the
    result publishes on the prefill engine like any finished request."""
    _cfg, _params, _serve, build = disagg_pieces
    eng = build(role="prefill")
    eng.warmup()
    rid = eng.submit(np.arange(1, 11, dtype=np.int32), max_new_tokens=1)
    out = eng.run()
    assert rid in out and out[rid].size == 1 and not eng.handoffs


# -- the tentpole oracle -----------------------------------------------------


def test_disagg_token_identity_and_pure_roles(disagg_pieces):
    """Decode on migrated blocks == decode on local blocks, bit for
    bit, across a 1-prefill + 2-decode fleet under a templated load —
    with warm handoffs observed, both tiers compile-free, and the
    prefill tier's menu strictly smaller than the decode tier's."""
    from horovod_tpu.fleet.router import FleetRouter

    _cfg, _params, _serve, build = disagg_pieces
    prompts = _prompts(20, 10)
    ref = build()
    ref.warmup()
    rids = [ref.submit(p, max_new_tokens=12) for p in prompts]
    want = ref.run()

    router = FleetRouter(build, replicas=2, prefill_replicas=1)
    assert router.disagg
    pre = [r for r in router.replicas if r.tier == "prefill"]
    dec = [r for r in router.replicas if r.tier == "decode"]
    assert len(pre) == 1 and len(dec) == 2
    assert pre[0].engine.role == "prefill"
    assert all(k[0] == "mixed" for k in pre[0].engine._progs)
    assert pre[0].warmed_programs < dec[0].warmed_programs
    gids = [router.submit(p, 12) for p in prompts]
    got = router.run_until_drained()
    for i, (r, g) in enumerate(zip(rids, gids)):
        np.testing.assert_array_equal(want[r], got[g], err_msg=f"req {i}")
    assert router.handoffs["warm"] >= 1, "no warm handoff observed"
    assert router.handoffs["warm"] + router.handoffs["cold"] == len(
        prompts)
    assert router.all_compile_free(), "a tier compiled post-warmup"
    assert router.migrated_bytes > 0
    for rec in router.handoff_records:
        assert rec["path"] in ("warm", "cold") and rec["ms"] >= 0.0
        assert (rec["bytes"] > 0) == (rec["path"] == "warm")


def test_disagg_token_identity_sharded(disagg_pieces):
    """The oracle at shards=2: a tensor-sharded disaggregated fleet
    (every tier's pools head-sharded over 2 virtual chips) matches the
    single sharded engine — the snapshot path re-device_puts imported
    pages under the pool sharding."""
    import dataclasses as dc

    from horovod_tpu.fleet.router import FleetRouter
    from horovod_tpu.serving import ServingEngine

    cfg, params, serve, _build = disagg_pieces
    sharded = dc.replace(serve, shards=2)

    def build(role="both"):
        return ServingEngine(cfg, params, serve=sharded, role=role)

    prompts = _prompts(21, 6)
    ref = build()
    assert ref.shards == 2
    ref.warmup()
    rids = [ref.submit(p, max_new_tokens=10) for p in prompts]
    want = ref.run()
    router = FleetRouter(build, replicas=1, prefill_replicas=1)
    gids = [router.submit(p, 10) for p in prompts]
    got = router.run_until_drained()
    for i, (r, g) in enumerate(zip(rids, gids)):
        np.testing.assert_array_equal(want[r], got[g], err_msg=f"req {i}")
    assert router.handoffs["warm"] >= 1
    assert router.all_compile_free()


def test_handoff_bytes_modeled_equals_measured(disagg_pieces):
    """comm_model idiom: the modeled kvsnap wire bytes reproduce every
    warm handoff's measured bytes exactly, from the block count the
    record carries and the model config alone."""
    from horovod_tpu.fleet.router import FleetRouter
    from horovod_tpu.ops.comm_model import modeled_kvsnap_bytes

    cfg, _params, serve, build = disagg_pieces
    before = _instr.SERVE_MIGRATED_BYTES.get()
    router = FleetRouter(build, replicas=1, prefill_replicas=1)
    gids = [router.submit(p, 8) for p in _prompts(22, 6)]
    router.run_until_drained()
    assert len(router.results) == len(gids)
    warm = [r for r in router.handoff_records if r["path"] == "warm"]
    assert warm, "need at least one warm handoff to compare"
    for rec in warm:
        m = modeled_kvsnap_bytes(
            rec["blocks"], serve.block_size, cfg.num_layers,
            cfg.num_kv_heads, cfg.head_dim, "float32")
        assert rec["bytes"] == m["wire_bytes"]
    assert router.migrated_bytes == sum(r["bytes"] for r in warm)
    assert _instr.SERVE_MIGRATED_BYTES.get() - before == \
        router.migrated_bytes
