"""Serving beyond one plain engine (moved out of tests/test_serving.py,
whose oracle they ride): one model tensor-sharded over the ICI mesh
(ISSUE 12: token identity with evictions, a compile-free sharded menu,
modeled == measured per-chip reads and psum bytes from the lowering),
the pool watermark, the queue gauge and the drain gate the fleet router
reads (ISSUE 13), and request deadlines and cancellation (ISSUE 14).
"""

import numpy as np
import pytest

from horovod_tpu.metrics import instruments as _instr
from horovod_tpu.serving import (
    BlockAllocator, Request, ServeConfig, ServingEngine,
    modeled_decode_read_bytes,
)
from horovod_tpu.serving.kv_cache import PREFIX_HASH_ROOT
from tests.serving_helpers import (  # noqa: F401  (model_and_params: fixture)
    _deadline_engine, _shard_mesh, _template_prompts, _templated_load,
    model_and_params, ref_decode,
)


# -- tensor-sharded serving (ISSUE 12) ----------------------------------------


def test_modeled_decode_read_bytes_shards_pin():
    """The shards= satellite: per-chip modeled reads at shard factors
    1/2/4 equal the kernel term exactly — pages x one page's K+V bytes
    at THIS CHIP's kv-head slice x layers — and drop by the factor."""
    kw = dict(block_size=16, num_heads=8, num_kv_heads=4, head_dim=64,
              num_layers=4, dtype_bytes=2, max_seq_len=2048)
    base = modeled_decode_read_bytes(256, **kw)
    for s in (1, 2, 4):
        m = modeled_decode_read_bytes(256, shards=s, **kw)
        kernel_term = (kw["num_layers"] * m["pages_read"] * 2
                       * kw["block_size"] * (kw["num_kv_heads"] // s)
                       * kw["head_dim"] * kw["dtype_bytes"])
        assert m["paged_bytes"] == kernel_term == base["paged_bytes"] // s
        assert m["gathered_bytes"] == base["gathered_bytes"] // s
        assert m["pages_read"] == base["pages_read"], "geometry replicates"
        assert m["full_bytes"] == base["full_bytes"], "baseline unsharded"
    with pytest.raises(ValueError, match="divide"):
        modeled_decode_read_bytes(256, shards=3, **kw)


def test_env_tiers_reject_malformed(monkeypatch):
    """ServeConfig.from_env tier knobs fail at PARSE time with a clear
    ValueError — not as a confusing menu/program-key miss at warmup."""
    for bad, msg in (("1,banana", "int list"),
                     ("3,5", "powers of two"),
                     ("8,4", "ascending"),
                     ("4,4", "ascending"),
                     ("0,2", "powers of two"),
                     ("-2,4", "powers of two")):
        monkeypatch.setenv("HVD_TPU_SERVE_DECODE_TIERS", bad)
        with pytest.raises(ValueError, match=msg):
            ServeConfig.from_env()
    monkeypatch.setenv("HVD_TPU_SERVE_DECODE_TIERS", "2,8,32")
    monkeypatch.setenv("HVD_TPU_SERVE_PREFILL_TIERS", "16,64")
    got = ServeConfig.from_env()
    assert got.decode_tiers == (2, 8, 32)
    assert got.prefill_tiers == (16, 64)


def test_sharded_engine_validates(model_and_params):
    cfg, _, params = model_and_params  # num_kv_heads=2
    with pytest.raises(ValueError, match="divide"):
        ServingEngine(cfg, params, serve=ServeConfig(
            block_size=8, num_blocks=0, decode_tiers=(1, 2), shards=4),
            mesh=_shard_mesh(4))
    from horovod_tpu.parallel import tensor_shard_mesh
    with pytest.raises(ValueError, match="devices"):
        tensor_shard_mesh("tp", 99)


def test_sharded_decode_token_identical_with_evictions(model_and_params):
    """The standing oracle, sharded: prefix hits, CoW tails, chunked
    schedules AND forced LIFO evictions on a 2-shard engine emit
    token-for-token what the single-device engine emits."""
    cfg, model, params = model_and_params
    serve = dict(block_size=4, num_blocks=25, token_budget=64,
                 watermark=0, decode_tiers=(1, 2, 4), prefill_chunk=8)
    rs = np.random.RandomState(11)
    prompts = _template_prompts(rs, 4, t_len=11, s_lo=2, s_hi=5)
    outs = []
    for mesh in (None, _shard_mesh(2)):
        eng = ServingEngine(cfg, params, serve=ServeConfig(**serve),
                            mesh=mesh)
        ids = [eng.submit(p, max_new_tokens=14) for p in prompts]
        out = eng.run()
        outs.append([out[r] for r in ids])
        assert eng.scheduler.evictions > 0, "pool sized to force evictions"
        assert eng.scheduler.prefix_hit_blocks > 0, "templates must hit"
    for i, (a, b) in enumerate(zip(*outs)):
        np.testing.assert_array_equal(a, b, err_msg=f"req {i}")
        np.testing.assert_array_equal(
            a, ref_decode(model, params, prompts[i], 14),
            err_msg=f"req {i} vs no-cache reference")


def test_sharded_menu_compile_free_under_load(model_and_params):
    """Zero post-warmup compiles on the SHARDED program menu: warmup
    compiles |decode|x(|chunk|+|page|) shard_map programs, a randomized
    templated load adds no executable-cache misses, and the sharded
    psum byte counter grows per the comm model."""
    cfg, model, params = model_and_params
    eng = ServingEngine(cfg, params, serve=ServeConfig(
        block_size=8, num_blocks=0, token_budget=128, watermark=2,
        decode_tiers=(1, 2, 4), prefill_chunk=16, shards=2))
    assert eng.shards == 2
    menu = len(eng.decode_tiers) * (
        len(eng.chunk_tiers) + len(eng.page_tiers))
    warmed = eng.warmup()
    assert warmed == menu == eng.program_count
    miss0 = _instr.EXEC_CACHE.labels("miss").get()
    psum0 = _instr.SERVE_SHARD_PSUM_BYTES.get()
    rs = np.random.RandomState(12)
    templates = [rs.randint(1, 97, size=16).astype(np.int32)
                 for _ in range(2)]
    load = _templated_load(rs, 24, templates, lo=3, hi=20)
    ids = [eng.submit(p, max_new_tokens=g) for p, g in load]
    out = eng.run()
    assert eng.program_count == menu
    assert _instr.EXEC_CACHE.labels("miss").get() == miss0
    assert eng.shard_psum_bytes > 0
    assert _instr.SERVE_SHARD_PSUM_BYTES.get() - psum0 == \
        eng.shard_psum_bytes
    for i in (0, 13, 23):  # spot-check the oracle at this scale
        prompt, gen = load[i]
        np.testing.assert_array_equal(
            out[ids[i]], ref_decode(model, params, prompt, gen))


def test_sharded_models_match_lowering(model_and_params):
    """Modeled == measured per the PR-7 idiom, on the decode program
    the engine actually dispatches: the StableHLO all_reduce inventory
    equals the psum model, the rank-5 page-gather inventory equals the
    per-chip gathered-bytes model x batch tier, and BOTH drop by the
    shard factor vs the single-device lowering."""
    from horovod_tpu.ops.comm_model import (
        measured_tier_bytes, modeled_serve_psum_bytes,
        serve_gather_read_bytes,
    )

    cfg, _, params = model_and_params  # 2 kv heads, f32
    bt, pt = 2, 2
    gathered = {}
    for s in (1, 2):
        eng = ServingEngine(cfg, params, serve=ServeConfig(
            block_size=8, num_blocks=0, decode_tiers=(1, bt), shards=s))
        txt = eng.lowered_decode_text(batch_tier=bt, pages=pt)
        measured = measured_tier_bytes(txt, [0] * s)
        modeled = modeled_serve_psum_bytes(
            bt, 1, cfg.d_model, cfg.num_layers, s, "float32")
        assert measured["ici_bytes"] == modeled["stream_bytes"]
        n_psums = sum(1 for op in measured["ops"]
                      if op["op"] == "all_reduce")
        assert n_psums == modeled["psum_count"]
        m = modeled_decode_read_bytes(
            pt * 8, block_size=8, num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
            num_layers=cfg.num_layers, dtype_bytes=4,
            max_seq_len=cfg.max_seq_len, gather_pages=pt, shards=s)
        g = serve_gather_read_bytes(txt)
        assert g["gather_bytes"] == bt * m["gathered_bytes"]
        gathered[s] = g["gather_bytes"]
    assert gathered[2] == gathered[1] // 2, "per-chip reads halve"


def test_pool_watermark_defers_admission(model_and_params):
    """With a deep queue and a watermark, admission stops before the
    pool drains: running sequences keep headroom to grow."""
    cfg, _, params = model_and_params
    eng = ServingEngine(cfg, params, serve=ServeConfig(
        block_size=8, num_blocks=17, token_budget=256, watermark=6,
        decode_tiers=(1, 2, 4, 8)))
    for _ in range(8):
        eng.submit(np.ones((8,), np.int32), max_new_tokens=2)
    admitted = eng.scheduler.admit()
    # each sequence needs 2 blocks (8+1 tokens @ block 8); 16 free,
    # watermark 6 -> at most 5 admitted (16 - 5*2 = 6)
    assert 0 < len(admitted) <= 5
    assert eng.allocator.free_blocks >= 6


# -- PR 13: queue-depth honesty, the published prefix index, drain ----------


def test_queue_depth_gauge_counts_staged_rows(model_and_params):
    """The ISSUE-13 satellite pin: ``hvd_tpu_serve_queue_depth`` must
    count device-STAGED rows (attach_source's prefetcher queue), not
    just scheduler-pending ones — the fleet router's least-queue
    fallback reads the same sum (scheduler.queue_depth()), so an
    undercount would route new load onto a replica that is already
    backed up behind its staging queue."""
    import time as _time

    cfg, _, params = model_and_params
    eng = ServingEngine(cfg, params, serve=ServeConfig(
        block_size=8, num_blocks=0, token_budget=128, watermark=2,
        decode_tiers=(1, 2)))
    reqs = [Request(id=i, prompt=np.ones((8,), np.int32),
                    max_new_tokens=2) for i in range(6)]
    eng.attach_source(iter(reqs), depth=8)
    # the staging producer runs on its own thread: wait until it has
    # staged every row (meta appended at yield time, before device put)
    deadline = _time.time() + 10
    while len(eng._staging_meta) < 6 and _time.time() < deadline:
        _time.sleep(0.01)
    assert len(eng._staging_meta) == 6, "staging never filled"
    # nothing drained yet: pending==0, staged==6 — the sum is 6, on
    # both the router's read and (after a booking pass) the gauge
    assert eng.scheduler.queue_depth() == 6
    eng.scheduler._book()
    assert _instr.SERVE_QUEUE_DEPTH.get() == 6
    # draining moves rows staged -> pending -> admitted; the gauge
    # tracks the honest waiting count at every step of the way
    eng._drain_staging(block=True)
    assert eng.scheduler.queue_depth() == len(eng.scheduler.pending) \
        + len(eng._staging_meta)
    assert _instr.SERVE_QUEUE_DEPTH.get() == eng.scheduler.queue_depth()
    eng.run()
    assert _instr.SERVE_QUEUE_DEPTH.get() == 0


def test_peek_prefix_matches_match_prefix_without_side_effects():
    """peek_prefix (the router's placement probe) agrees with
    match_prefix on the match length but moves NO state: refcounts,
    LRU order and peak occupancy are untouched."""
    alloc = BlockAllocator(num_blocks=12, block_size=4)
    stream = np.arange(1, 13, dtype=np.int32)  # 3 full blocks
    blocks = alloc.alloc(3)
    parent = PREFIX_HASH_ROOT
    for i, b in enumerate(blocks):
        parent = alloc.register(b, parent, stream[i * 4:(i + 1) * 4])
    alloc.free(blocks)  # ref 0 -> parked on the LRU, still matchable
    refs_before = list(alloc._ref)
    lru_before = list(alloc._lru)
    peak_before = alloc.peak_occupancy
    assert alloc.peek_prefix(stream) == 3
    assert alloc.peek_prefix(stream, max_blocks=2) == 2
    assert alloc.peek_prefix(stream[:7]) == 1  # one full block only
    assert alloc.peek_prefix(np.flip(stream)) == 0
    assert list(alloc._ref) == refs_before, "peek bumped a refcount"
    assert list(alloc._lru) == lru_before, "peek un-parked a block"
    assert alloc.peak_occupancy == peak_before
    # the real match still works afterwards and DOES take references
    matched, _ = alloc.match_prefix(stream)
    assert len(matched) == 3 and all(alloc.ref(b) == 1 for b in matched)
    # collision safety: peek confirms content like match_prefix does
    alloc2 = BlockAllocator(num_blocks=6, block_size=4)
    alloc2.hash_fn = lambda parent, toks: 7  # every block collides
    b2 = alloc2.alloc(1)
    alloc2.register(b2[0], PREFIX_HASH_ROOT, stream[:4])
    assert alloc2.peek_prefix(stream[:4]) == 1
    assert alloc2.peek_prefix(np.flip(stream[:4]).copy()) == 0


def test_engine_drain_gate_rejects_new_intake(model_and_params):
    """accepting=False (the fleet drain hook): new submits and sources
    are rejected, in-flight work steps to completion untouched."""
    cfg, model, params = model_and_params
    eng = ServingEngine(cfg, params, serve=ServeConfig(
        block_size=8, num_blocks=0, token_budget=128, watermark=2,
        decode_tiers=(1, 2)))
    prompt = np.arange(1, 9, dtype=np.int32)
    rid = eng.submit(prompt, max_new_tokens=4)
    eng.accepting = False
    with pytest.raises(RuntimeError, match="draining"):
        eng.submit(prompt, max_new_tokens=4)
    with pytest.raises(RuntimeError, match="draining"):
        eng.attach_source(iter(()))
    out = eng.run()
    np.testing.assert_array_equal(out[rid],
                                  ref_decode(model, params, prompt, 4))


# -- request deadlines (ISSUE 14 satellite) ----------------------------------


def test_deadline_sheds_before_admission(model_and_params):
    """A request whose budget is spent while queued is shed by admit():
    its prefill would compute tokens nobody is waiting for.  The result
    entry publishes (empty) so callers never wait forever."""
    cfg, model, params = model_and_params
    t = [0.0]
    eng = _deadline_engine(cfg, params, lambda: t[0])
    before = _instr.SERVE_DEADLINE_EXCEEDED.get()
    rid = eng.submit(np.arange(1, 6), max_new_tokens=5, deadline_s=0.5)
    t[0] = 1.0
    eng.step()
    assert rid in eng.results and eng.results[rid].size == 0
    assert _instr.SERVE_DEADLINE_EXCEEDED.get() == before + 1


def test_deadline_cancels_in_flight_and_frees_blocks(model_and_params):
    """step() cancels an expired running sequence; its blocks release
    through the normal refcount path and the partial output publishes."""
    cfg, model, params = model_and_params
    t = [0.0]
    eng = _deadline_engine(cfg, params, lambda: t[0])
    free0 = eng.allocator.free_blocks
    rid = eng.submit(np.arange(1, 6), max_new_tokens=50, deadline_s=5.0)
    for _ in range(4):
        t[0] += 0.1
        eng.step()
    assert rid not in eng.results  # still generating inside budget
    t[0] = 10.0
    eng.step()
    assert rid in eng.results
    partial = eng.results[rid]
    assert 0 < partial.size < 50
    # the cancelled tokens match the reference stream prefix (greedy
    # decode: a cancellation truncates, never corrupts)
    ref = ref_decode(model, params, np.arange(1, 6), partial.size)
    np.testing.assert_array_equal(partial, ref)
    assert eng.allocator.free_blocks == free0


def test_engine_default_deadline_from_config(model_and_params):
    cfg, model, params = model_and_params
    t = [0.0]
    eng = _deadline_engine(cfg, params, lambda: t[0], deadline_s=0.25)
    rid = eng.submit(np.arange(1, 6), max_new_tokens=5)  # inherits 0.25
    t[0] = 1.0
    eng.step()
    assert rid in eng.results and eng.results[rid].size == 0
    # per-request override beats the engine default
    rid2 = eng.submit(np.arange(1, 6), max_new_tokens=5,
                      deadline_s=100.0, arrival=t[0])
    out = eng.run()
    assert out[rid2].size == 5


def test_no_deadline_requests_never_scan(model_and_params):
    """Without any deadline in play the expiry machinery stays off the
    hot path entirely (and outputs are oracle-exact, as ever)."""
    cfg, model, params = model_and_params
    eng = ServingEngine(cfg, params, serve=ServeConfig(
        block_size=8, num_blocks=0, token_budget=128, watermark=2,
        decode_tiers=(1, 2, 4)))
    assert not eng._any_deadline
    prompt = np.arange(1, 9, dtype=np.int32)
    rid = eng.submit(prompt, max_new_tokens=6)
    out = eng.run()
    assert not eng._any_deadline
    np.testing.assert_array_equal(out[rid],
                                  ref_decode(model, params, prompt, 6))


def test_deadline_expiry_mixed_with_live_requests(model_and_params):
    """Expired and live requests interleave: sheds must not disturb
    the survivors' token streams (the standing exactness oracle)."""
    cfg, model, params = model_and_params
    t = [0.0]
    eng = _deadline_engine(cfg, params, lambda: t[0])
    rs = np.random.RandomState(7)
    live_p = rs.randint(1, 97, size=9).astype(np.int32)
    dead_p = rs.randint(1, 97, size=9).astype(np.int32)
    rid_live = eng.submit(live_p, max_new_tokens=8, deadline_s=1e9)
    rid_dead = eng.submit(dead_p, max_new_tokens=8, deadline_s=0.2)
    t[0] = 0.5  # the second request expires before admission completes
    out = eng.run()
    assert out[rid_dead].size < 8
    np.testing.assert_array_equal(
        out[rid_live], ref_decode(model, params, live_p, 8))


def test_cancel_all_publishes_every_partial(model_and_params):
    """cancel_all (the fleet ejection hook) aborts running, pending AND
    device-staged requests, freeing blocks through the refcount path
    and publishing partials so no poller waits forever."""
    cfg, model, params = model_and_params
    eng = ServingEngine(cfg, params, serve=ServeConfig(
        block_size=8, num_blocks=0, token_budget=64, watermark=2,
        decode_tiers=(1, 2)))
    free0 = eng.allocator.free_blocks
    rid_run = eng.submit(np.arange(1, 9), max_new_tokens=20)
    for _ in range(3):
        eng.step()  # rid_run is mid-decode
    rid_pend = eng.submit(np.arange(2, 10), max_new_tokens=5)
    # an attached SOURCE request the router never placed: staged rows
    # must complete (empty), not hang their poller (review finding)
    eng.attach_source(iter([Request(id=500, prompt=np.arange(3, 11),
                                    max_new_tokens=4)]))
    eng._drain_staging(block=True)
    eng.cancel_all()
    assert 0 < eng.results[rid_run].size < 20
    assert rid_pend in eng.results
    assert 500 in eng.results
    assert eng.allocator.free_blocks == free0
    assert not eng.scheduler.running and not eng.scheduler.pending
    assert not eng.step()  # drained: nothing left to do


def test_sourced_requests_inherit_engine_default_deadline(model_and_params):
    """attach_source'd requests get ServeConfig.deadline_s exactly like
    submit()'s do — the open-loop intake is the path overload shedding
    exists for — and an UNSET arrival starts its clock when the request
    surfaces (a 0.0 default against a perf_counter clock would read as
    hours past budget and shed 100% of sourced traffic)."""
    cfg, model, params = model_and_params
    t = [100.0]  # a perf_counter-style clock: far from the 0.0 default
    eng = _deadline_engine(cfg, params, lambda: t[0], deadline_s=0.25)
    eng.attach_source(iter([Request(id=0, prompt=np.arange(1, 9),
                                    max_new_tokens=30)]))
    eng.step()  # drains + admits: arrival stamped 100.0, NOT shed
    assert eng._any_deadline
    assert 0 not in eng.results or eng.results[0].size > 0
    t[0] = 101.0  # now the inherited 0.25s budget is spent
    out = eng.run()
    assert out[0].size < 30  # cancelled mid-flight by the default


def test_cancel_all_stops_a_live_staging_producer(model_and_params):
    """cancel_all must CLOSE the staging prefetcher before publishing:
    a still-running producer would append more staged requests after
    the snapshot — ids that then never resolve (review finding)."""
    import itertools

    cfg, model, params = model_and_params
    eng = ServingEngine(cfg, params, serve=ServeConfig(
        block_size=8, num_blocks=0, token_budget=64, watermark=2,
        decode_tiers=(1, 2)))
    n = 12
    reqs = [Request(id=i, prompt=np.arange(1, 9), max_new_tokens=3)
            for i in range(n)]
    eng.attach_source(iter(reqs), depth=2)
    eng.step()  # let the producer spin up and stage a few
    eng.cancel_all()
    assert eng._staging.closed
    # EVERY id the staging pipeline ever surfaced has a results entry,
    # and nothing new arrives afterwards
    surfaced = set(eng.results)
    assert not eng.step()
    assert set(eng.results) == surfaced
    assert not eng._staging_meta
