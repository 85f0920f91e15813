"""Continuous-batching serving: the batched-decode oracle + bounded
compiled-program set (ISSUE 8 acceptance), extended with the prefix
cache, copy-on-write blocks and chunked prefill (ISSUE 10).  The
sharded engine, deadlines and cancellation are tests/test_serving_sharded.py,
speculative decoding tests/test_serving_speculative.py, KV snapshots and
migration tests/test_serving_migration.py; what they share is
tests/serving_helpers.py.

The oracle (the serving exactness contract, docs/SERVING.md): greedy
decode is deterministic, so continuous batching over the paged KV
cache — whatever admission order, padding tier, eviction, block-table
reuse, PREFIX-CACHE hit or CHUNKED-prefill schedule the scheduler
lands on — must emit token-for-token what one-at-a-time full-context
decode emits, and bit-identical streams with the prefix cache on vs
off.  Any paging bug (wrong block, stale page, bad tail-block offset,
a padded slot leaking into a real row, a shared block written through)
breaks exactness immediately, which is why the oracle is the test
rather than a statistical check.

Program bounding: the padding-tier menu caps the compiled-program set
by |decode_tiers| x (|chunk_tiers| + |page_tiers| + spec·|page_tiers|)
regardless of the request distribution; the 512-request randomized
load (now with 4 shared prompt templates) pins it via the PR-1
executable-cache counters (warmup compiles the menu, traffic must be
all hits) — spec off here AND spec on in
tests/test_serving_speculative.py (ISSUE 17: per-request draft lengths
vary every step, the program keys never do).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from horovod_tpu.metrics import instruments as _instr
from horovod_tpu.models.transformer import TransformerConfig
from horovod_tpu.serving import (
    BlockAllocator, Request, ServeConfig, ServingEngine, blocks_for,
    modeled_decode_read_bytes,
)
from horovod_tpu.serving.kv_cache import PREFIX_HASH_ROOT
from tests.serving_helpers import (  # noqa: F401  (model_and_params: fixture)
    _prompts, _template_prompts, _templated_load, model_and_params,
    ref_decode,
)


# -- the batched-decode oracle ----------------------------------------------


def test_continuous_batched_decode_matches_one_at_a_time(model_and_params):
    cfg, model, params = model_and_params
    eng = ServingEngine(cfg, params, serve=ServeConfig(
        block_size=8, num_blocks=0, token_budget=128, watermark=2,
        decode_tiers=(1, 2, 4)))
    rs = np.random.RandomState(0)
    prompts = _prompts(rs, 6)
    gens = [10, 3, 7, 10, 1, 5]
    ids = [eng.submit(p, max_new_tokens=g) for p, g in zip(prompts, gens)]
    out = eng.run()
    for i, rid in enumerate(ids):
        ref = ref_decode(model, params, prompts[i], gens[i])
        np.testing.assert_array_equal(out[rid], ref, err_msg=f"req {i}")


def test_oracle_pinned_across_evictions_and_block_reuse(model_and_params):
    """A pool too small for the batch forces LIFO recompute evictions;
    freed blocks are immediately reallocated to other sequences (table
    reuse), and the evicted sequence re-prefills prompt+generated.
    Token streams must be pinned through all of it."""
    cfg, model, params = model_and_params
    # 16 allocatable blocks of 4 = 64 cache slots for 3 sequences that
    # each want prompt+18 tokens (~7 blocks): admission overcommits,
    # growth evicts
    eng = ServingEngine(cfg, params, serve=ServeConfig(
        block_size=4, num_blocks=17, token_budget=64, watermark=0,
        decode_tiers=(1, 2, 4)))
    rs = np.random.RandomState(1)
    prompts = _prompts(rs, 3, lo=10, hi=14)
    ids = [eng.submit(p, max_new_tokens=18) for p in prompts]
    out = eng.run()
    assert eng.scheduler.evictions > 0, "pool was sized to force evictions"
    for i, rid in enumerate(ids):
        ref = ref_decode(model, params, prompts[i], 18)
        np.testing.assert_array_equal(out[rid], ref, err_msg=f"req {i}")


def test_eos_stops_generation(model_and_params):
    cfg, model, params = model_and_params
    eng = ServingEngine(cfg, params, serve=ServeConfig(
        block_size=8, num_blocks=0, token_budget=128, watermark=1,
        decode_tiers=(1, 2)))
    rs = np.random.RandomState(2)
    prompt = _prompts(rs, 1)[0]
    ref = ref_decode(model, params, prompt, 16)
    eos = int(ref[4])  # stop at the 5th token the model will emit
    rid = eng.submit(prompt, max_new_tokens=16, eos_id=eos)
    out = eng.run()
    np.testing.assert_array_equal(
        out[rid], ref_decode(model, params, prompt, 16, eos_id=eos))
    assert out[rid][-1] == eos and len(out[rid]) <= 16


def test_staged_source_path_matches_submit_path(model_and_params):
    """attach_source (DevicePrefetcher staging) and direct submit are
    the same requests — same tokens out."""
    cfg, model, params = model_and_params
    rs = np.random.RandomState(3)
    prompts = _prompts(rs, 5)
    reqs = [Request(id=i, prompt=p, max_new_tokens=6)
            for i, p in enumerate(prompts)]
    eng = ServingEngine(cfg, params, serve=ServeConfig(
        block_size=8, num_blocks=0, token_budget=128, watermark=2,
        decode_tiers=(1, 2, 4)))
    eng.attach_source(iter(reqs))
    out = eng.run()
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(
            out[i], ref_decode(model, params, p, 6), err_msg=f"req {i}")


def test_submit_validates(model_and_params):
    cfg, _, params = model_and_params
    eng = ServingEngine(cfg, params, serve=ServeConfig(
        block_size=8, num_blocks=0, decode_tiers=(1, 2)))
    with pytest.raises(ValueError, match="empty"):
        eng.submit(np.zeros((0,), np.int32), max_new_tokens=4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(np.ones((4,), np.int32), max_new_tokens=0)
    with pytest.raises(ValueError, match="max_seq_len"):
        eng.submit(np.ones((60,), np.int32), max_new_tokens=10)
    with pytest.raises(ValueError, match="causal"):
        ServingEngine(
            TransformerConfig(causal=False, dtype=jnp.float32), params)


def test_oversize_prefill_tier_dropped(model_and_params):
    """A tier > max_seq_len would index block-table columns past
    max_blocks and corrupt real KV through the clamped gather — the
    engine must drop it (warning) rather than compile it."""
    cfg, _, params = model_and_params  # max_seq_len = 64
    eng = ServingEngine(cfg, params, serve=ServeConfig(
        block_size=8, num_blocks=0, prefill_tiers=(32, 100),
        decode_tiers=(1, 2)))
    assert max(eng.prefill_tiers) <= cfg.max_seq_len
    assert eng.prefill_tiers == (32, 64)


def test_sourced_id_collision_rejected(model_and_params):
    """A sourced request reusing an id already handed out by submit()
    must be rejected, not silently clobber that request's results."""
    cfg, _, params = model_and_params
    eng = ServingEngine(cfg, params, serve=ServeConfig(
        block_size=8, num_blocks=0, decode_tiers=(1, 2)))
    rid = eng.submit(np.ones((4,), np.int32), max_new_tokens=2)
    eng.attach_source(iter(
        [Request(id=rid, prompt=np.ones((4,), np.int32),
                 max_new_tokens=2)]))
    with pytest.raises(ValueError, match="already in use"):
        eng.run()


# -- bounded compiled-program set under randomized load ----------------------


def test_program_count_bounded_under_randomized_load(model_and_params):
    """512 randomized requests over 4 shared prompt templates; the tier
    menu bounds the compiled set and the PR-1 executable-cache counters
    prove steady state is all hits: warmup compiles the menu, traffic
    (prefix hits, CoW tails, chunked prefills and all) adds ZERO
    misses."""
    cfg, model, params = model_and_params
    eng = ServingEngine(cfg, params, serve=ServeConfig(
        block_size=8, num_blocks=0, token_budget=256, watermark=2,
        decode_tiers=(1, 2, 4, 8), prefill_chunk=16))
    menu = len(eng.decode_tiers) * (
        len(eng.chunk_tiers) + len(eng.page_tiers))
    warmed = eng.warmup()
    assert warmed == menu == eng.program_count
    hits0 = _instr.EXEC_CACHE.labels("hit").get()
    miss0 = _instr.EXEC_CACHE.labels("miss").get()
    rs = np.random.RandomState(4)
    templates = [rs.randint(1, 97, size=24).astype(np.int32)
                 for _ in range(4)]
    load = _templated_load(rs, 512, templates)
    for prompt, gen in load:
        eng.submit(prompt, max_new_tokens=gen)
    out = eng.run()
    assert len(out) == 512 and all(len(v) >= 1 for v in out.values())
    assert eng.program_count == menu, (
        f"{eng.program_count} programs compiled; menu bounds it to {menu}")
    assert _instr.EXEC_CACHE.labels("miss").get() == miss0
    assert _instr.EXEC_CACHE.labels("hit").get() > hits0
    # the templated load must actually exercise the prefix cache
    assert eng.scheduler.prefix_hit_blocks > 0
    # spot-check the oracle still holds at this scale
    for rid in (0, 99, 511):
        prompt, gen = load[rid]
        np.testing.assert_array_equal(
            out[rid], ref_decode(model, params, prompt, gen))


# -- allocator / kv-model units ---------------------------------------------


def test_block_allocator_contract():
    a = BlockAllocator(8, block_size=4)
    assert a.capacity == 7 and a.free_blocks == 7
    got = a.alloc(3)
    assert len(got) == 3 and 0 not in got, "block 0 is the trash block"
    assert a.alloc(5) is None, "all-or-nothing"
    assert a.free_blocks == 4
    assert a.occupancy() == pytest.approx(3 / 7)
    assert a.peak_occupancy == pytest.approx(3 / 7)
    a.free(got)
    assert a.free_blocks == 7 and a.occupancy() == 0.0
    assert a.peak_occupancy == pytest.approx(3 / 7), "peak is sticky"
    with pytest.raises(ValueError, match="double free"):
        a.free([a.alloc(1)[0]] * 2)
    with pytest.raises(ValueError, match="out of range"):
        a.free([0])
    with pytest.raises(ValueError, match=">= 2"):
        BlockAllocator(1)
    assert blocks_for(9, 4) == 3 and blocks_for(8, 4) == 2


def test_modeled_decode_read_bytes_reductions():
    """The serve_bench kv_model column: paging (vs max-seq reservation),
    GQA (vs MHA) and windowing each cut modeled decode reads."""
    kw = dict(block_size=16, num_heads=8, num_kv_heads=2, head_dim=64,
              num_layers=4, dtype_bytes=2, max_seq_len=2048)
    m = modeled_decode_read_bytes(256, **kw)
    # 256 of 2048 tokens resident, GQA 4x: >= 16x kernel-read reduction
    assert m["full_bytes"] >= 16 * m["paged_bytes"]
    assert m["pages_read"] == 16
    # the window=None gather copy is max_blocks wide (static shapes):
    # only the GQA factor survives in the gather term
    assert m["pages_gathered"] == 2048 // 16
    assert m["full_bytes"] == 4 * m["gathered_bytes"]
    w = modeled_decode_read_bytes(1024, window=128, **kw)
    nw = modeled_decode_read_bytes(1024, **kw)
    assert w["paged_bytes"] < nw["paged_bytes"] / 4, "window caps reads"
    assert w["pages_read"] <= 128 // 16 + 2
    assert w["pages_gathered"] <= 128 // 16 + 2, "window truncates gather"
    # tier-bounded gather: the live-context page tier caps the copy
    # where the pre-tier model charged the full max_blocks width
    t = modeled_decode_read_bytes(256, gather_pages=32, **kw)
    assert t["pages_gathered"] == 32 < m["pages_gathered"] == 2048 // 16
    assert t["gathered_bytes"] == 2 * t["paged_bytes"]  # 32 vs 16 pages
    # the tier can never model FEWER pages than the kernel reads
    u = modeled_decode_read_bytes(1024, gather_pages=2, **kw)
    assert u["pages_gathered"] >= u["pages_read"]


def test_decode_gather_bounded_by_live_context_tier(model_and_params):
    """The unwindowed decode gather copy is keyed by the batch's live
    max-context PAGE TIER: short contexts run the small-tier program
    and growth walks up the menu — never a max_blocks-wide copy for a
    two-page batch."""
    cfg, model, params = model_and_params
    eng = ServingEngine(cfg, params, serve=ServeConfig(
        block_size=8, num_blocks=0, token_budget=128, watermark=2,
        decode_tiers=(1, 2)))
    assert eng.page_tiers == (1, 2, 4, 8)  # 64-token max_seq, 8/block
    rid = eng.submit(np.ones((4,), np.int32), max_new_tokens=8)
    eng.run()
    decode_keys = [k for k in eng._progs if k[0] == "decode"]
    # 4+8 tokens = 12 -> at most the 2-page tier was ever gathered
    assert decode_keys and all(k[2] <= 2 for k in decode_keys), decode_keys
    np.testing.assert_array_equal(
        eng.results[rid], ref_decode(model, params, np.ones((4,)), 8))


# -- prefix cache: refcount lifecycle, CoW, collisions ------------------------


def test_allocator_refcount_lifecycle():
    """Shared blocks: match bumps refs, each holder frees once, the
    block parks on the LRU only at refcount 0; double-free (over-free
    of a shared block included) is loud; eviction never reclaims a
    block with live refs."""
    a = BlockAllocator(8, block_size=4)
    owner = a.alloc(2)
    h0 = a.register(owner[0], PREFIX_HASH_ROOT, [1, 2, 3, 4])
    m, hs = a.match_prefix([1, 2, 3, 4, 9], max_blocks=1)
    assert m == [owner[0]] and hs == [h0]
    assert a.ref(owner[0]) == 2, "matched block is SHARED"
    a.free(owner)  # first holder releases
    assert a.ref(owner[0]) == 1
    assert a.cached_blocks == 1
    # eviction never reclaims a block with refs: draining the whole
    # pool must leave the shared block alone
    rest = a.alloc(a.free_blocks)
    assert owner[0] not in rest
    assert a.ref(owner[0]) == 1, "still owned by the matcher"
    a.free(rest)
    a.free(m)  # last holder -> parks on the LRU, still cached
    assert a.ref(owner[0]) == 0 and a.cached_blocks == 1
    with pytest.raises(ValueError, match="double free"):
        a.free(m)  # over-free of the shared block
    # parked block is still matchable...
    m2, _ = a.match_prefix([1, 2, 3, 4, 9], max_blocks=1)
    assert m2 == [owner[0]]
    a.free(m2)
    # ...until a full-pool allocation reclaims it LRU-last
    every = a.alloc(7)
    assert a.cached_blocks == 0, "reclaim drops the cache entry"
    a.free(every)


def test_register_guards():
    a = BlockAllocator(8, block_size=4)
    got = a.alloc(1)
    with pytest.raises(ValueError, match="full block"):
        a.register(got[0], PREFIX_HASH_ROOT, [1, 2])  # partial tail
    a.free(got)
    with pytest.raises(ValueError, match="unreferenced"):
        a.register(got[0], PREFIX_HASH_ROOT, [1, 2, 3, 4])
    off = BlockAllocator(8, block_size=4, prefix_cache=False)
    b = off.alloc(1)
    assert off.register(b[0], PREFIX_HASH_ROOT, [1, 2, 3, 4]) is None
    assert off.match_prefix([1, 2, 3, 4, 5]) == ([], [])
    off.free(b)
    assert off.free_blocks == 7 and off.cached_blocks == 0


def test_hash_collision_safe_via_full_compare():
    """A degenerate hash function collides EVERY block; the full
    token-id + parent compare must still reject false hits."""
    a = BlockAllocator(8, block_size=4)
    a.hash_fn = lambda parent, tokens: 42  # all chains collide
    got = a.alloc(1)
    a.register(got[0], PREFIX_HASH_ROOT, [1, 2, 3, 4])
    m, _ = a.match_prefix([5, 6, 7, 8, 0], max_blocks=1)
    assert m == [], "collision must NOT match different tokens"
    m, _ = a.match_prefix([1, 2, 3, 4, 0], max_blocks=1)
    assert m == [got[0]], "identical content still matches"
    a.free(m)
    a.free(got)


def test_partial_tail_block_never_matched():
    """CoW by construction: only FULL blocks register, and the match is
    capped one block short of the prompt, so the block a new sequence
    will write into is always private (refcount 1)."""
    a = BlockAllocator(16, block_size=4)
    owner = a.alloc(3)  # 12 tokens, say 10 real: blocks 0,1 full, 2 partial
    h0 = a.register(owner[0], PREFIX_HASH_ROOT, [1, 2, 3, 4])
    a.register(owner[1], h0, [5, 6, 7, 8])
    # identical 10-token prompt: both full blocks hit, tail is private
    m, _ = a.match_prefix([1, 2, 3, 4, 5, 6, 7, 8, 9, 9],
                          max_blocks=(10 - 1) // 4)
    assert m == owner[:2]
    # a prompt EQUAL to the cached full span still computes >= 1 token:
    # the (ctx-1)//bs cap leaves the last full block unmatched
    m2, _ = a.match_prefix([1, 2, 3, 4, 5, 6, 7, 8],
                           max_blocks=(8 - 1) // 4)
    assert m2 == owner[:1]
    a.free(m)
    a.free(m2)
    a.free(owner)


# -- prefix cache + chunked prefill: engine-level oracles ---------------------


def test_prefix_cache_hits_are_token_exact(model_and_params):
    """Requests sharing a prompt template, admitted in waves so later
    waves hit the cache: hits must be > 0 and every stream must match
    the no-cache one-at-a-time reference — cached K/V is REUSED, so any
    staleness or misindexed block surfaces here."""
    cfg, model, params = model_and_params
    eng = ServingEngine(cfg, params, serve=ServeConfig(
        block_size=8, num_blocks=0, token_budget=128, watermark=2,
        decode_tiers=(1, 2), prefill_chunk=8))
    rs = np.random.RandomState(7)
    prompts = _template_prompts(rs, 6)
    ids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    out = eng.run()
    assert eng.scheduler.prefix_hit_blocks > 0, "templates must hit"
    for i, rid in enumerate(ids):
        np.testing.assert_array_equal(
            out[rid], ref_decode(model, params, prompts[i], 6),
            err_msg=f"req {i}")


def test_prefix_cache_on_off_bit_identical(model_and_params):
    """The acceptance bar: the same request stream with the prefix
    cache disabled vs enabled produces bit-identical token streams,
    while the enabled engine computes measurably fewer prefill
    tokens."""
    cfg, model, params = model_and_params
    rs = np.random.RandomState(8)
    prompts = _template_prompts(rs, 6)
    outs, computed = [], []
    for enabled in (True, False):
        eng = ServingEngine(cfg, params, serve=ServeConfig(
            block_size=8, num_blocks=0, token_budget=128, watermark=2,
            decode_tiers=(1, 2), prefill_chunk=8, prefix_cache=enabled))
        ids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        out = eng.run()
        outs.append([out[r] for r in ids])
        computed.append(eng.prefill_tokens_computed)
    for a, b in zip(outs[0], outs[1]):
        np.testing.assert_array_equal(a, b)
    assert computed[0] < computed[1], (
        "prefix hits must shrink prefill_tokens_computed")


def test_chunked_prefill_interleaves_with_decode(model_and_params):
    """A long prompt arriving while short requests decode: with
    prefill_chunk set the prompt streams in across MIXED steps (chunk
    rows packed beside decode rows) and every stream stays
    token-exact."""
    cfg, model, params = model_and_params
    eng = ServingEngine(cfg, params, serve=ServeConfig(
        block_size=8, num_blocks=0, token_budget=64, watermark=2,
        decode_tiers=(1, 2, 4), prefill_chunk=8))
    rs = np.random.RandomState(9)
    short = _prompts(rs, 2, lo=3, hi=6)
    long_p = rs.randint(1, 97, size=40).astype(np.int32)
    ids = [eng.submit(p, max_new_tokens=10) for p in short]
    ids.append(eng.submit(long_p, max_new_tokens=6))
    out = eng.run()
    # the 40-token tail at chunk 8 takes >= 5 mixed steps; decode rows
    # rode along (mixed steps outnumber the long prompt's chunks alone)
    assert eng.prefill_tokens_computed >= 40 + sum(len(p) for p in short)
    for i, (p, g) in enumerate(zip(short + [long_p], [10, 10, 6])):
        np.testing.assert_array_equal(
            out[ids[i]], ref_decode(model, params, p, g),
            err_msg=f"req {i}")


def test_eviction_readmits_through_prefix_match(model_and_params):
    """LIFO recompute eviction + prefix cache: a preempted sequence's
    published full blocks park on the LRU, and — given any pool slack —
    its re-admission goes through the same prefix match as a fresh
    request, re-mapping the surviving blocks instead of re-prefilling
    from token 0 (hits recorded AFTER the eviction), with only the
    uncached tail re-booked against the token budget.  Streams stay
    pinned through all of it.  (The zero-slack case, where reclaim eats
    the parked blocks before re-admission, is the honest fallback and is
    covered by test_oracle_pinned_across_evictions.)"""
    cfg, model, params = model_and_params
    eng = ServingEngine(cfg, params, serve=ServeConfig(
        block_size=4, num_blocks=33, token_budget=64, watermark=0,
        decode_tiers=(1, 2)))
    rs = np.random.RandomState(10)
    prompts = _prompts(rs, 2, lo=12, hi=14)
    ids = [eng.submit(p, max_new_tokens=12) for p in prompts]
    for _ in range(6):  # prefill both + a few decode steps -> published
        eng.step()
    hits_before = eng.scheduler.prefix_hit_blocks
    assert eng.scheduler._evict_one(), "LIFO preemption of the newest seq"
    out = eng.run()
    assert eng.scheduler.evictions == 1
    assert eng.scheduler.prefix_hit_blocks > hits_before, (
        "re-admission must reuse the victim's surviving cached blocks")
    for i, rid in enumerate(ids):
        np.testing.assert_array_equal(
            out[rid], ref_decode(model, params, prompts[i], 12),
            err_msg=f"req {i}")
