"""Continuous-batching serving: the batched-decode oracle + bounded
compiled-program set (ISSUE 8 acceptance), extended with the prefix
cache, copy-on-write blocks and chunked prefill (ISSUE 10).

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
all hits) — spec off AND spec on (ISSUE 17: per-request draft lengths
vary every step, the program keys never do).

Speculative decoding (ISSUE 17) rides the same oracle: greedy
accept/reject emits only verifier argmaxes, so the speculative stream
is bit-identical to the plain one — with rollback (truncate_tail) in
the loop, at shard factors 1 and 2.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.metrics import instruments as _instr
from horovod_tpu.models.transformer import Transformer, TransformerConfig
from horovod_tpu.serving import (
    BlockAllocator, ModelDrafter, PromptLookupDrafter, Request,
    ServeConfig, ServingEngine, accept_greedy, blocks_for, make_drafter,
    modeled_decode_read_bytes,
)
from horovod_tpu.serving.kv_cache import PREFIX_HASH_ROOT


@pytest.fixture(scope="module")
def model_and_params():
    cfg = TransformerConfig(
        vocab_size=97, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=8, max_seq_len=64, dtype=jnp.float32,
        attention_impl="dot", causal=True)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32), train=False)["params"]
    return cfg, model, params


def ref_decode(model, params, prompt, n, eos_id=None):
    """One-at-a-time full-context greedy decode (no cache at all)."""
    toks = list(np.asarray(prompt))
    out = []
    for _ in range(n):
        x = jnp.asarray(np.asarray(toks, np.int32))[None]
        logits = model.apply({"params": params}, x, train=False)
        t = int(jnp.argmax(logits[0, -1].astype(jnp.float32)))
        toks.append(t)
        out.append(t)
        if eos_id is not None and t == eos_id:
            break
    return np.asarray(out, np.int32)


def _prompts(rs, n, lo=3, hi=20):
    return [rs.randint(1, 97, size=rs.randint(lo, hi)).astype(np.int32)
            for _ in range(n)]


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


def _templated_load(rs, n, templates, lo=3, hi=41):
    """Randomized load where ~half the prompts start with one of the
    shared templates — the dominant production shape (shared system
    prompts / few-shot headers) the prefix cache exists for."""
    load = []
    for _ in range(n):
        suffix = rs.randint(1, 97, size=rs.randint(lo, hi)).astype(np.int32)
        if rs.random_sample() < 0.5:
            t = templates[rs.randint(len(templates))]
            prompt = np.concatenate([t, suffix])[:57]  # < max_seq_len-gen
        else:
            prompt = suffix
        load.append((prompt, int(rs.randint(1, 7))))
    return load


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


def _template_prompts(rs, n, t_len=19, s_lo=2, s_hi=6):
    template = rs.randint(1, 97, size=t_len).astype(np.int32)
    return [np.concatenate([
        template, rs.randint(1, 97, size=rs.randint(s_lo, s_hi))
        .astype(np.int32)]) for _ in range(n)]


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


# -- tensor-sharded serving (ISSUE 12) ----------------------------------------


def _shard_mesh(n):
    from horovod_tpu.parallel import tensor_shard_mesh

    return tensor_shard_mesh("tp", n)


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


def _deadline_engine(cfg, params, clock, **kw):
    serve = ServeConfig(block_size=8, num_blocks=0, token_budget=128,
                        watermark=2, decode_tiers=(1, 2, 4), **kw)
    return ServingEngine(cfg, params, serve=serve, clock=clock)


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


# -- speculative decoding (ISSUE 17) -----------------------------------------


def test_truncate_tail_contract():
    """The rollback primitive: releases exactly the blocks past what
    keep_tokens occupies, no-ops when nothing extends past it, and the
    trash block 0 is as untouchable here as through free()."""
    a = BlockAllocator(10, block_size=4)
    table = a.alloc(3)  # covers up to 12 tokens
    assert a.truncate_tail(table, 5) == table[:2]  # 5 tokens -> 2 blocks
    assert a.free_blocks == 7
    assert a.truncate_tail(table[:2], 8) == table[:2], "exact fit no-ops"
    assert a.truncate_tail(table[:2], 9) == table[:2], \
        "keep past the table never allocates"
    assert a.truncate_tail(table[:2], 0) == []
    assert a.free_blocks == 9
    assert a.truncate_tail([], 0) == []
    with pytest.raises(ValueError, match="out of range"):
        a.truncate_tail([0], 0)  # the trash block guard


def test_truncate_tail_shared_tail_never_double_frees():
    """The CoW edge the rollback rides on: a speculative tail that
    lands in a PREFIX-REGISTERED shared block must drop this table's
    reference only — the block stays live under the other holder, and
    nothing ever reaches the free list while a ref survives."""
    a = BlockAllocator(10, block_size=4)
    owner = a.alloc(2)
    h = a.register(owner[0], PREFIX_HASH_ROOT, [1, 2, 3, 4])
    m, hs = a.match_prefix([1, 2, 3, 4, 9], max_blocks=1)
    assert m == [owner[0]] and hs == [h]
    sharer = m + a.alloc(1)  # shared prefix block + an owned tail
    free0 = a.free_blocks
    # rollback past the owned tail INTO the shared block's extent:
    # keep 4 tokens = the shared block only
    sharer = a.truncate_tail(sharer, 4)
    assert sharer == [owner[0]]
    assert a.free_blocks == free0 + 1, "only the owned tail released"
    assert a.ref(owner[0]) == 2, "shared block untouched"
    # roll the shared block off this table too: ref drops, block lives
    assert a.truncate_tail(sharer, 0) == []
    assert a.ref(owner[0]) == 1, "owner's ref survives the rollback"
    assert a.cached_blocks == 1, "still indexed for future prefix hits"
    a.free(owner)  # the real owner's release still works (no double free)
    assert a.ref(owner[0]) == 0 and a.cached_blocks == 1
    # only now, at refcount 0, may a full-pool allocation reclaim it
    every = a.alloc(a.capacity)
    assert every is not None and a.cached_blocks == 0
    a.free(every)


def test_prompt_lookup_drafter():
    """N-gram lookup over the sequence's own history: longest trailing
    n-gram wins, the most recent FULL-k-continuation occurrence wins
    (most recent of any as fallback), drafts cap at k, and no match
    (or a degenerate stream) drafts nothing."""
    d = PromptLookupDrafter(max_ngram=3, min_ngram=1)
    # trailing [1,2,3] recurs at the start; what followed it is drafted
    assert d.draft([1, 2, 3, 9, 8, 1, 2, 3], 2) == [9, 8]
    assert d.draft([1, 2, 3, 9, 8, 1, 2, 3], 5) == [9, 8, 1, 2, 3]
    # recency: trailing [1,2] matches at i=0 (-> 5) and i=3 (-> 7);
    # both have k of headroom, the most recent occurrence wins
    assert d.draft([1, 2, 5, 1, 2, 7, 1, 2], 1) == [7]
    # headroom beats recency: the recent match (-> [9,1,2]) can't fill
    # k=4, so the older full-length continuation is the draft
    assert d.draft([1, 2, 8, 8, 8, 1, 2, 9, 1, 2], 2) == [9, 1]
    assert d.draft([1, 2, 8, 8, 8, 1, 2, 9, 1, 2], 4) == [8, 8, 8, 1]
    # all-distinct stream: nothing to look up
    assert d.draft([1, 2, 3, 4, 5], 4) == []
    assert d.draft([7], 4) == [], "degenerate stream"
    # unigram fallback: the only earlier [3] match leaves one
    # continuation token, which is still worth drafting
    assert d.draft([3, 3, 3, 3], 2) == [3]


def test_model_drafter_and_registry():
    d = ModelDrafter(lambda toks, k: [11, 12, 13, 14, 15])
    assert d.draft([1, 2, 3], 3) == [11, 12, 13], "hook capped at k"
    assert isinstance(make_drafter("prompt_lookup"), PromptLookupDrafter)
    with pytest.raises(ValueError, match="prompt_lookup"):
        make_drafter("no_such_drafter")


def test_accept_greedy_edges():
    """The acceptance rule IS the exactness proof: every emitted token
    is the verifier's argmax, so full/partial/zero acceptance all emit
    exactly what plain greedy decode would have."""
    emitted, m = accept_greedy([1, 2, 3], [1, 2, 3, 7])
    assert emitted == [1, 2, 3, 7] and m == 3, "full accept + bonus"
    emitted, m = accept_greedy([1, 9, 3], [1, 2, 3, 7])
    assert emitted == [1, 2] and m == 1, "correction token at the split"
    emitted, m = accept_greedy([9], [5, 6])
    assert emitted == [5] and m == 0, "zero accept still emits one"
    emitted, m = accept_greedy([], [4])
    assert emitted == [4] and m == 0, "draft-free row decodes plain"


def test_spec_engine_validates(model_and_params):
    cfg, _, params = model_and_params
    with pytest.raises(ValueError, match="spec_k must be >= 1"):
        ServingEngine(cfg, params, serve=ServeConfig(
            block_size=8, num_blocks=0, spec=True, spec_k=0))
    eng = ServingEngine(cfg, params, serve=ServeConfig(
        block_size=8, num_blocks=0, spec=True, spec_k=4))
    assert eng.spec_w == 8, "next pow2 >= k+1"
    with pytest.raises(ValueError, match="spec_k must be >= 0"):
        eng.submit(np.arange(1, 5), max_new_tokens=2, spec_k=-1)


@pytest.mark.parametrize("shard", [1, 2])
def test_speculative_oracle_with_rollback(model_and_params, shard):
    """THE acceptance oracle: speculative decode over templated prompts
    with forced evictions, prefix hits and CoW tails — with both
    acceptance AND rollback exercised — emits bit-identical streams to
    the no-cache reference, at shard factors 1 and 2."""
    cfg, model, params = model_and_params
    mesh = None if shard == 1 else _shard_mesh(2)
    # Pool: four live requests end at >= 27 tokens = 7 blocks each, 28
    # in all, against 21 — evictions by arithmetic, not by which tokens
    # the random weights happen to emit.  (25 blocks sat one eviction
    # from none: the jax 0.5 change of the default PRNG stream gave
    # different weights, earlier finishes, and zero evictions.)
    eng = ServingEngine(cfg, params, serve=ServeConfig(
        block_size=4, num_blocks=21, token_budget=64, watermark=0,
        decode_tiers=(1, 2, 4), prefill_chunk=8, spec=True, spec_k=4),
        mesh=mesh)
    rs = np.random.RandomState(11)
    prompts = _template_prompts(rs, 4, t_len=11, s_lo=2, s_hi=5)
    ids = [eng.submit(p, max_new_tokens=14) for p in prompts]
    out = eng.run()
    assert eng.scheduler.evictions > 0, "pool sized to force evictions"
    assert eng.scheduler.prefix_hit_blocks > 0, "templates must hit"
    assert eng.spec_accepted_tokens > 0, "drafts must land"
    assert eng.spec_rolled_back_tokens > 0, "rollback must be in the loop"
    for i, rid in enumerate(ids):
        np.testing.assert_array_equal(
            out[rid], ref_decode(model, params, prompts[i], 14),
            err_msg=f"req {i} (shard factor {shard})")


def test_spec_menu_compile_free_under_randomized_load(model_and_params):
    """k as a STATIC menu axis: spec on adds exactly |decode_tiers| x
    |page_tiers| verify-width programs to the warmup menu, and a
    512-request randomized templated load adds ZERO executable-cache
    misses — per-request draft lengths vary every step, the program
    keys never do.  (Two decode tiers keep the warmup bill small; the
    menu arithmetic below is tier-count-generic.)"""
    cfg, model, params = model_and_params
    eng = ServingEngine(cfg, params, serve=ServeConfig(
        block_size=8, num_blocks=0, token_budget=256, watermark=2,
        decode_tiers=(2, 8), prefill_chunk=16, spec=True,
        spec_k=4))
    menu = len(eng.decode_tiers) * (
        len(eng.chunk_tiers) + 2 * len(eng.page_tiers))
    warmed = eng.warmup()
    assert warmed == menu == eng.program_count
    miss0 = _instr.EXEC_CACHE.labels("miss").get()
    rs = np.random.RandomState(4)
    templates = [rs.randint(1, 97, size=24).astype(np.int32)
                 for _ in range(4)]
    load = _templated_load(rs, 512, templates)
    for prompt, gen in load:
        eng.submit(prompt, max_new_tokens=gen)
    out = eng.run()
    assert len(out) == 512 and all(len(v) >= 1 for v in out.values())
    assert eng.program_count == menu
    assert _instr.EXEC_CACHE.labels("miss").get() == miss0
    assert eng.spec_steps > 0 and eng.spec_drafted_tokens > 0
    assert eng.spec_rolled_back_tokens > 0
    for rid in (0, 99, 511):  # spot-check the oracle at this scale
        prompt, gen = load[rid]
        np.testing.assert_array_equal(
            out[rid], ref_decode(model, params, prompt, gen))


def test_spec_cache_state_lags_one_and_republishes(model_and_params):
    """The tokens_in_cache invariant generalizes to k-token steps: the
    last emitted token is ALWAYS the verifier's bonus/correction token
    whose K/V the step never fed, so cache state lags the stream by
    exactly one in decode whatever k landed — and the block table never
    retains a speculative tail past a settle.  Prefix publication
    (which trusts tokens_in_cache) therefore re-admits a repeat prompt
    through the cache with a bit-identical stream."""
    cfg, model, params = model_and_params
    eng = ServingEngine(cfg, params, serve=ServeConfig(
        block_size=4, num_blocks=0, token_budget=64, watermark=2,
        decode_tiers=(1, 2), spec=True, spec_k=4))
    prompt = np.asarray([5, 6, 7, 5, 6, 7, 5, 6], np.int32)  # draftable
    rid = eng.submit(prompt, max_new_tokens=12)
    while eng.step():
        for s in eng.scheduler.running:
            if s.in_decode:
                assert s.tokens_in_cache == s.length - 1
                assert blocks_for(s.length, 4) <= len(s.blocks) \
                    <= blocks_for(s.length + 1, 4), \
                    "stale speculative tail in the block table"
    out1 = eng.results[rid]
    assert eng.spec_drafted_tokens > 0, "the load must actually draft"
    hits0 = eng.scheduler.prefix_hit_blocks
    rid2 = eng.submit(prompt, max_new_tokens=12)
    eng.run()
    assert eng.scheduler.prefix_hit_blocks > hits0, \
        "post-spec published blocks must re-admit"
    np.testing.assert_array_equal(eng.results[rid2], out1)
    np.testing.assert_array_equal(
        out1, ref_decode(model, params, prompt, 12))


def test_spec_k_per_request_opt_out(model_and_params):
    """submit(spec_k=0) turns speculation off for ONE request without
    touching the engine default — same stream either way."""
    cfg, model, params = model_and_params
    eng = ServingEngine(cfg, params, serve=ServeConfig(
        block_size=8, num_blocks=0, token_budget=64, watermark=2,
        decode_tiers=(1,), prefill_tiers=(16,), spec=True, spec_k=4))
    prompt = np.asarray([3, 4, 3, 4, 3, 4, 3, 4], np.int32)
    rid = eng.submit(prompt, max_new_tokens=10, spec_k=0)
    eng.run()
    assert eng.spec_drafted_tokens == 0 and eng.spec_steps == 0
    np.testing.assert_array_equal(
        eng.results[rid], ref_decode(model, params, prompt, 10))
    rid2 = eng.submit(prompt, max_new_tokens=10)  # engine default k
    eng.run()
    assert eng.spec_drafted_tokens > 0
    np.testing.assert_array_equal(eng.results[rid2], eng.results[rid])


def test_router_threads_spec_k(model_and_params):
    """The fleet path carries the per-request knob end to end: router
    -> replica -> engine, including on a spec-enabled replica."""
    from horovod_tpu.fleet.router import FleetRouter

    cfg, model, params = model_and_params

    def build():
        return ServingEngine(cfg, params, serve=ServeConfig(
            block_size=8, num_blocks=0, token_budget=64, watermark=2,
            decode_tiers=(1,), prefill_tiers=(16,), spec=True,
            spec_k=4))

    router = FleetRouter(build, replicas=1, mode="round_robin")
    eng = router.replicas[0].engine
    prompt = np.asarray([3, 4, 3, 4, 3, 4, 3, 4], np.int32)
    g0 = router.submit(prompt, 10, spec_k=0)
    while router.step() or router._placed:
        pass
    assert eng.spec_drafted_tokens == 0, "opt-out must reach the engine"
    g1 = router.submit(prompt, 10)
    while router.step() or router._placed:
        pass
    assert eng.spec_drafted_tokens > 0, "default k must reach the engine"
    np.testing.assert_array_equal(router.results[g0], router.results[g1])
    np.testing.assert_array_equal(
        router.results[g0], ref_decode(model, params, prompt, 10))


# -- KV snapshot / migration (ISSUE 18) ---------------------------------------


@pytest.mark.parametrize("shard", [1, 2])
@pytest.mark.parametrize("spec", [False, True])
def test_kv_migration_resumes_token_identical(model_and_params, shard,
                                              spec):
    """THE recovery oracle (ISSUE 18): a request interrupted mid-decode,
    exported (verified stream + KV block snapshot) and re-registered in
    a FRESH engine resumes bit-identical to uninterrupted decode — the
    warm path serves the re-prefill from the imported cache with zero
    post-warmup compiles — at shard factors 1 and 2, spec on and off."""
    cfg, model, params = model_and_params
    mesh = None if shard == 1 else _shard_mesh(2)

    def build():
        return ServingEngine(cfg, params, serve=ServeConfig(
            block_size=4, num_blocks=25, token_budget=64, watermark=0,
            decode_tiers=(1, 2), prefill_chunk=8, spec=spec, spec_k=4),
            mesh=mesh)

    src = build()
    rs = np.random.RandomState(18)
    prompt = rs.randint(1, 97, size=13).astype(np.int32)
    total = 18
    rid = src.submit(prompt, max_new_tokens=total)
    while True:  # interrupt with >= 2 full blocks of verified stream
        seq = next((s for s in src.scheduler.running
                    if s.req.id == rid), None)
        if seq is not None and len(seq.generated) >= 8:
            break
        assert src.step(), "request finished before the interruption"
    tokens, snap, _arr = src.export_requests()[rid]
    gen = np.asarray(tokens[len(prompt):], np.int32)
    assert gen.size >= 8
    assert snap is not None and len(snap["hashes"]) >= 2
    tgt = build()
    tgt.warmup()
    miss0 = _instr.EXEC_CACHE.labels("miss").get()
    assert tgt.import_kv(snap) == len(snap["hashes"])
    rid2 = tgt.submit(np.concatenate([prompt, gen]),
                      max_new_tokens=total - gen.size)
    out = tgt.run()
    assert tgt.scheduler.prefix_hit_blocks >= len(snap["hashes"]) - 1, \
        "the imported chain must serve the re-prefill (warm path)"
    assert _instr.EXEC_CACHE.labels("miss").get() == miss0, \
        "the recovery path must not compile"
    np.testing.assert_array_equal(
        np.concatenate([gen, out[rid2]]),
        ref_decode(model, params, prompt, total),
        err_msg=f"shard={shard} spec={spec}")


def test_import_blocks_verifies_chain_and_rolls_back():
    """The serve.migrate corrupt-detection contract: one flipped token
    anywhere in the snapshot fails the chain-hash recomputation BEFORE
    any allocator state changes; a pool too small mid-chain rolls back
    every reference and registration taken so far."""
    a = BlockAllocator(12, block_size=4)
    owner = a.alloc(2)
    h0 = a.register(owner[0], PREFIX_HASH_ROOT, [1, 2, 3, 4])
    a.register(owner[1], h0, [5, 6, 7, 8])
    snap = a.export_blocks(owner, [1, 2, 3, 4, 5, 6, 7, 8])
    with pytest.raises(ValueError, match="need exactly"):
        a.export_blocks(owner, [1, 2, 3])
    b = BlockAllocator(12, block_size=4)
    bad = dict(snap)
    bad["tokens"] = [1, 2, 3, 4, 5, 6, 7, 9]  # one corrupted token
    free0, cached0 = b.free_blocks, b.cached_blocks
    with pytest.raises(ValueError, match="chain-hash mismatch"):
        b.import_blocks(bad)
    assert (b.free_blocks, b.cached_blocks) == (free0, cached0)
    with pytest.raises(ValueError, match="format"):
        b.import_blocks({**snap, "format": "nope"})
    with pytest.raises(ValueError, match="block_size"):
        b.import_blocks({**snap, "block_size": 8})
    # the good snapshot imports as two FRESH registered blocks...
    blocks, fresh = b.import_blocks(snap)
    assert len(blocks) == 2 and [i for i, _ in fresh] == [0, 1]
    b.free(blocks)  # park: matchable like any cached prefix
    m, _ = b.match_prefix([1, 2, 3, 4, 5, 6, 7, 8, 9], max_blocks=2)
    assert m == blocks
    b.free(m)
    # ...and a re-import is all index hits (nothing fresh to fill)
    blocks2, fresh2 = b.import_blocks(snap)
    assert blocks2 == blocks and fresh2 == []
    b.free(blocks2)
    # pool exhausted mid-chain: all-or-nothing rollback
    c = BlockAllocator(2, block_size=4)  # 1 usable block (0 is trash)
    free0, cached0 = c.free_blocks, c.cached_blocks
    with pytest.raises(ValueError, match="pool exhausted"):
        c.import_blocks(snap)
    assert (c.free_blocks, c.cached_blocks) == (free0, cached0)
    # prefix cache off: the chain could never be matched — refuse
    off = BlockAllocator(12, block_size=4, prefix_cache=False)
    with pytest.raises(ValueError, match="prefix cache"):
        off.import_blocks(snap)
    a.free(owner)


def test_truncate_tail_registered_tail_parks_matchable():
    """Satellite audit (ISSUE 18): a REGISTERED block released by
    truncate_tail must PARK on the LRU — still indexed, still matching
    exactly its registered tokens — never reach the free list while
    cached; an UNREGISTERED tail block returns to the free list and is
    never matchable."""
    a = BlockAllocator(10, block_size=4)
    table = a.alloc(3)
    h0 = a.register(table[0], PREFIX_HASH_ROOT, [1, 2, 3, 4])
    a.register(table[1], h0, [5, 6, 7, 8])  # registered mid-block
    free0 = a.free_blocks
    kept = a.truncate_tail(table, 4)  # drop registered + unregistered
    assert kept == table[:1]
    # both tails count reclaimable, but the registered one PARKS (LRU,
    # still indexed) while the unregistered one hits the plain free list
    assert a.free_blocks == free0 + 2
    assert a.ref(table[1]) == 0 and a.cached_blocks == 2
    # the parked block re-matches with exactly its registered tokens
    m, _ = a.match_prefix([1, 2, 3, 4, 5, 6, 7, 8, 9], max_blocks=2)
    assert m == table[:2]
    # ...and never with different content behind the same chain
    m2, _ = a.match_prefix([1, 2, 3, 4, 9, 9, 9, 9, 9], max_blocks=2)
    assert m2 == table[:1]
    a.free(m2)
    # while matched (ref > 0) a full-pool drain must not hand it out
    rest = a.alloc(a.free_blocks)
    assert table[1] not in rest
    a.free(rest)
    a.free(m)
    a.free(table[:1])
