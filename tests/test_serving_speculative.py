"""Speculative decoding (ISSUE 17) rides the oracle of
tests/test_serving.py: greedy accept/reject emits only verifier
argmaxes, so the speculative stream is bit-identical to the plain one —
with rollback (truncate_tail) in the loop, at shard factors 1 and 2.
The 512-request randomized load pins the menu with spec on: per-request
draft lengths vary every step, the program keys never do.
"""

import numpy as np
import pytest

from horovod_tpu.metrics import instruments as _instr
from horovod_tpu.serving import (
    BlockAllocator, ModelDrafter, PromptLookupDrafter, ServeConfig,
    ServingEngine, accept_greedy, blocks_for, make_drafter,
)
from horovod_tpu.serving.kv_cache import PREFIX_HASH_ROOT
from tests.serving_helpers import (  # noqa: F401  (model_and_params: fixture)
    _shard_mesh, _template_prompts, _templated_load, model_and_params,
    ref_decode,
)


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
