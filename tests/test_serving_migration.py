"""KV snapshots and migration (ISSUE 18; docs/SERVING.md "Crash-surviving
requests"): a request interrupted mid-decode resumes on another engine
token-identical to the oracle of tests/test_serving.py, plain and
speculative, at shard factors 1 and 2; an imported snapshot is verified
along its hash chain and rolls back whole.
"""

import numpy as np
import pytest

from horovod_tpu.metrics import instruments as _instr
from horovod_tpu.serving import BlockAllocator, ServeConfig, ServingEngine
from horovod_tpu.serving.kv_cache import PREFIX_HASH_ROOT
from tests.serving_helpers import (  # noqa: F401  (model_and_params: fixture)
    _shard_mesh, model_and_params, ref_decode,
)


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
