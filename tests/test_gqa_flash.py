"""Kernel-native GQA + windowed flash oracle tests.

The flash kernels consume (B, S, H_kv, D) K/V directly (query head h
reads kv head h // group); the oracle here is the PRE-GQA-native
semantics — ``jnp.repeat`` K/V to full heads, then the unchanged MHA
dense path — so any grouping bug in the kernels or the grouped dense
einsums shows up as a numeric diff.  Gradients through the repeat
oracle sum each kv head's group automatically (autodiff of repeat is
the grouped sum), which pins the kernels' in-VMEM dK/dV accumulation.

Also here: the `_kb_range` block-skip property test (the bounds the
windowed kernels AND, through `tile_counts`, the bench's modeled columns
both rely on) and the
modeled-attention-bytes pin for the ~num_heads/num_kv_heads K/V
traffic reduction (ISSUE 5 acceptance).
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.transformer import causal_dot_attention
from horovod_tpu.ops.flash_attention import (
    _TILES_AN_ITERATION, _bd_tile_mask, _kb_range, _tile_mask, _tile_ranges,
    flash_attention, tile_counts,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_flash_bench():
    spec = importlib.util.spec_from_file_location(
        "flash_bench", os.path.join(_REPO, "tools", "flash_bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _qkv(b, s, h, h_kv, d, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    mk = lambda kk, heads: jax.random.normal(
        kk, (b, s, heads, d), jnp.float32).astype(dtype)
    return mk(ks[0], h), mk(ks[1], h_kv), mk(ks[2], h_kv)


def repeat_oracle(q, k, v, causal=True, window=None):
    """Pre-GQA-native semantics: expand K/V to full heads, MHA dense."""
    g = q.shape[2] // k.shape[2]
    return causal_dot_attention(
        q, jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2),
        causal=causal, window=window,
    )


def test_dense_gqa_matches_repeat_oracle():
    """The grouped dense einsum (no materialized repeat) is numerically
    the repeat+MHA computation."""
    q, k, v = _qkv(2, 48, 4, 2, 16, seed=11)
    for causal, window in ((True, None), (True, 7), (False, None),
                           (False, 7)):
        out = causal_dot_attention(q, k, v, causal=causal, window=window)
        ref = repeat_oracle(q, k, v, causal=causal, window=window)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5,
            err_msg=f"causal={causal} window={window}")


def test_dense_rejects_bad_head_split():
    q, k, v = _qkv(1, 8, 4, 3, 8)
    with pytest.raises(ValueError, match="multiple"):
        causal_dot_attention(q, k, v)
    with pytest.raises(ValueError, match="multiple"):
        flash_attention(q, k, v)


@pytest.mark.parametrize("ratio", [2, 4])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 120),
                                           (False, 120)])
def test_flash_gqa_matches_oracle(ratio, causal, window):
    """Grouped flash forward vs the repeat-dense reference across the
    causal x window x ratio grid (S=320 crosses 128-block boundaries,
    W=120 crosses them within a window)."""
    q, k, v = _qkv(1, 320, 4, 4 // ratio, 32, seed=ratio)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=128, block_k=128)
    ref = repeat_oracle(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("heads,kv_heads", [(4, 2), (6, 1), (8, 1)],
                         ids=["group2", "group6", "group8"])
@pytest.mark.parametrize("window", [None, 150])
def test_flash_gqa_gradients_match_oracle(window, heads, kv_heads):
    """GQA backward: dq per query head, dk/dv per KV head (the in-VMEM
    group accumulation) vs autodiff through the repeat oracle — whose
    repeat-transpose IS the grouped sum.  Groups of 6 and of 8 are the
    window-and-full-attention cell's (48 and 64 query heads over 8), the
    causal mask and a window that crosses the 128-tiles."""
    q, k, v = _qkv(1, 320, heads, kv_heads, 32, seed=5)
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, window=window, block_q=128,
                                   block_k=128)),
        np.asarray(repeat_oracle(q, k, v, window=window)),
        rtol=2e-5, atol=2e-5)

    gf = jax.grad(
        lambda a, b, c: (flash_attention(
            a, b, c, window=window, block_q=128, block_k=128) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    gd = jax.grad(
        lambda a, b, c: (repeat_oracle(a, b, c, window=window) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    assert gf[1].shape == k.shape and gf[2].shape == v.shape
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3)


def test_flash_gqa_bf16():
    q, k, v = _qkv(1, 256, 4, 1, 32, dtype=jnp.bfloat16, seed=7)
    out = flash_attention(q, k, v, block_q=128, block_k=128)
    ref = repeat_oracle(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2)


@pytest.mark.slow
def test_flash_gqa_exhaustive_grid():
    """Full causal x window x ratio x dtype sweep (slow tier; the fast
    tier covers the representative corners above)."""
    for ratio in (1, 2, 4):
        for causal in (True, False):
            for window in (None, 1, 33, 120):
                for dtype, tol in ((jnp.float32, 2e-5),
                                   (jnp.bfloat16, 2e-2)):
                    q, k, v = _qkv(1, 272, 4, 4 // ratio, 16,
                                   dtype=dtype, seed=ratio)
                    out = flash_attention(q, k, v, causal=causal,
                                          window=window, block_q=128,
                                          block_k=128)
                    ref = repeat_oracle(q, k, v, causal=causal,
                                        window=window)
                    np.testing.assert_allclose(
                        np.asarray(out, np.float32),
                        np.asarray(ref, np.float32), rtol=tol, atol=tol,
                        err_msg=f"ratio={ratio} causal={causal} "
                                f"window={window} dtype={dtype}")


# -- _kb_range block-skip bounds --------------------------------------------


def _brute_blocks(q_off, block_q, block_k, padded_kb, causal, window,
                  kv_off):
    """Brute-force: K blocks holding >= 1 (q, k) pair unmasked by the
    causal/window terms (padding excluded — _kb_range doesn't see it)."""
    blocks = set()
    for kb in range(padded_kb):
        hit = False
        for qp in range(q_off, q_off + block_q):
            for kp in range(kb * block_k, (kb + 1) * block_k):
                rel = qp - kp - kv_off
                if causal and rel < 0:
                    continue
                if window is not None:
                    if rel >= window or (not causal and rel <= -window):
                        continue
                hit = True
                break
            if hit:
                break
        if hit:
            blocks.add(kb)
    return blocks


def _bounds_int(fn, *args):
    lo, hi = fn(*args)
    return int(lo), int(hi)


def test_kb_range_bounds_property():
    """kv_off=0 (self/diagonal attention): [lo, hi) covers EXACTLY the
    causal/window-unmasked K blocks — no block skipped that has work,
    no empty block visited at either edge."""
    for block_q, block_k in ((64, 64), (128, 64), (64, 128)):
        for padded_kb in (2, 3):
            s_k = padded_kb * block_k
            for q_off in range(0, s_k, block_q):
                for causal in (True, False):
                    for window in (None, 1, 17, 100, 1000):
                        want = _brute_blocks(q_off, block_q, block_k,
                                             padded_kb, causal, window, 0)
                        lo, hi = _bounds_int(_kb_range, q_off, block_q,
                                             block_k, padded_kb, causal,
                                             window, 0)
                        got = set(range(lo, hi))
                        assert got == want, (
                            f"bq={block_q} bk={block_k} kb={padded_kb} "
                            f"q_off={q_off} causal={causal} "
                            f"window={window}: {sorted(got)} != "
                            f"{sorted(want)}")


def test_kb_range_bounds_with_offset():
    """kv_off != 0 (ring off-diagonal blocks): the bounds must CONTAIN
    every unmasked block (correctness — a skipped block with work would
    silently drop attention mass)."""
    rng = np.random.RandomState(0)
    for _ in range(200):
        block_q = int(rng.choice([32, 64]))
        block_k = int(rng.choice([32, 64]))
        padded_kb = int(rng.randint(1, 4))
        q_off = int(rng.randint(0, 3)) * block_q
        causal = bool(rng.randint(2))
        window = [None, 1, 9, 50][rng.randint(4)]
        kv_off = int(rng.randint(-3, 4)) * 32
        want = _brute_blocks(q_off, block_q, block_k, padded_kb, causal,
                             window, kv_off)
        lo, hi = _bounds_int(_kb_range, q_off, block_q, block_k,
                             padded_kb, causal, window, kv_off)
        assert want <= set(range(lo, hi)), (
            f"bq={block_q} bk={block_k} kb={padded_kb} q_off={q_off} "
            f"causal={causal} window={window} kv_off={kv_off}: "
            f"{sorted(want)} not within [{lo}, {hi})")


# -- every kernel's loop bounds against the mask, tile by tile ----------------


def _check_ranges(ranges, mask_of, n_other):
    """One program's loop ranges against the mask of each tile of the other
    side: a tile that holds an allowed pair is visited, exactly once and in
    rising order; a tile no range visits holds none."""
    visited = []
    for lo, hi in ranges:
        visited += range(int(lo), int(hi))   # lo >= hi: an empty range
    assert visited == sorted(set(visited)), ranges
    assert not visited or 0 <= visited[0] and visited[-1] < n_other, ranges
    for t in set(range(n_other)) - set(visited):
        assert not mask_of(t).any(), (t, ranges)


@pytest.mark.parametrize("seq_len", [96, 83], ids=["aligned", "padded"])
@pytest.mark.parametrize("window", [None, 5, 40],
                         ids=["nowin", "win<tile", "win>tile"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "bidir"])
def test_tile_ranges_visit_every_tile_the_mask_allows(causal, window,
                                                      seq_len):
    """``_tile_ranges`` from both sides (queries on the rows: forward and
    dQ; keys on the rows: dK/dV) against ``_tile_mask`` itself, over tile
    shapes and positive and negative ``kv_off``: what the loops skip has no
    allowed pair."""
    s_pad = 96
    for block_q, block_k in ((16, 16), (32, 16), (16, 32)):
        for kv_off in (-48, -7, 0, 16, 64):
            q_pos = np.arange(s_pad)[:, None]
            k_pos = np.arange(s_pad)[None, :]
            mask = np.broadcast_to(np.asarray(_tile_mask(
                q_pos, k_pos, causal, window, seq_len, kv_off)),
                (s_pad, s_pad)) & (q_pos < seq_len)
            kw = dict(causal=causal, window=window, kv_off=kv_off, bd=None,
                      xp=np)
            for q_off in range(0, s_pad, block_q):
                _check_ranges(
                    _tile_ranges(q_off, block_q, block_k, s_pad // block_k,
                                 seq_len, rows_are_queries=True, **kw),
                    lambda t: mask[q_off:q_off + block_q,
                                   t * block_k:(t + 1) * block_k],
                    s_pad // block_k)
            for k_off in range(0, s_pad, block_k):
                _check_ranges(
                    _tile_ranges(k_off, block_k, block_q, s_pad // block_q,
                                 seq_len, rows_are_queries=False, **kw),
                    lambda t: mask[t * block_q:(t + 1) * block_q,
                                   k_off:k_off + block_k],
                    s_pad // block_q)


@pytest.mark.parametrize("half,blk,block_q,block_k", [
    (128, 4, 32, 32),    # the cell's kind: blocks of 4, L a multiple of the tile
    (128, 32, 32, 32),   # blocks of 32, as long as a tile
    (100, 4, 32, 32),    # a tile straddles L, the last one is padded
    (96, 32, 64, 32),    # rectangular tiles
    (100, 7, 32, 16),    # B divides neither L nor a tile
])
def test_block_diffusion_tile_ranges_visit_what_the_mask_allows(
        half, blk, block_q, block_k):
    """The same for the block-diffusion kind, both orientations of
    ``_bd_tile_mask`` (which agree)."""
    seq_len = 2 * half
    s_pad = -(-seq_len // max(block_q, block_k)) * max(block_q, block_k)
    mask = np.asarray(_bd_tile_mask(0, 0, s_pad, s_pad, seq_len,
                                    (half, blk), True))
    assert (mask == np.asarray(_bd_tile_mask(
        0, 0, s_pad, s_pad, seq_len, (half, blk), False)).T).all()
    kw = dict(causal=False, window=None, kv_off=0, bd=(half, blk), xp=np)
    for q_off in range(0, s_pad, block_q):
        _check_ranges(
            _tile_ranges(q_off, block_q, block_k, s_pad // block_k, seq_len,
                         rows_are_queries=True, **kw),
            lambda t: mask[q_off:q_off + block_q,
                           t * block_k:(t + 1) * block_k],
            s_pad // block_k)
    for k_off in range(0, s_pad, block_k):
        _check_ranges(
            _tile_ranges(k_off, block_k, block_q, s_pad // block_q, seq_len,
                         rows_are_queries=False, **kw),
            lambda t: mask[t * block_q:(t + 1) * block_q,
                           k_off:k_off + block_k],
            s_pad // block_q)


_CAUSAL_8192 = dict(s_q=8192, s_k=8192, seq_len=8192, causal=True)


@pytest.mark.parametrize("cell,kw,a_tile,tiles,want,heads,dkv,dkv_a_head", [
    ("internlm2-1.8b-s4096-1chip",
     dict(s_q=4096, s_k=4096, seq_len=4096, causal=True), (136, 34), 1, (136, 34),
     2, (272, 44), (136, 34)),
    ("sdar-30b-a3b-bd4-s4096-1chip",
     dict(s_q=8192, s_k=8192, seq_len=8192, causal=False, bd=(4096, 4)),
     (288, 70), 1, (288, 70), 8, (2304, 288), (288, 60)),
    ("laguna-xs.2-s8192-1chip/full", _CAUSAL_8192, (528, 100), 1, (528, 100),
     6, (3168, 416), (528, 100)),
    ("laguna-xs.2-s8192-1chip/sliding",
     dict(s_q=8192, s_k=8192, seq_len=8192, causal=True, window=512), (93, 62),
     4, (93, 16), 8, (744, 93), (93, 62)),
    ("kimi-vl-a3b-s8192-1chip", _CAUSAL_8192, (528, 100), 1, (528, 100),
     1, (528, 100), (528, 100)),
    ("qwen3-next-80b-a3b-s8192-1chip", _CAUSAL_8192, (528, 100), 1, (528, 100),
     1, (528, 100), (528, 100)),
])
def test_tile_counts_at_the_cells_shapes(cell, kw, a_tile, tiles, want, heads, dkv,
                                         dkv_a_head):
    """A head's tile visits and the loop iterations they take in 256-tiles, eight
    tiles an iteration first in every kernel.  A forward or dQ program walks
    ``tiles`` consecutive query tiles of a head as one (PR 43;
    ``_query_tiles_a_program``: the fewest that give it 8 visits, at most 8): a
    window of 512 visits three tiles a query tile, the diagonal one and two
    before it, and four query tiles are 12 visits in two iterations where one a
    program took two for its three (eight would be 24 in three: 13 a head); a
    query tile under the causal or the block-diffusion mask has eight visits and
    more, and its program walks it alone, the block-diffusion mask's two ranges
    as one.  The dK/dV kernel walks the query heads a program holds as one
    (PR 42): at a group of 8 every iteration holds eight, the window's three
    tiles a head and the 512 lone visits of a block-diffusion layer's noisy key
    tiles among them; one head a program (``heads_a_program=1``: the same shape
    beyond ``_DKV_GROUP_BYTES``) has its own walks' remainders."""
    from horovod_tpu.ops.flash_attention import _query_tiles_a_program

    assert _query_tiles_a_program(block_q=256, block_k=256, **kw) == tiles
    counts = tile_counts(block_q=256, block_k=256, heads_a_program=heads,
                         query_tiles_a_program=tiles, **kw)
    assert counts == {"fwd": want, "bwd_dq": want, "bwd_dkv": dkv}
    one = tile_counts(block_q=256, block_k=256, **kw)
    assert (one["fwd"], one["bwd_dkv"]) == (a_tile, dkv_a_head)
    if "window" in kw:
        assert tile_counts(block_q=256, block_k=256, query_tiles_a_program=8,
                           **kw)["fwd"] == (93, 13)


def _walked(walk, bounds):
    """The ``100 * owner + tile`` a walk visits, in its order, its bounds traced."""
    def run(bounds):
        note = lambda g, t, carry: (carry[0].at[carry[1]].set(100 * g + t), carry[1] + 1)
        return walk(bounds, note, (jnp.full((64,), -1, jnp.int32), jnp.int32(0)))
    seen, n = jax.jit(run)(jnp.asarray(bounds, jnp.int32))
    return list(np.asarray(seen)[:int(n)])


@pytest.mark.parametrize("group", [1, 2, 8])
@pytest.mark.parametrize("ranges", [((2, 5),), ((3, 4), (6, 11)), ((0, 0), (1, 3)),
                                     ((4, 9), (9, 9)), ((0, 0), (0, 0))])
def test_the_group_s_walk_visits_what_a_walk_a_head_visits_in_its_order(ranges, group):
    """``_run_group_tiles`` against a loop nest: the same (head, tile) visits in
    the same order, head after head and range after range, bounds traced,
    whatever the ranges' lengths (an empty one, both empty) and the group; and
    ``_run_query_tiles`` over as many query tiles of those same ranges visits
    them alike (a walk of one query tile at a group of 1)."""
    from horovod_tpu.ops.flash_attention import _run_group_tiles, _run_query_tiles

    want = [100 * g + t for g in range(group) for lo, hi in ranges for t in range(lo, hi)]
    assert _walked(lambda r, note, carry: _run_group_tiles(
        tuple((lo, hi) for lo, hi in r), group, note, carry), ranges) == want
    assert _walked(lambda r, note, carry: _run_query_tiles(
        [tuple((lo, hi) for lo, hi in r)] * group, note, carry), ranges) == want


@pytest.mark.parametrize("kind,kw,kv_off", [
    ("causal", dict(causal=True, window=None, bd=None), 0),
    ("window", dict(causal=True, window=200, bd=None), 0),
    ("two-sided-window", dict(causal=False, window=130, bd=None), 0),
    ("block-diffusion", dict(causal=False, window=None, bd=(320, 4)), 0),
    ("ring-step-behind", dict(causal=True, window=300, bd=None), -256),
    ("ring-step-ahead", dict(causal=True, window=None, bd=None), 384),
])
@pytest.mark.parametrize("tiles", [1, 2, 5])
def test_the_query_tiles_walk_visits_each_tile_s_ranges_in_order(kind, kw, kv_off, tiles):
    """``_run_query_tiles`` over ``tiles`` consecutive query tiles a program
    visits, for every mask kind, exactly the tiles of each query tile's own
    ranges (``_tile_ranges`` at its offset), query tile after query tile and
    range after range in rising order: the order of a loop nest a query tile,
    so the sums come out the same.  The offset is traced (a ring step: the
    bounds are computed in the program; behind the queries some tiles' ranges
    are cut, ahead of them some are empty and the walk passes over them)."""
    from horovod_tpu.ops.flash_attention import _run_query_tiles

    block, s = 128, 640
    ranges_at = lambda off, kv_off, xp: _tile_ranges(
        off, block, block, s // block, s, kv_off=kv_off, rows_are_queries=True, xp=xp, **kw)
    for first in range(0, s // block, tiles):
        held = range(first, min(first + tiles, s // block))
        want = [100 * j + t for j in held
                for lo, hi in ranges_at(j * block, kv_off, np) for t in range(lo, hi)]
        got = _walked(lambda off, note, carry: _run_query_tiles(
            [ranges_at(j * block, off[0], jnp) for j in held],
            lambda j, t, carry: note(held[0] + j, t, carry), carry), [kv_off])
        assert got == want, (kind, first)
    assert kind != "ring-step-ahead" or not ranges_at(0, kv_off, np)[0][1]  # an empty one


@pytest.mark.parametrize("kind,s,heads,d,kw,tiles", [
    ("causal", 700, (2, 1), (32, 32), dict(causal=True), 2),
    ("window", 1000, (2, 1), (32, 32), dict(causal=True, window=200), 4),
    ("block-diffusion", 700, (2, 2), (32, 32), dict(causal=False, bd=(350, 4)), 2),
    ("latent-192-128", 700, (2, 2), (192, 128), dict(causal=True), 2),
])
def test_query_tiles_walked_as_one_give_the_sums_of_one_a_program(
        monkeypatch, kind, s, heads, d, kw, tiles):
    """Forward output, ``lse`` and dq of the programs the rule makes (several
    consecutive query tiles a program, walked as one: ``tiles``) against one
    query tile a program, BIT FOR BIT, in interpret mode: the walk visits each
    query tile's tiles in the order of its own loops, and each query tile has
    its own running sums.  128-row tiles at lengths that are no multiple of a
    program's rows (the last tile is padded), keys and values 192 / 128 wide
    among them."""
    from horovod_tpu.ops import flash_attention as fa

    (h, h_kv), (d_qk, d_v) = heads, d
    ks = jax.random.split(jax.random.PRNGKey(43), 4)
    mk = lambda key, n, width: jax.random.normal(
        key, (1, s, n, width), jnp.float32).astype(jnp.bfloat16)
    q, k, v, g = mk(ks[0], h, d_qk), mk(ks[1], h_kv, d_qk), mk(ks[2], h_kv, d_v), mk(ks[3], h, d_v)

    def run():
        def both(q, k, v, g):
            out, lse = fa._forward_impl(q, k, v, kw["causal"], 128, 128, True, with_lse=True,
                                        window=kw.get("window"), bd=kw.get("bd"))
            return out, lse, fa._backward_impl(
                q, k, v, out, lse, g, kw["causal"], 128, 128, True,
                window=kw.get("window"), bd=kw.get("bd"))[0]
        return jax.jit(both)(q, k, v, g)

    s_pad = s + (-s) % 128
    assert fa._query_tiles_a_program(s_pad, s_pad, 128, 128, s, **kw) == tiles
    walked = run()
    monkeypatch.setattr(fa, "_QUERY_TILES_MOST", 1)
    for name, a, b in zip(("out", "lse", "dq"), walked, run()):
        assert jnp.array_equal(a, b), (kind, name)
    assert np.isfinite(np.asarray(walked[0], np.float32)).all()


@pytest.mark.parametrize("causal,window,seq_len", [
    (True, None, 1024), (True, 300, 1000), (False, 300, 1000),
    (False, None, 1000), (True, 100, 1024)])
def test_tile_counts_follow_the_kernels_loop_bounds(causal, window, seq_len):
    """``visited`` is the sum of the kernels' own ``_kb_range`` ranges (the
    bench's ``_kv_tiles``), the same pairs from both sides; ``iterations``
    is what a walk of one query tile a program takes (``_walk``): eight tiles
    at a time, then four, two, one."""
    fb = _load_flash_bench()
    s_pad, blk = 1024, 128
    counts = tile_counts(s_pad, s_pad, blk, blk, seq_len, causal=causal,
                         window=window)
    lengths = [max(0, hi - lo) for lo, hi in (
        _bounds_int(_kb_range, q_off, blk, blk, s_pad // blk, causal,
                    window, 0) for q_off in range(0, s_pad, blk))]
    assert sum(lengths) == fb._kv_tiles(seq_len, causal, window, blk, blk)
    assert {v for v, _ in counts.values()} == {sum(lengths)}

    def iterations(n):
        steps = 0
        for size in _TILES_AN_ITERATION:
            steps, n = steps + n // size, n % size
        return steps

    assert counts["fwd"][1] == sum(iterations(n) for n in lengths)
    assert iterations(7) == 3 and iterations(1) == 1 and iterations(0) == 0
    if window == 100:   # a tile or two a program: little to overlap
        assert counts["fwd"][0] < 2 * counts["fwd"][1]
    elif window is None and not causal:
        assert counts["fwd"] == (64, 8)


@pytest.mark.parametrize("dtype,tol,gtol", [(jnp.float32, 2e-5, 1e-3),
                                            (jnp.bfloat16, 2e-2, 1e-1)])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 300),
                                           (False, None)])
def test_flash_gqa_several_tiles_an_iteration_match_oracle(causal, window,
                                                           dtype, tol, gtol):
    """Forward and gradients where the programs' loops take iterations of
    eight, four, two and one tile (S = 700 in 128-tiles, padded to 768: ranges
    of one to six tiles, two query tiles a forward or dQ program under the
    masks that cut them; two query heads a kv head, the dK/dV tile computed
    transposed), float32 and bfloat16 inputs, at the existing tolerances."""
    s = 700
    counts = tile_counts(768, 768, 128, 128, s, causal=causal, window=window)
    assert all(iterations < visited for visited, iterations in counts.values())
    q, k, v = _qkv(1, s, 4, 2, 32, dtype=dtype, seed=13)

    def loss(fn):
        return lambda a, b, c: (fn(a, b, c).astype(jnp.float32) ** 2).sum()

    flash = lambda a, b, c: flash_attention(
        a, b, c, causal=causal, window=window, block_q=128, block_k=128)
    oracle = lambda a, b, c: repeat_oracle(a, b, c, causal=causal,
                                           window=window)
    np.testing.assert_allclose(
        np.asarray(flash(q, k, v), np.float32),
        np.asarray(oracle(q, k, v), np.float32), rtol=tol, atol=tol)
    gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss(oracle), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=gtol, atol=gtol)


# -- modeled K/V traffic (ISSUE 5 acceptance pin) ---------------------------


def test_modeled_kv_bytes_shrink_by_group():
    """The bench's modeled-bytes column: flash GQA K/V HBM reads are
    exactly num_heads/num_kv_heads smaller than MHA, and the total
    (incl. the repeat materialization the old path paid) shrinks
    accordingly."""
    fb = _load_flash_bench()
    b, s, h, d = 4, 2048, 8, 128
    mha = fb.modeled_attention_bytes(b, s, h, h, d)
    for h_kv in (4, 2, 1):
        gqa = fb.modeled_attention_bytes(b, s, h, h_kv, d)
        assert gqa["kv_bytes"] * (h // h_kv) == mha["kv_bytes"]
        baseline = fb.modeled_repeat_baseline_bytes(b, s, h, h_kv, d)
        # old path: repeat materialization + MHA-sized kernel reads
        assert baseline["kv_bytes"] == mha["kv_bytes"]
        assert baseline["repeat_io_bytes"] > 0
        assert baseline["total_bytes"] > mha["total_bytes"]
        assert gqa["total_bytes"] < mha["total_bytes"]
    # MHA "baseline" pays no repeat traffic (repeat(1) is a no-op)
    assert fb.modeled_repeat_baseline_bytes(
        b, s, h, h, d)["repeat_io_bytes"] == 0


def test_modeled_flops_drop_with_window():
    fb = _load_flash_bench()
    full = fb.modeled_attention_flops(1, 4096, 8, 128, causal=True,
                                      window=None)
    prev = full
    for w in (2048, 1024, 512, 256):
        f = fb.modeled_attention_flops(1, 4096, 8, 128, causal=True,
                                       window=w)
        assert f <= prev
        prev = f
    # O(S·W): at W=256 with 256-blocks, each Q block visits <= 3 K blocks
    assert prev <= 4 * 1 * 8 * 256 * 256 * 128 * (4096 // 256) * 3


# -- q_len=1 decode entry (the paged-KV serving path, ISSUE 8) ---------------


from horovod_tpu.ops.flash_attention import flash_decode_attention  # noqa: E402


def decode_oracle(q, k, v, kv_lens, window=None, kv_start=None):
    """Dense per-sequence reference for single-token decode: query at
    global position kv_lens-1 attends keys at global positions
    kv_start..kv_start+S_kv-1 masked by length and window."""
    b, _, h, d = q.shape
    s_k = k.shape[1]
    g = h // k.shape[2]
    kf = np.repeat(np.asarray(k, np.float32), g, axis=2)
    vf = np.repeat(np.asarray(v, np.float32), g, axis=2)
    starts = (np.zeros(b, np.int64) if kv_start is None
              else np.asarray(kv_start, np.int64))
    outs = np.zeros((b, 1, h, d), np.float32)
    for i in range(b):
        qpos = int(kv_lens[i]) - 1
        kg = starts[i] + np.arange(s_k)
        mask = kg <= qpos
        if window is not None:
            mask &= (qpos - kg) < window
        if not mask.any():
            continue  # fully masked row: the kernel's -inf lse sentinel
        s = np.einsum("hd,shd->hs",
                      np.asarray(q[i, 0], np.float32) / np.sqrt(d), kf[i])
        s[:, ~mask] = -np.inf
        p = np.exp(s - s.max(axis=1, keepdims=True))
        p /= p.sum(axis=1, keepdims=True)
        outs[i, 0] = np.einsum("hs,shd->hd", p, vf[i])
    return outs


def _decode_qkv(b, s_k, h, h_kv, d, kv_lens, seed=0, kv_start=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
    k = np.array(jax.random.normal(ks[1], (b, s_k, h_kv, d)))
    v = np.array(jax.random.normal(ks[2], (b, s_k, h_kv, d)))
    # poison every position the mask must exclude: a wrong/missing mask
    # turns into a huge numeric diff, not a subtle one
    starts = np.zeros(b, np.int64) if kv_start is None else np.asarray(kv_start)
    for i in range(b):
        k[i, max(0, kv_lens[i] - starts[i]):] = 1e4
        v[i, max(0, kv_lens[i] - starts[i]):] = 1e4
    return q, jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("ratio", [1, 2, 4])
@pytest.mark.parametrize("window", [None, 24])
def test_flash_decode_matches_oracle(ratio, window):
    """Single query row vs dense reference across GQA ratio x window;
    per-sequence kv_lens land mid-block and at block boundaries, with
    poisoned K/V beyond every length."""
    kv_lens = np.array([1, 37, 128, 160], np.int32)  # edges + mid-block
    q, k, v = _decode_qkv(4, 160, 4, 4 // ratio, 16, kv_lens, seed=ratio)
    out = flash_decode_attention(q, k, v, kv_lens, window=window)
    ref = decode_oracle(q, k, v, kv_lens, window=window)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


def test_flash_decode_kv_start_offsets():
    """The windowed-gather contract: k[:, 0] sits at a per-sequence
    global position (page-aligned or not); masks must stay global.
    Covers kv_offset at non-zero block-size boundaries (128 = one
    block_k) and unaligned starts."""
    starts = np.array([0, 128, 37], np.int64)
    kv_lens = np.array([60, 170, 95], np.int32)
    q, k, v = _decode_qkv(3, 64, 4, 2, 16, kv_lens, seed=9,
                          kv_start=starts)
    for window in (None, 16):
        out = flash_decode_attention(q, k, v, kv_lens, window=window,
                                     kv_start=starts)
        ref = decode_oracle(q, k, v, kv_lens, window=window,
                            kv_start=starts)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5,
                                   atol=2e-5, err_msg=f"window={window}")


def test_flash_decode_fully_masked_rows_are_zero():
    """kv_lens<=0 pad slots (and window pushed fully past the gather)
    ride the -inf lse sentinel: all-zero output, no NaN."""
    kv_lens = np.array([0, 48, 0], np.int32)
    q, k, v = _decode_qkv(3, 64, 4, 2, 16, kv_lens, seed=3)
    out = np.asarray(flash_decode_attention(q, k, v, kv_lens))
    assert np.isfinite(out).all()
    assert np.all(out[0] == 0) and np.all(out[2] == 0)
    ref = decode_oracle(q, k, v, kv_lens)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_flash_decode_validates():
    q = jnp.zeros((2, 3, 4, 16))
    kv = jnp.zeros((2, 64, 2, 16))
    with pytest.raises(ValueError, match="q_len=1"):
        flash_decode_attention(q, kv, kv, np.array([1, 1]))
    with pytest.raises(ValueError, match="window"):
        flash_decode_attention(jnp.zeros((2, 1, 4, 16)), kv, kv,
                               np.array([1, 1]), window=0)
