"""The routed experts' grouped products (``ops/grouped_matmul.py``) against
the plain definition, on the CPU: the kernels run in interpret mode, so this
is the code the chip runs.

The plain definition: ``x[start:end].astype(f32) @ w[g].astype(f32)`` a
group, ordinary autodiff.  float32 is held to 1e-5, bf16 to its rounding (one
rounding of a float32 sum).  Row tiles of 16 (``small_tiles``) make tiny
groups share tiles, span several, and leave tiles beyond them unvisited, as
the cells' groups do at 256.

Each group of cases fails under one mutation of the kernels, which the last
three tests make by hand: the row mask of a shared tile dropped; the group of
a visit off by one; the empty group's zeroing removed.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import grouped_matmul as gm
from horovod_tpu.ops.grouped_matmul import Tiles, grouped_matmul

ROWS = 96
# group sizes over 96 rows in row tiles of 16
SIZES = {
    "equal": [16, 16, 16, 16],            # every group one whole tile
    "skewed": [13, 7, 29, 5],             # no multiple of 8; tiles shared by 2 and 3 groups
    "empty_group": [21, 0, 19, 0],        # empty in the middle and last
    "one_group": [0, 90, 0, 0],           # every held row in one group
    "half": [11, 13, 9, 15],              # sum(sizes) = rows / 2
    "full": [30, 18, 40, 8],              # sum(sizes) = rows
}


@pytest.fixture
def small_tiles(monkeypatch):
    """Row tiles of 16, k and n in tiles of 128: two k tiles and three column
    tiles at the shapes below."""
    monkeypatch.setattr(gm, "tiles", lambda rows, k, n, expected, dtype: Tiles(16, 128, 128))


def _plain(x, w, sizes, transpose_w=False):
    """The definition: float32 ``(rows, n)``, zero beyond the groups."""
    parts, start = [], 0
    for g, size in enumerate(sizes):
        wg = w[g].astype(jnp.float32)
        parts.append(x[start:start + size].astype(jnp.float32) @ (wg.T if transpose_w else wg))
        start += size
    n = w.shape[1] if transpose_w else w.shape[2]
    parts.append(jnp.zeros((x.shape[0] - start, n), jnp.float32))
    return jnp.concatenate(parts)


def _operands(dtype, rows, k, n, groups, seed=0, transpose_w=False):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((rows, k)), dtype)
    w = jnp.asarray(rng.standard_normal((groups, n, k) if transpose_w else (groups, k, n))
                    / np.sqrt(k), dtype)
    dy = jnp.asarray(rng.standard_normal((rows, n)), dtype)
    return x, w, dy


def _close(got, want, dtype):
    """float32 to 1e-5 of the result's scale; bf16 to one rounding of the
    float32 value."""
    got = np.asarray(jnp.asarray(got, jnp.float32))
    want = np.asarray(want, np.float32)
    scale = max(float(np.abs(want).max()), 1e-6)
    if jnp.dtype(dtype) == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * scale)
    else:
        np.testing.assert_allclose(got, want, rtol=2.0 ** -7, atol=1e-5 * scale)


def _check(dtype, sizes, rows, k, n, transpose_w=False, seed=0):
    """The product and both gradients on the held rows."""
    x, w, dy = _operands(dtype, rows, k, n, len(sizes), seed, transpose_w)
    held = sum(sizes)
    valid = (jnp.arange(rows) < held)[:, None]

    def kernel(x, w):
        out = grouped_matmul(x, w, jnp.asarray(sizes, jnp.int32), transpose_w=transpose_w)
        return jnp.where(valid, out, 0)    # undefined beyond the groups

    def plain(x, w):
        return _plain(x, w, sizes, transpose_w)

    out, vjp = jax.vjp(kernel, x, w)
    want, want_vjp = jax.vjp(plain, x, w)
    assert out.dtype == x.dtype and out.shape == (rows, n)
    _close(out, want, dtype)
    dx, dw = vjp(dy)
    want_dx, want_dw = want_vjp(jnp.where(valid, dy, 0).astype(jnp.float32))
    assert dx.dtype == x.dtype and dw.dtype == w.dtype and dw.shape == w.shape
    _close(dx[:held], want_dx[:held], dtype)
    _close(dw, want_dw, dtype)
    return dx, dw


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", SIZES)
def test_product_and_gradients_match_the_plain_definition(small_tiles, case, dtype):
    _check(dtype, SIZES[case], ROWS, 256, 384)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["skewed", "empty_group", "full"])
def test_w_read_transposed(small_tiles, case, dtype):
    """``w`` (groups, n, k) read transposed: what the gradient with respect
    to the rows runs, and differentiable itself."""
    _check(dtype, SIZES[case], ROWS, 256, 384, transpose_w=True)


@pytest.mark.parametrize("tm", [16, 32])
@pytest.mark.parametrize("k,n", [(256, 96), (96, 256), (256, 176), (176, 256)],
                         ids=["sdar_up", "sdar_down", "kimi_up", "kimi_down"])
def test_the_cells_width_ratios(monkeypatch, k, n, tm):
    """The two cells' widths at an eighth: 2,048 -> 768 and back, 2,048 ->
    1,408 and back, an expert's matrix whole in a tile as the cells have it."""
    monkeypatch.setattr(gm, "tiles", lambda rows, k, n, groups, dtype: Tiles(tm, k, n))
    _check("bfloat16", [23, 9, 31, 14, 2, 0, 17, 12], 256, k, n)


def test_the_default_tiles_run_too():
    """No tile patched: what the shapes give (one 48-row tile here)."""
    _check("bfloat16", [5, 20, 0, 11], 48, 128, 256)
    _check("float32", [5, 20, 0, 11], 44, 128, 256)     # rows no multiple of 8: padded


@pytest.mark.parametrize("case", ["half", "skewed", "empty_group"])
def test_rows_beyond_the_groups_are_read_into_no_result(small_tiles, case):
    """Whatever the rows beyond ``sum(sizes)`` hold, in ``x`` and in the
    cotangent, the held rows' results and the matrices' gradient are the
    same numbers, bit for bit."""
    sizes = SIZES[case]
    held = sum(sizes)
    x, w, dy = _operands("bfloat16", ROWS, 256, 384, len(sizes))
    sizes_ = jnp.asarray(sizes, jnp.int32)

    def run(x, dy):
        out, vjp = jax.vjp(lambda x, w: grouped_matmul(x, w, sizes_), x, w)
        dx, dw = vjp(dy)
        return out[:held], dx[:held], dw

    poison = (jnp.arange(ROWS) >= held)[:, None]
    got = run(jnp.where(poison, jnp.nan, x), jnp.where(poison, jnp.inf, dy))
    for a, b in zip(got, run(x, dy)):
        assert np.isfinite(np.asarray(a, np.float32)).all()
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_an_empty_group_s_weight_gradient_is_exactly_zero(small_tiles, dtype):
    _, dw = _check(dtype, SIZES["empty_group"], ROWS, 256, 384)
    dw = np.asarray(dw, np.float32)
    assert not dw[1].any() and not dw[3].any() and dw[0].any() and dw[2].any()
    # and where nothing is held at all
    x, w, dy = _operands(dtype, ROWS, 256, 384, 4)
    dw = jax.grad(lambda w: jnp.sum(
        grouped_matmul(x, w, jnp.zeros((4,), jnp.int32))[:0].astype(jnp.float32))
        + 0 * jnp.sum(w))(w)
    assert not np.asarray(dw, np.float32).any()


def test_visited_rows_of_no_group_are_zero(small_tiles):
    """A visited tile's rows that no group owns (the end of the last tile in
    use) hold zeros, not what the buffer held."""
    x, w, _ = _operands("float32", ROWS, 256, 384, 4)
    out = grouped_matmul(x, w, jnp.asarray([13, 7, 29, 5], jnp.int32))   # 54 rows: tile 3 ends at 64
    assert not np.asarray(out[54:64]).any()


# -- the walk over the row tiles -------------------------------------------------


def _walk(sizes, rows, tm, visit_empty):
    """Brute force: every (group, tile) pair with a row in common, in order;
    an empty group, if visited, at the tile its start lies in."""
    visits, start = [], 0
    last_tile = -(-rows // tm) - 1
    for g, size in enumerate(sizes):
        if size:
            visits += [(g, t) for t in range(start // tm, (start + size - 1) // tm + 1)]
        elif visit_empty:
            visits.append((g, min(start // tm, last_tile)))
        start += size
    return visits


@pytest.mark.parametrize("visit_empty", [False, True], ids=["product", "weight_gradient"])
@pytest.mark.parametrize("seed", range(4))
def test_the_walk_visits_each_tile_of_each_group_once_in_order(seed, visit_empty):
    rng = np.random.default_rng(seed)
    groups, rows, tm = 6, 160, 16
    sizes = rng.multinomial(rows // 2 + 8 * seed, rng.dirichlet(np.ones(groups) * 0.7))
    sizes[rng.integers(groups)] = 0
    offsets, group, tile, count = gm._visits(jnp.asarray(sizes, jnp.int32), rows, tm, visit_empty)
    want = _walk(sizes, rows, tm, visit_empty)
    assert int(count) == len(want) <= group.shape[0] == tile.shape[0]
    assert list(zip(np.asarray(group)[:len(want)], np.asarray(tile)[:len(want)])) == want
    assert list(np.asarray(offsets)) == [0, *np.cumsum(sizes)]
    # tiles beyond the groups are in no visit
    assert max(t for _, t in want) == max((sizes.sum() - 1) // tm, 0) or visit_empty


def test_the_walk_of_nothing_is_empty():
    _, _, _, count = gm._visits(jnp.zeros((4,), jnp.int32), 64, 16, False)
    assert int(count) == 0
    _, group, tile, count = gm._visits(jnp.zeros((4,), jnp.int32), 64, 16, True)
    assert int(count) == 4 and list(np.asarray(group)[:4]) == [0, 1, 2, 3] and not np.asarray(tile).any()


# -- tile sizes from the shapes --------------------------------------------------


@pytest.mark.parametrize("shape,want", [
    # (rows, k, n, the rows a group is expected to hold).  The cells' first
    # chunks, nine eighths of the expected assignments (Kimi: 768 rows an expert
    # of 8, SDAR: 512 of 16, Mellum 2: 1,024 of 16): an expert's matrix whole,
    # row tiles of 256
    ((6912, 2048, 1408, 768), (256, 2048, 1408)),
    ((6912, 1408, 2048, 768), (256, 1408, 2048)),
    ((9216, 2048, 768, 512), (256, 2048, 768)),
    ((9216, 768, 2048, 512), (256, 768, 2048)),
    ((18432, 2304, 896, 1024), (256, 2304, 896)),
    # a later chunk, a quarter of them: the tiles are the layer's, not the chunk's
    ((2048, 2048, 768, 512), (256, 2048, 768)),
    # eight times the rows a group: the same tiles, the length of a group is device data
    ((98304, 2048, 1408, 6144), (256, 2048, 1408)),
    # short groups: row tiles of 128 (Qwen3-Next: 160 rows an expert, Laguna: 256)
    ((5760, 2048, 512, 160), (128, 2048, 512)),
    ((4608, 2048, 512, 256), (128, 2048, 512)),
    ((4096, 2048, 768, 128), (128, 2048, 768)),
])
def test_tiles_follow_from_the_shapes(shape, want):
    assert tuple(gm.tiles(*shape, jnp.bfloat16)) == want


def test_the_expected_group_is_the_rows_over_the_groups_where_none_is_given(monkeypatch):
    """``grouped_matmul`` without ``expected`` takes the rows over the groups, and
    with it the caller's: the argument reaches ``tiles`` forward and backward."""
    seen, tiles = [], gm.tiles
    monkeypatch.setattr(gm, "tiles", lambda rows, k, n, expected, dtype: (
        seen.append(expected), tiles(rows, k, n, expected, dtype))[1])
    x, w = jnp.ones((64, 32), jnp.float32), jnp.ones((4, 32, 24), jnp.float32)
    sizes = jnp.asarray([16, 16, 16, 16], jnp.int32)
    for expected, want in ((None, 16), (8, 8)):
        del seen[:]
        jax.grad(lambda x: jnp.sum(grouped_matmul(x, w, sizes, expected=expected)))(x)
        assert len(seen) == 3 and set(seen) == {want}     # forward, dx, dw


def test_tiles_of_a_matrix_too_large_for_a_block():
    """A (k, n) too large to hold whole: columns in tiles first, then k in a
    divisor that is a multiple of 128; a k that has none is refused."""
    tm, tk, tn = gm.tiles(16384, 4096, 14336, 1024, jnp.bfloat16)
    assert 4096 % tk == 0 and tk % 128 == 0 and tn % 128 == 0
    assert tk * tn * 2 <= gm._W_BLOCK_BYTES
    with pytest.raises(ValueError, match="multiple of 128"):
        gm.tiles(16384, 100000, 4096, 1024, jnp.bfloat16)


def test_visit_counts_at_the_cells_shapes():
    """Balanced groups of 768 rows on 256-row tiles are whole tiles: 24 visits
    of the chunk's 48 tiles; of 512 rows: 32 of 64."""
    assert gm.visit_counts(12288, 8, 256, 768) == (24, 48)
    assert gm.visit_counts(16384, 16, 256, 512) == (32, 64)
    assert gm.visit_counts(12288, 8, 256, 700) == (29, 48)     # unaligned: shared tiles twice


def test_operands_of_other_shapes_or_dtypes_are_refused():
    x, w, _ = _operands("float32", 32, 128, 128, 4)
    sizes = jnp.asarray([8, 8, 8, 8], jnp.int32)
    with pytest.raises(ValueError, match="one dtype"):
        grouped_matmul(x.astype(jnp.bfloat16), w, sizes)
    with pytest.raises(ValueError, match="sizes"):
        grouped_matmul(x, w, sizes[:3])
    with pytest.raises(ValueError, match="do not contract"):
        grouped_matmul(x, w[:, :64], sizes)


# -- the mutations ---------------------------------------------------------------


@pytest.fixture
def mutated():
    """The kernels' wrappers are jitted: what they traced before a mutation
    must not answer for it, nor the mutant's traces for a later test."""
    gm._gmm.clear_cache(), gm._tgmm.clear_cache()
    yield
    gm._gmm.clear_cache(), gm._tgmm.clear_cache()


def _fails(case, **kw):
    with pytest.raises(AssertionError):
        _check("float32", SIZES[case], ROWS, 256, 384, **kw)


def test_mutation_the_row_mask_of_a_shared_tile_dropped(small_tiles, mutated, monkeypatch):
    """Every row of a visited tile taken for the visit's group: the cases
    whose groups share tiles fail, the aligned ones do not notice."""
    monkeypatch.setattr(gm, "_in_group", lambda start, end, tile, tm, width:
                        jnp.ones((tm, width), bool))
    for case in ("skewed", "empty_group", "half", "full"):
        _fails(case)
    _check("float32", SIZES["equal"], ROWS, 256, 384)


def test_mutation_the_group_of_a_visit_off_by_one(small_tiles, mutated, monkeypatch):
    real = gm._visits

    def next_group(sizes, rows, tm, visit_empty):
        offsets, group, tile, count = real(sizes, rows, tm, visit_empty)
        return offsets, jnp.minimum(group + 1, sizes.shape[0] - 1), tile, count

    monkeypatch.setattr(gm, "_visits", next_group)
    for case in SIZES:
        _fails(case)


def test_mutation_the_empty_group_s_zeroing_removed(small_tiles, mutated, monkeypatch):
    """Without the empty groups' visits their gradient is never stored: it is
    whatever the buffer held (interpret mode hands out NaN), and only the
    cases with an empty group notice."""
    real = gm._visits
    monkeypatch.setattr(gm, "_visits",
                        lambda sizes, rows, tm, visit_empty: real(sizes, rows, tm, False))
    _fails("empty_group")
    _fails("one_group")
    _check("float32", SIZES["full"], ROWS, 256, 384)


# -- the tool's leg ---------------------------------------------------------------


def _flash_bench():
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "tools", "flash_bench.py")
    spec = importlib.util.spec_from_file_location("flash_bench", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_bench_leg_holds_the_kernels_to_ragged_dot_and_names_no_cpu_time(capsys):
    """``tools/flash_bench.py --grouped`` at a tiny size: each of the three
    products agrees with ``jax.lax.ragged_dot``'s on the held rows, and off the
    chip the device time is null, never a host number under its name."""
    import json

    fb = _flash_bench()
    fb.leg_grouped({"tiny": (192, 256, 384, 4)}, 1, 1, True)
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["bench"] == "grouped_matmul" and rec["held_rows"] == 96
    assert set(rec["variants"]) == {"ragged_dot", "kernel"}
    for product in ("fwd", "dx", "dw"):
        assert rec["variants"]["kernel"][product + "_gap"] < 2.0 ** -7
        for variant in rec["variants"].values():
            assert variant[product + "_device_ms"] is None
            assert variant[product + "_mxu_share"] is None


def test_the_bench_s_sizes_are_a_first_step_s_routing():
    """Half the chunk held, unequal, the same for the same seed."""
    fb = _flash_bench()
    for rows, groups in ((12288, 8), (16384, 16)):
        sizes = fb.routed_sizes(rows, groups)
        assert sizes.sum() == rows // 2 and len(set(sizes)) > groups // 2
        assert 1.0 < sizes.max() / sizes.mean() < 1.4 and sizes.min() > 0
        assert (sizes == fb.routed_sizes(rows, groups)).all()
