"""Rows packed of several documents (PR 45): a document mask in the flash
kernels beside the causal mask and beside the window, positions that restart a
document, a loss that leaves out each document's last position; Mellum 2's
layers (sliding-window and full attention at equal heads, plain RoPE / YaRN on
the whole head, a softmax router) against the benchmark's plain float32
reference (``benchmark/reference/mellum2_moe.py``: its own mask on indices and
ids, its own positions, YaRN and router) at small sizes on the CPU.

Kept small on purpose (ROADMAP D20): the interpret-mode kernels at no more than
256 rows and 2 x 1 heads, a compile a case."""

import copy
import functools
import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import horovod_tpu as hvd  # noqa: E402
from benchmark import families, flops_mellum2, harness  # noqa: E402
from benchmark.families_mellum2 import Mellum2, document_ids  # noqa: E402
from benchmark.reference import chain, mellum2_moe as reference  # noqa: E402
from horovod_tpu import trace, training  # noqa: E402
from horovod_tpu.models import transformer  # noqa: E402
from horovod_tpu.models.transformer import (  # noqa: E402
    Transformer, TransformerConfig, next_token_loss,
)
from horovod_tpu.ops import flash_attention as fa  # noqa: E402
from horovod_tpu.parallel.moe import RoutedExperts  # noqa: E402

OPS = chain.Ops("float32")
CELL = "mellum2-12b-a2.5b-pack8192-1chip"
YARN = dict(theta=5e5, factor=16.0, original_max_position_embeddings=32, beta_fast=32.0,
            beta_slow=1.0, attention_factor=1.2772588722239782)
# 160 tokens are two 128-tiles of the flash kernels, the second partial; the
# documents' boundaries lie inside tiles; the longest document (91) is longer
# than the window (48), and YaRN's original context is 32 positions
SEQ, WINDOW, DOCUMENTS = 160, 48, (23, 91, 46)
_TIGHT = {"loss_gap": 2e-6, "grad_norm_gap": 5e-5, "delta_norm_gap": 5e-5,
          "grad_diff_gap": 5e-5}


def _config(impl="flash", **kw):
    """The tiny preset of the cell's shape: a sliding layer and a full one at
    equal heads (a group of 2), every feed-forward routed."""
    base = dict(
        vocab_size=64, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16, hidden_size=32,
        max_seq_len=256, dtype=jnp.float32, attention_impl=impl, rms_norm_eps=1e-6,
        tie_word_embeddings=False, layer_types=("sliding_attention", "full_attention"),
        sliding_window=WINDOW,
        rope_parameters={"full_attention": YARN, "sliding_attention": dict(theta=5e5)},
        num_experts=8, num_experts_per_tok=3, moe_intermediate_size=12, held_experts=(2, 4))
    return TransformerConfig(**{**base, **kw})


def _ids(lengths):
    return jnp.asarray(np.repeat(np.arange(len(lengths)), lengths))[None]


def _tiny_cell(impl, documents=DOCUMENTS):
    """The cell's configuration with every size made tiny (widths too: a test's
    sizes, never a cell's): its first two kinds of layer are a sliding and a
    full one here."""
    config = copy.deepcopy(harness.load_json(ROOT, "benchmark", "configs",
                                             "mellum2-12b-a2.5b.json"))
    config.update(
        hidden_size=32, moe_intermediate_size=12, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, vocab_size=64, router_experts=8, num_experts=4,
        held_experts_first=2, num_experts_per_tok=3, max_position_embeddings=256,
        sliding_window=WINDOW, compute_dtype="float32",
        layer_types=["sliding_attention", "full_attention"])
    config["rope_parameters"]["full_attention"]["original_max_position_embeddings"] = 32
    config["model"] = dict(config["model"], kwargs={"attention_impl": impl})
    config["check"] = dict(config["check"], diff_leaves="", limits=_TIGHT)
    traffic = {"samples_per_chip": 1, "seq_len": SEQ, "documents": list(documents),
               "layout": "dp", "step_options": {}, "span_steps": 1, "trace_steps": 3,
               "check_steps": 1}
    return harness.Cell(
        name="tiny-mellum2-1", config_name="tiny", config=config, traffic_name="tiny",
        traffic=traffic, chips=1,
        end_to_end=["setup_s", "train_tokens_per_s", "step_ms_p90", "mfu"], per_layer=[])


@pytest.fixture
def short_walk(monkeypatch):
    """The kernels' walk two tiles an iteration and then one, for the file's
    seconds: the interpret-mode kernels compile a tile's body once an entry of
    the ladder (15 bodies a kernel at the program's (8, 4, 2, 1)), and the mask
    these tests hold is in each body alike."""
    monkeypatch.setattr(fa, "_TILES_AN_ITERATION", (2, 1))


# -- the program against the reference, and one compiled step for every layout --------


def test_family_through_the_harness_matches_the_reference_and_a_second_layout_compiles_nothing():
    """The family through the harness's own set-up (``hvd.init`` ->
    ``create_train_state`` -> ``replicate_state`` -> ``data_parallel_train_step``,
    the step compiled ONCE), its first steps against the plain reference at
    float32 through ``harness.compare``; then another layout of the same shapes
    through the same compiled step: no compile, another loss.  'dot' here, for
    the file's seconds: 'flash' is held to 'dot' below, kernel by kernel and as
    a model, and runs this same comparison in
    ``benchmark/tests/test_mellum2_family.py`` and on the chip."""
    cell = _tiny_cell("dot")
    device = jax.devices()[0]
    p = harness.prepare(cell, 7, [device])
    other = dict(cell.traffic, documents=[64, 5, 70, 21])
    inputs, labels = jax.jit(lambda key: Mellum2.batch(key, cell.config, other, 1))(
        jax.random.PRNGKey(3))
    assert inputs.shape == p.inputs.shape and not np.array_equal(inputs[:, 1], p.inputs[:, 1])
    inputs, labels = jax.tree_util.tree_map(
        lambda new, old: jax.device_put(new, old.sharding), (inputs, labels),
        (p.inputs, p.labels))
    compiles = p.meter.snapshot()[0]
    _, loss = p.call(p.state, inputs, labels)
    assert np.isfinite(float(loss)) and p.meter.snapshot()[0] == compiles
    assert abs(float(loss) - p.first["losses"][-1]) > 1e-4      # the layout is seen
    ref = harness.run_reference(cell, 7, device,
                                other_first_gradient=p.first["first_gradient"])
    rows = harness.compare(p.first, ref, _TIGHT, ref["grad_diff_norms"])
    assert all(r["ok"] for r in rows), rows


# -- a packed row is its documents, each alone ---------------------------------------


@pytest.fixture(scope="module")
def tiny_params():
    """The tiny preset's parameters: they know neither a row's length nor the
    kind of attention."""
    return jax.jit(Transformer(_config("dot")).init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_a_packed_row_equals_its_documents_run_alone(short_walk, tiny_params):
    """Each document's logits in the packed row are the UNPACKED model's on that
    document from position 0 (no ids at all: the document first in a row, whose
    later tokens a causal model's earlier logits do not see), under a window
    shorter than the longest document, 'dot'; 'flash' gives the packed row's
    logits as 'dot' does, layout for layout; one compiled call serves every
    layout; the model counts the row's documents and says so in its events.
    168 tokens: a length no other test of this file traces the kernels at."""
    documents = DOCUMENTS + (8,)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, SEQ + 8), 0, 64)
    both = transformer._packed_input(jnp.stack([ids, _ids(documents)], axis=1))
    assert all(np.array_equal(a, b) for a, b in zip(both, (ids, _ids(documents))))  # one array
    dot, flash = Transformer(_config("dot")), Transformer(_config("flash"))
    packed = {impl: jax.jit(lambda t, d, model=model: model.apply(tiny_params, (t, d)))
              for impl, model in (("dot", dot), ("flash", flash))}
    plain = jax.jit(lambda t: dot.apply(tiny_params, t)[0])
    logits, aux = packed["dot"](ids, _ids(documents))
    assert aux["documents"].tolist() == [len(documents)]
    start = 0
    for length in documents:
        alone = plain(jnp.roll(ids, -start, axis=1))[:, :length]
        np.testing.assert_allclose(logits[:, start:start + length], alone, atol=2e-5)
        start += length
    whole = plain(ids)      # and without ids the row is one document
    assert float(jnp.max(jnp.abs(whole[:, documents[0]:] - logits[:, documents[0]:]))) > 1e-3
    t0 = trace.now()
    for layout in (documents, (70, 5, 93)):
        got, want = (packed[impl](ids, _ids(layout))[0] for impl in ("flash", "dot"))
        np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(jnp.max(jnp.abs(want - logits))) > 1e-3
    assert all(f._cache_size() == 1 for f in packed.values())  # a layout is data
    events = [(r[0], r[3]) for r in trace.snapshot(t0)]
    (layers,) = [args["layers"] for name, args in events if name == "attn.layers"]
    assert [(l["kind"], l["window"], l["rope_type"], l["rotary_columns"], l["documents"])
            for l in layers] == [("sliding_attention", WINDOW, "default", 16, True),
                                 ("full_attention", None, "yarn", 16, True)]
    tiles = [args for name, args in events if name == "flash.tiles"]
    assert {t["kernel"] for t in tiles} == {"flash_attention_fwd"}
    assert all(t["documents"] and t["at_most"] for t in tiles)
    assert {t["window"] for t in tiles} == {None, WINDOW}


def test_positions_restart_at_each_document_whatever_the_ids_are():
    """A document is a run of equal ids (any integers): positions count from
    where the run begins.  No comparison of outputs can hold this (rotary
    scores see distances alone), so the values are held here."""
    ids = jnp.asarray([[7, 7, 7, 2, 2, 9, 9, 9, 9], [5, 1, 1, 1, 1, 1, 1, 0, 0]])
    assert transformer.document_positions(ids).tolist() == [
        [0, 1, 2, 0, 1, 0, 1, 2, 3], [0, 0, 1, 2, 3, 4, 5, 0, 1]]
    assert transformer.document_starts(ids).sum(axis=1).tolist() == [3, 3]


# -- the three kernels with ids --------------------------------------------------------


def _masked_dot(q, k, v, documents, window):
    """Plain attention under ``same document and k <= q and q - k < window``."""
    s, group = q.shape[1], q.shape[2] // k.shape[2]
    index = jnp.arange(s)
    back = index[:, None] - index[None, :]
    seen = (back >= 0) if window is None else (back >= 0) & (back < window)
    seen = seen[None] & (documents[:, :, None] == documents[:, None, :])
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(seen[:, None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _runs(*layouts):
    return jnp.concatenate([_ids(lengths) for lengths in layouts])


# ids that are no packed row's: a document that comes back after another (A B
# A), ids that fall run by run, ids shuffled position by position, three ids
# drawn a position.  A bound taken from where a row's runs start would drop
# tiles of these in which equal ids meet
_NOT_RUNS = (
    jnp.stack([jnp.asarray(np.repeat([5, 2, 5], [170, 130, 200])),
               jnp.asarray(np.repeat([9, 7, 4, 1, 0], [83, 160, 7, 164, 86]))]),
    jnp.stack([jnp.asarray(np.random.default_rng(1).permutation(
                   np.repeat(np.arange(4), 125))),
               jnp.asarray(np.random.default_rng(2).integers(0, 3, 500))]))


@pytest.mark.parametrize("window,form,block,layouts", [
    (None, "group", 128, (_runs((37, 110, 53), (129, 3, 68)), _runs((200,), (1, 198, 1)))),
    (40, "head", 128, (_runs((37, 110, 53), (129, 3, 68)), _runs((200,), (1, 198, 1)))),
    (None, "group", 32, _NOT_RUNS), (None, "head", 32, _NOT_RUNS),
    (40, "group", 32, _NOT_RUNS), (40, "head", 32, _NOT_RUNS)],
    ids=["causal-group", "window-head", "causal-group-not-runs", "causal-head-not-runs",
         "window-group-not-runs", "window-head-not-runs"])
def test_kernels_with_ids_give_the_masked_dot_attention_s_gradients(window, form, block, layouts,
                                                                    monkeypatch, short_walk):
    """The forward, dQ and dK/dV kernels with ids against ``jax.grad`` of the
    masked dot attention: 200 rows in 128-tiles (the second partial), 2 query
    heads over 1, boundaries inside tiles, two rows of two layouts; the dK/dV
    kernel in both forms (the whole group a program, or a head a program where
    the group's bytes pass ``_DKV_GROUP_BYTES``).  The ids are arguments of the
    compiled call: other layouts run through it and compile nothing.  Since
    PR 46 the ids bound every program's walk, safely for ANY ids: the cases
    that are no runs (``_NOT_RUNS``) walk sixteen 32-tiles under both masks in
    both forms, 500 rows (under the window a forward or dQ program is the walk
    of four query tiles, each under its own document bounds)."""
    if form == "head":
        monkeypatch.setattr(fa, "_DKV_GROUP_BYTES", 0)
    assert fa._dkv_heads_a_program(2, 256, 16, 16, 4)[0] == (2 if form == "group" else 1)
    rows = layouts[0].shape[1]
    keys = jax.random.split(jax.random.PRNGKey(4), 4)
    q = jax.random.normal(keys[0], (2, rows, 2, 16))
    k, v = (jax.random.normal(key, (2, rows, 1, 16)) for key in keys[1:3])
    weight = jax.random.normal(keys[3], q.shape)
    with_ids = jax.jit(jax.value_and_grad(lambda q, k, v, documents: jnp.sum(
        weight * fa.flash_attention(q, k, v, window=window, documents=documents,
                                    block_q=block, block_k=block)), argnums=(0, 1, 2)))
    plain = jax.jit(jax.value_and_grad(lambda q, k, v, documents: jnp.sum(
        weight * _masked_dot(q, k, v, documents, window)), argnums=(0, 1, 2)))
    for documents in layouts:
        got, want = with_ids(q, k, v, documents), plain(q, k, v, documents)
        for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)
    assert with_ids._cache_size() == 1


# -- the tiles a row's ids let meet: every program's bounds (PR 46) ----------------------


def _allowed_tiles(ids, block_q, block_k, causal, window):
    """(query tiles, key tiles) bool by brute force over every (query, key)
    pair: whether the tile holds a pair that the whole mask allows."""
    s = len(ids)
    back = np.arange(s)[:, None] - np.arange(s)[None, :]
    seen = ids[:, None] == ids[None, :]
    if causal:
        seen &= back >= 0
    if window is not None:
        seen &= (back < window) & (back > -window)
    pad = lambda n, block: (-n) % block
    seen = np.pad(seen, ((0, pad(s, block_q)), (0, pad(s, block_k))))
    return seen.reshape(-1, block_q, seen.shape[1] // block_k, block_k).any(axis=(1, 3))


def _joined_ranges(ids, block_q, block_k, causal, window):
    """Each query tile's and each key tile's joined ``(lo, hi)``: the kernels'
    own arithmetic (``_document_bounds`` and ``_tile_ranges`` on numpy)."""
    s = len(ids)
    n_q, n_k = -(-s // block_q), -(-s // block_k)
    by_query, by_key = fa._document_bounds(ids[None], block_q, block_k, xp=np)
    mask = dict(causal=causal, window=window, kv_off=0, bd=None, xp=np)
    ((q_lo, q_hi),) = fa._tile_ranges(np.arange(n_q) * block_q, block_q, block_k, n_k, s,
                                      rows_are_queries=True, doc=by_query, **mask)
    ((k_lo, k_hi),) = fa._tile_ranges(np.arange(n_k) * block_k, block_k, block_q, n_q, s,
                                      rows_are_queries=False, doc=by_key, **mask)
    return (q_lo[0], q_hi[0]), (k_lo[0], k_hi[0])


def test_the_cell_s_row_visits_144_causal_and_113_window_tiles_a_head():
    """The benchmark's eleven lengths at 256-tiles: of a head's 528 causal tile
    visits 144 hold a pair its documents allow and of the 1,024 window's 150,
    113; brute force over every pair, the bound arithmetic on the query side
    (forward, dQ) and on the key side (dK/dV), and ``tile_counts`` with the
    ids all say so."""
    ids = document_ids(harness.load_cell(CELL).traffic).astype(np.int32)
    for window, bound, want in ((None, 528, 144), (1024, 150, 113)):
        assert int(_allowed_tiles(ids, 256, 256, True, window).sum()) == want
        for lo, hi in _joined_ranges(ids, 256, 256, True, window):
            assert int(np.maximum(hi - lo, 0).sum()) == want
        shape = dict(s_q=8192, s_k=8192, block_q=256, block_k=256, seq_len=8192, window=window)
        counts = fa.tile_counts(documents=ids, **shape)
        assert [counts[k][0] for k in ("fwd", "bwd_dq", "bwd_dkv")] == [want] * 3
        assert fa.tile_counts(**shape)["fwd"][0] == bound
        # the heads and query tiles a program walks as one, and rows that add up
        held = fa.tile_counts(documents=np.stack([ids, ids]), heads_a_program=8,
                              query_tiles_a_program=2, **shape)
        assert (held["fwd"][0], held["bwd_dkv"][0]) == (2 * want, 2 * 8 * want)


def _layout(kind, seed, s=200):
    rng = np.random.default_rng(seed)
    if kind == "one":
        return np.zeros(s, np.int64)
    if kind == "tokens":                        # every document one token
        return np.arange(s)
    if kind == "on-tiles":                      # every boundary on a 32-tile
        cuts = np.sort(rng.choice(np.arange(1, s // 32 + 1), 3, replace=False)) * 32
    elif kind == "off-tiles":
        cuts = np.sort(rng.choice(np.arange(1, s), rng.integers(1, 12), replace=False))
    if kind in ("on-tiles", "off-tiles"):
        return np.repeat(np.arange(len(cuts) + 1), np.diff([0, *cuts, s]))
    if kind == "came-back":                     # A B A
        a, b = sorted(rng.choice(np.arange(1, s), 2, replace=False))
        return np.repeat([3, 1, 3], [a, b - a, s - b])
    if kind == "falling":
        return _layout("off-tiles", seed, s)[::-1].copy()
    return rng.integers(0, 4, s)                # "any"


@pytest.mark.parametrize("kind", ["one", "tokens", "on-tiles", "off-tiles", "came-back",
                                  "falling", "any"])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 40), (False, None), (False, 40)],
                         ids=["causal", "causal-window", "none", "window"])
def test_the_joined_ranges_keep_every_tile_in_which_the_mask_allows_a_pair(kind, causal, window):
    """Whatever the ids: every tile pair in which the whole mask (ids, causal,
    window, padding) allows an element lies inside its query tile's joined
    range and inside its key tile's, by brute force over the pairs, at square
    and at unequal tiles; and for a packed row (ids that rise run by run) a
    range that is not empty holds no empty tile at either end."""
    for seed in range(3):
        ids = _layout(kind, seed)
        for block_q, block_k in ((32, 32), (16, 64)):
            allowed = _allowed_tiles(ids, block_q, block_k, causal, window)
            by_query, by_key = _joined_ranges(ids, block_q, block_k, causal, window)
            for (lo, hi), sees in ((by_query, allowed), (by_key, allowed.T)):
                other = np.arange(sees.shape[1])
                inside = (other >= lo[:, None]) & (other < hi[:, None])
                assert not (sees & ~inside).any(), (kind, seed, block_q, block_k)
                if kind in ("one", "tokens", "on-tiles", "off-tiles"):
                    some = hi > lo
                    assert sees[some, lo[some]].all() and sees[some, hi[some] - 1].all()
                    assert (some == sees.any(axis=1)).all()


def test_the_tiles_event_of_a_call_with_ids_keeps_the_static_counts_and_says_at_most():
    """The visits of a call with ids are data: its ``flash.tiles`` events keep
    the other masks' counts (what the same call without ids records) and say
    that they only bound the walk; a call without ids says nothing new."""
    def traced(documents):
        t0 = trace.now()
        q = jnp.ones((1, 384, 2, 16), jnp.float32)
        jax.make_jaxpr(jax.grad(lambda a: fa.flash_attention(
            a, a[:, :, :1], a[:, :, :1], block_q=128, block_k=128, window=130,
            documents=documents).sum()))(q)
        return {r[3]["kernel"]: r[3] for r in trace.snapshot(t0) if r[0] == "flash.tiles"}

    with_ids, without = traced(_ids((188, 196))), traced(None)
    assert sorted(with_ids) == sorted(without) == [
        "flash_attention_bwd_dkv", "flash_attention_bwd_dq", "flash_attention_fwd"]
    for name, event in with_ids.items():
        assert event["documents"] and event["at_most"] is True
        assert not without[name]["documents"] and "at_most" not in without[name]
        assert (event["visited"], event["iterations"]) == (
            without[name]["visited"], without[name]["iterations"])
    made = fa.tile_counts(384, 384, 128, 128, 384, window=130,
                          documents=np.asarray(_ids((188, 196)))[0])
    assert made["fwd"][0] == 5 < with_ids["flash_attention_fwd"]["visited"] == 6


def test_the_device_s_bounds_are_the_host_s_and_lie_under_the_docmask_scope():
    """``_document_operands`` under ``jit`` (what ``flash_attention`` hands its
    kernels: the ids and both sides' bounds, once a call) against
    ``_document_bounds`` on numpy, for rows of every kind at once; the
    comparison's operations carry the ``attn_docmask`` scope."""
    rows = np.stack([_layout(kind, 1) for kind in
                     ("one", "tokens", "on-tiles", "off-tiles", "came-back", "falling", "any")])
    on_device = jax.jit(functools.partial(fa._document_operands, block_q=32, block_k=64))
    ids, by_query, by_key = on_device(jnp.asarray(rows, jnp.int32))
    want = fa._document_bounds(rows, 32, 64, xp=np)
    np.testing.assert_array_equal(ids, rows)
    for got, bounds in zip((by_query, by_key), want):
        for a, b in zip(got, bounds):
            assert a.dtype == jnp.int32 and a.shape == b.shape
            np.testing.assert_array_equal(a, b)
    assert by_query[0].shape == (7, 7) and by_key[0].shape == (7, 4)
    text = on_device.lower(jnp.asarray(rows, jnp.int32)).compile().as_text()
    named = re.findall(r'op_name="(jit[^"]*)"', text)    # a reduction's body has a bare name
    assert named and all("/attn_docmask/" in name for name in named)


# -- YaRN on the whole head, the share of the experts, the count -----------------------


def test_yarn_on_the_whole_head_at_the_published_values_needs_no_code():
    """The full layers' ``rope_parameters`` as published (factor 16 over 8,192,
    ``beta_fast`` 32, ``beta_slow`` 1, theta 500,000, no partial factor): the
    program's frequencies over all 128 columns are the reference's own formula's,
    pairs below the ramp keep theirs and pairs above turn 16 x slower, and cos
    and sin carry the published ``attention_factor`` 0.1 ln 16 + 1."""
    config = harness.load_cell(CELL).config
    full = config["rope_parameters"]["full_attention"]
    cfg = Mellum2.model(config).cfg
    own = cfg.layer_rope("full_attention")
    rot, freqs, scale = transformer._rotary_terms(cfg, own)
    assert (rot, own.rope_type) == (128, "yarn")
    assert scale == full["attention_factor"] and abs(scale - (0.1 * np.log(16) + 1)) < 1e-12
    want = reference.inverse_frequencies(128, reference.rope_static(full))
    np.testing.assert_allclose(freqs, want, rtol=1e-6)
    plain = 5e5 ** (-np.arange(0, 128, 2) / 128)
    np.testing.assert_allclose(freqs[:16], plain[:16], rtol=1e-6)
    np.testing.assert_allclose(freqs[-16:], plain[-16:] / 16, rtol=1e-6)
    assert cfg.layer_rope("sliding_attention").rope_type == "default"
    assert transformer._rotary_terms(cfg, cfg.layer_rope("sliding_attention"))[2] is None


def test_all_four_shares_of_sixteen_experts_sum_to_the_uncut_layer():
    """64 experts, 8 a token, softmax scores renormalised over the chosen, cut
    as the cell cuts them (an even share a chip): the four shares of 16 experts
    each, each computed by the program's layer told which experts it holds, add
    up to the reference's feed-forward that holds all 64."""
    experts, top_k, width, ff, shares = 64, 8, 16, 8, 4
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 24, width))
    whole = RoutedExperts(experts, top_k, width, ff, dtype=jnp.float32)
    moe = whole.init(jax.random.PRNGKey(5), x)["params"]
    moe = dict(moe, router={"kernel": 3.0 * moe["router"]["kernel"]})
    want, _ = reference.routed_feed_forward(
        OPS, moe, x.reshape(-1, width), top_k, 0, jax.nn.silu)
    total, assigned = 0.0, 0
    for share in range(shares):
        first, count = share * experts // shares, experts // shares
        own = dict(moe, **{k: moe[k][first:first + count]
                           for k in ("w_gate", "w_up", "w_down")})
        y, out = RoutedExperts(experts, top_k, width, ff, held=(first, count),
                               dtype=jnp.float32).apply({"params": own}, x)
        total, assigned = total + y, assigned + int(out["assigned"])
        assert int(out["dropped"]) == 0
    assert assigned == 24 * top_k                # every assignment on exactly one share
    np.testing.assert_allclose(total.reshape(-1, width), want, atol=3e-6)


def test_required_flops_pairs_and_the_cut_are_the_issue_s():
    cell = harness.load_cell(CELL)
    config, traffic = cell.config, cell.traffic
    assert traffic["documents"] == [557, 2909, 131, 1087, 293, 1523, 72, 811, 241, 389, 179]
    assert sum(traffic["documents"]) == traffic["seq_len"] == 8192
    assert all(sum(traffic["documents"][:i]) % 256 for i in range(1, 11))  # none on a tile
    assert flops_mellum2.layers(config) == ["sliding_attention"] * 3 + ["full_attention"]
    assert flops_mellum2.mask_pairs("full_attention", config, traffic) == 6_644_589
    assert flops_mellum2.mask_pairs("sliding_attention", config, traffic) == 4_740_268
    per_token = flops_mellum2.train_flops_per_token(config, traffic)
    assert per_token == families.flops_per_sample(config, traffic)
    assert abs(per_token - 1.275e9) < 0.001e9 and abs(per_token * 8192 - 10.45e12) < 0.01e12
    assert 6 * flops_mellum2.attention_matrix_params(config) == 6 * 21_233_664
    window = flops_mellum2.window_attention_train_flops_per_step(config, traffic, 1)
    full = flops_mellum2.full_attention_train_flops_per_step(config, traffic, 1)
    assert window == 12 * 128 * 32 * 4_740_268 * 3 and full == 12 * 128 * 32 * 6_644_589
    assert flops_mellum2.expert_ffn_train_flops_per_step(config, traffic, 1) == \
        4 * 6.0 * 3 * 2304 * 896 * 16384
    # the batch: ids over document ids, 8,181 weighted labels, the boundaries as data
    inputs, (targets, weights) = jax.eval_shape(
        lambda key: Mellum2.batch(key, config, traffic, 1), jax.random.PRNGKey(0))
    assert inputs.shape == (1, 2, 8192) and targets.shape == weights.shape == (1, 8192)
    documents = document_ids(traffic)
    assert documents[0] == 0 and documents[-1] == 10 and int(np.sum(np.diff(documents))) == 10
    # the cut: one whole period, 16 of 64 experts, a quarter of the vocabulary
    assert (config["num_hidden_layers"], config["num_experts"], config["router_experts"],
            config["vocab_size"] * 4) == (4, 16, 64, 98304)
    assert config["published"] == {"num_hidden_layers": 28, "num_experts": 64,
                                   "vocab_size": 98304}
    assert (config["hidden_size"], config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["sliding_window"]) == (2304, 32, 4, 128, 896, 8, 1024)
    shapes = jax.eval_shape(
        lambda key: Mellum2.model(config).init(key, jnp.zeros((1, 2, 8192), jnp.int32)),
        jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == \
        config["parameters"] == 595_153_152


# -- refusals ---------------------------------------------------------------------------


_DENSE = dict(num_experts=None, held_experts=None, moe_intermediate_size=None)
_ONE_KIND = dict(layer_types=None, sliding_window=None, rope_parameters=None, **_DENSE)


@pytest.mark.parametrize("kw,call,message", [
    (dict(_ONE_KIND, attention_impl="ring", seq_axis_name="sp"), {},
     "take no attention_impl 'ring': the ring rotates keys and values without their ids"),
    (dict(_ONE_KIND, shard_axis="tp"), {}, "take no shard_axis"),
    (_DENSE, dict(train=False, paged=object()), "take no paged serving: a cache row is one"),
    (dict(_ONE_KIND, num_kv_heads=None, kv_lora_rank=16, qk_nope_head_dim=8,
          qk_rope_head_dim=4, v_head_dim=8), {}, "take no latent attention"),
    (dict(_ONE_KIND, block_diffusion=4), {}, "take no block_diffusion"),
    (dict(_DENSE, layer_types=("linear_attention", "full_attention"), sliding_window=None,
          rope_parameters=None, linear_num_key_heads=2, linear_key_head_dim=8,
          linear_num_value_heads=4, linear_value_head_dim=8), {},
     "take no 'linear_attention' layer: a recurrence's state"),
], ids=["ring", "shard_axis", "paged", "latent", "block_diffusion", "gated_delta_net"])
def test_paths_that_cannot_honour_document_ids_refuse_them_with_their_reason(kw, call, message):
    model = Transformer(_config("dot", **kw))
    ids = jnp.zeros((1, 16), jnp.int32)
    with pytest.raises(ValueError, match=message) as refusal:
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), (ids, ids), **call))
    assert str(refusal.value).startswith("document ids (packed rows) take no ")


def test_the_kernels_refuse_ids_at_latent_widths_and_under_block_diffusion():
    q = jnp.zeros((1, 16, 2, 24))
    ids = jnp.zeros((1, 16), jnp.int32)
    with pytest.raises(ValueError, match="latent attention\\) take no documents"):
        fa.flash_attention(q, q, q[..., :16], documents=ids)
    with pytest.raises(ValueError, match="block_diffusion takes no documents"):
        fa.flash_attention(q, q, q, block_diffusion=(8, 4), documents=ids)
    with pytest.raises(ValueError, match="documents are a row's integer ids"):
        fa.flash_attention(q, q, q, documents=ids[:, :8])


# -- a model without ids is the program it was -------------------------------------------

# sha256 of the step below lowered at this PR's parent (commit 9556487), these
# lines run in that tree
_STEP_AT_THE_PARENT = "fbeb608829bfa719f82bd531edd50ad9f545dae3a7db913eeb73af76ff2e4776"


def test_a_model_without_ids_lowers_to_the_parent_s_step_text():
    """A train step of the tiny preset's sliding layer over a dense feed-forward
    ('flash', the three kernels in interpret mode) on plain tokens: no operand,
    no mask term and no scope of the document path is in it."""
    tokens = jnp.zeros((1, SEQ), jnp.int32)
    cfg = _config(num_layers=1, layer_types=("sliding_attention",), **_DENSE)
    model, optimizer = Transformer(cfg), optax.adamw(1e-3)
    state = jax.eval_shape(lambda: training.create_train_state(
        model, optimizer, jax.random.PRNGKey(0), tokens))
    step = training.data_parallel_train_step(
        model, optimizer, mesh=Mesh(np.array(jax.devices()[:1]), (hvd.WORLD_AXIS,)),
        loss_fn=functools.partial(next_token_loss, aux_coef=0.001))
    text = step.lower(state, tokens, tokens).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == _STEP_AT_THE_PARENT
