"""The routed feed-forward, the block-diffusion mask kind and the
masked-diffusion loss (PR 28): the program against the benchmark's plain
float32 reference, at small sizes on the CPU."""

import functools
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import flops_sdar, harness  # noqa: E402
from benchmark.reference import chain, sdar_moe as reference  # noqa: E402
from horovod_tpu.models import transformer  # noqa: E402
from horovod_tpu.models.transformer import (  # noqa: E402
    Transformer, TransformerConfig, block_diffusion_mask,
)
from horovod_tpu.ops.flash_attention import flash_attention  # noqa: E402
from horovod_tpu.parallel.moe import RoutedExperts  # noqa: E402


def _tiny_config(dtype="float32"):
    """The cell's configuration with every size made tiny (widths too: a
    test's sizes, never a cell's)."""
    config = harness.load_json(ROOT, "benchmark", "configs", "sdar-30b-a3b.json")
    config.update(
        hidden_size=32, head_dim=8, num_attention_heads=8, num_key_value_heads=2,
        moe_intermediate_size=24, num_hidden_layers=2, vocab_size=64,
        mask_token_id=63, router_experts=16, num_experts=4, held_experts_first=4,
        num_experts_per_tok=4, max_position_embeddings=64, compute_dtype=dtype)
    return config


_TRAFFIC = {"samples_per_chip": 1, "seq_len": 40, "block_length": 4, "t_min": 0.001,
            "layout": "dp", "step_options": {}, "span_steps": 2, "trace_steps": 3}


# -- the family through the harness: loss and every leaf's gradient -----------


@pytest.mark.parametrize("chips", [1, 4])
def test_family_through_run_cell_matches_the_reference_at_float32(chips):
    config = _tiny_config()
    config["check"] = dict(config["check"], limits={
        "loss_gap": 2e-6, "grad_norm_gap": 5e-5, "delta_norm_gap": 5e-5,
        "grad_diff_gap": 5e-5})
    cell = harness.Cell(
        name=f"tiny-sdar-{chips}", config_name="tiny", config=config,
        traffic_name="tiny", traffic=_TRAFFIC, chips=chips,
        end_to_end=["setup_s", "train_tokens_per_s", "step_ms_p90", "mfu"], per_layer=[])
    harness.check_names(cell)
    result = harness.run_cell(cell, seed=2 ** 31 + 28, seconds=0.3, trace=False,
                              devices=jax.devices()[:chips])
    assert result["correct"], json.dumps(result["checks"])
    assert result["checks"]["grad_diff_gap"]["value"] < 5e-5
    assert set(reference.REFERENCE_ROUTING) == {0, 1}


def test_the_control_precision_fails_the_same_comparison():
    """The reference at bfloat16 operands is not the float32 program."""
    config = _tiny_config()
    cell = harness.Cell(
        name="tiny-sdar-control", config_name="tiny", config=config, traffic_name="tiny",
        traffic=_TRAFFIC, chips=1, end_to_end=[], per_layer=[])
    sound = harness.run_reference(cell, 7, jax.devices()[0], keep_first_gradient=True)
    control = harness.run_reference(cell, 7, jax.devices()[0], precision="bfloat16",
                                    other_first_gradient=sound["first_gradient"])
    share, _ = harness.worst_leaf_diff(control["grad_diff_norms"], sound["grad_norms"])
    assert share > 1e-3


def test_batch_is_block_diffusion_input():
    from benchmark.families_sdar import SdarMoe

    config = _tiny_config()
    inputs, (targets, weights) = SdarMoe.batch(
        jax.random.PRNGKey(3), config, dict(_TRAFFIC, seq_len=38), 5)
    length = 38
    assert inputs.shape == (5, 2 * length) and targets.shape == weights.shape == (5, length)
    xt, x0 = np.asarray(inputs[:, :length]), np.asarray(inputs[:, length:])
    w = np.asarray(weights)
    assert (x0 == np.asarray(targets)).all() and x0.max() < config["mask_token_id"]
    masked = xt == config["mask_token_id"]
    assert (xt[~masked] == x0[~masked]).all() and masked.any() and (~masked).any()
    assert (w[~masked] == 0).all() and (w[masked] >= 1.0).all()
    # one t a block: the weights of a block's masked positions agree
    for row in range(5):
        for start in range(0, length, 4):
            values = w[row, start:start + 4][masked[row, start:start + 4]]
            assert np.allclose(values, values[:1])
    assert len({tuple(r) for r in x0}) == 5


# -- the flash mask kind against the dense mask -------------------------------


def _dense_attention(q, k, v, mask):
    group = q.shape[2] // k.shape[2]
    kk, vv = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(mask[None, None], logits, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, vv)


@pytest.mark.parametrize("half,block,heads,kv,tile", [
    (200, 4, 4, 2, 128),    # L no multiple of the tile; a tile straddles L
    (160, 32, 8, 1, 128),   # B 32, eight query heads a key/value head
    (72, 4, 2, 2, 256),     # one tile holds both halves
    (130, 7, 2, 1, 128),    # B divides neither L nor the tile
    (384, 4, 4, 2, 128),    # the cell's kind, three tiles a half: loop
                            # iterations of four, two and one tile
    (320, 32, 2, 1, 128),   # the same where a tile straddles L
])
def test_flash_block_diffusion_matches_the_dense_mask(half, block, heads, kv, tile):
    keys = jax.random.split(jax.random.PRNGKey(half), 4)
    q = jax.random.normal(keys[0], (1, 2 * half, heads, 16))
    k = jax.random.normal(keys[1], (1, 2 * half, kv, 16))
    v = jax.random.normal(keys[2], (1, 2 * half, kv, 16))
    w = jax.random.normal(keys[3], q.shape)
    mask = block_diffusion_mask(half, block)
    assert mask.sum() == flops_sdar.allowed_pairs(half, block)
    assert mask.diagonal().all()   # every row sees at least itself

    def flash(q, k, v):
        return flash_attention(q, k, v, block_q=tile, block_k=tile,
                               block_diffusion=(half, block))

    np.testing.assert_allclose(flash(q, k, v), _dense_attention(q, k, v, mask), atol=5e-6)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_dense_attention(*a, mask) * w), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5)


def _bd_grads(half, block, heads, kv, dtype, fn=None):
    """dq, dk, dv of ``fn`` (default: the flash kernels in 128-tiles) under
    the block-diffusion mask, of one weighted sum of its output."""
    keys = jax.random.split(jax.random.PRNGKey(half + heads), 4)
    q, k, v = (jax.random.normal(key, (1, 2 * half, n, 16)).astype(dtype)
               for key, n in zip(keys, (heads, kv, kv)))
    w = jax.random.normal(keys[3], q.shape)
    if fn is None:
        fn = lambda q, k, v: flash_attention(
            q, k, v, block_q=128, block_k=128, block_diffusion=(half, block))
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w), (0, 1, 2))(q, k, v)


@pytest.fixture
def dkv_form(monkeypatch):
    """``dkv_form(one_head)`` chooses the dK/dV form by the module's own
    threshold, and ``dkv_form.traced()`` says what the kernels traced since
    held: ``heads_a_program`` of each ``flash_attention_bwd_dkv_bd`` event.
    The jitted entry caches its trace by arguments and not by the module's
    globals, so the cache is emptied around the change."""
    from horovod_tpu import trace
    from horovod_tpu.ops import flash_attention as fa

    def choose(one_head):
        fa.flash_attention.clear_cache()
        if one_head:
            monkeypatch.setattr(fa, "_DKV_GROUP_BYTES", 0)
        choose.t0 = trace.now()

    choose.traced = lambda: [
        r[3]["heads_a_program"] for r in trace.snapshot(choose.t0)
        if r[0] == "flash.tiles" and r[3]["kernel"] == "flash_attention_bwd_dkv_bd"]
    yield choose
    fa.flash_attention.clear_cache()


@pytest.mark.parametrize("one_head", [False, True],
                         ids=["dkv_whole_group", "dkv_one_head_a_program"])
@pytest.mark.parametrize("heads,kv", [(8, 1), (2, 2)], ids=["group_8", "group_1"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 6e-2)],
                         ids=["float32", "bfloat16"])
def test_flash_block_diffusion_dkv_forms_match_the_dense_mask(
        dkv_form, dtype, tol, heads, kv, one_head):
    """dq, dk, dv under the mask against the dense mask, dK/dV by both forms
    of its kernel: the whole query-head group a program (what the group's
    bytes allow: the cell's 64 MiB), and one query head a program with the
    sums in VMEM scratch (forced by a threshold of 0, as for the causal mask
    in ``test_flash_256_wide_heads_match_dense``).  400 rows in 128-tiles: a
    length that is no multiple of the tile, a tile that straddles L.  A group
    of one has one form."""
    half, block = 200, 4
    dkv_form(one_head)
    got = _bd_grads(half, block, heads, kv, dtype)
    assert dkv_form.traced() == [1 if one_head else heads // kv]
    mask = block_diffusion_mask(half, block)
    want = _bd_grads(half, block, heads, kv, dtype, lambda q, k, v: _dense_attention(
        *(x.astype(jnp.float32) for x in (q, k, v)), mask))
    for a, b in zip(got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, atol=tol * max(1.0, np.abs(b).max()))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_flash_block_diffusion_dkv_forms_agree_bit_for_bit(dkv_form, dtype):
    """The same tiles in the same order a head, the heads summed in the same
    order, in float32: the two forms of the dK/dV kernel give the same dk and
    dv to the last bit (the scratch the head form carries between its
    programs is the carry of the group form's loop)."""
    dkv_form(False)
    group = _bd_grads(200, 4, 8, 1, dtype)
    dkv_form(True)
    head = _bd_grads(200, 4, 8, 1, dtype)
    assert dkv_form.traced() == [1]
    for a, b in zip(group, head):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_flash_block_diffusion_refuses_what_it_cannot_mask():
    q = jnp.zeros((1, 16, 2, 8))
    with pytest.raises(ValueError, match="2 L"):
        flash_attention(q, q, q, block_diffusion=(7, 4))
    with pytest.raises(ValueError, match="no window"):
        flash_attention(q, q, q, window=4, block_diffusion=(8, 4))
    with pytest.raises(ValueError, match="block_diffusion"):
        TransformerConfig(block_diffusion=4, attention_impl="ring")


@pytest.mark.parametrize("length,block", [(8, 4), (40, 4), (4096, 4), (37, 5), (96, 32), (5, 8)])
def test_allowed_pairs_is_the_brute_force_count(length, block):
    pairs = flops_sdar.allowed_pairs(length, block)
    if length <= 128:
        assert pairs == int(block_diffusion_mask(length, block).sum())
        assert pairs == int(reference.allowed(length, block).sum())
    if length % block == 0:
        assert pairs == length * length + length * block


def test_required_flops_are_the_issue_s_count():
    cell = harness.load_cell("sdar-30b-a3b-bd4-s4096-1chip")
    per_token = flops_sdar.train_flops_per_token(cell.config, cell.traffic)
    assert abs(per_token - 3.16e9) < 0.01e9
    attention = flops_sdar.bd_attention_train_flops_per_step(cell.config, cell.traffic, 1)
    assert abs(attention / 4096 / per_token - 0.38) < 0.01
    experts = flops_sdar.expert_ffn_train_flops_per_step(cell.config, cell.traffic, 1)
    assert experts == 6 * 6.0 * 3 * 2048 * 768 * 8192


# -- the routed layer ----------------------------------------------------------


def _layer(held, chunk_rows=None, experts=16, top_k=4, width=32, ff=24):
    return RoutedExperts(experts, top_k, width, ff, held=held, chunk_rows=chunk_rows,
                         dtype=jnp.float32)


def _reference_experts(params, x, top_k, first):
    """The reference's layer on one chip's rows: (y, aux)."""
    z = x.reshape(-1, x.shape[-1])
    y, aux = reference._experts(chain.Ops("float32"), params, z, top_k, first)
    return y.reshape(x.shape), aux


@pytest.mark.parametrize("held,chunk_rows", [((4, 4), None), ((0, 16), None),
                                             ((4, 4), 16), ((8, 2), 8)])
def test_routed_experts_match_the_reference_layer(held, chunk_rows):
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 32))
    layer = _layer(held, chunk_rows)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    y, stats = layer.apply({"params": params}, x)
    want, aux = _reference_experts(params, x, 4, held[0])
    np.testing.assert_allclose(y, want, atol=2e-6)
    np.testing.assert_allclose(stats["aux_loss"], aux, rtol=1e-6)
    assert int(stats["dropped"]) == 0
    chosen = np.asarray(stats["expert_index"])
    inside = (chosen >= held[0]) & (chosen < held[0] + held[1])
    assert int(stats["assigned"]) == inside.sum()

    def loss(fn):
        return lambda p, x: jnp.sum(fn(p, x)[0] ** 2) + fn(p, x)[1]

    got = jax.grad(loss(lambda p, x: (lambda o: (o[0], o[1]["aux_loss"]))(
        layer.apply({"params": p}, x))), (0, 1))(params, x)
    want = jax.grad(loss(lambda p, x: _reference_experts(p, x, 4, held[0])), (0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_identical_rows_route_as_one_and_later_chunks_carry_their_gradient():
    """Rows that are one token (every masked position of block-diffusion
    training) choose one set of experts: the router is the model's own, nothing
    spreads them.  The held expert they chose then takes every row, the sort
    runs past the first chunk, and the later chunks' recomputed backward pass
    gives the reference's gradient."""
    x = jnp.broadcast_to(jax.random.normal(jax.random.PRNGKey(4), (1, 1, 32)), (1, 64, 32))
    layer = _layer((0, 16), chunk_rows=48)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    y, stats = layer.apply({"params": params}, x)
    assert len({tuple(sorted(r)) for r in np.asarray(stats["expert_index"])}) == 1
    assert int(stats["assigned"]) == 64 * 4 and int(stats["dropped"]) == 0   # six chunks of 48
    assert float(stats["load_max_over_mean"]) == 4.0            # 4 of the 16 held take all
    want, aux = _reference_experts(params, x, 4, 0)
    np.testing.assert_allclose(y, want, atol=2e-6)
    np.testing.assert_allclose(stats["aux_loss"], aux, rtol=1e-6)
    w = jax.random.normal(jax.random.PRNGKey(6), x.shape)
    got = jax.grad(lambda p, x: jnp.sum(w * layer.apply({"params": p}, x)[0]), (0, 1))(params, x)
    ref = jax.grad(lambda p, x: jnp.sum(w * _reference_experts(p, x, 4, 0)[0]), (0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_eight_shares_sum_to_the_uncut_layer():
    """128 experts, 8 a token: the eight shares of 16 experts each, each
    computed by the program's layer told which experts it holds, add up to the
    reference's layer that holds all 128."""
    experts, top_k, width, ff = 128, 8, 16, 8
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 24, width))
    whole = _layer(None, experts=experts, top_k=top_k, width=width, ff=ff)
    params = whole.init(jax.random.PRNGKey(5), x)["params"]
    params = dict(params, router={"kernel": 4.0 * params["router"]["kernel"]})
    want, _ = _reference_experts(params, x, top_k, 0)
    total, assigned = 0.0, 0
    for share in range(8):
        first = 16 * share
        own = dict(params, **{k: params[k][first:first + 16]
                              for k in ("w_gate", "w_up", "w_down")})
        layer = _layer((first, 16), experts=experts, top_k=top_k, width=width, ff=ff)
        y, stats = layer.apply({"params": own}, x)
        total, assigned = total + y, assigned + int(stats["assigned"])
        assert int(stats["dropped"]) == 0
    assert assigned == 24 * top_k
    np.testing.assert_allclose(total, want, atol=2e-6)
    assert float(jnp.max(jnp.abs(want))) > 1e-3


def test_dropless_under_a_router_forced_onto_one_expert():
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (2, 40, 32)))
    layer = _layer((4, 4), chunk_rows=32)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    kernel = (0.01 * params["router"]["kernel"]).at[:, 5].add(1.0)
    params = dict(params, router={"kernel": kernel})
    y, stats = layer.apply({"params": params}, x)
    chosen = np.asarray(stats["expert_index"])
    assert (chosen == 5).any(axis=-1).all()          # every row chose expert 5
    # chunks of 32 sorted rows: the first three are full, so later chunks
    # ran, and nothing was dropped
    assert int(stats["assigned"]) > 3 * 32
    assert int(stats["dropped"]) == 0 and float(stats["load_max_over_mean"]) > 2.0
    want, _ = _reference_experts(params, x, 4, 4)
    np.testing.assert_allclose(y, want, atol=2e-6)


# -- how the rows move (PR 31): gathers by rank against the plain definition -------


def _plain_layer(params, x, top_k, held, experts=16):
    """The routed layer as it was before its rows moved by gathers alone,
    kept plain: the chosen gates by ``take_along_axis``, the counts by
    ``bincount``, every sorted assignment in ONE chunk, rows into expert
    order by ``z[token]`` and back by ``zeros.at[token].add(...)``, ordinary
    autodiff throughout.  Returns ``(y, stats)`` as the layer does."""
    first, n_held = held
    z = x.reshape(-1, x.shape[-1])
    slots = z.shape[0] * top_k
    logits = jnp.dot(z, params["router"]["kernel"], precision=jax.lax.Precision.HIGHEST)
    gates = jax.nn.softmax(logits, axis=-1)
    _, index = jax.lax.top_k(logits, top_k)
    chosen = jnp.take_along_axis(gates, index, axis=-1)
    chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    counts = jnp.bincount(index.reshape(-1), length=experts)
    aux = experts * jnp.sum(jax.lax.stop_gradient(counts / slots) * jnp.mean(gates, axis=0))
    local = index.reshape(-1) - first
    order = jnp.argsort(jnp.where((local >= 0) & (local < n_held), local, n_held), stable=True)
    sizes = counts[first:first + n_held].astype(jnp.int32)
    assigned = jnp.sum(sizes)
    token = order // top_k
    keep = (jnp.arange(slots) < assigned)[:, None]
    rows = jnp.where(keep, z[token], 0)
    hidden = jnp.where(keep, jax.nn.silu(jax.lax.ragged_dot(rows, params["w_gate"], sizes))
                       * jax.lax.ragged_dot(rows, params["w_up"], sizes), 0)
    y = jnp.where(keep, jax.lax.ragged_dot(hidden, params["w_down"], sizes), 0)
    y = y * chosen.reshape(-1)[order][:, None]
    out = jnp.zeros(z.shape, jnp.float32).at[token].add(y)
    stats = {"aux_loss": aux, "assigned": assigned, "expert_index": index,
             "load_max_over_mean": jnp.max(sizes) / (jnp.maximum(assigned, 1) / n_held)}
    return out.reshape(x.shape), stats


def _routed_by_hand(choices, seed=0):
    """``(x, router kernel)`` under which row ``t`` of ``x`` (1, T, 32) chooses
    exactly ``choices[t]`` of 16 experts, in that order: row ``t`` is four
    times the ``t``-th unit vector plus a little noise, and the router's
    ``t``-th row holds the logits wanted of it."""
    rng = np.random.default_rng(seed)
    rows, top_k = choices.shape
    wanted = 0.05 * rng.standard_normal((32, 16))
    for t in range(rows):
        wanted[t, choices[t]] = 4.5 - 0.5 * np.arange(top_k)
    x = 4.0 * np.eye(rows, 32) + 0.02 * rng.standard_normal((rows, 32))
    return jnp.asarray(x[None], jnp.float32), jnp.asarray(wanted, jnp.float32)


def _choices(rows, top_k, rng, always=()):
    """Distinct experts a row, ``always`` among them."""
    rest = [e for e in range(16) if e not in always]
    return np.array([list(always) + list(rng.permutation(rest)[:top_k - len(always)])
                     for _ in range(rows)])


def _choices_held(rng, held_a_row, held=(4, 4), top_k=4):
    """Row ``t`` chooses ``held_a_row[t]`` of the held experts and the rest of
    its ``top_k`` among the others, in a random order."""
    inside = list(range(held[0], held[0] + held[1]))
    outside = [e for e in range(16) if e not in inside]
    return np.array([rng.permutation(list(rng.permutation(inside)[:n])
                                     + list(rng.permutation(outside)[:top_k - n]))
                     for n in held_a_row])


def _chunk_rows(slots, n_held, chunk_rows=None, experts=16):
    """``(first, later)``: the rows of the layer's first chunk and of each
    later one, the rule written out: ``chunk_rows`` where it is set, else nine
    eighths and a quarter of the expected assignments, in whole eights."""
    expected = slots * n_held / experts
    sizes = (chunk_rows,) * 2 if chunk_rows else (9 * expected / 8, expected / 4)
    return tuple(min(-(-max(math.ceil(n), 1) // 8) * 8, -(-slots // 8) * 8) for n in sizes)


def _routing(case):
    """``(choices (T, 4), held, chunk_rows, chunks in use)`` of a named case."""
    rng = np.random.default_rng(31)
    if case == "balanced":                      # the default chunks, one in use
        return _choices(24, 4, rng), (4, 4), None, 1
    if case == "one_expert_many_chunks":        # every row onto held expert 5
        return _choices(24, 4, rng, always=(5,)), (4, 4), 8, None
    if case == "all_held_and_none_held":
        choices = _choices(24, 4, rng)
        choices[0], choices[1] = [7, 4, 6, 5], [0, 9, 15, 3]
        return choices, (4, 4), 8, None
    if case == "held_range_from_8":
        return _choices(24, 4, rng), (8, 2), None, 1
    if case == "chunk_not_a_multiple_of_the_load":
        return _choices(24, 4, rng, always=(6,)), (4, 4), 16, None
    # the default sizes under load: 96 slots, 24 expected, a first chunk of 32
    # rows (nine eighths, in whole eights) and later ones of 8
    if case == "default_just_over_the_first_chunk":     # 36 held: one quarter chunk
        return _choices_held(rng, [2] * 12 + [1] * 12), (4, 4), None, 2
    if case == "default_twice_the_expected":            # 48 held: two quarter chunks
        return _choices_held(rng, [2] * 24), (4, 4), None, 3
    if case == "default_every_slot_held":               # 96 held: every chunk there is
        return _choices_held(rng, [4] * 24), (4, 4), None, 9
    raise KeyError(case)


_ROUTING_CASES = ["balanced", "one_expert_many_chunks", "all_held_and_none_held",
                  "held_range_from_8", "chunk_not_a_multiple_of_the_load",
                  "default_just_over_the_first_chunk", "default_twice_the_expected",
                  "default_every_slot_held"]


def _close(got, want, what):
    scale = max(1.0, float(np.max(np.abs(want))))
    np.testing.assert_allclose(got, want, atol=1e-6 * scale, rtol=0, err_msg=what)


@pytest.mark.parametrize("case", _ROUTING_CASES)
def test_rows_moved_by_gathers_give_the_plain_definition(case):
    """The layer (rows into expert order by one gather, back by ``top_k``
    gathers by rank, every transpose written by hand) against
    ``_plain_layer`` (``z[token]``, ``zeros.at[token].add``, autodiff): the
    output, the statistics, and the gradient with respect to the rows, the
    three expert matrices and, through the router, the weights."""
    choices, held, chunk_rows, in_use = _routing(case)
    x, kernel = _routed_by_hand(choices)
    layer = _layer(held, chunk_rows)
    params = dict(layer.init(jax.random.PRNGKey(0), x)["params"], router={"kernel": kernel})
    y, stats = layer.apply({"params": params}, x)
    want, plain = _plain_layer(params, x, 4, held)
    np.testing.assert_array_equal(stats["expert_index"], choices)      # the case is what it says
    np.testing.assert_array_equal(stats["expert_index"], plain["expert_index"])
    inside = (choices >= held[0]) & (choices < held[0] + held[1])
    assert int(stats["assigned"]) == int(plain["assigned"]) == inside.sum()
    assert int(stats["dropped"]) == 0
    assert float(stats["load_max_over_mean"]) == float(plain["load_max_over_mean"])
    np.testing.assert_allclose(stats["aux_loss"], plain["aux_loss"], rtol=1e-6)
    first, later = _chunk_rows(96, held[1], chunk_rows)
    chunks = 1 + -(-max(int(inside.sum()) - first, 0) // later)
    assert int(stats["chunks"]) == chunks
    if in_use is None:
        assert chunks > 1                                  # a later chunk runs
    else:
        assert chunks == in_use
    if case == "all_held_and_none_held":
        assert inside[0].all() and not inside[1].any()
        assert float(jnp.max(jnp.abs(y[0, 1]))) == 0.0
    if case == "chunk_not_a_multiple_of_the_load":
        assert inside.sum() % first
    _close(y, want, "output")
    assert float(jnp.max(jnp.abs(want))) > 1e-2

    w = jax.random.normal(jax.random.PRNGKey(6), x.shape)

    def loss(fn):
        def of(p, x):
            y, stats = fn(p, x)
            return jnp.sum(w * y) + stats["aux_loss"]
        return of

    got = jax.grad(loss(lambda p, x: layer.apply({"params": p}, x)), (0, 1))(params, x)
    ref = jax.grad(loss(lambda p, x: _plain_layer(p, x, 4, held)), (0, 1))(params, x)
    flat = dict(jax.tree_util.tree_leaves_with_path(ref))
    for path, leaf in jax.tree_util.tree_leaves_with_path(got):
        _close(leaf, flat[path], jax.tree_util.keystr(path))
    assert float(jnp.max(jnp.abs(ref[0]["router"]["kernel"]))) > 1e-3


@pytest.mark.parametrize("case", _ROUTING_CASES)
def test_chosen_gates_and_counts_are_the_scatter_forms_exactly(case):
    """One dense comparison against ``take_along_axis`` and ``bincount``:
    the same numbers to the last bit, and the same gradient."""
    from horovod_tpu.parallel import moe

    choices = jnp.asarray(_routing(case)[0])
    gates = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(3), (24, 16)), axis=-1)
    chosen, counts = moe._chosen_and_counts(gates, choices)
    np.testing.assert_array_equal(chosen, jnp.take_along_axis(gates, choices, axis=-1))
    np.testing.assert_array_equal(counts, jnp.bincount(choices.reshape(-1), length=16))
    assert counts.dtype == jnp.int32 and int(counts.sum()) == choices.size
    w = jax.random.normal(jax.random.PRNGKey(4), choices.shape)
    np.testing.assert_array_equal(
        jax.grad(lambda g: jnp.sum(w * moe._chosen_and_counts(g, choices)[0]))(gates),
        jax.grad(lambda g: jnp.sum(w * jnp.take_along_axis(g, choices, axis=-1)))(gates))


def _primitives(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _primitives(inner)


@pytest.mark.parametrize("dtype,chunk_rows", [("float32", None), ("bfloat16", None),
                                              ("bfloat16", 8)])
def test_no_scatter_forward_or_backward_and_rows_gathered_in_the_layer_s_dtype(dtype, chunk_rows):
    """What autodiff would write for a gather is a scatter-add: the traced
    layer, forward and backward, holds none (a row moves by gathers, a single
    number by sorts), and every gather of rows is in the layer's dtype: the
    cotangent is cast on its (rows, D) side before it is gathered."""
    layer = RoutedExperts(16, 4, 32, 24, held=(4, 4), chunk_rows=chunk_rows,
                          dtype=jnp.dtype(dtype))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 32), jnp.dtype(dtype))
    params = layer.init(jax.random.PRNGKey(0), x)["params"]

    def loss(p, x):
        y, stats = layer.apply({"params": p}, x)
        return jnp.sum(y.astype(jnp.float32) ** 2) + stats["aux_loss"]

    eqns = list(_primitives(jax.make_jaxpr(jax.grad(loss, (0, 1)))(params, x).jaxpr))
    names = {e.primitive.name for e in eqns}
    assert not {n for n in names if n.startswith("scatter")}, names
    assert {"gather", "sort", "pallas_call"} <= names   # the grouped products' kernels
    assert not {n for n in names if n.startswith("ragged_dot")}, names
    wide = [e for e in eqns if e.primitive.name == "gather" and e.invars[0].aval.ndim == 2
            and e.invars[0].aval.shape[1] == 32]
    assert len(wide) >= 2 * (1 + 4)             # x and g; y and dx for each of 4 slots
    assert {str(e.invars[0].aval.dtype) for e in wide} == {dtype}


def test_moe_rows_event_says_what_a_chunk_moves():
    """Tracing the layer leaves one ``moe.rows`` instant: shape arithmetic."""
    from horovod_tpu import trace

    layer = RoutedExperts(16, 4, 32, 24, held=(4, 4), dtype=jnp.bfloat16)
    x = jnp.zeros((2, 12, 32), jnp.bfloat16)
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), x))["params"]
    t0 = trace.now()
    jax.make_jaxpr(lambda p: layer.apply({"params": p}, x)[0])(params)
    events = [r[3] for r in trace.snapshot(t0) if r[0] == "moe.rows"]
    # the first chunk: nine eighths of the 24 expected, in whole eights; a later
    # one a quarter of them.  The grouped products: one 32-row tile holds the
    # chunk, and each of the four balanced groups of 6 rows visits it
    assert (32, 8) == _chunk_rows(96, 4)
    assert events == [{"rows": 24, "slots": 96, "chunk": 32, "first": 32, "later": 8,
                       "expected": 24.0, "dtype": "bfloat16",
                       "gathered": 2 * 32 + 2 * 96, "scoring": "softmax",
                       "tiles": [32, 32, 24], "visits": 4, "chunk_tiles": 1,
                       "width": 32, "gated": True}]


# -- the model's keys -----------------------------------------------------------


def _lowered(cfg):
    model = Transformer(cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = jax.eval_shape(lambda k: model.init(k, tokens), jax.random.PRNGKey(0))

    def loss(p):
        return optax.softmax_cross_entropy_with_integer_labels(
            model.apply(p, tokens), tokens).mean()

    return jax.jit(jax.value_and_grad(loss)).lower(params).as_text()


def test_new_keys_default_to_the_model_that_was():
    """A configuration that states none of the new keys (InternLM2's) builds
    the program it built: stating each default changes nothing."""
    base = dict(vocab_size=64, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
                max_seq_len=32, attention_impl="flash")
    stated = dict(base, hidden_size=32, rope_theta=10000.0, rms_norm_eps=1e-5,
                  tie_word_embeddings=True, qk_norm=False, num_experts=None,
                  block_diffusion=None)
    assert _lowered(TransformerConfig(**base)) == _lowered(TransformerConfig(**stated))
    cfg = TransformerConfig(**base)
    assert cfg.d_model == 32 and cfg.rope_theta == 10000.0 and cfg.rms_norm_eps == 1e-5
    for key, value in (("rope_theta", 1e6), ("rms_norm_eps", 1e-6), ("qk_norm", True),
                       ("tie_word_embeddings", False)):
        assert _lowered(TransformerConfig(**dict(base, **{key: value}))) != _lowered(cfg)


def test_hidden_size_is_its_own_key_and_the_head_is_untied():
    cfg = TransformerConfig(vocab_size=50, num_layers=1, num_heads=4, num_kv_heads=2,
                            head_dim=8, hidden_size=16, dtype=jnp.float32,
                            tie_word_embeddings=False, qk_norm=True)
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = Transformer(cfg).init(jax.random.PRNGKey(0), tokens)["params"]
    shapes = jax.tree_util.tree_map(lambda x: x.shape, params)
    assert shapes["embed"]["embedding"] == (50, 16) and shapes["head"]["kernel"] == (16, 50)
    attn = shapes["layer_0"]["attn"]
    assert attn["q"]["kernel"] == (16, 4, 8) and attn["o"]["kernel"] == (4, 8, 16)
    assert attn["q_norm"]["scale"] == attn["k_norm"]["scale"] == (8,)
    logits = Transformer(cfg).apply({"params": params}, tokens)
    assert logits.shape == (1, 8, 50) and logits.dtype == jnp.float32


def test_block_diffusion_model_trains_through_the_normal_path():
    """create_train_state -> replicate_state -> data_parallel_train_step, a
    loss_fn through the step's own argument, labels a tuple."""
    import horovod_tpu as hvd
    from horovod_tpu import training

    hvd.init()
    losses = {}
    for impl in ("dot", "flash"):
        cfg = TransformerConfig(
            vocab_size=50, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
            hidden_size=16, max_seq_len=64, dtype=jnp.float32, attention_impl=impl,
            rope_theta=1e6, rms_norm_eps=1e-6, tie_word_embeddings=False, qk_norm=True,
            num_experts=8, num_experts_per_tok=2, moe_intermediate_size=12,
            held_experts=(2, 4), block_diffusion=4)
        model = Transformer(cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(0), (8, 48), 0, 50)
        labels = (tokens[:, 24:], jax.random.uniform(jax.random.PRNGKey(1), (8, 24)))
        logits, aux = model.apply(
            model.init(jax.random.PRNGKey(2), tokens[:1]), tokens[:1])
        assert logits.shape == (1, 24, 50) and int(aux["dropped_assignments"]) == 0
        assert aux["expert_index"].shape == (2, 48, 2)
        # 48 rows x 2 slots, 4 of 8 held: 48 expected a layer, chunks of 56 and 16
        held = np.sum((aux["expert_index"] >= 2) & (aux["expert_index"] < 6), axis=(1, 2))
        assert int(aux["expert_assignments"]) == held.sum()
        assert int(aux["expert_chunks"]) == sum(1 + -(-max(int(n) - 56, 0) // 16) for n in held)
        state = training.create_train_state(
            model, optax.adamw(1e-2), jax.random.PRNGKey(2), np.asarray(tokens[:1]))
        state = training.replicate_state(state, hvd.world_mesh())
        step = training.data_parallel_train_step(
            model, optax.adamw(1e-2), loss_fn=functools.partial(
                transformer.block_diffusion_loss, aux_coef=0.001))
        losses[impl] = []
        for _ in range(4):
            state, loss = step(state, tokens, labels)
            losses[impl].append(float(loss))
        assert losses[impl][-1] < losses[impl][0]
    np.testing.assert_allclose(losses["dot"], losses["flash"], rtol=2e-5)
