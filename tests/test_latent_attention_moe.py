"""Latent attention (MLA), the sigmoid router with its selection bias and
scaling factor, shared experts and a leading dense layer (PR 32): the program
against the benchmark's plain float32 reference, at small sizes on the CPU."""

import hashlib
import json
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

from benchmark import flops_kimi, harness  # noqa: E402
from benchmark.reference import chain, kimi_moe as reference  # noqa: E402
from horovod_tpu import training  # noqa: E402
from horovod_tpu.models import transformer  # noqa: E402
from horovod_tpu.models.transformer import (  # noqa: E402
    Attention, MlpBlock, Transformer, TransformerConfig, causal_dot_attention,
    modeled_activation_bytes,
)
from horovod_tpu.ops.flash_attention import (  # noqa: E402
    flash_attention, flash_chunk_attention,
)
from horovod_tpu.parallel.moe import RoutedExperts  # noqa: E402

OPS = chain.Ops("float32")
LATENT = dict(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8)


def _config(**kw):
    base = dict(
        vocab_size=64, num_layers=3, num_heads=4, hidden_size=32, max_seq_len=64,
        dtype=jnp.float32, intermediate_size=48, num_experts=16, num_experts_per_tok=3,
        moe_intermediate_size=12, held_experts=(4, 4), first_dense_layers=1,
        num_shared_experts=2, router_scoring="sigmoid", routed_scaling_factor=2.446,
        router_selection_bias=True, router_seq_aux=True, tie_word_embeddings=False,
        rope_theta=800000.0, **LATENT)
    return TransformerConfig(**{**base, **kw})


def _tiny_cell_config(dtype="float32"):
    """The cell's configuration with every size made tiny (widths too: a
    test's sizes, never a cell's)."""
    config = harness.load_json(ROOT, "benchmark", "configs", "kimi-vl-a3b.json")
    config.update(
        hidden_size=32, intermediate_size=48, moe_intermediate_size=12,
        num_hidden_layers=3, num_attention_heads=4, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, vocab_size=64, router_experts=16,
        n_routed_experts=4, held_experts_first=4, num_experts_per_tok=3,
        max_position_embeddings=64, compute_dtype=dtype)
    return config


_TRAFFIC = {"samples_per_chip": 2, "seq_len": 40, "layout": "dp", "step_options": {},
            "span_steps": 2, "trace_steps": 3}


# -- the family through the harness: loss and every leaf's gradient -----------


def test_family_through_run_cell_matches_the_reference_at_float32():
    config = _tiny_cell_config()
    config["check"] = dict(config["check"], diff_leaves="", limits={
        "loss_gap": 2e-6, "grad_norm_gap": 5e-5, "delta_norm_gap": 5e-5,
        "grad_diff_gap": 5e-5})
    cell = harness.Cell(
        name="tiny-kimi-1", config_name="tiny", config=config, traffic_name="tiny",
        traffic=_TRAFFIC, chips=1,
        end_to_end=["setup_s", "train_tokens_per_s", "step_ms_p90", "mfu"], per_layer=[])
    harness.check_names(cell)
    result = harness.run_cell(cell, seed=2 ** 31 + 32, seconds=0.3, trace=False,
                              devices=jax.devices()[:1])
    assert result["correct"], json.dumps(result["checks"])
    assert result["checks"]["grad_diff_gap"]["value"] < 5e-5     # over every leaf
    assert set(reference.REFERENCE_ROUTING) == {1, 2}            # layer 0 is dense
    assert reference.REFERENCE_ROUTING[1].shape == (2, 40, 3)


def test_batch_is_the_next_token_of_every_position():
    from benchmark.families_kimi import KimiMoe

    inputs, labels = KimiMoe.batch(jax.random.PRNGKey(3), _tiny_cell_config(),
                                   dict(_TRAFFIC, seq_len=38), 5)
    assert inputs.shape == labels.shape == (5, 38)
    assert (np.asarray(inputs[:, 1:]) == np.asarray(labels[:, :-1])).all()
    assert int(inputs.max()) < 64 and len({tuple(r) for r in np.asarray(inputs)}) == 5


def test_required_flops_are_the_issue_s_table():
    cell = harness.load_cell("kimi-vl-a3b-s8192-1chip")
    config, traffic = cell.config, cell.traffic
    assert flops_kimi.mla_matrix_params(config) == 13_763_072 - 512     # less the 512-wide norm
    assert flops_kimi.dense_layer_matrix_params(config) == 82_973_184 - 512 - 2 * 2048
    routed = flops_kimi.routed_layer_matrix_params(config)
    assert routed == 13_762_560 + 131_072 + 17_301_504 + 0.75 * 8_650_752
    per_token = flops_kimi.train_flops_per_token(config, traffic)
    assert abs(per_token - 2.28e9) < 0.01e9 and abs(per_token * 8192 - 18.7e12) < 0.05e12
    attention = flops_kimi.mla_attention_train_flops_per_step(config, traffic, 1)
    assert attention == 5 * 3 * 2 * (192 + 128) * 16 * 8192 ** 2 / 2
    assert abs(attention / 8192 / per_token - 0.276) < 0.002
    experts = flops_kimi.expert_ffn_train_flops_per_step(config, traffic, 1)
    assert experts == 4 * 6.0 * 3 * 2048 * 1408 * 6144
    assert config["parameters"] == 568_484_608 - 4 * 64            # the bias is no parameter
    assert sorted(config["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    for key, value in (("hidden_size", 2048), ("num_attention_heads", 16), ("kv_lora_rank", 512),
                       ("qk_nope_head_dim", 128), ("qk_rope_head_dim", 64), ("v_head_dim", 128),
                       ("intermediate_size", 11264), ("moe_intermediate_size", 1408),
                       ("num_experts_per_tok", 6), ("router_experts", 64), ("n_shared_experts", 2),
                       ("routed_scaling_factor", 2.446), ("rope_theta", 800000),
                       ("rms_norm_eps", 1e-5)):
        assert config[key] == value, key


# -- latent attention ----------------------------------------------------------


@pytest.mark.parametrize("impl", ["dot", "flash"])
def test_latent_attention_matches_the_reference_forward_and_gradients(impl):
    cfg = _config(attention_impl=impl)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 32))
    positions = jnp.broadcast_to(jnp.arange(40), (2, 40))
    layer = Attention(cfg)
    params = layer.init(jax.random.PRNGKey(0), x, positions)["params"]
    shapes = jax.tree_util.tree_map(lambda p: p.shape, params)
    assert shapes == {"q": {"kernel": (32, 4, 12)}, "kv_a": {"kernel": (32, 20)},
                      "kv_a_norm": {"scale": (16,)}, "kv_b": {"kernel": (16, 4, 16)},
                      "o": {"kernel": (4, 8, 32)}}
    params = dict(params, kv_a_norm={"scale": 1.0 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(2), (16,))})

    def program(p, x):
        return layer.apply({"params": p}, x, positions)

    def plain(p, x):
        return jax.vmap(lambda r: reference.attention(OPS, p, r, 1e-5, 800000.0, 8))(x)

    np.testing.assert_allclose(program(params, x), plain(params, x), atol=3e-6)
    w = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    got = jax.grad(lambda p, x: jnp.sum(w * program(p, x)), (0, 1))(params, x)
    want = jax.grad(lambda p, x: jnp.sum(w * plain(p, x)), (0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-5)


@pytest.mark.parametrize("dtype,seq,heads,kv,causal,tile,atol", [
    ("float32", 200, 4, 4, True, 128, 1e-5),    # S no multiple of the tile
    ("float32", 384, 2, 2, True, 128, 1e-5),    # three tiles: iterations of two and one
    ("float32", 130, 4, 2, True, 128, 1e-5),    # grouped key/value heads
    ("float32", 200, 2, 2, False, 128, 1e-5),   # no mask
    ("bfloat16", 200, 4, 4, True, 128, 3e-2),
    ("bfloat16", 256, 2, 2, True, 256, 3e-2),   # one tile
])
def test_flash_matches_dot_for_keys_and_values_of_unequal_widths(
        dtype, seq, heads, kv, causal, tile, atol):
    keys = jax.random.split(jax.random.PRNGKey(seq), 4)
    q = jax.random.normal(keys[0], (2, seq, heads, 48), dtype)
    k = jax.random.normal(keys[1], (2, seq, kv, 48), dtype)
    v = jax.random.normal(keys[2], (2, seq, kv, 32), dtype)
    w = jax.random.normal(keys[3], (2, seq, heads, 32), jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=tile, block_k=tile)

    def dot(q, k, v):
        return causal_dot_attention(q, k, v, causal=causal)

    out = flash(q, k, v)
    assert out.shape == (2, seq, heads, 32) and out.dtype == q.dtype
    f32 = lambda x: np.asarray(x, np.float32)
    np.testing.assert_allclose(f32(out), f32(dot(q, k, v)), atol=atol)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dot(*a) * w), (0, 1, 2))(q, k, v)
    assert [g.shape for g in got] == [q.shape, k.shape, v.shape]   # dk 48 wide, dv 32
    for a, b in zip(got, want):
        np.testing.assert_allclose(f32(a), f32(b), atol=atol * max(1.0, float(np.abs(f32(b)).max())))


def test_flash_tiles_event_carries_both_widths():
    from horovod_tpu import trace

    t0 = trace.now()
    q = jnp.ones((1, 256, 2, 48), jnp.float32)
    jax.make_jaxpr(jax.grad(lambda a: flash_attention(
        a, a, a[..., :32], block_q=128, block_k=128).sum()))(q)
    events = [r[3] for r in trace.snapshot(t0) if r[0] == "flash.tiles"]
    assert {e["kernel"] for e in events} == {
        "flash_attention_fwd", "flash_attention_bwd_dq", "flash_attention_bwd_dkv"}
    assert all(e["d_qk"] == 48 and e["d_v"] == 32 for e in events)


def test_unequal_widths_are_refused_where_the_kernels_are_not_widened():
    q, v = jnp.zeros((1, 16, 2, 12)), jnp.zeros((1, 16, 2, 8))
    with pytest.raises(ValueError, match="no window and no block_diffusion"):
        flash_attention(q, q, v, window=4)
    with pytest.raises(ValueError, match="no window and no block_diffusion"):
        flash_attention(q, q, v, causal=False, block_diffusion=(8, 4))
    with pytest.raises(ValueError, match="do not fit"):
        flash_attention(q, v, v)                       # q and k differ in width
    with pytest.raises(ValueError, match="no latent cache"):
        flash_chunk_attention(q, q, v, jnp.zeros((1,), jnp.int32))
    from horovod_tpu.parallel.ring_attention import ring_attention
    with pytest.raises(ValueError, match="one width"):
        ring_attention(q, q, v, axis_name=None)


def test_a_latent_config_is_refused_where_it_cannot_run():
    with pytest.raises(ValueError, match="ring rotates"):
        _config(attention_impl="ring")
    with pytest.raises(ValueError, match="no window, no block_diffusion"):
        _config(window=8)
    with pytest.raises(ValueError, match="no window, no block_diffusion"):
        _config(block_diffusion=4)
    with pytest.raises(ValueError, match="hidden_size"):
        TransformerConfig(num_heads=4, kv_lora_rank=16)        # the other three missing
    with pytest.raises(ValueError, match="need num_experts"):
        TransformerConfig(num_shared_experts=2)
    with pytest.raises(ValueError, match="router_scoring"):
        _config(router_scoring="tanh")
    # paged serving: no latent cache (and no routed feed-forward)
    dense = _config(num_experts=None, first_dense_layers=0, num_shared_experts=0,
                    held_experts=None, num_layers=1)
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = Transformer(dense).init(jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="no latent attention"):
        Transformer(dense).apply(params, tokens, train=False, paged=object())
    # a bound shard axis of more than one chip
    sharded = _config(num_experts=None, first_dense_layers=0, num_shared_experts=0,
                      held_experts=None, num_layers=1, shard_axis="tp")
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("tp",))
    with pytest.raises(ValueError, match="takes no latent attention"):
        jax.shard_map(lambda t: Transformer(sharded).apply(params, t), mesh=mesh,
                      in_specs=jax.sharding.PartitionSpec(),
                      out_specs=jax.sharding.PartitionSpec(), check_vma=False)(tokens)
    with pytest.raises(ValueError, match="cannot count"):
        modeled_activation_bytes(_config(), batch=1, seq=32)
    with pytest.raises(ValueError, match="intermediate_size"):
        modeled_activation_bytes(TransformerConfig(intermediate_size=100), batch=1)
    assert modeled_activation_bytes(TransformerConfig(), batch=1)["total_bytes"] > 0


# -- the router ----------------------------------------------------------------


def _layer(held=(4, 4), experts=16, top_k=3, width=32, ff=12, **kw):
    kw = {"scoring": "sigmoid", "scaling_factor": 2.446, "selection_bias": True,
          "seq_aux": True, **kw}
    return RoutedExperts(experts, top_k, width, ff, held=held, dtype=jnp.float32, **kw)


def _init(layer, x, bias=None, seed=0):
    variables = layer.init(jax.random.PRNGKey(seed), x)
    params = variables["params"]
    params = dict(params, router={"kernel": 3.0 * params["router"]["kernel"]})
    stats = variables.get("batch_stats")
    if bias is not None:
        stats = {"e_score_correction_bias": jnp.asarray(bias, jnp.float32)}
    return params, stats


def _apply(layer, params, stats, x):
    variables = {"params": params}
    if stats is not None:
        variables["batch_stats"] = stats
    return layer.apply(variables, x)


def _reference_layer(params, x, top_k, first, scale, bias=None, shared=None):
    """The reference's routed feed-forward, a sequence at a time: (y, aux)."""
    y, aux = jax.vmap(lambda r: reference.routed_feed_forward(
        OPS, params, shared, r, top_k, first, scale, bias))(x)
    return y, jnp.mean(aux)


@pytest.mark.parametrize("held,biased", [((4, 4), False), ((0, 16), True), ((8, 2), True)])
def test_routed_experts_match_the_reference_layer(held, biased):
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 32))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(7), (16,)) if biased else None
    layer = _layer(held)
    params, stats = _init(layer, x, bias)
    assert stats["e_score_correction_bias"].shape == (16,)
    y, out = _apply(layer, params, stats, x)
    want, aux = _reference_layer(params, x, 3, held[0], 2.446, bias)
    np.testing.assert_allclose(y, want, atol=3e-6)
    np.testing.assert_allclose(out["aux_loss"], aux, rtol=1e-6)
    assert int(out["dropped"]) == 0

    def loss(fn):
        return lambda p, x: jnp.sum(fn(p, x)[0] ** 2) + fn(p, x)[1]

    got = jax.grad(loss(lambda p, x: (lambda o: (o[0], o[1]["aux_loss"]))(
        _apply(layer, p, stats, x))), (0, 1))(params, x)
    want = jax.grad(loss(lambda p, x: _reference_layer(p, x, 3, held[0], 2.446, bias)),
                    (0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=5e-5)


def test_the_bias_selects_and_does_not_weigh():
    """A bias that changes the chosen set leaves the chosen weights' formula
    alone: the weights are the sigmoid scores of the chosen, renormalised and
    scaled, the bias nowhere in them; the bias's gradient is exactly zero."""
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 24, 32))
    layer = _layer((0, 16))
    params, _ = _init(layer, x)
    bias = jnp.zeros((16,)).at[5].set(10.0).at[2].set(-10.0)      # 5 always, 2 never
    plain = _apply(layer, params, {"e_score_correction_bias": jnp.zeros((16,))}, x)[1]
    biased = _apply(layer, params, {"e_score_correction_bias": bias}, x)[1]
    chosen = np.asarray(biased["expert_index"])
    assert (chosen == 5).any(axis=-1).all() and not (chosen == 2).any()
    assert (np.asarray(plain["expert_index"]) == 2).any()         # it did change the set
    scores = np.asarray(jax.nn.sigmoid(x.reshape(-1, 32) @ params["router"]["kernel"]))
    # the two largest of the rest, by score alone
    rest = np.where(np.isin(np.arange(16), (2, 5)), -np.inf, scores)
    assert (np.sort(chosen, axis=-1) == np.sort(np.concatenate(
        [np.full((24, 1), 5), np.argsort(-rest, axis=-1)[:, :2]], axis=-1), axis=-1)).all()
    # weights: recovered from a layer whose experts return their input's first entry
    y, _ = _apply(layer, params, {"e_score_correction_bias": bias}, x)
    want, _ = _reference_layer(params, x, 3, 0, 2.446, bias)
    np.testing.assert_allclose(y, want, atol=3e-6)
    picked = np.take_along_axis(scores, chosen, axis=-1)
    weights = picked / picked.sum(-1, keepdims=True) * 2.446
    np.testing.assert_allclose(
        reference.route(OPS, params, x[0], 3, 2.446, bias)[1], weights, rtol=1e-5)
    grad = jax.grad(lambda b: jnp.sum(_apply(
        layer, params, {"e_score_correction_bias": b}, x)[0] ** 2))(bias)
    assert (np.asarray(grad) == 0).all()


def test_the_scaling_factor_scales_the_routed_sum_alone():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 24, 32))
    params, stats = _init(_layer(), x)
    one = _apply(_layer(scaling_factor=1.0), params, stats, x)
    scaled = _apply(_layer(scaling_factor=2.446), params, stats, x)
    np.testing.assert_allclose(scaled[0], 2.446 * one[0], rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(scaled[1]["aux_loss"], one[1]["aux_loss"], rtol=1e-7)
    assert (np.asarray(scaled[1]["expert_index"]) == np.asarray(one[1]["expert_index"])).all()


def test_sequence_wise_auxiliary_loss_is_the_loop():
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 20, 32))
    layer = _layer((0, 16))
    params, stats = _init(layer, x)
    _, out = _apply(layer, params, stats, x)
    scores = np.asarray(jax.nn.sigmoid(x @ params["router"]["kernel"]), np.float64)
    chosen = np.asarray(out["expert_index"]).reshape(3, 20, 3)
    total = 0.0
    for b in range(3):                       # a sequence
        for e in range(16):                  # an expert
            f = 16 / (3 * 20) * (chosen[b] == e).sum()
            p = np.mean([scores[b, t, e] / scores[b, t].sum() for t in range(20)])
            total += f * p
    np.testing.assert_allclose(out["aux_loss"], total / 3, rtol=1e-6)
    # the Switch form, which the softmax router keeps, is another number
    switch = _apply(_layer((0, 16), seq_aux=False), params, stats, x)[1]["aux_loss"]
    assert abs(float(switch) - float(out["aux_loss"])) > 1e-3
    # no gradient through the counts: d aux / d router = sum_e f_e dP_e
    grad = jax.grad(lambda p: _apply(layer, p, stats, x)[1]["aux_loss"])(params)
    assert float(jnp.max(jnp.abs(grad["router"]["kernel"]))) > 0
    assert all(float(jnp.max(jnp.abs(grad[k]))) == 0 for k in ("w_gate", "w_up", "w_down"))


def test_eight_shares_and_the_shared_experts_once_sum_to_the_uncut_layer():
    """64 experts, 6 a token, 2 shared: the eight shares of 8 experts each,
    each computed by the program's layer told which experts it holds, plus the
    shared experts counted ONCE, add up to the reference's layer that holds
    all 64."""
    experts, top_k, width, ff = 64, 6, 16, 8
    cfg = _config(hidden_size=width, moe_intermediate_size=ff, num_experts=experts,
                  num_experts_per_tok=top_k)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, width))
    whole = _layer(None, experts=experts, top_k=top_k, width=width, ff=ff)
    bias = 0.2 * jax.random.normal(jax.random.PRNGKey(9), (experts,))
    params, stats = _init(whole, x, bias, seed=5)
    shared_block = MlpBlock(cfg, hidden=2 * ff)
    shared = shared_block.init(jax.random.PRNGKey(6), x)["params"]
    want, _ = _reference_layer(params, x, top_k, 0, 2.446, bias, shared=shared)
    total, assigned = shared_block.apply({"params": shared}, x), 0
    for share in range(8):
        first = 8 * share
        own = dict(params, **{k: params[k][first:first + 8]
                              for k in ("w_gate", "w_up", "w_down")})
        layer = _layer((first, 8), experts=experts, top_k=top_k, width=width, ff=ff)
        y, out = _apply(layer, own, stats, x)
        total, assigned = total + y, assigned + int(out["assigned"])
        assert int(out["dropped"]) == 0
    assert assigned == 2 * 24 * top_k
    np.testing.assert_allclose(total, want, atol=3e-6)
    routed_only, _ = _reference_layer(params, x, top_k, 0, 2.446, bias)
    assert float(jnp.max(jnp.abs(want - routed_only))) > 1e-3     # the shared part counts


def test_dropless_under_a_router_forced_onto_one_expert():
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (2, 40, 32)))
    layer = _layer((4, 4), chunk_rows=32)
    params, stats = _init(layer, x)
    kernel = (0.01 * params["router"]["kernel"]).at[:, 5].add(1.0)
    params = dict(params, router={"kernel": kernel})
    y, out = _apply(layer, params, stats, x)
    chosen = np.asarray(out["expert_index"])
    assert (chosen == 5).any(axis=-1).all()          # every row chose expert 5
    assert int(out["assigned"]) > 2 * 32 and int(out["dropped"]) == 0
    assert float(out["load_max_over_mean"]) > 2.0
    want, _ = _reference_layer(params, x, 3, 4, 2.446)
    np.testing.assert_allclose(y, want, atol=3e-6)


def test_moe_rows_event_carries_the_scoring():
    from horovod_tpu import trace

    x = jnp.ones((1, 16, 32))
    # 48 slots.  4 of 16 held: 12 expected, chunks of 16 (nine eighths, in whole
    # eights) and 8; every expert held: the first chunk is all the slots
    for layer, scoring, chunks in (
            (_layer(), "sigmoid", (16, 8)),
            (RoutedExperts(16, 3, 32, 12, dtype=jnp.float32), "softmax", (48, 16))):
        t0 = trace.now()
        jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), x))
        events = [r[3] for r in trace.snapshot(t0) if r[0] == "moe.rows"]
        assert events and all(e["scoring"] == scoring for e in events)
        assert all((e["chunk"], e["first"], e["later"]) == (chunks[0], *chunks)
                   for e in events)


# -- the model -----------------------------------------------------------------


def test_the_model_s_tree_and_its_layers():
    cfg = _config(attention_impl="flash")
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 24), 0, 64)
    variables = Transformer(cfg).init(jax.random.PRNGKey(1), tokens)
    params = variables["params"]
    assert set(params["layer_0"]) == {"ln1", "attn", "ln2", "mlp"}         # the dense layer
    assert params["layer_0"]["mlp"]["gate"]["kernel"].shape == (32, 48)    # intermediate_size
    for i in (1, 2):
        assert set(params[f"layer_{i}"]) == {"ln1", "attn", "ln2", "moe", "shared_experts"}
        assert params[f"layer_{i}"]["shared_experts"]["down"]["kernel"].shape == (24, 32)
        assert params[f"layer_{i}"]["moe"]["w_gate"].shape == (4, 32, 12)
        assert "e_score_correction_bias" not in params[f"layer_{i}"]["moe"]
    assert set(variables["batch_stats"]) == {"layer_1", "layer_2"}         # the bias: no parameter
    (logits, aux), _ = Transformer(cfg).apply(variables, tokens, mutable=["batch_stats"])
    assert logits.shape == (2, 24, 64) and logits.dtype == jnp.float32
    assert aux["expert_index"].shape == (2, 48, 3) and int(aux["dropped_assignments"]) == 0
    dot = Transformer(_config(attention_impl="dot")).apply(variables, tokens)[0]
    np.testing.assert_allclose(logits, dot, atol=2e-5)


def test_trains_through_the_normal_path_and_the_bias_stays_bit_equal():
    """create_train_state -> replicate_state -> data_parallel_train_step; the
    selection bias rides in the state's batch_stats: AdamW (with weight decay)
    never sees it and a step leaves it bit-equal."""
    import functools

    import horovod_tpu as hvd

    hvd.init()
    losses = {}
    for impl in ("dot", "flash"):
        model = Transformer(_config(attention_impl=impl))
        tokens = jax.random.randint(jax.random.PRNGKey(0), (8, 33), 0, 64)
        inputs, labels = tokens[:, :-1], tokens[:, 1:]
        state = training.create_train_state(
            model, optax.adamw(1e-2, weight_decay=0.1), jax.random.PRNGKey(2),
            np.asarray(inputs[:1]))
        bias = jax.tree_util.tree_map(
            lambda b: 0.3 * jax.random.normal(jax.random.PRNGKey(8), b.shape), state.batch_stats)
        state = training.replicate_state(state.replace(batch_stats=bias), hvd.world_mesh())
        before = jax.tree_util.tree_map(np.asarray, state.batch_stats)
        step = training.data_parallel_train_step(
            model, optax.adamw(1e-2, weight_decay=0.1), loss_fn=functools.partial(
                transformer.next_token_loss, aux_coef=0.001))
        losses[impl] = []
        for _ in range(4):
            state, loss = step(state, inputs, labels)
            losses[impl].append(float(loss))
        assert losses[impl][-1] < losses[impl][0]
        after = jax.tree_util.tree_map(np.asarray, state.batch_stats)
        for a, b in zip(jax.tree_util.tree_leaves(after), jax.tree_util.tree_leaves(before)):
            assert a.tobytes() == b.tobytes() and np.abs(a).max() > 0
    np.testing.assert_allclose(losses["dot"], losses["flash"], rtol=2e-5)


def _step_text(cfg, tokens, labels, **kw):
    import horovod_tpu as hvd

    hvd.init()
    model, optimizer = Transformer(cfg), optax.adamw(1e-3)
    state = training.replicate_state(training.create_train_state(
        model, optimizer, jax.random.PRNGKey(0), tokens[:1]))
    text = training.data_parallel_train_step(model, optimizer, **kw).lower(
        state, tokens, labels).as_text()
    tree = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), state.params)
    return text, tree


_LM = dict(vocab_size=64, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
           max_seq_len=32, attention_impl="flash")
_SDAR = dict(vocab_size=32, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
             hidden_size=16, max_seq_len=32, num_experts=8, num_experts_per_tok=2,
             moe_intermediate_size=8, held_experts=(0, 4), block_diffusion=4, qk_norm=True,
             tie_word_embeddings=False, rope_theta=1e6, rms_norm_eps=1e-6,
             attention_impl="flash")
_DEFAULTS = dict(kv_lora_rank=None, qk_nope_head_dim=None, qk_rope_head_dim=None,
                 v_head_dim=None, intermediate_size=None, first_dense_layers=0,
                 num_shared_experts=0, router_scoring="softmax", routed_scaling_factor=1.0,
                 router_selection_bias=False, router_seq_aux=False)
# sha256 of the lowered step's text at the parent of PR 32 (commit 1d8bdce), this
# test's own helper run in that tree on the suite's eight CPU devices.  SDAR's is
# PR 33's: its routed layer's grouped products became the package's own kernels
# (ops/grouped_matmul.py), another program by design (before: b704eb86...7fe89);
# interpreted on the CPU their bodies are part of the text, so it moves with them;
# and PR 42's: dK/dV under the block-diffusion mask by the whole-group kernel.
# Both are PR 42's since the dK/dV kernels walk a program's heads as one under
# every mask, another program by design (before: the LM's d1d9a043...5463, PR
# 32's parent's; SDAR's f916682c...a577), and PR 43's since the forward and dQ
# kernels walk a program's query tiles as one with their sums in VMEM scratch,
# eight tiles an iteration first (before: the LM's 036f9338...f5dc, SDAR's
# ecd6688a...0e1b).  SDAR's is PR 47's: its routed layer's chunks are nine
# eighths and a quarter of the expected assignments where one size was twice
# them, another program by design (before: 8451fea3...9bc5); the LM's stays
_PARENT_TEXT = {"lm": "cc9b24840038ca14b23ce0dd5bed4aed58c54a3e1700c939a71bddbd833b7d0b", "sdar": "a69f593eee0cfd737aebeb3cfe29ea9bdcd4bdb81370ea52dac0912b7ba26a35"}


@pytest.mark.parametrize("name", ["lm", "sdar"])
def test_every_new_key_at_its_default_gives_the_model_that_was(name):
    """The LM's and SDAR's tiny models: stating every new key at its default
    changes neither the parameter tree nor the lowered step, and that step is,
    to the byte, the one the parent commit lowered."""
    import horovod_tpu as hvd

    hvd.init()
    tokens = jnp.zeros((hvd.size(), 32), jnp.int32)
    if name == "lm":
        base, labels, kw = _LM, tokens, {}
    else:
        base, kw = _SDAR, {"loss_fn": transformer.block_diffusion_loss}
        labels = (tokens[:, :16], jnp.ones((hvd.size(), 16), jnp.float32))
    text, tree = _step_text(TransformerConfig(**base), tokens, labels, **kw)
    stated_text, stated_tree = _step_text(
        TransformerConfig(**base, **_DEFAULTS), tokens, labels, **kw)
    assert stated_tree == tree and stated_text == text
    assert hashlib.sha256(text.encode()).hexdigest() == _PARENT_TEXT[name]
