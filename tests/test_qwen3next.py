"""Layers of two mixers in one model (PR 35): Gated DeltaNet beside gated
softmax attention with rotary positions on part of a head, zero-centred norms, a
gated shared expert beside top-k routed experts; the program against the
benchmark's plain float32 reference (``benchmark/reference/qwen3next_moe.py``:
the recurrence token by token) at small sizes on the CPU."""

import functools
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

from benchmark import flops_qwen3next, harness  # noqa: E402
from benchmark.families_qwen3next import Qwen3Next, layer_types  # noqa: E402
from benchmark.reference import chain, qwen3next_moe as reference  # noqa: E402
from horovod_tpu import trace, training  # noqa: E402
from horovod_tpu.models import transformer  # noqa: E402
from horovod_tpu.models.transformer import (  # noqa: E402
    Attention, GatedDeltaNet, MlpBlock, Transformer, TransformerConfig,
    ZeroCenteredRMSNorm, causal_depthwise_conv, modeled_activation_bytes,
)
from horovod_tpu.ops.grouped_matmul import tiles, visit_counts  # noqa: E402
from horovod_tpu.parallel.moe import RoutedExperts  # noqa: E402

OPS = chain.Ops("float32")
CELL = "qwen3-next-80b-a3b-s8192-1chip"
KINDS = ("linear_attention",) * 3 + ("full_attention",)
SIZES = (2, 8, 4, 8)      # key heads, key width, value heads, value width


def _config(**kw):
    """The tiny preset of the cell's shape: two key heads, four value heads,
    [linear, linear, linear, full], eight experts of which four are held, a
    gated shared expert, partial rotary, gated attention."""
    base = dict(
        vocab_size=64, num_layers=4, num_heads=4, num_kv_heads=2, head_dim=16,
        hidden_size=32, max_seq_len=256, dtype=jnp.float32, rms_norm_eps=1e-6,
        rope_theta=1e7, tie_word_embeddings=False, qk_norm=True, norm_zero_centered=True,
        attn_output_gate=True, partial_rotary_factor=0.25, layer_types=KINDS,
        linear_num_key_heads=2, linear_key_head_dim=8, linear_num_value_heads=4,
        linear_value_head_dim=8, num_experts=8, num_experts_per_tok=3,
        moe_intermediate_size=12, held_experts=(2, 4), num_shared_experts=1,
        shared_expert_gate=True)
    return TransformerConfig(**{**base, **kw})


def _tiny_cell_config(dtype="float32"):
    """The cell's configuration with every size made tiny (widths too: a
    test's sizes, never a cell's)."""
    config = harness.load_json(ROOT, "benchmark", "configs", "qwen3-next-80b-a3b.json")
    config.update(
        hidden_size=32, moe_intermediate_size=12, shared_expert_intermediate_size=12,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        linear_num_key_heads=2, linear_key_head_dim=8, linear_num_value_heads=4,
        linear_value_head_dim=8, vocab_size=64, router_experts=8, num_experts=4,
        held_experts_first=2, num_experts_per_tok=3, max_position_embeddings=256,
        compute_dtype=dtype)
    return config


# 150 tokens: three of the rule's chunks, the last one partial
_TRAFFIC = {"samples_per_chip": 2, "seq_len": 150, "layout": "dp", "step_options": {},
            "span_steps": 2, "trace_steps": 3}
_TIGHT = {"loss_gap": 2e-6, "grad_norm_gap": 5e-5, "delta_norm_gap": 5e-5,
          "grad_diff_gap": 5e-5}


def _tiny_cell(config):
    return harness.Cell(
        name="tiny-qwen3next-1", config_name="tiny", config=config, traffic_name="tiny",
        traffic=_TRAFFIC, chips=1,
        end_to_end=["setup_s", "train_tokens_per_s", "step_ms_p90", "mfu"], per_layer=[])


def _random_scales(params, seed=11):
    """Norm scales away from their initial 0 or 1, so that the (1 + w) and the
    plain form are told apart."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    leaves = [p + 0.2 * jax.random.normal(k, p.shape) if "scale" in jax.tree_util.keystr(path)
              else p for (path, p), k in zip(flat, keys)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


# -- the family through the harness: loss and every leaf's first gradient ----------


def test_family_through_run_cell_matches_the_reference_at_float32():
    config = _tiny_cell_config()
    config["check"] = dict(config["check"], diff_leaves="", limits=_TIGHT)
    cell = _tiny_cell(config)
    harness.check_names(cell)
    result = harness.run_cell(cell, seed=2 ** 31 + 35, seconds=0.3, trace=False,
                              devices=jax.devices()[:1])
    assert result["correct"], json.dumps(result["checks"])
    assert result["checks"]["grad_diff_gap"]["value"] < 5e-5     # over every leaf
    assert set(reference.REFERENCE_ROUTING) == {0, 1, 2, 3}
    assert reference.REFERENCE_ROUTING[0].shape == (1, 2 * 150, 3)
    assert set(reference.REFERENCE_DECAYS) == {0, 1, 2}          # layer 3 is full attention
    assert reference.REFERENCE_DECAYS[0].shape == (2, 150, 4)
    assert "heads in [0.9, 0.9999]" in reference.readings_report(2, 4)


@pytest.mark.parametrize("impl", ["dot", "flash"])
def test_logits_match_the_reference_forward(impl):
    """The whole model's logits, the carry as the kernel ('flash') and as the
    scan ('dot'), against the reference's stages run forward."""
    config = _tiny_cell_config()
    config["model"] = dict(config["model"], kwargs={"attention_impl": impl})
    model = Qwen3Next.model(config)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 150), 0, 64)
    params = _random_scales(model.init(jax.random.PRNGKey(1), tokens)["params"])
    logits, aux = model.apply({"params": params}, tokens)
    stages, _ = reference.build(config, _TRAFFIC)
    x = tokens
    for stage in stages:
        x = stage.forward(OPS, tuple(params[k] for k in stage.keys), x)
    want = reference.norm(x.h, params["ln_f"]["scale"], 1e-6) @ params["head"]["kernel"]
    # float32 through four layers of flax's own initial weights: the chunked
    # form sums in another order than the recurrence (1e-6 a layer, which the
    # norms and the router's softmax carry on); a row routed otherwise, or a
    # carry dropped, is 1e-2 and more
    np.testing.assert_allclose(logits, want, atol=5e-4)
    np.testing.assert_allclose(aux["aux_loss"], x.aux / 4, rtol=1e-5)
    assert int(aux["dropped_assignments"]) == 0


def test_batch_is_the_next_token_of_every_position():
    inputs, labels = Qwen3Next.batch(jax.random.PRNGKey(3), _tiny_cell_config(),
                                     dict(_TRAFFIC, seq_len=38), 5)
    assert inputs.shape == labels.shape == (5, 38)
    assert (np.asarray(inputs[:, 1:]) == np.asarray(labels[:, :-1])).all()
    assert int(inputs.max()) < 64 and len({tuple(r) for r in np.asarray(inputs)}) == 5


# -- the cell's numbers ---------------------------------------------------------------


def test_required_flops_and_the_cut_are_the_issue_s():
    cell = harness.load_cell(CELL)
    config, traffic = cell.config, cell.traffic
    assert layer_types(config) == KINDS and flops_qwen3next.layer_kinds(config) == (3, 1)
    linear = flops_qwen3next.linear_mixer_matrix_params(config)
    assert linear == 2048 * 12288 + 2048 * 64 + 4 * 8192 + 4096 * 2048      # 33.7 M
    full = flops_qwen3next.full_mixer_matrix_params(config)
    assert full == 2048 * 16 * 512 + 2 * 2048 * 2 * 256 + 4096 * 2048       # 27.3 M
    feed = flops_qwen3next.feed_forward_matrix_params(config)
    assert feed == 2048 * 512 + 3 * 2048 * 512 + 2048 + 0.625 * 3 * 2048 * 512
    delta = flops_qwen3next.delta_rule_flops_per_token(config)
    assert delta == 3 * 32 * (2 * 64 * (3 * 128 + 2 * 128) + 6 * 128 * 128)
    attention = 3 * 2 * (256 + 256) * 16 * 8192 / 2
    per_token = flops_qwen3next.train_flops_per_token(config, traffic)
    assert per_token == (6 * (3 * linear + full + 4 * feed + 2048 * 18992)
                         + 3 * delta + attention)
    assert abs(per_token - 1.405e9) < 0.002e9 and abs(per_token * 8192 - 11.51e12) < 0.01e12
    assert flops_qwen3next.gated_delta_train_flops_per_step(config, traffic, 1) == \
        3 * delta * 8192
    assert abs(3 * delta * 8192 - 0.425e12) < 0.001e12
    assert flops_qwen3next.attention_train_flops_per_step(config, traffic, 1) == \
        attention * 8192
    assert flops_qwen3next.expert_ffn_train_flops_per_step(config, traffic, 1) == \
        4 * 6.0 * 3 * 2048 * 512 * 5120
    # the cut: every width as published
    assert sorted(config["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                   "vocab_size": 151936}
    assert (config["num_hidden_layers"], config["num_experts"], config["router_experts"],
            config["vocab_size"] * 8) == (4, 32, 512, 151936)
    for key, value in (
            ("hidden_size", 2048), ("head_dim", 256), ("num_attention_heads", 16),
            ("num_key_value_heads", 2), ("partial_rotary_factor", 0.25),
            ("linear_num_key_heads", 16), ("linear_key_head_dim", 128),
            ("linear_num_value_heads", 32), ("linear_value_head_dim", 128),
            ("linear_conv_kernel_dim", 4), ("full_attention_interval", 4),
            ("moe_intermediate_size", 512), ("shared_expert_intermediate_size", 512),
            ("num_experts_per_tok", 10), ("rms_norm_eps", 1e-6), ("rope_theta", 10000000),
            ("intermediate_size", 5120), ("max_position_embeddings", 262144)):
        assert config[key] == value, key
    assert config["parameters"] == 625_667_136
    model = Qwen3Next.model(config)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32))["params"])
    assert sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes)) == 625_667_136


# -- the new modules against the reference's functions --------------------------------


def test_zero_centred_norm_is_one_plus_the_weight():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 7, 16))
    layer = ZeroCenteredRMSNorm(epsilon=1e-6, dtype=jnp.float32)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    assert float(jnp.max(jnp.abs(params["scale"]))) == 0.0           # w starts at 0
    w = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (16,))
    np.testing.assert_allclose(layer.apply({"params": {"scale": w}}, x),
                               reference.norm(x, w, 1e-6), atol=1e-6)
    unit = layer.apply({"params": params}, x)
    np.testing.assert_allclose(jnp.mean(unit ** 2, axis=-1), 1.0, atol=1e-4)


def test_causal_depthwise_convolution_is_four_shifted_multiply_adds():
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 20, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    got = causal_depthwise_conv(u, w)
    want = jax.vmap(lambda r: reference.causal_conv(r, w))(u)
    np.testing.assert_allclose(got, want, atol=1e-6)
    by_hand = jax.nn.silu(w[3] * u[0, 5] + w[2] * u[0, 4] + w[1] * u[0, 3] + w[0] * u[0, 2])
    np.testing.assert_allclose(got[0, 5], by_hand, atol=1e-6)
    np.testing.assert_allclose(got[0, 0], jax.nn.silu(w[3] * u[0, 0]), atol=1e-6)  # zeros before


def _grads_match(program, plain, params, x, atol=3e-5):
    w = jax.random.normal(jax.random.PRNGKey(3), program(params, x).shape)
    got = jax.grad(lambda p, x: jnp.sum(w * program(p, x)), (0, 1))(params, x)
    want = jax.grad(lambda p, x: jnp.sum(w * plain(p, x)), (0, 1))(params, x)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=atol * max(1.0, float(jnp.max(jnp.abs(b)))),
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("impl", ["dot", "flash"])
def test_gated_delta_net_matches_the_reference_forward_and_gradients(impl):
    cfg = _config(attention_impl=impl)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 150, 32))
    layer = GatedDeltaNet(cfg)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    assert jax.tree_util.tree_map(lambda p: p.shape, params) == {
        "in_proj_qkvz": {"kernel": (32, 2 * 16 + 2 * 32)}, "in_proj_ba": {"kernel": (32, 8)},
        "conv_kernel": (4, 2 * 16 + 32), "A_log": (4,), "dt_bias": (4,),
        "norm": {"scale": (8,)}, "out_proj": {"kernel": (32, 32)}}
    # heads that forget slowly: the state lives across the rule's chunks
    params = _random_scales(dict(params, A_log=jnp.log(jnp.array([0.02, 0.1, 0.5, 2.0])),
                                 dt_bias=jnp.full((4,), -1.0)))

    def program(p, x):
        return layer.apply({"params": p}, x)

    def plain(p, x):
        return jax.vmap(lambda r: reference.linear_mixer(OPS, p, r, 1e-6, *SIZES))(x)

    np.testing.assert_allclose(program(params, x), plain(params, x), atol=1e-5)
    _grads_match(program, plain, params, x)


@pytest.mark.parametrize("impl", ["dot", "flash"])
def test_gated_attention_matches_the_reference_forward_and_gradients(impl):
    """A query and a gate a head, zero-centred q / k norms, RoPE on a quarter of
    the head, two query heads a key/value head."""
    cfg = _config(attention_impl=impl)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 32))
    positions = jnp.broadcast_to(jnp.arange(40), (2, 40))
    layer = Attention(cfg)
    params = layer.init(jax.random.PRNGKey(0), x, positions)["params"]
    assert jax.tree_util.tree_map(lambda p: p.shape, params) == {
        "q": {"kernel": (32, 4, 32)}, "k": {"kernel": (32, 2, 16)},
        "v": {"kernel": (32, 2, 16)}, "q_norm": {"scale": (16,)},
        "k_norm": {"scale": (16,)}, "o": {"kernel": (4, 16, 32)}}
    params = _random_scales(params)

    def program(p, x):
        return layer.apply({"params": p}, x, positions)

    def plain(p, x):
        return jax.vmap(lambda r: reference.attention(OPS, p, r, 1e-6, 1e7, 4))(x)

    np.testing.assert_allclose(program(params, x), plain(params, x), atol=3e-6)
    _grads_match(program, plain, params, x)
    # the rotary part is the first quarter alone, and the gate gates
    whole = Attention(_config(attention_impl=impl, partial_rotary_factor=1.0))
    assert float(jnp.max(jnp.abs(whole.apply({"params": params}, x, positions)
                                 - program(params, x)))) > 1e-3
    ungated = Attention(_config(attention_impl=impl, attn_output_gate=False))
    assert ungated.init(jax.random.PRNGKey(0), x, positions)["params"]["q"]["kernel"].shape \
        == (32, 4, 16)


# -- the shares: one layer cut as the deployment cuts it --------------------------------


def test_all_shares_and_the_shared_expert_once_sum_to_the_uncut_layer():
    """32 experts, 5 a token, cut as the cell cuts its 512 (an even share a
    chip): the four shares of 8 experts each, each computed by the program's
    layer told which experts it holds, plus the gated shared expert counted
    ONCE, add up to the reference's feed-forward that holds all 32."""
    experts, top_k, width, ff, shares = 32, 5, 16, 8, 4
    cfg = _config(hidden_size=width, moe_intermediate_size=ff, num_experts=experts,
                  num_experts_per_tok=top_k)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, width))
    whole = RoutedExperts(experts, top_k, width, ff, dtype=jnp.float32)
    moe = whole.init(jax.random.PRNGKey(5), x)["params"]
    moe = dict(moe, router={"kernel": 3.0 * moe["router"]["kernel"]})
    shared_block = MlpBlock(cfg, hidden=ff)
    shared = shared_block.init(jax.random.PRNGKey(6), x)["params"]
    gate = jax.random.normal(jax.random.PRNGKey(7), (width, 1))
    uncut = {"moe": moe, "shared_experts": shared, "shared_expert_gate": {"kernel": gate}}
    want, _ = reference.feed_forward(OPS, uncut, x.reshape(-1, width), top_k, 0)
    total = shared_block.apply({"params": shared}, x) * jax.nn.sigmoid(x @ gate)
    assigned = 0
    for share in range(shares):
        first, count = share * experts // shares, experts // shares
        own = dict(moe, **{k: moe[k][first:first + count]
                           for k in ("w_gate", "w_up", "w_down")})
        layer = RoutedExperts(experts, top_k, width, ff, held=(first, count),
                              dtype=jnp.float32)
        y, out = layer.apply({"params": own}, x)
        total, assigned = total + y, assigned + int(out["assigned"])
        assert int(out["dropped"]) == 0
    assert assigned == 2 * 24 * top_k            # every assignment on exactly one share
    np.testing.assert_allclose(total.reshape(-1, width), want, atol=3e-6)
    routed_only, _ = reference.feed_forward(
        OPS, dict(uncut, shared_experts=jax.tree_util.tree_map(jnp.zeros_like, shared)),
        x.reshape(-1, width), top_k, 0)
    assert float(jnp.max(jnp.abs(want - routed_only))) > 1e-3      # the shared part counts


def test_many_small_experts_are_dropless_and_tiled_by_their_groups():
    """The cell's routed shape: tiles of 128 rows for groups of 160 (the first
    time on a chip), an expert's whole matrix a tile; the two shapes the
    benchmark had keep theirs."""
    assert tuple(tiles(5760, 2048, 512, 160, jnp.bfloat16)) == (128, 2048, 512)
    assert tuple(tiles(5760, 512, 2048, 160, jnp.bfloat16)) == (128, 512, 2048)
    assert tuple(tiles(9216, 2048, 768, 512, jnp.bfloat16)) == (256, 2048, 768)     # SDAR
    assert tuple(tiles(6912, 2048, 1408, 768, jnp.bfloat16)) == (256, 2048, 1408)   # Kimi
    assert visit_counts(5760, 32, 128, 160) == (64, 45)
    # top-3 of 8 with every row on one held expert: nothing is dropped
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (2, 40, 32)))
    layer = RoutedExperts(8, 3, 32, 12, held=(2, 4), chunk_rows=32, dtype=jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    kernel = (0.01 * params["router"]["kernel"]).at[:, 3].add(1.0)
    y, out = layer.apply({"params": dict(params, router={"kernel": kernel})}, x)
    assert int(out["assigned"]) > 2 * 32 and int(out["dropped"]) == 0


def test_events_carry_the_cell_s_shapes():
    """``moe.rows`` at 8,192 rows, top-10 of 512, 32 held: 81,920 slots, a first
    chunk of 5,760 (nine eighths of the 5,120 expected) and later ones of 1,280,
    128-row tiles, 64 visits of a balanced step against the chunk's 45 row
    tiles; ``flash.tiles`` at 256-wide heads."""
    from horovod_tpu.ops.flash_attention import flash_attention

    layer = RoutedExperts(512, 10, 2048, 512, held=(0, 32), dtype=jnp.bfloat16)
    t0 = trace.now()
    jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0),
                                      jnp.zeros((1, 8192, 2048), jnp.bfloat16)))
    event = [r[3] for r in trace.snapshot(t0) if r[0] == "moe.rows"][-1]
    assert (event["rows"], event["slots"], event["chunk"], event["expected"]) == \
        (8192, 81920, 5760, 5120.0)
    assert (event["first"], event["later"], event["gathered"]) == \
        (5760, 1280, 2 * 5760 + 2 * 81920)
    assert (event["tiles"], event["visits"], event["chunk_tiles"]) == ([128, 2048, 512], 64, 45)
    t0 = trace.now()
    q = jax.ShapeDtypeStruct((1, 8192, 16, 256), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8192, 2, 256), jnp.bfloat16)
    jax.eval_shape(jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, interpret=True).astype(jnp.float32))), q, kv, kv)
    events = {r[3]["kernel"]: r[3] for r in trace.snapshot(t0) if r[0] == "flash.tiles"}
    assert set(events) == {"flash_attention_fwd", "flash_attention_bwd_dq",
                           "flash_attention_bwd_dkv"}
    assert all(e["d_qk"] == e["d_v"] == 256 for e in events.values())
    assert events["flash_attention_fwd"]["visited"] == 528        # 32 x 33 / 2 tiles a head


# -- the model --------------------------------------------------------------------------


def test_the_model_s_tree_and_its_layers():
    cfg = _config(attention_impl="flash")
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 70), 0, 64)
    params = Transformer(cfg).init(jax.random.PRNGKey(1), tokens)["params"]
    rest = {"ln1", "ln2", "moe", "shared_experts", "shared_expert_gate"}
    for i in range(3):
        assert set(params[f"layer_{i}"]) == rest | {"linear_attn"}
    assert set(params["layer_3"]) == rest | {"attn"}
    assert params["layer_0"]["shared_expert_gate"]["kernel"].shape == (32, 1)
    assert params["layer_0"]["moe"]["w_gate"].shape == (4, 32, 12)
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        path = jax.tree_util.keystr(path)
        if path.endswith("['scale']"):      # zero-centred everywhere but the rule's own norm
            assert float(leaf[0]) == (1.0 if "linear_attn" in path else 0.0), path
    logits, aux = Transformer(cfg).apply({"params": params}, tokens)
    assert logits.shape == (2, 70, 64) and logits.dtype == jnp.float32
    assert aux["expert_index"].shape == (4, 140, 3) and int(aux["dropped_assignments"]) == 0
    dot = Transformer(_config(attention_impl="dot")).apply({"params": params}, tokens)[0]
    np.testing.assert_allclose(logits, dot, atol=5e-4)
    remat = Transformer(_config(attention_impl="flash", remat_policy="full"))
    grads = [jax.jit(jax.grad(lambda p: transformer.next_token_loss(
        m.apply({"params": p}, tokens), tokens, 0.001)))(params)
        for m in (Transformer(cfg), remat)]
    for a, b in zip(*map(jax.tree_util.tree_leaves, grads)):
        np.testing.assert_allclose(a, b, atol=1e-5 * max(1.0, float(jnp.max(jnp.abs(b)))))


def test_trains_through_the_normal_path():
    """create_train_state -> replicate_state -> data_parallel_train_step, the
    carry as the kernel and as the scan: the same losses, falling."""
    import horovod_tpu as hvd

    hvd.init()
    losses = {}
    for impl in ("dot", "flash"):
        model = Transformer(_config(attention_impl=impl))
        tokens = jax.random.randint(jax.random.PRNGKey(0), (8, 71), 0, 64)
        inputs, labels = tokens[:, :-1], tokens[:, 1:]
        state = training.create_train_state(
            model, optax.adamw(1e-2), jax.random.PRNGKey(2), np.asarray(inputs[:1]))
        state = training.replicate_state(state, hvd.world_mesh())
        step = training.data_parallel_train_step(
            model, optax.adamw(1e-2), loss_fn=functools.partial(
                transformer.next_token_loss, aux_coef=0.001))
        losses[impl] = []
        for _ in range(4):
            state, loss = step(state, inputs, labels)
            losses[impl].append(float(loss))
        assert losses[impl][-1] < losses[impl][0]
    # the first loss is the same forward pass; AdamW at 1e-2 then turns the
    # float32 noise of two summation orders into steps of their own
    np.testing.assert_allclose(losses["dot"][0], losses["flash"][0], rtol=1e-5)
    np.testing.assert_allclose(losses["dot"], losses["flash"], rtol=2e-2)


# -- the backward reads what the forward made (PR 41) -----------------------------------

_RULE_PROGRAMS = ("gated_delta_kkt", "gated_delta_fwd", "_block_inverse",
                  "gdn_conv_norm_fwd", "_rule")


def _checkpointed_mix(monkeypatch):
    """The mixer as it was until PR 41: steps 2-5 (the input pass, the gates,
    the rule) under a BARE ``jax.checkpoint``, which kept their inputs alone
    and made the rest again in the backward (the package's ``_mix`` keeps
    every residual).  The old form lives here, not in the package."""
    monkeypatch.setattr(transformer, "_mix", jax.checkpoint(
        transformer._delta_mix, static_argnums=(0,)))


def _two_mixer_loss(impl, **kw):
    """Two linear layers and a full one over routed experts, 150 tokens (three
    of the rule's chunks, the last partial): parameters, loss(params)."""
    cfg = _config(attention_impl=impl, num_layers=3,
                  layer_types=("linear_attention",) * 2 + ("full_attention",), **kw)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 150), 0, 64)
    model = Transformer(cfg)
    # one program, not a layer operation by operation (under a block's remat too)
    params = _random_scales(jax.jit(model.init)(jax.random.PRNGKey(1), tokens)["params"])
    return params, lambda p: transformer.next_token_loss(
        model.apply({"params": p}, tokens), tokens, 0.001)


@pytest.mark.parametrize("impl", ["dot", "flash"])
def test_loss_and_gradients_are_the_checkpointed_mixer_s(impl, monkeypatch):
    """Nothing of the arithmetic changed with the checkpoint: the backward
    reads the very tensors it used to make again.  Loss bit for bit, every
    leaf's gradient to float32's last places."""
    params, loss = _two_mixer_loss(impl)
    got, grads = jax.jit(jax.value_and_grad(loss))(params)
    _checkpointed_mix(monkeypatch)
    want, want_grads = jax.jit(jax.value_and_grad(loss))(params)
    assert float(got) == float(want)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(flat) > 30 and all(bool(jnp.any(g != 0)) for _, g in flat)
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-7 * float(jnp.max(jnp.abs(b))),
                                   err_msg=jax.tree_util.keystr(path))


def _programs_in_the_gradient(loss, params):
    """Call sites of each of ``_RULE_PROGRAMS`` (a kernel's or a ``jit``'s
    name) in ``jax.grad(loss)``'s program, and whether any ``jax.checkpoint``
    in it makes something again."""
    from test_routed_block_diffusion import _primitives

    eqns = list(_primitives(jax.make_jaxpr(jax.grad(loss))(params).jaxpr))
    names = [eqn.params.get("name") for eqn in eqns]
    # a checkpoint with no policy keeps its inputs alone (``_mix``'s keeps all)
    bare = any(e.primitive.name.startswith("remat") and e.params.get("policy") is None
               for e in eqns)
    return [names.count(name) for name in _RULE_PROGRAMS], bare


@pytest.mark.parametrize("impl", ["dot", "flash"])
def test_the_differentiated_program_runs_the_rule_s_forward_once_a_layer(impl, monkeypatch):
    """Two linear layers: the inverse's own program (``_inverse``, the ``jit`` of
    ``_block_inverse``), ``gated_delta_kkt``, ``gated_delta_fwd`` and the
    input pass's forward kernel twice each in ``jax.grad``'s program, once a
    layer, and no checkpoint that makes anything again (``_mix``'s policy
    keeps every residual).  Under the old bare checkpoint each forward is
    there a second time: what fails if a later change makes the rule again.
    (The rule's ``jit`` is split three ways a layer under either.)"""
    params, loss = _two_mixer_loss(impl)
    once = [2, 2, 2, 2] if impl == "flash" else [0, 0, 0, 0]
    assert _programs_in_the_gradient(loss, params) == (once + [6], False)
    _checkpointed_mix(monkeypatch)
    assert _programs_in_the_gradient(loss, params) == ([2 * n for n in once] + [6], True)


def test_a_block_under_remat_policy_makes_its_mixer_again_and_agrees_with_the_reference():
    """The per-block policy composes: under ``remat_policy="full"`` the family
    trains through ``run_cell`` (three checked steps and a window) within the
    float32 limits of the reference, and the block's backward holds the rule's
    forward a second time (the block's own choice, as for any other layer)."""
    config = _tiny_cell_config()
    config["model"] = dict(config["model"], kwargs={"remat_policy": "full"})
    config["check"] = dict(config["check"], diff_leaves="", limits=_TIGHT)
    assert Qwen3Next.model(config).cfg.block_remat_policies() == ("full",) * 4
    result = harness.run_cell(_tiny_cell(config), seed=41, seconds=0.3, trace=False,
                              devices=jax.devices()[:1])
    assert result["correct"], json.dumps(result["checks"])
    assert result["checks"]["grad_diff_gap"]["value"] < 5e-5     # over every leaf
    params, loss = _two_mixer_loss("flash", remat_policy="full")
    assert _programs_in_the_gradient(loss, params) == ([4, 4, 4, 4, 8], True)


# -- what is refused, by the key's name ---------------------------------------------------


def test_a_linear_layer_is_refused_where_it_cannot_run():
    with pytest.raises(ValueError, match="layer_types names one of"):
        _config(layer_types=KINDS[:3])
    with pytest.raises(ValueError, match="layer_types names one of"):
        _config(layer_types=("linear",) * 4)
    with pytest.raises(ValueError, match="needs linear_num_key_heads"):
        _config(linear_key_head_dim=None)
    with pytest.raises(ValueError, match="a multiple of the key heads"):
        _config(linear_num_value_heads=3)
    with pytest.raises(ValueError, match=r"layer_types with a 'linear_attention' layer "
                                         r"supports attention_impl 'dot'/'flash'"):
        _config(attention_impl="ring", seq_axis_name="sp")
    with pytest.raises(ValueError, match="layer_types with a 'linear_attention' layer "
                                         "takes no block_diffusion"):
        _config(block_diffusion=4)
    with pytest.raises(ValueError, match="shared_expert_gate needs num_shared_experts"):
        _config(num_shared_experts=0)
    with pytest.raises(ValueError, match="partial_rotary_factor"):
        _config(partial_rotary_factor=0.2)
    with pytest.raises(ValueError, match="no attn_output_gate and no partial_rotary_factor"):
        TransformerConfig(num_heads=4, hidden_size=32, kv_lora_rank=16, qk_nope_head_dim=8,
                          qk_rope_head_dim=4, v_head_dim=8, attn_output_gate=True)
    # paged serving, and so the serving engine: no recurrent-state cache
    dense = _config(num_experts=None, num_shared_experts=0, shared_expert_gate=False,
                    held_experts=None)
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = Transformer(dense).init(jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match=r"paged serving takes no 'linear_attention' layer "
                                         r"\(layer_types\)"):
        Transformer(dense).apply(params, tokens, train=False, paged=object())
    # a bound shard axis of more than one chip
    sharded = _config(num_experts=None, num_shared_experts=0, shared_expert_gate=False,
                      held_experts=None, shard_axis="tp")
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("tp",))
    with pytest.raises(ValueError, match=r"shard_axis 'tp' takes no 'linear_attention' layer "
                                         r"\(layer_types\)"):
        jax.shard_map(lambda t: Transformer(sharded).apply(params, t), mesh=mesh,
                      in_specs=jax.sharding.PartitionSpec(),
                      out_specs=jax.sharding.PartitionSpec(), check_vma=False)(tokens)
    with pytest.raises(ValueError, match=r"layers of two mixers \(layer_types\)"):
        modeled_activation_bytes(dense, batch=1, seq=32)
    # all full attention is what None is
    full = _config(layer_types=("full_attention",) * 4)
    assert not full.has_linear_attention and not _config(layer_types=None).has_linear_attention


# -- every new key at its default is the model that was -----------------------------------

_NEW_DEFAULTS = dict(layer_types=None, linear_num_key_heads=None, linear_key_head_dim=None,
                     linear_num_value_heads=None, linear_value_head_dim=None,
                     linear_conv_kernel_dim=4, partial_rotary_factor=1.0,
                     attn_output_gate=False, norm_zero_centered=False,
                     shared_expert_gate=False)


@pytest.mark.parametrize("name", ["lm", "sdar"])
def test_every_new_key_at_its_default_gives_the_model_that_was(name):
    """The LM's and SDAR's tiny models of ``tests/test_latent_attention_moe.py``
    (its helper, its parent hashes): stating PR 35's keys at their defaults
    changes neither the parameter tree nor the lowered step, which is still, to
    the byte, the one those parents lowered."""
    import horovod_tpu as hvd
    import test_latent_attention_moe as before

    hvd.init()
    tokens = jnp.zeros((hvd.size(), 32), jnp.int32)
    if name == "lm":
        base, labels, kw = before._LM, tokens, {}
    else:
        base, kw = before._SDAR, {"loss_fn": transformer.block_diffusion_loss}
        labels = (tokens[:, :16], jnp.ones((hvd.size(), 16), jnp.float32))
    text, tree = before._step_text(TransformerConfig(**base), tokens, labels, **kw)
    stated_text, stated_tree = before._step_text(
        TransformerConfig(**base, **_NEW_DEFAULTS), tokens, labels, **kw)
    assert stated_tree == tree and stated_text == text
    assert hashlib.sha256(text.encode()).hexdigest() == before._PARENT_TEXT[name]
