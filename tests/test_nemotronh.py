"""Layers of ONE sublayer in one model (PR 48): Mamba-2 mixers on the chunked
state-space scan, squared-ReLU experts routed in a latent narrower than the
stream beside a shared expert, attention without rotary positions; the program
against the benchmark's plain float32 reference
(``benchmark/reference/nemotronh.py``: the recurrence token by token) at small
sizes on the CPU, and a head share tied to the uncut layer."""

import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import flops_nemotronh, harness  # noqa: E402
from benchmark.families_nemotronh import NemotronH, sublayers  # noqa: E402
from benchmark.reference import chain, nemotronh as reference  # noqa: E402
from horovod_tpu import trace  # noqa: E402
from horovod_tpu.models.transformer import (  # noqa: E402
    Attention, Mamba2, MlpBlock, Transformer, TransformerConfig, modeled_activation_bytes,
)
from horovod_tpu.parallel.moe import RoutedExperts  # noqa: E402

OPS = chain.Ops("float32")
CELL = "nemotron3-super-120b-a12b-s8192-1chip"
PATTERN = "MEMEMEMEM*E"
SIZES = (4, 8, 2, 16)      # Mamba heads, head width, groups, state size


def _config(**kw):
    """The tiny preset of the cell's shape: four Mamba heads in two groups,
    four query heads over one key/value head, sixteen experts of which four are
    held, in a 16-wide latent of a 32-wide stream."""
    base = dict(
        vocab_size=64, num_layers=11, num_heads=4, num_kv_heads=1, head_dim=16,
        hidden_size=32, intermediate_size=24, max_seq_len=256, dtype=jnp.float32,
        tie_word_embeddings=False, partial_rotary_factor=0.0,
        sublayers=tuple({"M": "mamba", "E": "moe", "*": "attention"}[c] for c in PATTERN),
        mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16, n_groups=2, chunk_size=16,
        mlp_hidden_act="relu2", num_experts=16, num_experts_per_tok=3,
        moe_intermediate_size=24, moe_latent_size=16, moe_shared_expert_intermediate_size=40,
        held_experts=(2, 4), router_scoring="sigmoid", routed_scaling_factor=2.5,
        router_selection_bias=True)
    return TransformerConfig(**{**base, **kw})


def _tiny_cell_config(dtype="float32"):
    """The cell's configuration with every size made tiny (widths too: a
    test's sizes, never a cell's)."""
    config = harness.load_json(ROOT, "benchmark", "configs", "nemotron3-super-120b-a12b.json")
    config.update(
        hidden_size=32, intermediate_size=24, moe_intermediate_size=24, moe_latent_size=16,
        moe_shared_expert_intermediate_size=40, num_attention_heads=4, num_key_value_heads=1,
        head_dim=16, mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16, n_groups=2,
        chunk_size=16, vocab_size=64, router_experts=16, n_routed_experts=4,
        held_experts_first=2, num_experts_per_tok=3, max_position_embeddings=256,
        compute_dtype=dtype, hybrid_override_pattern=PATTERN, hybrid_override_layers=[0, 11])
    return config


# 40 tokens: three of the scan's chunks of 16, the last one partial
_TRAFFIC = {"samples_per_chip": 2, "seq_len": 40, "layout": "dp", "step_options": {},
            "span_steps": 2, "trace_steps": 3}
_TIGHT = {"loss_gap": 2e-6, "grad_norm_gap": 5e-5, "delta_norm_gap": 5e-5,
          "grad_diff_gap": 5e-5}


def _grads_match(program, plain, params, x, atol=3e-5):
    w = jax.random.normal(jax.random.PRNGKey(3), program(params, x).shape)
    got = jax.grad(lambda p, x: jnp.sum(w * program(p, x)), (0, 1))(params, x)
    want = jax.grad(lambda p, x: jnp.sum(w * plain(p, x)), (0, 1))(params, x)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=atol * max(1.0, float(jnp.max(jnp.abs(b)))),
                                   err_msg=jax.tree_util.keystr(path))


def _slow_mamba(params, heads=4):
    """Heads that forget slowly (the state lives across the scan's chunks), a
    bias, a skip and a norm scale away from their initial 0 and 1."""
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    return dict(
        params, A_log=jnp.log(jnp.linspace(0.05, 2.0, heads)), dt_bias=jnp.full((heads,), -1.0),
        D=jax.random.normal(keys[0], (heads,)),
        conv_bias=0.3 * jax.random.normal(keys[1], params["conv_bias"].shape),
        norm={"scale": 1.0 + 0.2 * jax.random.normal(keys[2], params["norm"]["scale"].shape)})


# -- the family through the harness: loss and every leaf's first gradient -------------


def test_family_through_run_cell_matches_the_reference_at_float32():
    """A tiny ``MEMEMEMEM*E`` model through ``harness.run_cell`` (the program's
    normal path, the kernels interpreted): the loss of three steps and every
    leaf's first gradient against the plain reference."""
    config = _tiny_cell_config()
    config["check"] = dict(config["check"], diff_leaves="", limits=_TIGHT)
    cell = harness.Cell(
        name="tiny-nemotronh-1", config_name="tiny", config=config, traffic_name="tiny",
        traffic=_TRAFFIC, chips=1,
        end_to_end=["setup_s", "train_tokens_per_s", "step_ms_p90", "mfu"], per_layer=[])
    harness.check_names(cell)
    result = harness.run_cell(cell, seed=2 ** 31 + 48, seconds=0.3, trace=False,
                              devices=jax.devices()[:1])
    assert result["correct"], json.dumps(result["checks"])
    assert result["checks"]["grad_diff_gap"]["value"] < 5e-5     # over every leaf
    assert set(reference.REFERENCE_ROUTING) == {1, 3, 5, 7, 10}
    assert reference.REFERENCE_ROUTING[1].shape == (1, 2 * 40, 3)
    assert set(reference.REFERENCE_DECAYS) == {0, 2, 4, 6, 8}
    assert reference.REFERENCE_DECAYS[0].shape == (2, 40, 4)
    assert "heads in [0.9, 0.9999]" in reference.readings_report(2, 4)


def test_required_flops_and_the_cut_are_the_issue_s():
    cell = harness.load_cell(CELL)
    config, traffic = cell.config, cell.traffic
    assert len(config["hybrid_override_pattern"]) == 88        # the published string, whole
    assert config["hybrid_override_layers"] == [27, 38]
    assert flops_nemotronh.pattern(config) == PATTERN
    assert sublayers(config).count("mamba") == 5 and sublayers(config)[9] == "attention"
    assert flops_nemotronh.layer_counts(config) == {"M": 5, "E": 5, "*": 1, "-": 0}
    mamba = flops_nemotronh.mamba_matrix_params(config)
    assert mamba == 4096 * 2320 + 4 * 1280 + 1024 * 4096                     # 13.7 M
    scan = flops_nemotronh.ssd_flops_per_token(config)
    assert scan == 3 * (16 * (2 * 128 * 64 + 4 * 128 * 64) + 2 * 128 * 128)
    routed = 6 * 0.34375 * 2 * 1024 * 2688
    per_token = flops_nemotronh.train_flops_per_token(config, traffic)
    assert per_token == (
        6 * (5 * mamba + 5 * (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376)
             + (4096 * 4 * 128 * 2 + 2 * 4096 * 128) + 4096 * 16384)
        + 5 * routed + 5 * scan + 3 * 2 * 256 * 4 * 8192 / 2)
    assert abs(per_token - 2.575e9) < 0.003e9 and abs(per_token * 8192 - 21.1e12) < 0.05e12
    assert 5 * scan / per_token < 0.01                    # the scan: under 1 % of the FLOPs
    assert flops_nemotronh.ssd_train_flops_per_step(config, traffic, 1) == 5 * scan * 8192
    assert flops_nemotronh.expert_ffn_train_flops_per_step(config, traffic, 1) == \
        5 * 6.0 * 2 * 1024 * 2688 * 2816
    # the cut: every width as published
    cut = {"num_hidden_layers": (11, 88), "mamba_num_heads": (16, 128), "n_groups": (1, 8),
           "num_attention_heads": (4, 32), "num_key_value_heads": (1, 2),
           "n_routed_experts": (8, 512), "vocab_size": (16384, 131072)}
    assert sorted(config["reduced"]) == sorted(cut)
    for key, (here, published) in cut.items():
        assert (config[key], config["published"][key]) == (here, published), key
    for key, value in (
            ("hidden_size", 4096), ("head_dim", 128), ("mamba_head_dim", 64),
            ("ssm_state_size", 128), ("conv_kernel", 4), ("chunk_size", 128), ("expand", 2),
            ("moe_intermediate_size", 2688), ("moe_latent_size", 1024),
            ("moe_shared_expert_intermediate_size", 5376), ("intermediate_size", 2688),
            ("num_experts_per_tok", 22), ("routed_scaling_factor", 5), ("router_experts", 512),
            ("norm_eps", 1e-5), ("mlp_hidden_act", "relu2"), ("use_conv_bias", True),
            ("max_position_embeddings", 262144)):
        assert config[key] == value, key
    assert config["parameters"] == 700_862_960
    assert 0.25 * 16e9 < config["compiled_step_bytes"] < 16e9
    model = NemotronH.model(config)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32))["params"])
    assert sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes)) == 700_862_960
    assert shapes["layer_0"]["mixer"]["in_proj"]["kernel"].shape == (4096, 2320)
    assert shapes["layer_1"]["moe"]["w_up"].shape == (8, 1024, 2688)
    assert shapes["layer_9"]["attn"]["k"]["kernel"].shape == (4096, 1, 128)


# -- the new modules against the reference's functions --------------------------------


def test_mamba2_matches_the_reference_forward_and_gradients():
    """The kernels' path ('flash'); the ``jnp`` path's gradients are held at the
    scan (tests/test_ssd.py) and its forward at the model (below)."""
    cfg = _config(attention_impl="flash")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 32))
    layer = Mamba2(cfg)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    assert jax.tree_util.tree_map(lambda p: p.shape, params) == {
        "in_proj": {"kernel": (32, 32 + 32 + 2 * 2 * 16 + 4)}, "conv_kernel": (4, 96),
        "conv_bias": (96,), "A_log": (4,), "D": (4,), "dt_bias": (4,),
        "norm": {"scale": (32,)}, "out_proj": {"kernel": (32, 32)}}
    # the published initialisation: steps within (1e-3, 0.1), A within (-16, -1)
    dt = jax.nn.softplus(params["dt_bias"])
    assert float(dt.min()) >= 9e-4 and float(dt.max()) <= 0.11
    assert float(params["A_log"].min()) >= 0.0 and float(params["A_log"].max()) <= np.log(16)
    params = _slow_mamba(params)

    def program(p, x):
        return layer.apply({"params": p}, x)

    def plain(p, x):
        return jax.vmap(lambda r: reference.mamba_mixer(OPS, p, r, 1e-5, *SIZES))(x)

    np.testing.assert_allclose(program(params, x), plain(params, x), atol=1e-5)
    _grads_match(program, plain, params, x)


@pytest.mark.parametrize("chunk_rows,router", [(None, "spread"), (32, "one_expert")],
                         ids=["balanced_one_chunk", "overflow_chunks"])
def test_latent_routed_layer_matches_the_reference(chunk_rows, router):
    """Squared-ReLU experts of two matrices in a 16-wide latent of the 32-wide
    stream, sigmoid top-3 of 16 times 2.5, four held; under a router that sends
    every row to one held expert the overflow chunks run and nothing is
    dropped."""
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (2, 40, 32)))
    layer = RoutedExperts(16, 3, 32, 24, held=(2, 4), chunk_rows=chunk_rows,
                          dtype=jnp.float32, scoring="sigmoid", scaling_factor=2.5,
                          selection_bias=True, latent=16, gated=False)
    variables = layer.init(jax.random.PRNGKey(0), x)
    params, stats = variables["params"], variables["batch_stats"]
    assert jax.tree_util.tree_map(lambda p: p.shape, params) == {
        "router": {"kernel": (32, 16)}, "latent_down": {"kernel": (32, 16)},
        "latent_up": {"kernel": (16, 32)}, "w_up": (4, 16, 24), "w_down": (4, 24, 16)}
    kernel = 3.0 * params["router"]["kernel"]
    if router == "one_expert":
        kernel = (0.01 * kernel).at[:, 3].add(1.0)
    params = dict(params, router={"kernel": kernel})

    def program(p, x):
        return layer.apply({"params": p, "batch_stats": stats}, x)[0]

    def plain(p, x):
        shared = {"up": {"kernel": jnp.zeros((32, 8))}, "down": {"kernel": jnp.zeros((8, 32))}}
        y, _ = reference.feed_forward(OPS, {"moe": p, "shared_experts": shared},
                                      x.reshape(-1, 32), 3, 2, 2.5)
        return y.reshape(x.shape)

    out = layer.apply({"params": params, "batch_stats": stats}, x)[1]
    assert int(out["dropped"]) == 0
    if router == "one_expert":
        assert int(out["assigned"]) >= 2 * 40 and int(out["chunks"]) > 1
    np.testing.assert_allclose(program(params, x), plain(params, x), atol=1e-5)
    _grads_match(program, plain, params, x)
    want_aux = reference.feed_forward(
        OPS, {"moe": params, "shared_experts": {
            "up": {"kernel": jnp.zeros((32, 8))}, "down": {"kernel": jnp.zeros((8, 32))}}},
        x.reshape(-1, 32), 3, 2, 2.5)[1]
    np.testing.assert_allclose(out["aux_loss"], want_aux, rtol=1e-5)


def test_attention_without_rotation_and_the_plain_feed_forward_match_the_reference():
    """``partial_rotary_factor`` 0 rotates no column: the layer ignores its
    positions; four query heads over one key/value head.  ``relu2``: no gate
    matrix."""
    impl = "flash"
    cfg = _config(attention_impl=impl)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 32))
    positions = jnp.broadcast_to(jnp.arange(40), (2, 40))
    layer = Attention(cfg)
    params = layer.init(jax.random.PRNGKey(0), x, positions)["params"]
    assert jax.tree_util.tree_map(lambda p: p.shape, params) == {
        "q": {"kernel": (32, 4, 16)}, "k": {"kernel": (32, 1, 16)},
        "v": {"kernel": (32, 1, 16)}, "o": {"kernel": (4, 16, 32)}}
    program = lambda p, x: layer.apply({"params": p}, x, positions)
    plain = lambda p, x: jax.vmap(lambda r: reference.attention(OPS, p, r))(x)
    np.testing.assert_allclose(program(params, x), plain(params, x), atol=3e-6)
    np.testing.assert_allclose(layer.apply({"params": params}, x, positions + 7),
                               program(params, x), atol=0)
    _grads_match(program, plain, params, x)
    rotated = Attention(_config(attention_impl=impl, partial_rotary_factor=1.0))
    assert float(jnp.max(jnp.abs(rotated.apply({"params": params}, x, positions)
                                 - program(params, x)))) > 1e-3
    mlp = MlpBlock(cfg)
    weights = mlp.init(jax.random.PRNGKey(2), x)["params"]
    assert set(weights) == {"up", "down"} and weights["up"]["kernel"].shape == (32, 24)
    np.testing.assert_allclose(
        mlp.apply({"params": weights}, x),
        jax.vmap(lambda r: reference.relu2(OPS, weights, r))(x), atol=3e-6)
    assert set(MlpBlock(_config(mlp_hidden_act="silu")).init(
        jax.random.PRNGKey(2), x)["params"]) == {"gate", "up", "down"}


# -- the shares: one layer cut as the deployment cuts it ------------------------------


def _mamba_share(whole, rank, ranks, heads, p, groups, n):
    """A head-parallel rank's slice of a whole Mamba-2 layer's weights: its
    heads' columns of z, x and dt, its groups' columns of B and C, the same of
    the convolution, its heads' A, D, dt_bias, norm columns and ``out_proj``
    rows."""
    inner = heads * p
    h0, h1 = rank * heads // ranks, (rank + 1) * heads // ranks
    g0, g1 = rank * groups // ranks, (rank + 1) * groups // ranks
    mine = np.r_[h0 * p:h1 * p]
    mixed = np.r_[mine, inner + np.r_[g0 * n:g1 * n], inner + groups * n + np.r_[g0 * n:g1 * n]]
    columns = np.r_[mine, inner + mixed, 2 * inner + 2 * groups * n + np.r_[h0:h1]]
    return {
        "in_proj": {"kernel": whole["in_proj"]["kernel"][:, columns]},
        "conv_kernel": whole["conv_kernel"][:, mixed], "conv_bias": whole["conv_bias"][mixed],
        "A_log": whole["A_log"][h0:h1], "D": whole["D"][h0:h1],
        "dt_bias": whole["dt_bias"][h0:h1], "norm": {"scale": whole["norm"]["scale"][mine]},
        "out_proj": {"kernel": whole["out_proj"]["kernel"][mine]}}


def test_the_head_shares_sum_to_the_uncut_mamba_and_attention_layers():
    """The cut tied to the model: eight heads in four groups cut as the cell cuts
    its 128 in 8 (a group a rank), each rank's mixer the program's module built
    at the rank's heads and ONE group with weights sliced from one whole layer by
    head and group; the ranks' ``out_proj`` summands add up to the reference's
    UNCUT layer.  Attention: eight query heads over two key/value heads on four
    ranks, a key/value head held by two ranks."""
    ranks, heads, p, groups, n, impl = 4, 8, 8, 4, 16, "flash"
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 32))
    whole_cfg = _config(attention_impl=impl, mamba_num_heads=heads, n_groups=groups,
                        num_heads=8, num_kv_heads=2)
    whole = _slow_mamba(Mamba2(whole_cfg).init(jax.random.PRNGKey(0), x)["params"], heads)
    want = jax.vmap(lambda r: reference.mamba_mixer(
        OPS, whole, r, 1e-5, heads, p, groups, n))(x)
    share_cfg = _config(attention_impl=impl, mamba_num_heads=heads // ranks,
                        n_groups=groups // ranks, num_heads=2, num_kv_heads=1)
    total = sum(Mamba2(share_cfg).apply(
        {"params": _mamba_share(whole, rank, ranks, heads, p, groups, n)}, x)
        for rank in range(ranks))
    np.testing.assert_allclose(total, want, atol=2e-5)
    one = Mamba2(share_cfg).apply(
        {"params": _mamba_share(whole, 0, ranks, heads, p, groups, n)}, x)
    assert float(jnp.max(jnp.abs(one - want))) > 1e-3          # a summand is no layer
    positions = jnp.broadcast_to(jnp.arange(40), (2, 40))
    attn = Attention(whole_cfg).init(jax.random.PRNGKey(2), x, positions)["params"]
    want = jax.vmap(lambda r: reference.attention(OPS, attn, r))(x)
    total = 0.0
    for rank in range(ranks):
        q, kv = slice(2 * rank, 2 * rank + 2), slice(rank // 2, rank // 2 + 1)
        own = {"q": {"kernel": attn["q"]["kernel"][:, q]},
               "k": {"kernel": attn["k"]["kernel"][:, kv]},
               "v": {"kernel": attn["v"]["kernel"][:, kv]},
               "o": {"kernel": attn["o"]["kernel"][q]}}
        total = total + Attention(share_cfg).apply({"params": own}, x, positions)
    np.testing.assert_allclose(total, want, atol=3e-6)


def test_the_expert_shares_and_the_shared_expert_once_sum_to_the_uncut_routed_layer():
    """32 experts, 5 a token, cut as the cell cuts its 512 (an even share a
    chip): the four shares of 8 experts, each computed by the program's layer
    told which experts it holds (the router and both latent projections whole
    on each), plus the shared expert counted ONCE, add up to the reference's
    layer that holds all 32.  ``latent_up`` is linear, so the shares' sums
    commute with it."""
    experts, top_k, width, latent, ff, shares = 32, 5, 32, 16, 8, 4
    cfg = _config()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, width))
    make = functools.partial(RoutedExperts, experts, top_k, width, ff, dtype=jnp.float32,
                             scoring="sigmoid", scaling_factor=2.5, selection_bias=True,
                             latent=latent, gated=False)
    variables = make().init(jax.random.PRNGKey(5), x)
    moe, stats = variables["params"], variables["batch_stats"]
    moe = dict(moe, router={"kernel": 3.0 * moe["router"]["kernel"]})
    shared_block = MlpBlock(cfg, hidden=40)
    shared = shared_block.init(jax.random.PRNGKey(6), x)["params"]
    want, _ = reference.feed_forward(OPS, {"moe": moe, "shared_experts": shared},
                                     x.reshape(-1, width), top_k, 0, 2.5)
    total, assigned = shared_block.apply({"params": shared}, x), 0
    for share in range(shares):
        first, count = share * experts // shares, experts // shares
        own = dict(moe, **{k: moe[k][first:first + count] for k in ("w_up", "w_down")})
        y, out = make(held=(first, count)).apply({"params": own, "batch_stats": stats}, x)
        total, assigned = total + y, assigned + int(out["assigned"])
        assert int(out["dropped"]) == 0
    assert assigned == 2 * 24 * top_k            # every assignment on exactly one share
    np.testing.assert_allclose(total.reshape(-1, width), want, atol=5e-6)


# -- the model --------------------------------------------------------------------------


def test_the_model_s_tree_and_its_events():
    """One norm and one sublayer a layer; 'dot' and 'flash' agree; the events a
    traced program leaves.  (The normal path, create_train_state ->
    replicate_state -> data_parallel_train_step, is the harness's: the first
    test.)"""
    cfg = _config(attention_impl="flash")
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 41), 0, 64)
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    t0 = trace.now()
    variables = Transformer(cfg).init(jax.random.PRNGKey(1), inputs)
    events = trace.snapshot(t0)
    params = variables["params"]
    for i, letter in enumerate(PATTERN):
        assert set(params[f"layer_{i}"]) == {"norm"} | {
            "M": {"mixer"}, "E": {"moe", "shared_experts"}, "*": {"attn"}}[letter]
    assert variables["batch_stats"]["layer_1"]["moe"]["e_score_correction_bias"].shape == (16,)
    assert set(params["layer_1"]["shared_experts"]) == {"up", "down"}
    assert params["layer_1"]["shared_experts"]["up"]["kernel"].shape == (32, 40)
    scans = [r[3] for r in events if r[0] == "ssd.chunks"]
    assert len(scans) == 5 and scans[0]["heads"] == 4 and scans[0]["groups"] == 2
    rows = [r[3] for r in events if r[0] == "moe.rows"]
    assert len(rows) == 5 and rows[0]["width"] == 16 and rows[0]["gated"] is False
    logits, aux = Transformer(cfg).apply(variables, inputs)
    assert logits.shape == (2, 40, 64) and logits.dtype == jnp.float32
    assert aux["expert_index"].shape == (5, 80, 3) and int(aux["dropped_assignments"]) == 0
    dot = Transformer(_config(attention_impl="dot")).apply(variables, inputs)[0]
    np.testing.assert_allclose(logits, dot, atol=5e-4)
    # a '-' layer, the dense feed-forward alone, comes free
    dense = _config(num_layers=2, sublayers=("mamba", "mlp"), num_experts=None,
                    moe_latent_size=None, moe_shared_expert_intermediate_size=None,
                    held_experts=None)
    own = Transformer(dense).init(jax.random.PRNGKey(1), inputs)["params"]
    assert set(own["layer_1"]) == {"norm", "mlp"} and set(own["layer_1"]["mlp"]) == {"up", "down"}
    assert Transformer(dense).apply({"params": own}, inputs).shape == (2, 40, 64)


@pytest.mark.parametrize("kw,message", [
    (dict(sublayers=("mamba",) * 3), "sublayers names one of"),
    (dict(sublayers=("ssm",) * 11), "sublayers names one of"),
    (dict(layer_types=("full_attention",) * 11), "takes no layer_types"),
    (dict(num_heads_per_layer=(4,) * 11), "takes no num_heads_per_layer"),
    (dict(first_dense_layers=1), "takes no first_dense_layers"),
    (dict(num_experts=None, moe_latent_size=None, moe_shared_expert_intermediate_size=None),
     "num_experts without a 'moe' layer, and no 'moe' layer without num_experts"),
    (dict(mamba_head_dim=None), "needs mamba_num_heads"),
    (dict(n_groups=3), r"n_groups \(a divisor of the heads\)"),
    (dict(attention_impl="ring", seq_axis_name="sp"),
     "a 'mamba' layer .sublayers. takes no attention_impl 'ring': the ring shards"),
    (dict(block_diffusion=4), "a 'mamba' layer .sublayers. takes no block_diffusion"),
    (dict(mlp_hidden_act="gelu"), "mlp_hidden_act is 'silu' .SwiGLU. or 'relu2'"),
    (dict(partial_rotary_factor=-0.5), "partial_rotary_factor"),
], ids=["short", "unknown", "layer_types", "heads_per_layer", "first_dense", "experts",
        "sizes", "groups", "ring", "block_diffusion", "activation", "rotary"])
def test_a_configuration_that_cannot_run_is_refused_with_its_reason(kw, message):
    with pytest.raises(ValueError, match=message):
        _config(**kw)


def test_a_mamba_layer_is_refused_where_it_cannot_run():
    with pytest.raises(ValueError, match="moe_latent_size and moe_shared_expert"):
        TransformerConfig(moe_latent_size=16)
    dense = _config(num_layers=2, sublayers=("mamba", "attention"), num_experts=None,
                    moe_latent_size=None, moe_shared_expert_intermediate_size=None,
                    held_experts=None)
    tokens = jnp.zeros((1, 16), jnp.int32)
    params = Transformer(dense).init(jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match=r"paged serving takes no 'mamba' layer \(sublayers\)"):
        Transformer(dense).apply(params, tokens, train=False, paged=object())
    with pytest.raises(ValueError, match="document ids .packed rows. take no 'mamba' layer: "
                                         "the scan's state"):
        Transformer(dense).apply(params, (tokens, tokens))
    sharded = dataclasses.replace(dense, shard_axis="tp")
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("tp",))
    with pytest.raises(ValueError, match=r"shard_axis 'tp' takes no 'mamba' layer \(sublayers\)"):
        jax.shard_map(lambda t: Transformer(sharded).apply(params, t), mesh=mesh,
                      in_specs=jax.sharding.PartitionSpec(),
                      out_specs=jax.sharding.PartitionSpec(), check_vma=False)(tokens)
    with pytest.raises(ValueError, match=r"layers of one sublayer \(sublayers"):
        modeled_activation_bytes(dense, batch=1, seq=32)
    assert dense.has_mamba and not _config(
        sublayers=("attention", "moe") * 5 + ("attention",)).has_mamba
