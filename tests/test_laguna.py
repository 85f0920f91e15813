"""Attention that differs by layer (PR 39): sliding-window and full layers in one
model, each kind with its own head count and its own rotary positions (plain RoPE
/ YaRN on part of a head), a sigmoid gate a head, over a leading dense layer and
sigmoid-routed experts beside a shared expert; the program against the
benchmark's plain float32 reference (``benchmark/reference/laguna_moe.py``: its own
mask from positions, its own YaRN, its own head counts and router) at small sizes
on the CPU."""

import copy
import dataclasses
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

from benchmark import flops_laguna, harness  # noqa: E402
from benchmark.families_laguna import Laguna, leading_dense_layers, rope_parameters  # noqa: E402
from benchmark.reference import chain, laguna_moe as reference  # noqa: E402
from horovod_tpu import trace, training  # noqa: E402
from horovod_tpu.models import transformer  # noqa: E402
from horovod_tpu.models.transformer import (  # noqa: E402
    MlpBlock, RopeParameters, Transformer, TransformerConfig, yarn_inv_freq,
)
from horovod_tpu.parallel.moe import RoutedExperts  # noqa: E402

OPS = chain.Ops("float32")
CELL = "laguna-xs.2-s8192-1chip"
KINDS = ("full_attention",) + ("sliding_attention",) * 3 + ("full_attention",)
HEADS = (6, 8, 8, 8, 6)        # groups of 3 (full) and 4 (sliding) over 2 key/value heads
YARN = dict(theta=5e5, partial_rotary_factor=0.5, factor=64.0,
            original_max_position_embeddings=16, beta_fast=64.0, beta_slow=1.0,
            attention_factor=1.4158883083359672)
ROPE = {"full_attention": YARN, "sliding_attention": dict(theta=1e4)}
# 300 tokens are two 256-tiles of the flash kernels, the second partial; a window
# of 70 is no multiple of a tile and shorter than the sequence; YaRN's original
# context is 16 positions, so nearly all positions lie past it.  span_steps 1: on a
# loaded host the 0.3 s window holds two of these steps, too few for spans of two
SEQ, WINDOW = 300, 70
_TRAFFIC = {"samples_per_chip": 2, "seq_len": SEQ, "layout": "dp", "step_options": {},
            "span_steps": 1, "trace_steps": 3}
_TIGHT = {"loss_gap": 2e-6, "grad_norm_gap": 5e-5, "delta_norm_gap": 5e-5,
          "grad_diff_gap": 5e-5}


def _config(**kw):
    """The tiny preset of the cell's shape: a dense full-attention layer 0, three
    sliding layers and a full one over routed experts, every kind of layer."""
    base = dict(
        vocab_size=64, num_layers=5, num_heads=6, num_kv_heads=2, head_dim=16,
        hidden_size=32, max_seq_len=512, dtype=jnp.float32, rms_norm_eps=1e-6,
        tie_word_embeddings=False, layer_types=KINDS, sliding_window=WINDOW,
        num_heads_per_layer=HEADS, rope_parameters=ROPE, attn_head_gate=True,
        intermediate_size=48, first_dense_layers=1, num_shared_experts=1,
        num_experts=8, num_experts_per_tok=3, moe_intermediate_size=12,
        held_experts=(2, 4), router_scoring="sigmoid", routed_scaling_factor=2.5)
    return TransformerConfig(**{**base, **kw})


def _tiny_cell_config(impl="flash", dtype="float32"):
    """The cell's configuration with every size made tiny (widths too: a test's
    sizes, never a cell's); the per-layer lists keep their published order."""
    config = copy.deepcopy(harness.load_json(ROOT, "benchmark", "configs", "laguna-xs.2.json"))
    config.update(
        hidden_size=32, intermediate_size=48, moe_intermediate_size=12,
        shared_expert_intermediate_size=12, num_hidden_layers=5, num_attention_heads=6,
        num_key_value_heads=2, head_dim=16, vocab_size=64, router_experts=8, num_experts=4,
        held_experts_first=2, num_experts_per_tok=3, max_position_embeddings=512,
        sliding_window=WINDOW, compute_dtype=dtype)
    config["num_attention_heads_per_layer"] = [
        {48: 6, 64: 8}[h] for h in config["num_attention_heads_per_layer"]]
    config["rope_parameters"]["full_attention"]["original_max_position_embeddings"] = 16
    config["model"] = dict(config["model"], kwargs={"attention_impl": impl})
    config["check"] = dict(config["check"], diff_leaves="", limits=_TIGHT)
    return config


def _tiny_cell(config):
    return harness.Cell(
        name="tiny-laguna-1", config_name="tiny", config=config, traffic_name="tiny",
        traffic=_TRAFFIC, chips=1,
        end_to_end=["setup_s", "train_tokens_per_s", "step_ms_p90", "mfu"], per_layer=[])


def _reference_logits(config, params, tokens):
    stages, _ = reference.build(config, _TRAFFIC)
    x = tokens
    for stage in stages:
        x = stage.forward(OPS, tuple(params[k] for k in stage.keys), x)
    logits = reference.rms_norm(x.h, params["ln_f"]["scale"], 1e-6) @ params["head"]["kernel"]
    return logits, x.aux


# -- the program against the reference ------------------------------------------------


@pytest.mark.parametrize("impl", ["dot", "flash"])
def test_family_through_run_cell_matches_the_reference_at_float32(impl):
    """Loss of three steps, every leaf's first gradient and every leaf's change,
    'dot' and interpreted 'flash', through the harness's own comparison."""
    cell = _tiny_cell(_tiny_cell_config(impl))
    harness.check_names(cell)
    result = harness.run_cell(cell, seed=2 ** 31 + 39, seconds=0.3, trace=False,
                              devices=jax.devices()[:1])
    assert result["correct"], json.dumps(result["checks"])
    # 3e-6 is the other families' float32 bar on every leaf's gradient
    assert result["checks"]["grad_diff_gap"]["value"] < 3e-6
    assert set(reference.REFERENCE_ROUTING) == {1, 2, 3, 4}     # layer 0 is dense
    assert reference.REFERENCE_ROUTING[1].shape == (1, 2 * SEQ, 3)


@pytest.mark.parametrize("impl", ["dot", "flash"])
def test_logits_match_the_reference_forward(impl):
    config = _tiny_cell_config(impl)
    model = Laguna.model(config)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, SEQ), 0, 64)
    params = model.init(jax.random.PRNGKey(1), tokens)["params"]
    logits, aux = model.apply({"params": params}, tokens)
    want, want_aux = _reference_logits(config, params, tokens)
    np.testing.assert_allclose(logits, want, atol=2e-5)
    np.testing.assert_allclose(aux["aux_loss"], want_aux / 4, rtol=1e-5)
    assert int(aux["dropped_assignments"]) == 0


def test_one_window_for_all_layers_is_not_the_model():
    """The reference told that every layer slides, or that none does, is far from
    the program: a program that applied one window to all layers would be that
    far from the reference of the test above."""
    config = _tiny_cell_config("dot")
    model = Laguna.model(config)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, SEQ), 0, 64)
    params = model.init(jax.random.PRNGKey(1), tokens)["params"]
    # sharper attention than flax's initial weights give, so that what a query
    # sees matters
    params = jax.tree_util.tree_map_with_path(
        lambda path, p: p * 6 if "/attn/q" in "/".join(str(k.key) for k in path) else p, params)
    logits, _ = model.apply({"params": params}, tokens)
    # float32 sums in another order under the sharpened scores: 7e-5 at most
    np.testing.assert_allclose(logits, _reference_logits(config, params, tokens)[0], atol=3e-4)
    unwindowed = dict(config, sliding_window=10 ** 6)             # no window anywhere
    for wrong in (_windowed_everywhere(params, tokens),
                  _reference_logits(unwindowed, params, tokens)[0]):
        assert float(jnp.max(jnp.abs(logits - wrong))) > 1e-2


def _windowed_everywhere(params, tokens):
    """The reference with every layer's MASK the window's (each layer keeping its
    own head count and rotary positions)."""
    stages, _ = reference.build(_tiny_cell_config("dot"), _TRAFFIC)
    x = tokens
    for stage in stages:
        if stage.keys[0].startswith("layer_"):
            static = list(stage.static)
            static[2] = WINDOW                      # (eps, shape, window, rope, ...)
            stage = chain.Stage(stage.keys, stage.fn, tuple(static))
        x = stage.forward(OPS, tuple(params[k] for k in stage.keys), x)
    return reference.rms_norm(x.h, params["ln_f"]["scale"], 1e-6) @ params["head"]["kernel"]


def test_each_layer_is_built_at_its_own_head_count():
    """A full layer has 6 query heads and a sliding one 8, in q, o and the gate;
    the key/value heads are 2 everywhere; and a model whose full layers were built
    at the sliding layers' count is refused by the reference's own shapes."""
    cfg = _config()
    tokens = jnp.zeros((1, 16), jnp.int32)
    params = Transformer(cfg).init(jax.random.PRNGKey(0), tokens)["params"]
    for i, heads in enumerate(HEADS):
        attn = params[f"layer_{i}"]["attn"]
        assert attn["q"]["kernel"].shape == (32, heads, 16), i
        assert attn["o"]["kernel"].shape == (heads, 16, 32), i
        assert attn["gate"]["kernel"].shape == (32, heads), i
        assert attn["k"]["kernel"].shape == attn["v"]["kernel"].shape == (32, 2, 16), i
    same = Transformer(_config(num_heads_per_layer=(8,) * 5)).init(
        jax.random.PRNGKey(0), tokens)["params"]
    config = _tiny_cell_config("dot")
    stages, _ = reference.build(config, dict(_TRAFFIC, seq_len=16))
    with pytest.raises(ValueError, match="a layer of 6 query heads over 2 of 16 got"):
        stages[1].forward(OPS, (same["layer_0"],), stages[0].forward(
            OPS, (same["embed"],), jnp.zeros((2, 16), jnp.int32)))


def test_events_name_every_layer_s_attention_and_each_kernel_s_window():
    """``attn.layers`` from the model, and ``flash.tiles`` from each kernel call
    that is traced (one a distinct shape and window: the three sliding layers
    share one trace): the full layers' calls carry no window and the sliding
    layers' their own.  308 tokens: a length no other test of this file traces."""
    cfg = _config(attention_impl="flash")
    tokens = jnp.zeros((1, 308), jnp.int32)
    t0 = trace.now()
    jax.eval_shape(lambda t: Transformer(cfg).init(jax.random.PRNGKey(0), t), tokens)
    events = [(r[0], r[3]) for r in trace.snapshot(t0)]
    (layers,) = [args["layers"] for name, args in events if name == "attn.layers"]
    assert [l["kind"] for l in layers] == list(KINDS)
    assert [l["heads"] for l in layers] == list(HEADS)
    assert [l["window"] for l in layers] == [None, WINDOW, WINDOW, WINDOW, None]
    assert [l["rotary_columns"] for l in layers] == [8, 16, 16, 16, 8]
    assert [l["rope_type"] for l in layers] == ["yarn"] + ["default"] * 3 + ["yarn"]
    assert all(l["kv_heads"] == 2 for l in layers)
    tiles = [args for name, args in events if name == "flash.tiles"]
    assert [t["window"] for t in tiles if t["kernel"] == "flash_attention_fwd"] == \
        [None, WINDOW]
    # a window of 70 in 256-tiles over two tiles: the second query tile sees both
    windowed = next(t for t in tiles if t["window"] == WINDOW)
    assert (windowed["visited"], windowed["iterations"]) == (3, 2)


# -- YaRN -----------------------------------------------------------------------------


def test_yarn_frequencies_are_the_formula_s_at_the_published_values():
    """Laguna-XS.2's full layers: 64 rotary columns (half of 128), theta 500,000,
    factor 64 over an original context of 4,096, beta_fast 64, beta_slow 1.  By
    hand: ln 500000 = 13.122363; low = floor(64 ln(4096 / (64 x 2 pi)) / (2 x
    13.122363)) = floor(64 x 2.321056 / 26.244727) = floor(5.660) = 5; high =
    ceil(64 ln(4096 / (2 pi)) / 26.244727) = ceil(64 x 6.479939 / 26.244727) =
    ceil(15.802) = 16.  Pair 0 (below the ramp): 1.  Pair 10 (ramp 5 / 11): extra =
    500000^(-20/64) = e^-4.100739 = 0.01656047; 0.01656047 x (6/11 + 5/11 / 64) =
    0.00915058.  Pair 31 (above the ramp): 500000^(-62/64) / 64 = e^-12.712289 / 64
    = 3.013858e-6 / 64 = 4.709154e-8.  attention_factor = 0.1 ln 64 + 1."""
    got = yarn_inv_freq(64, 500000.0, 64.0, 4096, 64.0, 1.0)
    assert got.shape == (32,) and got.dtype == np.float32
    extra = 500000.0 ** (-np.arange(0, 64, 2) / 64.0)
    np.testing.assert_allclose(got[:6], extra[:6], rtol=1e-6)          # through pair 5
    np.testing.assert_allclose(got[16:], extra[16:] / 64.0, rtol=1e-6)  # from pair 16
    np.testing.assert_allclose(got[[0, 10, 31]], [1.0, 0.00915058, 4.709154e-8], rtol=2e-6)
    assert np.all(np.diff(got) < 0)
    np.testing.assert_allclose(0.1 * np.log(64.0) + 1.0, 1.4158883083359672, rtol=1e-12)
    # the reference's own are the same numbers, and the published config's keys
    config = harness.load_json(ROOT, "benchmark", "configs", "laguna-xs.2.json")
    static = reference.rope_static(config["rope_parameters"]["full_attention"])
    assert static == (500000.0, 0.5, (64.0, 4096, 64.0, 1.0, 1.4158883083359672))
    np.testing.assert_allclose(reference.inverse_frequencies(64, static), got, rtol=1e-6)
    plain = reference.rope_static(config["rope_parameters"]["sliding_attention"])
    np.testing.assert_allclose(reference.inverse_frequencies(128, plain),
                               10000.0 ** (-np.arange(0, 128, 2) / 128.0), rtol=1e-6)
    assert rope_parameters(config)["full_attention"] == dict(
        theta=500000.0, partial_rotary_factor=0.5, factor=64,
        original_max_position_embeddings=4096, beta_fast=64, beta_slow=1,
        attention_factor=1.4158883083359672)


def test_rotary_step_by_layer_type():
    """Plain RoPE on the whole head and YaRN on its first half (cos and sin times
    the factor, the second half passes), against the reference's own rotation."""
    cfg = _config()
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, 3, 16))
    positions = jnp.arange(40)[None]
    for kind in ("sliding_attention", "full_attention"):
        own = cfg.layer_rope(kind)
        assert isinstance(own, RopeParameters)
        got = transformer._rotary(cfg, x, positions, own)
        given = {"rope_theta": own.theta, "partial_rotary_factor": own.partial_rotary_factor}
        if own.yarn is not None:
            given.update(rope_type="yarn", factor=own.factor, beta_fast=own.beta_fast,
                         original_max_position_embeddings=own.original_max_position_embeddings,
                         beta_slow=own.beta_slow, attention_factor=own.attention_factor)
        want = reference.rotate(x[0], reference.rope_static(given))
        np.testing.assert_allclose(got[0], want, atol=2e-6)
    half = transformer._rotary(cfg, x, positions, cfg.layer_rope("full_attention"))
    np.testing.assert_array_equal(half[..., 8:], x[..., 8:])
    # a type that rope_parameters does not name takes the model's, as before
    plain = TransformerConfig(head_dim=16, rope_theta=123.0)
    np.testing.assert_array_equal(
        transformer._rotary(plain, x, positions, plain.layer_rope("full_attention")),
        transformer.rope(x, positions, 123.0))


# -- the share of the experts -----------------------------------------------------------


def test_all_shares_and_the_shared_expert_once_sum_to_the_uncut_layer():
    """32 experts, 4 a token, sigmoid scores times 2.5, cut as the cell cuts its
    256 (an even share a chip): the four shares of 8 experts each, each computed
    by the program's layer told which experts it holds, plus the shared expert
    counted ONCE, add up to the reference's feed-forward that holds all 32."""
    experts, top_k, width, ff, shares = 32, 4, 16, 8, 4
    cfg = _config(hidden_size=width, moe_intermediate_size=ff, num_experts=experts,
                  num_experts_per_tok=top_k, held_experts=None)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, width))
    kw = dict(dtype=jnp.float32, scoring="sigmoid", scaling_factor=2.5)
    whole = RoutedExperts(experts, top_k, width, ff, **kw)
    moe = whole.init(jax.random.PRNGKey(5), x)["params"]
    moe = dict(moe, router={"kernel": 3.0 * moe["router"]["kernel"]})
    shared_block = MlpBlock(cfg, hidden=ff)
    shared = shared_block.init(jax.random.PRNGKey(6), x)["params"]
    want, _ = reference.routed_feed_forward(
        OPS, moe, shared, x.reshape(-1, width), top_k, 0, 2.5, "sigmoid", jax.nn.silu)
    total = shared_block.apply({"params": shared}, x)
    assigned = 0
    for share in range(shares):
        first, count = share * experts // shares, experts // shares
        own = dict(moe, **{k: moe[k][first:first + count]
                           for k in ("w_gate", "w_up", "w_down")})
        y, out = RoutedExperts(experts, top_k, width, ff, held=(first, count), **kw).apply(
            {"params": own}, x)
        total, assigned = total + y, assigned + int(out["assigned"])
        assert int(out["dropped"]) == 0
    assert assigned == 2 * 24 * top_k            # every assignment on exactly one share
    np.testing.assert_allclose(total.reshape(-1, width), want, atol=3e-6)
    routed_only, _ = reference.routed_feed_forward(
        OPS, moe, None, x.reshape(-1, width), top_k, 0, 2.5, "sigmoid", jax.nn.silu)
    assert float(jnp.max(jnp.abs(want - routed_only))) > 1e-3      # the shared part counts


# -- the cut and the count ----------------------------------------------------------------


def test_required_flops_and_the_cut_are_the_issue_s():
    cell = harness.load_cell(CELL)
    config, traffic = cell.config, cell.traffic
    assert flops_laguna.layers(config) == [
        ("full_attention", 48, "dense"), ("sliding_attention", 64, "sparse"),
        ("sliding_attention", 64, "sparse"), ("sliding_attention", 64, "sparse"),
        ("full_attention", 48, "sparse")]
    assert leading_dense_layers(config) == 1
    assert flops_laguna.mask_pairs("sliding_attention", config, 8192) == 4_063_488
    assert flops_laguna.mask_pairs("full_attention", config, 8192) == 8192 * 8193 // 2
    per_layer = [flops_laguna.layer_flops_per_token(config, traffic, i) for i in range(5)]
    for got, want in zip(per_layer, (0.781e9, 0.308e9, 0.308e9, 0.308e9, 0.510e9)):
        assert abs(got - want) < 0.001e9, per_layer
    per_token = flops_laguna.train_flops_per_token(config, traffic)
    assert abs(per_token - 2.37e9) < 0.005e9
    assert abs(per_token * 8192 - 19.4e12) < 0.05e12
    window = flops_laguna.window_attention_train_flops_per_step(config, traffic, 1)
    full = flops_laguna.full_attention_train_flops_per_step(config, traffic, 1)
    assert window == 12 * 128 * 64 * 4_063_488 * 3
    assert full == 12 * 128 * 48 * (8192 * 8193 // 2) * 2
    assert abs(window / (per_token * 8192) - 0.062) < 0.001       # 6.2 % of the step
    assert abs(full / (per_token * 8192) - 0.255) < 0.001         # 25.5 %
    assert flops_laguna.expert_ffn_train_flops_per_step(config, traffic, 1) == \
        4 * 6.0 * 3 * 2048 * 512 * 4096
    # the model the family builds: the counted parameters, the issue's 490.3 M
    model = Laguna.model(config)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    count = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert count == config["parameters"] == 490_297_344
    by_layer = [sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes[f"layer_{i}"]))
                for i in range(5)]
    assert by_layer == [79_794_176, 91_885_568, 91_885_568, 91_885_568, 83_464_192]
    cfg = model.cfg
    assert cfg.layer_types == KINDS and cfg.num_heads_per_layer == (48, 64, 64, 64, 48)
    assert cfg.sliding_window == 512 and cfg.window is None and cfg.attn_head_gate
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.held_experts) == (256, 8, (0, 16))
    assert (cfg.router_scoring, cfg.routed_scaling_factor) == ("sigmoid", 2.5)
    assert cfg.first_dense_layers == 1 and cfg.num_shared_experts == 1
    assert cfg.vocab_size * 8 == config["published"]["vocab_size"] == 100_352


# -- the normal path ------------------------------------------------------------------------


def test_trains_through_the_normal_path():
    """create_train_state -> replicate_state -> data_parallel_train_step with the
    flash kernels (interpreted): one fixed batch is learned."""
    import functools

    import horovod_tpu as hvd

    hvd.init()
    cfg = _config(attention_impl="flash", num_layers=2, layer_types=KINDS[:2],
                  num_heads_per_layer=HEADS[:2], sliding_window=20)
    model, optimizer = Transformer(cfg), optax.adam(3e-2)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (hvd.size(), 49), 0, 64)
    state = training.replicate_state(training.create_train_state(
        model, optimizer, jax.random.PRNGKey(1), np.asarray(tokens[:1, :-1])), hvd.world_mesh())
    step = training.data_parallel_train_step(
        model, optimizer, loss_fn=functools.partial(transformer.next_token_loss, aux_coef=0.001))
    losses = []
    for _ in range(12):
        state, loss = step(state, tokens[:, :-1], tokens[:, 1:])
        losses.append(float(loss))
    assert losses[-1] < 0.6 * losses[0], losses


def test_rotary_kernels_engaged_give_the_dot_model_s_loss_and_gradients():
    """128-wide heads and rows that tile: the 'flash' model rotates q and k by
    the kernel pair of ``ops/rope_kernel.py`` (interpreted; plain RoPE on the
    whole head in the sliding layer, YaRN on half of it in the full one), the
    'dot' model by ``rope``: one loss and one gradient on every leaf."""
    import functools

    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 49), 0, 64)
    loss_fn = functools.partial(transformer.next_token_loss, aux_coef=0.001)
    found = {}
    for impl in ("dot", "flash"):
        cfg = _config(attention_impl=impl, head_dim=128, num_layers=2, layer_types=KINDS[:2],
                      num_heads_per_layer=HEADS[:2], sliding_window=20)
        model = Transformer(cfg)
        t0 = trace.now()
        params = model.init(jax.random.PRNGKey(1), tokens[:, :-1])["params"]
        found[impl] = jax.value_and_grad(lambda p: loss_fn(
            model.apply({"params": p}, tokens[:, :-1]), tokens[:, 1:]))(params)
        events = [r[3] for r in trace.snapshot(t0) if r[0] == "rope.rotate"]
        assert events and all(e["kernel"] == (impl == "flash") for e in events)
        assert {(e["heads"], e["rot"], e["rope_type"]) for e in events} == {
            (6, 64, "yarn"), (8, 128, "default")}
    (loss, grads), (want_loss, want_grads) = found["flash"], found["dot"]
    np.testing.assert_allclose(loss, want_loss, rtol=2e-6)
    flat, want_flat = (jax.tree_util.tree_leaves_with_path(g) for g in (grads, want_grads))
    for (path, got), (_, want) in zip(flat, want_flat):
        np.testing.assert_allclose(got, want, atol=3e-6 * float(jnp.max(jnp.abs(want))) + 1e-9,
                                   err_msg=jax.tree_util.keystr(path))


# -- refusals ---------------------------------------------------------------------------------


@pytest.mark.parametrize("kw,message", [
    (dict(attention_impl="ring", seq_axis_name="sp"), "takes no attention_impl 'ring'"),
    (dict(attention_impl="ring_flash", seq_axis_name="sp"), "takes no attention_impl 'ring_flash'"),
    (dict(shard_axis="tp"), "takes no shard_axis"),
    (dict(block_diffusion=4), "takes no block_diffusion"),
    (dict(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
          num_kv_heads=None, num_heads_per_layer=None, attn_head_gate=False,
          rope_parameters=None), "takes no latent attention"),
], ids=["ring", "ring_flash", "shard_axis", "block_diffusion", "latent"])
def test_paths_that_cannot_honour_a_layer_s_own_attention_refuse_it(kw, message):
    with pytest.raises(ValueError, match=message) as refusal:
        _config(**kw)
    assert "attention that differs by layer" in str(refusal.value)


@pytest.mark.parametrize("by_layer", ["sliding", "heads"])
def test_paged_serving_refuses_a_layer_s_own_window_or_heads(by_layer):
    kw = dict(num_experts=None, num_shared_experts=0, first_dense_layers=0, held_experts=None)
    if by_layer == "heads":
        kw.update(layer_types=None, sliding_window=None)
    else:
        kw.update(num_heads_per_layer=None)
    cfg = _config(**kw)
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = Transformer(cfg).init(jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="paged serving takes no 'sliding_attention' layer "
                                         "and no num_heads_per_layer"):
        Transformer(cfg).apply(params, tokens, train=False, paged=object())


@pytest.mark.parametrize("kw,message", [
    (dict(num_heads_per_layer=HEADS[:4]), "num_heads_per_layer names for each of the 5 layers"),
    (dict(num_heads_per_layer=(6, 8, 8, 7, 6)), "a multiple of the 2 key/value heads"),
    (dict(num_heads_per_layer=(6, 8, 8, 0, 6)), "a multiple of the 2 key/value heads"),
    (dict(hidden_size=None), "num_heads_per_layer needs hidden_size"),
    (dict(layer_types=KINDS[:4]), "layer_types names one of"),
    (dict(layer_types=("window",) * 5), "layer_types names one of"),
    (dict(sliding_window=0), "sliding_window must be >= 1"),
    (dict(sliding_window=None), "sliding_window is the window of the 'sliding_attention'"),
    (dict(layer_types=("full_attention",) * 5), "sliding_window is the window of the"),
    (dict(window=32), "give one of the two"),
    (dict(rope_parameters={"full_attention": dict(YARN, beta_fast=None)}),
     "YaRN takes factor, original_max_position_embeddings, beta_fast"),
    (dict(rope_parameters={"full_attention": dict(theta=1e4, partial_rotary_factor=0.45)}),
     "is no even number of columns"),
    (dict(rope_parameters={"full_attention": dict(theta=1e4, partial_rotary_factor=1.5)}),
     "is no even number of columns"),
    (dict(rope_parameters={"linear_attention": dict(theta=1e4)}), "rope_parameters names"),
    (dict(attn_output_gate=True), "two forms of one gate"),
], ids=["heads_length", "heads_multiple", "heads_zero", "heads_hidden", "types_length",
        "types_name", "window_zero", "window_missing", "window_without_layer",
        "window_and_sliding", "yarn_partial", "rotary_odd", "rotary_over", "rope_kind",
        "two_gates"])
def test_per_layer_fields_are_validated_with_their_reason(kw, message):
    with pytest.raises(ValueError, match=message):
        _config(**kw)


def test_per_layer_fields_at_their_defaults_and_in_their_stored_form():
    cfg = _config()
    assert cfg.has_sliding_attention and not cfg.has_linear_attention
    assert isinstance(cfg.num_heads_per_layer, tuple) and hash(cfg) == hash(_config())
    assert cfg.layer_rope("full_attention").rope_type == "yarn"
    assert cfg.layer_rope("sliding_attention") == RopeParameters(theta=1e4)
    plain = TransformerConfig()
    assert (plain.sliding_window, plain.num_heads_per_layer, plain.rope_parameters,
            plain.attn_head_gate) == (None, None, None, False)
    assert plain.layer_rope("full_attention") == RopeParameters() and \
        not plain.has_sliding_attention
    assert [l["heads"] for l in plain.attention_layers()] == [12] * 12
    # a type that rope_parameters does not name keeps the model's theta and share
    half = dataclasses.replace(cfg, rope_parameters={"full_attention": YARN},
                               rope_theta=123.0, partial_rotary_factor=0.5)
    assert half.layer_rope("sliding_attention") == RopeParameters(123.0, 0.5)
    assert [l["rotary_columns"] for l in half.attention_layers()] == [8] * 5
