"""The ``kimi_moe`` family's files (PR 32): names, the FLOP count of the
issue's table, the family through the harness at a tiny size, the control, a
broken step, the scope reader on its recorded fixture.

Tiny sizes hold 80 rows for 16 experts, so one row routed differently moves an
expert's gradient by a large share: bfloat16 and fp8 do not separate there, and
the comparison at these sizes is made at float32 (the control is then the
reference with bfloat16 operands, the nearest precision below).  The cell's
own limits separate bfloat16 from fp8 at its real size, on the chip
(``configs/kimi-vl-a3b.json`` ``check.readings``)."""

import copy
import json
import os

import jax
import pytest

from benchmark import families, flops, flops_kimi, harness, readers, readers_scope, trace as tr
from benchmark.reference import kimi_moe as reference
from horovod_tpu import training

ROOT = harness.ROOT
CELL = "kimi-vl-a3b-s8192-1chip"
TINY_LIMITS = {"loss_gap": 2e-6, "grad_norm_gap": 5e-5, "delta_norm_gap": 5e-5,
               "grad_diff_gap": 5e-5}
TRAFFIC = {"samples_per_chip": 2, "seq_len": 40, "layout": "dp", "step_options": {},
           "span_steps": 2, "trace_steps": 3}


def tiny_cell(chips=1):
    config = copy.deepcopy(harness.load_cell(CELL).config)
    config.update(
        hidden_size=32, intermediate_size=48, moe_intermediate_size=12, num_hidden_layers=3,
        num_attention_heads=4, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, vocab_size=64, router_experts=16, n_routed_experts=4,
        held_experts_first=4, num_experts_per_tok=3, max_position_embeddings=64,
        compute_dtype="float32")
    config["check"] = dict(config["check"], limits=TINY_LIMITS, control_precision="bfloat16",
                           diff_leaves="")
    return harness.Cell(
        name=f"tiny-kimi-{chips}", config_name="tiny", config=config, traffic_name="tiny",
        traffic=TRAFFIC, chips=chips,
        end_to_end=["setup_s", "train_tokens_per_s", "step_ms_p90", "mfu"], per_layer=[])


def test_the_cell_s_names_resolve_and_its_numbers_are_stated():
    cell = harness.load_cell(CELL)
    assert families.family(cell.config).reference == "benchmark.reference.kimi_moe"
    assert cell.end_to_end == ["setup_s", "train_tokens_per_s", "step_ms_p90", "mfu"]
    assert set(cell.per_layer) >= {
        "flash_attention_ms", "flash_attention_fwd_ms", "flash_attention_bwd_dq_ms",
        "flash_attention_bwd_dkv_ms", "router_ms", "expert_ffn_ms", "mla_attention_roofline",
        "mla_proj_ms", "shared_expert_ms", "kimi_expert_ffn_roofline"}
    assert not {"flash_attention_roofline", "bd_attention_roofline",
                "expert_ffn_roofline"} & set(cell.per_layer)
    for name in cell.per_layer:
        spec = harness.load_json(ROOT, "benchmark", "metrics", name + ".json")
        readers.reader(spec["reader"])
        if "flops_function" in spec:
            flops.function(spec["flops_function"])
    config = cell.config
    assert config["router_experts"] == config["published"]["n_routed_experts"] == 64
    assert config["n_routed_experts"] == 8 and config["num_experts_per_tok"] == 6
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["num_hidden_layers"] == 5 and config["published"]["num_hidden_layers"] == 27
    assert sorted(config["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert set(config["assumed"]) >= {"router_aux_loss_coef", "e_score_correction_bias", "rope",
                                      "seq_len", "optimizer", "init"}
    assert cell.traffic["seq_len"] == 8192 and cell.traffic["samples_per_chip"] == 1
    readings = config["check"]["readings"]
    for name, limit in config["check"]["limits"].items():
        if "sound_largest" in readings.get(name, {}):
            assert readings[name]["sound_largest"] < limit
        if "control_smallest" in readings.get(name, {}):
            assert limit < readings[name]["control_smallest"]
    assert any("control_smallest" in v for v in readings.values() if isinstance(v, dict))


def test_the_flop_count_is_the_issue_s_table():
    cell = harness.load_cell(CELL)
    config, traffic = cell.config, cell.traffic
    per_token = flops_kimi.train_flops_per_token(config, traffic)
    assert per_token == families.flops_per_sample(config, traffic)
    layer0 = 6 * flops_kimi.dense_layer_matrix_params(config)
    routed = 6 * flops_kimi.routed_layer_matrix_params(config)
    attention = 3 * 2 * (192 + 128) * 16 * 8192 / 2
    head = 6 * 2048 * 20480
    assert abs(layer0 - 0.498e9) < 0.001e9 and abs(routed - 0.226e9) < 0.001e9
    assert abs(attention - 0.126e9) < 0.001e9 and abs(head - 0.252e9) < 0.001e9
    assert per_token == layer0 + 4 * routed + 5 * attention + head
    assert abs(per_token - 2.28e9) < 0.005e9
    assert abs(5 * attention / per_token - 0.276) < 0.001          # attention 27.6 %
    assert abs(4 * 6 * 17_301_504 / per_token - 0.18) < 0.005      # shared experts 18 %
    assert abs(4 * 6 * 0.75 * 8_650_752 / per_token - 0.07) < 0.003  # routed experts 7 %
    assert abs(head / per_token - 0.11) < 0.003                    # head 11 %
    assert flops_kimi.mla_attention_train_flops_per_step(config, traffic, 1) == 5 * attention * 8192
    assert flops_kimi.expert_ffn_train_flops_per_step(config, traffic, 1) == \
        4 * 6.0 * 3 * 2048 * 1408 * 6144


@pytest.mark.parametrize("chips", [1, 4])
def test_family_through_run_cell(chips):
    result = harness.run_cell(tiny_cell(chips), seed=2 ** 31 + 32, seconds=0.3,
                              trace=False, devices=jax.devices()[:chips])
    assert result["correct"], json.dumps(result["checks"])
    assert set(result["metrics"]) == {"setup_s", "train_tokens_per_s", "step_ms_p90", "mfu"}


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 7])
def test_the_control_is_not_correct_and_the_program_is(seed):
    cell = tiny_cell()
    device = jax.devices()[0]
    check = cell.config["check"]
    sound = harness.prepare(cell, seed, [device]).first
    ref = harness.run_reference(cell, seed, device, keep_first_gradient=True,
                                other_first_gradient=sound["first_gradient"])
    rows = harness.compare(sound, ref, check["limits"], ref["grad_diff_norms"], "")
    assert all(r["ok"] for r in rows), rows
    control = harness.run_reference(cell, seed, device, precision=check["control_precision"],
                                    other_first_gradient=ref["first_gradient"])
    rows = harness.compare(control, ref, check["limits"], control["grad_diff_norms"], "")
    assert not all(r["ok"] for r in rows), rows


def _unchanged_state(real):
    return jax.jit(lambda s, x, y: (s, real(s, x, y)[1]))


def _no_shared_experts(real):
    """The step with the shared experts' output matrices zeroed: the routed
    sum alone."""
    def step(s, x, y):
        params = jax.tree_util.tree_map_with_path(
            lambda path, p: p * 0 if "shared_experts" in jax.tree_util.keystr(path)
            and "down" in jax.tree_util.keystr(path) else p, s.params)
        return real(s.replace(params=params), x, y)

    return jax.jit(step)


@pytest.mark.parametrize("breaker", [_unchanged_state, _no_shared_experts],
                         ids=["state_unchanged", "no_shared_experts"])
def test_broken_step_is_not_correct(monkeypatch, breaker):
    build = training.data_parallel_train_step
    monkeypatch.setattr(training, "data_parallel_train_step",
                        lambda *a, **k: breaker(build(*a, **k)))
    result = harness.run_cell(tiny_cell(), seed=2 ** 31 + 5, seconds=0.3, trace=False,
                              devices=jax.devices()[:1])
    assert result["correct"] is False, result


def test_the_reference_reports_its_own_routing():
    cell = tiny_cell()
    harness.run_reference(cell, 5, jax.devices()[0])
    assert set(reference.REFERENCE_ROUTING) == {1, 2}
    assert reference.REFERENCE_ROUTING[1].shape == (2, TRAFFIC["seq_len"], 3)
    text = reference.routing_report(4, 4)
    assert text.startswith("# routing") and "over 2 routed layers" in text


FIXTURE = os.path.join(ROOT, "benchmark", "fixtures", CELL)


def test_scope_readers_on_the_recorded_step():
    """One traced step of the cell on the chip, with the ``op_name`` of every
    instruction as the capture's own program gave it: the four scopes the
    cell's metrics read, and the kernels by name."""
    t = tr.load(FIXTURE + ".trace.json")
    names = harness.load_json(FIXTURE + ".scopes.json")
    want = harness.load_json(FIXTURE + ".expected.json")
    assert set(want["scopes"]) >= {"/router/", "/experts/", "/mla/", "/shared_experts/"}
    for pattern, ns in want["scopes"].items():
        got = readers_scope.scope_ns(t, names, pattern)
        assert ns > 0 and abs(got - ns) <= 1e-6 * ns, (pattern, got, ns)
    # the shared experts are not inside the routed experts' scope, nor the reverse
    shared = {n for n, path in names.items() if "/shared_experts/" in path}
    routed = {n for n, path in names.items() if "/experts/" in path}
    assert shared and routed and not shared & routed
    for pattern in want["patterns"]:
        assert abs(tr.matching_ns(t, pattern) - want["values"]["matching_ns:" + pattern]) <= 1e-3
    # a program without the scopes (the parent of PR 32) gives nothing to read
    bare = {n: "jit(_step)/jvp(forward)/Transformer/layer_0/attn/q/dot_general" for n in names}
    assert readers_scope.scope_ns(t, bare, "/mla/") == 0.0
