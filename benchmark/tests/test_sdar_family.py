"""The ``sdar_moe`` family's files (PR 28): names, the family through the
harness at a tiny size, the control, the scope reader on its recorded fixture.

Tiny sizes hold 80 rows for 16 experts, so one row routed differently moves an
expert's gradient by a large share: bfloat16 and fp8 do not separate there, and
the comparison at these sizes is made at float32 (the control is then the
reference with bfloat16 operands, the nearest precision below).  The cell's
own limits separate bfloat16 from fp8 at its real size, on the chip
(``configs/sdar-30b-a3b.json`` ``check.readings``)."""

import copy
import json
import os

import jax
import pytest

from benchmark import families, flops, harness, readers, readers_scope, trace as tr
from benchmark.reference import sdar_moe as reference

ROOT = harness.ROOT
CELL = "sdar-30b-a3b-bd4-s4096-1chip"
TINY_LIMITS = {"loss_gap": 2e-6, "grad_norm_gap": 5e-5, "delta_norm_gap": 5e-5,
               "grad_diff_gap": 5e-5}
TRAFFIC = {"samples_per_chip": 1, "seq_len": 40, "block_length": 4, "t_min": 0.001,
           "layout": "dp", "step_options": {}, "span_steps": 2, "trace_steps": 3}


def tiny_cell(chips=1):
    config = copy.deepcopy(harness.load_cell(CELL).config)
    config.update(
        hidden_size=32, head_dim=8, num_attention_heads=8, num_key_value_heads=2,
        moe_intermediate_size=24, num_hidden_layers=2, vocab_size=64, mask_token_id=63,
        router_experts=16, num_experts=4, held_experts_first=4, num_experts_per_tok=4,
        max_position_embeddings=64, compute_dtype="float32")
    config["check"] = dict(config["check"], limits=TINY_LIMITS, control_precision="bfloat16")
    return harness.Cell(
        name=f"tiny-sdar-{chips}", config_name="tiny", config=config, traffic_name="tiny",
        traffic=TRAFFIC, chips=chips,
        end_to_end=["setup_s", "train_tokens_per_s", "step_ms_p90", "mfu"], per_layer=[])


def test_the_cell_s_names_resolve_and_its_numbers_are_stated():
    cell = harness.load_cell(CELL)
    assert families.family(cell.config).reference == "benchmark.reference.sdar_moe"
    assert cell.end_to_end == ["setup_s", "train_tokens_per_s", "step_ms_p90", "mfu"]
    for name in cell.per_layer:
        spec = harness.load_json(ROOT, "benchmark", "metrics", name + ".json")
        readers.reader(spec["reader"])
        if "flops_function" in spec:
            flops.function(spec["flops_function"])
    config = cell.config
    assert config["router_experts"] == config["published"]["num_experts"] == 128
    assert config["num_experts"] == 16 and config["num_experts_per_tok"] == 8
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert sorted(config["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert set(config["assumed"]) >= {"block_length", "noise_schedule", "mask_token_id",
                                      "qk_norm", "router_aux_loss_coef"}
    assert "router_selection_noise" not in config        # the router is the model's own
    readings = config["check"]["readings"]
    for name, limit in config["check"]["limits"].items():
        if "sound_largest" in readings.get(name, {}):
            assert readings[name]["sound_largest"] < limit
        if "control_smallest" in readings.get(name, {}):
            assert limit < readings[name]["control_smallest"]
    assert any("control_smallest" in v for v in readings.values() if isinstance(v, dict))


@pytest.mark.parametrize("chips", [1, 4])
def test_family_through_run_cell(chips):
    result = harness.run_cell(tiny_cell(chips), seed=2 ** 31 + 28, seconds=0.3,
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


def test_the_reference_reports_its_own_routing():
    cell = tiny_cell()
    harness.run_reference(cell, 5, jax.devices()[0])
    assert set(reference.REFERENCE_ROUTING) == {0, 1}
    assert reference.REFERENCE_ROUTING[0].shape == (1, 2 * TRAFFIC["seq_len"], 4)
    text = reference.routing_report(4, 4)
    assert text.startswith("# routing") and "over 2 layers" in text


def test_scope_reader_finds_nothing_where_the_program_has_no_scope(tmp_path):
    """As on a program that lacks the scopes, or without a capture: None, and
    the metric is left out of the line."""
    t = tr.Trace(ops={"0": [("fusion.1", 0.0, 10.0)]}, modules={"0": [("jit__step(1)", 0.0, 10.0)]})
    r = readers.Readings(config={}, traffic={}, peaks={}, chips=1, rows_per_step=1, trace=t,
                         trace_dir=str(tmp_path), steps_traced=1)
    assert readers_scope.trace_scope_per_step(r, {"pattern": "/experts/"}) is None
    r.trace_dir = None
    assert readers_scope.trace_scope_per_step(r, {"pattern": "/experts/"}) is None
    assert readers_scope.scope_ns(t, {"fusion.1": "jit(_step)/jvp(forward)/x"}, "/experts/") == 0.0


FIXTURE = os.path.join(ROOT, "benchmark", "fixtures", CELL)


def test_scope_reader_on_the_recorded_step():
    """One traced step of the cell on the chip, with the ``op_name`` of every
    instruction as the capture's own program gave it."""
    t = tr.load(FIXTURE + ".trace.json")
    names = harness.load_json(FIXTURE + ".scopes.json")
    want = harness.load_json(FIXTURE + ".expected.json")
    for pattern, ns in want["scopes"].items():
        got = readers_scope.scope_ns(t, names, pattern)
        assert abs(got - ns) <= 1e-6 * ns, (pattern, got, ns)
    # a conditional or a loop and the operations of its body count once
    nested = tr.Trace(ops={"0": [("while.1", 0.0, 100.0), ("fusion.2", 10.0, 20.0),
                                 ("fusion.3", 200.0, 5.0)]})
    paths = {"while.1": "a/experts/while", "fusion.2": "a/experts/while/body/dot",
             "fusion.3": "a/router/top_k"}
    assert readers_scope.scope_ns(nested, paths, "/experts/") == 100.0
    assert readers_scope.scope_ns(nested, paths, "/router/") == 5.0
