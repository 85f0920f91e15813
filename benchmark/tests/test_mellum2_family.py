"""The ``mellum2_moe`` family's files (PR 45): names, the family through the
harness at a tiny size ('flash', one chip and four), the control, broken steps
(a step that ignores the document ids among them), every data file, the new
readers on a program without the scope.

Tiny sizes hold 300 rows for 8 experts, so one row routed differently moves an
expert's gradient by a large share: bfloat16 and fp8 do not separate there, and
the comparison at these sizes is made at float32 (the control is then the
reference with bfloat16 operands, the nearest precision below).  The cell's own
limits separate bfloat16 from fp8 at its real size, on the chip
(``configs/mellum2-12b-a2.5b.json`` ``check.readings``)."""

import copy
import json
import os
import re

import jax
import pytest

from benchmark import families, flops, harness, readers, readers_scope, trace as tr
from benchmark.reference import mellum2_moe as reference
from horovod_tpu import training

ROOT = harness.ROOT
CELL = "mellum2-12b-a2.5b-pack8192-1chip"
TINY_LIMITS = {"loss_gap": 2e-6, "grad_norm_gap": 5e-5, "delta_norm_gap": 5e-5,
               "grad_diff_gap": 5e-5}
# 300 tokens: two 256-tiles of the flash kernels; four documents whose boundaries lie
# inside tiles, one longer than the window of 70; span_steps 1: on a loaded host the
# 0.3 s window holds two of these steps
TRAFFIC = {"samples_per_chip": 2, "seq_len": 300, "documents": [57, 131, 20, 92],
           "layout": "dp", "step_options": {}, "span_steps": 1, "trace_steps": 3}
NEW = ["mellum2_window_attention_roofline", "mellum2_full_attention_roofline",
       "mellum2_expert_ffn_roofline", "attn_docmask_ms"]


def tiny_cell(chips=1):
    config = copy.deepcopy(harness.load_cell(CELL).config)
    config.update(
        hidden_size=32, moe_intermediate_size=12, num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, vocab_size=64, router_experts=8, num_experts=4,
        held_experts_first=2, num_experts_per_tok=3, max_position_embeddings=512,
        sliding_window=70, compute_dtype="float32")
    config["rope_parameters"]["full_attention"]["original_max_position_embeddings"] = 16
    config["check"] = dict(config["check"], limits=TINY_LIMITS, control_precision="bfloat16",
                           diff_leaves="")
    return harness.Cell(
        name=f"tiny-mellum2-{chips}", config_name="tiny", config=config,
        traffic_name="tiny", traffic=TRAFFIC, chips=chips,
        end_to_end=["setup_s", "train_tokens_per_s", "step_ms_p90", "mfu"], per_layer=[])


def test_the_cell_s_names_resolve_and_its_numbers_are_stated():
    cell = harness.load_cell(CELL)
    assert families.family(cell.config).reference == "benchmark.reference.mellum2_moe"
    assert cell.end_to_end == ["setup_s", "train_tokens_per_s", "step_ms_p90", "mfu"]
    assert set(cell.per_layer) == set(NEW) | {
        "init_s", "compile_s", "device_step_ms", "flash_attention_ms",
        "flash_attention_fwd_ms", "flash_attention_bwd_dq_ms", "flash_attention_bwd_dkv_ms",
        "router_ms", "expert_ffn_ms", "window_attention_ms", "full_attention_ms",
        "attn_rope_ms", "import_s", "hvd_init_s", "model_init_s", "model_init_compiles",
        "step_compile_s", "step_cache_hits", "forward_ms", "backward_ms", "optimizer_ms",
        "unattributed_ms"}
    for name in cell.per_layer:
        spec = harness.load_json(ROOT, "benchmark", "metrics", name + ".json")
        readers.reader(spec["reader"])
        if "flops_function" in spec:
            flops.function(spec["flops_function"])
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == NEW
    assert all(m["moves"] == "mfu" and m["source"] == "device_trace" for m in new)
    assert [m["layer"] for m in new] == ["kernels"] * 2 + ["routed experts",
                                                           "window and full attention"]
    # a time is read before the share that divides by it
    order = cell.per_layer.index
    for name in NEW:
        spec = harness.load_json(ROOT, "benchmark", "metrics", name + ".json")
        if "time_metric" in spec:
            assert order(spec["time_metric"]) < order(name)
    (workload,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (workload["config"], workload["traffic"], workload["chips"]) == (
        "mellum2-12b-a2.5b", "pack8192-1chip", 1)
    config = cell.config
    assert len(config["layer_types"]) == len(config["mlp_layer_types"]) == 28
    assert sorted(config["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert set(config["assumed"]) >= {"router", "qk_norm", "rope", "seq_len", "packing",
                                      "router_aux_loss_coef", "optimizer", "init"}
    assert config["deployment"] and config["departures"]
    assert 4e9 < config["compiled_step_bytes"] < 15e9
    readings = config["check"]["readings"]
    for name, limit in config["check"]["limits"].items():
        if "sound_largest" in readings.get(name, {}):
            assert readings[name]["sound_largest"] < limit
        if "control_smallest" in readings.get(name, {}):
            assert limit < readings[name]["control_smallest"]
    assert any("control_smallest" in v for v in readings.values() if isinstance(v, dict))
    leaves = re.compile(config["check"]["diff_leaves"])
    for leaf in ("layer_0/attn/q/kernel", "layer_1/attn/k/kernel", "layer_2/attn/v/kernel",
                 "layer_3/attn/o/kernel", "head/kernel", "embed/embedding"):
        assert leaves.search(leaf), leaf
    for leaf in ("layer_1/moe/w_gate", "layer_3/moe/router/kernel"):
        assert not leaves.search(leaf), leaf


@pytest.mark.parametrize("chips", [1, 4])
def test_family_through_run_cell(chips):
    result = harness.run_cell(tiny_cell(chips), seed=2 ** 31 + 45, seconds=0.3,
                              trace=False, devices=jax.devices()[:chips])
    assert result["correct"], json.dumps(result["checks"])
    assert set(result["metrics"]) == {"setup_s", "train_tokens_per_s", "step_ms_p90", "mfu"}


def test_the_control_is_not_correct_and_the_program_is():
    cell = tiny_cell()
    device, seed = jax.devices()[0], 2 ** 31 + 7
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


def _ignores_the_ids(real):
    """The step on the tokens alone: every row one document, what a program that
    dropped the ids would compute.  (Positions that run on through the row
    while the mask is kept are no such fault: rotary scores see the distance of
    two positions alone, and within a document that is the same either way.)"""
    return jax.jit(lambda s, x, y: real(s, x.at[:, 1].set(0), y))


@pytest.mark.parametrize("breaker", [_unchanged_state, _ignores_the_ids],
                         ids=["state_unchanged", "ids_ignored"])
def test_broken_step_is_not_correct(monkeypatch, breaker):
    build = training.data_parallel_train_step
    monkeypatch.setattr(training, "data_parallel_train_step",
                        lambda *a, **k: breaker(build(*a, **k)))
    result = harness.run_cell(tiny_cell(), seed=2 ** 31 + 5, seconds=0.3, trace=False,
                              devices=jax.devices()[:1])
    assert result["correct"] is False
    if breaker is not _unchanged_state:
        assert result["checks"]["grad_diff_gap"]["value"] > 1e-3


def test_the_reference_reports_its_own_routing():
    cell = tiny_cell()
    harness.run_reference(cell, 5, jax.devices()[0])
    assert set(reference.REFERENCE_ROUTING) == {0, 1, 2, 3}
    assert reference.REFERENCE_ROUTING[1].shape == (1, 2 * TRAFFIC["seq_len"], 3)
    text = reference.routing_report(2, 4)
    assert text.startswith("# routing") and "over 4 layers" in text


def test_every_new_data_file_loads_and_names_what_is_there():
    """What ``selftest.check_files`` holds every file to, on this PR's own."""
    import selftest

    selftest.check_files()
    spec = harness.load_json(ROOT, "benchmark", "metrics", "attn_docmask_ms.json")
    assert spec["reader"] == "benchmark.readers_scope:trace_scope_per_step"
    assert spec["pattern"] == "/attn_docmask/"
    traffic = harness.load_json(ROOT, "benchmark", "traffic", "pack8192-1chip.json")
    assert sum(traffic["documents"]) == traffic["seq_len"] and len(traffic["documents"]) == 11


def test_the_new_readers_find_nothing_in_a_program_without_the_scope():
    """The recorded step of Laguna's cell (a program that takes no ids: no
    ``attn_docmask``): the new scope metric reads nothing there and does not
    raise, which is what the parent gives for a metric new in this PR; a step
    that has the scope reads its operations, forward and backward, in the model
    and under a kernel call alike."""
    fixture = os.path.join(ROOT, "benchmark", "fixtures", "laguna-xs.2-s8192-1chip")
    t = tr.load(fixture + ".trace.json")
    names = harness.load_json(fixture + ".scopes.json")
    spec = harness.load_json(ROOT, "benchmark", "metrics", "attn_docmask_ms.json")
    assert readers_scope.scope_ns(t, names, spec["pattern"]) == 0.0
    r = readers.Readings(config={}, traffic={}, peaks={}, chips=1, rows_per_step=1)
    for name in NEW:        # no trace, no time read: nothing, and no share
        one = harness.load_json(ROOT, "benchmark", "metrics", name + ".json")
        assert readers.reader(one["reader"])(r, one) is None
    paths = {
        "fusion.1": "jit(_step)/jvp(forward)/Transformer/attn_docmask/cummax",
        "fusion.2": "jit(_step)/jvp(forward)/Transformer/layer_1/attn/attn_window/"
                    "jit(flash_attention)/attn_docmask/broadcast_in_dim",
        "fusion.3": "jit(_step)/transpose(jvp(forward))/Transformer/layer_3/attn/attn_full/"
                    "jit(flash_attention)/attn_docmask/pad",
        "flash_attention_fwd.4": "jit(_step)/jvp(forward)/Transformer/layer_1/attn/attn_window/"
                                 "jit(flash_attention)/flash_attention_fwd/pallas_call"}
    trace = tr.Trace(ops={"0": [("fusion.1", 0, 4), ("fusion.2", 4, 3), ("fusion.3", 7, 2),
                                ("flash_attention_fwd.4", 9, 50)]})
    assert readers_scope.scope_ns(trace, paths, spec["pattern"]) == 9
