"""The ``qwen3next_moe`` family's files (PR 35): names, the FLOP count of the
issue's table, the family through the harness at a tiny size, the control, broken
steps (a carry over the chunks that is dropped among them), every data file.

Tiny sizes hold 300 rows for 8 experts, so one row routed differently moves an
expert's gradient by a large share: bfloat16 and fp8 do not separate there, and
the comparison at these sizes is made at float32 (the control is then the
reference with bfloat16 operands, the nearest precision below).  The cell's own
limits separate bfloat16 from fp8 at its real size, on the chip
(``configs/qwen3-next-80b-a3b.json`` ``check.readings``)."""

import copy
import os
import re
import json

import jax
import jax.numpy as jnp
import pytest

from benchmark import families, flops, flops_qwen3next, harness, readers, readers_scope, trace as tr
from benchmark.families_qwen3next import layer_types
from benchmark.reference import qwen3next_moe as reference
from horovod_tpu import training

ROOT = harness.ROOT
CELL = "qwen3-next-80b-a3b-s8192-1chip"
TINY_LIMITS = {"loss_gap": 2e-6, "grad_norm_gap": 5e-5, "delta_norm_gap": 5e-5,
               "grad_diff_gap": 5e-5}
# 150 tokens: three chunks of the rule, the last one partial
TRAFFIC = {"samples_per_chip": 2, "seq_len": 150, "layout": "dp", "step_options": {},
           "span_steps": 2, "trace_steps": 3}


def tiny_cell(chips=1):
    config = copy.deepcopy(harness.load_cell(CELL).config)
    config.update(
        hidden_size=32, moe_intermediate_size=12, shared_expert_intermediate_size=12,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        linear_num_key_heads=2, linear_key_head_dim=8, linear_num_value_heads=4,
        linear_value_head_dim=8, vocab_size=64, router_experts=8, num_experts=4,
        held_experts_first=2, num_experts_per_tok=3, max_position_embeddings=256,
        compute_dtype="float32")
    config["check"] = dict(config["check"], limits=TINY_LIMITS, control_precision="bfloat16",
                           diff_leaves="")
    return harness.Cell(
        name=f"tiny-qwen3next-{chips}", config_name="tiny", config=config,
        traffic_name="tiny", traffic=TRAFFIC, chips=chips,
        end_to_end=["setup_s", "train_tokens_per_s", "step_ms_p90", "mfu"], per_layer=[])


def test_the_cell_s_names_resolve_and_its_numbers_are_stated():
    cell = harness.load_cell(CELL)
    assert families.family(cell.config).reference == "benchmark.reference.qwen3next_moe"
    assert cell.end_to_end == ["setup_s", "train_tokens_per_s", "step_ms_p90", "mfu"]
    assert set(cell.per_layer) == {
        "init_s", "compile_s", "device_step_ms", "flash_attention_ms",
        "flash_attention_fwd_ms", "flash_attention_bwd_dq_ms", "flash_attention_bwd_dkv_ms",
        "router_ms", "expert_ffn_ms", "shared_expert_ms", "gated_delta_ms",
        "gated_delta_roofline", "gdn_proj_ms", "qwen3next_attention_roofline",
        "qwen3next_expert_ffn_roofline"}
    for name in cell.per_layer:
        spec = harness.load_json(ROOT, "benchmark", "metrics", name + ".json")
        readers.reader(spec["reader"])
        if "flops_function" in spec:
            flops.function(spec["flops_function"])
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == [
        "gated_delta_ms", "gated_delta_roofline", "gdn_proj_ms",
        "qwen3next_attention_roofline", "qwen3next_expert_ffn_roofline"]
    assert all(m["moves"] == "mfu" and m["source"] == "device_trace" for m in new)
    assert [m["layer"] for m in new] == ["linear attention"] * 3 + ["kernels", "routed experts"]
    config = cell.config
    assert layer_types(config) == ("linear_attention",) * 3 + ("full_attention",)
    assert config["router_experts"] == config["published"]["num_experts"] == 512
    assert config["num_experts"] == 32 and config["num_experts_per_tok"] == 10
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["num_hidden_layers"] == 4 and config["published"]["num_hidden_layers"] == 48
    assert sorted(config["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert set(config["assumed"]) >= {"router_aux_loss_coef", "gates", "rope", "seq_len",
                                      "optimizer", "init"}
    assert cell.traffic["seq_len"] == 8192 and cell.traffic["samples_per_chip"] == 1
    readings = config["check"]["readings"]
    for name, limit in config["check"]["limits"].items():
        if "sound_largest" in readings.get(name, {}):
            assert readings[name]["sound_largest"] < limit
        if "control_smallest" in readings.get(name, {}):
            assert limit < readings[name]["control_smallest"]
    assert any("control_smallest" in v for v in readings.values() if isinstance(v, dict))
    # the Gated DeltaNet leaves and the gated attention's are among those compared
    leaves = re.compile(config["check"]["diff_leaves"])
    for leaf in ("layer_0/linear_attn/in_proj_qkvz/kernel", "layer_1/linear_attn/in_proj_ba/kernel",
                 "layer_2/linear_attn/conv_kernel", "layer_0/linear_attn/A_log",
                 "layer_0/linear_attn/dt_bias", "layer_0/linear_attn/out_proj/kernel",
                 "layer_0/linear_attn/norm/scale", "layer_3/attn/q/kernel",
                 "layer_3/attn/k_norm/scale", "layer_3/attn/o/kernel", "head/kernel",
                 "embed/embedding", "layer_1/shared_experts/up/kernel"):
        assert leaves.search(leaf), leaf
    for leaf in ("layer_0/moe/w_gate", "layer_3/moe/router/kernel"):
        assert not leaves.search(leaf), leaf


def test_the_flop_count_is_the_issue_s_table():
    cell = harness.load_cell(CELL)
    config, traffic = cell.config, cell.traffic
    per_token = flops_qwen3next.train_flops_per_token(config, traffic)
    assert per_token == families.flops_per_sample(config, traffic)
    linear = 6 * flops_qwen3next.linear_mixer_matrix_params(config)
    full = 6 * flops_qwen3next.full_mixer_matrix_params(config)
    feed = 6 * flops_qwen3next.feed_forward_matrix_params(config)
    delta = flops_qwen3next.delta_rule_flops_per_token(config)
    attention = 3 * 2 * (256 + 256) * 16 * 8192 / 2
    head = 6 * 2048 * 18992
    assert abs(linear - 0.2023e9) < 0.0005e9 and abs(full - 0.1636e9) < 0.0005e9
    assert abs(feed - 0.0370e9) < 0.0005e9 and abs(delta - 0.0173e9) < 0.0001e9
    assert abs(attention - 0.2013e9) < 0.0001e9 and abs(head - 0.2334e9) < 0.0001e9
    assert per_token == 3 * linear + full + 4 * feed + 3 * delta + attention + head
    assert abs(per_token - 1.405e9) < 0.002e9                       # "about 1.4 GFLOP a token"
    assert abs(per_token * 8192 - 11.51e12) < 0.01e12               # "11.5 TFLOP a step"
    assert abs(3 * delta * 8192 - 0.425e12) < 0.001e12              # the rule: 0.43 TFLOP
    assert abs(attention * 8192 - 1.649e12) < 0.001e12              # the full layer: 1.65 TFLOP
    assert flops_qwen3next.gated_delta_train_flops_per_step(config, traffic, 1) == \
        3 * delta * 8192
    assert flops_qwen3next.attention_train_flops_per_step(config, traffic, 1) == \
        attention * 8192
    assert flops_qwen3next.expert_ffn_train_flops_per_step(config, traffic, 1) == \
        4 * 6.0 * 3 * 2048 * 512 * 5120


@pytest.mark.parametrize("chips", [1, 4])
def test_family_through_run_cell(chips):
    result = harness.run_cell(tiny_cell(chips), seed=2 ** 31 + 35, seconds=0.3,
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


def _no_shared_expert(real):
    """The step with the shared expert's output matrix zeroed: the routed sum
    alone."""
    def step(s, x, y):
        params = jax.tree_util.tree_map_with_path(
            lambda path, p: p * 0 if "shared_experts" in jax.tree_util.keystr(path)
            and "down" in jax.tree_util.keystr(path) else p, s.params)
        return real(s.replace(params=params), x, y)

    return jax.jit(step)


def _broken(monkeypatch, breaker):
    build = training.data_parallel_train_step
    monkeypatch.setattr(training, "data_parallel_train_step",
                        lambda *a, **k: breaker(build(*a, **k)))
    return harness.run_cell(tiny_cell(), seed=2 ** 31 + 5, seconds=0.3, trace=False,
                            devices=jax.devices()[:1])


@pytest.mark.parametrize("breaker", [_unchanged_state, _no_shared_expert],
                         ids=["state_unchanged", "no_shared_expert"])
def test_broken_step_is_not_correct(monkeypatch, breaker):
    assert _broken(monkeypatch, breaker)["correct"] is False


def test_a_carry_that_is_dropped_is_not_correct(monkeypatch):
    """The rule with its state reset at every chunk (each chunk computed as if
    the sequence began there): what a wrong carry over the chunks would give.
    The seeded heads forget slowly, so the check sees it."""
    from horovod_tpu.ops import gated_delta

    real = gated_delta.gated_delta_rule

    def reset_at_each_chunk(q, k, v, g, beta, chunk=64, **kw):
        b, t = q.shape[:2]
        pad = (-t) % chunk
        cut = lambda x: jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)).reshape(
            b * ((t + pad) // chunk), chunk, *x.shape[2:])
        o = real(*map(cut, (q, k, v, g, beta)), chunk=chunk, **kw)
        return o.reshape(b, t + pad, *o.shape[2:])[:, :t]

    monkeypatch.setattr(gated_delta, "gated_delta_rule", reset_at_each_chunk)
    result = harness.run_cell(tiny_cell(), seed=2 ** 31 + 5, seconds=0.3, trace=False,
                              devices=jax.devices()[:1])
    assert result["correct"] is False, result
    assert result["checks"]["grad_diff_gap"]["value"] > 1e-2


def test_the_reference_reports_its_own_routing_and_decays():
    cell = tiny_cell()
    harness.run_reference(cell, 5, jax.devices()[0])
    assert set(reference.REFERENCE_ROUTING) == {0, 1, 2, 3}
    assert reference.REFERENCE_ROUTING[0].shape == (1, 2 * TRAFFIC["seq_len"], 3)
    assert set(reference.REFERENCE_DECAYS) == {0, 1, 2}
    text = reference.readings_report(2, 4)
    assert text.startswith("# routing") and "over 4 layers" in text
    assert text.count("# decays") == 3 and "of 4 heads in [0.9, 0.9999]" in text
    # the seeded gates: a good share of the heads forget slowly
    slow = sum(int(((d.mean(axis=(0, 1)) >= 0.9)).sum()) for d in reference.REFERENCE_DECAYS.values())
    assert slow >= 8, reference.REFERENCE_DECAYS


def test_every_new_data_file_loads_and_names_what_is_there():
    """What ``selftest.check_files`` holds every file to, on this PR's own."""
    import selftest

    selftest.check_files()
    for name in ("gated_delta_ms", "gdn_proj_ms"):
        spec = harness.load_json(ROOT, "benchmark", "metrics", name + ".json")
        assert spec["reader"] == "benchmark.readers_scope:trace_scope_per_step"
    patterns = {harness.load_json(ROOT, "benchmark", "metrics", n + ".json")["pattern"]
                for n in ("gated_delta_ms", "gdn_proj_ms")}
    assert patterns == {"/gated_delta/", "/gdn/"}


def test_the_new_readers_find_nothing_in_a_program_without_the_scopes():
    """The recorded step of Kimi's cell (a program that has neither scope):
    the two scope metrics read nothing there and do not raise, which is what the
    parent gives for a metric new in this PR."""
    fixture = os.path.join(ROOT, "benchmark", "fixtures", "kimi-vl-a3b-s8192-1chip")
    t = tr.load(fixture + ".trace.json")
    names = harness.load_json(fixture + ".scopes.json")
    for pattern in ("/gated_delta/", "/gdn/"):
        assert readers_scope.scope_ns(t, names, pattern) == 0.0
    r = readers.Readings(config={}, traffic={}, peaks={}, chips=1, rows_per_step=1)
    for name in ("gated_delta_ms", "gdn_proj_ms"):
        spec = harness.load_json(ROOT, "benchmark", "metrics", name + ".json")
        assert readers.reader(spec["reader"])(r, spec) is None
    spec = harness.load_json(ROOT, "benchmark", "metrics", "gated_delta_roofline.json")
    assert readers.reader(spec["reader"])(r, spec) is None      # no time read: no share
    # a step that has them: the patterns read each and not the other
    paths = {"a.1": "jit(_step)/jvp(forward)/Transformer/layer_0/linear_attn/gdn/in_proj_qkvz/dot",
             "b.2": "jit(_step)/jvp(forward)/Transformer/layer_0/linear_attn/checkpoint/"
                    "gated_delta/jit(_rule)/gated_delta_fwd/pallas_call",
             "c.3": "jit(_step)/transpose(jvp(forward))/Transformer/layer_0/linear_attn/"
                    "checkpoint/rematted_computation/gdn/mul"}
    trace = tr.Trace(ops={"0": [("a.1", 0, 10), ("b.2", 10, 30), ("c.3", 40, 5)]})
    assert readers_scope.scope_ns(trace, paths, "/gdn/") == 15
    assert readers_scope.scope_ns(trace, paths, "/gated_delta/") == 30


FIXTURE = os.path.join(ROOT, "benchmark", "fixtures", CELL)


def test_scope_readers_on_the_recorded_step():
    """One traced step of the cell on the chip, with the ``op_name`` of every
    instruction as the capture's own program gave it: the scopes the cell's
    metrics read, the kernels by name, and each kernel under its scope."""
    t = tr.load(FIXTURE + ".trace.json")
    names = harness.load_json(FIXTURE + ".scopes.json")
    want = harness.load_json(FIXTURE + ".expected.json")
    assert set(want["scopes"]) >= {"/router/", "/experts/", "/shared_experts/", "/gdn/",
                                   "/gated_delta/"}
    for pattern, ns in want["scopes"].items():
        got = readers_scope.scope_ns(t, names, pattern)
        assert ns > 0 and abs(got - ns) <= 1e-6 * ns, (pattern, got, ns)
    # the rule and the projections are siblings: no operation is under both
    rule = {n for n, path in names.items() if "/gated_delta/" in path}
    proj = {n for n, path in names.items() if "/gdn/" in path}
    assert rule and proj and not rule & proj
    # every gated_delta kernel lies under the rule's scope: the forward, the
    # backward's own forward (the mixer is rematerialised) and the backward
    kernels = [n for n in names if n.startswith("gated_delta_")]
    assert sorted(k.split(".")[0] for k in kernels) == \
        ["gated_delta_bwd"] * 3 + ["gated_delta_fwd"] * 6
    assert all("/gated_delta/" in names[k] and "/linear_attn/" in names[k] for k in kernels)
    assert all("/layer_3/attn/" in path for n, path in names.items()
               if n.startswith("flash_attention"))
    assert all("/experts/" in path for n, path in names.items() if n.startswith("grouped_matmul"))
    for pattern in want["patterns"]:
        assert abs(tr.matching_ns(t, pattern) - want["values"]["matching_ns:" + pattern]) <= 1e-3
    # the kernels are a small part of the rule's time: the chunk-local part is XLA's
    assert want["values"]["matching_ns:^gated_delta"] < 0.2 * want["scopes"]["/gated_delta/"]
