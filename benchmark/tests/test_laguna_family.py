"""The ``laguna_moe`` family's files (PR 39): names, the FLOP count of the
issue's table, the family through the harness at a tiny size, the control, broken
steps (a window that is dropped on one layer among them), every data file, the
new readers on a program without the scopes and on the recorded step.

Tiny sizes hold 600 rows for 8 experts, so one row routed differently moves an
expert's gradient by a large share: bfloat16 and fp8 do not separate there, and
the comparison at these sizes is made at float32 (the control is then the
reference with bfloat16 operands, the nearest precision below).  The cell's own
limits separate bfloat16 from fp8 at its real size, on the chip
(``configs/laguna-xs.2.json`` ``check.readings``)."""

import copy
import json
import os
import re

import jax
import pytest

from benchmark import (families, flops, flops_laguna, harness, readers, readers_kernels,
                       readers_scope, trace as tr)
from benchmark.reference import laguna_moe as reference
from horovod_tpu import training

ROOT = harness.ROOT
CELL = "laguna-xs.2-s8192-1chip"
TINY_LIMITS = {"loss_gap": 2e-6, "grad_norm_gap": 5e-5, "delta_norm_gap": 5e-5,
               "grad_diff_gap": 5e-5}
# 300 tokens: two 256-tiles of the flash kernels; a window of 70 is no multiple of one;
# span_steps 1: on a loaded host the 0.3 s window holds two of these steps
TRAFFIC = {"samples_per_chip": 2, "seq_len": 300, "layout": "dp", "step_options": {},
           "span_steps": 1, "trace_steps": 3}
NEW = ["window_attention_ms", "full_attention_ms", "window_attention_roofline",
       "laguna_full_attention_roofline", "attn_rope_ms", "attn_gate_ms",
       "laguna_expert_ffn_roofline"]


def tiny_cell(chips=1):
    config = copy.deepcopy(harness.load_cell(CELL).config)
    config.update(
        hidden_size=32, intermediate_size=48, moe_intermediate_size=12,
        shared_expert_intermediate_size=12, num_hidden_layers=5, num_attention_heads=6,
        num_key_value_heads=2, head_dim=16, vocab_size=64, router_experts=8, num_experts=4,
        held_experts_first=2, num_experts_per_tok=3, max_position_embeddings=512,
        sliding_window=70, compute_dtype="float32")
    config["num_attention_heads_per_layer"] = [
        {48: 6, 64: 8}[h] for h in config["num_attention_heads_per_layer"]]
    config["rope_parameters"]["full_attention"]["original_max_position_embeddings"] = 16
    config["check"] = dict(config["check"], limits=TINY_LIMITS, control_precision="bfloat16",
                           diff_leaves="")
    return harness.Cell(
        name=f"tiny-laguna-{chips}", config_name="tiny", config=config,
        traffic_name="tiny", traffic=TRAFFIC, chips=chips,
        end_to_end=["setup_s", "train_tokens_per_s", "step_ms_p90", "mfu"], per_layer=[])


def test_the_cell_s_names_resolve_and_its_numbers_are_stated():
    cell = harness.load_cell(CELL)
    assert families.family(cell.config).reference == "benchmark.reference.laguna_moe"
    assert cell.end_to_end == ["setup_s", "train_tokens_per_s", "step_ms_p90", "mfu"]
    assert set(cell.per_layer) == set(NEW) | {
        "init_s", "compile_s", "device_step_ms", "flash_attention_ms",
        "flash_attention_fwd_ms", "flash_attention_bwd_dq_ms", "flash_attention_bwd_dkv_ms",
        "router_ms", "expert_ffn_ms", "shared_expert_ms", "import_s", "hvd_init_s",
        "model_init_s", "model_init_compiles", "step_compile_s", "step_cache_hits",
        "forward_ms", "backward_ms", "optimizer_ms", "unattributed_ms"}
    for name in cell.per_layer:
        spec = harness.load_json(ROOT, "benchmark", "metrics", name + ".json")
        readers.reader(spec["reader"])
        if "flops_function" in spec:
            flops.function(spec["flops_function"])
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == NEW
    assert all(m["moves"] == "mfu" and m["source"] == "device_trace" for m in new)
    assert [m["layer"] for m in new] == ["kernels"] * 4 + ["window and full attention"] * 2 + [
        "routed experts"]
    # a time is read before the share that divides by it
    order = cell.per_layer.index
    for name in NEW:
        spec = harness.load_json(ROOT, "benchmark", "metrics", name + ".json")
        if "time_metric" in spec:
            assert order(spec["time_metric"]) < order(name)
    (workload,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (workload["config"], workload["traffic"], workload["chips"]) == (
        "laguna-xs.2", "s8192-1chip", 1)
    config = cell.config
    # the catalog's keys, every published width and the per-layer lists whole
    assert (config["hidden_size"], config["head_dim"], config["num_key_value_heads"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["shared_expert_intermediate_size"], config["sliding_window"],
            config["num_experts_per_tok"]) == (2048, 128, 8, 8192, 512, 512, 512, 8)
    assert len(config["layer_types"]) == len(config["mlp_layer_types"]) == \
        len(config["num_attention_heads_per_layer"]) == 40
    assert config["router_experts"] == config["published"]["num_experts"] == 256
    assert config["num_experts"] == 16 and config["vocab_size"] * 8 == 100352
    assert config["num_hidden_layers"] == 5 and config["published"]["num_hidden_layers"] == 40
    assert sorted(config["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert set(config["assumed"]) >= {"gating", "router", "hidden_act", "qk_norm", "rope",
                                      "seq_len", "router_aux_loss_coef", "optimizer", "init"}
    assert (config["gating_type"], config["router_scoring"], config["hidden_act"]) == (
        "per_head", "sigmoid", "silu")
    assert config["deployment"] and config["departures"]
    assert cell.traffic["seq_len"] == 8192 and cell.traffic["samples_per_chip"] == 1
    readings = config["check"]["readings"]
    for name, limit in config["check"]["limits"].items():
        if "sound_largest" in readings.get(name, {}):
            assert readings[name]["sound_largest"] < limit
        if "control_smallest" in readings.get(name, {}):
            assert limit < readings[name]["control_smallest"]
    assert any("control_smallest" in v for v in readings.values() if isinstance(v, dict))
    # the attention's leaves of all three kinds of layer are among those compared
    leaves = re.compile(config["check"]["diff_leaves"])
    for leaf in ("layer_0/attn/q/kernel", "layer_0/attn/gate/kernel", "layer_1/attn/k/kernel",
                 "layer_2/attn/v/kernel", "layer_3/attn/o/kernel", "layer_4/attn/gate/kernel",
                 "layer_0/mlp/down/kernel", "layer_4/shared_experts/up/kernel", "head/kernel",
                 "embed/embedding"):
        assert leaves.search(leaf), leaf
    for leaf in ("layer_1/moe/w_gate", "layer_4/moe/router/kernel"):
        assert not leaves.search(leaf), leaf


def test_the_flop_count_is_the_issue_s_table():
    cell = harness.load_cell(CELL)
    config, traffic = cell.config, cell.traffic
    per_token = flops_laguna.train_flops_per_token(config, traffic)
    assert per_token == families.flops_per_sample(config, traffic)
    full_matrices = 6 * flops_laguna.attention_matrix_params(config, 48)
    sliding_matrices = 6 * flops_laguna.attention_matrix_params(config, 64)
    dense = 6 * flops_laguna.feed_forward_matrix_params(config, "dense")
    sparse = 6 * flops_laguna.feed_forward_matrix_params(config, "sparse")
    full = 12 * 128 * 48 * 8193 / 2                 # a token sees 4,096.5 keys
    sliding = 12 * 128 * 64 * 4_063_488 / 8192      # and 496.0 under the window
    head = 6 * 2048 * 12544
    assert abs(full_matrices - 0.177e9) < 0.0005e9 and abs(sliding_matrices - 0.227e9) < 0.0005e9
    assert abs(dense - 0.302e9) < 0.0005e9 and abs(sparse - 0.0315e9) < 0.0005e9
    assert abs(full - 0.302e9) < 0.0005e9 and abs(sliding - 0.0488e9) < 0.0005e9
    assert abs(head - 0.154e9) < 0.0005e9
    want = (2 * full_matrices + 3 * sliding_matrices + dense + 4 * sparse + 2 * full
            + 3 * sliding + head)
    assert abs(per_token - want) < 1.0
    assert abs(per_token - 2.37e9) < 0.005e9 and abs(per_token * 8192 - 19.4e12) < 0.05e12
    # the shares the cell's `why` and PERF.md state
    step = per_token * 8192
    shares = {
        "full kernels": 2 * full * 8192 / step, "window kernels": 3 * sliding * 8192 / step,
        "attention matrices": (2 * full_matrices + 3 * sliding_matrices) * 8192 / step,
        "dense": dense * 8192 / step, "head": head * 8192 / step}
    for name, want in (("full kernels", 0.255), ("window kernels", 0.062),
                       ("attention matrices", 0.437), ("dense", 0.128), ("head", 0.065)):
        assert abs(shares[name] - want) < 0.002, (name, shares[name])
    assert 0.74 < sum(shares[k] for k in ("full kernels", "window kernels",
                                           "attention matrices")) < 0.76   # "75 %"
    assert flops_laguna.window_attention_train_flops_per_step(config, traffic, 1) == \
        3 * sliding * 8192
    assert flops_laguna.full_attention_train_flops_per_step(config, traffic, 1) == \
        2 * full * 8192
    assert flops_laguna.expert_ffn_train_flops_per_step(config, traffic, 1) == \
        4 * 6.0 * 3 * 2048 * 512 * 4096


@pytest.mark.parametrize("chips", [1, 4])
def test_family_through_run_cell(chips):
    result = harness.run_cell(tiny_cell(chips), seed=2 ** 31 + 39, seconds=0.3,
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


@pytest.mark.parametrize("breaker", [_unchanged_state, _no_shared_expert],
                         ids=["state_unchanged", "no_shared_expert"])
def test_broken_step_is_not_correct(monkeypatch, breaker):
    build = training.data_parallel_train_step
    monkeypatch.setattr(training, "data_parallel_train_step",
                        lambda *a, **k: breaker(build(*a, **k)))
    result = harness.run_cell(tiny_cell(), seed=2 ** 31 + 5, seconds=0.3, trace=False,
                              devices=jax.devices()[:1])
    assert result["correct"] is False


def test_a_window_dropped_on_one_layer_is_not_correct(monkeypatch):
    """Layer 2 (the second of the three sliding layers) computed with no window:
    what a program that lost one layer's window would give.  Every pass over the
    model calls the kernel five times, in layer order."""
    from horovod_tpu.ops import flash_attention as fa

    real, calls, dropped = fa.flash_attention, [], []

    def one_layer_unwindowed(q, k, v, causal=True, window=None, **kw):
        calls.append(window)
        if len(calls) % 5 == 3:
            assert window == 70
            dropped.append(window)
            window = None
        return real(q, k, v, causal=causal, window=window, **kw)

    monkeypatch.setattr(fa, "flash_attention", one_layer_unwindowed)
    result = harness.run_cell(tiny_cell(), seed=2 ** 31 + 5, seconds=0.3, trace=False,
                              devices=jax.devices()[:1])
    assert dropped and calls[:5] == [None, 70, 70, 70, None]
    assert result["correct"] is False, result
    assert result["checks"]["grad_diff_gap"]["value"] > 1e-3


def test_the_reference_reports_its_own_routing():
    cell = tiny_cell()
    harness.run_reference(cell, 5, jax.devices()[0])
    assert set(reference.REFERENCE_ROUTING) == {1, 2, 3, 4}
    assert reference.REFERENCE_ROUTING[1].shape == (1, 2 * TRAFFIC["seq_len"], 3)
    text = reference.routing_report(2, 4)
    assert text.startswith("# routing") and "over 4 routed layers" in text


def test_every_new_data_file_loads_and_names_what_is_there():
    """What ``selftest.check_files`` holds every file to, on this PR's own."""
    import selftest

    selftest.check_files()
    specs = {n: harness.load_json(ROOT, "benchmark", "metrics", n + ".json") for n in NEW}
    for name, scope in (("attn_rope_ms", "/attn_rope/"), ("attn_gate_ms", "/attn_gate/")):
        assert specs[name]["reader"] == "benchmark.readers_scope:trace_scope_per_step"
        assert specs[name]["pattern"] == scope
    for name, scope in (("window_attention_ms", "/attn_window/"),
                        ("full_attention_ms", "/attn_full/")):
        assert specs[name]["reader"] == \
            "benchmark.readers_kernels:trace_kernels_in_scope_per_step"
        assert (specs[name]["kernel"], specs[name]["pattern"]) == ("^flash_attention", scope)


def test_the_new_readers_find_nothing_in_a_program_without_the_scopes():
    """The recorded step of Qwen3-Next's cell (a program whose attention layer has
    none of the four scopes): the new scope metrics read nothing there and do not
    raise, which is what the parent gives for a metric new in this PR."""
    fixture = os.path.join(ROOT, "benchmark", "fixtures", "qwen3-next-80b-a3b-s8192-1chip")
    t = tr.load(fixture + ".trace.json")
    names = harness.load_json(fixture + ".scopes.json")
    specs = {n: harness.load_json(ROOT, "benchmark", "metrics", n + ".json") for n in NEW}
    for name in ("attn_rope_ms", "attn_gate_ms"):
        assert readers_scope.scope_ns(t, names, specs[name]["pattern"]) == 0.0
    for name in ("window_attention_ms", "full_attention_ms"):
        assert readers_kernels.kernels_in_scope_ns(
            t, names, specs[name]["kernel"], specs[name]["pattern"]) == 0.0
    r = readers.Readings(config={}, traffic={}, peaks={}, chips=1, rows_per_step=1)
    for name in NEW:
        if name != "laguna_expert_ffn_roofline":         # no time read: no share
            assert readers.reader(specs[name]["reader"])(r, specs[name]) is None
    # a step that has them: each reads its own kernels and not what XLA put around them
    paths = {
        "flash_attention_fwd.1": "jit(_step)/jvp(forward)/Transformer/layer_1/attn/attn_window/"
                                 "jit(flash_attention)/flash_attention_fwd/pallas_call",
        "reduce.2": "jit(_step)/jvp(forward)/Transformer/layer_1/attn/attn_window/"
                    "jit(flash_attention)/flash_attention_fwd/pallas_call",
        "flash_attention_bwd_dkv.3": "jit(_step)/transpose(jvp(forward))/Transformer/layer_4/"
                                     "attn/attn_full/jit(flash_attention)/"
                                     "flash_attention_bwd_dkv/pallas_call",
        "fusion.4": "jit(_step)/jvp(forward)/Transformer/layer_4/attn/attn_rope/cos",
        "fusion.5": "jit(_step)/transpose(jvp(forward))/Transformer/layer_0/attn/attn_gate/mul"}
    trace = tr.Trace(ops={"0": [("flash_attention_fwd.1", 0, 10), ("reduce.2", 10, 3),
                                ("flash_attention_bwd_dkv.3", 13, 30), ("fusion.4", 43, 5),
                                ("fusion.5", 48, 7)]})
    got = {n: readers_kernels.kernels_in_scope_ns(trace, paths, specs[n]["kernel"],
                                                  specs[n]["pattern"])
           for n in ("window_attention_ms", "full_attention_ms")}
    got.update({n: readers_scope.scope_ns(trace, paths, specs[n]["pattern"])
                for n in ("attn_rope_ms", "attn_gate_ms")})
    assert got == {"window_attention_ms": 10, "full_attention_ms": 30, "attn_rope_ms": 5,
                   "attn_gate_ms": 7}
    assert readers_scope.scope_ns(trace, paths, "/attn_window/") == 13     # the scope alone
    assert tr.matching_ns(trace, "^flash_attention") == got["window_attention_ms"] + \
        got["full_attention_ms"]


FIXTURE = os.path.join(ROOT, "benchmark", "fixtures", CELL)


def test_scope_readers_on_the_recorded_step():
    """One traced step of the cell on the chip, with the ``op_name`` of every
    instruction as the capture's own program gave it: the scopes the cell's
    metrics read, the kernels by name, and each kernel under its layer's scope."""
    t = tr.load(FIXTURE + ".trace.json")
    names = harness.load_json(FIXTURE + ".scopes.json")
    want = harness.load_json(FIXTURE + ".expected.json")
    assert set(want["scopes"]) >= {"/router/", "/experts/", "/shared_experts/", "/attn_rope/",
                                   "/attn_gate/", "/attn_window/", "/attn_full/"}
    for pattern, ns in want["scopes"].items():
        got = readers_scope.scope_ns(t, names, pattern)
        assert ns > 0 and abs(got - ns) <= 1e-6 * ns, (pattern, got, ns)
    kernels_ns = {scope: readers_kernels.kernels_in_scope_ns(t, names, "^flash_attention", scope)
                  for scope in ("/attn_window/", "/attn_full/")}
    for scope, ns in want["kernels_in_scope"].items():
        assert ns > 0 and abs(kernels_ns[scope] - ns) <= 1e-6 * ns, (scope, kernels_ns, ns)
        assert kernels_ns[scope] < want["scopes"][scope]       # the scope holds XLA's folds too
    for pattern in want["patterns"]:
        assert abs(tr.matching_ns(t, pattern) - want["values"]["matching_ns:" + pattern]) <= 1e-3
    # the window layers' kernels and the full layers' are the flash kernels, each once
    kernels = {n: path for n, path in names.items() if n.startswith("flash_attention")}
    assert sorted(k.split(".")[0] for k in kernels) == sorted(
        ["flash_attention_fwd", "flash_attention_bwd_dq", "flash_attention_bwd_dkv"] * 5)
    by_scope = {"attn_window": set(), "attn_full": set()}
    for name, path in kernels.items():
        (scope,) = [p for p in path.split("/") if p in by_scope]
        by_scope[scope].add(re.search(r"/layer_(\d)/", path).group(1))
    assert by_scope == {"attn_window": {"1", "2", "3"}, "attn_full": {"0", "4"}}
    total = want["values"]["matching_ns:^flash_attention"]
    assert abs(sum(kernels_ns.values()) - total) <= 1e-6 * total
    assert all("/experts/" in path for n, path in names.items() if n.startswith("grouped_matmul"))
    # no operation is under two of the four scopes
    four = [{n for n, path in names.items() if f"/{s}/" in path}
            for s in ("attn_rope", "attn_window", "attn_full", "attn_gate")]
    assert all(four) and not any(a & b for i, a in enumerate(four) for b in four[i + 1:])
