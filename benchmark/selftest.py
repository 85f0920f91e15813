#!/usr/bin/env python3
"""The harness's functions at a tiny size on the CPU; no chip, no timing claims.

    JAX_PLATFORMS=cpu python3 benchmark/selftest.py

It drives ``harness.run_cell`` (set-up, window, reference, comparison) for a
tiny ResNet, a tiny decoder and the worked example of a family brought by new
files alone (``tests/example_moe/``) on one virtual CPU device and on four, reduces
the small recorded trace in ``fixtures/`` and compares with the numbers
recorded beside it, and loads every file under ``configs/``, ``traffic/`` and
``metrics/`` against ``BENCHMARK.json`` and the driver's rules for names.
Numbers printed here are CPU numbers and mean nothing about speed.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import json
import re

TINY_RESNET = {
    "family": "resnet", "stage_sizes": [1, 1], "num_filters": 8,
    "bottleneck_expansion": 4, "num_classes": 10, "image_size": 32,
    "compute_dtype": "bfloat16", "param_dtype": "float32",
    "model": {"name": "ResNet50", "kwargs": {
        "stage_sizes": [1, 1], "num_filters": 8, "num_classes": 10,
        "stem": "space_to_depth"}},
    "optimizer": {"name": "sgd", "learning_rate": 0.1, "momentum": 0.9},
    "init": {"dense_std": None, "norm_scale": 1.0,
             "zero_scale": "BottleneckBlock_[0-9]+/BatchNorm_2/scale$"},
    "flops": {"function": "resnet_train_flops_per_image"},
    "check": {"steps": 3, "limits": {}},
}
TINY_LM = {
    "family": "decoder_lm", "hidden_size": 64, "intermediate_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 2, "vocab_size": 128, "max_position_embeddings": 64,
    "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "compute_dtype": "bfloat16", "param_dtype": "float32",
    "model": {"name": "Transformer", "kwargs": {"attention_impl": "flash"}},
    "optimizer": {"name": "adamw", "learning_rate": 1e-3, "b1": 0.9, "b2": 0.999,
                  "eps": 1e-8, "weight_decay": 1e-4},
    "init": {"dense_std": 0.02, "embed_std": 0.02, "norm_scale": 1.0},
    "flops": {"function": "decoder_lm_train_flops_per_token"},
    "check": {"steps": 3, "limits": {}},
}
TINY_TRAFFIC = {
    "resnet": {"samples_per_chip": 8, "span_steps": 2, "trace_steps": 3},
    "decoder_lm": {"samples_per_chip": 2, "seq_len": 64, "span_steps": 2, "trace_steps": 3},
}
EXAMPLE = os.path.join(ROOT, "benchmark", "tests", "example_moe")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def tiny_cell(config: dict, chips: int):
    from benchmark import harness

    return harness.Cell(
        name=f"tiny-{config['family']}-{chips}", config_name="tiny", config=config,
        traffic_name="tiny", traffic=TINY_TRAFFIC[config["family"]], chips=chips,
        end_to_end=["setup_s", "train_images_per_s", "train_tokens_per_s",
                    "step_ms_p90", "mfu"],
        per_layer=["init_s", "compile_s"])


def example_cell(chips: int):
    """The worked example's cell: its configuration (with its own limits) and
    traffic from its own files, every name of the ``<module>:<attribute>`` form."""
    from benchmark import harness

    cell = harness.Cell(
        name=f"example-moe-{chips}", config_name="example_moe",
        config=harness.load_json(EXAMPLE, "config.json"), traffic_name="example_moe",
        traffic=harness.load_json(EXAMPLE, "traffic.json"), chips=chips,
        end_to_end=["setup_s", "train_tokens_per_s", "step_ms_p90", "mfu"], per_layer=[])
    harness.check_names(cell)
    return cell


def check_cells():
    import jax

    from benchmark import harness

    for make in (lambda n: tiny_cell(TINY_RESNET, n), lambda n: tiny_cell(TINY_LM, n),
                 example_cell):
        for chips in (1, 4):
            cell = make(chips)
            result = harness.run_cell(cell, seed=2 ** 31 + 11, seconds=0.5,
                                      trace=False, devices=jax.devices()[:chips])
            assert result["correct"], result
            assert result["failed"] == 0 and result["attempted"] > 3, result
            print("ok", cell.name, json.dumps(result["metrics"]))


def check_trace_reducer():
    from benchmark import trace as tr

    fixtures = os.path.join(ROOT, "benchmark", "fixtures")
    for name in sorted(os.listdir(fixtures)):
        if not name.endswith(".trace.json"):
            continue
        t = tr.load(os.path.join(fixtures, name))
        want = json.load(open(os.path.join(fixtures, name.replace(".trace.", ".expected."))))
        got = {
            "busy_ns": tr.busy_ns(t), "window_ns": tr.window_ns(t),
            **{f"matching_ns:{p}": tr.matching_ns(t, p) for p in want.get("patterns", [])},
            **{f"exposed_ns:{p}": tr.exposed_ns(t, p) for p in want.get("patterns", [])},
        }
        for k, v in want["values"].items():
            assert abs(got[k] - v) <= 1e-6 * max(abs(v), 1.0), (name, k, got[k], v)
        assert len(tr.top_ops(t)) <= 10 and len(tr.idle_gaps(t)) <= 10
        print("ok trace", name, {k: round(v) for k, v in got.items()})
    # the arithmetic on a hand-made trace
    t = tr.Trace(ops={"0": [("a", 0.0, 10.0), ("all-reduce.1", 5.0, 10.0), ("b", 30.0, 5.0)]})
    assert tr.busy_ns(t) == 20.0 and tr.window_ns(t) == 35.0
    assert tr.matching_ns(t, "all-reduce") == 10.0 and tr.exposed_ns(t, "all-reduce") == 5.0
    assert [round(g[1] * 1e9) for g in tr.idle_gaps(t)] == [15]
    # an asynchronous collective beside compute: 20 long, 12 of it hidden
    t = tr.Trace(ops={"0": [("all-reduce-start.1", 0.0, 1.0), ("a", 2.0, 12.0),
                            ("all-reduce-done.1", 19.0, 1.0)]},
                 async_ops={"0": [("all-reduce-start.1", 0.0, 20.0)]})
    assert tr.matching_ns(t, "^all-reduce") == 20.0 and tr.exposed_ns(t, "^all-reduce") == 8.0


def check_spec_names(spec: dict):
    """A metric's file: its reader and its FLOP function are in the harness's
    tables, or of the new form with the module a file under ``benchmark/``."""
    from benchmark import check_name, flops, readers

    for key, table in (("reader", readers.READERS), ("flops_function", flops.FUNCTIONS)):
        if key in spec and spec[key] not in table:
            check_name(spec[key], key)


def check_files():
    from benchmark import families, harness

    bench = harness.load_json(ROOT, "BENCHMARK.json")
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.chips in (1, 4)
        families.family(cell.config)
        assert families.flops_per_sample(cell.config, cell.traffic) > 0
        for key in ("samples_per_chip", "span_steps", "trace_steps"):
            assert cell.traffic[key] > 0, (w["name"], key)
        for name in [w["name"], w["config"], w["traffic"]]:
            assert NAME.match(name), name
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in bench["configs"]:
        config = harness.load_json(ROOT, c["file"])
        assert config["source"] == c["source"], c["name"]
        assert sorted(config["reduced"]) == sorted(c["reduced"]), c["name"]
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in bench["per_layer"]:
        spec = harness.load_json(ROOT, "benchmark", "metrics", m["name"] + ".json")
        assert "reader" in spec, m["name"]
        check_spec_names(spec)
    for sub in ("configs", "traffic", "metrics"):
        for name in os.listdir(os.path.join(ROOT, "benchmark", sub)):
            harness.load_json(ROOT, "benchmark", sub, name)
            assert NAME.match(name), name
    for name in sorted(os.listdir(os.path.join(EXAMPLE, "metrics"))):
        check_spec_names(harness.load_json(EXAMPLE, "metrics", name))
    print("ok files")


if __name__ == "__main__":
    check_files()
    check_trace_reducer()
    check_cells()
    print("selftest passed")
