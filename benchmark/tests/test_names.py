"""The rule for names (``benchmark/__init__.py``), the seeded weights' rules and
what a run imports.

ISSUE 27 asked for these cases under ``tests/`` (the tier-1 suite); a PR of the
benchmark's kind may add files under ``benchmark/`` only, so they stand here
(PERF.md section 7 lists the move for a later PR).
"""

import hashlib
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import benchmark
import selftest
from benchmark import families, flops, harness, readers, weights

ROOT = harness.ROOT
TABLE_NAMES = (
    [("family", families.FAMILIES, n, o) for n, o in (
        ("resnet", families.Resnet), ("decoder_lm", families.DecoderLm))]
    + [("flops", flops.FUNCTIONS, n, getattr(flops, n)) for n in (
        "resnet_train_flops_per_image", "decoder_lm_train_flops_per_token",
        "flash_attention_train_flops_per_step")]
    + [("reader", readers.READERS, n, getattr(readers, n)) for n in (
        "host_span", "compile_meter", "trace_busy_per_step", "trace_events_per_step",
        "trace_exposed_per_step", "roofline")])


@pytest.mark.parametrize("kind,table,name,want", TABLE_NAMES,
                         ids=[f"{k}-{n}" for k, _, n, _ in TABLE_NAMES])
def test_a_name_of_today_resolves_to_the_object_it_resolved_to(kind, table, name, want):
    assert benchmark.resolve(name, table, kind) is want
    via = {"family": lambda: families.family({"family": name}),
           "flops": lambda: flops.function(name), "reader": lambda: readers.reader(name)}
    assert via[kind]() is want
    assert len(table) == {"family": 2, "flops": 3, "reader": 6}[kind]


NEW_NAMES = [
    ("benchmark.tests.example_moe.family:MoeDecoder", families.FAMILIES, "sample_unit"),
    ("benchmark.tests.example_moe.flops:train_flops_per_token", flops.FUNCTIONS, "__call__"),
    ("benchmark.tests.example_moe.flops:expert_weight_bytes_per_step", flops.FUNCTIONS,
     "__call__"),
    ("benchmark.tests.example_moe.readers:expert_assignments_per_step", readers.READERS,
     "__call__"),
    ("benchmark.flops:causal_attention_train_flops_per_token", flops.FUNCTIONS, "__call__"),
]


@pytest.mark.parametrize("name,table,has", NEW_NAMES, ids=[n for n, _, _ in NEW_NAMES])
def test_a_module_attribute_name_under_benchmark_resolves(name, table, has):
    found = benchmark.resolve(name, table)
    module, attribute = name.split(":")
    assert found is getattr(sys.modules[module], attribute) and hasattr(found, has)


REFUSED = [
    "horovod_tpu.trace.device:phases",        # the program as its own yardstick
    "horovod_tpu.models.transformer:Transformer",
    "json:loads",                             # anything else that can be imported
    "benchmark:resolve",                      # not a file under benchmark/
    "benchmark.no_such_module:f",
    "benchmark..harness:f",
    "benchmark/harness:f",
    "benchmarks.harness:f",
    "benchmark.flops:",
    "benchmark.flops:not an identifier",
    "no_colon_and_in_no_table",
    "",
]


@pytest.mark.parametrize("name", REFUSED)
def test_any_other_name_is_refused_with_the_rule(name):
    before = set(sys.modules)
    for table in (families.FAMILIES, flops.FUNCTIONS, readers.READERS):
        with pytest.raises(ValueError) as e:
            benchmark.resolve(name, table, "name")
        assert benchmark.RULE in str(e.value) and "under benchmark/" in str(e.value)
    assert set(sys.modules) == before, "a refused name must import nothing"


def _example_cell(**changes):
    cell = selftest.example_cell(1)
    cell.config = json.loads(json.dumps(cell.config))
    for path, value in changes.items():
        *keys, last = path.split(".")
        node = cell.config
        for k in keys:
            node = node[k]
        node[last] = value
    return cell


@pytest.mark.parametrize("change", [
    {"family": "horovod_tpu.models.transformer:Transformer"},
    {"flops.function": "horovod_tpu.ops.comm_model:modeled_flops"},
    {"flops.function": "benchmark.tests.example_moe.no_such:f"},
], ids=["family", "flops_function", "flops_function_no_file"])
def test_a_configuration_that_names_the_program_is_refused_before_any_device_work(change):
    with pytest.raises(ValueError, match="under benchmark/"):
        harness.check_names(_example_cell(**change))


def test_a_family_whose_reference_is_outside_the_benchmark_is_refused(monkeypatch):
    from benchmark.tests.example_moe import family

    monkeypatch.setattr(family.MoeDecoder, "reference", "horovod_tpu.models.transformer")
    with pytest.raises(ValueError, match="reference .* is refused"):
        harness.check_names(_example_cell())
    with pytest.raises(ValueError, match="reference .* is refused"):
        families.reference(_example_cell().config)


def test_a_metric_file_that_names_a_reader_of_the_program_is_refused(tmp_path):
    (tmp_path / "benchmark" / "metrics").mkdir(parents=True)
    cell = _example_cell()
    cell.per_layer = ["phases_ms"]
    spec = {"reader": "horovod_tpu.trace.device:phases"}
    (tmp_path / "benchmark" / "metrics" / "phases_ms.json").write_text(json.dumps(spec))
    with pytest.raises(ValueError, match="reader .* is refused"):
        harness.check_names(cell, str(tmp_path))
    spec = {"reader": "roofline", "flops_function": "horovod_tpu.ops.comm_model:modeled_flops"}
    (tmp_path / "benchmark" / "metrics" / "phases_ms.json").write_text(json.dumps(spec))
    with pytest.raises(ValueError, match="FLOP function .* is refused"):
        harness.check_names(cell, str(tmp_path))


# -- seeded weights ---------------------------------------------------------------

# sha256 over every leaf's path and bytes, made by the parent's weights.py (commit
# 6015434, before rules existed) for selftest's tiny models, seed 2**31 + 11, on the CPU
PARENT_DIGESTS = {
    "decoder_lm": ("3ec1211f5446d8787b6b1b4a66824adc4e30633fc436b88aaa66d259170fffb4", 20),
    "resnet": ("6f0f7bd5cd69311e9147f9127f916d39ea8b1f787ab85b1241a667047e2c32c9", 29),
}


def _shapes(config, traffic):
    fam = families.family(config)
    inputs, _ = fam.batch(jax.random.PRNGKey(0), config, traffic, 2)
    model = fam.model(config)
    return jax.eval_shape(lambda k, x: model.init(k, x)["params"], jax.random.PRNGKey(0),
                          inputs[:1])


def _params(config, traffic, seed):
    _, k_params, _ = jax.random.split(weights.seed_key(seed), 3)
    return weights.make_params(_shapes(config, traffic), k_params, config["init"])


@pytest.mark.parametrize("config", [selftest.TINY_LM, selftest.TINY_RESNET],
                         ids=lambda c: c["family"])
def test_with_no_rules_the_leaves_are_bit_for_bit_the_parents(config):
    params = _params(config, selftest.TINY_TRAFFIC[config["family"]], 2 ** 31 + 11)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    h = hashlib.sha256()
    for path, x in flat:
        h.update("/".join(str(getattr(k, "key", k)) for k in path).encode())
        h.update(np.asarray(x).tobytes())
    assert (h.hexdigest(), len(flat)) == PARENT_DIGESTS[config["family"]]


def test_a_rule_gives_the_stacked_tensor_the_fan_in_it_names():
    cell = selftest.example_cell(1)
    params = _params(cell.config, cell.traffic, 12345)
    d, f = cell.config["hidden_size"], cell.config["moe_intermediate_size"]
    experts = cell.config["num_experts"]
    for i in range(cell.config["num_hidden_layers"]):
        layer = params[f"layer_{i}"]
        w_in, w_out = layer["moe"]["w_in"], layer["moe"]["w_out"]
        assert w_in.shape == (experts, d, f) and w_out.shape == (experts, f, d)
        # fan in is axis 1 (d, or f), not experts x d: 8,192 values a tensor, so within 5 %
        assert abs(float(np.std(w_in)) * np.sqrt(d) - 1.0) < 0.05
        assert abs(float(np.std(w_out)) * np.sqrt(f) - 1.0) < 0.05
        assert abs(float(np.std(layer["router"]["kernel"])) / 0.5 - 1.0) < 0.2
        # the built-in rules still serve the leaves no rule of the file matches
        assert abs(float(np.std(layer["attn"]["q"]["kernel"])) / 0.02 - 1.0) < 0.1
        assert np.all(np.asarray(layer["ln1"]["scale"]) == 1.0)


@pytest.mark.parametrize("rule,want", [
    ({"constant": 0.25}, lambda x: np.all(x == 0.25)),
    ({"std": 3.0}, lambda x: abs(np.std(x) / 3.0 - 1.0) < 0.05),
    ({"fan_in_axes": [0, 1], "gain": 2.0}, lambda x: abs(np.std(x) * np.sqrt(64 * 32) / 2.0 - 1.0)
     < 0.05),
], ids=["constant", "std", "fan_in_axes"])
def test_each_form_of_rule(rule, want):
    shapes = {"stack": {"w": jax.ShapeDtypeStruct((64, 32, 16), np.float32)},
              "plain": {"kernel": jax.ShapeDtypeStruct((8, 4), np.float32)}}
    init = {"dense_std": 0.02, "rules": [dict(rule, match="^stack/w$")]}
    params = weights.make_params(shapes, jax.random.PRNGKey(1), init)
    assert want(np.asarray(params["stack"]["w"]))
    plain = weights.make_params(shapes["plain"], jax.random.PRNGKey(1), {"dense_std": 0.02})
    assert plain["kernel"].shape == (8, 4)


def test_a_leaf_with_no_rule_is_still_an_error_that_names_the_way_out():
    shapes = {"moe": {"w_in": jax.ShapeDtypeStruct((4, 8, 8), np.float32)}}
    with pytest.raises(ValueError, match="init.rules"):
        weights.make_params(shapes, jax.random.PRNGKey(0), {"dense_std": 0.02})
    with pytest.raises(ValueError, match="constant, std or fan_in_axes"):
        weights.make_params(shapes, jax.random.PRNGKey(0), {"rules": [{"match": "w_in"}]})


# -- what a run of today's cells imports ---------------------------------------------

_IMPORTS = """
import sys
sys.path.insert(0, {root!r})
from benchmark import harness
cell = harness.load_cell({cell!r})
print(sorted(m for m in sys.modules if m == "benchmark" or m.startswith("benchmark.")))
print("jax" in sys.modules)
"""


@pytest.mark.parametrize("cell", ["resnet50-b256-1chip", "internlm2-1.8b-s4096-1chip"])
def test_loading_a_cell_of_today_imports_what_the_parent_imported(cell):
    """The parent (commit 6015434): ``benchmark`` and ``benchmark.harness``, and
    not jax: run.py looks for the chip only after the cell's files are read."""
    out = subprocess.run([sys.executable, "-c", _IMPORTS.format(root=ROOT, cell=cell)],
                         capture_output=True, text=True, timeout=120, check=True,
                         env=dict(os.environ, JAX_PLATFORMS="cpu")).stdout.splitlines()
    assert out == ["['benchmark', 'benchmark.harness']", "False"]


def test_step_options_are_the_familys_under_the_traffic_files():
    cell = selftest.example_cell(1)
    own = families.step_options(cell.config, cell.traffic)
    assert set(own) == {"loss_fn"} and callable(own["loss_fn"])
    merged = families.step_options(cell.config, {"step_options": {"overlap": True, "loss_fn": 7}})
    assert merged == {"overlap": True, "loss_fn": 7}
    for config in (selftest.TINY_LM, selftest.TINY_RESNET):   # today's families have none
        assert families.step_options(config, {}) == {}
        assert families.step_options(config, {"step_options": {"overlap": True}}) == {
            "overlap": True}
