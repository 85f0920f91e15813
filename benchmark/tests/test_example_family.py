"""The worked example: a model family brought by new files alone
(``example_moe/``: family, program-side module, reference, FLOP and bytes
functions, readers, ``init.rules``, a loss through ``step_options``), driven
through ``harness.run_cell`` like a cell.  README.md's "Adding things" walks
through the same files.
"""

import jax
import pytest

import selftest
from benchmark import families, harness, readers
from benchmark.tests.example_moe import family as example_family

SEEDS = (5, 2 ** 31 + 13, 987654321)
METRICS = ("benchmark", "tests", "example_moe", "metrics")


def _run(chips, seed=2 ** 31 + 3):
    return harness.run_cell(selftest.example_cell(chips), seed=seed, seconds=0.3, trace=False,
                            devices=jax.devices()[:chips])


@pytest.mark.parametrize("chips", [1, 4])
def test_the_example_is_correct_against_its_reference(chips):
    result = _run(chips)
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 3
    assert set(result["metrics"]) == {"setup_s", "train_tokens_per_s", "step_ms_p90", "mfu"}
    assert list(result)[-1] == "checks"      # the numbers compared, last in the line
    limits = selftest.example_cell(1).config["check"]["limits"]
    for name, limit in limits.items():
        rows = [v for k, v in result["checks"].items() if k.startswith(name)]
        assert rows and all(r["limit"] == limit and r["value"] <= limit for r in rows)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_bfloat16_control_is_not_correct_and_the_program_is(seed):
    """The configuration states float32; the control is the reference one
    precision below, bfloat16 operands, and has to fail the stated limits."""
    cell = selftest.example_cell(1)
    device = jax.devices()[0]
    limits = cell.config["check"]["limits"]
    sound = harness.prepare(cell, seed, [device]).first
    ref = harness.run_reference(cell, seed, device, keep_first_gradient=True,
                                other_first_gradient=sound["first_gradient"])
    rows = harness.compare(sound, ref, limits, ref["grad_diff_norms"])
    assert all(r["ok"] for r in rows), rows
    control = harness.run_reference(cell, seed, device,
                                    precision=cell.config["check"]["control_precision"],
                                    other_first_gradient=ref["first_gradient"])
    rows = harness.compare(control, ref, limits, control["grad_diff_norms"])
    failed = {r["name"] for r in rows if not r["ok"]}
    assert {"grad_norm_gap", "delta_norm_gap", "grad_diff_gap"} <= failed, rows


def test_without_the_familys_loss_the_run_is_not_correct(monkeypatch):
    """``step_options`` is what carries the loss: take it away and the program
    trains on plain cross-entropy, 0.4 % off the reference's loss."""
    monkeypatch.delattr(example_family.MoeDecoder, "step_options")
    result = _run(1)
    assert result["correct"] is False, result
    assert result["checks"]["loss_gap_step1"]["value"] > 1e-3


def test_a_step_that_leaves_its_state_unchanged_is_not_correct(monkeypatch):
    from horovod_tpu import training
    from test_broken_path import _unchanged_state

    build = training.data_parallel_train_step
    monkeypatch.setattr(training, "data_parallel_train_step",
                        lambda *a, **k: _unchanged_state(build(*a, **k)))
    assert _run(1)["correct"] is False


def _readings(**kw):
    cell = selftest.example_cell(4)
    return readers.Readings(
        config=cell.config, traffic=cell.traffic, chips=4, rows_per_step=8,
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}, **kw)


def _spec(name):
    return harness.load_json(harness.HERE, *METRICS[1:], name + ".json")


def test_a_reader_in_a_new_file_reads_the_runs_readings():
    spec = _spec("expert_assignments")
    # 8 rows x 32 tokens x 2 experts a token x 2 layers
    assert readers.reader(spec["reader"])(_readings(), spec) == 8 * 32 * 2 * 2


def test_readings_carry_the_captures_directory(tmp_path):
    spec = _spec("capture_bytes")
    read = readers.reader(spec["reader"])
    assert read(_readings(), spec) is None                      # nothing to read: nothing
    assert read(_readings(trace_dir=str(tmp_path / "missing")), spec) is None
    (tmp_path / "plugins").mkdir()
    (tmp_path / "plugins" / "x.xplane.pb").write_bytes(b"0123456789")
    assert read(_readings(trace_dir=str(tmp_path)), spec) == 10.0


def test_the_roofline_reader_serves_a_bandwidth_bound_kernel_with_a_bytes_function():
    spec = _spec("expert_weights_roofline")
    assert spec["reader"] == "roofline" and spec["peak"] == "hbm_bytes_per_s"
    r = _readings(values={"expert_ffn_ms": 0.5})
    # 3 x 4 B x 2 layers x (2 x 4 experts x 32 x 64) parameters = 393,216 B
    want = 100.0 * (393216 / 819e9) / 0.5e-3
    assert readers.roofline(r, spec) == pytest.approx(want, rel=1e-12)
    assert readers.roofline(_readings(), spec) is None          # no time read: no share


def test_the_flop_function_counts_the_active_experts_only():
    cell = selftest.example_cell(1)
    per_token = families.flops_per_sample(cell.config, cell.traffic)
    d, f, width, vocab, layers, seq = 32, 64, 32, 64, 2, 32
    active = layers * (4 * d * width + d * 4 + 2 * 2 * d * f) + d * vocab
    assert per_token == 6.0 * active + 6.0 * layers * seq * width
    every = dict(cell.config, num_experts_per_tok=cell.config["num_experts"])
    assert families.flops_per_sample(every, cell.traffic) > per_token
