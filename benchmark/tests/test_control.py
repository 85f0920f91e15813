"""The control of the output check, at a size a test run can hold.

The control is the plain reference put in the program's place and computed
one precision below the configuration's bfloat16: fp8 operands.  It has to
come out as not correct under limits that the sound program (bfloat16, the
real model code) passes.  The limits here are for the tiny sizes, set the
way the real ones are: above the sound runs' largest reading over the seeds
below, under the control's smallest (PERF.md section 2 has the chip's).
"""

import copy

import jax
import pytest

import selftest
from benchmark import harness

SEEDS = (3, 2 ** 31 + 7, 123456789)
# readings at these sizes on the CPU, four seeds (sound largest / control smallest):
# tiny ResNet, 8 images: grad_norm_gap 0.017 / 0.055, grad_diff_gap on head/kernel
# 0.0041 / 0.037 (loss and delta_norm_gap guard gross faults: 3 x sound);
# tiny decoder, 2 x 64 tokens: grad_norm_gap 0.0019 / 0.0068, delta_norm_gap
# 0.0013 / 0.0038, grad_diff_gap 0.0090 / 0.081
TINY_LIMITS = {
    "resnet": {"loss_gap": 0.003, "grad_norm_gap": 0.04, "delta_norm_gap": 0.25,
               "grad_diff_gap": 0.012},
    "decoder_lm": {"loss_gap": 0.01, "grad_norm_gap": 0.004, "delta_norm_gap": 0.0025,
                   "grad_diff_gap": 0.03},
}
DIFF_LEAVES = {"resnet": "^head/kernel$", "decoder_lm": ""}


def tiny_cell(config, chips=1):
    config = copy.deepcopy(config)
    config["check"]["limits"] = TINY_LIMITS[config["family"]]
    config["check"]["diff_leaves"] = DIFF_LEAVES[config["family"]]
    return selftest.tiny_cell(config, chips)


@pytest.mark.parametrize("config", [selftest.TINY_RESNET, selftest.TINY_LM],
                         ids=lambda c: c["family"])
@pytest.mark.parametrize("seed", SEEDS)
def test_fp8_control_is_not_correct_and_the_program_is(config, seed):
    cell = tiny_cell(config)
    device = jax.devices()[0]
    check = cell.config["check"]
    sound = harness.prepare(cell, seed, [device]).first
    ref = harness.run_reference(cell, seed, device, keep_first_gradient=True,
                                other_first_gradient=sound["first_gradient"])
    rows = harness.compare(sound, ref, check["limits"], ref["grad_diff_norms"],
                           check["diff_leaves"])
    assert all(r["ok"] for r in rows), rows
    control = harness.run_reference(cell, seed, device, precision="fp8",
                                    other_first_gradient=ref["first_gradient"])
    rows = harness.compare(control, ref, check["limits"], control["grad_diff_norms"],
                           check["diff_leaves"])
    assert not all(r["ok"] for r in rows), rows
