"""A run with the timed path broken underneath has to say ``correct: false``.

These skip ``run.py``'s look for a chip and drive the rest of a run
(``harness.run_cell``: set-up, first steps, window, reference, comparison) on
CPU devices at a tiny size, with ``training.data_parallel_train_step``
replaced by a step that is wrong in one way.
"""

import jax
import jax.numpy as jnp
import pytest

import selftest
from benchmark import harness
from horovod_tpu import training
from test_control import tiny_cell


def _unchanged_state(real):
    return jax.jit(lambda s, x, y: (s, real(s, x, y)[1]))


def _half_the_batch(real):
    def step(s, x, y):
        half = x.shape[0] // 2
        return real(s, jnp.concatenate([x[:half], x[:half]]),
                    jnp.concatenate([y[:half], y[:half]]))

    return jax.jit(step)


def _run(monkeypatch, config, chips, breaker):
    cell = tiny_cell(config, chips)
    if breaker is not None:
        build = training.data_parallel_train_step
        monkeypatch.setattr(training, "data_parallel_train_step",
                            lambda *a, **k: breaker(build(*a, **k)))
    return harness.run_cell(cell, seed=2 ** 31 + 5, seconds=0.3, trace=False,
                            devices=jax.devices()[:chips])


def test_sound_run_is_correct(monkeypatch):
    assert _run(monkeypatch, selftest.TINY_LM, 1, None)["correct"]


@pytest.mark.parametrize("breaker", [_unchanged_state, _half_the_batch],
                         ids=["state_unchanged", "half_the_batch"])
def test_broken_step_is_not_correct(monkeypatch, breaker):
    result = _run(monkeypatch, selftest.TINY_LM, 1, breaker)
    assert result["correct"] is False, result


def test_exchange_left_out_is_not_correct(monkeypatch):
    """Four devices and no exchange between them: the all-reduce of the
    gradients (and of the loss) returns what it was given, so each device
    trains on its own rows alone."""
    from horovod_tpu.ops import spmd_ops

    monkeypatch.setattr(spmd_ops, "allreduce", lambda x, **_: x)
    result = _run(monkeypatch, selftest.TINY_LM, 4, None)
    assert result["correct"] is False, result


def test_four_devices_sound_run_is_correct(monkeypatch):
    assert _run(monkeypatch, selftest.TINY_LM, 4, None)["correct"]
