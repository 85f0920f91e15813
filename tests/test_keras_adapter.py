"""Keras 3 adapter tests.

Reference analog: test/parallel/test_tensorflow2_keras.py (SURVEY.md §4) —
DistributedOptimizer under model.fit, the four callbacks, elastic
KerasState.  Single-process world (per-rank semantics are covered by the
launcher integration tests).
"""

import numpy as np
import pytest

keras = pytest.importorskip("keras")

import horovod_tpu.keras as hvd  # noqa: E402


def _tiny_model():
    model = keras.Sequential([
        keras.Input(shape=(4,)),
        keras.layers.Dense(8, activation="relu"),
        keras.layers.Dense(1),
    ])
    return model


def _data(n=64):
    rng = np.random.RandomState(0)
    x = rng.randn(n, 4).astype(np.float32)
    y = (x @ rng.randn(4, 1)).astype(np.float32)
    return x, y


def test_distributed_optimizer_fit_reduces_loss():
    model = _tiny_model()
    opt = hvd.DistributedOptimizer(keras.optimizers.SGD(0.05))
    model.compile(optimizer=opt, loss="mse")
    x, y = _data()
    hist = model.fit(x, y, batch_size=16, epochs=5, verbose=0)
    losses = hist.history["loss"]
    assert losses[-1] < losses[0] * 0.7, losses


def test_distributed_optimizer_matches_plain_sgd():
    x, y = _data(32)
    w_init = None
    results = []
    for distributed in (False, True):
        keras.utils.set_random_seed(0)
        model = _tiny_model()
        if w_init is None:
            w_init = model.get_weights()
        else:
            model.set_weights(w_init)
        opt = keras.optimizers.SGD(0.1)
        if distributed:
            opt = hvd.DistributedOptimizer(opt)
        model.compile(optimizer=opt, loss="mse")
        model.fit(x, y, batch_size=32, epochs=3, shuffle=False, verbose=0)
        results.append(model.get_weights())
    for a, b in zip(*results):
        # world of one process: allreduce is identity, so training must
        # match plain SGD bit-for-bit up to float noise
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_distributed_optimizer_rejects_double_wrap():
    # ADVICE round 3: wrapping twice used to recurse infinitely inside
    # super(self.__class__, self).apply — must be a clear error instead.
    opt = hvd.DistributedOptimizer(keras.optimizers.SGD(0.1))
    with pytest.raises(ValueError, match="already"):
        hvd.DistributedOptimizer(opt)


def test_warmup_default_initial_lr_uses_process_count(monkeypatch):
    # ADVICE round 3: gradient averaging divides by the PROCESS count
    # (cross_size), so the warmup default must start from
    # target/processes, not target/chips.
    from horovod_tpu.common import basics
    from horovod_tpu.keras.callbacks import LearningRateWarmupCallback

    monkeypatch.setattr(basics, "is_initialized", lambda: True)
    monkeypatch.setattr(basics, "cross_size", lambda: 2)
    monkeypatch.setattr(basics, "size", lambda: 16)  # 8 chips/process
    cb = LearningRateWarmupCallback(target_lr=0.8)
    assert cb._initial() == pytest.approx(0.4)


def test_backward_passes_per_step_aggregates():
    model = _tiny_model()
    opt = hvd.DistributedOptimizer(
        keras.optimizers.SGD(0.05), backward_passes_per_step=2
    )
    model.compile(optimizer=opt, loss="mse", run_eagerly=True)
    x, y = _data()
    w_before = [w.copy() for w in model.get_weights()]
    hist = model.fit(x, y, batch_size=16, epochs=3, verbose=0)
    assert hist.history["loss"][-1] < hist.history["loss"][0]
    assert any(
        not np.allclose(a, b)
        for a, b in zip(w_before, model.get_weights())
    )


def test_broadcast_callback_single_process():
    model = _tiny_model()
    model.compile(optimizer=hvd.DistributedOptimizer(
        keras.optimizers.SGD(0.01)), loss="mse")
    x, y = _data(32)
    w0 = [w.copy() for w in model.get_weights()]
    cb = hvd.callbacks.BroadcastGlobalVariablesCallback(0)
    model.fit(x, y, batch_size=32, epochs=1, verbose=0, callbacks=[cb])
    assert cb._done  # broadcast executed (identity at world 1)
    assert len(w0) == len(model.get_weights())


def test_metric_average_callback_single_process():
    cb = hvd.callbacks.MetricAverageCallback()
    logs = {"loss": 1.5, "acc": 0.5}
    cb.on_epoch_end(0, logs)
    assert logs == {"loss": 1.5, "acc": 0.5}  # world of 1: unchanged


def test_lr_warmup_callback_ramps():
    model = _tiny_model()
    opt = hvd.DistributedOptimizer(keras.optimizers.SGD(0.0))
    model.compile(optimizer=opt, loss="mse")
    cb = hvd.callbacks.LearningRateWarmupCallback(
        target_lr=0.8, warmup_epochs=2, steps_per_epoch=2, initial_lr=0.0
    )
    x, y = _data(64)
    model.fit(x, y, batch_size=32, epochs=3, verbose=0, callbacks=[cb])
    # warmup finished: LR pinned at target
    assert abs(float(np.array(model.optimizer.learning_rate)) - 0.8) < 1e-6


def test_lr_schedule_callback_staircase():
    model = _tiny_model()
    opt = hvd.DistributedOptimizer(keras.optimizers.SGD(1.0))
    model.compile(optimizer=opt, loss="mse")
    cb = hvd.callbacks.LearningRateScheduleCallback(
        initial_lr=1.0, multiplier=lambda e: 0.1 ** e, start_epoch=0
    )
    x, y = _data(32)
    model.fit(x, y, batch_size=32, epochs=3, verbose=0, callbacks=[cb])
    # last epoch (2) set lr = 1.0 * 0.1^2
    assert abs(float(np.array(model.optimizer.learning_rate)) - 0.01) < 1e-8


def test_keras_state_commit_restore_with_optimizer():
    model = _tiny_model()
    model.compile(optimizer=keras.optimizers.SGD(0.05), loss="mse")
    x, y = _data(32)
    model.fit(x, y, batch_size=32, epochs=1, verbose=0)  # builds optimizer
    state = hvd.elastic.KerasState(model, epoch=1)
    state.commit()
    w_committed = [w.copy() for w in model.get_weights()]
    model.fit(x, y, batch_size=32, epochs=1, verbose=0)
    state.epoch = 2
    state.restore()
    for got, want in zip(model.get_weights(), w_committed):
        np.testing.assert_allclose(got, want)
    assert state.epoch == 1


def test_commit_state_callback_commits_every_n():
    class DummyState:
        def __init__(self):
            self.commits = 0

        def commit(self):
            self.commits += 1

    st = DummyState()
    cb = hvd.elastic.CommitStateCallback(st, batches_per_commit=2)
    for b in range(6):
        cb.on_train_batch_end(b)
    assert st.commits == 3


@pytest.mark.parametrize("backend", ["jax", "torch"])
def test_alt_backend_distributed_optimizer_subprocess(backend):
    """KERAS_BACKEND=jax reaches the eager engine via jax.pure_callback
    from inside keras's jitted train step; KERAS_BACKEND=torch bridges
    grads through numpy and returns torch tensors.  A subprocess per
    backend is required because the keras backend is fixed at import."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = (
        "import numpy as np, keras\n"
        "import horovod_tpu.keras as hvd\n"
        "hvd.init()\n"
        f"assert keras.backend.backend() == '{backend}'\n"
        "model = keras.Sequential([keras.Input(shape=(4,)),"
        " keras.layers.Dense(1)])\n"
        "opt = hvd.DistributedOptimizer(keras.optimizers.SGD(0.05))\n"
        "model.compile(optimizer=opt, loss='mse')\n"
        "rng = np.random.RandomState(0)\n"
        "x = rng.randn(64, 4).astype(np.float32)\n"
        "y = (x @ rng.randn(4, 1)).astype(np.float32)\n"
        "h = model.fit(x, y, batch_size=16, epochs=4, verbose=0)\n"
        "assert h.history['loss'][-1] < h.history['loss'][0] * 0.7\n"
        "print('ALT-BACKEND-OK')\n"
    )
    env = os.environ.copy()
    env.update({"KERAS_BACKEND": backend,
                "JAX_PLATFORMS": "cpu", "TF_CPP_MIN_LOG_LEVEL": "3",
                "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", "")})
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=repo)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "ALT-BACKEND-OK" in res.stdout


def test_load_model_round_trips_distributed_optimizer(tmp_path):
    """Reference: hvd.keras.load_model — the REAL scenario: a model
    saved mid-training with a DistributedOptimizer-wrapped optimizer
    (whose dynamic subclass rides the saved config) must load and come
    back wrapped."""
    import keras
    import numpy as np

    import horovod_tpu.keras as hvd_keras

    model = keras.Sequential([keras.Input(shape=(4,)),
                              keras.layers.Dense(2)])
    model.compile(
        optimizer=hvd_keras.DistributedOptimizer(
            keras.optimizers.SGD(0.05)),
        loss="mse",
    )
    x = np.random.RandomState(0).randn(8, 4).astype(np.float32)
    model.fit(x, np.zeros((8, 2), np.float32), epochs=1, verbose=0)
    path = str(tmp_path / "m.keras")
    model.save(path)
    loaded = hvd_keras.load_model(path)
    assert hasattr(loaded.optimizer, "_hvd_passes_per_step") or \
        "Distributed" in type(loaded.optimizer).__name__
    # the restored model still trains
    loaded.fit(x, np.zeros((8, 2), np.float32), epochs=1, verbose=0)


def test_broadcast_global_variables_contract():
    """Keras-3 mapping of broadcast_global_variables: explicit models
    broadcast deterministically; the bare TF1-style call raises with
    migration guidance instead of guessing at live models."""
    import keras
    import numpy as np
    import pytest

    import horovod_tpu.keras as hvd_keras

    model = keras.Sequential([keras.Input(shape=(3,)),
                              keras.layers.Dense(2)])
    before = [np.asarray(w) for w in model.get_weights()]
    hvd_keras.broadcast_global_variables(0, models=model)
    for a, b in zip(before, model.get_weights()):
        np.testing.assert_allclose(a, np.asarray(b))
    with pytest.raises(ValueError, match="BroadcastGlobalVariables"):
        hvd_keras.broadcast_global_variables(0)
