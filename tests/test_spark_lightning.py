"""Lightning-estimator contract tests with a faked pytorch_lightning.

Reference analog: test/integration/test_spark_lightning.py (SURVEY.md
§2.4 lightning estimator row).  lightning is not installable in this
image, so — like the pyspark/ray/mxnet surfaces — a minimal fake
(tests/_fake_modules/pytorch_lightning) provides the LightningModule
base class; the estimator, worker loop (configure_optimizers →
DistributedOptimizer, training_step, validation_step,
on_train_epoch_end) and Store plumbing all run for real across 2
subprocess workers.
"""

import os
import sys

import numpy as np
import pytest


FAKES = os.path.join(os.path.dirname(__file__), "_fake_modules")


@pytest.fixture
def lightning_env(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    # workers must import the fake pytorch_lightning to unpickle the model
    monkeypatch.setenv(
        "PYTHONPATH",
        FAKES + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    monkeypatch.syspath_prepend(FAKES)
    yield
    for name in list(sys.modules):
        if name.startswith("pytorch_lightning"):
            del sys.modules[name]


def _regression_df(n=64, seed=0):
    rng = np.random.RandomState(seed)
    feats = rng.randn(n, 4).astype(np.float32)
    w = np.asarray([1.0, -2.0, 0.5, 3.0], np.float32)
    return {"features": feats, "label": feats @ w}


def test_resolve_configure_optimizers_shapes(lightning_env):
    import torch

    from horovod_tpu.spark._estimator_worker import (
        _resolve_lightning_optimizer,
    )
    from tests.estimator_models_lightning import LitRegression

    m = LitRegression()
    opt = torch.optim.SGD(m.parameters(), lr=0.1)
    sched = torch.optim.lr_scheduler.StepLR(opt, step_size=1)
    assert _resolve_lightning_optimizer(opt) == (opt, None)
    assert _resolve_lightning_optimizer({"optimizer": opt}) == (opt, None)
    assert _resolve_lightning_optimizer(
        {"optimizer": opt, "lr_scheduler": {"scheduler": sched}}
    ) == (opt, sched)
    assert _resolve_lightning_optimizer(([opt], [sched])) == (opt, sched)
    assert _resolve_lightning_optimizer(([opt], [])) == (opt, None)
    # lightning's list-of-dicts shape
    assert _resolve_lightning_optimizer([{"optimizer": opt}]) == (opt, None)
    assert _resolve_lightning_optimizer(
        [{"optimizer": opt, "lr_scheduler": sched}]
    ) == (opt, sched)


@pytest.mark.integration
def test_lightning_estimator_fit_transform(tmp_path, lightning_env):
    from horovod_tpu.spark import LocalStore
    from horovod_tpu.spark.lightning import (
        LightningEstimator, TorchEstimator,
    )
    from tests.estimator_models_lightning import LitRegression

    assert LightningEstimator is TorchEstimator  # both reference names
    data = _regression_df()
    est = TorchEstimator(
        model=LitRegression(),
        store=LocalStore(str(tmp_path)),
        batch_size=16,
        epochs=20,
        num_proc=2,
        validation=0.1,
    )
    model = est.fit(data)
    out = model.transform(data)
    labels = data["label"]
    mse = float(((out["label__output"] - labels) ** 2).mean())
    base = float((labels ** 2).mean())
    assert mse < 0.1 * base, f"mse {mse} vs baseline {base}"
    # per-epoch history incl. the validation_step series
    assert model.history and len(model.history["loss"]) == 20
    assert len(model.history["val_loss"]) == 20


@pytest.mark.integration
def test_lightning_dict_configure_optimizers(tmp_path, lightning_env):
    from horovod_tpu.spark import LocalStore
    from horovod_tpu.spark.lightning import TorchEstimator
    from tests.estimator_models_lightning import LitDictOptimizer

    data = _regression_df(n=48, seed=1)
    est = TorchEstimator(
        model=LitDictOptimizer(),
        store=LocalStore(str(tmp_path)),
        batch_size=16,
        epochs=4,
        num_proc=1,
    )
    model = est.fit(data)
    assert len(model.history["loss"]) == 4
    # loss decreased over training
    assert model.history["loss"][-1] < model.history["loss"][0]
