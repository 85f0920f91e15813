"""Estimator + Store contract tests.

Reference analog: test/integration/test_spark_keras.py /
test_spark_torch.py (SURVEY.md §4) — fit a DataFrame, get a Transformer
back, checkpoint lands in the Store.  pyspark is absent, so the
launcher-subprocess backend runs the workers (the `local-cluster`
technique: real multi-process on one box).
"""

import os

import numpy as np
import pytest


from horovod_tpu.spark import LocalStore, Store
from horovod_tpu.spark.keras import FlaxEstimator, KerasEstimator
from horovod_tpu.spark.torch import TorchEstimator
from tests.estimator_models import TinyMLP, TinyTorchNet


def _blob_data(n=96, seed=0):
    """Linearly separable 3-class blobs: learnable by a tiny MLP fast."""
    rng = np.random.RandomState(seed)
    centers = np.asarray(
        [[2, 2, 0, 0], [-2, 2, 0, 0], [0, -2, 2, 0]], np.float32
    )
    labels = rng.randint(0, 3, size=n)
    feats = centers[labels] + 0.3 * rng.randn(n, 4).astype(np.float32)
    return {"features": feats, "label": labels.astype(np.int32)}


def test_store_create_dispatch(tmp_path):
    s = Store.create(str(tmp_path))
    assert isinstance(s, LocalStore)
    s.write_bytes(str(tmp_path / "a" / "b.bin"), b"xyz")
    assert s.read_bytes(str(tmp_path / "a" / "b.bin")) == b"xyz"
    assert s.exists(str(tmp_path / "a" / "b.bin"))
    assert s.list_files(str(tmp_path / "a")) == ["b.bin"]
    # URL schemes dispatch through fsspec; s3 needs s3fs (absent here)
    with pytest.raises(ImportError):
        Store.create("s3://bucket/prefix")


def test_fsspec_store_roundtrip():
    """Remote-store contract against fsspec's in-process fake filesystem
    (reference: HDFSStore/S3Store — VERDICT r3 item 4's 'local fake
    filesystem test')."""
    from horovod_tpu.spark import FsspecStore

    s = Store.create("memory://hvd-store-test")
    assert isinstance(s, FsspecStore)
    path = "memory://hvd-store-test/x/y.bin"
    assert not s.exists(path)
    s.write_bytes(path, b"payload")
    assert s.read_bytes(path) == b"payload"
    assert s.exists(path)
    assert s.list_files("memory://hvd-store-test/x") == ["y.bin"]
    assert s.list_files("memory://hvd-store-test/absent") == []
    # worker-side reconstruction travels (class name, prefix)
    spec = s.worker_spec()
    assert spec == {"store_cls": "FsspecStore",
                    "store_prefix": "memory://hvd-store-test"}


def test_sharded_materialization_accounting():
    """Streamed dealing: balanced per-rank rows, bounded shard files,
    equalized usable_rows, validation split — all recorded in the
    manifest (reference: Petastorm row-group assignment)."""
    from horovod_tpu.spark import sharding

    store = Store.create("memory://hvd-shard-test")
    rng = np.random.RandomState(0)

    def chunks():
        for i in range(7):
            n = 37 + i  # ragged chunk sizes on purpose
            yield {
                "features": rng.randn(n, 4).astype(np.float32),
                "label": rng.randint(0, 3, n).astype(np.int32),
            }

    m = sharding.materialize_streaming(
        store, "run1", chunks(), num_proc=3, batch_size=16,
        validation=0.1, seed=0, shard_rows=40,
    )
    total = sum(37 + i for i in range(7))
    assert sum(m["rows_per_rank"]) + m["val_rows"] == total
    assert max(m["rows_per_rank"]) - min(m["rows_per_rank"]) <= 1
    assert m["usable_rows"] == (min(m["rows_per_rank"]) // 16) * 16
    # every shard file exists and respects the row bound
    for rank in range(3):
        for i in range(m["shards_per_rank"][rank]):
            name = f"part_{rank}_{i:05d}.npz"
            p = store.get_train_data_path("run1") + "/" + name
            assert store.exists(p), name


def test_shard_reader_memory_contract():
    """The epoch reader holds at most one shard + a sub-batch carry in
    memory and yields exactly usable_rows//batch_size whole batches —
    the per-shard memory high-water VERDICT r3 item 4 requires."""
    from horovod_tpu.spark import sharding

    store = Store.create("memory://hvd-reader-test")
    rng = np.random.RandomState(0)
    n, shard_rows, bs = 500, 64, 32
    data = {
        "features": rng.randn(n, 2).astype(np.float32),
        "label": np.arange(n, dtype=np.int64),  # unique → coverage check
    }
    m = sharding.materialize_streaming(
        store, "r", iter([data]), num_proc=1, batch_size=bs,
        shuffle=True, seed=1, shard_rows=shard_rows,
    )
    reader = sharding.ShardReader(
        store, store.get_train_data_path("r"), 0, m["shards_per_rank"][0]
    )
    seen = []
    nb = 0
    for batch in reader.iter_batches(
        np.random.RandomState(2), bs, m["usable_rows"]
    ):
        assert len(batch["label"]) == bs
        seen.extend(batch["label"].tolist())
        nb += 1
    assert nb == m["usable_rows"] // bs
    assert len(set(seen)) == len(seen)  # no row repeated within an epoch
    assert reader.max_resident_rows <= shard_rows + bs
    # different epoch rng → different order (shuffling actually happens)
    other = [
        b["label"].tolist()
        for b in reader.iter_batches(
            np.random.RandomState(3), bs, m["usable_rows"]
        )
    ]
    assert [x for b in other for x in b] != seen


@pytest.mark.integration
def test_flax_estimator_fit_transform(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    data = _blob_data()

    # feed fit() a CHUNK ITERATOR (the fully streaming input path) with
    # shard_rows small enough to force multiple shards per rank — the
    # subprocess workers then exercise the multi-shard epoch reader
    def chunk_stream():
        for start in range(0, 96, 24):
            yield {k: v[start:start + 24] for k, v in data.items()}

    est = FlaxEstimator(
        model=TinyMLP(features=3),
        optimizer=("sgd", {"learning_rate": 0.2}),
        loss="softmax_cross_entropy",
        store=LocalStore(str(tmp_path)),
        batch_size=16,
        epochs=8,
        num_proc=2,
        validation=0.1,
        shard_rows=20,
    )
    model = est.fit(chunk_stream())
    from horovod_tpu.spark import sharding

    manifest = sharding.read_manifest(
        est.store, est.store.get_run_path(est.run_id)
    )
    assert all(s >= 2 for s in manifest["shards_per_rank"]), manifest
    # checkpoint landed in the store under the run id
    assert est.run_id is not None
    ckpt = os.path.join(
        est.store.get_checkpoint_path(est.run_id), "model.bin"
    )
    assert est.store.exists(ckpt)
    # transformer appends predictions; separable blobs must be learned
    out = model.transform(data)
    preds = np.argmax(out["label__output"], axis=-1)
    acc = float((preds == data["label"]).mean())
    assert out["label__output"].shape == (96, 3)
    assert acc >= 0.8, f"accuracy {acc}"


@pytest.mark.integration
def test_torch_estimator_fit_transform(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    pd = pytest.importorskip("pandas")
    rng = np.random.RandomState(0)
    feats = rng.randn(64, 4).astype(np.float32)
    w = np.asarray([1.0, -2.0, 0.5, 3.0], np.float32)
    labels = feats @ w
    # pandas with an object column of per-row vectors — the reference's
    # vector-features input shape (stacked dense by _to_columns)
    df = pd.DataFrame({"features": list(feats), "label": labels})
    est = TorchEstimator(
        model=TinyTorchNet(),
        optimizer=("sgd", {"lr": 0.05}),
        loss="mse",
        store=LocalStore(str(tmp_path)),
        batch_size=16,
        epochs=20,
        num_proc=2,
        validation=0.1,
    )
    model = est.fit(df)
    out = model.transform({"features": feats, "label": labels})
    mse = float(((out["label__output"] - labels) ** 2).mean())
    base = float((labels ** 2).mean())
    assert mse < 0.1 * base, f"mse {mse} vs baseline {base}"
    # per-epoch history recorded, including the validation series
    assert model.history and len(model.history["loss"]) == 20
    assert len(model.history["val_loss"]) == 20


@pytest.mark.integration
def test_keras_estimator_fit_transform(tmp_path, monkeypatch):
    """Real-Keras estimator: a Keras 3 model trains across the worker
    fleet via the Keras adapter's DistributedOptimizer (reference:
    spark/keras KerasEstimator)."""
    keras = pytest.importorskip("keras")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("TF_CPP_MIN_LOG_LEVEL", "3")
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    rng = np.random.RandomState(0)
    x = rng.randn(96, 4).astype(np.float32)
    w_true = rng.randn(4, 1).astype(np.float32)
    data = {"features": x, "label": (x @ w_true).ravel()}

    keras.utils.set_random_seed(3)
    model = keras.Sequential([
        keras.Input(shape=(4,)), keras.layers.Dense(1)
    ])
    est = KerasEstimator(
        model=model,
        optimizer=keras.optimizers.SGD(0.1),
        loss="mse",
        store=LocalStore(str(tmp_path)),
        batch_size=16,
        epochs=6,
        num_proc=2,
        validation=0.1,
    )
    trained = est.fit(data)
    assert trained.history is not None
    losses = trained.history["loss"]
    assert losses[-1] < losses[0] * 0.2, losses
    assert len(trained.history["val_loss"]) == 6  # per-epoch contract
    out = trained.transform(data)
    pred = out["label__output"].ravel()
    mse = float(np.mean((pred - data["label"]) ** 2))
    assert mse < 0.1, mse


@pytest.mark.integration
def test_keras_estimator_deferred_build_model(tmp_path, monkeypatch):
    """A driver model with no Input spec ships no weights; workers must
    build against the data and broadcast rank 0's init instead of
    training from divergent per-process random initializations."""
    keras = pytest.importorskip("keras")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("TF_CPP_MIN_LOG_LEVEL", "3")
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    rng = np.random.RandomState(1)
    x = rng.randn(64, 3).astype(np.float32)
    data = {"features": x, "label": (x.sum(axis=1)).astype(np.float32)}

    model = keras.Sequential([keras.layers.Dense(1)])  # deferred build
    assert model.get_weights() == []
    est = KerasEstimator(
        model=model, optimizer="sgd", loss="mse",
        store=LocalStore(str(tmp_path)), batch_size=16, epochs=3,
        num_proc=2,
    )
    trained = est.fit(data)
    losses = trained.history["loss"]
    assert losses[-1] < losses[0], losses


def test_validation_credit_accumulates_across_small_chunks():
    """validation=0.1 with 4-row chunks must still yield ~10% val rows
    (fractional credit carries across chunks instead of rounding to
    zero per chunk)."""
    from horovod_tpu.spark import sharding

    store = Store.create("memory://hvd-valcredit-test")
    rng = np.random.RandomState(0)

    def chunks():
        for _ in range(50):  # 200 rows total, 4 at a time
            yield {"x": rng.randn(4, 2).astype(np.float32),
                   "label": np.zeros(4, np.int32)}

    m = sharding.materialize_streaming(
        store, "r", chunks(), num_proc=2, batch_size=8,
        validation=0.1, seed=0, shard_rows=64,
    )
    assert m["val_rows"] == 20, m  # exactly 10% of 200


def test_materialize_missing_column_fails_before_writing():
    """A typo'd feature column raises on the FIRST chunk — before the
    stream is consumed and shards land in the store."""
    from horovod_tpu.spark import sharding

    store = Store.create("memory://hvd-failfast-test")
    consumed = []

    def chunks():
        for i in range(100):
            consumed.append(i)
            yield {"x": np.zeros((8, 2), np.float32),
                   "label": np.zeros(8, np.int32)}

    with pytest.raises(ValueError, match="featurez"):
        sharding.materialize_streaming(
            store, "r", chunks(), num_proc=1, batch_size=4,
            required_columns=["featurez", "label"],
        )
    assert len(consumed) == 1  # only the first chunk was pulled
