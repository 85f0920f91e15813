"""Keras estimator example: DataFrame in, trained Transformer out.

Reference analog: examples/spark/keras/keras_spark_mnist.py — the
estimator contract (`fit(df)` → model with `transform`).  Runs without
pyspark: fit() accepts a pandas DataFrame, a dict of arrays, or (shown
here) any iterable of row-chunks — the fully streaming input path,
where the driver's memory high-water is one chunk + one filling shard
per worker (spark/sharding.py).  With pyspark installed, pass a Spark
DataFrame instead; it streams through toLocalIterator the same way.

    JAX_PLATFORMS=cpu python examples/spark/keras_spark_estimator.py
"""

import argparse
import os
import tempfile

import numpy as np


def synthetic_chunks(n_chunks=20, rows=256, seed=0):
    """A stream of row-chunks: y = x @ w + noise (never materialized
    as one array — stands in for a larger-than-memory table)."""
    rng = np.random.RandomState(seed)
    w = np.asarray([0.5, -2.0, 1.0, 3.0], np.float32)
    for _ in range(n_chunks):
        x = rng.randn(rows, 4).astype(np.float32)
        yield {
            "features": x,
            "label": (x @ w + 0.01 * rng.randn(rows)).astype(np.float32),
        }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--num-proc", type=int, default=2)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--work-dir", default=None)
    args = ap.parse_args()

    import keras

    from horovod_tpu.spark import LocalStore
    from horovod_tpu.spark.keras import KerasEstimator

    work = args.work_dir or tempfile.mkdtemp(prefix="hvd_spark_example_")
    keras.utils.set_random_seed(0)
    model = keras.Sequential([
        keras.Input(shape=(4,)),
        keras.layers.Dense(16, activation="relu"),
        keras.layers.Dense(1),
    ])
    est = KerasEstimator(
        model=model,
        optimizer=keras.optimizers.SGD(0.05),
        loss="mse",
        store=LocalStore(work),
        batch_size=64,
        epochs=args.epochs,
        num_proc=args.num_proc,
        validation=0.1,
        shard_rows=1024,  # small shards: workers stream one at a time
    )
    trained = est.fit(synthetic_chunks())
    print(f"run_id={est.run_id} store={work}")
    print("train loss per epoch:", [round(v, 4) for v in
                                    trained.history["loss"]])
    print("val loss per epoch:  ", [round(v, 4) for v in
                                    trained.history["val_loss"]])

    probe = next(synthetic_chunks(n_chunks=1, rows=8, seed=99))
    out = trained.transform(probe)
    err = float(np.mean((out["label__output"].ravel() - probe["label"])
                        ** 2))
    print(f"holdout mse: {err:.4f}")
    assert err < 0.5, err
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    raise SystemExit(main())
