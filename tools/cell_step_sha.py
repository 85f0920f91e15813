#!/usr/bin/env python3
"""sha256 of a benchmark cell's lowered training step, from shapes alone.

    JAX_PLATFORMS=cpu python tools/cell_step_sha.py [--root TREE] CELL [CELL ...]

Lowers each cell's step (``benchmark/harness.py``'s families, model, optimizer
and step options; no weights, no data) on one CPU device and prints the first 16
hex digits of its StableHLO text's sha256 and the text's length.  What a PR
must not move, it runs in both trees (``--root`` a ``git archive`` of the
parent): equal lines, equal programs.  The number belongs to this script: a
step lowered another way (other argument shardings, a real batch) hashes
otherwise.
"""

import argparse
import hashlib
import os
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("cells", nargs="+")
    args = ap.parse_args()
    sys.path.insert(0, args.root)
    os.chdir(args.root)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from benchmark import families, harness
    from horovod_tpu import training

    mesh = Mesh(np.array(jax.devices()[:1]), (hvd.WORLD_AXIS,))
    shaped = lambda tree, spec: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=NamedSharding(mesh, spec)),
        tree)
    for name in args.cells:
        cell = harness.load_cell(name)
        config, traffic = cell.config, cell.traffic
        fam = families.family(config)
        inputs, labels = jax.eval_shape(
            lambda key: fam.batch(key, config, traffic, traffic["samples_per_chip"]),
            jax.random.PRNGKey(0))
        model, optimizer = fam.model(config), families.optimizer(config["optimizer"])
        sample = jax.tree_util.tree_map(
            lambda x: jnp.zeros((1,) + x.shape[1:], x.dtype), inputs)
        state = jax.eval_shape(lambda: training.create_train_state(
            model, optimizer, jax.random.PRNGKey(0), sample))
        step = training.data_parallel_train_step(
            model, optimizer, mesh=mesh, **families.step_options(config, traffic))
        text = step.lower(shaped(state, P()), shaped(inputs, P(hvd.WORLD_AXIS)),
                          shaped(labels, P(hvd.WORLD_AXIS))).as_text()
        print(name, hashlib.sha256(text.encode()).hexdigest()[:16], len(text), flush=True)


if __name__ == "__main__":
    main()
