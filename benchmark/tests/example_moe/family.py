"""The worked example's family: everything the contract in
``benchmark/families.py`` asks for, in a file of its own.

A configuration takes it with ``"family":
"benchmark.tests.example_moe.family:MoeDecoder"``.  A real family's ``model``
returns a class of ``horovod_tpu.models``; this one returns the small module
beside it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.tests.example_moe import model as program

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


class MoeDecoder:
    sample_unit = "tokens"
    throughput_metric = "train_tokens_per_s"
    reference = "benchmark.tests.example_moe.reference"

    @staticmethod
    def model(config: dict):
        sizes = {k: v for k, v in config.items() if isinstance(v, (int, float))}
        return program.MoeDecoder(sizes, _DTYPES[config["compute_dtype"]])

    @staticmethod
    def batch(key, config: dict, traffic: dict, rows: int):
        """Uniform random tokens and targets; every row differs."""
        k1, k2 = jax.random.split(key)
        shape = (rows, traffic["seq_len"])
        return (jax.random.randint(k1, shape, 0, config["vocab_size"]),
                jax.random.randint(k2, shape, 0, config["vocab_size"]))

    @staticmethod
    def samples_per_row(traffic: dict) -> int:
        return traffic["seq_len"]

    @staticmethod
    def expects_kernel(config: dict) -> bool:
        return False

    @staticmethod
    def step_options(config: dict, traffic: dict) -> dict:
        """What JSON cannot carry: the loss, a function."""
        return {"loss_fn": functools.partial(program.z_loss_cross_entropy, z=config["z_loss"])}

