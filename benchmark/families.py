"""The model families the harness can build.

A family says how a configuration's file becomes the program's model, what a
batch looks like, what a sample is, and where its plain reference lives.  A
configuration names its family (``"family"``); a new model of a family that
is here needs no code.  ``resnet`` and ``decoder_lm`` are in the table below;
any other name is ``<module>:<attribute>`` under ``benchmark/`` (the rule in
``benchmark/__init__.py``), so a new family is a new file.

The contract: a family is an object (a class used as a namespace will do) with

  ``model(config)``                   the program's model: a flax module whose
                                      ``init(key, sample)`` gives ``params`` (and
                                      maybe ``batch_stats``) and whose ``apply``
                                      gives what the step's loss takes
  ``batch(key, config, traffic, rows)``  (inputs, labels) of ``rows`` rows from
                                      the key, traceable, every row different
  ``samples_per_row(traffic)``        samples (images, tokens) a row holds
  ``expects_kernel(config)``          whether the lowered step has to hold a
                                      ``tpu_custom_call`` on a TPU
  ``sample_unit``                     ``"images"``, ``"tokens"``
  ``throughput_metric``               the end-to-end metric of its rate
  ``reference``                       the module of its plain reference, under
                                      ``benchmark/``, with ``build(config, traffic)
                                      -> (stages, loss_backward)`` for
                                      ``reference.chain.train_steps``

and, optionally (today's two families have none, so their runs do not change),

  ``step_options(config, traffic)``   keyword arguments for
                                      ``training.data_parallel_train_step`` that a
                                      JSON file cannot carry (a ``loss_fn``); the
                                      traffic file's ``step_options`` go on top.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

from benchmark import check_module, flops, resolve

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


class Resnet:
    sample_unit = "images"
    throughput_metric = "train_images_per_s"
    reference = "benchmark.reference.resnet"

    @staticmethod
    def model(config: dict):
        from horovod_tpu import models

        spec = config["model"]
        return getattr(models, spec["name"])(
            dtype=_DTYPES[config["compute_dtype"]], **spec["kwargs"])

    @staticmethod
    def batch(key, config: dict, traffic: dict, rows: int):
        """Standard-normal images and uniform labels; every row differs."""
        k1, k2 = jax.random.split(key)
        size = traffic.get("image_size", config["image_size"])
        images = jax.random.normal(k1, (rows, size, size, 3), jnp.float32)
        labels = jax.random.randint(k2, (rows,), 0, config["num_classes"])
        return images, labels

    @staticmethod
    def samples_per_row(traffic: dict) -> int:
        return 1

    @staticmethod
    def expects_kernel(config: dict) -> bool:
        return False


class DecoderLm:
    sample_unit = "tokens"
    throughput_metric = "train_tokens_per_s"
    reference = "benchmark.reference.decoder_lm"

    @staticmethod
    def model(config: dict):
        from horovod_tpu.models import transformer

        spec = config["model"]
        cfg = transformer.TransformerConfig(
            vocab_size=config["vocab_size"],
            num_layers=config["num_hidden_layers"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            mlp_ratio=config["intermediate_size"] // config["hidden_size"],
            max_seq_len=config["max_position_embeddings"],
            dtype=_DTYPES[config["compute_dtype"]], **spec["kwargs"])
        return getattr(transformer, spec["name"])(cfg)

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
        return config["model"]["kwargs"].get("attention_impl") == "flash"


FAMILIES = {"resnet": Resnet, "decoder_lm": DecoderLm}


def family(config: dict):
    return resolve(config["family"], FAMILIES, "family")


def reference(config: dict):
    return importlib.import_module(check_module(family(config).reference, "reference"))


def step_options(config: dict, traffic: dict) -> dict:
    """Keyword arguments for ``data_parallel_train_step``: the family's, where
    it has any, under the traffic file's."""
    own = getattr(family(config), "step_options", None)
    return {**(own(config, traffic) if own else {}), **traffic.get("step_options", {})}


def flops_per_sample(config: dict, traffic: dict) -> float:
    return flops.function(config["flops"]["function"])(config, traffic)


def optimizer(spec: dict):
    """The program-side optimizer (optax) for a configuration's spec; the
    references write the same two out by hand."""
    import optax

    if spec["name"] == "sgd":
        return optax.sgd(spec["learning_rate"], momentum=spec["momentum"])
    if spec["name"] == "adamw":
        return optax.adamw(spec["learning_rate"], b1=spec["b1"], b2=spec["b2"],
                           eps=spec["eps"], weight_decay=spec["weight_decay"])
    raise ValueError(f"unknown optimizer {spec['name']!r}")


def first_gradient(opt_state, spec: dict):
    """The first gradient as the optimizer got it, from the optimizer's state
    after one step, as (tree, factor): SGD's momentum trace after one step is
    the gradient; Adam's first moment is (1 - b1) times it."""
    field, factor = ("trace", 1.0) if spec["name"] == "sgd" else ("mu", 1.0 / (1.0 - spec["b1"]))
    (found,) = [s for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda s: hasattr(s, field)) if hasattr(s, field)]
    return getattr(found, field), factor
