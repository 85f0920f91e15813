"""The ``sdar_moe`` family: a decoder whose feed-forward is a router over
SwiGLU experts, trained by diffusion over blocks (SDAR; BD3-LM,
arXiv:2503.09573).  A configuration takes it with ``"family":
"benchmark.families_sdar:SdarMoe"``; the contract is in ``families.py``.

The batch.  A row is ``L = seq_len`` data tokens ``x0``, ids uniform in
``[0, V - 1)``; the mask id is ``V - 1``.  Blocks of ``B = block_length``
positions; for each block ``t ~ U(t_min, 1)``, each of its positions masked
with probability ``t``; ``xt = where(masked, V - 1, x0)``.  The model's input
is one array, ``[xt || x0]`` (2 L ids a row); the labels are ``(x0, masked /
t)``, the targets and the loss's weights.  All of it from the key.

The loss is the program's (``transformer.block_diffusion_loss``: the weighted
cross-entropy of the noisy half plus the router's auxiliary term), handed to
the step through ``step_options``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


class SdarMoe:
    sample_unit = "tokens"
    throughput_metric = "train_tokens_per_s"
    reference = "benchmark.reference.sdar_moe"

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
            hidden_size=config["hidden_size"],
            max_seq_len=config["max_position_embeddings"],
            rope_theta=float(config["rope_theta"]),
            rms_norm_eps=float(config["rms_norm_eps"]),
            tie_word_embeddings=config["tie_word_embeddings"],
            qk_norm=config["qk_norm"],
            num_experts=config["router_experts"],
            num_experts_per_tok=config["num_experts_per_tok"],
            moe_intermediate_size=config["moe_intermediate_size"],
            held_experts=(config["held_experts_first"], config["num_experts"]),
            block_diffusion=config["block_length"],
            dtype=_DTYPES[config["compute_dtype"]], **spec["kwargs"])
        return getattr(transformer, spec["name"])(cfg)

    @staticmethod
    def batch(key, config: dict, traffic: dict, rows: int):
        """``([xt || x0], (x0, masked / t))``; every row differs."""
        length, block = traffic["seq_len"], traffic["block_length"]
        if block != config["block_length"]:
            raise ValueError(
                f"the traffic's block_length {block} is not the configuration's "
                f"{config['block_length']} (the model's mask is built from that)")
        mask_id = config["mask_token_id"]
        k_data, k_t, k_mask = jax.random.split(key, 3)
        x0 = jax.random.randint(k_data, (rows, length), 0, config["vocab_size"] - 1)
        t = jax.random.uniform(k_t, (rows, -(-length // block)), jnp.float32,
                               traffic["t_min"], 1.0)
        t = jnp.repeat(t, block, axis=1)[:, :length]
        masked = jax.random.uniform(k_mask, (rows, length), jnp.float32) < t
        xt = jnp.where(masked, mask_id, x0)
        return (jnp.concatenate([xt, x0], axis=1),
                (x0, jnp.where(masked, 1.0 / t, 0.0)))

    @staticmethod
    def samples_per_row(traffic: dict) -> int:
        return traffic["seq_len"]

    @staticmethod
    def expects_kernel(config: dict) -> bool:
        return config["model"]["kwargs"].get("attention_impl") == "flash"

    @staticmethod
    def step_options(config: dict, traffic: dict) -> dict:
        """What JSON cannot carry: the loss, a function."""
        from horovod_tpu.models import transformer

        return {"loss_fn": functools.partial(
            transformer.block_diffusion_loss, aux_coef=config["router_aux_loss_coef"])}
