"""The ``kimi_moe`` family: a causal decoder with latent attention (MLA), a
leading dense layer, then layers whose feed-forward is a sigmoid router with a
selection bias and a scaling factor over SwiGLU experts beside shared experts
(the DeepSeek-V3 block; Kimi-VL-A3B's language decoder).  A configuration
takes it with ``"family": "benchmark.families_kimi:KimiMoe"``; the contract is
in ``families.py``.

The batch.  A row is ``seq_len + 1`` ids uniform over the vocabulary slice
from the key; the inputs are the first ``seq_len``, the labels the last
``seq_len`` (the next token of every position).

The loss is the program's (``transformer.next_token_loss``: the mean
cross-entropy in float32 plus the router's auxiliary term), handed to the step
through ``step_options``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark import families

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


class KimiMoe(families.DecoderLm):
    """A decoder counted in tokens, timed like ``DecoderLm``; its own model,
    batch, reference and loss."""

    reference = "benchmark.reference.kimi_moe"

    @staticmethod
    def model(config: dict):
        from horovod_tpu.models import transformer

        spec = config["model"]
        cfg = transformer.TransformerConfig(
            vocab_size=config["vocab_size"],
            num_layers=config["num_hidden_layers"],
            num_heads=config["num_attention_heads"],
            hidden_size=config["hidden_size"],
            max_seq_len=config["max_position_embeddings"],
            rope_theta=float(config["rope_theta"]),
            rms_norm_eps=float(config["rms_norm_eps"]),
            tie_word_embeddings=config["tie_word_embeddings"],
            kv_lora_rank=config["kv_lora_rank"],
            qk_nope_head_dim=config["qk_nope_head_dim"],
            qk_rope_head_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            intermediate_size=config["intermediate_size"],
            first_dense_layers=config["first_k_dense_replace"],
            num_shared_experts=config["n_shared_experts"],
            num_experts=config["router_experts"],
            num_experts_per_tok=config["num_experts_per_tok"],
            moe_intermediate_size=config["moe_intermediate_size"],
            held_experts=(config["held_experts_first"], config["n_routed_experts"]),
            router_scoring=config["scoring_func"],
            routed_scaling_factor=float(config["routed_scaling_factor"]),
            router_selection_bias=config["topk_method"] == "noaux_tc",
            router_seq_aux=config["seq_aux"],
            dtype=_DTYPES[config["compute_dtype"]], **spec["kwargs"])
        return getattr(transformer, spec["name"])(cfg)

    @staticmethod
    def batch(key, config: dict, traffic: dict, rows: int):
        """``(ids[:, :-1], ids[:, 1:])``; every row differs."""
        ids = jax.random.randint(key, (rows, traffic["seq_len"] + 1), 0,
                                 config["vocab_size"])
        return ids[:, :-1], ids[:, 1:]

    @staticmethod
    def step_options(config: dict, traffic: dict) -> dict:
        """What JSON cannot carry: the loss, a function."""
        from horovod_tpu.models import transformer

        return {"loss_fn": functools.partial(
            transformer.next_token_loss, aux_coef=config["router_aux_loss_coef"])}
