"""The ``qwen3next_moe`` family: a causal decoder whose layers mix tokens by
Gated DeltaNet (a gated delta rule over a recurrent state) or, every
``full_attention_interval``-th, by gated softmax attention with rotary
positions on part of a head; every layer's feed-forward a softmax router over
SwiGLU experts beside one gated shared expert; norms in the zero-centred form
(Qwen3-Next-80B-A3B-Instruct's language model).  A configuration takes it with
``"family": "benchmark.families_qwen3next:Qwen3Next"``; the contract is in
``families.py``.

The batch.  A row is ``seq_len + 1`` ids uniform over the vocabulary slice from
the key; the inputs are the first ``seq_len``, the labels the last ``seq_len``
(the next token of every position).

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


def layer_types(config: dict) -> tuple:
    """The published rule: layer ``i`` (from 0) is ``full_attention`` when ``(i
    + 1) % full_attention_interval == 0``, else ``linear_attention``."""
    interval = config["full_attention_interval"]
    return tuple("full_attention" if (i + 1) % interval == 0 else "linear_attention"
                 for i in range(config["num_hidden_layers"]))


class Qwen3Next(families.DecoderLm):
    """A decoder counted in tokens, timed like ``DecoderLm``; its own model,
    batch, reference and loss."""

    reference = "benchmark.reference.qwen3next_moe"

    @staticmethod
    def model(config: dict):
        from horovod_tpu.models import transformer

        if not hasattr(transformer, "GatedDeltaNet"):
            raise NotImplementedError(
                "this program has no linear-attention layer "
                "(horovod_tpu.models.transformer.GatedDeltaNet): it cannot run "
                "the qwen3next_moe family")
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
            qk_norm=True,
            norm_zero_centered=True,
            attn_output_gate=True,
            partial_rotary_factor=config["partial_rotary_factor"],
            layer_types=layer_types(config),
            linear_num_key_heads=config["linear_num_key_heads"],
            linear_key_head_dim=config["linear_key_head_dim"],
            linear_num_value_heads=config["linear_num_value_heads"],
            linear_value_head_dim=config["linear_value_head_dim"],
            linear_conv_kernel_dim=config["linear_conv_kernel_dim"],
            num_experts=config["router_experts"],
            num_experts_per_tok=config["num_experts_per_tok"],
            moe_intermediate_size=config["moe_intermediate_size"],
            held_experts=(config["held_experts_first"], config["num_experts"]),
            num_shared_experts=(config["shared_expert_intermediate_size"]
                                // config["moe_intermediate_size"]),
            shared_expert_gate=True,
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
