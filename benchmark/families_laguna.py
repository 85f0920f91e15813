"""The ``laguna_moe`` family: a causal decoder whose attention differs by layer
(``layer_types``: sliding-window or full, ``num_attention_heads_per_layer``
query heads over shared key/value heads, ``rope_parameters`` by layer type:
plain RoPE or YaRN on part of a head, a sigmoid gate a head on the output), a
leading dense layer, then layers whose feed-forward is a router over SwiGLU
experts beside one shared expert (poolside's Laguna-XS.2).  A configuration
takes it with ``"family": "benchmark.families_laguna:Laguna"``; the contract is
in ``families.py``.

The configuration's file keeps the published per-layer lists whole; a
configuration cut in depth holds their first ``num_hidden_layers`` entries.

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
_YARN = ("factor", "original_max_position_embeddings", "beta_fast", "beta_slow",
         "attention_factor")


def rope_parameters(config: dict) -> dict:
    """The published ``rope_parameters`` a layer type, under the program's names."""
    out = {}
    for kind in ("full_attention", "sliding_attention"):
        given = config["rope_parameters"][kind]
        out[kind] = {"theta": float(given["rope_theta"]),
                     "partial_rotary_factor": float(given.get("partial_rotary_factor", 1.0))}
        if given.get("rope_type", "default") == "yarn":
            out[kind].update({k: given[k] for k in _YARN})
    return out


def leading_dense_layers(config: dict) -> int:
    """``mlp_layer_types`` of the layers held: ``dense`` ones first, then
    ``sparse`` ones only."""
    kinds = config["mlp_layer_types"][:config["num_hidden_layers"]]
    dense = next((i for i, k in enumerate(kinds) if k != "dense"), len(kinds))
    if any(k != "sparse" for k in kinds[dense:]):
        raise ValueError(f"mlp_layer_types {kinds}: dense layers lead, sparse ones follow")
    return dense


class Laguna(families.DecoderLm):
    """A decoder counted in tokens, timed like ``DecoderLm``; its own model,
    batch, reference and loss."""

    reference = "benchmark.reference.laguna_moe"

    @staticmethod
    def model(config: dict):
        from horovod_tpu.models import transformer

        if not hasattr(transformer, "RopeParameters"):
            raise NotImplementedError(
                "this program has no attention that differs by layer (no "
                "'sliding_attention' in TransformerConfig.layer_types, no "
                "num_heads_per_layer, no rope_parameters): it cannot run the "
                "laguna_moe family")
        spec, n = config["model"], config["num_hidden_layers"]
        gating = config["gating_type"] if config["gating"] else None
        if gating not in (None, "per_head"):
            raise ValueError(f"gating_type {gating!r}: the family has 'per_head'")
        cfg = transformer.TransformerConfig(
            vocab_size=config["vocab_size"],
            num_layers=n,
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            hidden_size=config["hidden_size"],
            max_seq_len=config["max_position_embeddings"],
            rms_norm_eps=float(config["rms_norm_eps"]),
            tie_word_embeddings=config["tie_word_embeddings"],
            layer_types=tuple(config["layer_types"][:n]),
            sliding_window=config["sliding_window"],
            num_heads_per_layer=tuple(config["num_attention_heads_per_layer"][:n]),
            rope_parameters=rope_parameters(config),
            attn_head_gate=gating == "per_head",
            intermediate_size=config["intermediate_size"],
            first_dense_layers=leading_dense_layers(config),
            num_shared_experts=(config["shared_expert_intermediate_size"]
                                // config["moe_intermediate_size"]),
            num_experts=config["router_experts"],
            num_experts_per_tok=config["num_experts_per_tok"],
            moe_intermediate_size=config["moe_intermediate_size"],
            held_experts=(config["held_experts_first"], config["num_experts"]),
            router_scoring=config["router_scoring"],
            routed_scaling_factor=float(config["moe_routed_scaling_factor"]),
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
