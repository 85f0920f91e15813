"""The ``nemotronh`` family: a causal decoder whose every layer is ONE sublayer
under one norm, ``x <- x + f(RMSNorm(x))``, with ``f`` a Mamba-2 mixer (``M`` of
``hybrid_override_pattern``), a router over squared-ReLU experts that work in a
latent narrower than the stream beside one shared expert (``E``), softmax
attention without rotary positions (``*``) or the dense feed-forward (``-``)
(NVIDIA-Nemotron-3-Super-120B-A12B's ``nemotron_h``).  A configuration takes it
with ``"family": "benchmark.families_nemotronh:NemotronH"``; the contract is in
``families.py``.

The batch.  A row is ``seq_len + 1`` ids uniform over the vocabulary slice from
the key; the inputs are the first ``seq_len``, the labels the last ``seq_len``
(the next token of every position).

The loss is the program's (``transformer.next_token_loss``: the mean
cross-entropy in float32 plus the router's auxiliary term), handed to the step
through ``step_options``.

A program without a Mamba-2 mixer cannot run the family: that is told from
its source before anything of it is imported or a device is touched, so a run
of such a program ends at ``harness.load_cell``.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from benchmark import families

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
_SUBLAYERS = {"M": "mamba", "E": "moe", "*": "attention", "-": "mlp"}
_PROGRAM = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "horovod_tpu", "models", "transformer.py")


def _program_has_mamba() -> bool:
    try:
        with open(_PROGRAM, encoding="utf-8") as f:
            return "class Mamba2(" in f.read()
    except OSError:
        return False


if not _program_has_mamba():
    raise NotImplementedError(
        "this program has no state-space mixer "
        "(horovod_tpu.models.transformer.Mamba2): it cannot run the nemotronh family")


def pattern(config: dict) -> str:
    """The letters of the configuration's layers: the slice ``hybrid_override_layers``
    of the published ``hybrid_override_pattern`` (all of it without the key)."""
    lo, hi = config.get("hybrid_override_layers", (0, None))
    return config["hybrid_override_pattern"][lo:hi]


def sublayers(config: dict) -> tuple:
    """The program's name of each layer's one sublayer, from the pattern's
    letters."""
    letters = pattern(config)
    if len(letters) != config["num_hidden_layers"]:
        raise ValueError(
            f"hybrid_override_pattern's slice {letters!r} names {len(letters)} layers, "
            f"num_hidden_layers is {config['num_hidden_layers']}")
    return tuple(_SUBLAYERS[c] for c in letters)


class NemotronH(families.DecoderLm):
    """A decoder counted in tokens, timed like ``DecoderLm``; its own model,
    batch, reference and loss."""

    reference = "benchmark.reference.nemotronh"

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
            intermediate_size=config["intermediate_size"],
            max_seq_len=config["max_position_embeddings"],
            rms_norm_eps=float(config["norm_eps"]),
            tie_word_embeddings=config["tie_word_embeddings"],
            # assumed.rope: the family's attention has no positions
            partial_rotary_factor=0.0,
            sublayers=sublayers(config),
            mamba_num_heads=config["mamba_num_heads"],
            mamba_head_dim=config["mamba_head_dim"],
            ssm_state_size=config["ssm_state_size"],
            n_groups=config["n_groups"],
            conv_kernel=config["conv_kernel"],
            chunk_size=config["chunk_size"],
            mlp_hidden_act=config["mlp_hidden_act"],
            num_experts=config["router_experts"],
            num_experts_per_tok=config["num_experts_per_tok"],
            moe_intermediate_size=config["moe_intermediate_size"],
            moe_latent_size=config["moe_latent_size"],
            moe_shared_expert_intermediate_size=config["moe_shared_expert_intermediate_size"],
            held_experts=(config["held_experts_first"], config["n_routed_experts"]),
            router_scoring="sigmoid",
            routed_scaling_factor=float(config["routed_scaling_factor"]),
            router_selection_bias=True,
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
