"""The ``mellum2_moe`` family: a causal decoder whose layers are sliding-window
or full attention by ``layer_types`` (equal heads in both kinds, plain RoPE in
the sliding layers and YaRN on the whole head in the full ones, by
``rope_parameters``), every layer's feed-forward a softmax router over SwiGLU
experts (JetBrains' Mellum 2), trained on rows PACKED of several documents.  A
configuration takes it with ``"family": "benchmark.families_mellum2:Mellum2"``;
the contract is in ``families.py``.

The configuration's file keeps the published per-layer lists whole; a
configuration cut in depth holds their first ``num_hidden_layers`` entries.

The batch.  A row is ``seq_len + 1`` ids uniform over the vocabulary slice from
the key, packed of the traffic's ``documents`` (their lengths, in order; they
fill the row).  The inputs are ONE integer array ``(rows, 2, seq_len)``: ``[:,
0]`` the first ``seq_len`` ids and ``[:, 1]`` each position's document id (0,
1, ... along the row): the form of ``(ids, documents)`` that
``Transformer.__call__`` takes as one array, since the harness slices its
inputs by row (``inputs[:1]``) and a pair has no rows.  The labels are
``(targets, weights)``: the next token of every position, and weight 0 at each
document's last position, whose next token is another document's (1 elsewhere).
The boundaries are data of the step: another layout of the same shapes runs
through the same compiled program.

The loss is the program's (``transformer.next_token_loss`` with weights: the
mean cross-entropy in float32 over the weighted positions plus the router's
auxiliary term), handed to the step through ``step_options``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import families
from benchmark.families_laguna import rope_parameters

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def document_ids(traffic: dict) -> np.ndarray:
    """(seq_len,) int32: 0, 1, ... for the positions of the traffic's documents."""
    lengths = np.asarray(traffic["documents"], np.int64)
    if lengths.min() < 1 or lengths.sum() != traffic["seq_len"]:
        raise ValueError(
            f"documents {lengths.tolist()} do not fill a row of {traffic['seq_len']}")
    return np.repeat(np.arange(len(lengths), dtype=np.int32), lengths)


class Mellum2(families.DecoderLm):
    """A decoder counted in tokens, timed like ``DecoderLm``; its own model,
    batch, reference and loss."""

    reference = "benchmark.reference.mellum2_moe"

    @staticmethod
    def model(config: dict):
        from horovod_tpu.models import transformer

        if not hasattr(transformer, "document_positions"):
            raise NotImplementedError(
                "this program takes no document ids with its tokens (no document "
                "mask in its attention, no positions that restart a document): it "
                "cannot run the mellum2_moe family's packed rows")
        spec, n = config["model"], config["num_hidden_layers"]
        if any(kind != "sparse" for kind in config["mlp_layer_types"][:n]):
            raise ValueError("mlp_layer_types: the family's layers are all 'sparse'")
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
            rope_parameters=rope_parameters(config),
            num_experts=config["router_experts"],
            num_experts_per_tok=config["num_experts_per_tok"],
            moe_intermediate_size=config["moe_intermediate_size"],
            held_experts=(config["held_experts_first"], config["num_experts"]),
            router_scoring=config["router_scoring"],
            dtype=_DTYPES[config["compute_dtype"]], **spec["kwargs"])
        return getattr(transformer, spec["name"])(cfg)

    @staticmethod
    def batch(key, config: dict, traffic: dict, rows: int):
        """``([ids[:, :-1] | document ids], (ids[:, 1:], weights))``; every
        row's ids differ, every row's layout is the traffic's."""
        s = traffic["seq_len"]
        ids = jax.random.randint(key, (rows, s + 1), 0, config["vocab_size"])
        documents = document_ids(traffic)
        last = np.append(documents[1:] != documents[:-1], True)  # a document's last position
        weights = jnp.broadcast_to(jnp.asarray(~last, jnp.float32), (rows, s))
        inputs = jnp.stack(
            [ids[:, :-1], jnp.broadcast_to(jnp.asarray(documents), (rows, s))], axis=1)
        return inputs, (ids[:, 1:], weights)

    @staticmethod
    def step_options(config: dict, traffic: dict) -> dict:
        """What JSON cannot carry: the loss, a function."""
        from horovod_tpu.models import transformer

        return {"loss_fn": functools.partial(
            transformer.next_token_loss, aux_coef=config["router_aux_loss_coef"])}
