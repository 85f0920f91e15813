"""Required operations of the ``kimi_moe`` family, from shapes alone (the
conventions of ``flops.py``: a multiply-accumulate is two operations, a
training step 3 x forward, recomputation and elementwise work not counted, a
causal mask at half).

A token meets, in every layer, latent attention's five matrices: ``q`` (hidden
x heads x (nope + rope)), ``kv_a`` (hidden x (rank + rope)), ``kv_b`` (rank x
heads x (nope + v)), ``o`` (heads x v x hidden); in a leading dense layer the
three SwiGLU matrices of ``intermediate_size``; in a routed layer the router's
``hidden x router_experts``, the shared experts' three matrices of
``n_shared_experts x moe_intermediate_size``, and the routed experts it is
sent to that this chip holds: the expected number, ``num_experts_per_tok x held
/ router_experts`` (0.75 at 6 x 8 / 64), each three matrices of ``hidden x
moe_intermediate_size``.  Attention: every allowed (query, key) pair costs the
score over ``nope + rope`` and the value product over ``v``, 2 x heads x (nope +
rope + v) forward, the same count whichever layout of the kernels computes it.
"""

from __future__ import annotations


def mla_matrix_params(config: dict) -> int:
    d, h = config["hidden_size"], config["num_attention_heads"]
    nope, rope, v = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"])
    rank = config["kv_lora_rank"]
    return d * h * (nope + rope) + d * (rank + rope) + rank * h * (nope + v) + h * v * d


def _expert_matrices(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def _expert_assignments_per_token(config: dict) -> float:
    """Expected (token, held expert) assignments a token a routed layer."""
    return (config["num_experts_per_tok"] * config["n_routed_experts"]
            / config["router_experts"])


def _routed_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def dense_layer_matrix_params(config: dict) -> int:
    return mla_matrix_params(config) + 3 * config["hidden_size"] * config["intermediate_size"]


def routed_layer_matrix_params(config: dict) -> float:
    """Matrix parameters a token meets in a routed layer, the routed experts
    at the expected assignments."""
    return (mla_matrix_params(config)
            + config["hidden_size"] * config["router_experts"]
            + config["n_shared_experts"] * _expert_matrices(config)
            + _expert_assignments_per_token(config) * _expert_matrices(config))


def _attention_per_pair(config: dict) -> float:
    """Forward and backward of one (query, key) pair, every head."""
    width = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
             + config["v_head_dim"])
    return 3.0 * 2.0 * width * config["num_attention_heads"]


def train_flops_per_token(config: dict, traffic: dict) -> float:
    """Required operations a token of a training step."""
    dense, routed = config["first_k_dense_replace"], _routed_layers(config)
    matrices = (dense * dense_layer_matrix_params(config)
                + routed * routed_layer_matrix_params(config)
                + config["hidden_size"] * config["vocab_size"])
    pairs_per_token = traffic["seq_len"] / 2.0           # causal: half
    return (6.0 * matrices + config["num_hidden_layers"]
            * _attention_per_pair(config) * pairs_per_token)


def mla_attention_train_flops_per_step(config: dict, traffic: dict, rows: int) -> float:
    """What the three flash kernels of a step over ``rows`` sequences are
    required to do: 3 x 2 x (192 + 128) x heads x S^2 / 2 a layer a sequence."""
    s = traffic["seq_len"]
    return config["num_hidden_layers"] * _attention_per_pair(config) * s * s / 2.0 * rows


def expert_ffn_train_flops_per_step(config: dict, traffic: dict, rows: int) -> float:
    """What the held routed experts' products of a step are required to do at
    the expected assignments: 6 x three matrices for each of ``rows x S x
    num_experts_per_tok x held / router_experts`` assignments a routed layer."""
    assignments = rows * traffic["seq_len"] * _expert_assignments_per_token(config)
    return _routed_layers(config) * 6.0 * _expert_matrices(config) * assignments
