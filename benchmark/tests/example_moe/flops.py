"""The worked example's yardstick functions: required operations a token, and
a kernel's required bytes a step.  A configuration names the first under
``flops.function``; a metric's file names the second as its ``flops_function``
with ``"peak": "hbm_bytes_per_s"``, and the harness's ``roofline`` reader then
gives a bandwidth-bound kernel's share of its roofline.
"""

from __future__ import annotations


def active_matrix_params(config: dict) -> int:
    """Parameters a token's matrix multiplications touch: per layer q, k, v,
    o, the router, and the two matrices of each of the ``num_experts_per_tok``
    experts it is routed to (not of all ``num_experts``: the rest of the stack
    is not required work, whatever a program computes); plus the head."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    width = config["num_attention_heads"] * config["head_dim"]
    layer = (3 * d * width + width * d + d * config["num_experts"]
             + config["num_experts_per_tok"] * 2 * d * f)
    return config["num_hidden_layers"] * layer + d * config["vocab_size"]


def train_flops_per_token(config: dict, traffic: dict) -> float:
    """6 x active matrix parameters, plus causal attention at half:
    6 x layers x seq x heads x head_dim."""
    width = config["num_attention_heads"] * config["head_dim"]
    attention = 6.0 * config["num_hidden_layers"] * traffic["seq_len"] * width
    return 6.0 * active_matrix_params(config) + attention


def expert_weight_bytes_per_step(config: dict, traffic: dict, rows: int) -> float:
    """Bytes the expert matrices of a step have to move at the least: every
    expert's two matrices read once in the forward pass and once in the
    backward pass, and their gradient written once, in float32 (3 x 4 B a
    parameter).  It does not grow with ``rows``: that is what makes a routed
    feed-forward bandwidth-bound at small batches."""
    stack = 2 * config["num_experts"] * config["hidden_size"] * config["moe_intermediate_size"]
    return 3.0 * 4.0 * config["num_hidden_layers"] * stack
