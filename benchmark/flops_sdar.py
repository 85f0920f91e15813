"""Required operations of the ``sdar_moe`` family, from shapes alone (the
conventions of ``flops.py``: a multiply-accumulate is two operations, a
training step 3 x forward, recomputation and elementwise work not counted).

A data token is TWO rows through the layers (the noisy copy and the clean
one) and one row through the head (the noisy half only).  Of the layers'
matrices a row meets: q, k, v, o; the router's ``hidden x router_experts``;
and the experts it is routed to that this chip holds: the expected number,
``num_experts_per_tok x held / router_experts`` (one a row at 8 x 16 / 128),
each three matrices of ``hidden x moe_intermediate_size``.  The stack of held
experts is not counted, only the assignments.

Attention is counted over the pairs the block-diffusion mask allows and no
others: with ``b(i) = i // B``, noisy -> noisy iff ``b(j) = b(i)``; noisy ->
clean iff ``b(j) < b(i)``; clean -> clean iff ``b(j) <= b(i)``; clean ->
noisy never: ``L^2 + L B`` pairs a row when ``B`` divides ``L``
(``allowed_pairs``).  A pair costs ``QK^T`` and ``PV``, each 2 x heads x
head_dim forward: 12 x heads x head_dim a layer with the backward.
"""

from __future__ import annotations


def allowed_pairs(length: int, block: int) -> int:
    """(query, key) pairs the block-diffusion mask allows in one row of
    ``length`` data tokens: block by block, the noisy queries see their own
    block's noisy keys and the clean keys before it, the clean queries the
    clean keys up to and with their own block."""
    pairs = 0
    for start in range(0, length, block):
        size = min(block, length - start)
        pairs += size * size             # noisy -> noisy
        pairs += size * start            # noisy -> clean
        pairs += size * (start + size)   # clean -> clean
    return pairs


def _attention_per_pair(config: dict) -> float:
    return 12.0 * config["num_attention_heads"] * config["head_dim"]


def _expert_assignments_per_row(config: dict) -> float:
    """Expected (row, held expert) assignments a row of the layers' input."""
    return (config["num_experts_per_tok"] * config["num_experts"]
            / config["router_experts"])


def _expert_matrices(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def train_flops_per_token(config: dict, traffic: dict) -> float:
    """Required operations a DATA token of a training step."""
    d, hd = config["hidden_size"], config["head_dim"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    attention = 2 * d * h * hd + 2 * d * kv * hd
    router = d * config["router_experts"]
    experts = _expert_assignments_per_row(config) * _expert_matrices(config)
    layers = 2 * config["num_hidden_layers"] * 6.0 * (attention + router + experts)
    head = 6.0 * d * config["vocab_size"]
    length = traffic["seq_len"]
    pairs = allowed_pairs(length, traffic["block_length"]) / length
    return (layers + head
            + config["num_hidden_layers"] * _attention_per_pair(config) * pairs)


def bd_attention_train_flops_per_step(config: dict, traffic: dict, rows: int) -> float:
    """What the three flash kernels of a step over ``rows`` rows are required
    to do: 12 x heads x head_dim for every allowed pair, every layer."""
    pairs = allowed_pairs(traffic["seq_len"], traffic["block_length"])
    return config["num_hidden_layers"] * _attention_per_pair(config) * pairs * rows


def expert_ffn_train_flops_per_step(config: dict, traffic: dict, rows: int) -> float:
    """What the held experts' products of a step are required to do at the
    expected assignments: 6 x three matrices for each of ``rows x 2 L x
    num_experts_per_tok x held / router_experts`` assignments a layer."""
    assignments = rows * 2 * traffic["seq_len"] * _expert_assignments_per_row(config)
    return config["num_hidden_layers"] * 6.0 * _expert_matrices(config) * assignments
