"""Required operations of the ``laguna_moe`` family, from shapes alone (the
conventions of ``flops.py``: a multiply-accumulate is two operations, a
training step 3 x forward, recomputation and elementwise work not counted).

Attention differs by layer, so everything is counted a layer, over the first
``num_hidden_layers`` entries of the configuration's per-layer lists.  A layer
of ``H`` query heads over ``Hkv`` key/value heads of ``hd``: ``q`` and ``o``
(hidden x H x hd each), ``k`` and ``v`` (hidden x Hkv x hd each), the gate a
head (hidden x H), and for every (query, key) pair its mask allows the score
and the value product, ``2 x 2 x hd`` a head forward.  The pairs are the mask's
own, the same whatever kernel computes them: a full layer ``S (S + 1) / 2`` a
sequence; a sliding layer of window ``W`` ``W (W + 1) / 2 + (S - W) W`` (a
query sees itself and the ``W - 1`` before it): 4,063,488 at 8,192 under 512.

A ``dense`` layer's feed-forward is three matrices of hidden x
``intermediate_size``.  A ``sparse`` layer's: the router's hidden x
``router_experts``, the shared expert's three matrices of
``shared_expert_intermediate_size``, and the routed experts a token is sent to
that this chip holds: the expected number, ``num_experts_per_tok x held /
router_experts`` (0.5 at 8 x 16 / 256), each three matrices of hidden x
``moe_intermediate_size``.
"""

from __future__ import annotations


def layers(config: dict) -> list:
    """(attention kind, query heads, feed-forward kind) of each layer held."""
    n = config["num_hidden_layers"]
    return list(zip(config["layer_types"][:n], config["num_attention_heads_per_layer"][:n],
                    config["mlp_layer_types"][:n]))


def mask_pairs(kind: str, config: dict, seq_len: int) -> int:
    """(query, key) pairs a sequence that a layer's mask allows."""
    if kind == "full_attention":
        return seq_len * (seq_len + 1) // 2
    w = min(config["sliding_window"], seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def attention_matrix_params(config: dict, heads: int) -> int:
    d, hd, kv = config["hidden_size"], config["head_dim"], config["num_key_value_heads"]
    return 2 * d * heads * hd + 2 * d * kv * hd + (d * heads if config["gating"] else 0)


def _attention_per_pair(config: dict, heads: int) -> float:
    """Forward and backward of one (query, key) pair, every head: 12 x hd."""
    return 3.0 * 2.0 * 2 * config["head_dim"] * heads


def _expert_matrices(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def _expert_assignments_per_token(config: dict) -> float:
    """Expected (token, held expert) assignments a token a sparse layer."""
    return (config["num_experts_per_tok"] * config["num_experts"]
            / config["router_experts"])


def feed_forward_matrix_params(config: dict, kind: str) -> float:
    d = config["hidden_size"]
    if kind == "dense":
        return 3 * d * config["intermediate_size"]
    return (d * config["router_experts"]
            + 3 * d * config["shared_expert_intermediate_size"]
            + _expert_assignments_per_token(config) * _expert_matrices(config))


def layer_flops_per_token(config: dict, traffic: dict, index: int) -> float:
    """Required operations a token of layer ``index``, forward and backward."""
    kind, heads, feed = layers(config)[index]
    s = traffic["seq_len"]
    return (6.0 * (attention_matrix_params(config, heads)
                   + feed_forward_matrix_params(config, feed))
            + _attention_per_pair(config, heads) * mask_pairs(kind, config, s) / s)


def train_flops_per_token(config: dict, traffic: dict) -> float:
    """Required operations a token of a training step."""
    return (sum(layer_flops_per_token(config, traffic, i)
                for i in range(config["num_hidden_layers"]))
            + 6.0 * config["hidden_size"] * config["vocab_size"])


def _attention_train_flops_per_step(config, traffic, rows, kind) -> float:
    s = traffic["seq_len"]
    return rows * sum(_attention_per_pair(config, heads) * mask_pairs(kind, config, s)
                      for k, heads, _ in layers(config) if k == kind)


def window_attention_train_flops_per_step(config: dict, traffic: dict, rows: int) -> float:
    """What the flash kernels of the sliding layers of a step over ``rows``
    sequences are required to do: 12 x hd x heads x the window mask's pairs."""
    return _attention_train_flops_per_step(config, traffic, rows, "sliding_attention")


def full_attention_train_flops_per_step(config: dict, traffic: dict, rows: int) -> float:
    """The same of the full layers: 12 x hd x heads x S (S + 1) / 2 a layer."""
    return _attention_train_flops_per_step(config, traffic, rows, "full_attention")


def expert_ffn_train_flops_per_step(config: dict, traffic: dict, rows: int) -> float:
    """What the held routed experts' products of a step are required to do at
    the expected assignments: 6 x three matrices for each of ``rows x S x
    num_experts_per_tok x held / router_experts`` assignments a sparse layer."""
    sparse = sum(feed != "dense" for _, _, feed in layers(config))
    assignments = rows * traffic["seq_len"] * _expert_assignments_per_token(config)
    return sparse * 6.0 * _expert_matrices(config) * assignments
