"""Required operations of the ``mellum2_moe`` family, from shapes and the
traffic's document lengths alone (the conventions of ``flops.py``: a
multiply-accumulate is two operations, a training step 3 x forward,
recomputation and elementwise work not counted).

A layer of ``H`` query heads over ``Hkv`` key/value heads of ``hd``: ``q`` and
``o`` (hidden x H x hd each), ``k`` and ``v`` (hidden x Hkv x hd each), and for
every (query, key) pair its mask allows the score and the value product, ``2 x
2 x hd`` a head forward.  The pairs are the mask's own, the same whatever
kernel computes them and whatever tiles it visits.  A row is packed of the
traffic's ``documents`` (their lengths, in order): a query sees the keys of its
own document alone, so a full layer allows ``L (L + 1) / 2`` pairs a document
of ``L`` tokens and a sliding layer of window ``W`` the same up to ``W`` and ``W
(W + 1) / 2 + (L - W) W`` beyond (a query sees itself and the ``W - 1`` before
it): 6,644,589 and 4,740,268 a row of 8,192 under the cell's eleven documents
and 1,024.

Every layer's feed-forward is routed: the router's hidden x ``router_experts``
and the routed experts a token is sent to that this chip holds: the expected
number, ``num_experts_per_tok x held / router_experts`` (2 at 8 x 16 / 64),
each three matrices of hidden x ``moe_intermediate_size``.
"""

from __future__ import annotations


def layers(config: dict) -> list:
    """The attention kind of each layer held."""
    return list(config["layer_types"][:config["num_hidden_layers"]])


def documents(traffic: dict) -> list:
    """The lengths of a row's documents; they fill the row."""
    lengths = [int(n) for n in traffic["documents"]]
    if min(lengths) < 1 or sum(lengths) != traffic["seq_len"]:
        raise ValueError(f"documents {lengths} do not fill a row of {traffic['seq_len']}")
    return lengths


def mask_pairs(kind: str, config: dict, traffic: dict) -> int:
    """(query, key) pairs a row that a layer's mask allows."""
    if kind == "full_attention":
        return sum(n * (n + 1) // 2 for n in documents(traffic))
    w = config["sliding_window"]
    return sum(n * (n + 1) // 2 if n <= w else w * (w + 1) // 2 + (n - w) * w
               for n in documents(traffic))


def attention_matrix_params(config: dict) -> int:
    d, hd = config["hidden_size"], config["head_dim"]
    return 2 * d * hd * (config["num_attention_heads"] + config["num_key_value_heads"])


def _attention_per_pair(config: dict) -> float:
    """Forward and backward of one (query, key) pair, every head: 12 x hd."""
    return 3.0 * 2.0 * 2 * config["head_dim"] * config["num_attention_heads"]


def _expert_matrices(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def _expert_assignments_per_token(config: dict) -> float:
    """Expected (token, held expert) assignments a token a layer."""
    return (config["num_experts_per_tok"] * config["num_experts"]
            / config["router_experts"])


def train_flops_per_token(config: dict, traffic: dict) -> float:
    """Required operations a token of a training step."""
    s = traffic["seq_len"]
    matrices = (attention_matrix_params(config)
                + config["hidden_size"] * config["router_experts"]
                + _expert_assignments_per_token(config) * _expert_matrices(config))
    return (sum(6.0 * matrices + _attention_per_pair(config)
                * mask_pairs(kind, config, traffic) / s for kind in layers(config))
            + 6.0 * config["hidden_size"] * config["vocab_size"])


def _attention_train_flops_per_step(config, traffic, rows, kind) -> float:
    return (rows * layers(config).count(kind) * _attention_per_pair(config)
            * mask_pairs(kind, config, traffic))


def window_attention_train_flops_per_step(config: dict, traffic: dict, rows: int) -> float:
    """What the flash kernels of the sliding layers of a step over ``rows`` rows
    are required to do: 12 x hd x heads x the pairs of the window and the
    documents together."""
    return _attention_train_flops_per_step(config, traffic, rows, "sliding_attention")


def full_attention_train_flops_per_step(config: dict, traffic: dict, rows: int) -> float:
    """The same of the full layers: 12 x hd x heads x sum L (L + 1) / 2 a layer."""
    return _attention_train_flops_per_step(config, traffic, rows, "full_attention")


def expert_ffn_train_flops_per_step(config: dict, traffic: dict, rows: int) -> float:
    """What the held routed experts' products of a step are required to do at
    the expected assignments: 6 x three matrices for each of ``rows x S x
    num_experts_per_tok x held / router_experts`` assignments a layer."""
    assignments = rows * traffic["seq_len"] * _expert_assignments_per_token(config)
    return len(layers(config)) * 6.0 * _expert_matrices(config) * assignments
