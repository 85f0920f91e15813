"""Required operations of the ``qwen3next_moe`` family, from shapes alone (the
conventions of ``flops.py``: a multiply-accumulate is two operations, a
training step 3 x forward, recomputation and elementwise work not counted, a
causal mask at half).

A token meets, in every layer, the router's ``hidden x router_experts``, the
shared expert's three matrices of ``shared_expert_intermediate_size`` and its
gate's ``hidden x 1``, and the routed experts it is sent to that this chip
holds: the expected number, ``num_experts_per_tok x held / router_experts``
(0.625 at 10 x 32 / 512), each three matrices of ``hidden x
moe_intermediate_size``.

In a linear layer (Gated DeltaNet): ``in_proj_qkvz`` (hidden x (2 Hk dk + 2 Hv
dv)), ``in_proj_ba`` (hidden x 2 Hv), the depthwise convolution's ``taps``
multiply-adds a channel of q, k, v, ``out_proj`` (Hv dv x hidden), and the gated
delta rule in its chunked form at chunk ``C`` (``DELTA_CHUNK``), which is what a
training pass over a long sequence requires of any implementation: a value
head a token, forward, ``2 C (3 dk + 2 dv)`` for the chunk-local products (``k
k^T``, ``q k^T``, ``T`` times the keys, ``T`` times the values, the masked ``q
k^T`` times the new values) and ``3 x 2 dk dv`` for the carry (the keys' part
of the state read, the queries' part read, the state updated).  The inverse of
the unit triangular matrix itself is not counted (a solve, not a product: its
least cost is a third of one of the products above), nor the decays.

In a full layer: ``q`` (hidden x heads x 2 head_dim: a query and a gate a
head), ``k``, ``v`` (hidden x kv heads x head_dim), ``o``, and for every allowed
(query, key) pair the score and the value product, 2 x heads x (head_dim +
head_dim) forward.
"""

from __future__ import annotations

# tokens a chunk of the delta rule's chunked form, as the configuration's file
# and ISSUE 35 state it for this count
DELTA_CHUNK = 64


def layer_kinds(config: dict) -> tuple:
    """(linear layers, full layers) of the configuration's depth."""
    interval = config["full_attention_interval"]
    full = sum((i + 1) % interval == 0 for i in range(config["num_hidden_layers"]))
    return config["num_hidden_layers"] - full, full


def _expert_matrices(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def _expert_assignments_per_token(config: dict) -> float:
    """Expected (token, held expert) assignments a token a layer."""
    return (config["num_experts_per_tok"] * config["num_experts"]
            / config["router_experts"])


def feed_forward_matrix_params(config: dict) -> float:
    d = config["hidden_size"]
    return (d * config["router_experts"]
            + 3 * d * config["shared_expert_intermediate_size"] + d
            + _expert_assignments_per_token(config) * _expert_matrices(config))


def linear_mixer_matrix_params(config: dict) -> int:
    d = config["hidden_size"]
    key_dim = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    value_dim = config["linear_num_value_heads"] * config["linear_value_head_dim"]
    return (d * (2 * key_dim + 2 * value_dim) + d * 2 * config["linear_num_value_heads"]
            + config["linear_conv_kernel_dim"] * (2 * key_dim + value_dim)
            + value_dim * d)


def full_mixer_matrix_params(config: dict) -> int:
    d, hd = config["hidden_size"], config["head_dim"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return d * h * 2 * hd + 2 * d * kv * hd + h * hd * d


def delta_rule_flops_per_token(config: dict) -> float:
    """Forward and backward of the gated delta rule, one linear layer, a token."""
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    forward = 2.0 * DELTA_CHUNK * (3 * dk + 2 * dv) + 3 * 2.0 * dk * dv
    return 3.0 * forward * config["linear_num_value_heads"]


def _attention_per_pair(config: dict) -> float:
    """Forward and backward of one (query, key) pair, every head."""
    return 3.0 * 2.0 * 2 * config["head_dim"] * config["num_attention_heads"]


def train_flops_per_token(config: dict, traffic: dict) -> float:
    """Required operations a token of a training step."""
    linear, full = layer_kinds(config)
    matrices = (linear * linear_mixer_matrix_params(config)
                + full * full_mixer_matrix_params(config)
                + (linear + full) * feed_forward_matrix_params(config)
                + config["hidden_size"] * config["vocab_size"])
    pairs_per_token = traffic["seq_len"] / 2.0           # causal: half
    return (6.0 * matrices + linear * delta_rule_flops_per_token(config)
            + full * _attention_per_pair(config) * pairs_per_token)


def gated_delta_train_flops_per_step(config: dict, traffic: dict, rows: int) -> float:
    """What the gated delta rule of a step over ``rows`` sequences is required
    to do, every linear layer, whatever implements it."""
    linear, _ = layer_kinds(config)
    return linear * delta_rule_flops_per_token(config) * rows * traffic["seq_len"]


def attention_train_flops_per_step(config: dict, traffic: dict, rows: int) -> float:
    """What the three flash kernels of a step over ``rows`` sequences are
    required to do: 3 x 2 x (256 + 256) x heads x S^2 / 2 a full layer."""
    _, full = layer_kinds(config)
    s = traffic["seq_len"]
    return full * _attention_per_pair(config) * s * s / 2.0 * rows


def expert_ffn_train_flops_per_step(config: dict, traffic: dict, rows: int) -> float:
    """What the held routed experts' products of a step are required to do at
    the expected assignments: 6 x three matrices for each of ``rows x S x
    num_experts_per_tok x held / router_experts`` assignments a layer."""
    assignments = rows * traffic["seq_len"] * _expert_assignments_per_token(config)
    return config["num_hidden_layers"] * 6.0 * _expert_matrices(config) * assignments
