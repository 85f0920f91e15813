"""Required operations of the ``nemotronh`` family, from shapes alone (the
conventions of ``flops.py``: a multiply-accumulate is two operations, a
training step 3 x forward, recomputation and elementwise work not counted, a
causal mask at half).

A layer is ONE sublayer (``hybrid_override_pattern``).  A token meets,

in an ``M`` layer (Mamba-2): ``in_proj`` (hidden x (2 H P + 2 G N + H)), the
depthwise convolution's ``conv_kernel`` multiply-adds a channel of ``xBC``,
``out_proj`` (H P x hidden), and the state-space scan in its chunked form at
chunk ``C`` (``chunk_size``), which is what a training pass over a long
sequence requires of any implementation: a head a token, forward, ``2 C P``
for the masked ``C B^T`` times the values, ``2 N P`` for the state read and ``2
N P`` for the state updated; and a GROUP a token ``2 C N`` for ``C B^T``, which
the ``H / G`` heads of a group share (B and C counted once a group).  The
decays and the gates are not counted;

in an ``E`` layer: the router's ``hidden x router_experts``, the two latent
projections ``2 x hidden x moe_latent_size``, the shared expert's two matrices
of ``hidden x moe_shared_expert_intermediate_size``, and the routed experts it
is sent to that this chip holds: the expected number, ``num_experts_per_tok x
held / router_experts`` (0.34375 at 22 x 8 / 512), each two matrices of
``moe_latent_size x moe_intermediate_size``;

in a ``*`` layer: ``q`` (hidden x heads x head_dim), ``k``, ``v`` (hidden x kv
heads x head_dim), ``o``, and for every allowed (query, key) pair the score and
the value product, 2 x heads x (head_dim + head_dim) forward;

in a ``-`` layer: the dense feed-forward's two matrices of ``hidden x
intermediate_size``.
"""

from __future__ import annotations


def pattern(config: dict) -> str:
    """The letters of the configuration's layers: the slice ``hybrid_override_layers``
    of the published ``hybrid_override_pattern`` (all of it without the key)."""
    lo, hi = config.get("hybrid_override_layers", (0, None))
    return config["hybrid_override_pattern"][lo:hi]


def layer_counts(config: dict) -> dict:
    """How many layers of each letter the configuration's pattern has."""
    letters = pattern(config)
    return {letter: letters.count(letter) for letter in "ME*-"}


def _expert_matrices(config: dict) -> int:
    return 2 * config["moe_latent_size"] * config["moe_intermediate_size"]


def _expert_assignments_per_token(config: dict) -> float:
    """Expected (token, held expert) assignments a token a layer."""
    return (config["num_experts_per_tok"] * config["n_routed_experts"]
            / config["router_experts"])


def mamba_matrix_params(config: dict) -> int:
    d = config["hidden_size"]
    inner = config["mamba_num_heads"] * config["mamba_head_dim"]
    mixed = inner + 2 * config["n_groups"] * config["ssm_state_size"]
    return (d * (inner + mixed + config["mamba_num_heads"])
            + config["conv_kernel"] * mixed + inner * d)


def latent_and_router_params(config: dict) -> int:
    d = config["hidden_size"]
    return d * config["router_experts"] + 2 * d * config["moe_latent_size"]


def shared_expert_params(config: dict) -> int:
    return 2 * config["hidden_size"] * config["moe_shared_expert_intermediate_size"]


def attention_matrix_params(config: dict) -> int:
    d, hd = config["hidden_size"], config["head_dim"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def ssd_flops_per_token(config: dict) -> float:
    """Forward and backward of the scan, one Mamba-2 layer, a token."""
    c, p, n = config["chunk_size"], config["mamba_head_dim"], config["ssm_state_size"]
    forward = (config["mamba_num_heads"] * (2.0 * c * p + 2 * 2.0 * n * p)
               + config["n_groups"] * 2.0 * c * n)
    return 3.0 * forward


def _attention_per_pair(config: dict) -> float:
    """Forward and backward of one (query, key) pair, every head."""
    return 3.0 * 2.0 * 2 * config["head_dim"] * config["num_attention_heads"]


def train_flops_per_token(config: dict, traffic: dict) -> float:
    """Required operations a token of a training step."""
    n = layer_counts(config)
    matrices = (
        n["M"] * mamba_matrix_params(config)
        + n["E"] * (latent_and_router_params(config) + shared_expert_params(config)
                    + _expert_assignments_per_token(config) * _expert_matrices(config))
        + n["*"] * attention_matrix_params(config)
        + n["-"] * 2 * config["hidden_size"] * config["intermediate_size"]
        + config["hidden_size"] * config["vocab_size"])
    pairs_per_token = traffic["seq_len"] / 2.0           # causal: half
    return (6.0 * matrices + n["M"] * ssd_flops_per_token(config)
            + n["*"] * _attention_per_pair(config) * pairs_per_token)


def ssd_train_flops_per_step(config: dict, traffic: dict, rows: int) -> float:
    """What the scan of a step over ``rows`` sequences is required to do, every
    Mamba-2 layer, whatever implements it."""
    return (layer_counts(config)["M"] * ssd_flops_per_token(config)
            * rows * traffic["seq_len"])


def expert_ffn_train_flops_per_step(config: dict, traffic: dict, rows: int) -> float:
    """What the held routed experts' products of a step are required to do at
    the expected assignments: 6 x two matrices for each of ``rows x S x
    num_experts_per_tok x held / router_experts`` assignments an ``E`` layer."""
    assignments = rows * traffic["seq_len"] * _expert_assignments_per_token(config)
    return layer_counts(config)["E"] * 6.0 * _expert_matrices(config) * assignments
