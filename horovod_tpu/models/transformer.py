"""GPT-style decoder-only transformer (flax linen), TPU-first.

Reference analog: the BERT-Large SQuAD fine-tune and Llama-7B pretrain
configs tracked in BASELINE.json — the reference trains these data-parallel
via DistributedOptimizer; this model is the framework's flagship for the
same role, designed so sequence parallelism can shard the context:

  * ``attention_impl='dot'`` — plain causal attention (default);
  * ``attention_impl='flash'`` — the pallas VMEM-resident flash kernel
    (ops/flash_attention.py; 2-3x over dense at S=4096 on v5e);
  * ``attention_impl='ring'`` — ring attention over a mesh axis
    (parallel/ring_attention.py): the sequence dimension is sharded and
    KV blocks rotate via ``ppermute``, enabling contexts far beyond one
    chip's HBM.  The reference has no analog (SURVEY.md §5.7) — it only
    ships the alltoall/allgather primitives such schemes build on;
  * ``attention_impl='ring_flash'`` — same ring schedule with each block
    computed by the pallas flash kernels (no (S/n)² logits in HBM even
    within a block).

bfloat16 activations, float32 params; RoPE positions; pre-norm blocks.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .. import trace as _trace
from ..ops import spmd_ops
from ..ops.reduce_ops import Sum


# Named activation-remat policies for the decoder blocks (Chen et al.,
# 2016 sublinear memory; jax.checkpoint / jax.checkpoint_policies).  What
# the backward pass may READ from the forward without recomputing:
#   none          — every intermediate saved (no remat; fastest, most HBM)
#   dots          — MXU (matmul) outputs saved, elementwise/norm/softmax
#                   recomputed (jax.checkpoint_policies.checkpoint_dots)
#   dots_no_batch — only batch-free matmul outputs saved; in a decoder
#                   block every dot carries the batch dim, so this
#                   recomputes the whole block from its input (the
#                   historical `remat=True` policy)
#   full          — save nothing but the block input (jax.checkpoint's
#                   default policy): minimum memory, ~1/3 extra FLOPs
REMAT_POLICIES = {
    "none": None,
    "dots": "checkpoint_dots",
    "dots_no_batch": "checkpoint_dots_with_no_batch_dims",
    "full": None,
}


def _checkpoint_policy(name: str):
    """The jax.checkpoint_policies member for a policy name (None = save
    nothing, i.e. jax.checkpoint's default)."""
    attr = REMAT_POLICIES[name]
    return getattr(jax.checkpoint_policies, attr) if attr else None


def resolve_remat_policies(policy, num_layers: int,
                           default: str = "none"):
    """Normalize a remat-policy selection to one name per block.

    ``policy`` may be None (→ ``default`` everywhere), a single policy
    name applied to every block, or a sequence of ``num_layers`` names
    selecting per block (e.g. remat only the deep half of the stack).
    """
    if policy is None:
        policy = default
    if isinstance(policy, str):
        policies = (policy,) * num_layers
    else:
        policies = tuple(policy)
        if len(policies) != num_layers:
            raise ValueError(
                f"per-block remat policy needs {num_layers} entries, "
                f"got {len(policies)}"
            )
    for p in policies:
        if p not in REMAT_POLICIES:
            raise ValueError(
                f"unknown remat policy {p!r}; expected one of "
                f"{sorted(REMAT_POLICIES)}"
            )
    return policies


@dataclasses.dataclass(frozen=True)
class RopeParameters:
    """RoPE of one kind of layer (``TransformerConfig.rope_parameters``):
    ``theta``, the share of each head that is rotated, and YaRN's numbers
    (arXiv:2309.00071; the keys of the published configs), all five or none:
    ``yarn_inv_freq`` blends the frequencies and cos and sin are multiplied
    by ``attention_factor``."""

    theta: float = 10000.0
    partial_rotary_factor: float = 1.0
    factor: Optional[float] = None
    original_max_position_embeddings: Optional[int] = None
    beta_fast: Optional[float] = None
    beta_slow: Optional[float] = None
    attention_factor: Optional[float] = None

    @property
    def yarn(self) -> Optional[tuple]:
        """YaRN's five numbers, or None for plain RoPE."""
        five = (self.factor, self.original_max_position_embeddings,
                self.beta_fast, self.beta_slow, self.attention_factor)
        return None if all(v is None for v in five) else five

    @property
    def rope_type(self) -> str:
        return "default" if self.yarn is None else "yarn"


ATTENTION_KINDS = ("full_attention", "sliding_attention")
# what a layer of one sublayer may be (``TransformerConfig.sublayers``)
SUBLAYERS = ("mamba", "attention", "moe", "mlp")


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 12
    num_heads: int = 12
    # GQA (Ainslie et al., 2023; the Llama-2-70B/Llama-3 layout): K/V
    # projections produce this many heads, shared by num_heads/num_kv_heads
    # query heads each.  None (default) = MHA.  Every attention_impl
    # (dot, flash, ring, ring_flash) consumes the grouped K/V NATIVELY —
    # the dense paths group their einsums and the pallas kernels share
    # each K/V head across its query-head group in VMEM — so attention
    # K/V bytes/FLOPs, ring comms, the K/V projections and any KV cache
    # all shrink by num_heads/num_kv_heads; nothing is ever repeated.
    num_kv_heads: Optional[int] = None
    head_dim: int = 64
    mlp_ratio: int = 4
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    # 'dot' | 'ring'; 'ring' requires seq_axis_name and running inside
    # shard_map with the sequence sharded over that axis.
    attention_impl: str = "dot"
    seq_axis_name: Optional[str] = None
    # False = bidirectional (encoder / BERT-family) attention; supported
    # by every impl — dot, the pallas flash kernel, and both ring modes
    # (the causal block-skipping simply switches off)
    causal: bool = True
    # Mistral-style sliding-window attention: each token attends the last
    # `window` positions, itself included (q_pos - k_pos < window, the
    # Mistral/HF convention; symmetric reach when causal=False).  Exact on
    # every impl: mask-level on 'dot' and dense 'ring'; on 'flash' and
    # 'ring_flash' out-of-window blocks are SKIPPED in the kernels —
    # compute O(S·window), the real Mistral training path — and a causal
    # window additionally truncates the ring rotation itself
    # (parallel/ring_attention.py ring_window_steps), so out-of-window
    # ring steps cost neither compute nor comms.
    window: Optional[int] = None
    # rematerialize each decoder block in the backward pass: activation
    # memory drops from O(layers) to O(1) blocks at ~1/3 extra FLOPs —
    # the standard TPU memory/compute trade (jax.checkpoint) that lets
    # long-context and large-batch configs fit HBM.  Legacy boolean
    # switch: True ≡ remat_policy="dots_no_batch" (kept for callers
    # predating configurable policies).
    remat: bool = False
    # Configurable activation-remat policy (docs/OPTIM.md policy
    # matrix): None (derive from `remat`), a REMAT_POLICIES name applied
    # to every block, or a tuple of num_layers names selecting PER
    # BLOCK — e.g. ("none",)*6 + ("full",)*6 remats only the deep half.
    remat_policy: Any = None
    # Megatron-style tensor sharding (Shoeybi et al.; docs/SERVING.md
    # sharding section): name of a mesh axis the module is being traced
    # under (shard_map).  When set AND bound, every sublayer runs on its
    # 1/tp slice — q/k/v projections and attention per LOCAL head group
    # (kv heads shard too, so the paged KV pool shards with them), MLP
    # gate/up column-split — and the two row-parallel projections
    # (attention output, MLP down) finish with ONE psum each: the
    # classic 2-psums-per-block TP schedule.  Unbound or None degrades
    # to the unsharded program (identical params, identical math), so
    # the same config serves single- and multi-chip.  num_heads,
    # num_kv_heads and d_model*mlp_ratio must all divide by the axis
    # size (validated at trace).  Inference-first: the serving engine
    # is the consumer; training paths keep using parallel/sharded.py.
    shard_axis: Optional[str] = None
    # Width of the residual stream when it is not num_heads * head_dim
    # (a model whose attention is wider than its residual: 32 heads of
    # 128 on a 2048-wide stream).  None = num_heads * head_dim.
    hidden_size: Optional[int] = None
    # What used to be fixed in code; the defaults are those values.
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    # False = an output matrix of its own (``head/kernel``, logits in
    # float32) instead of the embedding's transpose.
    tie_word_embeddings: bool = True
    # RMSNorm over head_dim with a learned scale on q and on k, before
    # RoPE (the Qwen3 family).
    qk_norm: bool = False
    # Routed feed-forward (parallel/moe.py RoutedExperts): a router over
    # ``num_experts`` experts of width ``moe_intermediate_size`` (SwiGLU, or
    # ``mlp_hidden_act``'s form),
    # ``num_experts_per_tok`` a token, of which this chip holds
    # ``held_experts = (first, count)`` (None: all).  None = the dense
    # MlpBlock.  With it the model returns ``(logits, aux)``: ``aux`` has
    # the router's ``aux_loss`` (mean over layers) for the step's loss,
    # the counters ``expert_assignments`` (sum over layers),
    # ``expert_load_max_over_mean`` (largest over layers),
    # ``dropped_assignments`` (sum; 0), ``expert_chunks`` (sum of the chunks
    # in use: the routed layers' number where none overflows) and
    # ``expert_index`` (layers, T, k).
    num_experts: Optional[int] = None
    num_experts_per_tok: int = 1
    moe_intermediate_size: Optional[int] = None
    held_experts: Optional[Any] = None
    # Block-diffusion training (BD3-LM, arXiv:2503.09573; SDAR): the
    # block length B.  The input is ``[noisy || clean]``, two copies of L
    # positions; positions run ``[0..L) || [0..L)``; attention takes the
    # block-diffusion mask (``block_diffusion_mask``) in place of
    # ``causal``/``window``; the head runs on the noisy half only, so the
    # logits are (B, L, V).  'dot' and 'flash' attention only.
    block_diffusion: Optional[int] = None
    # Latent attention (MLA; DeepSeek-V2, arXiv:2405.04434), named as the
    # published configs name it.  All four set: ``Attention`` builds ``q``
    # (heads of ``qk_nope_head_dim + qk_rope_head_dim``), ``kv_a`` (the
    # stream down to ``kv_lora_rank`` + ONE rotary key of ``qk_rope_head_dim``
    # for all heads), ``kv_a_norm`` (RMSNorm over the latent), ``kv_b`` (the
    # latent up to a head's ``qk_nope_head_dim`` key and ``v_head_dim``
    # value) and ``o``; RoPE on the rotary parts only; scores scaled by
    # ``(qk_nope_head_dim + qk_rope_head_dim) ** -0.5``.  Needs
    # ``hidden_size``; ``head_dim`` and ``num_kv_heads`` play no part.
    # 'dot' and 'flash' only, training only: no window, no block diffusion,
    # no ring, no ``shard_axis`` > 1, no paged serving (no latent cache yet).
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    # The dense feed-forward's width when it is not d_model * mlp_ratio.
    intermediate_size: Optional[int] = None
    # With a routed feed-forward: the first ``first_dense_layers`` layers
    # keep the dense MlpBlock; ``num_shared_experts`` > 0 adds, in every
    # routed layer, one feed-forward (SwiGLU, or ``mlp_hidden_act``'s form) of
    # width num_shared_experts x moe_intermediate_size beside the routed sum,
    # whole on every chip.
    first_dense_layers: int = 0
    num_shared_experts: int = 0
    # The router (parallel/moe.py RoutedExperts): ``router_scoring``
    # 'softmax' | 'sigmoid'; the chosen weights, renormalised, times
    # ``routed_scaling_factor``; ``router_selection_bias``: a bias a
    # expert added to the scores for the SELECTION only (DeepSeek-V3's
    # ``e_score_correction_bias``: no gradient, not the optimizer's: it
    # lives in the ``batch_stats`` collection, which the train steps carry
    # beside the parameters); ``router_seq_aux``: the auxiliary loss in
    # DeepSeek's sequence-wise form instead of Switch's.
    router_scoring: str = "softmax"
    routed_scaling_factor: float = 1.0
    router_selection_bias: bool = False
    router_seq_aux: bool = False
    # Layers of two mixers in one model (the ``qwen3_next`` configs' keys, each
    # under its own name).  ``layer_types``: a tuple a layer of
    # ``"full_attention"`` (``Attention``) or ``"linear_attention"``
    # (``GatedDeltaNet``); None = all full.  The five ``linear_*`` sizes are
    # Gated DeltaNet's: key heads of ``linear_key_head_dim``, value heads (a
    # multiple of the key heads) of ``linear_value_head_dim``, and the taps of
    # its causal depthwise convolution.  A linear layer trains on 'dot' and
    # 'flash' models only: no paged serving (no recurrent-state cache yet), no
    # bound ``shard_axis``, no ring, no block diffusion.
    layer_types: Optional[Any] = None
    linear_num_key_heads: Optional[int] = None
    linear_key_head_dim: Optional[int] = None
    linear_num_value_heads: Optional[int] = None
    linear_value_head_dim: Optional[int] = None
    linear_conv_kernel_dim: int = 4
    # ``Attention``: RoPE on the first ``partial_rotary_factor`` of each head
    # (the rest passes); ``attn_output_gate``: ``q`` projects to a query and a
    # gate a head, and the attention's output is multiplied by the gate's
    # sigmoid before ``o``.
    partial_rotary_factor: float = 1.0
    attn_output_gate: bool = False
    # Every RMSNorm of the residual stream and the q / k norms in the
    # zero-centred form ``x / rms(x) * (1 + w)``, ``w`` initialised 0.
    norm_zero_centered: bool = False
    # ``shared_expert_gate``: the shared experts' output times
    # ``sigmoid(x @ w)``, ``w`` (d_model, 1).
    shared_expert_gate: bool = False
    # Attention that differs by layer (the ``laguna`` configs' keys).
    # ``layer_types`` also takes ``"sliding_attention"``: such a layer sees the
    # last ``sliding_window`` positions, itself included (``sliding_mask``),
    # and a ``"full_attention"`` layer all of them; ``window`` stays the form
    # that windows every layer, and the two together are refused.
    # ``num_heads_per_layer``: a layer's query heads where they differ (each
    # a multiple of ``num_kv_heads``; ``q``, ``o`` and a gate a head are built
    # at the layer's count, ``d_model`` needs ``hidden_size``).
    # ``rope_parameters``: ``{layer type: RopeParameters (or its fields as a
    # dict)}`` for the ``ATTENTION_KINDS``; a type it does not name takes
    # ``rope_theta`` and ``partial_rotary_factor``.  ``attn_head_gate``: the
    # attention's output times ``sigmoid(x @ w)``, ``w`` (d_model, heads): one
    # number a head and token (``attn_output_gate`` is the form with a gate a
    # column, inside ``q``).  'dot' and 'flash' only: no ring, no
    # ``shard_axis``, no latent attention, no block diffusion, no paged
    # serving (one cache allocator for window and full layers is not there).
    sliding_window: Optional[int] = None
    num_heads_per_layer: Optional[Any] = None
    rope_parameters: Optional[Any] = None
    attn_head_gate: bool = False
    # Layers of ONE sublayer (the ``nemotron_h`` configs' keys, each under its
    # own name).  ``sublayers``: a tuple a layer of ``SUBLAYERS``: ``"mamba"``
    # (``Mamba2``), ``"attention"`` (``Attention``), ``"moe"`` (the routed
    # feed-forward) or ``"mlp"`` (the dense one); such a layer is ``x + f(norm(x))``
    # with that one ``f`` (``hybrid_override_pattern``'s ``M``, ``*``, ``E``, ``-``;
    # the family maps the letters).  None = every layer a mixer and a
    # feed-forward, as ``layer_types`` and ``first_dense_layers`` say; the two
    # forms together are refused.  Mamba-2's sizes: ``mamba_num_heads`` heads of
    # ``mamba_head_dim``, a state of ``ssm_state_size`` a head, B and C in
    # ``n_groups`` groups (a divisor of the heads), a causal depthwise
    # convolution of ``conv_kernel`` taps with a bias, and ``chunk_size`` tokens a
    # chunk of the scan (``ops/ssd.py``).  A Mamba layer trains on 'dot' and
    # 'flash' models only: no paged serving (no recurrent-state cache yet), no
    # bound ``shard_axis``, no ring, no block diffusion, no document ids.
    # ``mlp_hidden_act``: ``"silu"`` (SwiGLU: gate, up, down) or ``"relu2"``
    # (``down(relu(up x)^2)``, no gate matrix) for the dense feed-forward, the
    # shared expert and the routed experts alike.  ``moe_latent_size``: the
    # routed experts work in a latent that wide (``RoutedExperts.latent``);
    # ``moe_shared_expert_intermediate_size``: the shared expert's width where it
    # is no multiple of ``moe_intermediate_size`` (in place of
    # ``num_shared_experts``).
    sublayers: Optional[Any] = None
    mamba_num_heads: Optional[int] = None
    mamba_head_dim: Optional[int] = None
    ssm_state_size: Optional[int] = None
    n_groups: int = 1
    conv_kernel: int = 4
    chunk_size: int = 128
    mlp_hidden_act: str = "silu"
    moe_latent_size: Optional[int] = None
    moe_shared_expert_intermediate_size: Optional[int] = None

    def __post_init__(self):
        kv = self.num_kv_heads
        if kv is not None and (kv <= 0 or self.num_heads % kv):
            raise ValueError(
                f"num_heads ({self.num_heads}) must be a multiple of "
                f"num_kv_heads ({kv})"
            )
        if self.remat_policy is not None:
            # normalize early so invalid names fail at config build, and
            # store a hashable tuple (the dataclass is frozen/hashable)
            object.__setattr__(
                self, "remat_policy",
                self.remat_policy if isinstance(self.remat_policy, str)
                else tuple(self.remat_policy),
            )
            resolve_remat_policies(self.remat_policy, self.num_layers)
        if self.held_experts is not None:
            object.__setattr__(
                self, "held_experts", tuple(int(v) for v in self.held_experts))
        if self.num_experts is not None and not self.moe_intermediate_size:
            raise ValueError("num_experts needs moe_intermediate_size")
        if self.block_diffusion is not None:
            if self.block_diffusion < 1:
                raise ValueError(
                    f"block_diffusion is a block length >= 1, got "
                    f"{self.block_diffusion}")
            if self.attention_impl not in ("dot", "flash"):
                raise ValueError(
                    "block_diffusion supports attention_impl 'dot'/'flash', "
                    f"not {self.attention_impl!r}")
            if self.window is not None:
                raise ValueError("block_diffusion takes no window")
        latent = (self.kv_lora_rank, self.qk_nope_head_dim,
                  self.qk_rope_head_dim, self.v_head_dim)
        if any(v is not None for v in latent):
            if not all(latent) or self.hidden_size is None:
                raise ValueError(
                    "latent attention needs kv_lora_rank, qk_nope_head_dim, "
                    f"qk_rope_head_dim, v_head_dim (got {latent}) and "
                    "hidden_size")
            if self.attention_impl not in ("dot", "flash"):
                raise ValueError(
                    "latent attention supports attention_impl 'dot'/'flash', "
                    f"not {self.attention_impl!r} (the ring rotates keys and "
                    "values of one width)")
            if (self.window is not None or self.block_diffusion is not None
                    or self.qk_norm or kv not in (None, self.num_heads)):
                raise ValueError(
                    "latent attention takes no window, no block_diffusion, "
                    "no qk_norm and no grouped key/value heads")
            if self.attn_output_gate or self.partial_rotary_factor != 1.0:
                raise ValueError(
                    "latent attention takes no attn_output_gate and no "
                    "partial_rotary_factor (its rotary part is its own key)")
        if self.num_experts is None and (
                self.first_dense_layers or self.num_shared_experts):
            raise ValueError(
                "first_dense_layers and num_shared_experts need num_experts")
        if self.router_scoring not in ("softmax", "sigmoid"):
            raise ValueError(
                f"router_scoring is 'softmax' or 'sigmoid', got "
                f"{self.router_scoring!r}")
        if self.shared_expert_gate and not self.num_shared_experts:
            raise ValueError("shared_expert_gate needs num_shared_experts")
        if self.partial_rotary_factor != 1.0 and (
                not 0.0 <= self.partial_rotary_factor < 1.0
                or int(self.head_dim * self.partial_rotary_factor) % 2):
            raise ValueError(
                f"partial_rotary_factor {self.partial_rotary_factor} of "
                f"head_dim {self.head_dim} is no even number of columns")
        if self.layer_types is not None:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
            kinds = ATTENTION_KINDS + ("linear_attention",)
            if (len(self.layer_types) != self.num_layers
                    or any(t not in kinds for t in self.layer_types)):
                raise ValueError(
                    f"layer_types names one of {kinds} for each of the "
                    f"{self.num_layers} layers, got {self.layer_types}")
        if self.has_linear_attention:
            sizes = (self.linear_num_key_heads, self.linear_key_head_dim,
                     self.linear_num_value_heads, self.linear_value_head_dim)
            if not all(sizes) or sizes[2] % sizes[0] or (
                    self.linear_conv_kernel_dim < 1):
                raise ValueError(
                    "layer_types with a 'linear_attention' layer needs "
                    "linear_num_key_heads, linear_key_head_dim, "
                    "linear_num_value_heads (a multiple of the key heads), "
                    f"linear_value_head_dim (got {sizes}) and "
                    "linear_conv_kernel_dim >= 1")
            if self.attention_impl not in ("dot", "flash"):
                raise ValueError(
                    "layer_types with a 'linear_attention' layer supports "
                    "attention_impl 'dot'/'flash', not "
                    f"{self.attention_impl!r} (the ring shards the sequence "
                    "and the recurrent state is not handed from shard to "
                    "shard yet)")
            if self.block_diffusion is not None:
                raise ValueError(
                    "layer_types with a 'linear_attention' layer takes no "
                    "block_diffusion (a recurrence has no block-diffusion "
                    "mask)")
        self._check_per_layer_attention()
        self._check_sublayers()

    def _check_sublayers(self):
        """The fields of layers of one sublayer and of Mamba-2, each refusal
        with its reason; the tuple stored hashable."""
        if self.mlp_hidden_act not in ("silu", "relu2"):
            raise ValueError(
                f"mlp_hidden_act is 'silu' (SwiGLU) or 'relu2' (squared ReLU, no "
                f"gate matrix), got {self.mlp_hidden_act!r}")
        if self.num_experts is None and (
                self.moe_latent_size or self.moe_shared_expert_intermediate_size):
            raise ValueError(
                "moe_latent_size and moe_shared_expert_intermediate_size need "
                "num_experts")
        if self.sublayers is None:
            return
        object.__setattr__(self, "sublayers", tuple(self.sublayers))
        if (len(self.sublayers) != self.num_layers
                or any(s not in SUBLAYERS for s in self.sublayers)):
            raise ValueError(
                f"sublayers names one of {SUBLAYERS} for each of the "
                f"{self.num_layers} layers, got {self.sublayers}")
        for refused, why in (
                (self.layer_types is not None,
                 "layer_types: it names the mixers of layers that have a "
                 "feed-forward too"),
                (self.num_heads_per_layer is not None,
                 "num_heads_per_layer: it counts a mixer a layer"),
                (bool(self.first_dense_layers),
                 "first_dense_layers: an 'mlp' layer is the dense feed-forward"),
                (("moe" in self.sublayers) != (self.num_experts is not None),
                 "num_experts without a 'moe' layer, and no 'moe' layer without "
                 f"num_experts (got {self.num_experts})")):
            if refused:
                raise ValueError(
                    f"sublayers (layers of one sublayer) takes no {why}")
        if not self.has_mamba:
            return
        sizes = (self.mamba_num_heads, self.mamba_head_dim, self.ssm_state_size,
                 self.n_groups)
        if not all(sizes) or sizes[0] % sizes[3] or min(
                self.conv_kernel, self.chunk_size) < 1:
            raise ValueError(
                "sublayers with a 'mamba' layer needs mamba_num_heads, "
                "mamba_head_dim, ssm_state_size, n_groups (a divisor of the "
                f"heads) (got {sizes}), conv_kernel >= 1 and chunk_size >= 1")
        for refused, why in (
                (self.attention_impl not in ("dot", "flash"),
                 f"attention_impl {self.attention_impl!r}: the ring shards the "
                 "sequence and the scan's state is not handed from shard to "
                 "shard yet"),
                (self.block_diffusion is not None,
                 "block_diffusion: a recurrence has no block-diffusion mask")):
            if refused:
                raise ValueError(f"a 'mamba' layer (sublayers) takes no {why}")

    def _check_per_layer_attention(self):
        """The fields of attention that differs by layer, each refusal with
        its reason; tuples stored hashable."""
        if self.num_heads_per_layer is not None:
            heads = tuple(int(h) for h in self.num_heads_per_layer)
            object.__setattr__(self, "num_heads_per_layer", heads)
            kv = self.num_kv_heads or self.num_heads
            if len(heads) != self.num_layers or any(
                    h < 1 or h % kv for h in heads):
                raise ValueError(
                    f"num_heads_per_layer names for each of the "
                    f"{self.num_layers} layers a multiple of the {kv} "
                    f"key/value heads, got {heads}")
            if self.hidden_size is None:
                raise ValueError(
                    "num_heads_per_layer needs hidden_size (no one head "
                    "count gives the stream's width)")
        if self.rope_parameters is not None:
            given = dict(self.rope_parameters)
            unknown = sorted(set(given) - set(ATTENTION_KINDS))
            if unknown:
                raise ValueError(
                    f"rope_parameters names {ATTENTION_KINDS}, got {unknown}")
            rope_of = {
                kind: p if isinstance(p, RopeParameters) else RopeParameters(**p)
                for kind, p in given.items()}
            object.__setattr__(
                self, "rope_parameters", tuple(sorted(rope_of.items())))
            for kind, p in rope_of.items():
                if p.yarn is not None and None in p.yarn:
                    raise ValueError(
                        f"rope_parameters[{kind!r}]: YaRN takes factor, "
                        "original_max_position_embeddings, beta_fast, "
                        f"beta_slow and attention_factor, all or none, got "
                        f"{p.yarn}")
                if (not 0.0 <= p.partial_rotary_factor <= 1.0
                        or int(self.head_dim * p.partial_rotary_factor) % 2):
                    raise ValueError(
                        f"rope_parameters[{kind!r}]: partial_rotary_factor "
                        f"{p.partial_rotary_factor} of head_dim "
                        f"{self.head_dim} is no even number of columns")
        sliding = self.has_sliding_attention
        if sliding != (self.sliding_window is not None):
            raise ValueError(
                "sliding_window is the window of the 'sliding_attention' "
                f"layers of layer_types: got sliding_window "
                f"{self.sliding_window} and layer_types {self.layer_types}")
        if sliding and self.sliding_window < 1:
            raise ValueError(
                f"sliding_window must be >= 1, got {self.sliding_window}")
        if sliding and self.window is not None:
            raise ValueError(
                "window windows every layer and sliding_window the "
                "'sliding_attention' layers: give one of the two")
        if self.attn_head_gate and self.attn_output_gate:
            raise ValueError(
                "attn_head_gate (a gate a head) and attn_output_gate (a gate "
                "a column) are two forms of one gate: give one")
        by_layer = [name for name, is_set in (
            ("a 'sliding_attention' layer", sliding),
            ("num_heads_per_layer", self.num_heads_per_layer is not None),
            ("rope_parameters", self.rope_parameters is not None),
            ("attn_head_gate", self.attn_head_gate)) if is_set]
        if not by_layer:
            return
        for refused, why in (
                (self.attention_impl not in ("dot", "flash"),
                 f"attention_impl {self.attention_impl!r}: the ring rotates "
                 "keys and values under one window and one head count"),
                (self.shard_axis is not None,
                 "shard_axis: the head slices of layers that differ are not "
                 "sharded yet"),
                (self.kv_lora_rank is not None,
                 "latent attention: it has no window and its own rotary key"),
                (self.block_diffusion is not None,
                 "block_diffusion: its mask takes the place of causal and "
                 "window")):
            if refused:
                raise ValueError(
                    f"{', '.join(by_layer)} (attention that differs by "
                    f"layer) takes no {why}")

    @property
    def has_linear_attention(self) -> bool:
        return (self.layer_types is not None
                and "linear_attention" in self.layer_types)

    @property
    def has_mamba(self) -> bool:
        return self.sublayers is not None and "mamba" in self.sublayers

    @property
    def has_sliding_attention(self) -> bool:
        return (self.layer_types is not None
                and "sliding_attention" in self.layer_types)

    def layer_rope(self, kind: str) -> RopeParameters:
        """``rope_parameters`` of a layer type; the model's ``rope_theta`` and
        ``partial_rotary_factor`` for a type they do not name."""
        return dict(self.rope_parameters or ()).get(kind) or RopeParameters(
            self.rope_theta, self.partial_rotary_factor)

    def attention_layers(self) -> list:
        """For each layer what its attention is built from (the ``attn.layers``
        event's fields): ``kind``, query ``heads``, ``kv_heads``, ``window``
        (None: every position), ``rotary_columns`` of a head and ``rope_type``;
        a 'linear_attention' layer has its kind alone."""
        layers = []
        kinds = self.layer_types or ("full_attention",) * self.num_layers
        counts = self.num_heads_per_layer or (self.num_heads,) * self.num_layers
        for kind, heads in zip(kinds, counts):
            if kind not in ATTENTION_KINDS:
                layers.append({"kind": kind})
                continue
            own = self.layer_rope(kind)
            layers.append({
                "kind": kind, "heads": heads,
                "kv_heads": self.num_kv_heads or self.num_heads,
                "window": (self.sliding_window if kind == "sliding_attention"
                           else self.window),
                "rotary_columns": int(self.head_dim * own.partial_rotary_factor),
                "rope_type": own.rope_type})
        return layers

    @property
    def shared_expert_hidden(self) -> int:
        """Width of the shared expert beside a routed sum (0: none)."""
        return (self.moe_shared_expert_intermediate_size
                or self.num_shared_experts * (self.moe_intermediate_size or 0))

    @property
    def mlp_hidden(self) -> int:
        """Width of the dense feed-forward."""
        if self.intermediate_size is not None:
            return self.intermediate_size
        return self.d_model * self.mlp_ratio

    def block_remat_policies(self):
        """Per-block policy names (``remat_policy`` resolved, with the
        legacy ``remat`` bool as the default)."""
        return resolve_remat_policies(
            self.remat_policy, self.num_layers,
            default="dots_no_batch" if self.remat else "none",
        )

    @property
    def d_model(self) -> int:
        if self.hidden_size is not None:
            return self.hidden_size
        return self.num_heads * self.head_dim


def yarn_inv_freq(dim: int, theta: float, factor: float,
                  original_max_position_embeddings: int, beta_fast: float,
                  beta_slow: float) -> np.ndarray:
    """YaRN's ``dim // 2`` frequencies (arXiv:2309.00071, Hugging Face's
    ``_compute_yarn_parameters``), float32, host arithmetic at trace time:
    pair ``i`` turns at ``theta^(-2i/dim)`` (extrapolation) below the ramp and
    at ``1 / factor`` of it (interpolation) above, blended linearly between
    ``low`` and ``high``, the pairs that make ``beta_fast`` and ``beta_slow``
    turns over the original context."""
    extra = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    inter = extra / factor

    def pair_of(turns):
        return (dim * math.log(original_max_position_embeddings
                               / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / max(high - low, 0.001), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rope_frequencies(d: int, theta: float) -> np.ndarray:
    """Plain RoPE's ``d // 2`` frequencies: pair ``i`` turns at ``theta^(-2i/d)``."""
    return 1.0 / (theta ** (np.arange(0, d, 2) / d))


def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0,
         inv_freq=None, scale: Optional[float] = None) -> jax.Array:
    """Rotary position embedding; x: (B, S, H, D), positions: (B, S).
    ``inv_freq``: the D/2 frequencies where they are not ``theta``'s
    (``yarn_inv_freq``); ``scale``: YaRN's ``attention_factor`` on cos and sin."""
    freqs = rope_frequencies(x.shape[-1], theta) if inv_freq is None else inv_freq
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def sliding_mask(q_pos, k_pos, causal=True, window=None):
    """(Sq, Sk) bool attention mask shared by the dot oracle and the
    ring path (the two must stay exactly equivalent).  Causal:
    ``q_pos >= k_pos``; window (Mistral/HF convention): each query
    attends the last ``window`` positions, ITSELF INCLUDED
    (``q_pos - k_pos < window``; symmetric |Δ| < window when
    bidirectional).  ``window`` must be >= 1: a non-positive window
    would mask every entry and silently degrade to uniform attention
    (dot) or NaN (ring online-softmax)."""
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    delta = q_pos[:, None] - k_pos[None, :]
    mask = (delta >= 0) if causal else jnp.ones_like(delta, bool)
    if window is not None:
        reach = delta if causal else jnp.abs(delta)
        mask = mask & (reach < window)
    return mask


def block_diffusion_mask(half: int, block: int):
    """(2L, 2L) bool mask of block-diffusion training over ``[noisy ||
    clean]``, ``L = half`` positions each in blocks of ``block``: with
    ``b(i) = i // block``, noisy -> noisy iff ``b(j) == b(i)``; noisy ->
    clean iff ``b(j) < b(i)``; clean -> clean iff ``b(j) <= b(i)``; clean
    -> noisy never.  Every row sees at least itself.  The flash kernels
    compute the same mask tile by tile (ops/flash_attention.py)."""
    pos = np.arange(2 * half)
    clean = pos >= half
    blk = np.where(clean, pos - half, pos) // block
    qc, kc, qb, kb = clean[:, None], clean[None, :], blk[:, None], blk[None, :]
    return np.where(qc, kc & (kb <= qb), np.where(kc, kb < qb, kb == qb))


def causal_dot_attention(q, k, v, *, q_offset=0, k_offset=0, causal=True,
                         window=None, mask=None, documents=None):
    """Standard attention; offsets support sequence-sharded blocks.

    q: (B, S, H, D); k, v: (B, S, H_kv, D) with H_kv | H (``v`` may be of
    another width than ``q`` and ``k``, latent attention's 192 / 128: the
    scale is that of ``q``'s width and the output as wide as ``v``) — under GQA
    (H_kv < H) the einsums GROUP the contraction (query head
    ``hk*g + j`` reads kv head ``hk``) instead of repeating K/V to full
    heads, so no inflated K/V tensor is ever materialized.  Softmax in
    float32 (TPU numerics), matmuls in the input dtype so they hit the
    MXU in bf16.  ``causal=False`` is the bidirectional (encoder /
    BERT-family) form — no mask at all.  ``window``: Mistral-style
    sliding window — each token attends the last ``window`` positions,
    itself included (see ``sliding_mask``).  ``mask``: an explicit (Sq, Sk)
    bool mask (``block_diffusion_mask``) in place of ``causal``/``window``.
    ``documents``: a packed row's document ids (B, S), of queries and keys
    alike: a query sees a key only of its own document (equal ids), under
    ``causal`` and ``window`` as they are.
    """
    b, s_q, h, d = q.shape
    s_k, h_kv = k.shape[1], k.shape[2]
    if h_kv <= 0 or h % h_kv:
        raise ValueError(
            f"query heads ({h}) must be a multiple of kv heads ({h_kv})"
        )
    if h_kv != h:
        qg = q.reshape(b, s_q, h_kv, h // h_kv, d)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).reshape(
            b, h, s_q, s_k
        ) / jnp.sqrt(d).astype(q.dtype)
    else:
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(d).astype(
            q.dtype)
    logits = logits.astype(jnp.float32)
    if mask is not None:
        logits = jnp.where(mask[None, None], logits, -1e30)
    elif causal or window is not None:
        mask = sliding_mask(
            q_offset + jnp.arange(q.shape[1]),
            k_offset + jnp.arange(k.shape[1]),
            causal=causal, window=window,
        )
        logits = jnp.where(mask[None, None], logits, -1e30)
    if documents is not None:
        same = documents[:, :, None] == documents[:, None, :]
        logits = jnp.where(same[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if h_kv != h:
        return jnp.einsum(
            "bhgqk,bkhd->bqhgd",
            probs.reshape(b, h_kv, h // h_kv, s_q, s_k), v,
        ).reshape(b, s_q, h, v.shape[-1])
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


class ZeroCenteredRMSNorm(nn.Module):
    """``x / rms(x) * (1 + scale)``, ``scale`` initialised 0, statistics in
    float32 (the ``qwen3_next`` norm of the residual stream and of q / k)."""

    epsilon: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.zeros, (x.shape[-1],),
                           jnp.float32)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.epsilon)
        return (y * (1.0 + scale)).astype(self.dtype)


def _rms_norm(cfg: TransformerConfig):
    """The model's RMSNorm, in the form ``cfg.norm_zero_centered`` names."""
    return functools.partial(
        ZeroCenteredRMSNorm if cfg.norm_zero_centered else nn.RMSNorm,
        dtype=cfg.dtype, epsilon=cfg.rms_norm_eps)


def _rotary_terms(cfg: TransformerConfig, own: RopeParameters):
    """The rotated columns a head, their ``rot / 2`` frequencies and the factor
    on cos and sin (None: 1) by ``own``: YaRN's where it has one."""
    rot = int(cfg.head_dim * own.partial_rotary_factor)
    if own.yarn is None:
        return rot, rope_frequencies(rot, own.theta), None
    return rot, yarn_inv_freq(rot, own.theta, *own.yarn[:4]), own.attention_factor


def _rotary(cfg: TransformerConfig, x, positions, own: RopeParameters):
    """RoPE by ``own`` (``cfg.layer_rope`` of the layer's type) on the first
    ``partial_rotary_factor`` of each head's columns."""
    rot, freqs, scale = _rotary_terms(cfg, own)
    turn = functools.partial(rope, positions=positions, inv_freq=freqs, scale=scale)
    if rot == cfg.head_dim:
        return turn(x)
    return jnp.concatenate([turn(x[..., :rot]), x[..., rot:]], axis=-1)


def _rotary_qk(cfg: TransformerConfig, q, k, positions, own: RopeParameters):
    """``_rotary`` of q and k.  A 'flash' model whose shapes the rotary kernels
    take (``ops/rope_kernel.py`` ``engages``: 128-wide heads, rows that tile)
    rotates whole heads in one pass each and hands the flash kernels their own
    head-major layout; every other shape and model keeps ``rope``."""
    from ..ops import rope_kernel

    if not own.partial_rotary_factor:
        return q, k    # no column is rotated: attention without positions
    rot, freqs, scale = _rotary_terms(cfg, own)
    kernel = cfg.attention_impl == "flash" and all(
        rope_kernel.engages(x.shape, rot) for x in (q, k))
    if _trace.enabled():
        sizes = (rope_kernel.counts(q.shape, k.shape[2], rot, q.dtype.itemsize) if kernel
                 else dict(row_tile=0, programs=0, hbm_bytes=0))
        _trace.event(
            "rope.rotate", rows=q.shape[0] * q.shape[1], heads=q.shape[2],
            kv_heads=k.shape[2], head_dim=q.shape[3], rot=rot, rope_type=own.rope_type,
            kernel=kernel, **sizes)
    if not kernel:
        return _rotary(cfg, q, positions, own), _rotary(cfg, k, positions, own)
    c, s = rope_kernel.tables(positions, freqs, rot, scale)
    return rope_kernel.rotate(q, c, s, rot), rope_kernel.rotate(k, c, s, rot)


def causal_depthwise_conv(u, w, bias=None):
    """``silu(sum_j w[j] * u[t - (K - 1) + j] [+ bias])`` a channel, zeros before
    the sequence: ``u`` (B, T, C), ``w`` (K, C), ``bias`` (C,) or None; the sum in
    float32."""
    taps, t = w.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    acc = sum(padded[:, j:j + t].astype(jnp.float32) * w[j] for j in range(taps))
    if bias is not None:
        acc = acc + bias
    return nn.silu(acc).astype(u.dtype)


def l2_unit(y, scale=1.0, eps=1e-6):
    """``y / sqrt(sum(y^2) + eps) * scale`` over the last axis (a head), in
    float32, rounded to ``y``'s dtype."""
    y32 = y.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.sum(jnp.square(y32), axis=-1, keepdims=True) + eps)
    return (y32 * (inv * scale)).astype(y.dtype)


def _shard_size(cfg: TransformerConfig) -> int:
    """Bound size of ``cfg.shard_axis`` (1 when unset/unbound), with the
    divisibility contract checked at trace: every per-chip slice —
    query heads, kv heads (the paged pool shards with them) and the MLP
    hidden — must be exact, or shards would disagree on shapes."""
    from ..parallel._mesh_utils import axis_size_or_1

    tp = axis_size_or_1(cfg.shard_axis)
    if tp > 1:
        kv = cfg.num_kv_heads or cfg.num_heads
        hidden = cfg.mlp_hidden
        if cfg.kv_lora_rank is not None:
            raise ValueError(
                f"shard_axis {cfg.shard_axis!r} of size {tp} takes no latent "
                "attention (the latent products are not sharded yet)")
        if cfg.num_heads % tp or kv % tp or hidden % tp:
            raise ValueError(
                f"shard_axis {cfg.shard_axis!r} of size {tp} must divide "
                f"num_heads ({cfg.num_heads}), num_kv_heads ({kv}) and "
                f"d_model*mlp_ratio ({hidden})")
    return tp


def _own_scope(cfg: TransformerConfig, scope):
    """``scope`` (a ``jax.named_scope``) in a model with a 'sliding_attention'
    layer, so that a trace tells a window layer's kernels, rotary step and
    gate from a full layer's; no scope, and so the ``op_name``s that were, in
    every other model."""
    return scope if cfg.has_sliding_attention else contextlib.nullcontext()


class Attention(nn.Module):
    cfg: TransformerConfig
    # THIS layer where attention differs by layer: whether it is a
    # 'sliding_attention' one (``cfg.layer_types``), and its query heads
    # (``cfg.num_heads_per_layer``; None: ``cfg.num_heads``)
    sliding: bool = False
    heads: Optional[int] = None

    def _latent_qkv(self, x, positions, dense, heads):
        """Latent attention's q and k (``qk_nope_head_dim +
        qk_rope_head_dim`` wide) and v (``v_head_dim``) as the kernels take
        them: keys and values come up from one ``kv_lora_rank``-wide latent a
        position, and the key's rotary part is ONE head, shared by all (it
        is broadcast to the heads here: the layout the chip ran faster,
        docs/ATTENTION.md).  Only the rotary parts are rotated."""
        cfg = self.cfg
        rank, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
        rot, vd = cfg.qk_rope_head_dim, cfg.v_head_dim
        q = dense(features=(heads, nope + rot), name="q")(x)
        with jax.named_scope("mla"):
            kv_a = dense(features=rank + rot, name="kv_a")(x)
            latent = nn.RMSNorm(dtype=cfg.dtype, epsilon=cfg.rms_norm_eps,
                                name="kv_a_norm")(kv_a[..., :rank])
            kv_b = dense(features=(heads, nope + vd), name="kv_b")(latent)
            k_rot = rope(kv_a[..., None, rank:], positions, cfg.rope_theta)
            q = jnp.concatenate(
                [q[..., :nope], rope(q[..., nope:], positions,
                                     cfg.rope_theta)], axis=-1)
            k = jnp.concatenate(
                [kv_b[..., :nope],
                 jnp.broadcast_to(k_rot, (*kv_b.shape[:-1], rot))], axis=-1)
            v = kv_b[..., nope:]
        return q, k, v

    @nn.compact
    def __call__(self, x, positions, paged=None, layer: int = 0,
                 documents=None):
        cfg = self.cfg
        dense = functools.partial(
            nn.DenseGeneral, dtype=cfg.dtype, use_bias=False
        )
        # divisibility/positivity validated in TransformerConfig.__post_init__
        kv_heads = (cfg.num_heads if cfg.num_kv_heads is None
                    else cfg.num_kv_heads)
        # Megatron head sharding: under a bound shard_axis this trace
        # sees the LOCAL head slice — q/k/v kernels are (D, H/tp, d)
        # column slices, attention runs on H/tp query heads over the
        # H_kv/tp kv heads this chip owns (the GQA group ratio is
        # shard-invariant), and the output projection below reassembles
        # with one psum (row-parallel).
        tp = _shard_size(cfg)
        heads = (self.heads or cfg.num_heads) // tp
        kv_heads = kv_heads // tp
        kind = ATTENTION_KINDS[self.sliding]
        window = cfg.sliding_window if self.sliding else cfg.window
        # the attention core alone: the kernel call or the dot path
        core = (jax.named_scope("attn_window") if self.sliding
                else jax.named_scope("attn_full"))
        if paged is not None and (
                cfg.has_sliding_attention or self.heads is not None):
            raise ValueError(
                "paged serving takes no 'sliding_attention' layer and no "
                "num_heads_per_layer: the cache allocator keeps one window "
                "and one head count for all layers")
        gate = None
        if cfg.kv_lora_rank is not None:
            if paged is not None:
                raise ValueError(
                    "paged serving takes no latent attention (the cache "
                    "holds keys and values of one width: no latent cache yet)")
            q, k, v = self._latent_qkv(x, positions, dense, heads)
        else:
            if cfg.attn_output_gate:
                # a head's columns are [query | gate]
                q = dense(features=(heads, 2 * cfg.head_dim), name="q")(x)
                q, gate = q[..., :cfg.head_dim], q[..., cfg.head_dim:]
            else:
                q = dense(features=(heads, cfg.head_dim), name="q")(x)
            k = dense(features=(kv_heads, cfg.head_dim), name="k")(x)
            v = dense(features=(kv_heads, cfg.head_dim), name="v")(x)
            if cfg.qk_norm:
                q = _rms_norm(cfg)(name="q_norm")(q)
                k = _rms_norm(cfg)(name="k_norm")(k)
            with _own_scope(cfg, jax.named_scope("attn_rope")):
                own = cfg.layer_rope(kind)
                q, k = _rotary_qk(cfg, q, k, positions, own)
        if paged is not None and cfg.block_diffusion is not None:
            raise ValueError("paged serving takes no block_diffusion model")
        if paged is not None:
            # serving path (docs/SERVING.md): K/V live in the paged
            # cache's block pools, not in this activation.  Chunk (the
            # mixed chunked-prefill + decode step — whole-prompt
            # prefill is its offset-0 case) writes each row's chunk at
            # its own offset then attends the GATHERED pages — cached
            # prefix included — with per-row global offsets; decode
            # writes the one new token then attends the gathered pages
            # with the q_len=1 kernel.
            if cfg.attention_impl not in ("dot", "flash"):
                raise ValueError(
                    f"paged serving supports attention_impl 'dot'/'flash', "
                    f"not {cfg.attention_impl!r}")
            if not cfg.causal:
                raise ValueError("paged serving requires causal=True")
            if paged.mode == "chunk":
                from ..ops.flash_attention import flash_chunk_attention

                paged.write_chunk(layer, k, v)
                gk, gv, kv_start = paged.gather(
                    layer, window=cfg.window, q_span=k.shape[1])
                out = flash_chunk_attention(
                    q, gk, gv, paged.lens, window=cfg.window,
                    kv_start=kv_start,
                )
            else:
                from ..ops.flash_attention import flash_decode_attention

                paged.write_decode(layer, k, v)
                gk, gv, kv_start = paged.gather(layer, window=cfg.window)
                out = flash_decode_attention(
                    q, gk, gv, paged.lens + 1, window=cfg.window,
                    kv_start=kv_start,
                )
        # GQA needs no expansion: every impl consumes (B, S, H_kv, D)
        # K/V natively — the kernels/einsums share each kv head across
        # its query-head group, so the group factor is saved in
        # attention HBM bytes, FLOPs and ring comms, not just in the
        # projections.
        elif cfg.block_diffusion is not None:
            half = x.shape[1] // 2
            if x.shape[1] != 2 * half:
                raise ValueError(
                    "block_diffusion takes [noisy || clean], an even "
                    f"number of rows, got {x.shape[1]}")
            if cfg.attention_impl == "flash":
                from ..ops.flash_attention import flash_attention

                out = flash_attention(
                    q, k, v, block_diffusion=(half, cfg.block_diffusion))
            else:
                out = causal_dot_attention(
                    q, k, v,
                    mask=block_diffusion_mask(half, cfg.block_diffusion))
        elif cfg.attention_impl in ("ring", "ring_flash"):
            from ..parallel.ring_attention import ring_attention

            out = ring_attention(
                q, k, v, axis_name=cfg.seq_axis_name,
                impl="flash" if cfg.attention_impl == "ring_flash"
                else "dense",
                causal=cfg.causal,
                window=cfg.window,
            )
        elif cfg.attention_impl == "flash":
            from ..ops.flash_attention import flash_attention

            with _own_scope(cfg, core):
                out = flash_attention(q, k, v, causal=cfg.causal,
                                      window=window, documents=documents)
        else:
            with _own_scope(cfg, core):
                out = causal_dot_attention(q, k, v, causal=cfg.causal,
                                           window=window, documents=documents)
        if gate is not None:
            out = (out.astype(jnp.float32)
                   * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(cfg.dtype)
        if cfg.attn_head_gate:
            # one number a head and token, from the layer's (normed) input
            with _own_scope(cfg, jax.named_scope("attn_gate")):
                head_gate = dense(features=heads, name="gate")(x)
                out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                    head_gate.astype(jnp.float32))[..., None]).astype(cfg.dtype)
        out = nn.DenseGeneral(
            features=cfg.d_model, axis=(-2, -1), dtype=cfg.dtype,
            use_bias=False, name="o",
        )(out)
        if tp > 1:
            # row-parallel output projection: each chip contracted its
            # local head slice (the kernel is an (H/tp, d, D) row slice
            # of the global one); ONE psum reassembles the sublayer —
            # the first of Megatron's two collectives per block
            out = spmd_ops.allreduce(out, op=Sum, axis=cfg.shard_axis)
        return out


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log(A)``, ``A`` uniform over (0, 16): the published initialisation."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-3, 16.0))


def _conv_init(key, shape, dtype=jnp.float32):
    """Uniform within ``1 / sqrt(taps)``: a depthwise convolution's fan in is
    its taps."""
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


class _NormScale(nn.Module):
    """``nn.RMSNorm``'s one parameter, under the name and with the
    initialisation it has there, for a kernel that applies it itself."""

    @nn.compact
    def __call__(self, features):
        return self.param("scale", nn.initializers.ones, (features,), jnp.float32)


def _delta_mix(cfg, qkvz, ba, conv_w, a_log, dt_bias):
    """Steps 2-5 of ``GatedDeltaNet``: ``in_proj_qkvz``'s rows (B, T, [q | k |
    v | z]), ``in_proj_ba``'s (B, T, [b | a]) and the layer's three small
    parameters -> the rule's ``o``, (B, T, value heads x width) rows in a
    'flash' model, (B, T, value heads, width) in a 'dot' model.

    Nothing of it is made again in the backward (PR 41; until then a bare
    ``jax.checkpoint`` kept its inputs alone): the backward reads what the
    forward made -- the rule's residuals (its q, k, v rows, ``g``,
    ``beta``, the inverse ``T``, each chunk's ``D`` and the state it was
    handed: ``ops/gated_delta.py`` ``_fused_fwd``; 0.40 GB a layer at 8,192
    tokens, 0.46 with ``o``) and the input pass's operands -- so the inverse,
    ``gated_delta_kkt`` and ``gated_delta_fwd`` run once a layer and not
    twice.  q, k, v are kept too: making them again from the projection's
    rows (``jax.checkpoint`` with ``save_only_these_names`` over ``T``, ``D``
    and the states) ran 1.0 % slower on the chip, 46,367 against 46,828
    tokens/s, for 0.41 GB, and the benchmark's step fits as it is (14.06 GB
    where the old checkpoint's was 12.69; PERF.md section 6, PR 41).  A
    block under ``remat_policy`` makes this again with the rest of its
    layer."""
    from ..ops.gated_delta import gated_delta_rule
    from ..ops.gdn_kernels import gdn_conv_norm

    hk, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
    hv, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    key_dim, value_dim = hk * dk, hv * dv
    b, t, _ = qkvz.shape
    f32 = jnp.float32
    kernels = cfg.attention_impl == "flash"
    with jax.named_scope("gdn"):
        if kernels:
            # one pass, token-major rows out, [q | k | v] read out of the
            # projection's rows in place (ops/gdn_kernels.py)
            q, k, v = gdn_conv_norm(
                qkvz, conv_w, key_heads=hk, key_head_dim=dk,
                value_heads=hv, value_head_dim=dv)
        else:
            mixed = causal_depthwise_conv(
                qkvz[..., :2 * key_dim + value_dim], conv_w)
            keys = lambda x: x.reshape(b, t, hk, dk)
            q = l2_unit(keys(mixed[..., :key_dim]), dk ** -0.5)
            k = l2_unit(keys(mixed[..., key_dim:2 * key_dim]))
            v = mixed[..., 2 * key_dim:]
        beta = jax.nn.sigmoid(ba[..., :hv].astype(f32))
        g = -jnp.exp(a_log) * jax.nn.softplus(
            ba[..., hv:].astype(f32) + dt_bias)
    with jax.named_scope("gated_delta"):
        # q, k at the key heads: the rule reads a value head's key head
        # itself.  Rows in and rows out ('flash'): the reshapes fold away
        o = gated_delta_rule(
            q.reshape(b, t, hk, dk), k.reshape(b, t, hk, dk),
            v.reshape(b, t, hv, dv), g, beta,
            impl="kernel" if kernels else "jnp")
        return o.reshape(b, t, value_dim) if kernels else o


# ``_delta_mix`` as ONE jaxpr a layer for ``jax.grad`` to work on, every
# residual kept: nothing is made again.  Traced bare, the same compiled step
# cost the benchmark's set-up 20 s more (PERF.md section 6, PR 41)
_mix = jax.checkpoint(_delta_mix, static_argnums=(0,),
                      policy=jax.checkpoint_policies.everything_saveable)


class GatedDeltaNet(nn.Module):
    """The linear-attention mixer of the ``qwen3_next`` family (Gated DeltaNet,
    arXiv:2412.06464): ``x`` (B, T, d_model) ->

      1. ``in_proj_qkvz``: q, k (``linear_num_key_heads`` of
         ``linear_key_head_dim``), v, z (``linear_num_value_heads`` of
         ``linear_value_head_dim``), in that column order (the published
         checkpoint interleaves them a key head: a loader's business);
         ``in_proj_ba``: b, a, one number a value head, float32;
      2. ``[q, k, v]`` through a causal depthwise convolution of
         ``linear_conv_kernel_dim`` taps (``conv_kernel`` (taps, channels), no
         bias), then SiLU;
      3. q, k L2-normalised a head (eps 1e-6), q scaled by ``key_head_dim **
         -0.5``;
      4. ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``;
      5. the gated delta rule (``ops/gated_delta.py``), a state of ``key_head_dim
         x value_head_dim`` a value head, each reading the key head it shares,
         in chunks of 64 with the carry a Mosaic kernel ('flash' models) or a
         scan ('dot' models);
      6. ``norm(o) * silu(z)`` (RMSNorm over a head in the plain form, one
         weight vector for all heads), then ``out_proj``.

    'flash' models run steps 2-3 and the norm of step 6 as one Mosaic kernel
    pair each (``ops/gdn_kernels.py``) on token-major rows: q, k, v reach the
    rule's kernels, and ``o`` leaves them, as ``(B, T, heads x width)`` rows,
    ``z`` is read out of ``in_proj_qkvz``'s rows in place, and XLA is left the
    projections and the gates.  'dot' models keep the ``jnp`` functions
    (``causal_depthwise_conv``, ``l2_unit``, ``nn.RMSNorm``).

    Steps 1-4 and 6 trace under ``jax.named_scope("gdn")``, step 5 under its
    sibling ``"gated_delta"``.  Training only: no recurrent-state cache, no
    bound ``shard_axis`` (``TransformerConfig.layer_types``)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        from ..ops.gdn_kernels import gdn_gated_norm
        from ..parallel._mesh_utils import axis_size_or_1

        cfg = self.cfg
        if axis_size_or_1(cfg.shard_axis) > 1:
            raise ValueError(
                f"shard_axis {cfg.shard_axis!r} takes no 'linear_attention' "
                "layer (layer_types): the gated delta rule's heads are not "
                "sharded yet")
        hk, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
        hv, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
        key_dim, value_dim = hk * dk, hv * dv
        b, t, _ = x.shape
        f32 = jnp.float32
        with jax.named_scope("gdn"):
            qkvz = nn.Dense(2 * key_dim + 2 * value_dim, use_bias=False,
                            dtype=cfg.dtype, name="in_proj_qkvz")(x)
            ba = nn.Dense(
                2 * hv, use_bias=False, dtype=cfg.dtype, name="in_proj_ba",
                dot_general=functools.partial(
                    jax.lax.dot_general, preferred_element_type=f32))(x)
            conv_w = self.param("conv_kernel", _conv_init,
                                (cfg.linear_conv_kernel_dim,
                                 2 * key_dim + value_dim), f32)
            a_log = self.param("A_log", _a_log_init, (hv,), f32)
            dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,), f32)

        kernels = cfg.attention_impl == "flash"
        o = _mix(cfg, qkvz, ba, conv_w, a_log, dt_bias)
        with jax.named_scope("gdn"):
            if kernels:
                # one pass on the rule's rows, z read out of the projection's
                o = gdn_gated_norm(o, qkvz, _NormScale(name="norm")(dv), heads=hv,
                                   eps=cfg.rms_norm_eps)
            else:
                z = qkvz[..., 2 * key_dim + value_dim:].reshape(b, t, hv, dv)
                o = nn.RMSNorm(dtype=cfg.dtype, epsilon=cfg.rms_norm_eps,
                               name="norm")(o)
                o = (o.astype(f32) * nn.silu(z.astype(f32))).astype(cfg.dtype)
            return nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                            name="out_proj")(o.reshape(b, t, value_dim))


def _mamba_a_log_init(key, shape, dtype=jnp.float32):
    """``log(A)``, ``A`` uniform over (1, 16): Mamba-2's published
    initialisation."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The bias whose softplus is a step log-uniform over (0.001, 0.1), floor
    1e-4: the published ``time_step_min``, ``time_step_max``,
    ``time_step_floor``."""
    low, high = math.log(1e-3), math.log(1e-1)
    dt = jnp.maximum(
        jnp.exp(jax.random.uniform(key, shape, dtype) * (high - low) + low), 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))


class _Kernel(nn.Module):
    """``nn.Dense``'s one parameter without a bias, under the name and with the
    initialisation it has there, for a product taken of its columns in parts."""

    @nn.compact
    def __call__(self, shape):
        return self.param("kernel", nn.initializers.lecun_normal(), shape, jnp.float32)


class Mamba2(nn.Module):
    """The state-space mixer of the ``nemotron_h`` family (Mamba-2, Dao and Gu,
    arXiv:2405.21060): ``u`` (B, T, d_model) ->

      1. ``in_proj``: ``[z | xBC | dt] = u W_in``, ``z`` and ``x`` of
         ``mamba_num_heads x mamba_head_dim`` columns, ``B`` and ``C`` of
         ``n_groups x ssm_state_size`` each, ``dt`` one number a head (summed
         in float32), no bias;
      2. ``xBC <- silu(conv(xBC) + conv_bias)``, a causal depthwise convolution
         of ``conv_kernel`` taps (``conv_kernel`` (taps, channels));
      3. ``dt <- softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head; the scan
         (``ops/ssd.py``): a head's state ``S_t = exp(dt_t A) S_{t-1} + dt_t B_t
         x_t^T`` (``ssm_state_size x mamba_head_dim``, head ``h`` reading group
         ``h // (heads / n_groups)``), ``y_t = S_t^T C_t + D x_t``, in chunks
         of ``chunk_size`` with the carry a Mosaic kernel ('flash' models) or a
         ``lax.scan`` ('dot' models);
      4. ``RMSNorm over each group's columns of (y * silu(z))`` with a learned
         scale a column (``norm``), then ``out_proj``.

    'flash' models run step 2 and step 4's gated norm as one Mosaic kernel pair
    each (``ops/gdn_kernels.py`` ``conv_bias_silu``, ``gated_group_norm``) on
    token-major rows; 'dot' models keep ``jnp``.  The whole traces under
    ``jax.named_scope("mamba")`` with ``in_proj``, ``conv``, ``ssd``,
    ``gated_norm`` and ``out_proj`` inside.  A share of the heads (a
    tensor-parallel rank) is this module built at the share's heads and groups:
    ``out_proj`` then gives the rank's summand.  Training only: no
    recurrent-state cache, no bound ``shard_axis`` (``TransformerConfig.sublayers``)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, u):
        from ..ops.gdn_kernels import conv_bias_silu, gated_group_norm
        from ..ops.ssd import ssd_scan
        from ..parallel._mesh_utils import axis_size_or_1

        cfg = self.cfg
        if axis_size_or_1(cfg.shard_axis) > 1:
            raise ValueError(
                f"shard_axis {cfg.shard_axis!r} takes no 'mamba' layer "
                "(sublayers): the sum over head-parallel ranks of a Mamba mixer "
                "is not written yet")
        h, p = cfg.mamba_num_heads, cfg.mamba_head_dim
        g, n = cfg.n_groups, cfg.ssm_state_size
        inner, mixed = h * p, h * p + 2 * g * n
        b, t, _ = u.shape
        f32 = jnp.float32
        kernels = cfg.attention_impl == "flash"
        with jax.named_scope("mamba"):
            with jax.named_scope("in_proj"):
                w_in = _Kernel(name="in_proj")(
                    (cfg.d_model, inner + mixed + h)).astype(cfg.dtype)
                u = u.astype(cfg.dtype)
                zx = jnp.dot(u, w_in[:, :inner + mixed])
                dt = jnp.dot(u, w_in[:, inner + mixed:], preferred_element_type=f32)
            conv_w = self.param("conv_kernel", _conv_init, (cfg.conv_kernel, mixed), f32)
            conv_b = self.param("conv_bias", nn.initializers.zeros, (mixed,), f32)
            a_log = self.param("A_log", _mamba_a_log_init, (h,), f32)
            skip = self.param("D", nn.initializers.ones, (h,), f32)
            dt_bias = self.param("dt_bias", _dt_bias_init, (h,), f32)
            with jax.named_scope("conv"):
                xbc = zx[..., inner:]
                xbc = (conv_bias_silu(xbc, conv_w, conv_b) if kernels
                       else causal_depthwise_conv(xbc, conv_w, conv_b))
            with jax.named_scope("ssd"):
                y = ssd_scan(
                    xbc[..., :inner].reshape(b, t, h, p),
                    jax.nn.softplus(dt + dt_bias), -jnp.exp(a_log),
                    xbc[..., inner:inner + g * n].reshape(b, t, g, n),
                    xbc[..., inner + g * n:].reshape(b, t, g, n), skip,
                    chunk=cfg.chunk_size, impl="kernel" if kernels else "jnp")
            with jax.named_scope("gated_norm"):
                y, z = y.reshape(b, t, inner), zx[..., :inner]
                scale = _NormScale(name="norm")(inner)
                if kernels:
                    y = gated_group_norm(y, z, scale, groups=g, eps=cfg.rms_norm_eps)
                else:
                    gated = (y.astype(f32) * nn.silu(z.astype(f32))).reshape(b, t, g, -1)
                    gated = gated * jax.lax.rsqrt(jnp.mean(
                        jnp.square(gated), axis=-1, keepdims=True) + cfg.rms_norm_eps)
                    y = (gated.reshape(b, t, inner) * scale).astype(cfg.dtype)
            with jax.named_scope("out_proj"):
                return nn.Dense(cfg.d_model, use_bias=False, dtype=cfg.dtype,
                                name="out_proj")(y)


class MlpBlock(nn.Module):
    cfg: TransformerConfig
    # the feed-forward's width where it is not the config's dense one (the
    # shared experts beside a routed sum)
    hidden: Optional[int] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        # Megatron MLP under a bound shard_axis: gate/up are COLUMN
        # slices ((D, F/tp) kernels — no comms, the nonlinearity is
        # elementwise on the slice), down is the ROW slice ((F/tp, D))
        # whose partial products ONE psum reassembles — the second of
        # Megatron's two collectives per block.  tp == 1 is the
        # unsharded program verbatim.
        tp = _shard_size(cfg)
        hidden = (self.hidden or cfg.mlp_hidden) // tp
        if cfg.mlp_hidden_act == "relu2":
            # no gate matrix: down(relu(up x)^2), column- and row-split alike
            up = nn.Dense(hidden, dtype=cfg.dtype, use_bias=False, name="up")(x)
            inner = jnp.square(nn.relu(up))
        else:
            gate = nn.Dense(hidden, dtype=cfg.dtype, use_bias=False, name="gate")(x)
            up = nn.Dense(hidden, dtype=cfg.dtype, use_bias=False, name="up")(x)
            inner = nn.silu(gate) * up
        out = nn.Dense(
            cfg.d_model, dtype=cfg.dtype, use_bias=False, name="down"
        )(inner)
        if tp > 1:
            out = spmd_ops.allreduce(out, op=Sum, axis=cfg.shard_axis)
        return out


def _routed_feed_forward(cfg: TransformerConfig, z):
    """The routed feed-forward of a block's normed rows ``z``: (the routed sum
    plus the shared expert, the layer's routing statistics).  A plain function
    inside the block's ``compact`` call, not a method: a module's method would
    put its own name into every ``op_name`` under it."""
    from ..parallel.moe import RoutedExperts

    y, stats = RoutedExperts(
        num_experts=cfg.num_experts, top_k=cfg.num_experts_per_tok,
        d_model=cfg.d_model, d_ff=cfg.moe_intermediate_size,
        held=cfg.held_experts, dtype=cfg.dtype,
        scoring=cfg.router_scoring,
        scaling_factor=cfg.routed_scaling_factor,
        selection_bias=cfg.router_selection_bias,
        seq_aux=cfg.router_seq_aux, latent=cfg.moe_latent_size,
        gated=cfg.mlp_hidden_act != "relu2", name="moe",
    )(z)
    if cfg.shared_expert_hidden:
        # an ordinary feed-forward beside the routed sum, every chip the
        # whole of it; a scope of its own, not the routed layer's ``experts``
        with jax.named_scope("shared_experts"):
            shared = MlpBlock(
                cfg, hidden=cfg.shared_expert_hidden,
                name="shared_experts")(z)
            if cfg.shared_expert_gate:
                shared = shared * jax.nn.sigmoid(nn.Dense(
                    1, use_bias=False, dtype=cfg.dtype,
                    name="shared_expert_gate")(z))
            y = y + shared
    return y, stats


def _one_sublayer(cfg: TransformerConfig, sublayer, x, positions, paged, layer, documents):
    """``x + f(norm(x))`` with the layer's one ``f`` (``cfg.sublayers`` of the
    layer); a 'moe' layer hands its routing statistics on as a two-sublayer
    routed layer does."""
    z = _rms_norm(cfg)(name="norm")(x)
    if sublayer == "mamba":
        if paged is not None:
            raise ValueError(
                "paged serving takes no 'mamba' layer (sublayers): the "
                "cache holds keys and values, no recurrent state yet")
        return x + Mamba2(cfg, name="mixer")(z)
    if sublayer == "attention":
        return x + Attention(cfg, name="attn")(
            z, positions, paged=paged, layer=layer, documents=documents)
    if sublayer == "mlp":
        return x + MlpBlock(cfg, name="mlp")(z)
    y, stats = _routed_feed_forward(cfg, z)
    return x + y, stats


class Block(nn.Module):
    cfg: TransformerConfig
    # whether THIS layer's feed-forward is the routed one: the model's
    # layers after its ``first_dense_layers``, where it has ``num_experts``
    routed: bool = False
    # whether THIS layer's mixer is the linear one (``GatedDeltaNet``):
    # ``cfg.layer_types`` of the layer
    linear: bool = False
    # THIS layer's attention where it differs by layer (``Attention``'s)
    sliding: bool = False
    heads: Optional[int] = None
    # THIS layer's ONE sublayer (``cfg.sublayers`` of the layer); None: a mixer
    # and a feed-forward, as the fields above say
    sublayer: Optional[str] = None

    @nn.compact
    def __call__(self, x, positions, paged=None, layer: int = 0,
                 documents=None):
        cfg = self.cfg
        if self.sublayer is not None:
            return _one_sublayer(cfg, self.sublayer, x, positions, paged, layer, documents)
        norm = _rms_norm(cfg)
        if self.linear:
            if paged is not None:
                raise ValueError(
                    "paged serving takes no 'linear_attention' layer "
                    "(layer_types): the cache holds keys and values, no "
                    "recurrent state yet")
            x = x + GatedDeltaNet(cfg, name="linear_attn")(norm(name="ln1")(x))
        else:
            x = x + Attention(cfg, sliding=self.sliding, heads=self.heads,
                              name="attn")(
                norm(name="ln1")(x), positions, paged=paged, layer=layer,
                documents=documents)
        if not self.routed:
            x = x + MlpBlock(cfg, name="mlp")(norm(name="ln2")(x))
            return x
        # routed feed-forward: (x, the layer's routing statistics)
        y, stats = _routed_feed_forward(cfg, norm(name="ln2")(x))
        return x + y, stats


def _packed_input(tokens):
    """A model's input as ``(token ids, document ids or None)``: the ids (B, S)
    alone, or, for rows packed of several documents, the ids with each
    position's document id: a pair ``(ids, documents)``, or ONE integer array
    (B, 2, S) of the two (``[:, 0]`` the tokens, ``[:, 1]`` the documents: what
    a loader that hands the step one array a batch gives)."""
    if isinstance(tokens, (tuple, list)):
        ids, documents = tokens
        return ids, documents
    if tokens.ndim == 3:
        return tokens[:, 0], tokens[:, 1]
    return tokens, None


def document_starts(documents):
    """(B, S) bool: the positions where a document of a packed row begins (the
    first, and every position whose id differs from the one before)."""
    return jnp.concatenate(
        [jnp.ones_like(documents[:, :1], bool),
         documents[:, 1:] != documents[:, :-1]], axis=1)


def document_positions(documents):
    """Positions that restart at each document of a packed row: a position's
    distance from where its document begins, (B, S) from the ids (B, S)."""
    index = jnp.arange(documents.shape[1])
    first = jax.lax.cummax(
        jnp.where(document_starts(documents), index, 0), axis=1)
    return index - first


class Transformer(nn.Module):
    """Decoder-only LM.  ``__call__(tokens, positions=None) -> logits``;
    ``(logits, aux)`` with a routed feed-forward (``cfg.num_experts``; the
    statistics are over the routed layers, ``aux_loss`` in the form
    ``cfg.router_seq_aux`` names); with
    ``cfg.block_diffusion`` the tokens are ``[noisy || clean]`` (B, 2L) and
    the logits those of the noisy half, (B, L, V).  ``tokens`` with document
    ids (``_packed_input``: rows packed of several documents): every attention
    layer keeps a query to the keys of its own document, under the layer's
    causal mask and window, and positions restart at each document
    (``document_positions``) where none are given; the ids are data, so one
    compiled step serves every layout of the same shapes."""

    cfg: TransformerConfig

    def _refuse_documents(self, paged):
        """The paths that cannot honour document ids, each with its reason."""
        cfg = self.cfg
        for refused, why in (
                (paged is not None,
                 "paged serving: a cache row is one sequence"),
                (cfg.attention_impl not in ("dot", "flash"),
                 f"attention_impl {cfg.attention_impl!r}: the ring rotates "
                 "keys and values without their ids"),
                (cfg.shard_axis is not None,
                 "shard_axis: the head-sharded path is the serving engine's, "
                 "whose rows are one sequence each"),
                (cfg.kv_lora_rank is not None,
                 "latent attention: the kernels at its two widths take no ids"),
                (cfg.block_diffusion is not None,
                 "block_diffusion: its mask is of one document's [noisy || "
                 "clean] rows"),
                (cfg.has_linear_attention,
                 "'linear_attention' layer: a recurrence's state and its "
                 "convolution's taps are not reset at a boundary yet"),
                (cfg.has_mamba,
                 "'mamba' layer: the scan's state and its convolution's taps "
                 "are not reset at a boundary yet")):
            if refused:
                raise ValueError(f"document ids (packed rows) take no {why}")

    @nn.compact
    def __call__(self, tokens, positions=None, train: bool = True,
                 paged=None):
        cfg = self.cfg
        tokens, documents = _packed_input(tokens)
        routed = cfg.num_experts is not None
        if paged is not None and routed:
            raise ValueError("paged serving takes no routed feed-forward")
        if documents is not None:
            self._refuse_documents(paged)
            # what the ids become outside the kernels, under one name
            with jax.named_scope("attn_docmask"):
                documents = jnp.asarray(documents, jnp.int32)
                documents_a_row = jnp.sum(document_starts(documents), axis=1)
                if positions is None:
                    positions = document_positions(documents)
        if positions is None and cfg.block_diffusion is not None:
            half = jnp.arange(tokens.shape[1] // 2)
            positions = jnp.broadcast_to(
                jnp.concatenate([half, half]), tokens.shape)
        if positions is None:
            local = jnp.arange(tokens.shape[1])
            if cfg.attention_impl in ("ring", "ring_flash") and \
                    cfg.seq_axis_name:
                # sequence is sharded over the axis: global position =
                # shard_index * S_local + local offset (RoPE must match
                # the global causal offsets ring_attention masks with)
                local = (
                    jax.lax.axis_index(cfg.seq_axis_name) * tokens.shape[1]
                    + local
                )
            positions = jnp.broadcast_to(local, tokens.shape)
        if _trace.enabled() and (
                cfg.has_sliding_attention or cfg.rope_parameters is not None
                or cfg.num_heads_per_layer is not None):
            _trace.event("attn.layers", layers=[
                dict(layer, documents=documents is not None)
                for layer in cfg.attention_layers()])
        emb = nn.Embed(
            cfg.vocab_size, cfg.d_model,
            dtype=cfg.dtype, name="embed",
        )
        x = emb(tokens)
        # per-block remat policy (flax-aware checkpoint transform); one
        # lifted class per distinct policy so identical policies share a
        # transform
        policies = cfg.block_remat_policies() if train else None
        block_cls_for = {"none": Block}
        layer_stats = []
        for i in range(cfg.num_layers):
            pol = policies[i] if policies is not None else "none"
            block_cls = block_cls_for.get(pol)
            if block_cls is None:
                block_cls = nn.remat(
                    Block, policy=_checkpoint_policy(pol)
                )
                block_cls_for[pol] = block_cls
            # a routed model's leading dense layers keep the dense MlpBlock
            routed_here = routed and i >= cfg.first_dense_layers
            kinds = {}
            if cfg.sublayers is not None:
                kinds["sublayer"] = cfg.sublayers[i]
                routed_here = cfg.sublayers[i] == "moe"
            if cfg.layer_types is not None:
                kinds["linear"] = cfg.layer_types[i] == "linear_attention"
                kinds["sliding"] = cfg.layer_types[i] == "sliding_attention"
            if cfg.num_heads_per_layer is not None:
                kinds["heads"] = cfg.num_heads_per_layer[i]
            block = block_cls(cfg, routed=routed_here, name=f"layer_{i}",
                              **kinds)
            if paged is not None:
                # serving (inference-only) path: the paged-cache state
                # threads through every block, each addressing its own
                # pool layer; never composes with remat (train=False)
                x = block(x, positions, paged, i)
            elif documents is not None:
                # a packed row: every block's attention takes the ids
                x = block(x, positions, None, i, documents)
            else:
                x = block(x, positions)
            if routed_here:
                x, stats = x
                layer_stats.append(stats)
        if cfg.block_diffusion is not None:
            x = x[:, : x.shape[1] // 2]  # the head on the noisy half only
        x = _rms_norm(cfg)(name="ln_f")(x)
        if cfg.tie_word_embeddings:
            logits = emb.attend(x.astype(jnp.float32))
        else:
            logits = nn.Dense(
                cfg.vocab_size, use_bias=False, dtype=cfg.dtype, name="head",
                dot_general=functools.partial(
                    jax.lax.dot_general,
                    preferred_element_type=jnp.float32),
            )(x)
        if paged is not None:
            return logits, paged
        if routed:
            per = {k: jnp.stack([s[k] for s in layer_stats])
                   for k in layer_stats[0]}
            packed = ({} if documents is None
                      else {"documents": documents_a_row})  # (B,): a row's count
            return logits, {
                **packed,
                "aux_loss": jnp.mean(per["aux_loss"]),
                "expert_assignments": jnp.sum(per["assigned"]),
                "expert_load_max_over_mean": jnp.max(
                    per["load_max_over_mean"]),
                "dropped_assignments": jnp.sum(per["dropped"]),
                "expert_chunks": jnp.sum(per["chunks"]),
                "expert_index": per["expert_index"],
            }
        return logits


def _cross_entropy_plus_aux(outputs, targets, weights, aux_coef):
    """Mean over the positions of (``weights`` x) the float32 cross-entropy of
    a model's ``(logits, aux)`` (or of the logits alone) against ``targets``,
    plus ``aux_coef`` x the router's auxiliary loss."""
    import optax

    logits, aux = outputs if isinstance(outputs, tuple) else (outputs, None)
    ce = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), targets)
    if weights is not None:
        ce = weights.astype(jnp.float32) * ce
    loss = jnp.mean(ce)
    if aux is not None and aux_coef:
        loss = loss + aux_coef * aux["aux_loss"]
    return loss


def block_diffusion_loss(outputs, labels, aux_coef: float = 0.0):
    """The masked-diffusion loss of block-diffusion training, a ``loss_fn``
    for the train step: ``outputs`` is the model's ``(logits, aux)`` (or the
    logits alone), logits (B, L, V) of the noisy half; ``labels`` is
    ``(targets, weights)``, both (B, L): the clean tokens, and for each
    position ``masked / t`` of its block (0 where the position was not
    masked).  Loss = mean over the L positions of weight x cross-entropy,
    in float32, plus ``aux_coef`` x the router's auxiliary loss."""
    targets, weights = labels
    return _cross_entropy_plus_aux(outputs, targets, weights, aux_coef)


def next_token_loss(outputs, labels, aux_coef: float = 0.0):
    """Mean softmax cross-entropy in float32 of a routed model's ``(logits,
    aux)`` (or of the logits alone) against integer ``labels``, plus
    ``aux_coef`` x the router's auxiliary loss: a ``loss_fn`` for the train
    step.  ``labels`` may be ``(targets, weights)``, both (B, S): the mean is
    then over the weighted positions, ``sum(w x ce) / sum(w)`` (a packed row
    gives weight 0 to a document's last position, whose next token is another
    document's)."""
    if not isinstance(labels, (tuple, list)):
        return _cross_entropy_plus_aux(outputs, labels, None, aux_coef)
    targets, weights = labels
    weights = weights.astype(jnp.float32)
    return _cross_entropy_plus_aux(
        outputs, targets, weights * (weights.size / jnp.sum(weights)), aux_coef)


def modeled_activation_bytes(cfg: TransformerConfig, batch: int,
                             seq: Optional[int] = None) -> dict:
    """Modeled forward-to-backward activation bytes under the config's
    remat policies — the capacity arithmetic PERF.md round 6 reasons
    with (batch 1024 = "remat territory"), pinned by
    tests/test_remat_policies.py.

    Counts, per block, the tensors the backward READS without
    recomputation (matmul inputs/outputs and nonlinear intermediates in
    the activation dtype; attention-impl-agnostic — flash never
    materializes the S×S probabilities, so no quadratic term appears):

      none          — block input, ln1/ln2 outputs, q, k, v, attention
                      context, gate, up, silu(gate)*up
      dots          — block input + matmul outputs only (q, k, v,
                      context, o-proj, gate, up, down-proj)
      dots_no_batch — block input only (every decoder dot carries the
                      batch dim, so the policy saves none of them)
      full          — block input only

    Returns ``{"total_bytes", "per_block_bytes": {policy: bytes},
    "policies"}``; ``total_bytes`` sums the per-block figure over the
    resolved per-block policies.
    """
    uncounted = [name for name, is_set in (
        ("latent attention (kv_lora_rank)", cfg.kv_lora_rank is not None),
        ("intermediate_size", cfg.intermediate_size is not None),
        ("a routed feed-forward (num_experts)", cfg.num_experts is not None),
        ("layers of two mixers (layer_types)", cfg.layer_types is not None),
        ("layers of one sublayer (sublayers: a Mamba-2 mixer, attention, a "
         "routed or a dense feed-forward alone)", cfg.sublayers is not None),
        ("a feed-forward without a gate matrix (mlp_hidden_act 'relu2')",
         cfg.mlp_hidden_act != "silu"),
    ) if is_set]
    if uncounted:
        raise ValueError(
            "modeled_activation_bytes counts one head width and a dense "
            f"feed-forward of d_model * mlp_ratio; it cannot count "
            f"{', '.join(uncounted)}")
    s = int(seq if seq is not None else cfg.max_seq_len)
    act = jnp.dtype(cfg.dtype).itemsize
    kv_heads = cfg.num_kv_heads or cfg.num_heads
    bsd = batch * s * cfg.d_model * act          # one (B, S, D) tensor
    kv = 2 * batch * s * kv_heads * cfg.head_dim * act   # K and V
    f = batch * s * cfg.d_model * cfg.mlp_ratio * act    # one MLP hidden
    per_block = {
        "none": 5 * bsd + kv + 3 * f,   # input, ln1, q, ctx, ln2 + k,v
                                        # + gate, up, silu(gate)*up
        "dots": 5 * bsd + kv + 2 * f,   # input, q, ctx, o, down + k,v
                                        # + gate, up
        "dots_no_batch": bsd,           # block input only
        "full": bsd,                    # block input only
    }
    policies = cfg.block_remat_policies()
    return {
        "total_bytes": sum(per_block[p] for p in policies),
        "per_block_bytes": per_block,
        "policies": policies,
    }


# Named sizes (flagship family; Llama-ish shapes for the pretrain config).
def gpt_small(**kw) -> TransformerConfig:
    return TransformerConfig(num_layers=12, num_heads=12, head_dim=64, **kw)


def gpt_tiny(**kw) -> TransformerConfig:
    return TransformerConfig(
        vocab_size=256, num_layers=2, num_heads=2, head_dim=16,
        max_seq_len=128, **kw,
    )


def llama_7b(**kw) -> TransformerConfig:
    return TransformerConfig(
        vocab_size=32000, num_layers=32, num_heads=32, head_dim=128,
        max_seq_len=4096, **kw,
    )


def llama3_8b(**kw) -> TransformerConfig:
    """Llama-3-8B layout: GQA with 8 K/V heads over 32 query heads."""
    return TransformerConfig(
        vocab_size=128256, num_layers=32, num_heads=32, num_kv_heads=8,
        head_dim=128, max_seq_len=8192, **kw,
    )
