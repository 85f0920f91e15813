"""Required floating-point operations, from shapes alone.

The yardstick's numerators.  Each function counts what the mathematics of the
configuration needs for one forward and one backward pass, never what a
program happens to execute: recomputed operations do not count, a causal mask
counts at half, and a stem rewritten as space-to-depth counts as the 7x7 it
equals.  One multiply-accumulate is two operations; the backward pass of a
matrix multiplication or convolution is two of the same size (one for the
input's gradient, one for the weight's), so a training step is 3 x forward.
Elementwise work (norms, activations, the softmax, the optimizer) is not
counted: it is bandwidth, not FLOPs, and under 1 % of either model.

XLA's own count for the compiled step (``compiled.cost_analysis()``) is
printed beside these on an earlier line of every run as a cross-check and is
never the numerator: it changes when the program does and leaves out the
Pallas calls.
"""

from __future__ import annotations

from benchmark import resolve


def _conv_macs(out_hw: int, k: int, cin: int, cout: int) -> int:
    return out_hw * out_hw * k * k * cin * cout


def resnet_forward_macs_per_image(config: dict) -> int:
    """Multiply-accumulates of one bottleneck ResNet forward pass.

    Stem 7x7/2 to ``num_filters`` at half resolution, 3x3/2 max-pool, then
    stages of bottleneck blocks (1x1 to f, 3x3 to f, 1x1 to 4f, and a 1x1
    projection on each stage's first block), v1.5 (the stride on the 3x3, so
    a strided block's first 1x1 still runs at the input's resolution), then
    the classifier.  ResNet-50 at 224: 4.09e9.
    """
    f0 = config["num_filters"]
    exp = config["bottleneck_expansion"]
    hw = config["image_size"] // 2
    macs = _conv_macs(hw, 7, 3, f0)
    hw //= 2
    cin = f0
    for i, blocks in enumerate(config["stage_sizes"]):
        f = f0 * 2 ** i
        for j in range(blocks):
            stride = 2 if (i > 0 and j == 0) else 1
            out_hw = hw // stride
            macs += _conv_macs(hw, 1, cin, f)
            macs += _conv_macs(out_hw, 3, f, f)
            macs += _conv_macs(out_hw, 1, f, f * exp)
            if j == 0:
                macs += _conv_macs(out_hw, 1, cin, f * exp)
            cin, hw = f * exp, out_hw
    return macs + cin * config["num_classes"]


def resnet_train_flops_per_image(config: dict, traffic: dict) -> float:
    return 3.0 * 2.0 * resnet_forward_macs_per_image(config)


def decoder_lm_matrix_params(config: dict) -> int:
    """Parameters that sit in matrix multiplications: per layer q, k, v, o
    and the three SwiGLU matrices; plus hidden x vocabulary for the output
    head.  (The embedding lookup is a gather, not a multiplication.)"""
    d = config["hidden_size"]
    hd = config["head_dim"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = 3 * d * config["intermediate_size"]
    return config["num_hidden_layers"] * (attn + mlp) + d * config["vocab_size"]


def causal_attention_train_flops_per_token(config: dict, seq_len: int) -> float:
    """QK^T and PV are each 2 x S x (H x D) operations a token forward at
    full attention; a causal mask needs half; forward + backward is 3 x:
    6 x S x H x D a layer a token.  (Flash kernels recompute QK^T in the
    backward pass; that is not counted.)"""
    width = config["num_attention_heads"] * config["head_dim"]
    return 6.0 * config["num_hidden_layers"] * seq_len * width


def decoder_lm_train_flops_per_token(config: dict, traffic: dict) -> float:
    return (6.0 * decoder_lm_matrix_params(config)
            + causal_attention_train_flops_per_token(config, traffic["seq_len"]))


def flash_attention_train_flops_per_step(config: dict, traffic: dict,
                                         rows: int) -> float:
    """What the flash-attention kernels (forward, dq, dk/dv) of a step over
    ``rows`` sequences are required to do: 6 x B x H x S^2 x D a layer."""
    s = traffic["seq_len"]
    return causal_attention_train_flops_per_token(config, s) * rows * s


FUNCTIONS = {
    "resnet_train_flops_per_image": resnet_train_flops_per_image,
    "decoder_lm_train_flops_per_token": decoder_lm_train_flops_per_token,
    "flash_attention_train_flops_per_step": flash_attention_train_flops_per_step,
}


def function(name: str):
    """The function a configuration or a metric's file names: one of the
    above, or ``<module>:<attribute>`` under ``benchmark/``, taking ``(config,
    traffic)`` for a sample or ``(config, traffic, rows)`` for a kernel's step.
    What it counts is its own (operations, or bytes against
    ``hbm_bytes_per_s``)."""
    return resolve(name, FUNCTIONS, "FLOP function")
