"""Ask the chip's compiler, without the chip (on-chip-measurement guide §2).

The TPU compiler is installed here and compiles for a v5e:2x2 that is
described, not attached: the kernels of the main path at real widths, and
the whole train steps chip_smoke.py runs.  Nothing executes, so nothing here
is a result or a time — a compile that passes is not a chip run.

Only one process may load libtpu, so the topology is described inside a
module-scoped fixture (never at import, never in conftest.py), everything
compiles in this test's own process, and all cases live in this one file.
The persistent compile cache is off around them: an entry written for a
described chip cannot be read back without one.

One compile a program, one contract a case: a whole step or layer is compiled
once, in a module-scoped fixture (``two_mixer_step``, ``by_layer_step``, ...),
and every fact held of it is a test of its own on that fixture, named for the
fact.  A new fact about a program that is compiled here already is a new case
on its fixture, not a new compile.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from horovod_tpu import models, training
from horovod_tpu.common.topology import WORLD_AXIS
from horovod_tpu.models.transformer import (
    Transformer, TransformerConfig, gpt_small,
)
from horovod_tpu.ops.flash_attention import (
    flash_attention, flash_chunk_attention, flash_decode_attention,
)

HBM_BYTES = 16e9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# -- flash attention (training kernels) ---------------------------------------

FLASH_CASES = {
    "gqa_d128_causal": dict(b=2, s=2048, h=32, kv=8, d=128, causal=True,
                            window=None),
    "gqa_d128_window": dict(b=2, s=2048, h=32, kv=8, d=128, causal=True,
                            window=512),
    "mha_d64_causal": dict(b=4, s=2048, h=12, kv=12, d=64, causal=True,
                           window=None),
    "mha_d64_noncausal": dict(b=4, s=2048, h=12, kv=12, d=64, causal=False,
                              window=None),
    # internlm2-1.8b-s4096-1chip: four tiles a loop iteration in every
    # kernel, the group of two query heads a dK/dV program
    "internlm2_cell": dict(b=1, s=4096, h=16, kv=8, d=128, causal=True,
                           window=None),
}


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_compiles(one_chip, case, backward):
    c = FLASH_CASES[case]
    q = _sds((c["b"], c["s"], c["h"], c["d"]), jnp.bfloat16, one_chip)
    kv = _sds((c["b"], c["s"], c["kv"], c["d"]), jnp.bfloat16, one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=c["causal"],
                               window=c["window"], interpret=False)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else fwd
    assert _has_kernel(_compile(fn, q, kv, kv))


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_latent_attention_compiles_at_the_cell_s_shapes(one_chip, backward):
    """kimi-vl-a3b-s8192-1chip: one sequence of 8,192 positions, 16 heads,
    queries and keys 192 wide (128 + 64 rotary), values 128: the three
    kernels hold a head's whole keys, values and (dK/dV) queries in VMEM at
    these widths, and give dq, dk 192 wide and dv 128."""
    q = _sds((1, 8192, 16, 192), jnp.bfloat16, one_chip)
    v = _sds((1, 8192, 16, 128), jnp.bfloat16, one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, interpret=False)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else fwd
    compiled = _compile(fn, q, q, v)
    assert _has_kernel(compiled)
    if backward:
        shapes = [tuple(o.shape) for o in jax.tree_util.tree_leaves(
            jax.eval_shape(fn, q, q, v))]
        assert shapes == [(1, 8192, 16, 192), (1, 8192, 16, 192), (1, 8192, 16, 128)]


def test_flash_latent_dkv_alone_compiles_with_its_vmem_stated(one_chip):
    """The dK/dV half alone at Kimi's shape (``tools/flash_bench.py --cells``'
    jit): 10 MiB of q and dO resident and an iteration's eight float32 tiles
    were 17.7 MiB, past the compiler's own 16 (refused on the chip and here,
    PR 42), so the call states its VMEM whatever its resident bytes."""
    from horovod_tpu.ops.flash_attention import _backward_impl

    q = _sds((1, 8192, 16, 192), jnp.bfloat16, one_chip)
    v = _sds((1, 8192, 16, 128), jnp.bfloat16, one_chip)
    lse = _sds((16, 8192, 1), jnp.float32, one_chip)
    compiled = _compile(
        lambda q, k, v, out, lse, g: _backward_impl(
            q, k, v, out, lse, g, True, 256, 256, False)[1:], q, q, v, v, lse, v)
    text = compiled.as_text()
    assert "flash_attention_bwd_dkv" in text and "flash_attention_bwd_dq" not in text


# the forward's and dQ's call at the cells' shapes: heads over key/value heads,
# (d_qk, d_v), the mask, and the consecutive query tiles of a head that a program
# walks as one (the rule's: 8,192 rows are 32 query tiles of 256)
_QUERY_WALK_CASES = {
    "laguna_sliding": (64, 8, (128, 128), dict(causal=True, window=512), 4),
    "laguna_full": (48, 8, (128, 128), dict(causal=True), 1),
    "sdar": (32, 4, (128, 128), dict(causal=False, bd=(4096, 4)), 1),
    "kimi": (16, 16, (192, 128), dict(causal=True), 1),
    "qwen3next": (16, 2, (256, 256), dict(causal=True), 1),
}


@pytest.mark.parametrize("kernel", ["flash_attention_fwd", "flash_attention_bwd_dq"])
@pytest.mark.parametrize("case", _QUERY_WALK_CASES)
def test_flash_forward_and_dq_alone_compile_at_the_query_tiles_the_rule_picks(
        one_chip, case, kernel):
    """The forward and the dQ call, each ALONE (``tools/flash_bench.py --cells``'
    jits; a whole backward compiling says nothing of one half's VMEM: PR 42), at
    the five cells' attention shapes with the query tiles a program that
    ``_query_tiles_a_program`` picks from the shapes: one call of that name in
    the compiled text, its grid ``(heads, 32 / query tiles)``."""
    from horovod_tpu.ops import flash_attention as fa

    h, h_kv, (d, dv), mask, tiles = _QUERY_WALK_CASES[case]
    q = _sds((1, 8192, h, d), jnp.bfloat16, one_chip)
    k = _sds((1, 8192, h_kv, d), jnp.bfloat16, one_chip)
    v = _sds((1, 8192, h_kv, dv), jnp.bfloat16, one_chip)
    out = _sds((1, 8192, h, dv), jnp.bfloat16, one_chip)
    lse = _sds((h, 8192, 1), jnp.float32, one_chip)
    kw = dict(window=mask.get("window"), bd=mask.get("bd"))
    if kernel == "flash_attention_fwd":
        fn, args = (lambda q, k, v: fa._forward_impl(
            q, k, v, mask["causal"], 256, 256, False, with_lse=True, **kw)), (q, k, v)
    else:
        fn, args = (lambda q, k, v, out, lse, g: fa._backward_impl(
            q, k, v, out, lse, g, mask["causal"], 256, 256, False, **kw)[0]), (
                q, k, v, out, lse, out)
    assert fa._query_tiles_a_program(8192, 8192, 256, 256, 8192, **mask) == tiles
    text = _compile(fn, *args).as_text()
    assert re.findall(r"%(flash_attention\w*)\.\d+ = [^\n]*tpu_custom_call", text) == [kernel]
    grids = [e.params["grid_mapping"].grid for e in jax.make_jaxpr(fn)(*args).eqns
             if e.primitive.name == "pallas_call"]
    assert grids[0] == (h, 32 // tiles), grids


def _dkv_bd_operands(text):
    """The operand shapes the compiled ``flash_attention_bwd_dkv_bd`` call
    constrains: its q is the second (the key offset comes first)."""
    (line,) = [l for l in text.splitlines()
               if re.search(r"%flash_attention_bwd_dkv_bd\.\d+ = ", l)]
    constraints = re.search(r"operand_layout_constraints=\{(.*?\})\}", line).group(1)
    return re.findall(r"\w+\[[\d,]*\]", constraints)


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_block_diffusion_compiles_at_the_cell_s_shapes(one_chip, backward):
    """sdar-30b-a3b-bd4-s4096-1chip: one row of [noisy || clean] = 8,192
    positions, 32 query heads over 4 key/value heads of 128, blocks of 4.
    A group of 8 query heads' q and dO at 8,192 rows are 64 MiB twice
    buffered: the dK/dV kernel holds the whole group a program as under the
    other masks (``_DKV_GROUP_BYTES``; Laguna's sliding layers are the same
    shape), stating 80 MiB of VMEM, and its q operand is the group's rows."""
    half = 4096
    q = _sds((1, 2 * half, 32, 128), jnp.bfloat16, one_chip)
    kv = _sds((1, 2 * half, 4, 128), jnp.bfloat16, one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, block_diffusion=(half, 4),
                               interpret=False)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else fwd
    text = _compile(fn, q, kv, kv).as_text()
    assert "flash_attention_fwd" in text
    if backward:
        assert "flash_attention_bwd_dq" in text
        operands = _dkv_bd_operands(text)
        assert operands[1] == operands[4] == "bf16[4,65536,128]", operands
        assert "bf16[32,8192,128]" not in operands
        assert operands[5] == operands[6] == "f32[4,1,65536]"


def test_flash_block_diffusion_beyond_the_group_s_bytes_is_one_head_a_program(one_chip):
    """256-wide heads under the mask, 16 query heads over 2 key/value heads at
    8,192 rows: the group's q and dO would be 128 MiB twice buffered, so
    dK/dV runs one query head a program (16 MiB, stated) under the same name."""
    half = 4096
    q = _sds((1, 2 * half, 16, 256), jnp.bfloat16, one_chip)
    kv = _sds((1, 2 * half, 2, 256), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_diffusion=(half, 4),
                                       interpret=False).astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv).as_text()
    operands = _dkv_bd_operands(text)
    assert operands[1] == operands[4] == "bf16[16,8192,256]", operands
    assert operands[5] == operands[6] == "f32[16,1,8192]"


@pytest.fixture(scope="module")
def document_ids_calls(one_chip):
    """The three kernels with a packed row's ids under the 1,024 window at the
    shape of mellum2-12b-a2.5b-pack8192-1chip's sliding layers (1 x 8,192, 32
    query heads over 4 of 128; PR 45), forward and backward in one program,
    compiled once: the compiled text, and each flash call's operands by name."""
    q = _sds((1, 8192, 32, 128), jnp.bfloat16, one_chip)
    kv = _sds((1, 8192, 4, 128), jnp.bfloat16, one_chip)
    ids = _sds((1, 8192), jnp.int32, one_chip)

    def loss(q, k, v, ids):
        return jnp.sum(flash_attention(q, k, v, window=1024, documents=ids,
                                       interpret=False).astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv, ids).as_text()
    return text, {m.group(1): m.group(2) for m in re.finditer(
        r"%(flash_attention\w*?)\.\d+ = [^\n]*tpu_custom_call[^\n]*"
        r"operand_layout_constraints=\{(.*?)\}, frontend", text)}


def test_flash_with_document_ids_compiles_at_the_cell_s_shapes(document_ids_calls):
    """Each of the three calls takes the ids laid out for its tiles (a
    position's id on 128 lanes for the side on a tile's rows, the ids along the
    lanes for the other) and, since PR 46, its side's tile bounds, two (rows,
    tiles) scalars' arrays (SMEM, beside ``kv_offset``): each query tile's first
    and one-past-last key tile in the forward and dQ calls, each key tile's
    over the query tiles in dK/dV."""
    _, calls = document_ids_calls
    assert sorted(calls) == ["flash_attention_bwd_dkv", "flash_attention_bwd_dq",
                             "flash_attention_fwd"]
    for name, operands in calls.items():
        assert operands.startswith("s32[1]{0}, "), (name, operands)
        assert operands.endswith("s32[1,8192,128]{2,1,0}, s32[1,1,8192]{2,1,0}, "
                                 "s32[1,32]{1,0}, s32[1,32]{1,0}"), (name, operands)


def test_flash_with_document_ids_holds_the_group_and_states_its_vmem(document_ids_calls):
    """The dK/dV call holds the whole group of 8 and states its VMEM with the
    ids' blocks (64 + 16 MiB + 0.75 MiB); the bounds are scalars and add
    nothing to it."""
    text, calls = document_ids_calls
    assert "bf16[4,65536,128]" in calls["flash_attention_bwd_dkv"]      # the group's rows
    (dkv,) = [l for l in text.splitlines() if re.search(r"%flash_attention_bwd_dkv\.\d+ = ", l)]
    assert f'"size":"{(64 + 16) * 2 ** 20 + 2 * 4 * (256 * 128 + 8 * 8192)}"' in dkv


def test_flash_with_document_ids_makes_its_bounds_once_a_call(document_ids_calls):
    """All three calls read ONE pair of bound arrays, made once under
    ``attn_docmask``: at square tiles a key tile's bounds over the query tiles
    are that query tile's over the key tiles (two tiles' id intervals overlap
    or do not, whichever side asks)."""
    text, calls = document_ids_calls
    bounds = {}
    for name in calls:
        (line,) = [l for l in text.splitlines() if re.search(rf"%{name}\.\d+ = ", l)]
        operands = re.sub(r"/\*index=\d+\*/", "", re.search(
            r"custom-call\((.*?)\), custom_call_target", line).group(1))
        bounds[name] = operands.split(", ")[-2:]
    assert bounds["flash_attention_fwd"] == bounds["flash_attention_bwd_dq"] \
        == bounds["flash_attention_bwd_dkv"]
    made = [l for l in text.splitlines() if "/attn_docmask/" in l and " fusion(" in l]
    assert made, "no fusion under attn_docmask"


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_256_wide_heads_compile_at_the_cell_s_shapes(one_chip, backward):
    """qwen3-next-80b-a3b-s8192-1chip: one sequence of 8,192 positions, 16
    query heads over 2 key/value heads of 256.  The forward and dQ kernels hold
    a head's 8,192 x 256 keys and values twice buffered (16 MiB: they state
    their VMEM, ``_kv_params``); a whole group's q and dO would be 128 MiB, so
    dK/dV runs one query head a program (``_bwd_dkv_head_kernel``) under the
    name the metrics read."""
    q = _sds((1, 8192, 16, 256), jnp.bfloat16, one_chip)
    kv = _sds((1, 8192, 2, 256), jnp.bfloat16, one_chip)

    def fwd(q, k, v):
        return flash_attention(q, k, v, interpret=False)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32) ** 2)

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else fwd
    text = _compile(fn, q, kv, kv).as_text()
    kernels = re.findall(r"%(flash_attention\w*)\.\d+ = [^\n]*tpu_custom_call", text)
    want = ["flash_attention_bwd_dkv", "flash_attention_bwd_dq", "flash_attention_fwd"] \
        if backward else ["flash_attention_fwd"]
    assert sorted(kernels) == want
    assert "bf16[16,8192,256]" in text and "bf16[2,8192,256]" in text


def _entry_instructions(text):
    """The lines of the compiled text's ENTRY computation: what runs as an
    operation of its own (a fusion's body is elsewhere)."""
    body = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", text, re.M | re.S).group(1)
    return body.splitlines()


def _xla_products(text, inside):
    """The compiled text's ``dot`` and ``convolution`` instructions (XLA's own
    products, in a fusion or not) whose ``op_name`` holds ``inside``."""
    return [line.strip() for line in text.splitlines()
            if re.search(r"= \S+ (dot|convolution)\(", line) and inside in line]


@pytest.mark.parametrize("backward,key_heads", [(False, 32), (True, 32), (True, 16)],
                         ids=["fwd", "fwd_bwd", "fwd_bwd_key_heads"])
def test_gated_delta_rule_is_a_kernel_on_the_chip(one_chip, backward, key_heads):
    """``ops.gated_delta.gated_delta_rule`` at the cell's shape (8,192 tokens,
    32 value heads, a 128 x 128 state) as the v5e compiler takes it: ``L``, the
    chunk-local tensors with the carry over the chunks, and their backward are
    three Mosaic kernels by their names, and XLA is left no product of the rule
    but the unit-triangular inverse's (PR 36).  With q and k at the layer's 16
    key heads (PR 38) the kernels' q, k operands and dq, dk results are 2,048
    wide: nothing is repeated, nothing summed over pairs of heads outside."""
    from horovod_tpu.ops.gated_delta import gated_delta_rule

    qk = _sds((1, 8192, key_heads, 128), jnp.bfloat16, one_chip)
    v = _sds((1, 8192, 32, 128), jnp.bfloat16, one_chip)
    gate = _sds((1, 8192, 32), jnp.float32, one_chip)

    def fwd(q, k, v, g, beta):
        return gated_delta_rule(q, k, v, g, beta, interpret=False)

    def loss(*a):
        return jnp.sum(fwd(*a).astype(jnp.float32) ** 2)

    fn = jax.grad(loss, argnums=(0, 1, 2, 3, 4)) if backward else fwd
    text = _compile(fn, qk, qk, v, gate, gate).as_text()
    calls = {m.group(1): m.group(0) for m in re.finditer(
        r"%(gated_delta\w*)\.\d+ = [^\n]*tpu_custom_call[^\n]*", text)}
    assert sorted(calls) == (["gated_delta_bwd"] if backward else []) + [
        "gated_delta_fwd", "gated_delta_kkt"]
    assert "triangular-solve" not in text and "while(" not in text.replace(" ", "")
    products = _xla_products(text, "jit(_rule)")
    assert products and all("jit(_block_inverse)" in line for line in products)
    # the kernels' operands as they cross HBM: q, k, v token-major, T float32
    assert "bf16[1,8192,4096]" in text and "f32[1,32,8192,64]" in text
    keys = f"bf16[1,8192,{key_heads * 128}]"
    layouts = lambda name: re.search(
        r"operand_layout_constraints=\{(.*?)\}, frontend_attributes", calls[name]).group(1)
    assert layouts("gated_delta_kkt").startswith(keys)           # k
    assert layouts("gated_delta_fwd").count(keys) == 2 + (key_heads == 32)     # q, k (, v)
    if backward:                                                   # dq, dk at the key heads
        assert calls["gated_delta_bwd"].split(" custom-call(")[0].count(keys) == 2 + (
            key_heads == 32)


def test_gdn_passes_are_kernels_on_the_chip(one_chip):
    """``ops.gdn_kernels`` at the cell's shape (PR 38): the input pass reads [q
    | k | v] and the output pass ``z`` out of the projection's 12,288-wide rows
    in place (no slice of XLA's), forward and backward a kernel each."""
    from horovod_tpu.ops.gdn_kernels import gdn_conv_norm, gdn_gated_norm

    qkvz = _sds((1, 8192, 12288), jnp.bfloat16, one_chip)
    taps = _sds((4, 8192), jnp.float32, one_chip)
    scale = _sds((128,), jnp.float32, one_chip)

    def loss(qkvz, taps, scale):
        q, k, v = gdn_conv_norm(qkvz, taps, key_heads=16, key_head_dim=128, value_heads=32,
                                value_head_dim=128, interpret=False)
        o = gdn_gated_norm(v, qkvz, scale, heads=32, interpret=False)
        return sum(jnp.sum(x.astype(jnp.float32) ** 2) for x in (q, k, o))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), qkvz, taps, scale).as_text()
    kernels = re.findall(r"%(gdn_\w*?)\.\d+ = [^\n]*tpu_custom_call", text)
    assert sorted(kernels) == ["gdn_conv_norm_bwd", "gdn_conv_norm_fwd",
                               "gdn_gated_norm_bwd", "gdn_gated_norm_fwd"]
    assert not re.search(r"= bf16\[1,8192,(8192|4096)\]\S* slice\(", text)


# the routed cells' grouped products: (rows of the first chunk, k, n, held experts,
# the rows an expert expects, row tile)
GROUPED_CASES = {
    "sdar_gate_up": (9216, 2048, 768, 16, 512, 256),
    "sdar_down": (9216, 768, 2048, 16, 512, 256),
    "kimi_gate_up": (6912, 2048, 1408, 8, 768, 256),
    "kimi_down": (6912, 1408, 2048, 8, 768, 256),
    "qwen3next_gate_up": (5760, 2048, 512, 32, 160, 128),
    "qwen3next_down": (5760, 512, 2048, 32, 160, 128),
    "mellum2_gate_up": (18432, 2304, 896, 16, 1024, 256),
    "mellum2_down": (18432, 896, 2304, 16, 1024, 256),
}


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("case", GROUPED_CASES)
def test_grouped_products_are_a_kernel_on_the_chip(one_chip, case, backward):
    """``ops.grouped_matmul`` at the routed cells' first chunks, nine eighths of
    the expected assignments (SDAR: 9,216 sorted rows, 16 held experts of 2,048
    x 768 and back; Kimi: 6,912 rows, 8 of 2,048 x 1,408 and back; Qwen3-Next:
    5,760 rows, 32 of 2,048 x 512 and back, 160-row groups on 128-row tiles;
    Mellum 2: 18,432 rows, 16 of 2,304 x 896 and back) as the v5e compiler takes it: the package's own
    Mosaic kernels, forward and both gradients, by their names; no
    ``ragged-dot``; an expert's whole matrix in a tile fits the fast memory
    the kernels ask for (the compile refuses what does not)."""
    from horovod_tpu.ops.grouped_matmul import grouped_matmul, tiles

    rows, k, n, held, group, tm = GROUPED_CASES[case]
    assert tuple(tiles(rows, k, n, group, jnp.bfloat16)) == (tm, k, n)
    x = _sds((rows, k), jnp.bfloat16, one_chip)
    w = _sds((held, k, n), jnp.bfloat16, one_chip)
    sizes = _sds((held,), jnp.int32, one_chip)

    def fwd(x, w, sizes):
        return grouped_matmul(x, w, sizes, expected=group, interpret=False)

    def loss(x, w, sizes):
        return jnp.sum(fwd(x, w, sizes).astype(jnp.float32) ** 2)

    fn = jax.grad(loss, argnums=(0, 1)) if backward else fwd
    text = _compile(fn, x, w, sizes).as_text()
    kernels = re.findall(r"%(grouped_matmul\w*?)\.\d+ = [^\n]*tpu_custom_call", text)
    # forward; the same kernel on the matrices read transposed; the other shape
    want = ["grouped_matmul", "grouped_matmul", "grouped_matmul_t"] if backward \
        else ["grouped_matmul"]
    assert sorted(kernels) == want
    assert "ragged-dot" not in text


_ROUTED_ROWS, _ROUTED_WIDTH = 8192, 2048
# the routed layers' (ff, experts, top_k, held)
_ROUTED_CASES = {"sdar": (768, 128, 8, 16), "qwen3next": (512, 512, 10, 32)}


@pytest.fixture(scope="module", params=list(_ROUTED_CASES))
def routed_layer(request, one_chip):
    """``RoutedExperts`` forward and backward at the SDAR cell's shapes (8,192
    rows of 2,048, top-8 of 128, 16 held: 8,192 expected assignments, the
    default chunks of 9,216 and 2,048) and at Qwen3-Next's (top-10 of 512, 32
    held: 5,120 expected, chunks of 5,760 and 1,280) as the v5e compiler leaves
    it, compiled once a shape for the tests below: ``(compiled text, (first
    chunk, later chunk), top_k)``.  The output's cotangent is an argument, as
    it is inside a model.  (Until PR 47 the loss was ``sum(y ** 2)``, which keeps
    the layer's own output for its backward: with chunks of two sizes the
    compiler's memory-space assignment then repacks a sliced prefetch among the
    kernels' 64 MiB reservations and dies there, a segmentation fault in
    ``BestFitRepacker::Finish`` at SDAR's and Kimi's shapes; the cells' whole
    steps and this program compile: PERF.md section 7.)"""
    from horovod_tpu.parallel.moe import RoutedExperts

    ff, experts, top_k, held = _ROUTED_CASES[request.param]
    rows, d = _ROUTED_ROWS, _ROUTED_WIDTH
    expected = rows * top_k * held // experts
    chunks = (9 * expected // 8, expected // 4)
    layer = RoutedExperts(experts, top_k, d, ff, held=(0, held), dtype=jnp.bfloat16)
    x = _sds((1, rows, d), jnp.bfloat16, one_chip)
    g = _sds((1, rows, d), jnp.float32, one_chip)
    params = jax.eval_shape(
        lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros((1, rows, d), jnp.bfloat16)))
    params = jax.tree_util.tree_map(
        lambda s: _sds(s.shape, s.dtype, one_chip), params["params"])

    def loss(p, x, g):
        y, stats = layer.apply({"params": p}, x)
        return jnp.sum(g * y.astype(jnp.float32)) + stats["aux_loss"]

    with pytest.MonkeyPatch.context() as patch:
        # the layer asks the backend whether its kernels are interpreted
        patch.setattr(jax, "default_backend", lambda: "tpu")
        text = _compile(jax.value_and_grad(loss, argnums=(0, 1)), params, x, g).as_text()
    return text, chunks, top_k


def _moved_shapes(text, kind):
    """The result shapes (layouts dropped) of the compiled text's ``gather``,
    ``scatter`` or ``sort`` instructions."""
    ops = re.findall(r"= (\(.*?\)|\S+) (gather|scatter|sort)\(", text)
    return [re.sub(r"\{[^}]*\}", "", s) for s, k in ops if k == kind]


def test_routed_layer_holds_no_scatter_on_the_chip(routed_layer):
    """No scatter at all (before PR 31: two scatter-adds of 16,384 rows, each a
    sort of its indices, a gather of its updates and a sorted scatter, and
    three scatters of single numbers, into ``s32[num_experts]`` among them)."""
    text, _, _ = routed_layer
    assert _moved_shapes(text, "scatter") == []


def test_routed_layer_moves_rows_by_gathers_on_the_chip(routed_layer):
    """The rows move by bf16 gathers and no float32 gather of rows."""
    text, (first, later), top_k = routed_layer
    rows, d = _ROUTED_ROWS, _ROUTED_WIDTH
    gathers = _moved_shapes(text, "gather")
    assert not [s for s in gathers if s.startswith("f32") and f",{d}]" in s], gathers
    # the first chunk, and the loop over the later ones (recomputed backward)
    assert gathers.count(f"bf16[{first},{d}]") == 2
    assert gathers.count(f"bf16[{later},{d}]") == 3
    assert gathers.count(f"bf16[{rows},{d}]") == 4 * top_k
    # nothing is as long as the chunk was before PR 47: twice the expected rows
    assert f"[{8 * later}," not in text


def test_routed_layer_sorts_only_what_it_asks_for_on_the_chip(routed_layer):
    """No sort but the four the layer asks for (top-k, the assignments by held
    expert, its inverse, the weights' cotangent back)."""
    text, chunks, top_k = routed_layer
    rows = _ROUTED_ROWS
    sorts = _moved_shapes(text, "sort")
    assert not [s for s in sorts if any(f"[{chunk}]" in s for chunk in chunks)], sorts
    assert len(sorts) == 4 and sum(f"[{rows * top_k}]" in s for s in sorts) == 3


def test_routed_layer_s_grouped_products_are_its_kernels_under_experts_on_the_chip(
        routed_layer):
    """The grouped products: three forward, six backward, and the three the
    later chunks' loop holds forward and nine backward (recomputed)."""
    text, _, _ = routed_layer
    assert "ragged-dot" not in text
    kernels = re.findall(r"%(grouped_matmul\w*?)\.\d+ = [^\n]*tpu_custom_call", text)
    assert kernels.count("grouped_matmul") == 3 + 3 + 3 + 6
    assert kernels.count("grouped_matmul_t") == 3 + 3
    # forward and backward alike run inside the ``experts`` scope
    lines = [l for l in text.splitlines() if re.search(r"%grouped_matmul\w*\.\d+ = ", l)]
    assert lines and all("/experts/" in re.search(r'op_name="([^"]*)"', l).group(1)
                         for l in lines)


# -- serving kernels: decode and chunked prefill ------------------------------


def _decode(one_chip, b, s_kv):
    q = _sds((b, 1, 32, 128), jnp.bfloat16, one_chip)
    kv = _sds((b, s_kv, 8, 128), jnp.bfloat16, one_chip)
    lens = _sds((b,), jnp.int32, one_chip)
    return _compile(
        lambda q, k, v, n: flash_decode_attention(q, k, v, n,
                                                  interpret=False),
        q, kv, kv, lens)


@pytest.mark.parametrize("s_kv", [2048, 8192])
def test_flash_decode_compiles(one_chip, s_kv):
    assert _has_kernel(_decode(one_chip, 16, s_kv))


def test_flash_decode_32k_keys_exceed_vmem(one_chip):
    """The ceiling, pinned until someone lifts it: the chunk/decode kernel
    keeps one row's whole gathered K and V resident (BlockSpec
    ``(1, s_k_pad, d)``), and at D128 32768 keys ask for 32 MB of a 16 MB
    scoped-VMEM limit.  About 16k keys is what this chip takes."""
    with pytest.raises(Exception, match=r"(?i)vmem"):
        _decode(one_chip, 8, 32768)


@pytest.mark.parametrize("chunk,s_kv", [(256, 4096), (512, 8192)])
def test_flash_chunk_compiles(one_chip, chunk, s_kv):
    b = 4
    q = _sds((b, chunk, 32, 128), jnp.bfloat16, one_chip)
    kv = _sds((b, s_kv, 8, 128), jnp.bfloat16, one_chip)
    starts = _sds((b,), jnp.int32, one_chip)
    compiled = _compile(
        lambda q, k, v, st: flash_chunk_attention(q, k, v, st,
                                                  interpret=False),
        q, kv, kv, starts)
    assert _has_kernel(compiled)


# -- whole train steps, as chip_smoke.py runs them ----------------------------


def _step_compiled(model, optimizer, mesh, sample, inputs, labels, **step_kw):
    """training.data_parallel_train_step over ``mesh`` (described devices),
    lowered from shapes: replicated state, batch sharded over the axis.
    ``labels``: one (shape, dtype) or a tuple of them."""
    replicated = NamedSharding(mesh, P())
    batch = NamedSharding(mesh, P(WORLD_AXIS))
    state = jax.eval_shape(lambda: training.create_train_state(
        model, optimizer, jax.random.PRNGKey(0), sample))
    state = jax.tree_util.tree_map(
        lambda s: _sds(s.shape, s.dtype, replicated), state)
    step = training.data_parallel_train_step(model, optimizer, mesh=mesh, **step_kw)
    several = isinstance(labels[0][0], tuple)
    return step.lower(
        state, _sds(*inputs, batch),
        tuple(_sds(*one, batch) for one in labels) if several else _sds(*labels, batch)
    ).compile()


def _resnet_step(topo, n_devices, batch, dtype, bn_axis_name=None):
    mesh = Mesh(np.array(topo.devices[:n_devices]), (WORLD_AXIS,))
    model = models.ResNet50(num_classes=1000, dtype=dtype,
                            stem="space_to_depth", bn_axis_name=bn_axis_name)
    return _step_compiled(
        model, optax.sgd(0.1, momentum=0.9), mesh,
        jnp.zeros((1, 224, 224, 3), jnp.float32),
        ((batch, 224, 224, 3), jnp.float32), ((batch,), jnp.int32))


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


def test_resnet50_b128_step_fits_one_chip(topo):
    compiled = _resnet_step(topo, 1, 128, jnp.bfloat16)
    assert _device_bytes(compiled) < HBM_BYTES


def test_resnet50_b512_step_over_four_chips_allreduces(topo):
    compiled = _resnet_step(topo, 4, 512, jnp.bfloat16)
    assert "all-reduce" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("n_devices", [4, 1])
def test_multichip_smoke_step_compiles(topo, n_devices):
    """chip_smoke.py --multichip: float32 sync-BN ResNet-50, global batch
    128, over the four chips and on one of them."""
    compiled = _resnet_step(topo, n_devices, 128, jnp.float32,
                            bn_axis_name=WORLD_AXIS)
    assert _device_bytes(compiled) < HBM_BYTES
    assert ("all-reduce" in compiled.as_text()) == (n_devices > 1)


def test_gpt_small_flash_step_fits_one_chip(topo, monkeypatch):
    """chip_smoke.py phase B(ii): 12 layers, S2048, batch 4, bf16, AdamW.
    The model lets flash_attention pick interpret mode from the backend's
    name, and here that is the CPU: steer it to the kernel for the test."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices[:1]), (WORLD_AXIS,))
    cfg = gpt_small(attention_impl="flash", max_seq_len=2048,
                    dtype=jnp.bfloat16)
    compiled = _step_compiled(
        Transformer(cfg), optax.adamw(1e-3), mesh,
        jnp.zeros((1, 2048), jnp.int32),
        ((4, 2048), jnp.int32), ((4, 2048), jnp.int32))
    assert _has_kernel(compiled)
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.fixture(scope="module")
def internlm2_block_step(topo):
    """One block at InternLM2-1.8B's widths (2048 = 16 x 128 heads, 8 kv
    heads, SwiGLU 8192) through the train step, 1 x 4096 tokens, compiled
    once for the tests below: what ``trace/device.py`` and the benchmark's
    per-kernel metrics read off a capture (docs/TRACING.md, "Device names")."""
    mesh = Mesh(np.array(topo.devices[:1]), (WORLD_AXIS,))
    cfg = TransformerConfig(
        vocab_size=1024, num_layers=1, num_heads=16, num_kv_heads=8,
        head_dim=128, mlp_ratio=4, max_seq_len=4096, dtype=jnp.bfloat16,
        attention_impl="flash")
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        return _step_compiled(
            Transformer(cfg), optax.adamw(1e-3), mesh,
            jnp.zeros((1, 4096), jnp.int32),
            ((1, 4096), jnp.int32), ((1, 4096), jnp.int32))


def _flash_kernels(text):
    return re.findall(r"%(flash_attention\w*)\.\d+ = [^\n]*tpu_custom_call",
                      text)


def test_internlm2_block_step_has_one_name_a_flash_kernel(internlm2_block_step):
    """Each flash kernel is an instruction of its own name (before PR 24 all
    three read ``flash_attention.<n>``, the enclosing jit's)."""
    from horovod_tpu import trace

    kernels = _flash_kernels(internlm2_block_step.as_text())
    assert sorted(kernels) == sorted(trace.DEVICE_KERNELS[:3])


def test_internlm2_block_step_s_phase_scopes_reach_the_op_names(internlm2_block_step):
    """Every phase scope of training.py reaches the instructions' ``op_name``."""
    op_names = set(re.findall(r'op_name="([^"]+)"', internlm2_block_step.as_text()))
    for component in ("jvp(forward)", "transpose(jvp(forward))", "optimizer"):
        assert any(component in o.split("/") for o in op_names), component


def test_internlm2_block_step_s_phase_table_puts_each_kernel_in_its_phase(
        internlm2_block_step):
    from horovod_tpu.trace import device

    text = internlm2_block_step.as_text()
    kernels = _flash_kernels(text)
    table = device.phase_table(text)
    phase_of = {k: table[next(n for n in table if n.startswith(k + "."))][0]
                for k in kernels}
    assert phase_of == {"flash_attention_fwd": "forward",
                        "flash_attention_bwd_dq": "backward",
                        "flash_attention_bwd_dkv": "backward"}


# -- the data-parallel step asks for asynchronous all-reduces (ISSUE 25) ------


def _internlm2_step(topo, monkeypatch, n_devices, depth):
    """The dp4 cell's step (benchmark/configs/internlm2-1.8b.json: every
    width as published, 1 x 4096 tokens a chip, AdamW) through the step
    builder alone, at ``depth`` of the cell's 8 layers: the whole step
    compiles in 90 s and more here, two layers in a third of that, and
    all layers are of one kind."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices[:n_devices]), (WORLD_AXIS,))
    cfg = TransformerConfig(
        vocab_size=92544, num_layers=depth, num_heads=16, num_kv_heads=8,
        head_dim=128, mlp_ratio=4, max_seq_len=32768, dtype=jnp.bfloat16,
        attention_impl="flash")
    return _step_compiled(
        Transformer(cfg), optax.adamw(1e-3), mesh,
        jnp.zeros((1, 4096), jnp.int32),
        ((n_devices, 4096), jnp.int32), ((n_devices, 4096), jnp.int32))


@pytest.fixture(scope="module")
def dp4_step(topo):
    """The dp4 cell's step over the four described chips at two of its layers,
    compiled once for the tests below."""
    with pytest.MonkeyPatch.context() as patch:
        return _internlm2_step(topo, patch, 4, depth=2)


def test_dp4_step_s_options_are_the_builder_s_own(topo):
    """Over four described chips the builder attaches the options itself
    (``spmd_ops.exchange_compile_options``)."""
    from horovod_tpu.ops import spmd_ops

    mesh = Mesh(np.array(topo.devices), (WORLD_AXIS,))
    assert spmd_ops.exchange_compile_options(mesh) \
        == spmd_ops._ASYNC_ALL_REDUCE_OPTIONS


def test_dp4_step_all_reduces_are_asynchronous(dp4_step):
    """The compiled schedule holds asynchronous collective pairs, and of the
    synchronous all-reduces (26 at the cell's depth with no option, one a leaf
    or tuple) only the loss's scalar and at most one tuple of small leaves are
    left."""
    from horovod_tpu.ops.comm_model import compiled_collective_counts

    compiled = dp4_step
    counts = compiled_collective_counts(compiled.as_text())
    assert counts["async_pairs"] >= 1
    assert counts["sync_all_reduces"] <= 2 < 26


def test_dp4_step_fits_a_chip(dp4_step):
    compiled = dp4_step
    assert _device_bytes(compiled) < HBM_BYTES


def test_one_chip_step_gets_no_option(topo, monkeypatch):
    """An axis of one has no exchange to hide: no option, no
    asynchronous pair, the same compiled text as with the helper forced
    to return nothing."""
    from horovod_tpu.ops import spmd_ops
    from horovod_tpu.ops.comm_model import compiled_collective_counts

    mesh = Mesh(np.array(topo.devices[:1]), (WORLD_AXIS,))
    assert spmd_ops.exchange_compile_options(mesh) == {}
    texts = []
    for forced in (False, True):   # one call site: the same stack frames
        if forced:
            monkeypatch.setattr(spmd_ops, "exchange_compile_options",
                                lambda mesh, axis=WORLD_AXIS: {})
        texts.append(_internlm2_step(topo, monkeypatch, 1, depth=1).as_text())
    assert compiled_collective_counts(texts[0]) == {
        "async_pairs": 0, "sync_all_reduces": 0}
    assert texts[0] == texts[1]


# -- layers of two mixers in one compiled step (PR 35) ------------------------


@pytest.fixture(scope="module")
def two_mixer_step(topo):
    """One Gated DeltaNet layer and one gated-attention layer at the widths of
    qwen3-next-80b-a3b-s8192-1chip (8,192 tokens; 32 value heads of 128; 16
    query heads over 2 key/value heads of 256; top-10 of 512 with 32 held, a
    gated shared expert), the train step compiled once for the tests below."""
    import functools

    from horovod_tpu.models.transformer import next_token_loss

    mesh = Mesh(np.array(topo.devices[:1]), (WORLD_AXIS,))
    cfg = TransformerConfig(
        vocab_size=1024, num_layers=2, num_heads=16, num_kv_heads=2, head_dim=256,
        hidden_size=2048, max_seq_len=8192, dtype=jnp.bfloat16, attention_impl="flash",
        rms_norm_eps=1e-6, rope_theta=1e7, tie_word_embeddings=False, qk_norm=True,
        norm_zero_centered=True, attn_output_gate=True, partial_rotary_factor=0.25,
        layer_types=("linear_attention", "full_attention"), linear_num_key_heads=16,
        linear_key_head_dim=128, linear_num_value_heads=32, linear_value_head_dim=128,
        num_experts=512, num_experts_per_tok=10, moe_intermediate_size=512,
        held_experts=(0, 32), num_shared_experts=1, shared_expert_gate=True)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        return _step_compiled(
            Transformer(cfg), optax.adamw(1e-7), mesh, jnp.zeros((1, 8192), jnp.int32),
            ((1, 8192), jnp.int32), ((1, 8192), jnp.int32),
            loss_fn=functools.partial(next_token_loss, aux_coef=0.001))


def _kernel_calls(text):
    """``{kernel name: [op_name of each of its calls]}`` of a compiled text."""
    calls = {}
    for line in text.splitlines():
        m = re.search(r"%(\w+?)\.\d+ = [^\n]*tpu_custom_call", line)
        if m:
            calls.setdefault(m.group(1), []).append(
                re.search(r'op_name="([^"]*)"', line).group(1))
    return calls


def test_two_mixer_step_s_bytes(two_mixer_step):
    compiled = two_mixer_step
    # 6.630 GB at PR 37, whose elementwise passes kept float32 copies; 6.072
    # at PR 38-40 with the mixer made again; since PR 41 the rule's residuals
    # (0.47 GB a linear layer) are kept and nothing of the mixer is made
    # again: 6.545 GB (6.511 with the mixer traced under no checkpoint at all)
    assert _device_bytes(compiled) <= 6_560_000_000


def test_two_mixer_step_s_routed_chunks_follow_the_load(two_mixer_step):
    """PR 47: 5,120 assignments are expected a layer; the first chunk holds
    5,760 sorted rows and a later one 1,280 where every chunk held 10,240.
    Nothing under ``experts`` is 10,240 rows long any more, and the step is
    smaller by more than what the first chunk's backward keeps of the 4,480
    rows that went (x, gate, up and h in bf16, two routed layers: 64.2 MB):
    6,544,909,824 B at the parent, 6,465,671,680 now, 79.2 MB less."""
    compiled = two_mixer_step
    under = [l for l in compiled.as_text().splitlines() if "/experts/" in l]
    assert under and not [l for l in under if "[10240," in l]
    assert [l for l in under if "[5760," in l] and [l for l in under if "[1280," in l]
    assert _device_bytes(compiled) <= 6_544_909_824 - 2 * 4480 * (2048 + 3 * 512) * 2


def test_two_mixer_step_runs_the_rule_s_kernels_once_each_and_makes_nothing_again(
        two_mixer_step):
    """The gated delta rule's carry is Mosaic calls by their names under the scope
    its metric reads (``gated_delta``, never ``gdn``): the rule's forward
    (``L``'s kernel, then the chunks') ONCE (until PR 41 twice: the mixer was
    made again in the backward) and its backward once."""
    text = two_mixer_step.as_text()
    calls = _kernel_calls(text)
    assert [len(calls[k]) for k in ("gated_delta_kkt", "gated_delta_fwd",
                                    "gated_delta_bwd")] == [1, 1, 1]
    assert "rematted_computation" not in text
    for name in ("gated_delta_kkt", "gated_delta_fwd", "gated_delta_bwd"):
        assert all("/linear_attn/" in o and "/gated_delta/" in o and "/gdn/" not in o
                   for o in calls[name]), calls[name]


def test_two_mixer_step_leaves_xla_the_inverse_alone_under_gated_delta(two_mixer_step):
    """Of the rule's products XLA keeps the inverse's alone."""
    text = two_mixer_step.as_text()
    assert all("jit(_block_inverse)" in line for line in _xla_products(text, "/gated_delta/"))


def test_two_mixer_step_runs_the_flash_kernels_once_each_at_256_wide_heads(two_mixer_step):
    """The 256-wide attention is the three flash kernels, once each, under the
    attention layer's scope."""
    text = two_mixer_step.as_text()
    calls = _kernel_calls(text)
    assert [len(calls[k]) for k in ("flash_attention_fwd", "flash_attention_bwd_dq",
                                    "flash_attention_bwd_dkv")] == [1, 1, 1]
    assert all("/layer_1/attn/" in o for k in calls if k.startswith("flash") for o in calls[k])
    assert "bf16[16,8192,256]" in text            # the 256-wide heads reach the kernels


def test_two_mixer_step_runs_the_gdn_passes_once_each_under_gdn(two_mixer_step):
    """Since PR 38 the linear layer is kernels and projections: the input pass
    and the output pass once each way, all under ``gdn``."""
    calls = _kernel_calls(two_mixer_step.as_text())
    assert [len(calls[k]) for k in ("gdn_conv_norm_fwd", "gdn_conv_norm_bwd",
                                    "gdn_gated_norm_fwd", "gdn_gated_norm_bwd")] == [1, 1, 1, 1]
    for name in (k for k in calls if k.startswith("gdn_")):
        assert all("/linear_attn/" in o and "/gdn/" in o and "/gated_delta/" not in o
                   for o in calls[name]), calls[name]


def test_two_mixer_step_s_projections_lie_under_gdn_forward_and_backward(two_mixer_step):
    op_names = set(re.findall(r'op_name="([^"]+)"', two_mixer_step.as_text()))
    assert any("/gdn/" in o and "in_proj_qkvz" in o for o in op_names)
    assert any("/gdn/" in o and "transpose(jvp(forward))" in o for o in op_names)


def test_two_mixer_step_hands_the_rule_q_and_k_16_heads_wide(two_mixer_step):
    for line in two_mixer_step.as_text().splitlines():
        if re.search(r"%gated_delta_(fwd|bwd)\.\d+ = ", line):
            operands = re.search(
                r"operand_layout_constraints=\{(.*?)\}, frontend_attributes", line).group(1)
            assert operands.startswith("bf16[1,8192,2048]{2,1,0}, bf16[1,8192,2048]{2,1,0}, "
                                       "bf16[1,8192,4096]{2,1,0}"), operands


def test_two_mixer_step_moves_no_bf16_activation_under_linear_attn(two_mixer_step):
    """Between the projections XLA moves no activation (bf16 here): no copy,
    reshape, pad, slice or broadcast of XLA's.  What is left under
    /linear_attn/ of these operations is the gates' float32 (B, T, 32) rows a
    chunk and the inverse's own blocks."""
    text = two_mixer_step.as_text()
    moved = [l for l in _entry_instructions(text)
             if "/linear_attn/" in l and re.search(
                 r"= bf16\S+ (copy|reshape|pad|slice|broadcast|transpose|concatenate)\(", l)]
    assert not moved, moved


def test_two_mixer_step_scatters_nothing_in_its_routed_or_linear_layer(two_mixer_step):
    """The step's only scatters are the embedding's and the loss's gradients,
    and the grouped products run under ``experts``."""
    text = two_mixer_step.as_text()
    calls = _kernel_calls(text)
    assert all("/experts/" in o for k in calls if k.startswith("grouped") for o in calls[k])
    scatters = [l for l in text.splitlines() if re.search(r"= (\(.*?\)|\S+) scatter\(", l)]
    assert not [l for l in scatters if "/moe/" in l or "/linear_attn/" in l], scatters


# -- attention that differs by layer in one compiled step (PR 39) --------------

_BY_LAYER_SCOPES = ("attn_window", "attn_full", "attn_rope", "attn_gate")


@pytest.fixture(scope="module")
def by_layer_step(topo):
    """A full-attention layer over a dense feed-forward and a sliding-window layer
    over routed experts at the widths of laguna-xs.2-s8192-1chip (8,192 tokens; 48
    and 64 query heads over 8 key/value heads of 128; window 512; YaRN on half of
    each head of the full layer; a gate a head), the train step compiled once for
    the tests below."""
    import functools

    from horovod_tpu.models.transformer import next_token_loss

    mesh = Mesh(np.array(topo.devices[:1]), (WORLD_AXIS,))
    cfg = TransformerConfig(
        vocab_size=1024, num_layers=2, num_heads=48, num_kv_heads=8, head_dim=128,
        hidden_size=2048, max_seq_len=8192, dtype=jnp.bfloat16, attention_impl="flash",
        rms_norm_eps=1e-6, tie_word_embeddings=False,
        layer_types=("full_attention", "sliding_attention"), sliding_window=512,
        num_heads_per_layer=(48, 64), attn_head_gate=True,
        rope_parameters={
            "full_attention": dict(
                theta=5e5, partial_rotary_factor=0.5, factor=64.0,
                original_max_position_embeddings=4096, beta_fast=64.0, beta_slow=1.0,
                attention_factor=1.4158883083359672),
            "sliding_attention": dict(theta=1e4)},
        intermediate_size=8192, first_dense_layers=1, num_experts=256, num_experts_per_tok=8,
        moe_intermediate_size=512, held_experts=(0, 16), num_shared_experts=1,
        router_scoring="sigmoid", routed_scaling_factor=2.5)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        return _step_compiled(
            Transformer(cfg), optax.adamw(1e-7), mesh, jnp.zeros((1, 8192), jnp.int32),
            ((1, 8192), jnp.int32), ((1, 8192), jnp.int32),
            loss_fn=functools.partial(next_token_loss, aux_coef=0.001))


def test_window_and_full_layers_step_fits_a_chip(by_layer_step):
    compiled = by_layer_step
    assert _device_bytes(compiled) < HBM_BYTES


def test_window_and_full_layers_are_their_own_kernel_calls_on_the_chip(by_layer_step):
    """Laguna's two kinds of layer through the train step: each of
    the three flash kernels is called once unwindowed at 48 heads under
    ``attn_full`` and once at 64 heads under ``attn_window``; the dK/dV kernel
    holds a whole group's q and dO and states its VMEM (48 + 16 MiB at the group
    of 6, 64 + 16 MiB at the group of 8: the compiler's own limit is 16 MiB)."""
    compiled = by_layer_step
    calls = {}
    for line in compiled.as_text().splitlines():
        m = re.search(r"%(flash_attention\w*?)\.\d+ = [^\n]*tpu_custom_call", line)
        if m:
            calls.setdefault(m.group(1), []).append((
                re.search(r'op_name="([^"]*)"', line).group(1),
                re.search(r"operand_layout_constraints=\{(.*?)\}, frontend", line).group(1),
                re.search(r'"scoped_memory_configs":\[(.*?)\]', line).group(1)))
    assert sorted(calls) == ["flash_attention_bwd_dkv", "flash_attention_bwd_dq",
                             "flash_attention_fwd"]
    for name, found in calls.items():
        by_scope = {next(p for p in op.split("/") if p in _BY_LAYER_SCOPES): (op, operands, vmem)
                    for op, operands, vmem in found}
        assert sorted(by_scope) == ["attn_full", "attn_window"], (name, found)
        assert "/layer_0/attn/attn_full/" in by_scope["attn_full"][0]
        assert "/layer_1/attn/attn_window/" in by_scope["attn_window"][0]
        # q (and in dK/dV the whole group's rows) at the layer's own head count
        rows = {"attn_full": "bf16[8,49152,128]", "attn_window": "bf16[8,65536,128]"} \
            if name == "flash_attention_bwd_dkv" else \
            {"attn_full": "bf16[48,8192,128]", "attn_window": "bf16[64,8192,128]"}
        for scope, want in rows.items():
            assert want in by_scope[scope][1], (name, scope, by_scope[scope][1])
        if name == "flash_attention_bwd_dkv":
            assert '"size":"67108864"' in by_scope["attn_full"][2]
            assert '"size":"83886080"' in by_scope["attn_window"][2]


def test_window_and_full_layers_rotary_step_and_gate_lie_under_their_scopes(by_layer_step):
    """The rotary step and the gate lie under their scopes, forward and backward."""
    compiled = by_layer_step
    op_names = set(re.findall(r'op_name="([^"]+)"', compiled.as_text()))
    for scope in ("attn_rope", "attn_gate"):
        for phase in ("jvp(forward)", "transpose(jvp(forward))"):
            assert any(f"/{scope}/" in o and phase in o.split("/") for o in op_names), (scope, phase)


def _instructions(text):
    """``{name: (shape, opcode, operand names, op_name)}`` of the ENTRY computation."""
    found = {}
    for line in _entry_instructions(text):
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\(.*?\)|\S+) ([\w-]+)\((.*?)\)(?:, |$)", line)
        if m:
            op = re.search(r'op_name="([^"]*)"', line)
            found[m.group(1)] = (m.group(2), m.group(3), re.findall(r"%([\w.-]+)", m.group(4)),
                                 op.group(1) if op else "")
    return found


def _made_by(instructions, name):
    """The instruction that made ``name``'s bytes: through bitcasts and tuple reads."""
    while instructions[name][1] in ("bitcast", "get-tuple-element"):
        name = instructions[name][2][0]
    return name


def _rotary_step_is_one_pass_a_direction(text, layers, rows, heads, kv_heads, d, under=""):
    """What PR 40 holds the rotary step to in a compiled step: ``rope_fwd`` and
    ``rope_bwd`` once each for q and for k a layer; under the step's ``op_name``s
    (``under``; the attention module's where the model names no scope) no float32 tensor of
    q's or k's size and none of theirs whose last dimension is half a head; and
    the layouts handed over as they are: each flash forward reads what a
    ``rope_fwd`` wrote, each ``rope_bwd`` what a flash backward kernel wrote, and
    no ``copy`` / ``transpose`` stands before or after a ``rope_*`` call."""
    found = _instructions(text)
    calls = {kind: [n for n in found if n.startswith(kind + ".")]
             for kind in ("rope_fwd", "rope_bwd", "flash_attention_fwd")}
    assert len(calls["rope_fwd"]) == len(calls["rope_bwd"]) == 2 * layers, calls
    sizes = {rows * n * d for n in set(heads) | {kv_heads}}
    for name, (shape, opcode, _, op_name) in found.items():
        if under in op_name and opcode != "custom-call":
            for dtype, dims in re.findall(r"(\w+)\[([\d,]+)\]", shape):
                dims = [int(x) for x in dims.split(",")]
                size = int(np.prod(dims))
                assert not (dtype == "f32" and size in sizes), (name, shape, op_name)
                assert not (dims[-1] == d // 2 and 2 * size in sizes), (name, shape, op_name)
    for name in calls["flash_attention_fwd"]:
        q, k = (_made_by(found, x) for x in found[name][2][1:3])
        assert q.startswith("rope_fwd.") and k.startswith("rope_fwd."), (name, q, k)
    for name in calls["rope_bwd"]:
        source = _made_by(found, found[name][2][0])
        assert source.startswith(("flash_attention_bwd_dq", "flash_attention_bwd_dkv")), \
            (name, source)
    rotary = set(calls["rope_fwd"] + calls["rope_bwd"])
    for name, (shape, opcode, operands, _) in found.items():
        if name in rotary:
            assert found[_made_by(found, operands[0])][1] not in ("copy", "transpose"), name
        elif opcode in ("copy", "transpose"):
            assert not rotary & {_made_by(found, x) for x in operands}, (name, shape)


def test_rotary_step_of_window_and_full_layers_is_one_pass_a_direction(by_layer_step):
    """Laguna's two kinds of layer: whole head at 64 heads, YaRN on 64 of 128
    columns at 48, both over 8 key/value heads (PR 40; before, XLA rotated float32
    copies of q in 64-wide halves: 0.9 GB through HBM a layer and pass)."""
    _rotary_step_is_one_pass_a_direction(
        by_layer_step.as_text(), layers=2, rows=8192, heads=(48, 64), kv_heads=8, d=128,
        under="/attn_rope/")


def test_rotary_step_under_q_k_norms_and_block_diffusion_is_one_pass_a_direction(
        topo, monkeypatch):
    """SDAR's kind of layer at its cell's widths (32 query heads over 4 of 128, q /
    k norms before the rotation, positions ``[0..L) || [0..L)``, the block-diffusion
    kernels) over a dense feed-forward: the model names no scope for the step, so
    it is the attention module that holds no float32 q and no half-head tensor."""
    from horovod_tpu.models.transformer import block_diffusion_loss

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices[:1]), (WORLD_AXIS,))
    cfg = TransformerConfig(
        vocab_size=1024, num_layers=1, num_heads=32, num_kv_heads=4, head_dim=128,
        hidden_size=2048, mlp_ratio=1, max_seq_len=8192, dtype=jnp.bfloat16,
        attention_impl="flash", rms_norm_eps=1e-6, tie_word_embeddings=False, qk_norm=True,
        block_diffusion=4, rope_theta=1e6)
    compiled = _step_compiled(
        Transformer(cfg), optax.adamw(1e-7), mesh, jnp.zeros((1, 8192), jnp.int32),
        ((1, 8192), jnp.int32), (((1, 4096), jnp.int32), ((1, 4096), jnp.float32)),
        loss_fn=block_diffusion_loss)
    assert "flash_attention_bwd_dkv_bd" in compiled.as_text()
    _rotary_step_is_one_pass_a_direction(
        compiled.as_text(), layers=1, rows=8192, heads=(32,), kv_heads=4, d=128,
        under="/attn/")


# sha256 of the step below lowered at the parent of PR 40 (commit 6fbca40), this
# test's own lines run in that tree; since PR 42 with its dK/dV kernel walking
# a program's heads as one, another program by design (before: 8e421982...245f);
# since PR 43 with its forward and dQ kernels walking a program's query tiles as
# one, their sums in VMEM scratch (before: 55dd310a...0715)
_LATENT_STEP_AT_THE_PARENT = "cdba4e1ac5008fa1407a4168b87d537b98e69cbfda892c9ae40796bea49f3c30"


# -- layers of one sublayer: the Nemotron 3 Super cell's own step (PR 48) ------


@pytest.fixture(scope="module")
def nemotronh_cell_step(topo):
    """The train step of nemotron3-super-120b-a12b-s8192-1chip AS THE BENCHMARK
    BUILDS IT (its configuration file through its family: eleven layers
    ``MEMEMEMEM*E`` at the published widths, one of eight head-parallel ranks, 8
    of 512 experts, 8,192 tokens), compiled once for the described v5e."""
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import families, harness

    cell = harness.load_cell("nemotron3-super-120b-a12b-s8192-1chip")
    config, traffic = cell.config, cell.traffic
    mesh = Mesh(np.array(topo.devices[:1]), (WORLD_AXIS,))
    tokens = ((1, traffic["seq_len"]), jnp.int32)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        compiled = _step_compiled(
            families.family(config).model(config), families.optimizer(config["optimizer"]),
            mesh, jnp.zeros((1, traffic["seq_len"]), jnp.int32), tokens, tokens,
            **families.step_options(config, traffic))
    return config, compiled


def test_nemotronh_cell_step_fits_a_chip_at_the_bytes_its_file_states(nemotronh_cell_step):
    """700,862,960 parameters are 11.21 GB at 16 B; the step with 8,192 tokens'
    activations is what the configuration's file states, under the chip's 16 GB
    and over a quarter of it."""
    config, compiled = nemotronh_cell_step
    assert 0.25 * HBM_BYTES < _device_bytes(compiled) < HBM_BYTES
    assert abs(_device_bytes(compiled) - config["compiled_step_bytes"]) < 0.01 * HBM_BYTES


def test_nemotronh_cell_step_runs_each_mamba_kernel_once_a_layer_under_its_scope(
        nemotronh_cell_step):
    """Five Mamba-2 layers: the scan, the convolution and the gated norm are
    Mosaic calls by their names, forward and backward once a layer each,
    under the scopes their metrics read; the flash kernels once (one ``*``
    layer); no block is made again."""
    text = nemotronh_cell_step[1].as_text()
    calls = _kernel_calls(text)
    for kernel, scope in (("ssd_scan", "ssd"), ("conv_bias_silu", "conv"),
                          ("gated_group_norm", "gated_norm")):
        for name in (kernel + "_fwd", kernel + "_bwd"):
            assert len(calls[name]) == 5, (name, len(calls.get(name, ())))
            assert all(f"/mixer/mamba/{scope}/" in o for o in calls[name]), calls[name]
    assert [len(calls[k]) for k in ("flash_attention_fwd", "flash_attention_bwd_dq",
                                    "flash_attention_bwd_dkv")] == [1, 1, 1]
    assert "rematted_computation" not in text


def test_nemotronh_cell_step_s_experts_move_the_latent_s_columns(nemotronh_cell_step):
    """The gathers, the grouped products and the sums under ``experts`` move
    1,024 columns (the latent), never the stream's 4,096; the two latent
    projections lie under scopes of their own outside ``experts``; the first
    chunk holds nine eighths of the 2,816 expected assignments."""
    text = nemotronh_cell_step[1].as_text()
    under = [l for l in text.splitlines() if "/experts/" in l and " = " in l]
    assert under and not [l for l in under if re.search(r"\[\d+,4096\]", l.split(" = ")[1].split("(")[0])]
    assert [l for l in under if "[3168,1024]" in l] and [l for l in under if "[3168,2688]" in l]
    for scope in ("latent_down", "latent_up"):
        lines = [l for l in text.splitlines() if f"/moe/{scope}/" in l]
        assert lines and not [l for l in lines if "/experts/" in l], scope
    assert len(_kernel_calls(text)["grouped_matmul"]) >= 5 * 2


def test_latent_attention_s_step_is_the_parent_s_to_the_byte():
    """Kimi's kind of layer at its head widths (128 + 64 rotary columns a query
    head, ONE 64-wide rotary key for all heads, 'flash'): a 64-wide rotary slice
    of a 192-wide head is no shape the rotary kernels take, so the lowered step
    holds none and is the one the parent lowered (PR 42's and PR 43's walks apart)."""
    import hashlib

    cfg = TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=4, head_dim=192, hidden_size=128, mlp_ratio=1,
        max_seq_len=256, dtype=jnp.bfloat16, attention_impl="flash", kv_lora_rank=64,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, rope_theta=8e5,
        tie_word_embeddings=False)
    tokens = jnp.zeros((1, 256), jnp.int32)
    model, optimizer = Transformer(cfg), optax.adamw(1e-3)
    state = jax.eval_shape(lambda: training.create_train_state(
        model, optimizer, jax.random.PRNGKey(0), tokens))
    step = training.data_parallel_train_step(
        model, optimizer, mesh=Mesh(np.array(jax.devices()[:1]), (WORLD_AXIS,)))
    text = step.lower(state, tokens, tokens).as_text()
    assert "rope_fwd" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == _LATENT_STEP_AT_THE_PARENT


def _lowered_names(cfg, tokens, labels=None, **step_kw):
    """The locations of a model's lowered step: where a ``jax.named_scope`` shows
    before any compiler has seen the program."""
    model, optimizer = Transformer(cfg), optax.adamw(1e-3)
    state = jax.eval_shape(lambda: training.create_train_state(
        model, optimizer, jax.random.PRNGKey(0), tokens[:1]))
    step = training.data_parallel_train_step(
        model, optimizer, mesh=Mesh(np.array(jax.devices()[:1]), (WORLD_AXIS,)), **step_kw)
    return step.lower(state, tokens, tokens if labels is None else labels).as_text(
        debug_info=True)


@pytest.mark.parametrize("name", ["internlm2", "sdar", "kimi", "qwen3next", "by_layer"])
def test_accepted_models_steps_hold_none_of_the_by_layer_scopes(name):
    """The four accepted language models' steps (their cells' kinds of layer at
    small sizes) name no ``attn_window`` / ``attn_full`` / ``attn_rope`` /
    ``attn_gate``: their ``op_name``s, and so their fixtures and what their
    metrics read, are what they were.  A model with a sliding layer names all
    four (the same lowering, so the pattern would have found them)."""
    import functools

    from horovod_tpu.models import transformer

    tokens, labels, kw = jnp.zeros((1, 64), jnp.int32), None, {}
    base = dict(vocab_size=64, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
                max_seq_len=64, dtype=jnp.float32, attention_impl="dot")
    routed = dict(num_experts=8, num_experts_per_tok=2, moe_intermediate_size=8,
                  held_experts=(0, 4), hidden_size=64)
    if name == "sdar":
        base.update(routed, qk_norm=True, block_diffusion=4, tie_word_embeddings=False)
        labels = (jnp.zeros((1, 32), jnp.int32), jnp.ones((1, 32), jnp.float32))
        kw["loss_fn"] = transformer.block_diffusion_loss
    elif name == "kimi":
        base.update(routed, num_kv_heads=None, kv_lora_rank=16, qk_nope_head_dim=8,
                    qk_rope_head_dim=4, v_head_dim=8, first_dense_layers=1,
                    num_shared_experts=2, router_scoring="sigmoid", routed_scaling_factor=2.4,
                    router_selection_bias=True, router_seq_aux=True)
    elif name == "qwen3next":
        base.update(routed, qk_norm=True, norm_zero_centered=True, attn_output_gate=True,
                    partial_rotary_factor=0.25, num_shared_experts=1, shared_expert_gate=True,
                    layer_types=("linear_attention", "full_attention"),
                    linear_num_key_heads=2, linear_key_head_dim=8, linear_num_value_heads=4,
                    linear_value_head_dim=8)
    elif name == "by_layer":
        base.update(routed, layer_types=("full_attention", "sliding_attention"),
                    sliding_window=16, num_heads_per_layer=(4, 6), attn_head_gate=True,
                    rope_parameters={"sliding_attention": dict(theta=1e4)})
    if "num_experts" in base:
        kw.setdefault("loss_fn", functools.partial(transformer.next_token_loss, aux_coef=0.001))
    text = _lowered_names(TransformerConfig(**base), tokens, labels, **kw)
    assert "forward" in text            # the phase scopes are in these locations
    found = {s for s in _BY_LAYER_SCOPES if re.search(rf"\b{s}\b", text)}
    assert found == (set(_BY_LAYER_SCOPES) if name == "by_layer" else set()), found
