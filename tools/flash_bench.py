#!/usr/bin/env python
"""Flash-attention kernel benchmark: pallas vs the XLA dense attention
(materialized S x S logits), plus GQA-ratio and window-sweep legs.

Every leg emits ONE bench-style JSON line on stdout (human summary on
stderr) so the numbers are regression-trackable round over round.  The
GQA legs carry a MODELED attention-bytes column — the HBM traffic the
kernel's BlockSpecs imply (K/V fetched once per KV head, Q/out once per
query head) — so the ``num_heads/num_kv_heads`` K/V-read reduction is
pinned even on a CPU box where wall-clock runs in interpret mode; the
chip legs have not been re-run on today's code.  The window legs carry the
modeled-FLOPs column from the block-skip bounds the kernels use
(``ops.flash_attention.tile_counts``: ``_kb_range`` itself, on numpy;
property-tested in tests/test_gqa_flash.py).

Timing uses chained iterations with a scalar fetch as the sync.
``HVD_TPU_BENCH_ITERS`` / ``HVD_TPU_BENCH_WARMUP``
override the iteration counts (docs/running.md).

Usage:
  flash_bench.py                 # chip kernel legs (dense vs flash)
  flash_bench.py --gqa           # GQA ratio sweep (1/2/4/8)
  flash_bench.py --window        # window sweep at fixed S
  flash_bench.py --cells         # forward, dQ and dK/dV apart at the
                                 #  benchmark cells' shapes (PERF.md §5)
  flash_bench.py --grouped       # the routed experts' grouped product alone,
                                 #  kernel against jax.lax.ragged_dot, DEVICE
                                 #  time from a capture (PERF.md §6, PR 33)
  flash_bench.py --gated-delta   # a Gated DeltaNet layer's input side and
                                 #  rule alone, as the Mosaic kernels and as the
                                 #  jnp functions, DEVICE time from a capture,
                                 #  split into the kernels by name and what is
                                 #  left in XLA (PERF.md §6, PR 35, 36, 38)
  flash_bench.py --rope          # the rotary step of q and k alone at the
                                 #  cells' shapes, as the Mosaic kernel pair and
                                 #  as the jnp function, DEVICE time of the
                                 #  kernels' own events (PERF.md §6, PR 40)
  flash_bench.py --smoke         # tiny interpret-mode pass of all legs
                                 #  (CI: runs on the CPU workflow)
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from horovod_tpu.common.retry import env_int  # noqa: E402
from horovod_tpu.models.transformer import causal_dot_attention  # noqa: E402
from horovod_tpu.ops import grouped_matmul as gm  # noqa: E402
from horovod_tpu.ops.flash_attention import (  # noqa: E402
    _backward_impl, _clamp_blocks, _dkv_heads_a_program, _document_operands,
    _forward_impl, _query_tiles_a_program, flash_attention, tile_counts,
)


# -- traffic / FLOP models ---------------------------------------------------
#
# _clamp_blocks is the KERNEL's clamp (imported, not mirrored), so the
# modeled columns track exactly the tiling the kernels execute.


def _pad(s, m):
    return s + (-s) % m


def _kv_tiles(s, causal, window, block_q, block_k):
    """Total (Q block, K block) tile pairs the forward kernel visits: the
    kernels' own loop bounds (``_kb_range``), summed on the host."""
    bq, bk = _clamp_blocks(s, block_q, block_k)
    return tile_counts(_pad(s, bq), _pad(s, bk), bq, bk, s, causal=causal,
                       window=window)["fwd"][0]


def modeled_attention_bytes(b, s, h, h_kv, d,
                            block_q=256, block_k=256, dtype_bytes=2):
    """Modeled HBM bytes of ONE flash forward: Q and out stream once per
    query head, K/V once per KV head (the GQA BlockSpec sharing), lse is
    one f32 per row.  Returns a dict with the K/V component split out —
    that component is what shrinks by num_heads/num_kv_heads.
    Deliberately window-independent: the kernel streams the whole K/V
    extent per program (the window's block-skip saves COMPUTE, not
    bytes — see modeled_attention_flops)."""
    bq, bk = _clamp_blocks(s, block_q, block_k)
    sq, sk = _pad(s, bq), _pad(s, bk)
    q_bytes = b * h * sq * d * dtype_bytes
    kv_bytes = 2 * b * h_kv * sk * d * dtype_bytes
    out_bytes = b * h * sq * d * dtype_bytes + b * h * sq * 4
    return {
        "q_bytes": q_bytes,
        "kv_bytes": kv_bytes,
        "out_bytes": out_bytes,
        "total_bytes": q_bytes + kv_bytes + out_bytes,
    }


def modeled_repeat_baseline_bytes(b, s, h, h_kv, d,
                                  block_q=256, block_k=256, dtype_bytes=2):
    """The pre-GQA-native baseline: repeat K/V to full heads (read H_kv
    heads, write H heads), then run the MHA kernel (which reads the
    repeated H heads)."""
    m = modeled_attention_bytes(b, s, h, h, d, block_q, block_k,
                                dtype_bytes)
    bq, bk = _clamp_blocks(s, block_q, block_k)
    sk = _pad(s, bk)
    # repeat(1) is a no-op — the MHA "baseline" pays no extra IO
    repeat_io = (0 if h == h_kv
                 else 2 * b * (h_kv + h) * sk * d * dtype_bytes)
    return {**m, "repeat_io_bytes": repeat_io,
            "total_bytes": m["total_bytes"] + repeat_io}


def modeled_attention_flops(b, s, h, d, causal=True, window=None,
                            block_q=256, block_k=256):
    """MXU FLOPs of one flash forward from the block-skip bounds: two
    (bq x d) @ (d x bk) matmuls per visited tile."""
    bq, bk = _clamp_blocks(s, block_q, block_k)
    tiles = _kv_tiles(s, causal, window, block_q, block_k)
    return 4 * b * h * bq * bk * d * tiles


# -- timing ------------------------------------------------------------------


def bench(fn, q, k, v, iters, warmup):
    out = None
    for _ in range(warmup):
        out = fn(q, k, v)
        q = out  # chain so iterations cannot overlap/elide
    float(jnp.sum(out[0, 0, 0]))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(q, k, v)
        q = out
    float(jnp.sum(out[0, 0, 0]))
    return (time.perf_counter() - t0) / iters * 1e3


def _qkv(b, s, h, h_kv, d, dtype=jnp.bfloat16, seed=0):
    """``d``: one width, or ``(d_qk, d_v)`` (latent attention's 192 / 128)."""
    d_qk, d_v = d if isinstance(d, tuple) else (d, d)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    mk = lambda kk, heads, width: jax.random.normal(
        kk, (b, s, heads, width), jnp.float32).astype(dtype)
    return mk(ks[0], h, d_qk), mk(ks[1], h_kv, d_qk), mk(ks[2], h_kv, d_v)


def _emit(rec, human):
    rec["backend"] = jax.default_backend()
    print(json.dumps(rec))
    print(human, file=sys.stderr)


# -- legs --------------------------------------------------------------------


def leg_kernel(shapes, iters, warmup, interpret):
    """Dense (materialized logits) vs flash at MHA shapes."""
    dense = jax.jit(causal_dot_attention)
    for (b, s, h, d) in shapes:
        q, k, v = _qkv(b, s, h, h, d)
        t_dense = bench(dense, q, k, v, iters, warmup)
        t_flash = bench(
            lambda a, b_, c: flash_attention(a, b_, c, interpret=interpret),
            q, k, v, iters, warmup,
        )
        flops = 2 * b * h * s * s * d  # two matmuls, halved by causality
        _emit(
            {"bench": "flash_kernel", "b": b, "s": s, "h": h, "d": d,
             "dense_ms": round(t_dense, 3), "flash_ms": round(t_flash, 3),
             "speedup": round(t_dense / t_flash, 3),
             "flash_tflops": round(flops / (t_flash / 1e3) / 1e12, 2)},
            f"B{b} S{s} H{h} D{d}: dense {t_dense:7.2f} ms  "
            f"flash {t_flash:7.2f} ms  speedup {t_dense / t_flash:4.2f}x",
        )


def leg_gqa(b, s, h, d, ratios, iters, warmup, interpret):
    """GQA ratio sweep: kernel-native grouped K/V vs the repeat baseline
    (materialize K/V at full heads, then the MHA kernel)."""
    for ratio in ratios:
        if h % ratio:
            continue
        h_kv = h // ratio
        q, k, v = _qkv(b, s, h, h_kv, d)

        def native(q_, k_, v_):
            return flash_attention(q_, k_, v_, interpret=interpret)

        @jax.jit
        def repeat_baseline(q_, k_, v_):
            k_ = jnp.repeat(k_, ratio, axis=2)
            v_ = jnp.repeat(v_, ratio, axis=2)
            return flash_attention(q_, k_, v_, interpret=interpret)

        t_native = bench(native, q, k, v, iters, warmup)
        t_repeat = bench(repeat_baseline, q, k, v, iters, warmup)
        m = modeled_attention_bytes(b, s, h, h_kv, d)
        m_rep = modeled_repeat_baseline_bytes(b, s, h, h_kv, d)
        _emit(
            {"bench": "flash_gqa", "b": b, "s": s, "h": h, "h_kv": h_kv,
             "d": d, "ratio": ratio,
             "native_ms": round(t_native, 3),
             "repeat_ms": round(t_repeat, 3),
             "kv_bytes": m["kv_bytes"],
             "kv_bytes_repeat": m_rep["kv_bytes"] + m_rep["repeat_io_bytes"],
             "attn_bytes": m["total_bytes"],
             "attn_bytes_repeat": m_rep["total_bytes"],
             "bytes_ratio": round(m_rep["total_bytes"] / m["total_bytes"],
                                  3)},
            f"GQA {h}/{h_kv} (x{ratio}): native {t_native:7.2f} ms  "
            f"repeat {t_repeat:7.2f} ms  "
            f"modeled bytes {m['total_bytes']:.3g} vs "
            f"{m_rep['total_bytes']:.3g}",
        )


def leg_window(b, s, h, d, windows, iters, warmup, interpret,
               block_q=256, block_k=256):
    """Window sweep at fixed S: block-skip compute scaling."""
    full_flops = modeled_attention_flops(b, s, h, d, causal=True,
                                         window=None, block_q=block_q,
                                         block_k=block_k)
    for w in windows:
        q, k, v = _qkv(b, s, h, h, d)
        t = bench(
            lambda a, b_, c: flash_attention(a, b_, c, window=w,
                                             block_q=block_q,
                                             block_k=block_k,
                                             interpret=interpret),
            q, k, v, iters, warmup,
        )
        flops = modeled_attention_flops(b, s, h, d, causal=True, window=w,
                                        block_q=block_q, block_k=block_k)
        _emit(
            {"bench": "flash_window", "b": b, "s": s, "h": h, "d": d,
             "window": w, "ms": round(t, 3), "modeled_flops": flops,
             "flops_frac": round(flops / full_flops, 4)},
            f"window {str(w):>6}: {t:7.2f} ms  "
            f"modeled flops {flops / full_flops:5.1%} of full",
        )


# (b, s, h, h_kv, d, causal, block_diffusion[, window[, documents]]) of the
# benchmark's transformer cells (benchmark/configs/): what a layer's attention
# sees.  ``d`` = (d_qk, d_v) where keys and values differ in width (latent
# attention: 128 + 64 rotary against 128); ``documents``: the lengths of a
# packed row's documents (benchmark/traffic/pack8192-1chip.json), whose ids
# the call takes and whose tiles the counts are (``tile_counts(documents=)``)
_PACK8192 = (557, 2909, 131, 1087, 293, 1523, 72, 811, 241, 389, 179)
CELL_SHAPES = {
    "internlm2-1.8b-s4096-1chip": (1, 4096, 16, 8, 128, True, None),
    "sdar-30b-a3b-bd4-s4096-1chip": (1, 8192, 32, 4, 128, False, (4096, 4)),
    "kimi-vl-a3b-s8192-1chip": (1, 8192, 16, 16, (192, 128), True, None),
    "qwen3-next-80b-a3b-s8192-1chip": (1, 8192, 16, 2, 256, True, None),
    "laguna-xs.2-s8192-1chip/sliding": (1, 8192, 64, 8, 128, True, None, 512),
    "laguna-xs.2-s8192-1chip/full": (1, 8192, 48, 8, 128, True, None),
    "mellum2-12b-a2.5b-pack8192-1chip/sliding": (
        1, 8192, 32, 4, 128, True, None, 1024, _PACK8192),
    "mellum2-12b-a2.5b-pack8192-1chip/full": (
        1, 8192, 32, 4, 128, True, None, None, _PACK8192),
}


def leg_cells(shapes, iters, warmup, interpret, block=256):
    """The three training kernels apart, one layer's call each: the dQ and
    the dK/dV program are the two halves of ``_backward_impl`` (a jit
    that returns one of them drops the other kernel).  Beside each time, the
    tile visits and the loop iterations they take (``tile_counts``: a head's,
    and for dK/dV those of the heads a program holds), what a program holds
    and walks as one (``query_tiles_a_program`` consecutive query tiles of a
    head in the forward and dQ kernels, ``heads_a_program`` query heads in
    dK/dV: the kernels' own rules) with a program's mean visits, and the time a
    tile visit, which PERF.md §5 holds against the 0.085 us a 256 x 256 x 128
    product needs on the v5e's MXU."""
    for cell, (b, s, h, h_kv, d, causal, bd, *more) in shapes.items():
        window, lengths = (*more, None, None)[:2]
        q, k, v = _qkv(b, s, h, h_kv, d)
        g = _qkv(b, s, h, h, d, seed=1)[2]  # dO: every query head, as wide as v
        bq, bk = _clamp_blocks(s, block, block)
        ids = None if lengths is None else np.tile(
            np.repeat(np.arange(len(lengths)), lengths), (b, 1))
        # with ids: the bounds made inside each timed program, as a step's are
        documents = lambda: None if ids is None else _document_operands(
            ids, bq, bk)

        def fwd(q, k, v):
            return _forward_impl(q, k, v, causal, block, block, interpret,
                                 with_lse=True, window=window, bd=bd,
                                 documents=documents())

        def bwd(q, k, v, out, lse, g):
            return _backward_impl(q, k, v, out, lse, g, causal, block, block,
                                  interpret, window=window, bd=bd,
                                  documents=documents())

        def timed(fn, *args):
            fn = jax.jit(fn)
            for _ in range(warmup):
                out = fn(*args)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn(*args)
            jax.block_until_ready(out)  # one device queue: calls run in turn
            return (time.perf_counter() - t0) / iters * 1e3

        out, lse = jax.jit(fwd)(q, k, v)
        ms = {"fwd": timed(fwd, q, k, v),
              "bwd_dq": timed(lambda *a: bwd(*a)[0], q, k, v, out, lse, g),
              "bwd_dkv": timed(lambda *a: bwd(*a)[1:], q, k, v, out, lse, g)}
        mask = dict(s_q=_pad(s, bq), s_k=_pad(s, bk), block_q=bq, block_k=bk,
                    seq_len=s, causal=causal, window=window, bd=bd)
        # what a program walks as one, by the kernels' own rules: its counts
        # are those of the query tiles, or of the query heads, that it holds
        query_tiles = _query_tiles_a_program(**mask)
        heads = _dkv_heads_a_program(
            h // h_kv, mask["s_q"], q.shape[-1], v.shape[-1],
            q.dtype.itemsize)[0]
        # with ids, the visits a head makes on that layout (every row's here)
        tiles = tile_counts(heads_a_program=heads,
                            query_tiles_a_program=query_tiles,
                            documents=None if ids is None else ids[0], **mask)
        walks = {"fwd": b * h, "bwd_dq": b * h, "bwd_dkv": b * h // heads}
        q_programs = mask["s_q"] // (query_tiles * bq)
        programs = {"fwd": q_programs, "bwd_dq": q_programs,
                    "bwd_dkv": mask["s_k"] // bk}
        rec = {"bench": "flash_cells", "cell": cell, "b": b, "s": s, "h": h,
               "h_kv": h_kv, "d": d, "window": window, "block": [bq, bk],
               "documents": None if lengths is None else len(lengths),
               "query_tiles_a_program": query_tiles, "heads_a_program": heads}
        for name, t in ms.items():
            visited, iterations = tiles[name]
            rec[name + "_ms"] = round(t, 4)
            rec[name + "_tiles"] = [visited, iterations]
            rec[name + "_a_program"] = [
                round(visited / programs[name], 2),
                round(iterations / programs[name], 2)]
            rec[name + "_us_per_tile"] = round(
                t * 1e3 / (walks[name] * visited), 4)
        _emit(rec, f"{cell}: " + "  ".join(
            f"{n} {t:7.3f} ms" for n, t in ms.items())
            + f"  tiles a head {tiles['fwd'][0]} in {tiles['fwd'][1]} iterations,"
            f" {query_tiles} query tiles a program: "
            f"{rec['fwd_a_program'][0]} visits in {rec['fwd_a_program'][1]}")


# (rows of a chunk, k, n, groups): the routed cells' products, a chunk twice
# the expected load (parallel/moe.py), and the same at 8 x the rows a group,
# which is what an expert of a deployment sees (PERF.md §4)
GROUPED_SHAPES = {
    "kimi-vl-a3b gate/up": (12288, 2048, 1408, 8),
    "kimi-vl-a3b down": (12288, 1408, 2048, 8),
    "sdar-30b-a3b gate/up": (16384, 2048, 768, 16),
    "sdar-30b-a3b down": (16384, 768, 2048, 16),
    "kimi-vl-a3b gate/up x8 rows": (98304, 2048, 1408, 8),
    "sdar-30b-a3b gate/up x8 rows": (131072, 2048, 768, 16),
    "qwen3-next-80b-a3b gate/up": (10240, 2048, 512, 32),
    "qwen3-next-80b-a3b down": (10240, 512, 2048, 32),
}

# (b, t, key heads, value heads, dk, dv): a linear layer's input side and gated
# delta rule in the benchmark's cell (benchmark/configs/qwen3-next-80b-a3b.json)
GATED_DELTA_SHAPES = {
    "qwen3-next-80b-a3b-s8192-1chip": (1, 8192, 16, 32, 128, 128),
}


def leg_gated_delta(shapes, iters, warmup, interpret, chunk=64):
    """A Gated DeltaNet layer from the projection's rows to the rule's output:
    the convolution, SiLU, L2 norms and the gated delta rule, forward and
    forward + backward, as the Mosaic kernels (``ops/gdn_kernels.py``, then
    ``ops/gated_delta.py`` with q and k at the key heads) and as the ``jnp``
    functions 'dot' models run (XLA's passes, the rule's chunk-local products
    and a ``jax.numpy`` scan), each its own ``jit`` inside ONE capture: DEVICE
    time a call from the capture's ``XLA Modules`` events (``null`` off the
    chip), the largest gap between the two outputs and, inside each program,
    the split of its operations' time into the kernels by name
    (``gdn_conv_norm_*``, ``gated_delta_*``) and what is left in XLA (the
    unit-triangular inverse, the layouts, and for ``jnp`` everything)."""
    from horovod_tpu.models.transformer import causal_depthwise_conv, l2_unit
    from horovod_tpu.ops.gated_delta import gated_delta_rule
    from horovod_tpu.ops.gdn_kernels import gdn_conv_norm

    programs, records = {}, []
    for name, (b, t, hk, h, dk, dv) in shapes.items():
        keys = jax.random.split(jax.random.PRNGKey(0), 5)
        key_dim = hk * dk
        qkv = jax.random.normal(keys[0], (b, t, 2 * key_dim + h * dv)).astype(
            jnp.bfloat16)
        taps = jax.random.uniform(keys[1], (4, qkv.shape[-1]), jnp.float32, -0.5, 0.5)
        beta = jax.nn.sigmoid(jax.random.normal(keys[2], (b, t, h)))
        g = -0.03 * jnp.exp(jax.random.normal(keys[3], (b, t, h)))
        do = jax.random.normal(keys[4], (b, t, h, dv)).astype(jnp.bfloat16)
        args = (qkv, taps, g, beta)
        rec = {"bench": "gated_delta", "shape": name, "b": b, "t": t, "key_heads": hk,
               "heads": h, "d_k": dk, "d_v": dv, "chunk": chunk, "variants": {}}
        outs = {}
        for impl in ("kernel", "jnp"):
            def fwd(qkv, taps, g, beta, impl=impl):
                if impl == "kernel":
                    q, k, v = gdn_conv_norm(
                        qkv, taps, key_heads=hk, key_head_dim=dk, value_heads=h,
                        value_head_dim=dv, interpret=interpret)
                else:
                    mixed = causal_depthwise_conv(qkv, taps)
                    q, k, v = (mixed[..., :key_dim], mixed[..., key_dim:2 * key_dim],
                               mixed[..., 2 * key_dim:])
                q, k = q.reshape(b, t, hk, dk), k.reshape(b, t, hk, dk)
                if impl == "jnp":
                    q, k = l2_unit(q, dk ** -0.5), l2_unit(k)
                return gated_delta_rule(q, k, v.reshape(b, t, h, dv), g, beta,
                                        chunk=chunk, impl=impl, interpret=interpret)

            def fwd_bwd(*a, fwd=fwd):
                return jax.grad(lambda *x: jnp.sum(
                    fwd(*x).astype(jnp.float32) * do.astype(jnp.float32)),
                    argnums=(0, 1, 2, 3))(*a)

            for what, fn in (("fwd", fwd), ("fwd_bwd", fwd_bwd)):
                fn.__name__ = "gd%d_%s_%s" % (len(records), impl, what)
                programs[(len(records), impl, what)] = (jax.jit(fn), args)
            outs[impl] = programs[(len(records), impl, "fwd")][0](*args).astype(
                jnp.float32)
        scale = float(jnp.max(jnp.abs(outs["jnp"])))
        rec["kernel_against_jnp_gap"] = float(
            jnp.max(jnp.abs(outs["kernel"] - outs["jnp"]))) / scale
        records.append(rec)
    _fill_variants(records, captured_ms(programs, iters, warmup,
                                        ("gated_delta_", "gdn_conv_norm_")))
    for rec in records:
        _emit(rec, f"{rec['shape']}: " + "  ".join(
            f"{impl} {p}" for impl, p in rec["variants"].items()))


# heads, key/value heads, head width, rotated columns at 1 x rows
ROPE_SHAPES = {
    "laguna-xs.2-s8192-1chip sliding": (8192, 64, 8, 128, 128),
    "laguna-xs.2-s8192-1chip full": (8192, 48, 8, 128, 64),
    "sdar-30b-a3b-bd4-s4096-1chip": (8192, 32, 4, 128, 128),
    "internlm2-1.8b-s4096-1chip": (4096, 16, 8, 128, 128),
    "qwen3-next-80b-a3b-s8192-1chip": (8192, 16, 2, 256, 64),
}


def leg_rope(shapes, iters, warmup, interpret, row_tiles=(None,)):
    """The rotary step of one layer's q and k, forward and forward + backward,
    as the kernel pair (``ops/rope_kernel.py``, at each of ``row_tiles``; None:
    the tile the shapes give) and as ``rope``: DEVICE time a program and its
    split into ``rope_fwd`` / ``rope_bwd`` by their own events and what is left
    in XLA (the tables; alone, also the copies into and out of the head-major
    layout, which a model's neighbours make unnecessary)."""
    from horovod_tpu.models.transformer import rope, rope_frequencies
    from horovod_tpu.ops import rope_kernel

    programs, records = {}, []
    for name, (s, h, h_kv, d, rot) in shapes.items():
        keys = jax.random.split(jax.random.PRNGKey(0), 2)
        q = jax.random.normal(keys[0], (1, s, h, d)).astype(jnp.bfloat16)
        k = jax.random.normal(keys[1], (1, s, h_kv, d)).astype(jnp.bfloat16)
        positions, freqs = jnp.arange(s)[None], rope_frequencies(rot, 1e4)
        rec = {"bench": "rope", "shape": name, "rows": s, "heads": h, "kv_heads": h_kv,
               "head_dim": d, "rot": rot, "variants": {},
               "floor_ms": round(4 * s * (h + h_kv) * d / 819e9 * 1e3, 4)}
        for tile in row_tiles + ("jnp",):
            def fwd(q, k, tile=tile, rot=rot, positions=positions, freqs=freqs):
                if tile == "jnp":
                    turn = lambda x: jnp.concatenate(
                        [rope(x[..., :rot], positions, inv_freq=freqs), x[..., rot:]], -1)
                    return turn(q), turn(k)
                c, sn = rope_kernel.tables(positions, freqs, rot)
                return tuple(rope_kernel.rotate(x, c, sn, rot, row_tile=tile,
                                                interpret=interpret) for x in (q, k))

            def fwd_bwd(q, k, fwd=fwd):
                return jax.grad(lambda *x: sum(
                    jnp.sum(o.astype(jnp.float32)) for o in fwd(*x)), argnums=(0, 1))(q, k)

            for what, fn in (("fwd", fwd), ("fwd_bwd", fwd_bwd)):
                fn.__name__ = "rope%d_%s_%s" % (len(records), tile, what)
                programs[(len(records), str(tile), what)] = (jax.jit(fn), (q, k))
        records.append(rec)
    _fill_variants(records, captured_ms(programs, iters, warmup, "rope_"))
    for rec in records:
        _emit(rec, f"{rec['shape']}: " + "  ".join(
            f"{tile} {p}" for tile, p in rec["variants"].items()))


def routed_sizes(rows, groups, seed=0):
    """Group sizes as a first step's routing leaves them: half the chunk's
    rows held, unequal (the largest group about 1.15 x the mean: PERF.md §6,
    PR 32) and no multiple of anything."""
    import numpy as np

    rng = np.random.default_rng(seed)
    share = np.maximum(1.0 + 0.1 * rng.standard_normal(groups), 0.5)
    return rng.multinomial(rows // 2, share / share.sum()).astype(np.int32)


def module_ms(capture_dir):
    """Mean DEVICE time of each program that ran during a capture, by the
    name of its ``jit`` (the ``XLA Modules`` line of the TPU plane); empty
    off the chip, where a capture has no such line."""
    import re

    from jax.profiler import ProfileData

    from horovod_tpu.trace import device as _device

    runs = {}
    data = ProfileData.from_file(_device.find_xplane(capture_dir))
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for e in line.events:
                name = re.sub(r"\(\d+\)$", "", e.name)
                runs.setdefault(name, []).append(e.duration_ns / 1e6)
    return {name: sum(ms) / len(ms) for name, ms in runs.items()}


def kernel_split_ms(capture_dir, module, prefix):
    """``{kernel name: ms, ..., "xla": ms}`` a run of the program ``module``:
    the DEVICE time of its operations (the capture's ``XLA Ops`` events inside
    the program's runs), those whose instruction starts with ``prefix`` (one, or
    a tuple of them) by their kernel's name and every other one under ``xla``."""
    import re

    from horovod_tpu.trace import device as _device

    split = {}
    events = _device.device_events(
        capture_dir, module=r"^%s(\(\d+\))?$" % re.escape(module))
    for dev in events.values():
        for name, _, ns in dev["ops"]:
            key = name.rsplit(".", 1)[0] if name.startswith(prefix) else "xla"
            split[key] = split.get(key, 0.0) + ns / 1e6 / dev["steps"] / len(events)
    return {k: round(v, 4) for k, v in sorted(split.items())}


def captured_ms(programs, iters, warmup, kernels=None):
    """``programs`` ``{key: (jitted function, its arguments)}``, each warmed and
    then run ``iters`` times inside ONE capture: ``{key: (mean DEVICE ms a call,
    split)}`` from the capture's ``XLA Modules`` events (``None`` off the chip,
    where a capture has no such line); ``split``: ``kernel_split_ms`` of the
    program's operations by the kernel-name prefix ``kernels`` (``None`` where
    not asked for, or with no device time)."""
    import shutil
    import tempfile

    capture_dir = tempfile.mkdtemp(prefix="flash_bench_capture_")
    for fn, args in programs.values():
        for _ in range(warmup):
            jax.block_until_ready(fn(*args))
    jax.profiler.start_trace(capture_dir)
    try:
        for fn, args in programs.values():
            for _ in range(iters):
                out = fn(*args)
            jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    ms, timed = module_ms(capture_dir), {}
    for key, (fn, _) in programs.items():
        t = ms.get("jit_" + fn.__name__)
        timed[key] = (round(t, 4) if t else None,
                      kernel_split_ms(capture_dir, "jit_" + fn.__name__, kernels)
                      if t and kernels else None)
    shutil.rmtree(capture_dir, ignore_errors=True)
    return timed


def _fill_variants(records, timed):
    """``captured_ms``'s ``{(record, variant, program): (ms, split)}`` into each
    record's ``variants``."""
    for (i, name, what), (t, split) in timed.items():
        variant = records[i]["variants"].setdefault(name, {})
        variant[what + "_device_ms"] = t
        if split:
            variant[what + "_split_ms"] = split


def grouped_variants(interpret):
    """``{variant: {product: function of (x, w, dy, sizes)}}``: the three
    products of one expert matrix (forward, the gradient with respect to the
    rows, and to the matrices; what a program does not use the compiler
    drops) as ``jax.lax.ragged_dot`` and autodiff make them, and as the
    layer's kernels do at the tiles the shapes give (``routed_sizes`` holds
    half the rows: the expected group is half the rows over the groups)."""
    def three(product):
        return {
            "fwd": lambda x, w, dy, sizes: product(x, w, sizes),
            "dx": lambda x, w, dy, sizes: jax.vjp(
                lambda x: product(x, w, sizes), x)[1](dy)[0],
            "dw": lambda x, w, dy, sizes: jax.vjp(
                lambda w: product(x, w, sizes), w)[1](dy)[0],
        }

    return {"ragged_dot": three(jax.lax.ragged_dot),
            "kernel": three(lambda x, w, sizes: gm.grouped_matmul(
                x, w, sizes, expected=x.shape[0] // 2 // w.shape[0],
                interpret=interpret))}


def leg_grouped(shapes, iters, warmup, interpret):
    """The routed experts' grouped product alone: each of its three products
    as its own ``jit``, all inside ONE capture, DEVICE time a call read from
    the capture's ``XLA Modules`` events (the host clock reads 17-22 % over
    it: PERF.md §6, PR 32).  Beside each time, the share of the bf16 MXU
    peak the held rows' FLOPs make of it.  Off the chip there is no device
    time (``null``); the kernels' results are held against ``ragged_dot``'s
    on the held rows either way."""
    import numpy as np

    programs, records = {}, []
    for shape_name, (rows, k, n, groups) in shapes.items():
        sizes = routed_sizes(rows, groups)
        held = int(sizes.sum())
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        x = jax.random.normal(keys[0], (rows, k), jnp.float32).astype(jnp.bfloat16)
        w = (jax.random.normal(keys[1], (groups, k, n), jnp.float32)
             / k ** 0.5).astype(jnp.bfloat16)
        dy = jax.random.normal(keys[2], (rows, n), jnp.float32).astype(jnp.bfloat16)
        args = (x, w, dy, jnp.asarray(sizes))
        rec = {"bench": "grouped_matmul", "shape": shape_name, "rows": rows,
               "k": k, "n": n, "groups": groups, "held_rows": held,
               "sizes_min_max": [int(sizes.min()), int(sizes.max())],
               "tiles": list(gm.tiles(rows, k, n, rows // 2 // groups, x.dtype)),
               "flops": 2 * held * k * n, "variants": {}}
        expected = {}
        for variant, products in grouped_variants(interpret).items():
            for product, fn in products.items():
                fn.__name__ = "g%d_%s_%s" % (len(records), variant, product)
                fn = jax.jit(fn)
                out = fn(*args)
                # the rows beyond the groups are undefined: compare the held
                valid = out if product == "dw" else out[:held]
                valid = np.asarray(valid.astype(jnp.float32))
                if variant == "ragged_dot":
                    expected[product] = valid
                else:
                    scale = np.abs(expected[product]).max()
                    gap = float(np.abs(valid - expected[product]).max() / scale)
                    rec["variants"].setdefault(variant, {})[product + "_gap"] = gap
                programs[(len(records), variant, product)] = (fn, args)
        records.append(rec)
    timed = captured_ms(programs, iters, warmup)
    peak = 197e12  # one v5e chip, bf16 (benchmark/peaks.json)
    for i, rec in enumerate(records):
        for (j, variant, product), (t, _) in timed.items():
            if j != i:
                continue
            out = rec["variants"].setdefault(variant, {})
            out[product + "_device_ms"] = t
            out[product + "_mxu_share"] = (
                round(rec["flops"] / (t * 1e-3) / peak, 4) if t else None)
        times = {v: sum(p.get(q + "_device_ms") or 0 for q in ("fwd", "dx", "dw"))
                 for v, p in rec["variants"].items()}
        _emit(rec, f"{rec['shape']}: " + "  ".join(
            f"{v} {t:6.3f} ms" for v, t in times.items())
            + "  (fwd + dx + dw, device time)")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--gqa", action="store_true")
    ap.add_argument("--window", action="store_true")
    ap.add_argument("--kernel", action="store_true")
    ap.add_argument("--cells", action="store_true")
    ap.add_argument("--grouped", action="store_true")
    ap.add_argument("--gated-delta", action="store_true")
    ap.add_argument("--rope", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny interpret-mode pass of every leg (CI)")
    args = ap.parse_args(argv)
    from horovod_tpu.utils import compile_cache

    compile_cache.enable()

    iters = env_int("HVD_TPU_BENCH_ITERS", 20)
    warmup = env_int("HVD_TPU_BENCH_WARMUP", 3)
    print("backend:", jax.default_backend(), file=sys.stderr)

    if args.smoke:
        # interpret mode, tiny shapes: proves the legs + JSON schema on
        # any box; chip numbers come from the un-smoked legs on TPU
        leg_kernel([(1, 256, 2, 64)], 2, 1, True)
        leg_gqa(1, 256, 4, 64, (1, 2, 4), 2, 1, True)
        leg_window(1, 384, 2, 64, (None, 128), 2, 1, True,
                   block_q=128, block_k=128)
        leg_cells({"causal-gqa": (1, 512, 4, 2, 32, True, None),
                   "packed": (1, 512, 4, 2, 32, True, None, 192,
                              (60, 200, 30, 222)),
                   "block-diffusion": (1, 512, 4, 1, 32, False, (256, 4)),
                   "latent": (1, 512, 4, 4, (48, 32), True, None)},
                  2, 1, True, block=128)
        leg_grouped({"skewed": (192, 256, 384, 4)}, 1, 1, True)
        leg_gated_delta({"tiny": (1, 80, 1, 2, 16, 16)}, 1, 1, True, chunk=16)
        leg_rope({"tiny": (32, 4, 2, 128, 64)}, 1, 1, True)
        return 0

    run_all = not (args.gqa or args.window or args.kernel or args.cells
                   or args.grouped or args.gated_delta or args.rope)
    if args.kernel or run_all:
        leg_kernel([(4, 1024, 8, 128), (4, 2048, 8, 128),
                    (2, 4096, 8, 128)], iters, warmup, None)
    if args.gqa or run_all:
        leg_gqa(4, 2048, 8, 128, (1, 2, 4, 8), iters, warmup, None)
    if args.window or run_all:
        leg_window(2, 4096, 8, 128,
                   (None, 2048, 1024, 512, 256), iters, warmup, None)
    if args.cells or run_all:
        leg_cells(CELL_SHAPES, iters, warmup, None)
    if args.grouped:
        leg_grouped(GROUPED_SHAPES, iters, warmup, None)
    if args.gated_delta:
        leg_gated_delta(GATED_DELTA_SHAPES, iters, warmup, None)
    if args.rope:
        leg_rope(ROPE_SHAPES, iters, warmup, None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
