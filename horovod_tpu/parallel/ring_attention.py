"""Ring attention: exact attention (causal or bidirectional) over a
sequence-sharded mesh axis.

No reference analog — the reference is data-parallel only and explicitly
lacks sequence/context parallelism (SURVEY.md §5.7); it ships only the
primitives (alltoall, allgather).  This module is the long-context pillar
of the framework: the sequence dimension is sharded over a mesh axis, each
chip keeps its Q shard resident, and K/V shards rotate around the ring via
``lax.ppermute`` (ICI neighbor exchange) while a flash-style online softmax
accumulates exact results — memory per chip is O(S/n), enabling contexts
that cannot fit a single chip's HBM.  (Liu et al., "Ring Attention with
Blockwise Transformers", 2023 — PAPERS.md.)

TPU mapping: each of the n steps is one ppermute (ICI hop, overlappable
with the block matmuls by XLA's latency-hiding scheduler) plus two MXU
matmuls in the compute dtype; softmax statistics stay in float32.

Sliding windows compose with the ring (both impls): masks act on GLOBAL
positions, and for a CAUSAL window the rotation itself is truncated —
ring steps whose K shard lies wholly outside every chip's window are
never taken (``ring_window_steps``), so both comms and compute degrade
to O(S·window/S_local) steps instead of O(n).

GQA: ``k``/``v`` may carry fewer (kv) heads than ``q`` — the dense path
groups the einsums and the flash path's kernels are GQA-native
(ops/flash_attention.py), so only H_kv heads of K/V rotate around the
ring: ring comms shrink by num_heads/num_kv_heads too.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..common.topology import WORLD_AXIS

_NEG_INF = -1e30


def ring_window_steps(n: int, s_local: int, causal: bool = True,
                      window: Optional[int] = None) -> int:
    """Number of ring steps (including the resident/diagonal step 0)
    that can contribute any in-window (q, k) pair on any chip.

    For a CAUSAL sliding window, ring step t >= 1 pairs each chip with
    the K shard t hops behind it; the closest (q, k) distance in that
    pairing is (t-1)*s_local + 1, so the step contributes iff
    (t-1)*s_local + 1 <= window - 1.  Steps beyond that bound are pure
    waste for EVERY chip — the schedule skips them entirely (no compute,
    no ppermute), which is what turns windowed ring attention into
    O(S·W) work.  Bidirectional windows still need the full rotation
    (a shard must transit the whole ring to reach chips on its other
    side), so only the per-chip masking prunes there."""
    if not causal or window is None:
        return n
    if window <= 1:
        return 1
    return min(n, (window - 2) // s_local + 2)


def _block_update(o, l, m, q, k, v, q_offset, k_offset, causal=True,
                  window=None):
    """One online-softmax accumulation step over a K/V block.

    o: (B,H,Sq,D) f32 accumulator; l: (B,H,Sq) row sums; m: (B,H,Sq) row
    maxes; q: (B,Sq,H,D); k,v: (B,Sk,H_kv,D) with H_kv | H (GQA groups
    the einsums — no repeat).  ``causal=False`` attends the whole block
    (encoder/bidirectional mode); ``window`` restricts reach to GLOBAL
    positions within the sliding window (the offsets make the mask exact
    across shards).
    """
    b, s_q, h, d = q.shape
    s_k, h_kv = k.shape[1], k.shape[2]
    if h_kv != h:
        # GQA: query head hk*g+j reads kv head hk — group the contraction
        # instead of repeating K to full heads (head order is kv-major,
        # matching the kernels and the old repeat-expanded layout)
        g = h // h_kv
        qg = q.reshape(b, s_q, h_kv, g, d)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).reshape(
            b, h, s_q, s_k).astype(jnp.float32)
    else:
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    logits = logits / jnp.sqrt(d)
    masked = causal or window is not None
    if masked:
        from ..models.transformer import sliding_mask

        mask = sliding_mask(
            q_offset + jnp.arange(q.shape[1]),
            k_offset + jnp.arange(k.shape[1]),
            causal=causal, window=window,
        )  # (Sq, Sk) — shared with the dot oracle so the two stay exact
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    block_max = jnp.max(logits, axis=-1)  # (B,H,Sq)
    new_m = jnp.maximum(m, block_max)
    p = jnp.exp(logits - new_m[..., None])
    if masked:
        # exp of masked entries is zeroed explicitly so fully-masked
        # blocks contribute nothing even when new_m is still the -inf
        # sentinel.
        p = jnp.where(mask[None, None], p, 0.0)
    corr = jnp.exp(m - new_m)
    new_l = l * corr + jnp.sum(p, axis=-1)
    if h_kv != h:
        pv = jnp.einsum(
            "bhgqk,bkhd->bhgqd",
            p.reshape(b, h_kv, h // h_kv, s_q, s_k).astype(v.dtype), v,
        ).reshape(b, h, s_q, d)
    else:
        pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v.dtype), v)
    new_o = o * corr[..., None] + pv.astype(jnp.float32)
    return new_o, new_l, new_m


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: Optional[str] = None,
    impl: str = "dense",
    causal: bool = True,
    window: Optional[int] = None,
) -> jax.Array:
    """Exact attention with K/V rotating around the mesh axis.

    Args:
      q, k, v: (B, S_local, H, D) — this chip's sequence shard; global
        sequence order follows the axis index.  GQA: k/v may carry
        H_kv < H heads (H_kv | H) — only the kv heads rotate.
      axis_name: mesh axis the sequence is sharded over (must be bound,
        i.e. called inside shard_map).  ``None`` falls back to the world
        axis.
      impl: ``"dense"`` computes each K/V block with XLA einsums
        (materializes (S/n)² logits per step); ``"flash"`` runs each block
        through the pallas flash kernels (``ops.flash_attention``) so NO
        logits tile ever hits HBM — per-chip attention memory is O(S/n)
        even inside a block, which is what lets block sizes grow with
        long contexts.
      causal: True = decoder (causal mask over GLOBAL positions); False =
        encoder/bidirectional (every shard attends every other — the
        long-context BERT-family mode).
      window: Mistral-style sliding window over GLOBAL positions —
        each token attends the last ``window`` positions, itself
        included (``q_pos - k_pos < window``; symmetric |Δ| < window
        when bidirectional).  Supported by BOTH impls; with
        ``causal=True`` the rotation stops after ``ring_window_steps``
        steps, so out-of-window shards cost neither compute nor comms.
    Returns:
      (B, S_local, H, D) attention output for the local Q shard.
    """
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if k.shape != v.shape:
        raise ValueError(
            f"k/v shapes differ: {k.shape} vs {v.shape} (ring attention "
            "rotates keys and values of one width: no latent attention)")
    if impl == "flash":
        return ring_flash_attention(q, k, v, axis_name, causal=causal,
                                    window=window)
    if impl != "dense":
        raise ValueError(f"unknown ring attention impl {impl!r}")
    axis = axis_name or WORLD_AXIS
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    b, s_local, h, d = q.shape
    if n == 1:
        from ..models.transformer import causal_dot_attention

        return causal_dot_attention(q, k, v, causal=causal, window=window)

    q_offset = idx * s_local
    perm = [(i, (i + 1) % n) for i in range(n)]
    steps = ring_window_steps(n, s_local, causal=causal, window=window)

    def step(t, carry):
        o, l, m, kk, vv = carry
        src = (idx - t) % n  # which shard's K/V we currently hold
        o, l, m = _block_update(o, l, m, q, kk, vv, q_offset,
                                src * s_local, causal=causal,
                                window=window)
        kk = jax.lax.ppermute(kk, axis, perm)
        vv = jax.lax.ppermute(vv, axis, perm)
        return o, l, m, kk, vv

    o = jnp.zeros((b, h, s_local, d), jnp.float32)
    l = jnp.zeros((b, h, s_local), jnp.float32)
    m = jnp.full((b, h, s_local), _NEG_INF, jnp.float32)
    o, l, m, _, _ = jax.lax.fori_loop(0, steps, step, (o, l, m, k, v))
    # every row sees at least the diagonal (causal, window >= 1) or
    # everything (bidirectional), so l > 0 everywhere
    out = o / l[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


# -- flash-block ring attention ---------------------------------------------
#
# Same ring schedule, but every (Q shard, K/V block) pair runs through the
# pallas flash kernels: VMEM-resident online softmax inside the block, so
# not even the (S/n x S/n) per-step logits tile is materialized in HBM.
# Partial block outputs merge by their logsumexps (exact); sliding
# windows pass the per-step global K−Q offset into the kernels, so the
# in-kernel block-skip and masks act on global positions and the merge
# stays online-softmax exact.  Backward re-rotates K/V and uses
# FlashAttention-2's decomposition: with the final (out, lse) fixed,
# each block's (dq, dk, dv) contribution is independent, and the dk/dv
# accumulators travel around the ring WITH their K/V block; a final
# home-shift ppermute returns them (one hop for the full rotation, a
# (steps-1)-shift when a causal window truncated the schedule).


def _ring_flash_fwd(q, k, v, axis, block_q, block_k, causal, window):
    from ..ops.flash_attention import flash_block_forward

    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]
    s_local = q.shape[1]

    # own block: diagonal-masked in causal mode, full in encoder mode;
    # the window needs no offset here (q and k share the global origin)
    o0, lse0 = flash_block_forward(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        window=window,
    )
    steps = ring_window_steps(n, s_local, causal=causal, window=window)

    def step(t, carry):
        o, lse, kk, vv = carry
        kk = jax.lax.ppermute(kk, axis, perm)
        vv = jax.lax.ppermute(vv, axis, perm)
        src = (idx - t) % n  # whose K/V block this chip now holds
        o_t, lse_t = flash_block_forward(
            q, kk, vv, causal=False, block_q=block_q, block_k=block_k,
            window=window, kv_offset=(src - idx) * s_local,
        )
        if causal:
            past = src < idx  # strictly-past blocks attend fully
            lse_t = jnp.where(past, lse_t, _NEG_INF)
        new_lse = jnp.logaddexp(lse, lse_t)
        a = jnp.exp(lse - new_lse)[..., None]
        c = jnp.exp(lse_t - new_lse)[..., None]
        o = o * a + o_t.astype(jnp.float32) * c
        return o, new_lse, kk, vv

    o, lse, _, _ = jax.lax.fori_loop(
        1, steps, step, (o0.astype(jnp.float32), lse0, k, v)
    )
    return o.astype(q.dtype), lse


def _ring_flash_bwd_impl(q, k, v, out, lse, g, axis, block_q, block_k,
                         causal, window):
    from ..ops import flash_attention as fa

    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]
    b, s, h, d = q.shape
    h_kv = k.shape[2]

    # fold/pad the step-invariant operands (q, g, lse, delta) ONCE; only
    # the folded K/V (and their gradient accumulators — kv heads only
    # under GQA) travel the ring
    bq, bk = fa._clamp_blocks(s, block_q, block_k)
    lse_col = lse.transpose(0, 2, 1).reshape(b * h, s, 1)
    qf, gf, lse_f, delta_f = fa._fold_bwd_invariants(q, out, lse_col, g, bq)
    kf = fa._fold(fa._pad_to(k, bk, axis=1), b, h_kv, d)
    vf = fa._fold(fa._pad_to(v, bk, axis=1), b, h_kv, d)
    s_q, s_k = qf.shape[1], kf.shape[1]

    def block_bwd(kf_, vf_, blk_causal, kv_off=None):
        return fa._backward_folded(
            qf, kf_, vf_, gf, lse_f, delta_f, orig_s=s, causal=blk_causal,
            block_q=bq, block_k=bk, interpret=None, window=window,
            kv_offset=kv_off,
        )

    dq0, dk0, dv0 = block_bwd(kf, vf, causal)
    steps = ring_window_steps(n, s, causal=causal, window=window)

    def step(t, carry):
        dq, dk_acc, dv_acc, kk, vv = carry
        kk = jax.lax.ppermute(kk, axis, perm)
        vv = jax.lax.ppermute(vv, axis, perm)
        dk_acc = jax.lax.ppermute(dk_acc, axis, perm)
        dv_acc = jax.lax.ppermute(dv_acc, axis, perm)
        src = (idx - t) % n
        dq_t, dk_t, dv_t = block_bwd(kk, vv, False,
                                     kv_off=(src - idx) * s)
        if causal:
            past = src < idx
            dq_t = jnp.where(past, dq_t.astype(jnp.float32), 0.0)
            dk_t = jnp.where(past, dk_t.astype(jnp.float32), 0.0)
            dv_t = jnp.where(past, dv_t.astype(jnp.float32), 0.0)
        dq = dq + dq_t.astype(jnp.float32)
        dk_acc = dk_acc + dk_t.astype(jnp.float32)
        dv_acc = dv_acc + dv_t.astype(jnp.float32)
        return dq, dk_acc, dv_acc, kk, vv

    dq, dk_acc, dv_acc, _, _ = jax.lax.fori_loop(
        1, steps, step,
        (dq0.astype(jnp.float32), dk0.astype(jnp.float32),
         dv0.astype(jnp.float32), kf, vf),
    )
    if steps > 1:
        # accumulators have rotated steps-1 hops with their K/V block;
        # one shift collective returns each block's gradient to its home
        # chip (shift -(steps-1); for the full rotation that is the
        # classic single forward hop)
        home = [(i, (i - (steps - 1)) % n) for i in range(n)]
        dk_acc = jax.lax.ppermute(dk_acc, axis, home)
        dv_acc = jax.lax.ppermute(dv_acc, axis, home)
    dq = fa._unfold(dq, b, h, s_q, d)[:, :s]
    dk = fa._unfold(dk_acc, b, h_kv, s_k, d)[:, :s]
    dv = fa._unfold(dv_acc, b, h_kv, s_k, d)[:, :s]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash(q, k, v, axis, block_q, block_k, causal, window):
    out, _ = _ring_flash_fwd(q, k, v, axis, block_q, block_k, causal,
                             window)
    return out


def _ring_flash_fwd_vjp(q, k, v, axis, block_q, block_k, causal, window):
    out, lse = _ring_flash_fwd(q, k, v, axis, block_q, block_k, causal,
                               window)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd_vjp(axis, block_q, block_k, causal, window, residuals,
                        g):
    q, k, v, out, lse = residuals
    return _ring_flash_bwd_impl(
        q, k, v, out, lse, g, axis, block_q, block_k, causal, window
    )


_ring_flash.defvjp(_ring_flash_fwd_vjp, _ring_flash_bwd_vjp)


def ring_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: Optional[str] = None,
    block_q: int = 256,
    block_k: int = 256,
    causal: bool = True,
    window: Optional[int] = None,
) -> jax.Array:
    """Ring attention whose per-block compute is the pallas flash kernel
    (see module docstring).  Differentiable; numerics match
    ``ring_attention(..., impl="dense")`` and the single-chip oracle.
    ``causal=False`` = encoder/bidirectional mode; ``window`` composes —
    per-step kernels mask/skip on global positions and, for causal
    windows, the rotation truncates to ``ring_window_steps``."""
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    axis = axis_name or WORLD_AXIS
    if jax.lax.axis_size(axis) == 1:
        from ..ops.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, window=window)
    return _ring_flash(q, k, v, axis, block_q, block_k, causal, window)
