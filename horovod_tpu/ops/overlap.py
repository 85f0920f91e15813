"""Backward/collective overlap: bucket-boundary segmented backprop.

SURVEY.md §7.3 item 5 names "overlap of grad production with ICI
collectives (backward-pass bucketing schedule)" as the remaining hard
part for ≥90% scaling parity — PR 7 built the two-level reduction, but a
``jax.grad`` train step reduces gradients only *after* the whole
backward, so every byte of communication is exposed.  The reference
hides it with a background thread consuming autograd hooks (SURVEY.md
§3.2); PyTorch DDP (Li et al., VLDB '20) showed the compiled-graph
answer: split the backward at *bucket boundaries* and launch each
bucket's collective while earlier layers' gradients are still
computing.

This module is that answer for the XLA world.  A model is expressed as
a chain of :class:`Segment`\\ s (``fn(params, x) -> x``, last returning
the scalar loss); the forward pass records one ``jax.vjp`` per segment,
and the backward walks them in reverse, fusing each
:class:`~horovod_tpu.ops.fusion.BucketSchedule` bucket the moment its
last gradient is produced and issuing its reduction *there* — between
segment computations, not after them.  An ``optimization_barrier`` at
each bucket boundary pins the dataflow: the bucket's collective and the
next segment's backward both depend on the boundary but not on each
other, and neither can be hoisted above the segment that produced the
bucket.  The lowered StableHLO therefore carries the collectives
interleaved with the segment computations — pinned by the
``overlap_inventory`` check in ``ops/comm_model.py`` (the PR-7
``measured_tier_bytes`` idiom), not assumed.

Lowered order is not the schedule.  What runs beside what is the
backend scheduler's decision, and on the TPU compiler an all-reduce is
SYNCHRONOUS unless the compile asks otherwise: compiled for a v5e with
no option, this chain's bucket all-reduces are all synchronous and the
scheduler sinks them behind the whole backward, barriers or not (ISSUE
25's compiles; PERF.md §6, PR 25).  Asynchrony comes from the compile
options the step builder attaches
(``spmd_ops.exchange_compile_options``, to ``overlap=False`` and
``overlap=True`` alike); ``overlap_inventory`` and
``hvd_tpu_overlap_exposed_comm_fraction`` describe the lowered program
only, and ``comm_model.compiled_collective_counts`` the compiled one.

Exactness contract: ``overlap=True`` and ``overlap=False`` run the SAME
arithmetic (same fusion, same per-bucket reduction, only the program
order differs), so gradients — and elementwise optimizer updates, ZeRO
on or off — are bit-equal at fp32 (tests/test_overlap.py).

:class:`BucketAutotuner` closes the loop upstream Horovod closes with
Bayesian search (SURVEY.md §5.6): it sweeps bucket-size (× DCN wire
dtype) candidates against the LIVE step-time measurements the PR-1
instruments already collect, pins the winner within a trial budget, and
never regresses against the static default (the default is always trial
zero).  docs/autotune.md describes the policy.
"""

from __future__ import annotations

import time
from typing import Any, Callable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.extend.core import Literal
import numpy as np

from ..metrics import instruments as _metrics
from .fusion import BucketSchedule, unfuse


class Segment(NamedTuple):
    """One link of a backward-overlap chain.

    ``fn(params, x) -> x`` takes the FULL parameter pytree plus the
    previous segment's activation; the last segment returns the scalar
    loss.  ``keys`` names the param-tree key paths the segment reads —
    each entry is a ``"/"``-joined path prefix (``"embed"``,
    ``"params/block_3"``); a tied embedding appears in several segments
    and its bucket completes at the EARLIEST one backprop reaches.
    ``None`` = auto-detect by jaxpr inspection (:func:`used_leaf_mask`).
    """

    fn: Callable[[Any, Any], Any]
    keys: Optional[Tuple[str, ...]] = None


def used_leaf_mask(fn: Callable, params: Any, x: Any) -> List[bool]:
    """Which leaves of ``params`` does ``fn(params, x)`` actually read?

    Traced abstractly (``jax.make_jaxpr`` — works on concrete arrays and
    inside an outer trace alike): a leaf is used iff its jaxpr input
    variable feeds any equation or output.  This is what lets a bare
    callable join a chain without declaring its parameter footprint.
    """
    flat, treedef = jax.tree_util.tree_flatten(params)

    def wrapped(flat_leaves, xx):
        return fn(jax.tree_util.tree_unflatten(treedef, flat_leaves), xx)

    closed = jax.make_jaxpr(wrapped)(flat, x)
    jaxpr = closed.jaxpr
    used = set()
    for eqn in jaxpr.eqns:
        used.update(v for v in eqn.invars if not isinstance(v, Literal))
    used.update(v for v in jaxpr.outvars if not isinstance(v, Literal))
    return [v in used for v in jaxpr.invars[: len(flat)]]


def _leaf_masks(
    segments: Sequence[Segment], params: Any, x0: Any
) -> Tuple[List[List[bool]], Any]:
    """Per-segment used-leaf masks (declared keys or jaxpr-detected) and
    the forward activations needed to size each auto-detection trace."""
    flat, treedef = jax.tree_util.tree_flatten(params)
    paths = [
        tuple(
            str(getattr(p, "key", getattr(p, "name", p))) for p in path
        )
        for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]
    ]
    masks: List[List[bool]] = []
    # the abstract activation is only needed by the auto-detect branch;
    # with every segment declaring keys (the shipped chains) no segment
    # is ever abstractly traced here
    auto_remaining = sum(1 for seg in segments if seg.keys is None)
    x = x0
    for seg in segments:
        if seg.keys is not None:
            prefixes = [tuple(k.split("/")) for k in seg.keys]
            masks.append([
                any(p[: len(pre)] == pre for pre in prefixes)
                for p in paths
            ])
        else:
            masks.append(used_leaf_mask(seg.fn, params, x))
            auto_remaining -= 1
        if auto_remaining:
            x = jax.eval_shape(seg.fn, params, x)
    return masks, treedef


def _barrier_pin(g: Any, bufs: List[jax.Array]):
    """Bucket-boundary pin: one ``optimization_barrier`` ties the
    outgoing activation cotangent and the just-fused bucket buffers
    together.  Downstream, the bucket collectives and the next segment's
    backward each depend on the barrier but NOT on each other — they may
    overlap, but neither may move above this segment's backward."""
    flat_g, gdef = jax.tree_util.tree_flatten(g)
    pinned = jax.lax.optimization_barrier(tuple(flat_g) + tuple(bufs))
    g = jax.tree_util.tree_unflatten(gdef, list(pinned[: len(flat_g)]))
    return g, list(pinned[len(flat_g):])


def overlapped_value_and_grad(
    segments: Sequence[Any],
    params: Any,
    x0: Any,
    *,
    bucket_reduce: Callable[[jax.Array], jax.Array],
    bucket_bytes: Optional[int] = None,
    schedule: Optional[BucketSchedule] = None,
    overlap: bool = True,
) -> Tuple[jax.Array, Any, BucketSchedule]:
    """Loss and *reduced* gradients of a segment chain, with each
    bucket's reduction launched at its bucket boundary.

    Args:
      segments: :class:`Segment`\\ s (bare callables are auto-detected);
        ``segments[k](params, x_k) -> x_{k+1}``, last returns the scalar
        loss.  Traceable — call inside jit/shard_map.
      params: full parameter pytree (every segment receives it).
      x0: first segment's input (the batch).
      bucket_reduce: reduction applied to each fused 1-D bucket buffer —
        e.g. ``lambda b: jax.lax.psum(b, axis) / world`` for a
        data-parallel Average, or a two-level
        ``spmd_ops._two_level_sum_leaf`` wrapper for the hierarchical
        fabric (docs/COLLECTIVES.md).  Must be elementwise-positional
        (it sees concatenated leaves).
      bucket_bytes: BucketSchedule threshold (ignored when ``schedule``
        is given); defaults to the init-time
        ``HVD_TPU_OVERLAP_BUCKET_BYTES``.
      schedule: a prebuilt :class:`BucketSchedule` over the flattened
        params (production order is overridden to match the chain).
      overlap: False = identical arithmetic with every reduction issued
        after the full backward — the bit-equality baseline and the
        negative control of the interleave check.

    Returns ``(loss, reduced_grads, schedule)``.
    """
    segments = [
        s if isinstance(s, Segment) else Segment(s) for s in segments
    ]
    if not segments:
        raise ValueError("overlap chain needs at least one segment")
    flat, treedef = jax.tree_util.tree_flatten(params)
    masks, _ = _leaf_masks(segments, params, x0)
    n_seg = len(segments)
    n_leaf = len(flat)

    # completion segment of each leaf: the SMALLEST segment index reading
    # it — backprop walks segments in reverse, so that's where its last
    # gradient contribution lands.  Unread leaves complete at segment 0
    # (their gradient is structurally zero).
    complete_at = [0] * n_leaf
    for i in range(n_leaf):
        touching = [k for k in range(n_seg) if masks[k][i]]
        complete_at[i] = min(touching) if touching else 0
    production = [n_seg - 1 - complete_at[i] for i in range(n_leaf)]

    if schedule is None:
        if bucket_bytes is None:
            from ..common import basics

            cfg = basics._state.config
            bucket_bytes = (
                cfg.overlap_bucket_bytes if cfg is not None
                else 4 * 1024 * 1024
            )
        schedule = BucketSchedule(flat, bucket_bytes, production)
    elif schedule.production_order != production:
        schedule = BucketSchedule(
            flat, schedule.threshold_bytes, production
        )

    # bucket b is ready after the backward of segment (n_seg-1-ready_at)
    ready_at_segment = [n_seg - 1 - r for r in schedule.ready_at]

    # ---- forward: one vjp per segment -------------------------------------
    x = x0
    vjps = []
    for k, seg in enumerate(segments):
        idxs = [i for i in range(n_leaf) if masks[k][i]]

        def seg_fn(sub, xx, _fn=seg.fn, _idxs=idxs):
            merged = list(flat)
            for j, i in enumerate(_idxs):
                merged[i] = sub[j]
            return _fn(jax.tree_util.tree_unflatten(treedef, merged), xx)

        x, vjp = jax.vjp(seg_fn, [flat[i] for i in idxs], x)
        vjps.append((vjp, idxs))
    loss = x
    if np.shape(loss) != ():
        raise ValueError(
            "the last overlap segment must return a scalar loss, got "
            f"shape {np.shape(loss)}"
        )

    # ---- backward: reverse walk, reducing buckets at their boundary -------
    acc: List[Optional[jax.Array]] = [None] * n_leaf
    reduced: List[Optional[jax.Array]] = [None] * schedule.num_buckets
    g = jnp.ones((), jnp.asarray(loss).dtype)
    pending: List[Tuple[int, jax.Array]] = []  # (bucket, fused buf)

    def _fused_bucket(b: int) -> jax.Array:
        dt, idxs = schedule.buckets[b]
        parts = []
        for i in idxs:
            leaf = acc[i]
            if leaf is None:
                shape, dtype = schedule.specs[i]
                leaf = jnp.zeros(shape, dtype)
            parts.append(jnp.ravel(leaf))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    for k in reversed(range(n_seg)):
        vjp, idxs = vjps[k]
        dsub, g = vjp(g)
        for j, i in enumerate(idxs):
            acc[i] = dsub[j] if acc[i] is None else acc[i] + dsub[j]
        ready = [
            b for b in range(schedule.num_buckets)
            if ready_at_segment[b] == k
        ]
        if not ready:
            continue
        bufs = [_fused_bucket(b) for b in ready]
        if overlap:
            if k > 0:
                g, bufs = _barrier_pin(g, bufs)
            for b, buf in zip(ready, bufs):
                reduced[b] = bucket_reduce(buf)
        else:
            pending.extend(zip(ready, bufs))
    if not overlap:
        for b, buf in pending:
            reduced[b] = bucket_reduce(buf)
    grads = jax.tree_util.tree_unflatten(
        treedef, unfuse(reduced, schedule)
    )
    return loss, grads, schedule


def record_overlap_metrics(lowered_text: str, min_payload_bytes: int = 0):
    """Feed the ``hvd_tpu_overlap_*`` instruments from a compiled step's
    StableHLO: the static exposed-comm fraction (stream bytes of
    collectives with no compute after them / total) and the per-bucket
    launch lead (compute ops still pending when each collective issues).
    Returns the :func:`~horovod_tpu.ops.comm_model.overlap_inventory`
    record it read, so benches/tests share the numbers the gauges saw."""
    from .comm_model import overlap_inventory

    inv = overlap_inventory(lowered_text, min_payload_bytes)
    _metrics.OVERLAP_EXPOSED_FRACTION.set(inv["exposed_fraction"])
    for op in inv["collectives"]:
        _metrics.OVERLAP_LAUNCH_LEAD.observe(op["compute_after"])
    return inv


class Candidate(NamedTuple):
    """One autotuner trial point: bucket size and (optionally) the DCN
    wire dtype of the hierarchical hop's tier assignment."""

    bucket_bytes: int
    wire_dtype: Optional[str] = None


_DEFAULT_SWEEP_MB = (1, 2, 4, 8, 16, 32)


class BucketAutotuner:
    """Metrics-driven sweep over bucket-size (× tier) candidates.

    Upstream Horovod tunes its fusion buffer with Bayesian search over
    *guessed* scores (SURVEY.md §5.6); here the score is the live
    step-time measurement the caller already collects (PR-1
    instruments).  Protocol::

        tuner = BucketAutotuner(default=Candidate(cfg.overlap_bucket_bytes))
        while not tuner.converged:
            cand = tuner.propose()
            step = build_step(bucket_bytes=cand.bucket_bytes, ...)
            tuner.observe(timed_step(step))   # once per step
        plan = tuner.pinned                   # fixed for the rest of the run

    Rules:
      * the static default is ALWAYS trial zero, and the winner is the
        argmin over every scored trial — the pinned plan can never
        regress against the default;
      * each trial scores as the median of ``steps_per_trial``
        observations with the first discarded (it pays the recompile);
      * the sweep stops early when ``trial_budget`` trials have scored —
        the best-so-far is pinned (convergence within the budget is
        structural, not probabilistic).
    """

    def __init__(
        self,
        candidates: Optional[Sequence[Candidate]] = None,
        default: Optional[Candidate] = None,
        trial_budget: Optional[int] = None,
        steps_per_trial: Optional[int] = None,
    ):
        from ..common import basics

        cfg = basics._state.config
        if default is None:
            default = Candidate(
                cfg.overlap_bucket_bytes if cfg is not None
                else 4 * 1024 * 1024
            )
        if candidates is None:
            candidates = [
                Candidate(mb << 20) for mb in _DEFAULT_SWEEP_MB
            ]
        if trial_budget is None:
            trial_budget = (
                cfg.overlap_autotune_trials if cfg is not None else 8
            )
        if steps_per_trial is None:
            steps_per_trial = (
                cfg.overlap_autotune_steps if cfg is not None else 3
            )
        if trial_budget < 1 or steps_per_trial < 1:
            raise ValueError(
                "trial_budget and steps_per_trial must be >= 1, got "
                f"{trial_budget}/{steps_per_trial}"
            )
        self.default = default
        # default first (trial 0), then the sweep minus duplicates
        self.candidates: List[Candidate] = [default] + [
            c for c in candidates if c != default
        ]
        self.trial_budget = int(trial_budget)
        self.steps_per_trial = int(steps_per_trial)
        self._trial = 0
        self._times: List[float] = []
        self._scores: List[Tuple[Candidate, float]] = []
        self._pinned: Optional[Candidate] = None

    @property
    def converged(self) -> bool:
        return self._pinned is not None

    @property
    def pinned(self) -> Optional[Candidate]:
        return self._pinned

    @property
    def scores(self) -> List[Tuple[Candidate, float]]:
        return list(self._scores)

    def propose(self) -> Candidate:
        """The candidate to run the next step with (stable within a
        trial; the pinned winner once converged)."""
        if self._pinned is not None:
            return self._pinned
        return self.candidates[self._trial]

    def observe(self, step_time_s: float) -> None:
        """Record one step's wall time under the current candidate."""
        if self._pinned is not None:
            return
        self._times.append(float(step_time_s))
        if len(self._times) < self.steps_per_trial:
            return
        # first step of a trial pays the new schedule's compile
        scored = self._times[1:] if len(self._times) > 1 else self._times
        score = float(np.median(scored))
        cand = self.candidates[self._trial]
        self._scores.append((cand, score))
        _metrics.OVERLAP_AUTOTUNE_TRIALS.inc()
        from .. import trace as _trace

        _trace.event("overlap.autotune", trial=self._trial,
                     bucket_bytes=cand.bucket_bytes,
                     wire_dtype=cand.wire_dtype, score_s=score)
        self._times = []
        self._trial += 1
        if (
            self._trial >= len(self.candidates)
            or len(self._scores) >= self.trial_budget
        ):
            self._pin()

    def _pin(self) -> None:
        best, t = min(self._scores, key=lambda ct: ct[1])
        self._pinned = best
        _metrics.OVERLAP_AUTOTUNE_PINNED_BYTES.set(best.bucket_bytes)

    def run(
        self,
        build_step: Callable[[Candidate], Callable[[], Any]],
        time_fn: Optional[Callable[[Callable[[], Any]], float]] = None,
    ) -> Candidate:
        """Drive the whole sweep: ``build_step(candidate)`` returns a
        zero-arg step thunk; each is timed ``steps_per_trial`` times.
        Returns the pinned candidate (benches and simple loops use this;
        training loops interleave ``propose``/``observe`` instead)."""
        if time_fn is None:
            def time_fn(thunk):
                t0 = time.perf_counter()
                jax.block_until_ready(thunk())
                return time.perf_counter() - t0

        while not self.converged:
            cand = self.propose()
            thunk = build_step(cand)
            for _ in range(self.steps_per_trial):
                if self.converged:
                    break
                self.observe(time_fn(thunk))
        return self._pinned
