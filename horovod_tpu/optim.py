"""DistributedOptimizer: gradient-averaging optimizer wrapper for optax.

Reference parity: horovod/torch/optimizer.py DistributedOptimizer +
horovod/tensorflow/__init__.py DistributedGradientTape (SURVEY.md §2.3,
§3.2 hot path).  The reference intercepts per-parameter gradients with
autograd hooks and enqueues async allreduces that overlap backprop; under
XLA the whole training step is one compiled program, so "overlap" is the
compiler's latency-hiding job and the wrapper simply inserts a (fused)
gradient allreduce before the update:

  * Inside a jitted/shard_map'ped step (the TPU-native deployment): the
    allreduce is a pytree ``psum`` over the mesh axis — XLA schedules it
    concurrently with independent backward computation, which is the
    compiled analog of the reference's backward/allreduce overlap.
  * Called eagerly (classic one-process-per-chip deployment): gradients go
    through the eager engine's fused, cached collective path.

``backward_passes_per_step`` (local gradient aggregation before the
allreduce, reference: horovod/torch/optimizer.py _LocalGradientAggregation)
is exposed via :func:`with_gradient_accumulation`.

Beyond reference parity, this module carries the ZeRO stage-1
sharded-state wrappers (:func:`ZeroDistributedOptimizer` /
:func:`ZeroSpmdOptimizer` — docs/OPTIM.md): reduce-scatter the flattened
gradients, update only this rank's optimizer-state shard, allgather the
update deltas — optimizer memory divided by world_size at allreduce's
communication cost.
"""

from __future__ import annotations

from typing import Any, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .common import basics
from .common.process_sets import ProcessSet
from .common.retry import env_int
from .common.topology import DCN_AXIS, ICI_AXIS, WORLD_AXIS
from .metrics import instruments as _metrics
from .ops import collective_ops, spmd_ops
from .ops.reduce_ops import Average, ReduceOp


def _in_spmd_context(axis: str) -> bool:
    """True when ``axis`` is bound, i.e. we are tracing inside shard_map.

    The reference distinguishes these worlds by process layout; we do it by
    trace context, which is the JAX-native equivalent.
    """
    try:
        jax.lax.axis_index(axis)
        return True
    except NameError:
        return False
    except Exception:
        return False


def allreduce_gradients(
    grads: Any,
    op: ReduceOp = Average,
    axis: str = WORLD_AXIS,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    process_set: Optional[ProcessSet] = None,
    hierarchical: Optional[bool] = None,
    ici_axis: str = ICI_AXIS,
    dcn_axis: str = DCN_AXIS,
    dcn_compression=None,
) -> Any:
    """Average a gradient pytree across workers, picking the SPMD or eager
    path automatically.  Reference: the allreduce step of §3.2.

    ``hierarchical`` selects the two-level ICI×DCN reduction (reference:
    HOROVOD_HIERARCHICAL_ALLREDUCE / NCCLHierarchicalAllreduce); it
    defaults to the env flag and requires tracing over a
    ``hierarchical_mesh()``'s (dcn, ici) axes — in a flat or eager context
    it falls back to the flat reduction (numerically identical); on the
    eager path the engine itself routes two-level when the flag is set
    and the topology spans slices (CollectiveEngine._route_hierarchical).
    ``dcn_compression`` casts only the DCN-crossing shard to its wire
    dtype on the SPMD two-level path (stateless here — no error
    feedback; thread a residual through
    ``spmd_ops.hierarchical_allreduce`` directly for that).
    """
    if hierarchical is None:
        st = basics._state
        hierarchical = bool(
            st.config is not None and st.config.hierarchical_allreduce
        )
    if (
        hierarchical
        and op in (ReduceOp.SUM, ReduceOp.AVERAGE)
        and _in_spmd_context(ici_axis)
        and _in_spmd_context(dcn_axis)
    ):
        if dcn_compression is not None and dcn_compression.error_feedback:
            raise ValueError(
                "allreduce_gradients is stateless — use "
                "spmd_ops.hierarchical_allreduce(residual=...) to carry "
                "the error-feedback residual"
            )
        return spmd_ops.hierarchical_allreduce(
            grads, op=op, ici_axis=ici_axis, dcn_axis=dcn_axis,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
            dcn_compression=dcn_compression,
        )
    if _in_spmd_context(axis):
        return spmd_ops.allreduce(
            grads, op=op, axis=axis,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
        )
    return collective_ops.allreduce(
        grads, op=op,
        prescale_factor=prescale_factor,
        postscale_factor=postscale_factor,
        process_set=process_set,
    )


def DistributedOptimizer(
    optimizer: optax.GradientTransformation,
    op: ReduceOp = Average,
    axis: str = WORLD_AXIS,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    process_set: Optional[ProcessSet] = None,
    backward_passes_per_step: int = 1,
    compression=None,
    hierarchical: Optional[bool] = None,
    ici_axis: str = ICI_AXIS,
    dcn_axis: str = DCN_AXIS,
    dcn_compression=None,
) -> optax.GradientTransformation:
    """Wrap an optax optimizer so updates see globally reduced gradients.

    Reference: horovod/torch/optimizer.py DistributedOptimizer — same
    contract (wraps an existing optimizer, averages grads across workers,
    supports op=Sum/Average/Adasum, pre/postscale, process sets, fp16/bf16
    ``compression`` on the wire, and local aggregation), expressed as an
    optax gradient transformation.  ``hierarchical=True`` (or the
    HVD_TPU_HIERARCHICAL_ALLREDUCE env flag) selects the two-level
    ICI×DCN reduction when stepping inside a ``hierarchical_mesh()``;
    ``dcn_compression`` then compresses only the DCN-crossing shard
    (vs ``compression``, which casts the WHOLE gradient around the whole
    reduction — the two compose but usually you want one or the other).
    """
    def _reduce(updates, params=None):
        if compression is not None:
            updates, ctx = compression.compress(updates)
        updates = allreduce_gradients(
            updates, op=op, axis=axis,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
            process_set=process_set,
            hierarchical=hierarchical,
            ici_axis=ici_axis, dcn_axis=dcn_axis,
            dcn_compression=dcn_compression,
        )
        if compression is not None:
            updates = compression.decompress(updates, ctx)
        return updates

    grad_reduce = optax.stateless(_reduce)
    chained = optax.chain(grad_reduce, optimizer)
    if backward_passes_per_step > 1:
        chained = optax.MultiSteps(
            chained, every_k_schedule=backward_passes_per_step
        )
    return chained


def with_gradient_accumulation(
    optimizer: optax.GradientTransformation, every_k: int
) -> optax.GradientTransformation:
    """Local aggregation of ``every_k`` microbatches before the global
    reduce (reference: backward_passes_per_step /
    _LocalGradientAggregationHelper in horovod/torch/optimizer.py)."""
    return optax.MultiSteps(optimizer, every_k_schedule=every_k)


# -- ZeRO-style sharded optimizer state (Rajbhandari et al., 2020) -----------
#
# ZeRO stage-1 partitioning on the framework's own collectives: gradients
# are REDUCE-SCATTERED (each rank receives the fully reduced values of one
# 1/world slice instead of all of them), the optimizer state lives only for
# this rank's slice (Adam's m/v shrink by world_size), the update is
# computed locally on the slice, and the updated-parameter DELTAS are
# ALLGATHERED back to full size.  Per step this moves the same bytes an
# allreduce does (reduce-scatter + allgather IS the ring allreduce, split
# around the update) while dividing optimizer-state memory by world_size —
# the memory-for-nothing half of the PERF.md round-6 large-batch attack.
#
# The partition is FLAT: the parameter pytree is raveled into one 1-D
# buffer per dtype (a ZeroPlan — same deterministic bucketing contract as
# ops/fusion.py, so every rank partitions identically with no
# negotiation), zero-padded so each buffer divides by world_size.  The
# inner optimizer therefore sees 1-D slices, which is exact for every
# ELEMENTWISE transformation (sgd, momentum, adam(w), rmsprop, ...):
# per-element arithmetic is identical to the replicated form, so sharded
# and replicated updates are BIT-EQUAL given bit-equal reduced gradients
# (pinned by tests/test_zero_optimizer.py).  Transformations that couple
# elements ACROSS the tree (clip_by_global_norm, adafactor's factored
# second moment) would silently compute per-shard statistics — apply
# those before the ZeRO wrapper instead (docs/OPTIM.md).


class ZeroPlan:
    """Deterministic flat partition of a pytree for ZeRO sharding.

    Pure function of (leaf shapes, leaf dtypes, world) — identical on
    every rank, like ops/fusion.py's FusionPlan.  Leaves group into one
    1-D buffer per dtype (sorted by dtype name), each zero-padded to a
    multiple of ``world`` so rank shards are uniform."""

    def __init__(self, leaves: Sequence[Any], world: int):
        self.world = int(world)
        self.specs = [
            (tuple(np.shape(x)), jnp.dtype(
                getattr(x, "dtype", jnp.asarray(x).dtype))) for x in leaves
        ]
        self.sizes = [
            int(np.prod(s, dtype=np.int64)) for s, _ in self.specs
        ]
        by_dtype = {}
        for i, (_, dt) in enumerate(self.specs):
            by_dtype.setdefault(str(dt), []).append(i)
        #: [(dtype_str, leaf indices)] in sorted-dtype order
        self.buckets: List[Tuple[str, List[int]]] = sorted(by_dtype.items())
        self.bucket_sizes = [
            sum(self.sizes[i] for i in idxs) for _, idxs in self.buckets
        ]
        self.shard_sizes = [
            -(-n // self.world) if n else 0 for n in self.bucket_sizes
        ]
        self.padded_sizes = [s * self.world for s in self.shard_sizes]

    @property
    def total_bytes(self) -> int:
        return sum(
            n * jnp.dtype(dt).itemsize
            for (dt, _), n in zip(self.buckets, self.bucket_sizes)
        )

    @property
    def padded_bytes(self) -> int:
        return sum(
            n * jnp.dtype(dt).itemsize
            for (dt, _), n in zip(self.buckets, self.padded_sizes)
        )

    @property
    def shard_bytes(self) -> int:
        return sum(
            n * jnp.dtype(dt).itemsize
            for (dt, _), n in zip(self.buckets, self.shard_sizes)
        )

    def flatten(self, leaves: Sequence[jax.Array]) -> List[jax.Array]:
        """Ravel + concat + zero-pad each dtype bucket.  Traceable."""
        out = []
        for (dt, idxs), padded in zip(self.buckets, self.padded_sizes):
            parts = [jnp.ravel(leaves[i]) for i in idxs]
            buf = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
            pad = padded - buf.size
            if pad:
                buf = jnp.concatenate([buf, jnp.zeros((pad,), buf.dtype)])
            out.append(buf)
        return out

    def unflatten(self, bufs: Sequence[jax.Array]) -> List[jax.Array]:
        """Inverse of :meth:`flatten` (padding dropped).  Traceable."""
        leaves: List[Any] = [None] * len(self.specs)
        for (dt, idxs), buf in zip(self.buckets, bufs):
            off = 0
            for i in idxs:
                shape, _ = self.specs[i]
                n = self.sizes[i]
                leaves[i] = jax.lax.dynamic_slice_in_dim(
                    buf, off, n).reshape(shape)
                off += n
        return leaves

    def shard_abstract(self) -> List[jax.ShapeDtypeStruct]:
        """Abstract per-rank shard buffers (what the inner optimizer's
        state is laid out over)."""
        return [
            jax.ShapeDtypeStruct((s,), jnp.dtype(dt))
            for (dt, _), s in zip(self.buckets, self.shard_sizes)
        ]


class ZeroState(NamedTuple):
    """Optimizer state of the ZeRO wrappers: the inner optimizer's state
    over THIS RANK's flat parameter shards (one 1-D slice per dtype
    bucket).  ``residual`` carries the DCN-hop error-feedback state (one
    shard-shaped leaf per dtype bucket) when a hierarchical wrapper runs
    with ``DcnCompression(error_feedback=True)``; None otherwise."""

    inner: Any
    residual: Any = None


def _zero_cast_grads(grads_leaves, specs):
    """Cast gradient leaves to the parameter dtype so the bucket layout
    (built from params) applies to the gradients too."""
    return [
        g if jnp.asarray(g).dtype == dt else jnp.asarray(g).astype(dt)
        for g, (_, dt) in zip(grads_leaves, specs)
    ]


def state_bytes(tree: Any) -> int:
    """Total array bytes of a pytree (optimizer state, params, ...) —
    the accounting the bench's ``opt_state_bytes_per_rank`` column and
    the ``hvd_tpu_optim_state_shard_bytes`` gauge report."""
    return sum(
        int(getattr(leaf, "nbytes", 0) or 0)
        for leaf in jax.tree_util.tree_leaves(tree)
    )


def _slice_shards(plan: "ZeroPlan", bufs, me):
    """Rank ``me``'s contiguous shard of each per-dtype flat buffer
    (empty buckets pass through untouched)."""
    return [
        jax.lax.dynamic_slice_in_dim(buf, me * s, s) if s else buf
        for buf, s in zip(bufs, plan.shard_sizes)
    ]


def _zero_min_bytes(explicit: Optional[int]) -> int:
    """Sharding threshold: below this many TOTAL parameter bytes the
    wrapper keeps replicated state and a single allreduce — two
    negotiated collectives (reduce-scatter + allgather) cost more than
    one for models whose whole Adam state fits comfortably anyway."""
    if explicit is not None:
        return int(explicit)
    return env_int("HVD_TPU_ZERO_MIN_BYTES", 0)


def ZeroDistributedOptimizer(
    optimizer: optax.GradientTransformation,
    op: ReduceOp = Average,
    process_set: Optional[ProcessSet] = None,
    backward_passes_per_step: int = 1,
    min_total_bytes: Optional[int] = None,
    hierarchical: Optional[bool] = None,
    dcn_compression=None,
) -> optax.GradientTransformation:
    """ZeRO stage-1 sharded-state optimizer for the EAGER (one process
    per chip) deployment — the sharded sibling of
    :func:`DistributedOptimizer`.

    ``update`` reduce-scatters the flattened gradients through the
    public collective API (native controller when launched under
    ``tpurun`` — the entries negotiate, fuse and cache exactly like
    allreduce entries — or the engine's compiled/cached executables on
    the fallback path, including the multi-bucket single-program path of
    ``CollectiveEngine.reducescatter_multi``), applies the inner update
    to this process's shard only, and allgathers the update deltas.
    The returned updates obey the usual optax contract
    (``optax.apply_updates(params, updates)``).

    ``op`` must be Average (default) or Sum.  ``params`` is REQUIRED at
    ``update`` time (the shard of the flattened parameters feeds the
    inner transformation, e.g. adamw's weight decay).
    ``backward_passes_per_step`` composes exactly as in
    :func:`DistributedOptimizer`: ``optax.MultiSteps`` accumulates the
    FULL local gradient and the sharded exchange runs once per k
    microbatches.  ``min_total_bytes`` (default
    ``HVD_TPU_ZERO_MIN_BYTES``, 0): below this many TOTAL parameter
    bytes (summed over the whole pytree, not per-rank shard) the
    wrapper falls back to replicated state + one allreduce — the
    decision is a pure function of the (static) parameter sizes, so
    every rank takes the same path with no negotiation.

    ``hierarchical`` (default: the HVD_TPU_HIERARCHICAL_ALLREDUCE env
    flag) selects the two-level fabric-aware exchange when the topology
    spans >1 slice and processes group evenly into slices: gradients
    reduce-scatter over the SLICE-LOCAL process set (ICI), only the
    1/n_local shard crosses DCN (an allreduce over the cross-slice set
    of same-position processes — optionally in ``dcn_compression``'s
    wire dtype, with the error-feedback residual riding
    ``ZeroState.residual``), and the update deltas allgather back on
    ICI.  The state then shards by the slice-local world (the ZeRO++
    "secondary partition": memory drops by processes-per-slice instead
    of world, in exchange for DCN traffic shrinking to the hierarchical
    -allreduce level — docs/COLLECTIVES.md has the byte model).  When
    the topology offers no such grouping the wrapper silently uses the
    flat exchange; both decisions are pure functions of the frozen
    topology, so every rank agrees with no negotiation.  NOTE: the
    eager cross-slice allreduce accumulates in the wire dtype (one
    negotiated op); prefer bf16 (fp32-range) wire, or the SPMD wrapper
    whose DCN hop accumulates in fp32.
    """
    if op not in (ReduceOp.AVERAGE, ReduceOp.SUM):
        raise ValueError(f"ZeroDistributedOptimizer supports Sum/Average, "
                         f"got {op!r}")
    min_bytes = _zero_min_bytes(min_total_bytes)

    def _world_me() -> Tuple[int, int]:
        eng = basics._require_init().engine
        return eng.member_info(process_set)

    # Hierarchical topology resolution — once, lazily (init() may run
    # before hvd.init in eval_shape contexts; the first real call pins
    # it).  Value: None = flat exchange; else (local_ps, cross_ps,
    # n_local, n_slices) with the process sets registered symmetrically
    # on every rank (same deterministic order).
    hier_cache: dict = {}

    def _hier_sets():
        if "v" in hier_cache:
            return hier_cache["v"]
        v = None
        if process_set is None:
            st = basics._require_init()
            want = hierarchical
            if want is None:
                want = bool(st.config is not None
                            and st.config.hierarchical_allreduce)
            groups = st.topology.process_slice_groups() if want else None
            if groups is not None and len(groups[0]) > 1:
                import horovod_tpu as hvd  # runtime: the package is loaded

                me_proc = st.topology.process_index

                def chips(procs):
                    return [
                        r for r, d in enumerate(st.topology.devices)
                        if getattr(d, "process_index", 0) in set(procs)
                    ]

                local_sets = [hvd.add_process_set(chips(g)) for g in groups]
                n_local = len(groups[0])
                cross_sets = [
                    hvd.add_process_set(
                        chips([g[j] for g in groups]))
                    for j in range(n_local)
                ]
                my_slice = next(
                    i for i, g in enumerate(groups) if me_proc in g
                )
                my_pos = groups[my_slice].index(me_proc)
                v = (local_sets[my_slice], cross_sets[my_pos],
                     n_local, len(groups))
        hier_cache["v"] = v
        return v

    feedback = dcn_compression is not None and dcn_compression.error_feedback

    # The plan is a pure function of (leaf shapes/dtypes, world); cache
    # it so un-jitted eager steps don't pay O(leaves) bucket/padding
    # arithmetic per update.  Keyed on world too: elastic restarts that
    # resize re-plan instead of slicing with stale shard sizes.
    plan_cache: dict = {}

    def _plan_for(params):
        if params is None:
            raise ValueError(
                "ZeroDistributedOptimizer requires params at init/update "
                "time (the inner update runs on the parameter shard)"
            )
        world, me = _world_me()
        hier = _hier_sets() if world > 1 else None
        plan_world = hier[2] if hier is not None else world
        leaves, treedef = jax.tree_util.tree_flatten(params)
        key = (plan_world, treedef, tuple(
            (tuple(np.shape(x)),
             jnp.dtype(getattr(x, "dtype", None) or jnp.asarray(x).dtype))
            for x in leaves
        ))
        cached = plan_cache.get(key)
        if cached is None:
            plan = ZeroPlan(leaves, plan_world)
            cached = (plan, plan_world > 1
                      and plan.total_bytes >= min_bytes)
            plan_cache[key] = cached
        plan, sharded = cached
        if hier is not None and sharded:
            # shard index = this process's position in the slice-local
            # member order (the engine's member index for that set — the
            # same order its reducescatter chunks and allgather concats)
            eng = basics._require_init().engine
            _, me_local = eng.member_info(hier[0])
            return plan, treedef, sharded, world, me_local, hier
        return plan, treedef, sharded, world, me, None

    def _init_residual(plan, hier):
        if not (feedback and hier is not None):
            return None
        return [
            jnp.zeros((s,), jnp.dtype(dt))
            for (dt, _), s in zip(plan.buckets, plan.shard_sizes)
        ]

    def init(params):
        plan, _, sharded, _, me, hier = _plan_for(params)
        bufs = plan.flatten(jax.tree_util.tree_leaves(params))
        if sharded:
            bufs = _slice_shards(plan, bufs, me)
        inner_state = optimizer.init(bufs)
        _metrics.OPTIM_STATE_SHARD_BYTES.set(
            state_bytes_abstract(inner_state))
        return ZeroState(
            inner=inner_state,
            residual=_init_residual(plan, hier) if sharded else None,
        )

    def update(grads, state, params=None):
        plan, treedef, sharded, world, me, hier = _plan_for(params)
        g_leaves = _zero_cast_grads(
            jax.tree_util.tree_leaves(grads), plan.specs)
        g_bufs = plan.flatten(g_leaves)
        p_bufs = plan.flatten(jax.tree_util.tree_leaves(params))
        new_residual = state.residual
        if sharded and hier is not None:
            local_ps, cross_ps, n_local, n_slices = hier
            from .ops.reduce_ops import Sum as _Sum

            _metrics.OPTIM_RS_BYTES.inc(plan.padded_bytes)
            # ICI: reduce-scatter the flat gradients over the slice
            g_shards = collective_ops.reducescatter(
                g_bufs, op=_Sum, name="zero.grads.local",
                process_set=local_ps,
            )
            # DCN: allreduce only the 1/n_local shard across slices, in
            # the wire dtype when compression is on (error feedback
            # rides ZeroState.residual)
            residuals = (
                state.residual if state.residual is not None
                else [None] * len(g_shards)
            )
            wires, new_residual = [], []
            for shard, res in zip(g_shards, residuals):
                if dcn_compression is not None:
                    w, nr = dcn_compression.compress_shard(shard, res)
                else:
                    w, nr = shard, res
                wires.append(w)
                new_residual.append(nr)
            if not feedback:
                new_residual = None
            reduced = collective_ops.allreduce(
                wires, op=_Sum, name="zero.grads.cross",
                process_set=cross_ps,
            )
            def _finish(w, shard):
                r = (dcn_compression.decompress_shard(w, shard.dtype)
                     if dcn_compression is not None else w)
                if op == ReduceOp.AVERAGE:
                    r = r / jnp.asarray(world, r.dtype)
                return r

            g_shards = [
                _finish(w, s) for w, s in zip(reduced, g_shards)
            ]
            p_shards = _slice_shards(plan, p_bufs, me)
            u_shards, new_inner = optimizer.update(
                g_shards, state.inner, p_shards
            )
            _metrics.OPTIM_AG_BYTES.inc(plan.shard_bytes)
            # ICI: the update deltas fan back out within the slice; all
            # slices computed identical shards, so params stay replicated
            u_bufs = collective_ops.allgather(
                u_shards, name="zero.updates.local", process_set=local_ps,
            )
        elif sharded:
            _metrics.OPTIM_RS_BYTES.inc(plan.padded_bytes)
            g_shards = collective_ops.reducescatter(
                g_bufs, op=op, name="zero.grads",
                process_set=process_set,
            )
            p_shards = _slice_shards(plan, p_bufs, me)
            u_shards, new_inner = optimizer.update(
                g_shards, state.inner, p_shards
            )
            _metrics.OPTIM_AG_BYTES.inc(plan.shard_bytes)
            u_bufs = collective_ops.allgather(
                u_shards, name="zero.updates", process_set=process_set,
            )
        else:
            if world > 1:
                g_bufs = collective_ops.allreduce(
                    g_bufs, op=op, name="zero.grads",
                    process_set=process_set,
                )
            # world of one: allreduce(avg) is identity, skip the call
            u_bufs, new_inner = optimizer.update(
                g_bufs, state.inner, p_bufs
            )
        updates = jax.tree_util.tree_unflatten(
            treedef, plan.unflatten(u_bufs)
        )
        return updates, ZeroState(inner=new_inner, residual=new_residual)

    zero = optax.GradientTransformation(init, update)
    if backward_passes_per_step > 1:
        zero = optax.MultiSteps(
            zero, every_k_schedule=backward_passes_per_step
        )
    return zero


def state_bytes_abstract(tree: Any) -> int:
    """``state_bytes`` over abstract (ShapeDtypeStruct) leaves."""
    return sum(
        int(np.prod(leaf.shape, dtype=np.int64))
        * jnp.dtype(leaf.dtype).itemsize
        for leaf in jax.tree_util.tree_leaves(tree)
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype")
    )


def ZeroSpmdOptimizer(
    optimizer: optax.GradientTransformation,
    axis: str = WORLD_AXIS,
    op: ReduceOp = Average,
    hierarchical: bool = False,
    ici_axis: str = ICI_AXIS,
    dcn_axis: str = DCN_AXIS,
    dcn_compression=None,
) -> optax.GradientTransformation:
    """The SPMD twin of :func:`ZeroDistributedOptimizer` — call ``init``
    and ``update`` INSIDE a ``shard_map`` over ``axis`` (the per-chip
    programming model of ``ops.spmd_ops``).

    Per chip: gradients flatten into per-dtype buffers, each
    ``psum_scatter``'d over ``axis`` (one fused ICI reduce-scatter —
    the first half of the ring allreduce XLA would have emitted), the
    inner optimizer updates this chip's 1/axis_size slice, and the
    update slices ``all_gather`` back (the second half).  The inner
    state holds only the shard, so Adam's m/v shrink by the axis size.

    ``hierarchical=True`` is the two-level fabric-aware variant for a
    ``hierarchical_mesh()``'s ``(dcn, ici)`` axes: the reduce-scatter
    runs ICI-first at full precision and only the 1/n_ici piece crosses
    DCN; the update-shard allgather crosses DCN first, then fans out on
    ICI.  A local chunk transpose keeps the shard landing identical to
    the flat order, so the partition (and the update arithmetic) is
    bit-compatible with the flat wrapper (pinned by
    tests/test_zero_optimizer.py).  ``dcn_compression``
    (:class:`~horovod_tpu.compression.DcnCompression`) then casts only
    the DCN-crossing bytes to the wire dtype; with ``error_feedback``
    the quantization residual rides ``ZeroState.residual``.

    State layout across the mesh: every inner-state leaf that mirrors a
    shard buffer is axis-sharded — :func:`zero_opt_state_specs` builds
    the matching ``PartitionSpec`` tree for host-side init/donation
    (``training.zero_train_setup`` wires both for the world mesh).

    Integrity-guard composition (``horovod_tpu.guard``,
    docs/FAULT_TOLERANCE.md; ``training.zero_train_setup(guard=True)``
    wires it): the guard's agreement object is the POST-allgather
    update deltas this wrapper returns — replicated across the axis,
    so digests compare cross-rank directly.  Per-chip intermediates
    (the reduce-scattered shards, local grads) deliberately carry NO
    detector: they differ across devices by design, so their values
    cannot ride a replicated diag output, and a non-finite shard
    reaches the returned deltas through the inner update the same
    step anyway.
    """
    if op not in (ReduceOp.AVERAGE, ReduceOp.SUM):
        raise ValueError(
            f"ZeroSpmdOptimizer supports Sum/Average, got {op!r}")
    if dcn_compression is not None and not hierarchical:
        raise ValueError(
            "dcn_compression requires hierarchical=True (it compresses "
            "the DCN hop, which only exists on the two-level exchange)")
    feedback = hierarchical and dcn_compression is not None and \
        dcn_compression.error_feedback

    def _world():
        if hierarchical:
            return jax.lax.axis_size(ici_axis) * jax.lax.axis_size(dcn_axis)
        return jax.lax.axis_size(axis)

    def _me():
        if hierarchical:
            return (
                jax.lax.axis_index(dcn_axis) * jax.lax.axis_size(ici_axis)
                + jax.lax.axis_index(ici_axis)
            )
        return jax.lax.axis_index(axis)

    def _plan_for(params):
        if params is None:
            raise ValueError(
                "ZeroSpmdOptimizer requires params at init/update time")
        leaves, treedef = jax.tree_util.tree_flatten(params)
        return ZeroPlan(leaves, _world()), treedef

    def _init_residual(plan):
        if not feedback:
            return None
        n_ici = jax.lax.axis_size(ici_axis)
        return [
            jnp.zeros((padded // n_ici,), jnp.dtype(dt))
            for (dt, _), padded in zip(plan.buckets, plan.padded_sizes)
        ]

    def init(params):
        plan, _ = _plan_for(params)
        bufs = plan.flatten(jax.tree_util.tree_leaves(params))
        inner_state = optimizer.init(_slice_shards(plan, bufs, _me()))
        # shapes are static, so the gauge is correct even though init
        # traces: set once per (re)trace with the shard's true bytes
        _metrics.OPTIM_STATE_SHARD_BYTES.set(
            state_bytes_abstract(inner_state))
        return ZeroState(inner=inner_state, residual=_init_residual(plan))

    def update(grads, state, params=None):
        plan, treedef = _plan_for(params)
        me = _me()
        world = plan.world
        g_leaves = _zero_cast_grads(
            jax.tree_util.tree_leaves(grads), plan.specs)
        g_bufs = plan.flatten(g_leaves)

        new_residual = state.residual
        if hierarchical:
            residuals = (
                state.residual if state.residual is not None
                else [None] * len(g_bufs)
            )
            g_shards, new_residual = [], []
            # "exchange" (trace.DEVICE_SCOPES): the training step scopes
            # this whole update "optimizer"; the innermost scope names
            # the phase, so the split all-reduce reads as the exchange
            with jax.named_scope("exchange"):
                for buf, res in zip(g_bufs, residuals):
                    shard, nr = spmd_ops._two_level_reduce_scatter_flat(
                        buf, ici_axis, dcn_axis, dcn_compression, res
                    )
                    if op == ReduceOp.AVERAGE:
                        shard = shard / jnp.asarray(world, shard.dtype)
                    g_shards.append(shard)
                    new_residual.append(nr)
            if not feedback:
                new_residual = None
        else:
            with jax.named_scope("exchange"):
                g_shards = [spmd_ops.reducescatter(buf, op=op, axis=axis)
                            for buf in g_bufs]
        p_bufs = plan.flatten(jax.tree_util.tree_leaves(params))
        p_shards = _slice_shards(plan, p_bufs, me)
        u_shards, new_inner = optimizer.update(
            g_shards, state.inner, p_shards
        )
        with jax.named_scope("exchange"):
            if hierarchical:
                u_bufs = [
                    spmd_ops._two_level_all_gather_flat(
                        u, ici_axis, dcn_axis, dcn_compression
                    )
                    for u in u_shards
                ]
            else:
                u_bufs = [spmd_ops.allgather(u, axis=axis)
                          for u in u_shards]
        updates = jax.tree_util.tree_unflatten(
            treedef, plan.unflatten(u_bufs)
        )
        return updates, ZeroState(inner=new_inner, residual=new_residual)

    return optax.GradientTransformation(init, update)


def zero_opt_state_specs(
    optimizer: optax.GradientTransformation,
    params: Any,
    world: int,
    axis=WORLD_AXIS,
    dcn_compression=None,
) -> Any:
    """``PartitionSpec`` tree for a :func:`ZeroSpmdOptimizer` state over
    a mesh whose ``axis`` has ``world`` chips.

    Inner-state leaves laid out like a shard buffer (1-D, one of the
    plan's per-dtype shard lengths) are sharded ``P(axis)`` — their
    global view is the (world*shard,) concatenation of every chip's
    slice; scalars and anything else (step counts, schedule state) are
    replicated.  The inner state is derived via ``eval_shape`` over the
    abstract shard buffers, so no device computation runs here.

    ``axis`` may be a tuple of mesh axis names for the hierarchical
    wrapper (``("dcn", "ici")`` — dim 0 sharded over both fabric tiers;
    ``world`` is then the product of both axis sizes).  With
    error-feedback ``dcn_compression`` the residual leaves (one per
    dtype bucket, also per-chip) get the same sharded spec."""
    leaves = jax.tree_util.tree_leaves(params)
    plan = ZeroPlan(leaves, world)
    inner_abs = jax.eval_shape(optimizer.init, plan.shard_abstract())
    shard_shapes = {
        ((s,), str(jnp.dtype(dt)))
        for (dt, _), s in zip(plan.buckets, plan.shard_sizes)
    }
    from jax.sharding import PartitionSpec as P

    def assign(leaf):
        if (tuple(leaf.shape), str(jnp.dtype(leaf.dtype))) in shard_shapes:
            return P(axis)
        return P()

    residual_specs = None
    if dcn_compression is not None and getattr(
        dcn_compression, "error_feedback", False
    ):
        residual_specs = [P(axis)] * len(plan.buckets)
    return ZeroState(
        inner=jax.tree_util.tree_map(assign, inner_abs),
        residual=residual_specs,
    )


def sharded_state_bytes_per_rank(state: Any, specs: Any,
                                 world: int) -> int:
    """Per-rank bytes of a mesh-laid-out state: leaves with a sharded
    ``PartitionSpec`` (from :func:`zero_opt_state_specs`) count 1/world
    of their global bytes, replicated leaves count fully — the
    ``opt_state_bytes_per_rank`` column of tools/transformer_bench.py."""
    from jax.sharding import PartitionSpec as P

    def leaf_bytes(leaf, spec):
        nb = int(getattr(leaf, "nbytes", 0) or 0)
        sharded = isinstance(spec, P) and any(
            s is not None for s in spec
        )
        return nb // world if sharded else nb

    return sum(
        jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(leaf_bytes, state, specs)
        )
    )
