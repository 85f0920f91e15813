"""Data-parallel training loop building blocks.

Reference analog: the training-loop pattern repeated across the reference's
examples/ (hvd.init → broadcast_parameters → DistributedOptimizer step —
SURVEY.md §3.2) packaged as a library: a ``TrainState`` and a compiled
SPMD train step over the world mesh.  One call produces the whole hot
path — forward, backward, fused gradient allreduce over ICI, optimizer
update — as a single XLA program, which is the TPU-native replacement for
the reference's background-thread overlap machinery.  The program alone
does not hide the exchange: on the TPU compiler an all-reduce is
synchronous unless the compile asks otherwise, so the data-parallel step
builder attaches ``spmd_ops.exchange_compile_options`` to its ``jit``
(TPU devices and more than one of them on the axis; else nothing).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import flax.struct
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import trace
from .common import basics
from .common.retry import env_int
from .common.topology import WORLD_AXIS
from .ops import spmd_ops
from .ops.reduce_ops import Average, ReduceOp


def _resolve_guard(guard: Optional[bool]) -> bool:
    """``guard=None`` defers to ``HVD_TPU_GUARD`` (docs/running.md) —
    the env spelling the ``HVD_TPU_GUARD=0`` zero-added-collectives
    contract is stated against (tools/guard_bench.py pins it)."""
    if guard is None:
        return bool(env_int("HVD_TPU_GUARD", 0))
    return bool(guard)


class TrainState(flax.struct.PyTreeNode):
    step: jax.Array
    params: Any
    opt_state: Any
    batch_stats: Any = None


def softmax_cross_entropy(logits, labels):
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, labels
    ).mean()


def _mean_written_stats(new_stats, old_stats, mean: Callable):
    """``mean`` over the leaves of ``batch_stats`` that the forward pass
    wrote, as one call over all of them: replicas see different batches, so
    running statistics are averaged (sync-BN semantics; reference:
    torch/sync_batch_norm.py).  A leaf the forward pass did not write (a
    router's selection bias: state that no step moves) is the same on every
    replica and is carried as it is, bit for bit: a sum of equal floats over
    a number of devices that is no power of two, or summed one by one, rounds."""
    leaves, treedef = jax.tree_util.tree_flatten(new_stats)
    written = [i for i, (new, old) in enumerate(
        zip(leaves, jax.tree_util.tree_leaves(old_stats))) if new is not old]
    for i, averaged in zip(written, mean([leaves[i] for i in written])):
        leaves[i] = averaged
    return treedef.unflatten(leaves)


def create_train_state(
    model, optimizer: optax.GradientTransformation, rng, sample_input
) -> TrainState:
    """Initialize the model and the optimizer state.  Recorded at the
    ``train.create_state`` site with the parameter count and the backend
    compiles the call paid (``trace.compile_totals``: the process's one
    recorder), and inside it ``train.model_init`` (``model.init`` runs op
    by op: this is where a job's start-up time goes) and
    ``train.optimizer_init``, each with its own compiles."""
    with trace.compile_span("train.create_state") as sp:
        with trace.compile_span("train.model_init"):
            variables = model.init(rng, sample_input)
        params = variables["params"]
        with trace.compile_span("train.optimizer_init"):
            state = TrainState(
                step=jnp.zeros((), jnp.int32),
                params=params,
                opt_state=optimizer.init(params),
                batch_stats=variables.get("batch_stats"),
            )
        if sp is not None:
            sp.set(params=sum(int(x.size) for x in
                              jax.tree_util.tree_leaves(params)))
    return state


def data_parallel_train_step(
    model,
    optimizer: optax.GradientTransformation,
    mesh: Optional[Mesh] = None,
    axis: str = WORLD_AXIS,
    loss_fn: Callable = softmax_cross_entropy,
    op: ReduceOp = Average,
    guard: Optional[bool] = None,
) -> Callable:
    """Build the compiled data-parallel train step.

    Returns ``step(state, images, labels) -> (state, loss)`` where the
    batch is sharded over ``axis`` and gradients are reduced with ``op``
    across it.  Everything the reference does per-step in §3.2 (ready-event
    waits, fusion memcpys, NCCL ring, handle sync) is this one program.

    ``optimizer`` should be the *inner* optax optimizer — the gradient
    allreduce is inserted here (equivalent to wrapping with
    DistributedOptimizer; don't do both or gradients reduce twice).

    On TPU devices with more than one of them on ``axis`` the step is
    compiled with ``spmd_ops.exchange_compile_options``: the gradients'
    all-reduces become asynchronous and run beside the backward's
    weight-gradient matmuls and the optimizer's update (on one chip, on
    the CPU and on any other backend: no option, the step as it always
    was).  The arithmetic is the same either way.

    ``guard=True`` (``None`` = the ``HVD_TPU_GUARD`` env flag) makes
    the step ALSO return the silent-corruption diagnostics
    (:func:`horovod_tpu.guard.step_diag` over the POST-allreduce
    gradients): ``step(state, x, y) -> (state, loss, diag)``.  The
    detectors are pure extra outputs over the same dataflow — state
    and loss stay BIT-identical to the unguarded step, and no
    collective is added (the digest exchange runs host-side at
    cadence; see :func:`fit_epoch` and docs/FAULT_TOLERANCE.md).
    """
    guard = _resolve_guard(guard)
    if mesh is None:
        mesh = basics._require_init().process_set_registry.get(0).mesh

    def _step(state: TrainState, images, labels):
        # forward / exchange / optimizer: device scopes
        # (trace.DEVICE_SCOPES).  Metadata only: the program is the same
        # operations with or without them; value_and_grad's transpose of
        # the forward scope is the backward
        def compute_loss(params):
            with jax.named_scope("forward"):
                variables = {"params": params}
                if state.batch_stats is not None:
                    variables["batch_stats"] = state.batch_stats
                    out, updates = model.apply(
                        variables, images, mutable=["batch_stats"]
                    )
                    logits = out
                    new_stats = updates["batch_stats"]
                else:
                    logits = model.apply(variables, images)
                    new_stats = None
                return loss_fn(logits, labels), new_stats

        (loss, new_stats), grads = jax.value_and_grad(
            compute_loss, has_aux=True
        )(state.params)
        with jax.named_scope("exchange"):
            grads = spmd_ops.allreduce(grads, op=op, axis=axis)
            loss = spmd_ops.allreduce(loss, axis=axis)
            if new_stats is not None:
                new_stats = _mean_written_stats(
                    new_stats, state.batch_stats,
                    lambda stats: spmd_ops.allreduce(stats, axis=axis))
        with jax.named_scope("optimizer"):
            updates, new_opt_state = optimizer.update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(
            step=state.step + 1,
            params=new_params,
            opt_state=new_opt_state,
            batch_stats=new_stats,
        )
        if guard:
            from .guard import step_diag

            return new_state, loss, step_diag(loss, grads)
        return new_state, loss

    sharded = jax.shard_map(
        _step,
        mesh=mesh,
        in_specs=(P(), P(axis), P(axis)),
        out_specs=(P(), P(), P()) if guard else (P(), P()),
        check_vma=False,
    )
    # the all-reduces run beside the backward only if the compile asks
    # for it (TPU, more than one device on the axis; else no option)
    return jax.jit(
        sharded, donate_argnums=(0,),
        compiler_options=spmd_ops.exchange_compile_options(mesh, axis)
        or None,
    )


def zero_train_setup(
    model,
    inner_optimizer: optax.GradientTransformation,
    rng,
    sample_input,
    mesh: Optional[Mesh] = None,
    axis: str = WORLD_AXIS,
    loss_fn: Callable = softmax_cross_entropy,
    op: ReduceOp = Average,
    hierarchical: bool = False,
    dcn_compression=None,
    guard: Optional[bool] = None,
):
    """Build a ZeRO-sharded data-parallel trainer over the world mesh.

    The sharded sibling of ``create_train_state`` +
    ``data_parallel_train_step``: the optimizer state is partitioned
    across ``axis`` (``optim.ZeroSpmdOptimizer`` — reduce-scatter →
    local shard update → allgather inside the one compiled step), so
    each chip holds ~1/world of Adam's m/v instead of a full replica —
    the ZeRO stage-1 memory attack on PERF.md's large-batch limiter.

    ``hierarchical=True`` lays the same program over the topology's
    2-D ``hierarchical_mesh()`` instead: the ZeRO exchange runs
    ICI-first and only the 1/n_ici piece crosses DCN — optionally in
    ``dcn_compression``'s wire dtype (docs/COLLECTIVES.md byte model);
    ``mesh`` then defaults to ``topology.hierarchical_mesh()`` and
    ``axis`` is ignored in favor of the ``(dcn, ici)`` fabric axes.

    Returns ``(state, step, opt_state_specs)``: ``state.opt_state``
    leaves that mirror shard buffers are laid out ``P(axis)`` on the
    mesh (``opt_state_specs`` says which — also what per-rank memory
    accounting divides by world), and ``step(state, inputs, labels) ->
    (state, loss)`` matches ``data_parallel_train_step``'s contract.
    Pass the INNER optax optimizer; do not wrap it in a Zero/Distributed
    wrapper yourself.

    ``guard=True`` (``None`` = ``HVD_TPU_GUARD``) adds the silent-
    corruption diagnostics as a third step output, composing with
    every mode above.  Both detectors read only REPLICATED values —
    the mean loss and the POST-allgather update deltas (the cross-rank
    agreement object): per-chip intermediates (local grads, the
    reduce-scattered shards) differ across devices by design and
    cannot ride the diag's ``P()`` output spec; a non-finite shard is
    still caught the SAME cadence because the inner update propagates
    it into the allgathered deltas.  State and loss stay bit-identical
    to the unguarded step; zero collectives are added.
    """
    from .common.topology import DCN_AXIS, ICI_AXIS
    from .optim import ZeroSpmdOptimizer, zero_opt_state_specs

    guard = _resolve_guard(guard)

    if hierarchical:
        if mesh is None:
            mesh = basics._require_init().topology.hierarchical_mesh()
        axis = (DCN_AXIS, ICI_AXIS)
        world = int(mesh.shape[DCN_AXIS] * mesh.shape[ICI_AXIS])
        zopt = ZeroSpmdOptimizer(
            inner_optimizer, op=op, hierarchical=True,
            ici_axis=ICI_AXIS, dcn_axis=DCN_AXIS,
            dcn_compression=dcn_compression,
        )
    else:
        if mesh is None:
            mesh = basics._require_init().process_set_registry.get(0).mesh
        world = int(mesh.shape[axis])
        zopt = ZeroSpmdOptimizer(inner_optimizer, axis=axis, op=op)

    # the split create_train_state records (docs/TRACING.md)
    with trace.compile_span("train.model_init"):
        variables = model.init(rng, sample_input)
    params = variables["params"]
    batch_stats = variables.get("batch_stats")
    with trace.compile_span("train.optimizer_init"):
        ospecs = zero_opt_state_specs(
            inner_optimizer, params, world, axis,
            dcn_compression=dcn_compression if hierarchical else None,
        )
        opt_state = jax.jit(jax.shard_map(
            zopt.init, mesh=mesh, in_specs=(P(),), out_specs=ospecs,
            check_vma=False,
        ))(params)
        state = TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=opt_state,
            batch_stats=batch_stats,
        )
    state_specs = TrainState(
        step=P(),
        params=P(),
        opt_state=ospecs,
        batch_stats=P() if batch_stats is not None else None,
    )

    def _mean(x):
        # a tuple axis (the hierarchical fabric mesh) means over both
        if isinstance(axis, tuple):
            return jax.tree_util.tree_map(
                # contract-ok: collectives -- unconditional scalar loss mean over BOTH fabric axes; the single-axis public API cannot spell a tuple-axis psum
                lambda t: jax.lax.psum(t, axis)
                / jnp.asarray(world, t.dtype),
                x,
            )
        return spmd_ops.allreduce(x, axis=axis)

    def _zero_diag(loss, updates):
        """Guard diagnostics for the ZeRO step, from REPLICATED values
        only: digest + finite sentinel over the POST-exchange update
        deltas (identical on every chip after the allgather — the
        cross-rank agreement object) and the mean loss.  Per-chip
        intermediates (local grads, reduce-scattered shards) differ
        across devices by design: feeding them to a ``P()``-spec'd
        output would surface ONE device's flag and silently drop the
        rest (check_vma=False) — and a non-finite shard reaches these
        deltas through the inner update the same step anyway."""
        from .guard import device_allfinite, device_digest

        return {"finite": device_allfinite((loss, updates)),
                "digest": device_digest(updates)}

    def _step(state: TrainState, images, labels):
        def compute_loss(params):
            with jax.named_scope("forward"):
                variables = {"params": params}
                if state.batch_stats is not None:
                    variables["batch_stats"] = state.batch_stats
                    out, updates = model.apply(
                        variables, images, mutable=["batch_stats"]
                    )
                    return loss_fn(out, labels), updates["batch_stats"]
                return (loss_fn(model.apply(variables, images), labels),
                        None)

        (loss, new_stats), grads = jax.value_and_grad(
            compute_loss, has_aux=True
        )(state.params)

        # no separate gradient allreduce: the ZeRO update IS the
        # reduction (reduce-scatter + allgather = the split allreduce;
        # ZeroSpmdOptimizer scopes that pair "exchange" itself)
        with jax.named_scope("exchange"):
            loss = _mean(loss)
            if new_stats is not None:
                new_stats = _mean_written_stats(
                    new_stats, state.batch_stats, _mean)
        with jax.named_scope("optimizer"):
            updates, new_opt_state = zopt.update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(
            step=state.step + 1,
            params=new_params,
            opt_state=new_opt_state,
            batch_stats=new_stats,
        )
        if guard:
            return new_state, loss, _zero_diag(loss, updates)
        return new_state, loss

    data_spec = P(axis)
    sharded = jax.shard_map(
        _step,
        mesh=mesh,
        in_specs=(state_specs, data_spec, data_spec),
        out_specs=(state_specs, P(), P()) if guard else (state_specs, P()),
        check_vma=False,
    )
    return state, jax.jit(sharded, donate_argnums=(0,)), ospecs


def fit_epoch(step: Callable, state: TrainState, loader,
              epoch: Optional[int] = None, *,
              checkpoint_dir: Optional[str] = None,
              checkpoint_every: int = 0,
              checkpoint_keep: Optional[int] = None,
              guard=None):
    """Drive one epoch of a compiled train step from a
    :class:`horovod_tpu.data.DataLoader` (or any iterable of
    ``(inputs, labels)`` batches).

    The drop-in loop for the ``horovod_tpu.data`` pipeline: the loader
    stages batch N+1 on device while the step computes batch N, so this
    is already overlapped — do NOT add ``block_until_ready`` per step
    (the chained-dependency dispatch queue is the pipeline).

        loader = hvd.data.DataLoader(source, batch_size=128)
        for epoch in range(epochs):
            state, loss = training.fit_epoch(step, state, loader, epoch)

    With ``checkpoint_dir`` + ``checkpoint_every`` set, rank 0 writes a
    crash-atomic checkpoint every N batches (``checkpoint.save_checkpoint``
    keyed by ``state.step``) — pair with ``checkpoint.restore_checkpoint``
    before training so a restarted job resumes instead of starting over
    (docs/FAULT_TOLERANCE.md).  The ``int(state.step)`` read is the only
    device sync this adds, and only on checkpoint batches.

    ``guard`` takes an armed :class:`horovod_tpu.guard.IntegrityGuard`
    when ``step`` was built with ``guard=True``: each step's on-device
    diagnostics feed the guard without a host sync, and on cadence
    steps the guard performs its ONE bounded sync (window + loss +
    param fingerprint), the cross-rank agreement check, and the
    response — :class:`~horovod_tpu.guard.IntegrityError` on detected
    corruption in non-elastic runs (reload a verified checkpoint), the
    quarantine/rollback restart path under the elastic driver
    (docs/FAULT_TOLERANCE.md, silent corruption).  ``checkpoint_keep``
    sizes the ring (default 3; with a guard armed it defaults to
    ``2 * guard.cadence`` — rollback discards every checkpoint newer
    than the last verified step, so a ring shallower than the cadence
    could be emptied entirely, degrading resume to step 0).

    Returns ``(state, last_loss)`` with the loss fetched to host — the
    end-of-epoch sync point.  ``last_loss`` is None for an empty shard.
    """
    from . import chaos as _chaos
    from . import checkpoint as _checkpoint
    from .utils.logging import set_log_context

    if epoch is not None and hasattr(loader, "set_epoch"):
        loader.set_epoch(epoch)
    if checkpoint_keep is None:
        checkpoint_keep = (max(3, 2 * guard.cadence)
                           if guard is not None
                           and getattr(guard, "enabled", False) else 3)
    loss = None
    batches = 0
    guard_base = None
    # the trace anchors steps GLOBALLY (cross-rank merge aligns on the
    # step number): one int(state.step) host sync per fit_epoch call,
    # and only while recording — the untraced loop stays sync-free.
    # The structured-log step field is stamped from the same base, so
    # it is only stamped while recording too (an epoch-relative number
    # would MISLABEL records against ckpt-N/guard step numbers).
    tracing = trace.enabled()
    trace_base = int(state.step) if tracing else 0
    for inputs, labels in loader:
        if _chaos.active:
            _chaos.raise_point("training.step")
        if tracing:
            step_no = trace_base + batches + 1
            set_log_context(step=step_no)
            # bridged as a STEP annotation: a capture groups device
            # work by the program's own step number
            with trace.span("train.step",
                            _xargs={"_r": 1, "step_num": step_no},
                            step=step_no,
                            epoch=-1 if epoch is None else epoch):
                out = step(state, inputs, labels)
        else:
            out = step(state, inputs, labels)
        if len(out) == 3:
            state, loss, diag = out
            if guard is not None:
                if guard_base is None:
                    # the guard numbers steps GLOBALLY (state.step):
                    # checkpoints are keyed by it, so rollback's
                    # discard_newer_than and the exchange keys must
                    # share the numbering across epochs and resumes.
                    # One host sync per fit_epoch call, not per step.
                    guard_base = int(state.step) - batches - 1
                guard.on_train_step(guard_base + batches + 1, loss,
                                    diag, params=state.params)
        else:
            state, loss = out
        batches += 1
        if (checkpoint_dir and checkpoint_every
                and batches % checkpoint_every == 0):
            _checkpoint.save_checkpoint(
                checkpoint_dir, state, int(state.step),
                keep=checkpoint_keep,
            )
    if loss is not None:
        loss = float(loss)
    return state, loss


def replicate_state(state: TrainState, mesh: Optional[Mesh] = None) -> TrainState:
    """Place the state replicated over the mesh (the moral equivalent of
    the reference's broadcast_parameters at train start: every chip holds
    identical weights)."""
    if mesh is None:
        mesh = basics._require_init().process_set_registry.get(0).mesh
    sharding = NamedSharding(mesh, P())
    with trace.span("train.replicate", bytes=sum(
            getattr(x, "nbytes", 0)
            for x in jax.tree_util.tree_leaves(state))):
        return jax.device_put(state, sharding)
