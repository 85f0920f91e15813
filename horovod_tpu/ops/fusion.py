"""Tensor fusion: dtype-bucketed pytree flattening.

TPU-native rethink of the reference's FusionBufferManager
(horovod/common/fusion_buffer_manager.cc, SURVEY.md §2.1): the reference
memcpys many small tensors into one persistent 64 MB device buffer so a
single NCCL call amortizes launch + ring latency.  Under XLA the concat and
split fuse into the collective's prologue/epilogue, so "the fusion buffer"
is simply ``concatenate`` inside the compiled program — no persistent
allocation, no memcpy kernels (cuda/cuda_kernels.cu BatchedD2DMemcpy has no
equivalent because XLA emits the batched copy itself).

What still matters on TPU and is kept:
  * one collective per dtype bucket (launch overhead, DCN message rate);
  * a byte threshold splitting huge buckets so a single fused psum does not
    blow HBM working-set limits (HOROVOD_FUSION_THRESHOLD semantics);
  * deterministic bucket assignment so every rank fuses identically — the
    invariant the reference's Controller negotiation exists to enforce.

:class:`BucketSchedule` extends the plan with a *launch order*: buckets
sorted by backward production order so each bucket's collective can start
while earlier layers' gradients are still computing — the PyTorch-DDP
bucketing insight (Li et al., VLDB '20), used by the torch bridge's
bucketed submission (``torch/optimizer.py``, docs/tensor-fusion.md).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _leaf_specs(leaves: Sequence[Any]) -> List[Tuple[Tuple[int, ...], Any]]:
    return [(tuple(x.shape), x.dtype) for x in leaves]


def _spec_nbytes(spec: Tuple[Tuple[int, ...], Any]) -> int:
    shape, dtype = spec
    return int(np.prod(shape, dtype=np.int64)) * jnp.dtype(dtype).itemsize


class FusionPlan:
    """Deterministic partition of a flat tensor list into dtype buckets.

    Equivalent role to the Response fusion built by the reference's
    Controller (horovod/common/controller.cc: tensors fused into Responses
    up to the fusion threshold), but computed locally: bucket layout is a
    pure function of (shapes, dtypes, threshold), identical on every rank
    because SPMD programs are identical — no negotiation required.
    """

    def __init__(self, leaves: Sequence[jax.Array], threshold_bytes: int):
        self._init_from_specs(_leaf_specs(leaves), threshold_bytes)

    @classmethod
    def from_specs(
        cls,
        specs: Sequence[Tuple[Sequence[int], Any]],
        threshold_bytes: int,
    ) -> "FusionPlan":
        """Build a plan from ``(shape, dtype)`` specs without arrays —
        the torch bridge builds its schedule from parameter metadata
        (``dtype`` is anything :func:`jnp.dtype` accepts, e.g.
        ``"float32"``)."""
        plan = cls.__new__(cls)
        plan._init_from_specs(
            [(tuple(s), d) for s, d in specs], threshold_bytes
        )
        return plan

    def _init_from_specs(self, specs, threshold_bytes: int):
        self.specs: List[Tuple[Tuple[int, ...], Any]] = list(specs)
        self.threshold_bytes = int(threshold_bytes)
        buckets: Dict[Any, List[int]] = {}
        bucket_bytes: Dict[Any, int] = {}
        self.buckets: List[Tuple[Any, List[int]]] = []
        if threshold_bytes <= 0:
            # HOROVOD_FUSION_THRESHOLD=0 disables fusion entirely
            # (reference contract): one bucket per tensor.
            self.buckets = [
                (jnp.dtype(dtype), [i])
                for i, (_, dtype) in enumerate(self.specs)
            ]
            return
        for i, (shape, dtype) in enumerate(self.specs):
            nbytes = int(np.prod(shape, dtype=np.int64)) * jnp.dtype(dtype).itemsize
            key = jnp.dtype(dtype)
            if key in buckets and (
                bucket_bytes[key] + nbytes <= threshold_bytes
                or bucket_bytes[key] == 0
            ):
                buckets[key].append(i)
                bucket_bytes[key] += nbytes
            else:
                if key in buckets:
                    self.buckets.append((key, buckets[key]))
                buckets[key] = [i]
                bucket_bytes[key] = nbytes
        for key, idxs in buckets.items():
            self.buckets.append((key, idxs))

    def signature(self) -> Tuple:
        """Hashable cache key (reference analog: the ResponseCache entry —
        SURVEY.md §7.1 maps negotiation caching onto executable caching).

        Includes the *bucket layout*, not just the leaf specs: two plans
        over the same leaves built under different
        ``HVD_TPU_FUSION_THRESHOLD`` values fuse into different buffer
        shapes, so a spec-only key would let an executable cached for one
        layout serve the other (the ops/engine.py collision this guards)."""
        return (
            tuple((tuple(s), str(jnp.dtype(d))) for s, d in self.specs),
            tuple(
                (str(jnp.dtype(dt)), tuple(idxs))
                for dt, idxs in self.buckets
            ),
        )


class BucketSchedule(FusionPlan):
    """A :class:`FusionPlan` whose buckets carry a *launch order* for
    backward/collective overlap (docs/tensor-fusion.md).

    ``production_order[i]`` is the position at which leaf ``i``'s gradient
    is complete during the backward pass (0 = produced first — i.e. the
    LAST forward layer, since backprop walks the chain in reverse).  When
    omitted, leaves are assumed listed in forward/parameter order and the
    production order is simply reversed list order.

    Layout rules:
      * leaves sort by ``(production_order, dtype, shape, size)`` — a pure
        function of the (spec, order) *multiset*, so ranks that observed
        the same tensors in permuted order build the identical layout (the
        invariant the reference's Controller negotiates; here it must hold
        by construction);
      * consecutively-produced same-dtype leaves pack greedily under
        ``threshold_bytes`` (``<= 0``: one bucket per leaf, the
        HOROVOD_FUSION_THRESHOLD=0 contract);
      * buckets order by ``ready_at`` — the production position of their
        LAST member, the earliest moment their collective can launch.
        The torch bridge submits bucket ``b`` as soon as the gradient at
        ``ready_at[b]`` has arrived, while earlier layers' are still
        computing.
    """

    def __init__(
        self,
        leaves: Sequence[jax.Array],
        threshold_bytes: int,
        production_order: Optional[Sequence[int]] = None,
    ):
        self._init_schedule(
            _leaf_specs(leaves), threshold_bytes, production_order
        )

    @classmethod
    def from_specs(
        cls,
        specs: Sequence[Tuple[Sequence[int], Any]],
        threshold_bytes: int,
        production_order: Optional[Sequence[int]] = None,
    ) -> "BucketSchedule":
        sched = cls.__new__(cls)
        sched._init_schedule(
            [(tuple(s), d) for s, d in specs], threshold_bytes,
            production_order,
        )
        return sched

    def _init_schedule(self, specs, threshold_bytes, production_order):
        self.specs = list(specs)
        self.threshold_bytes = int(threshold_bytes)
        n = len(self.specs)
        if production_order is None:
            production_order = [n - 1 - i for i in range(n)]
        if len(production_order) != n:
            raise ValueError(
                f"production_order has {len(production_order)} entries "
                f"for {n} leaves"
            )
        self.production_order = [int(p) for p in production_order]

        def key(i):
            shape, dtype = self.specs[i]
            return (
                self.production_order[i], str(jnp.dtype(dtype)), shape,
                _spec_nbytes(self.specs[i]),
            )

        order = sorted(range(n), key=key)
        self.buckets = []
        self.ready_at: List[int] = []
        self.bucket_nbytes: List[int] = []
        open_by_dtype: Dict[str, int] = {}  # dtype -> open bucket slot
        for i in order:
            _, dtype = self.specs[i]
            dt = jnp.dtype(dtype)
            nbytes = _spec_nbytes(self.specs[i])
            slot = open_by_dtype.get(str(dt))
            if (
                threshold_bytes > 0
                and slot is not None
                and (self.bucket_nbytes[slot] + nbytes <= threshold_bytes
                     or self.bucket_nbytes[slot] == 0)
            ):
                self.buckets[slot][1].append(i)
                self.bucket_nbytes[slot] += nbytes
                self.ready_at[slot] = max(
                    self.ready_at[slot], self.production_order[i]
                )
            else:
                open_by_dtype[str(dt)] = len(self.buckets)
                self.buckets.append((dt, [i]))
                self.bucket_nbytes.append(nbytes)
                self.ready_at.append(self.production_order[i])
        # launch order: earliest-ready first; dtype/content tie-breaks keep
        # the order a pure function of the (spec, order) multiset
        launch = sorted(
            range(len(self.buckets)),
            key=lambda b: (
                self.ready_at[b], str(self.buckets[b][0]),
                tuple(key(i) for i in self.buckets[b][1]),
            ),
        )
        self.buckets = [self.buckets[b] for b in launch]
        self.ready_at = [self.ready_at[b] for b in launch]
        self.bucket_nbytes = [self.bucket_nbytes[b] for b in launch]

    @property
    def num_buckets(self) -> int:
        return len(self.buckets)

    def signature(self) -> Tuple:
        return super().signature() + (
            tuple(self.production_order), tuple(self.ready_at),
        )

    def layout(self) -> Tuple:
        """Rank-comparable view of the bucket layout: per bucket, the
        ordered ``(shape, dtype, production_order)`` of its members —
        independent of the caller's leaf list order (the determinism
        tests compare this across permuted-but-equal inputs)."""
        return tuple(
            tuple(
                (self.specs[i][0], str(jnp.dtype(self.specs[i][1])),
                 self.production_order[i])
                for i in idxs
            )
            for _, idxs in self.buckets
        )


def fuse(leaves: Sequence[jax.Array], plan: FusionPlan) -> List[jax.Array]:
    """Flatten + concat each bucket into one 1-D buffer.  Traceable."""
    fused = []
    for _, idxs in plan.buckets:
        parts = [jnp.ravel(leaves[i]) for i in idxs]
        fused.append(parts[0] if len(parts) == 1 else jnp.concatenate(parts))
    return fused


def unfuse(fused: Sequence[jax.Array], plan: FusionPlan) -> List[jax.Array]:
    """Inverse of :func:`fuse`.  Traceable."""
    out: List[jax.Array] = [None] * len(plan.specs)  # type: ignore[list-item]
    for (dtype, idxs), buf in zip(plan.buckets, fused):
        offset = 0
        for i in idxs:
            shape, _ = plan.specs[i]
            n = int(np.prod(shape, dtype=np.int64))
            out[i] = jax.lax.dynamic_slice_in_dim(buf, offset, n).reshape(shape)
            offset += n
    return out
