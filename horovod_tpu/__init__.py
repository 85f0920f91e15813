"""horovod_tpu: a TPU-native distributed training framework with the
capabilities of Horovod (reference: rondogency/horovod — see SURVEY.md).

Public surface mirrors ``horovod.torch`` / ``horovod.tensorflow``
(SURVEY.md §2.3): ``init``/``shutdown``, rank/size topology queries, eager
async collectives with handles, ``DistributedOptimizer``,
``broadcast_parameters``, elastic state, process sets — plus the TPU-native
additions: the in-jit SPMD collective module (``hvd.spmd``), mesh access,
and the per-rank ``run_per_rank`` harness.

Typical JAX use::

    import horovod_tpu as hvd
    hvd.init()
    mesh = hvd.world_mesh()
    # ... shard batch over mesh axis "hvd"; inside the train step:
    grads = hvd.spmd.allreduce(grads)           # psum over ICI
    # or wrap the optimizer once:
    opt = hvd.DistributedOptimizer(optax.adam(1e-3))
"""

from __future__ import annotations

import sys as _sys
import time as _time

# the package's import on its own ring (site ``hvd.import``, recorded on
# the last line): trace first, it is import-light
_import_t0 = _time.perf_counter()
_jax_loaded = "jax" in _sys.modules
from . import trace
import jax as _jax  # noqa: F401  the compile recorder needs jax loaded

_import_compiles = trace.compile_totals()

from .common import basics as _basics
from .common.basics import (
    init,
    shutdown,
    is_initialized,
    rank,
    local_process_count,
    local_rank,
    size,
    local_size,
    cross_rank,
    cross_size,
    is_homogeneous,
    xla_built,
    nccl_built,
    mpi_enabled,
    mpi_built,
    mpi_threads_supported,
    gloo_built,
    gloo_enabled,
    ccl_built,
    cuda_built,
    rocm_built,
    ddl_built,
    native_built,
    start_timeline,
    stop_timeline,
)
from .common.exceptions import (
    HorovodInternalError,
    HostsUpdatedInterrupt,
    HorovodTpuError,
)
from .common.process_sets import ProcessSet, global_process_set
from .common.topology import WORLD_AXIS, DCN_AXIS, ICI_AXIS
from .ops import spmd_ops as spmd
from .ops.collective_ops import (
    Handle,
    allgather,
    allgather_async,
    allreduce,
    allreduce_async,
    alltoall,
    alltoall_async,
    barrier,
    broadcast,
    broadcast_async,
    grouped_allgather,
    grouped_allreduce,
    grouped_allreduce_async,
    grouped_reducescatter,
    grouped_reducescatter_async,
    join,
    poll,
    reducescatter,
    reducescatter_async,
    synchronize,
)
from .ops.flash_attention import flash_attention
from .ops.reduce_ops import Adasum, Average, Max, Min, Product, ReduceOp, Sum
from .ops.spmd_ops import run_per_rank
from .functions import (
    allgather_object,
    broadcast_object,
    broadcast_optimizer_state,
    broadcast_parameters,
)
from . import callbacks, chaos, checkpoint, data, elastic, guard, metrics
from .compression import Compression
from .sync_batch_norm import SyncBatchNorm
from .optim import (
    DistributedOptimizer,
    ZeroDistributedOptimizer,
    ZeroSpmdOptimizer,
    allreduce_gradients,
    with_gradient_accumulation,
    zero_opt_state_specs,
)

__version__ = "0.1.0"


def add_process_set(ranks) -> ProcessSet:
    """Register a new process set (reference: horovod/common/process_sets.py
    add_process_set).  Must be called symmetrically on every process; the
    set's member processes are mirrored into the native controller so
    negotiation counts readiness against the set, not the world."""
    st = _basics._require_init()
    ps = ranks if isinstance(ranks, ProcessSet) else ProcessSet(ranks)
    ps = st.process_set_registry.add(ps)
    if st.controller is not None and st.controller.is_native:
        procs = sorted({
            getattr(st.topology.devices[r], "process_index", 0)
            for r in ps.ranks
        })
        st.controller.register_process_set(ps.process_set_id, procs)
    return ps


def remove_process_set(process_set: ProcessSet) -> None:
    """Reference: horovod/common/process_sets.py remove_process_set."""
    st = _basics._require_init()
    set_id = process_set.process_set_id
    st.process_set_registry.remove(process_set)
    if st.controller is not None and st.controller.is_native:
        st.controller.remove_process_set(set_id)


def process_set_ids():
    return _basics._require_init().process_set_registry.ids()


def world_mesh():
    """The 1-D world mesh (every chip, axis ``"hvd"``)."""
    return _basics._require_init().process_set_registry.get(0).mesh


def hierarchical_mesh(num_groups=None):
    """2-D (dcn, ici) mesh for two-level reductions (reference analog:
    local/cross communicators of NCCLHierarchicalAllreduce)."""
    return _basics._require_init().topology.hierarchical_mesh(num_groups)


trace.add_span("hvd.import", _import_t0, trace.now(), jax_loaded=_jax_loaded,
               **trace.compile_delta(_import_compiles))
