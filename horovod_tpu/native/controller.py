"""ctypes binding to the native background controller.

Reference parity: horovod/torch/mpi_ops_v2.cc + handle_manager (SURVEY.md
§2.3) — the glue between the Python op layer and the C++ core.  The
reference builds a pybind11 module per framework; this image has no
pybind11, so the binding is ctypes over the flat C API (c_api.cc), which
is also closer to the reference's own `horovod/common/basics.py` ctypes
pattern for the C API.

Flow (the §3.2 hot path, TPU edition):
  Python enqueue -> C++ TensorQueue -> background thread negotiates ->
  fused Response -> exec callback (this module, on the C++ thread) ->
  CollectiveEngine launches the cached compiled XLA collective ->
  per-entry futures resolve -> Handle.wait() returns.
"""

from __future__ import annotations

import ctypes
import os
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import chaos as _chaos
from .. import trace as _trace
from ..common.exceptions import HorovodInternalError
from ..common.topology import Topology
from ..metrics import instruments as _metrics
from ..metrics.exposition import (
    register_health_source, unregister_health_source,
)
from ..metrics.registry import REGISTRY as _METRICS_REGISTRY
from ..utils.env_parser import Config
from ..utils.logging import get_logger

# Enum values must match native/src/common.h.
OP_ALLREDUCE, OP_ALLGATHER, OP_BROADCAST, OP_ALLTOALL, OP_REDUCESCATTER, \
    OP_BARRIER, OP_JOIN = range(7)

OP_NAMES = {
    OP_ALLREDUCE: "allreduce", OP_ALLGATHER: "allgather",
    OP_BROADCAST: "broadcast", OP_ALLTOALL: "alltoall",
    OP_REDUCESCATTER: "reducescatter", OP_BARRIER: "barrier",
    OP_JOIN: "join",
}

_DTYPES = [
    ("uint8", 0), ("int8", 1), ("int32", 2), ("int64", 3),
    ("float16", 4), ("bfloat16", 5), ("float32", 6), ("float64", 7),
    ("bool", 8), ("uint16", 9), ("uint32", 10), ("uint64", 11),
    ("int16", 12), ("complex64", 13), ("complex128", 14),
]
_DTYPE_TO_ENUM = {name: val for name, val in _DTYPES}
_ENUM_TO_DTYPE = {val: name for name, val in _DTYPES}

# HVD_TPU_PROFILER_BRIDGE=0 keeps the negotiated-collective spans out of
# jax.profiler captures (the trace ring still records them)
_BRIDGE = os.environ.get("HVD_TPU_PROFILER_BRIDGE", "1") != "0"


def _xname(name: str, activity: str):
    """The name a negotiated-collective span carries into an XPlane
    capture — the timeline's, ``hvd_tpu::<name>::<activity>``."""
    return f"hvd_tpu::{name}::{activity}" if _BRIDGE else False


_EXEC_CB = ctypes.CFUNCTYPE(
    None, ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
    ctypes.c_int, ctypes.c_double, ctypes.c_double,
    ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
    ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int),
    ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int),
    ctypes.c_int, ctypes.c_char_p,
)


class Future:
    """Reference analog: the handle slots of torch/handle_manager.h."""

    __slots__ = ("_event", "_result", "_error")

    def __init__(self):
        self._event = threading.Event()
        self._result = None
        self._error: Optional[BaseException] = None

    def set_result(self, value) -> None:
        self._result = value
        self._event.set()

    def set_error(self, err: BaseException) -> None:
        self._error = err
        self._event.set()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None):
        if not self._event.wait(timeout):
            raise TimeoutError("collective did not complete in time")
        if self._error is not None:
            raise self._error
        return self._result


class _Entry:
    __slots__ = ("payload", "future", "op", "extra", "name", "t0")

    def __init__(self, payload, future, op, extra=None, name=None,
                 t0=None):
        self.payload = payload
        self.future = future
        self.op = op
        self.extra = extra
        self.name = name  # set for locally submitted entries (timeline)
        self.t0 = t0  # submit perf_counter (None: synthesized entry)


class NativeController:
    is_native = True

    def __init__(self, lib_path: str, topology: Topology, config: Config):
        self._topology = topology
        self._config = config
        self._timeline_active = bool(config.timeline_filename)
        self._engine = None  # set via set_engine after engine construction
        self._entries: Dict[int, _Entry] = {}
        self._entries_lock = threading.Lock()
        self._name_counter = 0
        self._auto_counters: Dict[int, int] = {}
        self._auto_group_counters: Dict[int, int] = {}
        self._group_call_seqs: Dict[str, int] = {}
        self._lib = ctypes.CDLL(lib_path)
        self._declare(self._lib)
        # fault injection: export the transport.* rules of the installed
        # chaos plan into the core BEFORE init builds the transport (the
        # frame path evaluates them; no plan = one atomic check per frame)
        _chaos.configure_native_lib(self._lib,
                                    rank=topology.process_index)
        # the callback object must outlive the native thread: keep the ref
        self._cb = _EXEC_CB(self._on_exec)
        self._lib.hvdtpu_set_exec_callback(self._cb, None)
        # multi-process negotiation rides the TCP star the launcher set up
        # (HVD_TPU_NATIVE_PORT on the coordinator host); absent that,
        # loopback (reference analog: mpirun-vs-gloo controller selection)
        import os

        coord_host, coord_port = "", 0
        native_port = os.environ.get("HVD_TPU_NATIVE_PORT")
        if topology.num_processes > 1 and native_port:
            coord = os.environ.get("HVD_TPU_COORDINATOR", "127.0.0.1:0")
            coord_host = coord.rsplit(":", 1)[0]
            coord_port = int(native_port)
        rc = self._lib.hvdtpu_init(
            topology.process_index,
            max(topology.num_processes, 1) if coord_port else 1,
            coord_host.encode(),
            coord_port,
            ctypes.c_double(config.cycle_time_ms),
            ctypes.c_longlong(config.fusion_threshold_bytes),
            config.cache_capacity,
            config.timeline_filename.encode(),
            ctypes.c_double(
                0.0 if config.stall_check_disable
                else config.stall_warning_time_seconds
            ),
            ctypes.c_double(config.stall_shutdown_time_seconds),
            1 if config.autotune else 0,
            config.autotune_log.encode(),
        )
        if rc != 0:
            raise OSError(f"hvdtpu_init failed with {rc}")
        # telemetry: enqueue depth is live (set_function), the native
        # core's own stats refresh at scrape time (registry poll), and
        # /healthz reflects loop liveness + the stall inspector
        _metrics.ENQUEUE_DEPTH.set_function(self._depth)
        _METRICS_REGISTRY.register_poll(self._refresh_native_stats)
        register_health_source("native_controller", self._health)

    @staticmethod
    def _declare(lib) -> None:
        lib.hvdtpu_init.restype = ctypes.c_int
        lib.hvdtpu_init.argtypes = [
            ctypes.c_int, ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
            ctypes.c_double, ctypes.c_longlong,
            ctypes.c_int, ctypes.c_char_p, ctypes.c_double, ctypes.c_double,
            ctypes.c_int, ctypes.c_char_p,
        ]
        lib.hvdtpu_set_exec_callback.restype = None
        lib.hvdtpu_set_exec_callback.argtypes = [_EXEC_CB, ctypes.c_void_p]
        lib.hvdtpu_enqueue.restype = ctypes.c_longlong
        lib.hvdtpu_enqueue.argtypes = [
            ctypes.c_longlong, ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_longlong), ctypes.c_int, ctypes.c_int,
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
            ctypes.c_double, ctypes.c_double,
            ctypes.POINTER(ctypes.c_longlong), ctypes.c_int,
        ]
        try:
            lib.hvdtpu_enqueue_n.restype = ctypes.c_longlong
            lib.hvdtpu_enqueue_n.argtypes = [
                ctypes.c_int, ctypes.POINTER(ctypes.c_longlong),
                ctypes.POINTER(ctypes.c_char_p), ctypes.c_int,
                ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_longlong),
                ctypes.POINTER(ctypes.c_int), ctypes.c_int, ctypes.c_char_p,
                ctypes.c_int, ctypes.POINTER(ctypes.c_int),
                ctypes.c_double, ctypes.c_double,
            ]
        except AttributeError:
            # core built before the batched entry point: per-entry
            # enqueue still works (enqueue_batch callers check
            # supports_batch and fall back)
            pass
        lib.hvdtpu_register_process_set.restype = ctypes.c_int
        lib.hvdtpu_register_process_set.argtypes = [
            ctypes.c_int, ctypes.POINTER(ctypes.c_int), ctypes.c_int,
        ]
        lib.hvdtpu_remove_process_set.restype = ctypes.c_int
        lib.hvdtpu_remove_process_set.argtypes = [ctypes.c_int]
        # zero-arg getters carry explicit argtypes = [] — a bare
        # restype-only binding accepts (and silently discards) arbitrary
        # arguments, so arity drift would go unnoticed until the native
        # stack corrupted (tools/check.py c-api pass enforces this)
        lib.hvdtpu_shutdown.restype = None
        lib.hvdtpu_shutdown.argtypes = []
        lib.hvdtpu_initialized.restype = ctypes.c_int
        lib.hvdtpu_initialized.argtypes = []
        lib.hvdtpu_cache_hits.restype = ctypes.c_longlong
        lib.hvdtpu_cache_hits.argtypes = []
        lib.hvdtpu_cache_misses.restype = ctypes.c_longlong
        lib.hvdtpu_cache_misses.argtypes = []
        lib.hvdtpu_last_request_bytes.restype = ctypes.c_longlong
        lib.hvdtpu_last_request_bytes.argtypes = []
        lib.hvdtpu_fusion_threshold.restype = ctypes.c_longlong
        lib.hvdtpu_fusion_threshold.argtypes = []
        lib.hvdtpu_cycle_time_ms.restype = ctypes.c_double
        lib.hvdtpu_cycle_time_ms.argtypes = []
        lib.hvdtpu_autotune_active.restype = ctypes.c_int
        lib.hvdtpu_autotune_active.argtypes = []
        lib.hvdtpu_autotune_inject.restype = None
        lib.hvdtpu_autotune_inject.argtypes = [ctypes.c_double]
        lib.hvdtpu_pending_count.restype = ctypes.c_int
        lib.hvdtpu_pending_count.argtypes = []
        try:
            lib.hvdtpu_loop_dead.restype = ctypes.c_int
            lib.hvdtpu_loop_dead.argtypes = []
        except AttributeError:
            # core built before the liveness getter: /healthz then
            # reports liveness from the python-side entry table only
            pass
        try:
            lib.hvdtpu_chaos_set.restype = ctypes.c_int
            lib.hvdtpu_chaos_set.argtypes = [
                ctypes.c_char_p, ctypes.c_int, ctypes.c_double,
                ctypes.c_longlong, ctypes.c_longlong, ctypes.c_longlong,
                ctypes.c_double, ctypes.c_int, ctypes.c_char_p,
                ctypes.c_ulonglong,
            ]
            lib.hvdtpu_chaos_clear.restype = None
            lib.hvdtpu_chaos_clear.argtypes = []
            lib.hvdtpu_chaos_injections.restype = ctypes.c_longlong
            lib.hvdtpu_chaos_injections.argtypes = []
            lib.hvdtpu_heartbeat_misses.restype = ctypes.c_longlong
            lib.hvdtpu_heartbeat_misses.argtypes = []
        except AttributeError:
            # core built before the chaos/heartbeat API: transport.*
            # injection rules won't fire and heartbeat misses read 0
            # (configure_native_lib warns when a plan needs them)
            pass
        lib.hvdtpu_timeline_activity.restype = None
        lib.hvdtpu_timeline_activity.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int,
        ]
        lib.hvdtpu_start_timeline.restype = ctypes.c_int
        lib.hvdtpu_start_timeline.argtypes = [ctypes.c_char_p]
        lib.hvdtpu_stop_timeline.restype = ctypes.c_int
        lib.hvdtpu_stop_timeline.argtypes = []
        lib.hvdtpu_pack.restype = None
        lib.hvdtpu_pack.argtypes = [
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_longlong), ctypes.c_int,
            ctypes.c_void_p, ctypes.c_longlong,
        ]

    # -- wiring -------------------------------------------------------------

    def set_engine(self, engine) -> None:
        self._engine = engine

    def shutdown(self) -> None:
        _metrics.ENQUEUE_DEPTH.set_function(None)
        _METRICS_REGISTRY.unregister_poll(self._refresh_native_stats)
        unregister_health_source("native_controller")
        self._lib.hvdtpu_shutdown()
        # fail anything still registered so concurrent waiters raise
        # instead of blocking forever
        with self._entries_lock:
            leftovers = list(self._entries.values())
            self._entries.clear()
        err = HorovodInternalError("framework shut down with collectives "
                                   "in flight")
        for e in leftovers:
            e.future.set_error(err)

    # -- stats (reference: horovod_* C getters) -----------------------------

    def cache_hits(self) -> int:
        return int(self._lib.hvdtpu_cache_hits())

    def cache_misses(self) -> int:
        return int(self._lib.hvdtpu_cache_misses())

    def last_request_bytes(self) -> int:
        """Bytes of this rank's last non-empty negotiation report — small
        and constant in steady state (bit-vector bypass), larger when a
        full request encoding traveled (cache miss)."""
        return int(self._lib.hvdtpu_last_request_bytes())

    def fusion_threshold(self) -> int:
        return int(self._lib.hvdtpu_fusion_threshold())

    def cycle_time_ms(self) -> float:
        return float(self._lib.hvdtpu_cycle_time_ms())

    def autotune_active(self) -> bool:
        return bool(self._lib.hvdtpu_autotune_active())

    def autotune_inject(self, score: float) -> None:
        """Test hook: one tuner step with a synthetic score."""
        self._lib.hvdtpu_autotune_inject(float(score))

    def pending_count(self) -> int:
        return int(self._lib.hvdtpu_pending_count())

    def heartbeat_misses(self) -> int:
        """Heartbeat read-deadlines peers missed on the negotiation
        channel (0 on loopback or a pre-heartbeat core)."""
        fn = getattr(self._lib, "hvdtpu_heartbeat_misses", None)
        return int(fn()) if fn is not None else 0

    def chaos_injections(self) -> int:
        """Faults the NATIVE chaos engine injected so far (the Python
        engine counts its own through the metrics registry)."""
        fn = getattr(self._lib, "hvdtpu_chaos_injections", None)
        return int(fn()) if fn is not None else 0

    def loop_dead(self) -> bool:
        """True once the background loop exited (stall shutdown or
        transport death) — every later enqueue would raise."""
        fn = getattr(self._lib, "hvdtpu_loop_dead", None)
        return bool(fn()) if fn is not None else False

    # -- telemetry (metrics/ subsystem hooks) --------------------------------

    def _depth(self) -> int:
        with self._entries_lock:
            return len(self._entries)

    def _refresh_native_stats(self) -> None:
        """Scrape-time poll: copy the native core's cumulative stats into
        the pull gauges (zero hot-path cost — runs only on collection)."""
        _metrics.NATIVE_CACHE_HITS.set(self.cache_hits())
        _metrics.NATIVE_CACHE_MISSES.set(self.cache_misses())
        _metrics.NATIVE_PENDING.set(self.pending_count())
        _metrics.NATIVE_CYCLE_TIME_MS.set(self.cycle_time_ms())
        _metrics.NATIVE_FUSION_THRESHOLD.set(self.fusion_threshold())
        _metrics.NATIVE_AUTOTUNE_ACTIVE.set(
            1 if self.autotune_active() else 0
        )
        _metrics.NATIVE_LAST_REQUEST_BYTES.set(self.last_request_bytes())
        hb_delta = self.heartbeat_misses() - _metrics.HEARTBEAT_MISSES.get()
        if hb_delta > 0:
            _metrics.HEARTBEAT_MISSES.inc(hb_delta)
        native_chaos = self.chaos_injections()
        if native_chaos:
            # mirror the native engine's count under the shared chaos
            # counter (site granularity lives in its stderr log)
            counter = _metrics.CHAOS_INJECTIONS.labels(
                "transport.frame", "native")
            delta = native_chaos - counter.get()
            if delta > 0:
                counter.inc(delta)

    def _health(self):
        """/healthz source: unhealthy when the background loop died (the
        library rejects all further work) — pending work alone is normal
        and only reported as detail."""
        dead = self.loop_dead()
        return not dead, {
            "loop_dead": dead,
            "pending_collectives": self.pending_count(),
            "inflight_entries": self._depth(),
            "autotune_active": self.autotune_active(),
        }

    def auto_group_name(self, op_type: int) -> str:
        """Symmetric base name for an unnamed grouped call (the group key
        must match across ranks; see group_table.h).  Same contract as the
        per-op unnamed counters in enqueue(): unnamed grouped calls must
        happen in the same order on every rank (reference semantics for
        unnamed ops)."""
        with self._entries_lock:
            n = self._auto_group_counters.get(op_type, 0) + 1
            self._auto_group_counters[op_type] = n
            return f"op{op_type}.group.auto.{n}"

    def group_call_seq(self, name: str) -> int:
        """Per-name grouped-call sequence number, appended to the wire
        group key (``name#seq``).  Distinguishes a RETRY of a grouped call
        (fresh key — never poisoned by a previous call's membership error)
        from a late straggler member of the errored call itself (old key —
        fails via the coordinator's errored-group memory).  Member entry
        names are derived from the full ``name#seq`` key as well
        (collective_ops grouped_* submit ``name#seq.i``), so a straggler
        and a retry can never collide in the coordinator's table either.

        INVARIANT: every rank must make the same sequence of grouped
        calls per name (the same SPMD-symmetry contract tensor names
        already rely on); a rank that conditionally skips a grouped call
        desynchronizes the per-name counter and every later same-name
        group errors with a membership mismatch."""
        with self._entries_lock:
            n = self._group_call_seqs.get(name, 0)
            self._group_call_seqs[name] = n + 1
            return n

    def register_process_set(self, set_id: int, member_procs) -> None:
        """Mirror a process set's member *process* ranks into the C++
        controller so negotiation counts readiness against the set
        (reference: ProcessSetTable registration)."""
        m = [int(p) for p in member_procs]
        arr = (ctypes.c_int * max(len(m), 1))(*(m or [0]))
        self._lib.hvdtpu_register_process_set(set_id, arr, len(m))

    def remove_process_set(self, set_id: int) -> None:
        self._lib.hvdtpu_remove_process_set(set_id)

    def timeline_activity(self, tensor: str, activity: str,
                          begin: bool) -> None:
        self._lib.hvdtpu_timeline_activity(
            tensor.encode(), activity.encode(), 1 if begin else 0
        )

    def start_timeline(self, path: str) -> bool:
        """Begin tracing to ``path`` at runtime (reference:
        horovod_start_timeline)."""
        ok = self._lib.hvdtpu_start_timeline(path.encode()) == 0
        if ok:
            self._timeline_active = True
        return ok

    def stop_timeline(self) -> bool:
        self._timeline_active = False
        return self._lib.hvdtpu_stop_timeline() == 0

    # -- submission ---------------------------------------------------------

    def enqueue(
        self,
        array: jax.Array,
        op_type: int,
        reduce_op: int = 0,
        name: Optional[str] = None,
        process_set_id: int = 0,
        group_key: str = "",
        group_size: int = 0,
        root_rank: int = 0,
        prescale: float = 1.0,
        postscale: float = 1.0,
        splits=None,
        extra: Any = None,
    ) -> Future:
        """Submit one tensor; returns a Future resolved by the background
        thread (reference: EnqueueTensorAllreduce in operations.cc)."""
        with self._entries_lock:
            self._name_counter += 1
            counter = self._name_counter
            if name is None:
                # auto names must align ACROSS ranks: count per op type,
                # and only unnamed submissions — a single global counter
                # would desynchronize after ragged named calls (e.g. the
                # post-join barrier; reference: per-op unnamed counters in
                # horovod/torch/mpi_ops.py _allreduce_async naming)
                n = self._auto_counters.get(op_type, 0) + 1
                self._auto_counters[op_type] = n
                name = f"op{op_type}.auto.{n}"
        # chaos: a DROP here submits nothing while still handing back a
        # future — the caller waits on a collective that never happened,
        # the lost-submission fault; raise/delay/kill/hang act in place.
        # The future IS registered in _entries so shutdown() (which every
        # recovery path reaches) fails it — an injected fault must be
        # recoverable, never an unresolvable hang
        if _chaos.active and _chaos.point("controller.enqueue") is _chaos.DROP:
            fut = Future()
            with self._entries_lock:
                self._name_counter += 1
                self._entries[self._name_counter] = _Entry(
                    None, fut, op_type, name=name)
            return fut
        # the ENQUEUE span also lands in any active jax.profiler capture,
        # same activity name as the timeline
        with _trace.span("collective.enqueue",
                         _xname=_xname(name, "ENQUEUE"), name=name):
            arr = jnp.asarray(array)
            dtype_enum = _DTYPE_TO_ENUM.get(str(arr.dtype))
            if dtype_enum is None:
                raise TypeError(
                    f"dtype {arr.dtype} is not supported on the native "
                    "collective path"
                )
            shape = (ctypes.c_longlong * max(arr.ndim, 1))(*(
                list(arr.shape) or [0]
            ))
            fut = Future()
            # Register the future under a caller-assigned id BEFORE the
            # entry becomes visible to the background thread — the 1 ms
            # cycle can execute the entry before control returns from the
            # ctypes call.
            entry_id = counter
            with self._entries_lock:
                self._entries[entry_id] = _Entry(
                    arr, fut, op_type, extra, name=name,
                    t0=time.perf_counter(),
                )
            # reduce_op rides in the root_rank field for allreduce (the C
            # core treats both as opaque fuse keys); keep them separate
            # fields here.
            if splits is not None:
                splits_list = [int(s) for s in np.asarray(splits).ravel()]
                c_splits = (ctypes.c_longlong * len(splits_list))(
                    *splits_list)
                n_splits = len(splits_list)
            else:
                c_splits, n_splits = None, 0
            rc = self._lib.hvdtpu_enqueue(
                ctypes.c_longlong(entry_id), name.encode(), op_type,
                dtype_enum, shape, arr.ndim, process_set_id,
                group_key.encode(), group_size,
                root_rank if op_type == OP_BROADCAST else int(reduce_op),
                prescale, postscale, c_splits, n_splits,
            )
        if rc < 0:
            with self._entries_lock:
                self._entries.pop(entry_id, None)
            if rc == -1:
                raise ValueError(
                    f"a collective named {name!r} is already pending "
                    "(reference: duplicate-name check in TensorQueue)"
                )
            if rc == -3:
                raise HorovodInternalError(
                    "background loop has stopped (stall shutdown or peer "
                    "failure); reinitialize to continue"
                )
            raise HorovodInternalError("native controller not initialized")
        return fut

    @property
    def supports_batch(self) -> bool:
        return hasattr(self._lib, "hvdtpu_enqueue_n")

    def enqueue_batch(
        self,
        arrays: List[jax.Array],
        names: List[str],
        op_type: int,
        reduce_op: int = 0,
        process_set_id: int = 0,
        group_key: str = "",
        group_size: int = 0,
        root_rank: int = 0,
        prescale: float = 1.0,
        postscale: float = 1.0,
    ) -> List[Future]:
        """Submit N named tensors in ONE ctypes call (one GIL release, one
        queue lock): the whole batch is visible to the background loop
        atomically, so a grouped call or a backward-burst of gradients
        rides a single negotiation cycle instead of trickling one entry
        per cycle (measured ~1 ms/entry of added latency from the
        trickle; PERF.md r5).  All-or-nothing on duplicate names.
        Splits-carrying ops (alltoall) take the per-entry path."""
        assert len(arrays) == len(names) and arrays
        arrs = [jnp.asarray(a) for a in arrays]
        ids, dtypes, shape_flat, ndims = [], [], [], []
        with self._entries_lock:
            for _ in arrs:
                self._name_counter += 1
                ids.append(self._name_counter)
        if _chaos.active and _chaos.point("controller.enqueue") is _chaos.DROP:
            # lost batch; registered so shutdown() fails the futures
            # (see enqueue())
            dropped = []
            with self._entries_lock:
                for name in names:
                    self._name_counter += 1
                    fut = Future()
                    self._entries[self._name_counter] = _Entry(
                        None, fut, op_type, name=name)
                    dropped.append(fut)
            return dropped
        futs = []
        label = (names[0] if len(names) == 1
                 else f"{names[0]}+{len(names) - 1}")
        with _trace.span("collective.enqueue",
                         _xname=_xname(label, "ENQUEUE"), name=label):
            for arr in arrs:
                enum = _DTYPE_TO_ENUM.get(str(arr.dtype))
                if enum is None:
                    raise TypeError(
                        f"dtype {arr.dtype} is not supported on the native "
                        "collective path"
                    )
                dtypes.append(enum)
                shape_flat.extend(arr.shape)
                ndims.append(arr.ndim)
            # futures registered BEFORE the batch becomes visible (same
            # ordering contract as enqueue())
            with self._entries_lock:
                t0 = time.perf_counter()
                for i, arr in enumerate(arrs):
                    fut = Future()
                    self._entries[ids[i]] = _Entry(
                        arr, fut, op_type, None, name=names[i], t0=t0
                    )
                    futs.append(fut)
            n = len(arrs)
            c_ids = (ctypes.c_longlong * n)(*ids)
            c_names = (ctypes.c_char_p * n)(*[s.encode() for s in names])
            c_dtypes = (ctypes.c_int * n)(*dtypes)
            c_shapes = (ctypes.c_longlong * max(len(shape_flat), 1))(
                *(shape_flat or [0]))
            c_ndims = (ctypes.c_int * n)(*ndims)
            rors = [root_rank if op_type == OP_BROADCAST else int(reduce_op)
                    ] * n
            c_rors = (ctypes.c_int * n)(*rors)
            rc = self._lib.hvdtpu_enqueue_n(
                n, c_ids, c_names, op_type, c_dtypes, c_shapes, c_ndims,
                process_set_id, group_key.encode(), group_size, c_rors,
                prescale, postscale,
            )
        if rc < 0:
            with self._entries_lock:
                for i in ids:
                    self._entries.pop(i, None)
            if rc == -1:
                raise ValueError(
                    f"a collective named one of {names!r} is already "
                    "pending (reference: duplicate-name check in "
                    "TensorQueue)"
                )
            if rc == -3:
                raise HorovodInternalError(
                    "background loop has stopped (stall shutdown or peer "
                    "failure); reinitialize to continue"
                )
            raise HorovodInternalError("native controller not initialized")
        return futs

    # -- executor callback (runs on the C++ background thread) --------------

    def _on_exec(self, _user, op, dtype, process_set, root_or_rop, prescale,
                 postscale, ids_ptr, n_ids, shape_dims_ptr, shape_ndims_ptr,
                 extents_ptr, extent_lens_ptr, n_extent_ranks, error):
        entries: List[_Entry] = []
        try:
            ids = [int(ids_ptr[i]) for i in range(n_ids)]
            # per-id shapes (for zero-contribution synthesis after join)
            shapes, off = [], 0
            for i in range(n_ids):
                nd = int(shape_ndims_ptr[i])
                shapes.append(
                    tuple(int(shape_dims_ptr[off + j]) for j in range(nd))
                )
                off += nd
            # negotiated per-member extents (allgather dim0s/alltoall splits)
            extents: Optional[List[List[int]]] = None
            if n_extent_ranks > 0:
                extents, off = [], 0
                for r in range(n_extent_ranks):
                    ln = int(extent_lens_ptr[r])
                    extents.append(
                        [int(extents_ptr[off + j]) for j in range(ln)]
                    )
                    off += ln
            with self._entries_lock:
                real = {
                    i: self._entries.pop(i) for i in ids
                    if i != -1 and i in self._entries
                }
            if error:
                err = HorovodInternalError(error.decode())
                for e in real.values():
                    e.future.set_error(err)
                return
            me = self._me_in_set(process_set)
            if me is None:
                # not a member of this response's process set: no local
                # entries and no participation in its data-plane program
                return
            # align entries with the response's name order; ids this rank
            # doesn't hold (post-join) become zero contributions so the
            # SPMD program still sees a symmetric participant
            np_dtype = _ENUM_TO_DTYPE.get(dtype, "float32")
            entries = []
            for i, id_ in enumerate(ids):
                if id_ in real:
                    entries.append(real[id_])
                else:
                    if op in (OP_ALLGATHER, OP_ALLTOALL) and extents:
                        shp = (extents[me][0],) + shapes[i][1:]
                    else:
                        shp = shapes[i]
                    entries.append(
                        _Entry(jnp.zeros(shp, np_dtype), None, op)
                    )
            if not entries:
                return
            # chaos on the resolution path: raise/drop fail this fused
            # response's futures cleanly (via the except below); delay
            # holds resolution; kill/hang act in place
            if _chaos.active:
                _chaos.raise_point("controller.resolve")
            _metrics.FUSED_ENTRIES.observe(len(entries))
            # XLA_COMM span on the exec thread for jax.profiler captures —
            # covers dispatch of the fused program (through data-ready when
            # the timeline is active, which blocks in resolve()); matches
            # the timeline's span of the same name
            label = entries[0].name or f"op{op}"
            if len(entries) > 1:
                label += f"+{len(entries) - 1}"
            with _trace.span("collective.exec",
                             _xname=_xname(label, "XLA_COMM"), name=label):
                self._execute(op, process_set, root_or_rop, prescale,
                              postscale, entries, extents)
        except BaseException as exc:  # never let exceptions cross into C++
            get_logger().error("native exec callback failed: %s", exc)
            try:
                for e in entries:
                    if e.future is not None:
                        e.future.set_error(exc)
                    if self._timeline_active and e.name:
                        # close the XLA_COMM span C++ opened — the
                        # success path ends it in resolve(), which this
                        # entry never reached
                        self.timeline_activity(e.name, "XLA_COMM", False)
            except Exception:
                pass

    def _me_in_set(self, process_set_id: int) -> Optional[int]:
        """This process's position among the set's member processes, or
        None when it is not a member (mirrors engine ctx.me)."""
        if process_set_id == 0:
            return self._topology.process_index
        from ..common import basics as _basics

        try:
            ps = _basics._require_init().process_set_registry.get(
                process_set_id
            )
        except Exception:
            return None
        # ascending process order — must match the sorted registration in
        # add_process_set and the engine ctx's member order
        members = sorted({
            getattr(self._topology.devices[r], "process_index", 0)
            for r in ps.ranks
        })
        me = self._topology.process_index
        return members.index(me) if me in members else None

    def _execute(self, op, process_set, root_or_rop, prescale, postscale,
                 entries: List[_Entry], extents=None) -> None:
        from ..common import basics as _basics
        from ..ops.reduce_ops import ReduceOp

        eng = self._engine
        latency = _metrics.OP_LATENCY.labels(OP_NAMES.get(op, f"op{op}"))

        def resolve(e, value):
            if e.future is None:  # synthesized zero contribution (post-join)
                return
            if e.t0 is not None:
                latency.observe(time.perf_counter() - e.t0)
            if self._timeline_active and e.name:
                # end XLA_COMM when the data is actually ready, not at
                # async dispatch — tracing trades a bg-thread block for
                # span accuracy (reference: the op-completion events the
                # GPU completion-queue thread timestamps)
                jax.block_until_ready(value)
                self.timeline_activity(e.name, "XLA_COMM", False)
            e.future.set_result(value)
            # mark consumed so a later exception in THIS callback can't
            # overwrite the delivered result or double-close the span
            e.future = None
            e.name = None

        # resolve the response's process set so the engine applies its own
        # scoping rules (world = None fast path)
        ps = (
            None if process_set == 0
            else _basics._require_init().process_set_registry.get(process_set)
        )
        if op == OP_JOIN:
            # the join barrier released: result is the last joining rank
            # (reference: JoinOp returns last_joined_rank).  Every rank
            # sees this response at the same protocol point, so it is the
            # one safe moment to resynchronize the auto-name counters that
            # ragged unnamed submissions may have skewed across ranks.
            with self._entries_lock:
                self._auto_counters.clear()
            for e in entries:
                resolve(e, int(root_or_rop))
        elif op == OP_ALLREDUCE:
            # fused execution: one flat buffer, one collective (the native
            # fusion decision made by the controller).  The buffer is
            # padded to the next power of two: fusion buckets form by
            # arrival timing, so raw bucket sizes vary run to run and
            # each new size would compile a fresh executable (measured:
            # 225 ms mean burst-64 latency from recompile churn, PERF.md).
            # Quantized sizes bound the signature count to log2(max) per
            # dtype; zero padding is identity-safe for every reduce op
            # (elementwise ops ignore it, Adasum dots are unchanged by
            # zero elements) and the pad region is sliced away below.
            # Fuse/unfuse happen on the HOST with numpy: fusion buckets
            # form by arrival timing, so their compositions vary cycle to
            # cycle, and any per-composition XLA program (eager concat /
            # per-offset slices / a jitted unfuse) recompiles endlessly —
            # measured 150-1500 ms burst-64 latencies from exactly that
            # (PERF.md).  Host memcpys are composition-insensitive; only
            # the collective itself stays compiled.  Multi-entry buckets
            # pad to the next power of two so the collective's signature
            # count stays bounded (zero padding is identity-safe for all
            # reduce ops including Adasum's dots, and is sliced away
            # below); a single-entry bucket has a stable shape already —
            # padding it would only waste up to 2x transfer/ICI bytes.
            from ..ops.adasum import _next_pow2

            if len(entries) == 1:
                # single-entry bucket: no fusion buffer to build — the
                # numpy pack round-trip (payload→host, pack, host→device,
                # result→host) is pure overhead here, a measured slice of
                # eager single-op latency (PERF.md round-4).  Hand the
                # device array straight to the engine.
                e = entries[0]
                resolve(e, eng.allreduce(
                    jnp.asarray(e.payload), ReduceOp(root_or_rop),
                    prescale, postscale, ps,
                ))
                return
            # device-resident multi-arg program first: stable training
            # compositions hit the executable cache and skip the host
            # pack entirely (engine.allreduce_multi; None = fall back)
            outs = eng.allreduce_multi(
                [jnp.asarray(e.payload) for e in entries],
                ReduceOp(root_or_rop), prescale, postscale, ps,
            )
            if outs is not None:
                for e, o in zip(entries, outs):
                    resolve(e, o)
                return
            raw = [np.asarray(e.payload) for e in entries]
            sizes = [int(a.size) for a in raw]
            # shapes from the originals: ascontiguousarray promotes 0-d
            # scalars to 1-d, which would corrupt the unpack reshape
            shapes = [a.shape for a in raw]
            arrays = [np.ascontiguousarray(a) for a in raw]
            total = sum(sizes)
            padded = _next_pow2(total) if len(arrays) > 1 else total
            if padded:
                _metrics.FUSION_UTILIZATION.observe(total / padded)
            # pack in C (hvdtpu_pack memcpys + zeroes the pad tail):
            # ctypes releases the GIL for the call, so the training
            # thread keeps running while this background thread packs
            buf = np.empty((padded,), arrays[0].dtype)
            n_arr = len(arrays)
            srcs = (ctypes.c_void_p * n_arr)(
                *[a.ctypes.data for a in arrays]
            )
            nbytes = (ctypes.c_longlong * n_arr)(
                *[a.nbytes for a in arrays]
            )
            self._lib.hvdtpu_pack(
                srcs, nbytes, n_arr,
                ctypes.c_void_p(buf.ctypes.data),
                ctypes.c_longlong(buf.nbytes),
            )
            out = eng.allreduce(
                jnp.asarray(buf), ReduceOp(root_or_rop), prescale,
                postscale, ps,
            )
            out_np = np.asarray(out)  # one transfer; also a real sync
            offset = 0
            for e, sz, shp in zip(entries, sizes, shapes):
                resolve(e, out_np[offset:offset + sz].reshape(shp))
                offset += sz
        elif op == OP_ALLGATHER:
            # negotiated recvcounts: per-member dim0 from the response
            # (reference: MPIAllgather's recvcounts path)
            dim0s = [ext[0] for ext in extents] if extents else None
            for e in entries:
                resolve(e, eng.allgather(e.payload, ps, recv_dim0s=dim0s))
        elif op == OP_BROADCAST:
            for e in entries:
                resolve(e, eng.broadcast(e.payload, root_or_rop, ps))
        elif op == OP_ALLTOALL:
            # negotiated splits matrix: extents[m] = [dim0, splits...];
            # a member with no explicit splits sends even dim0/n chunks
            all_splits = None
            if extents:
                n = len(extents)
                all_splits = []
                for ext in extents:
                    dim0, sp = ext[0], ext[1:]
                    if not sp:
                        sp = [dim0 // n] * n
                    all_splits.append(sp)
            for e in entries:
                resolve(
                    e,
                    eng.alltoall(e.payload, e.extra, ps,
                                 all_splits=all_splits),
                )
        elif op == OP_REDUCESCATTER:
            for e in entries:
                resolve(
                    e, eng.reducescatter(e.payload, ReduceOp(root_or_rop), ps)
                )
        elif op == OP_BARRIER:
            for e in entries:
                eng.barrier(ps)
                resolve(e, None)
        else:
            err = HorovodInternalError(f"unknown native op {op}")
            for e in entries:
                if e.future is not None:
                    e.future.set_error(err)
