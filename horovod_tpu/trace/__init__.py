"""Distributed tracing + black-box flight recorder (docs/TRACING.md).

The span-level companion to the PR-1 aggregate metrics: a host-side,
always-on recorder that answers "what happened, in order, to THIS
request / THIS step / THIS rank" — the question counters and histograms
structurally cannot (Sigelman et al., *Dapper*; the MegaScale flight
recorder).  Three properties are load-bearing:

* **zero device code** — every span is host-side bookkeeping around
  dispatch points, so a traced program is BIT-IDENTICAL to the untraced
  one: same StableHLO, zero added collectives, zero extra compiles
  (tools/trace_bench.py pins all three);
* **bounded memory, lock-cheap** — each thread records into its own
  fixed-size ring (``HVD_TPU_TRACE_RING`` records; old records are
  overwritten, never grown), so the recorder can stay on for the life
  of a production job.  The hot path is two ``perf_counter`` reads and
  one list store under the GIL — no lock, no allocation beyond the
  record tuple;
* **~ns when disabled** — ``HVD_TPU_TRACE=0`` turns :func:`span` /
  :func:`event` into a single module-bool check returning a shared
  null context (the chaos ``point()`` discipline).

Start-up is on the ring too: ``hvd.import``, ``hvd.init`` and its two
children, ``train.create_state`` and its two, and one ``jax.compile``
record a backend compile or persistent-cache load, written by the
process's ONE ``jax.monitoring`` recorder (:func:`compile_totals`;
nothing is registered under ``HVD_TPU_TRACE=0``).  A child lies inside
its parent's extents on the same thread: that is the whole nesting, no
span stack and no ids (:func:`~horovod_tpu.trace.export.enclosing`).

Sites are catalogued in :data:`SITES` (the analysis ``trace`` pass
holds code ≡ catalogue ≡ docs/TRACING.md in both directions).  Spans
bridge into any active ``jax.profiler`` XPlane capture through the same
instrumentation point (``TraceAnnotation``), so the Chrome-trace export
and the profiler see ONE set of span names.

Inside the compiled step no host span can record, so the program names
its device work instead: :data:`DEVICE_SCOPES` (``jax.named_scope``
phases of every step builder in ``training.py``), :data:`DEVICE_SUBSCOPES`
(parts of the forward: the routed layer's ``router`` and ``experts``) and
:data:`DEVICE_KERNELS` (one ``name=`` a Pallas kernel).  Both are
metadata: the operations are the same with tracing on or off.
:mod:`.device` reduces a profiler capture by them.

Export: :mod:`.export` renders per-rank Chrome trace-event JSON
(perfetto-loadable; ``GET /trace`` on the PR-1 exposition endpoint,
loopback-only) and merges per-rank dumps with step-boundary clock
alignment.  :mod:`.flight` dumps the last N seconds of spans + metric
deltas as a crash bundle on kill / quarantine / rollback / preemption /
SLO breach.
"""

from __future__ import annotations

import contextlib
import os
import sys
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

__all__ = [
    "CompileTotals", "DEVICE_KERNELS", "DEVICE_SCOPES", "DEVICE_SUBSCOPES", "SITES",
    "add_span", "compile_delta", "compile_span", "compile_totals", "configure",
    "enabled", "event", "install_from_env", "new_trace_id", "now",
    "snapshot", "span", "wrapped",
]

#: Span/event site catalogue — every ``trace.span(<site>)`` /
#: ``trace.event(<site>)`` / ``trace.add_span(<site>)`` literal in the
#: package must name an entry here, every entry must have a live call
#: site, and docs/TRACING.md's table mirrors this tuple exactly (the
#: analysis ``trace`` pass checks all directions).
SITES = (
    "hvd.import",          # the package's import, first line to last
    "hvd.init",            # basics.init, the whole of it
    "hvd.init.topology",   # jax.distributed + topology.discover: PJRT's client
    "hvd.init.controller", # load_controller: the native core built or loaded
    "jax.compile",         # one backend compile or persistent-cache load (the
                           # process's one jax.monitoring recorder, below)
    "train.create_state",  # create_train_state: model.init + optimizer.init
    "train.model_init",    # model.init alone (op by op: start-up's compiles)
    "train.optimizer_init",  # optimizer.init and the step counter
    "train.replicate",     # replicate_state: the state placed over the mesh
    "train.step",          # fit_epoch loop body: dispatch + host work
    "data.wait",           # consumer wait on the prefetch queue
    "data.produce",        # host batch production (producer thread)
    "data.device_put",     # host->device staging copy
    "checkpoint.publish",  # crash-atomic checkpoint write (_atomic_publish)
    "collective.enqueue",  # negotiated-collective submission (controller)
    "collective.exec",     # fused collective dispatch->data-ready
    "overlap.bucket",      # torch bridge: one bucket's drained submission
    "serve.queued",        # request arrival -> admission (per request)
    "serve.prefill_chunk", # one prefill chunk computed (per request)
    "serve.step",          # one mixed/decode engine step (batch-wide)
    "serve.first_decode",  # the decode step that emitted a first token
    "serve.first_token",   # first-token emission (instant; TTFT arg)
    "serve.finish",        # request completion (instant)
    "serve.spec_verify",   # one request's speculative verify row scored
    "serve.spec_rollback", # rejected-draft KV tail trimmed (instant)
    "fleet.route",         # router placement decision (instant)
    "serve.migrate",       # one request's KV/stream handoff to a survivor
    "serve.hedge",         # hedged second dispatch issued (instant)
    "serve.handoff",       # prefill->decode tier handoff (disagg fleet)
    "fleet.scale",         # autoscaler applied a scale decision (instant)
    "fleet.preempt",       # preemption notice handled (instant)
    "guard.exchange",      # cross-rank digest/vote exchange (cadence)
    "chaos.inject",        # a chaos rule fired (instant, first-class)
    "elastic.restart",     # exec-restart about to replace the image
    "flash.tiles",         # a flash kernel traced: tile visits, iterations, widths,
                           # whether the call took document ids (its counts then
                           # bound the visits: at_most)
    "moe.rows",            # RoutedExperts traced: rows, slots, chunk, gathers, scoring,
                           # the grouped products' tiles and row-tile visits
    "gdn.chunks",          # the gated delta rule traced: rows, value and key heads,
                           # chunk, chunks a sequence, both head widths, the kernel's
                           # programs, the bytes XLA hands the forward through HBM
    "gdn.conv_norm",       # Gated DeltaNet's input pass traced (convolution, SiLU, L2
                           # norms): rows, channels, taps, heads, row tile, programs, bytes
    "gdn.gated_norm",      # its output pass traced (norm(o) * silu(z)): rows, channels,
                           # value heads, row tile, programs, bytes
    "ssd.chunks",          # Mamba-2's scan traced: rows, heads, groups, chunk, chunks a
                           # sequence, head width and state size, the kernel's
                           # programs, the state bytes a program carries
    "attn.layers",         # a model whose attention differs by layer traced: each
                           # layer's kind, heads, key/value heads, window, rotary
                           # columns and RoPE type, and whether it takes the
                           # call's document ids
    "rope.rotate",         # a layer's rotary step of q and k traced: rows, heads,
                           # key/value heads, head width, rotated columns, RoPE type,
                           # whether the kernel pair engaged, its row tile, programs
                           # and the bytes a pass moves
)

#: Device phase scopes — every ``jax.named_scope("...")`` literal in the
#: package names an entry here (the step builders of training.py, and
#: ZeroSpmdOptimizer's exchange), every entry has a call site, and
#: docs/TRACING.md's "Device names" table mirrors both tuples.  They
#: show in an operation's ``op_name`` (``jit(_step)/.../jvp(forward)/...``);
#: the backward has no scope of its own: it is ``transpose(jvp(forward))``.
DEVICE_SCOPES = (
    "forward",    # the loss computation value_and_grad differentiates
    "exchange",   # gradient / loss / batch-stats collectives
    "optimizer",  # optimizer.update + apply_updates
)

#: Parts of a phase — ``jax.named_scope`` literals INSIDE the forward scope
#: (so also inside its transpose, the backward), held by the same pass and
#: the same docs table.  They name no phase: ``trace/device.py`` reports
#: their time beside the phases' (``subscopes``), and the benchmark's
#: ``router_ms`` / ``expert_ffn_ms`` / ``mla_proj_ms`` / ``shared_expert_ms``
#: / ``gdn_proj_ms`` / ``gated_delta_ms`` / ``window_attention_ms`` /
#: ``full_attention_ms`` / ``attn_rope_ms`` / ``attn_gate_ms`` /
#: ``attn_docmask_ms`` / ``ssd_scan_ms`` / ``mamba_proj_ms`` /
#: ``latent_proj_ms`` read them by ``op_name`` pattern.
DEVICE_SUBSCOPES = (
    "router",   # parallel/moe.py RoutedExperts: router product, softmax,
                # top-k, counts, the sort by held expert and its inverse
    "experts",  # RoutedExperts: the rows gathered into expert order, the
                # grouped products (the grouped_matmul kernels since PR 33),
                # the rows gathered back by rank and summed with their
                # weights (no scatter since PR 31)
    "mla",      # models/transformer.py Attention, latent attention: the
                # stream down to the latent and the rotary key, the latent's
                # norm, the latent up to keys and values, RoPE on the rotary
                # parts, q and k assembled for the kernels (not the kernels)
    "shared_experts",  # models/transformer.py Block: the SwiGLU beside the
                       # routed sum, whole on every chip (with its gate, where
                       # ``shared_expert_gate``)
    "gdn",      # models/transformer.py GatedDeltaNet, all but the rule: the
                # two input projections, the input pass (the causal depthwise
                # convolution, SiLU, the L2 norms: since PR 38 the kernel pair
                # gdn_conv_norm_*), the gates beta and g, the output pass
                # (the gated RMSNorm: gdn_gated_norm_*) and the output
                # projection
    "gated_delta",  # GatedDeltaNet, the rule itself (ops/gated_delta.py): its
                    # three kernels (since PR 36 they make the chunk-local
                    # tensors themselves, since PR 38 they read q and k at the
                    # key heads), XLA's unit-triangular inverse and the gates'
                    # rows a chunk; a SIBLING of ``gdn``, not nested in it, so
                    # that one pattern reads each
    # models/transformer.py Mamba2 (a layer whose ``TransformerConfig.sublayers``
    # entry is 'mamba'): ``mamba`` around the whole mixer, the five inside it
    "mamba",       # the mixer, in_proj to out_proj
    "in_proj",     # [z | xBC] in the layer's dtype and dt in float32, two
                   # products of one kernel's columns
    "conv",        # the causal depthwise convolution with its bias and SiLU
                   # (the kernel pair conv_bias_silu_* in a 'flash' model)
    "ssd",         # the scan (ops/ssd.py): dt's softplus, A, the gates' rows a
                   # chunk and the kernels ssd_scan_fwd / ssd_scan_bwd
    "gated_norm",  # rmsnorm over a group of (y * silu(z)) (gated_group_norm_*)
    "out_proj",    # the mixer's output projection
    # parallel/moe.py RoutedExperts with ``latent``: outside ``experts``
    "latent_down",  # the stream down to the latent the experts work in
    "latent_up",    # the weighted sum back up to the stream
    # models/transformer.py Attention, only in a model with a
    # 'sliding_attention' layer (``TransformerConfig.layer_types``): other
    # models' ``op_name``s are what they were
    "attn_rope",    # the rotary step of q and k: the layer type's frequencies
                    # (YaRN's blend where it has one), the cos and sin tables,
                    # the (partial) rotation: the rope_* kernels since PR 40
    "attn_window",  # the attention CORE of a sliding layer: the flash kernels
                    # under ``window=sliding_window`` (or the dot path), their
                    # folds and pads; not the projections
    "attn_full",    # the same of a full layer: the kernels that share their
                    # names with a sliding layer's are told apart by this
    "attn_gate",    # the gate a head: the stream times (d_model, heads),
                    # sigmoid, times the attention's output
    # models/transformer.py Transformer and ops/flash_attention.py, only in a
    # call that took document ids with its tokens (a packed row)
    "attn_docmask",  # what the ids become outside the flash kernels: the
                     # positions that restart at each document and the
                     # documents counted, once a model call; the ids laid out
                     # for the kernels' tiles (a position's id on 128 lanes,
                     # the ids along the lanes), a kernel call.  The mask
                     # itself is inside the kernels and in their time
)

#: Pallas kernel names — every ``pl.pallas_call(..., name="...")`` of
#: ops/flash_attention.py, ops/grouped_matmul.py, ops/gated_delta.py,
#: ops/gdn_kernels.py, ops/ssd.py and ops/rope_kernel.py, one name a kernel; the
#: HLO instruction (and the profiler's event) is ``%<name>.<n>``.  The
#: attention kernels all start with ``flash_attention`` so one pattern still
#: reads them together; the routed experts' grouped products do not, and are
#: read by the ``experts`` scope they run in, as the gated delta rule's three
#: (``gated_delta*``) are by the ``gated_delta`` scope and Gated DeltaNet's
#: two passes (``gdn_*``) by the ``gdn`` scope and, in a model with a sliding
#: layer, the rotary step's two (``rope_*``) by the ``attn_rope`` scope.
DEVICE_KERNELS = (
    "flash_attention_fwd",      # _forward_impl
    "flash_attention_bwd_dq",   # _backward_folded: dQ
    "flash_attention_bwd_dkv",  # _backward_folded: dK/dV per kv head
    "flash_attention_bwd_dkv_bd",  # _backward_folded: dK/dV under the
                                   # block-diffusion mask, a query head a program
    "flash_attention_chunk",    # flash_chunk_attention (prefill, decode)
    "grouped_matmul",    # ops/grouped_matmul.py: rows x their group's matrix,
                         # forward and (the matrix read transposed) dx
    "grouped_matmul_t",  # the matrices' gradient: x[g]^T @ dy[g] a group
    "gated_delta_kkt",   # ops/gated_delta.py: L = strictly lower(beta (k k^T)
                         # decay) a chunk and head from k, g, beta, float32:
                         # what XLA's unit-triangular inverse takes
    "gated_delta_fwd",   # the chunk-local tensors made in VMEM from q, k, v,
                         # g, beta, T, and the rule's carry over the chunks,
                         # the state in VMEM; a program a (sequence, four
                         # value heads)
    "gated_delta_bwd",   # the same walk last to first, the state's cotangent
                         # in VMEM: the chunk-local tensors made again, their
                         # backward written out, dq, dk (summed a key head), dv,
                         # dg, dbeta
    "gdn_conv_norm_fwd",   # ops/gdn_kernels.py: q, k (key heads), v as token-major
                           # rows from the projection's rows: the four-tap causal
                           # convolution, SiLU, the L2 norm a head, q's scale
    "gdn_conv_norm_bwd",   # the same made again in VMEM, dqkv and the taps'
                           # gradient (summed over the row tiles in float32)
    "gdn_gated_norm_fwd",  # norm(o) * silu(z) on the rule's rows, z read out of
                           # the projection's rows in place
    "gdn_gated_norm_bwd",  # do, dz and the norm's scale's gradient
    "conv_bias_silu_fwd",    # ops/gdn_kernels.py, Mamba-2's input pass: silu(conv +
                             # bias) over all the channels, a lane tile at a time
    "conv_bias_silu_bwd",    # du, the taps' and the bias's gradient
    "gated_group_norm_fwd",  # rmsnorm over a group's columns of (y * silu(z)),
                             # a scale a column
    "gated_group_norm_bwd",  # dy, dz and the scale's gradient
    "ssd_scan_fwd",  # ops/ssd.py: Mamba-2's scan in chunks, a program a (sequence,
                     # group): C B^T once a group, the state of the group's
                     # heads in VMEM from chunk to chunk; y and a state a chunk
    "ssd_scan_bwd",  # the same walk last to first, the state's cotangent in
                     # VMEM: dx, dB, dC (summed over the group's heads), dg, ddt, dD
    "rope_fwd",  # ops/rope_kernel.py: q or k rotated on whole heads, head-major in
                 # and out, one pass (models whose heads are 128 lanes wide)
    "rope_bwd",  # the cotangent's pass: the same rotation with sin negated
)

ENV_TRACE = "HVD_TPU_TRACE"
ENV_RING = "HVD_TPU_TRACE_RING"

# wall-clock anchor: records carry perf_counter() times (monotonic);
# the export maps them to epoch microseconds via this pair so per-rank
# dumps land on one comparable axis before step alignment refines it
_WALL0 = time.time()
_PERF0 = time.perf_counter()

now = time.perf_counter


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return int(raw)
    except ValueError:  # contract-ok: env -- validated with warn-and-default here; common.retry.env_int imports metrics and trace must stay import-light
        return default


#: module fast-path flag (the chaos ``active`` discipline): False means
#: span()/event() are a bool check returning a shared null context
_enabled = os.environ.get(ENV_TRACE, "1") != "0"
_ring_cap = max(256, _env_int(ENV_RING, 16384))

#: rank stamped on exports/bundles (set by install_from_env at init)
_rank = 0
_host = ""

# jax.profiler.TraceAnnotation, resolved lazily and only when jax is
# ALREADY loaded (the elastic driver records spans without ever paying
# a jax import); None = no XPlane bridge
_ann_cls: Optional[type] = None
_ann_tried = False


def _annotation_cls():
    global _ann_cls, _ann_tried
    if not _ann_tried and "jax" in sys.modules:
        _ann_tried = True
        try:
            from jax.profiler import TraceAnnotation

            _ann_cls = TraceAnnotation
        except Exception:
            _ann_cls = None
    return _ann_cls


class _Ring:
    """One thread's fixed-size record ring.  Single writer (the owning
    thread); readers snapshot under the registry lock — a torn read of
    the newest slot is acceptable by design (the exporter sorts and
    drops malformed slots)."""

    __slots__ = ("buf", "idx", "cap", "tid", "owner")

    def __init__(self, cap: int, tid: str):
        # grown lazily to cap (a thread that records a handful of spans
        # must not pay the full ring's preallocation)
        self.buf: List[tuple] = []
        self.idx = 0
        self.cap = cap
        self.tid = tid
        self.owner: Optional[Any] = None  # weakref to the owning thread

    def append(self, rec: tuple) -> None:
        if len(self.buf) < self.cap:
            self.buf.append(rec)
        else:
            self.buf[self.idx % self.cap] = rec
        self.idx += 1

    def records(self) -> List[tuple]:
        if self.idx <= self.cap:
            return list(self.buf)
        start = self.idx % self.cap
        return self.buf[start:] + self.buf[:start]


_rings_lock = threading.Lock()
_rings: List[_Ring] = []
_local = threading.local()


def _ring() -> _Ring:
    r = getattr(_local, "ring", None)
    if r is None:
        import weakref

        t = threading.current_thread()
        r = _Ring(_ring_cap, f"{t.name}-{t.ident}")
        r.owner = weakref.ref(t)
        _local.ring = r
        with _rings_lock:
            _rings.append(r)
            # a thread-churny host (one ring per short-lived thread)
            # must not grow without bound — but ONLY dead threads'
            # rings may retire: evicting by age alone was measured to
            # drop the long-lived MAIN thread's ring after 64 worker
            # threads churned past it, silently losing every later
            # training span.  Live-thread count bounds the rest.
            if len(_rings) > 64:
                # _rings[:-64] is disjoint from the protected newest-64
                # tail by construction, so liveness is the only test
                for old in _rings[:-64]:
                    owner = old.owner() if old.owner is not None else None
                    if owner is None or not owner.is_alive():
                        _rings.remove(old)
    return r


# records: (site, t0, dur, args) — dur None = instant event.  args is a
# small dict or None; values must be JSON-serializable (export contract).


class _Span:
    __slots__ = ("site", "xname", "xargs", "args", "t0", "ann")

    def __init__(self, site: str, xname: Optional[str], xargs, args):
        self.site = site
        self.xname = xname
        self.xargs = xargs
        self.args = args
        self.ann = None

    def set(self, **args) -> None:
        """Add args known only once the spanned work is done."""
        self.args = {**(self.args or {}), **args}

    def __enter__(self):
        if self.xname is not None:
            cls = _annotation_cls()
            if cls is not None:
                self.ann = cls(self.xname, **(self.xargs or {}))
                self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if _enabled:
            _ring().append((self.site, self.t0, t1 - self.t0, self.args))
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False


_NULL = contextlib.nullcontext()


def span(site: str, /, _xname: Optional[str] = None,
         _xargs: Optional[Dict[str, Any]] = None, **args):
    """Context manager recording one host-side span at ``site``.

    ``args`` ride into the Chrome export's ``args`` field (keep them
    small and JSON-serializable; ``rid``/``step``/``trace`` are the
    anchoring conventions).  ``_xname`` overrides the name the span
    carries into an active jax.profiler capture (default
    ``hvd_tpu::<site>``); ``_xname=False`` suppresses the bridge for
    this span.  ``_xargs`` are the annotation's own keyword arguments
    (``{"_r": 1, "step_num": n}`` makes it a step annotation); the ring
    record does not carry them.  One module-bool check when tracing is
    off."""
    if not _enabled:
        # HVD_TPU_TRACE=0 drops the ring record, but a caller that
        # asked for a specific XPlane name (the profiler bridge) still
        # gets its annotation — the two switches stay independent
        if _xname:
            cls = _annotation_cls()
            if cls is not None:
                return cls(_xname, **(_xargs or {}))
        return _NULL
    xname = (None if _xname is False
             else (_xname or f"hvd_tpu::{site}"))
    return _Span(site, xname, _xargs, args or None)


def event(site: str, /, **args) -> None:
    """Record one instant event at ``site`` (no duration, no XPlane
    bridge — annotations need extents)."""
    if not _enabled:
        return
    _ring().append((site, time.perf_counter(), None, args or None))


def add_span(site: str, t0: float, t1: float, /, **args) -> None:
    """Record a span with explicit extents (``now()``-clock seconds) —
    for retroactive spans whose boundaries were observed elsewhere
    (e.g. a request's queued time, known only at admission)."""
    if not _enabled:
        return
    _ring().append((site, t0, max(0.0, t1 - t0), args or None))


def snapshot(since: float = 0.0) -> List[tuple]:
    """Every live record with ``t0 >= since`` across all thread rings,
    time-ordered: ``(site, t0, dur_or_None, args_or_None, tid)``."""
    with _rings_lock:
        rings = list(_rings)
    out = []
    for r in rings:
        for rec in r.records():
            if rec[1] >= since:
                out.append(rec + (r.tid,))
    out.sort(key=lambda r: r[1])
    return out


def wrapped() -> bool:
    """Whether any live ring has overwritten a record: a reader that sums
    a site over the whole process (the benchmark's ``program_span``) has
    the whole of it only while this is False."""
    with _rings_lock:
        return any(r.idx > r.cap for r in _rings)


# -- the process's one compile recorder ---------------------------------------
#
# jax reports every backend compile through jax.monitoring, on the thread
# that asked for it.  ONE duration listener turns each into a
# ``jax.compile`` ring record with explicit extents and keeps the process
# totals.  Which span caused a compile is read off the ring afterwards, by
# time and thread (``export.enclosing``): span()'s hot path knows nothing
# of it.

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class CompileTotals(NamedTuple):
    """The process's compile totals since the recorder was installed."""

    compiles: int = 0        # backend_compile_duration events: compiles AND cache loads
    compile_s: float = 0.0   # their seconds (a load reports its load time)
    cache_hits: int = 0      # of them, loads from the persistent cache


_totals = CompileTotals()
_totals_lock = threading.Lock()
_recorder_installed = False


def _on_duration(name: str, seconds: float, **kw) -> None:
    global _totals
    if not _enabled:
        return
    if name == _COMPILE_EVENT:
        # a persistent-cache hit reports its retrieval time on this thread
        # just before the compile event that wraps it (jax/_src/compiler.py
        # compile_or_get_cached): that is how a load is told from a compile
        cached = getattr(_local, "cache_load", False)
        _local.cache_load = False
        with _totals_lock:
            _totals = CompileTotals(_totals.compiles + 1,
                                    _totals.compile_s + seconds,
                                    _totals.cache_hits + cached)
        end = time.perf_counter()
        add_span("jax.compile", end - seconds, end,
                 fun=str(kw.get("fun_name", "")), cached=cached)
    elif name == _CACHE_LOAD_EVENT:
        _local.cache_load = True


def _install_compile_recorder() -> None:
    """Register the listener, once a process, when tracing is enabled and
    jax is ALREADY loaded (never imported from here)."""
    global _recorder_installed
    if _recorder_installed or not _enabled or "jax" not in sys.modules:
        return
    with _totals_lock:
        if not _recorder_installed:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(_on_duration)
            _recorder_installed = True


def compile_totals() -> CompileTotals:
    """The process's compile totals (zeros under ``HVD_TPU_TRACE=0``, and
    until jax is loaded).  A span that wants the compiles it paid takes
    the totals before and stamps :func:`compile_delta` on exit
    (:func:`compile_span` does both)."""
    _install_compile_recorder()
    return _totals


def compile_delta(before: CompileTotals) -> Dict[str, Any]:
    """``compiles``, ``compile_s`` and ``cache_hits`` since ``before``: the
    args a start-up span carries."""
    after = compile_totals()
    return {"compiles": after.compiles - before.compiles,
            "compile_s": after.compile_s - before.compile_s,
            "cache_hits": after.cache_hits - before.cache_hits}


@contextlib.contextmanager
def compile_span(site: str, /, **args):
    """:func:`span` whose record also carries the compile totals'
    difference over its extents (``compiles``, ``compile_s``,
    ``cache_hits``).  For start-up's spans, entered once a process: a
    generator's cost is nothing there.  Yields the span (``.set(...)``
    adds args), or None when tracing is off."""
    if not _enabled:
        yield None
        return
    before = compile_totals()
    with span(site, **args) as sp:
        try:
            yield sp
        finally:
            sp.set(**compile_delta(before))


def epoch_us(t: float) -> float:
    """Map a ``now()``-clock time to epoch microseconds (export axis)."""
    return (_WALL0 + (t - _PERF0)) * 1e6


_id_lock = threading.Lock()
_id_counter = 0


def new_trace_id() -> str:
    """A process-unique trace-context id (router -> replica -> engine ->
    scheduler propagation; docs/TRACING.md)."""
    global _id_counter
    with _id_lock:
        _id_counter += 1
        n = _id_counter
    return f"t{_rank}-{os.getpid():x}-{n:x}"


def enabled() -> bool:
    return _enabled


def configure(enabled: Optional[bool] = None,
              ring: Optional[int] = None) -> None:
    """Programmatic switch (benches/tests).  ``ring`` applies to rings
    created AFTER the call (existing threads keep their buffers)."""
    global _enabled, _ring_cap
    if enabled is not None:
        _enabled = bool(enabled)
    if ring is not None:
        _ring_cap = max(256, int(ring))


def install_from_env(rank: int = 0, host: Optional[str] = None) -> bool:
    """Init-time hook (``hvd.init()``): resolve the env switches, stamp
    the rank/host the export and flight bundles carry, switch the compile
    recorder on (once a process, however often this runs; never under
    ``HVD_TPU_TRACE=0``), mount the ``/trace`` control endpoint, and
    baseline the flight recorder's metric snapshot.  Returns whether
    recording is enabled."""
    global _enabled, _ring_cap, _rank, _host
    _enabled = os.environ.get(ENV_TRACE, "1") != "0"
    _ring_cap = max(256, _env_int(ENV_RING, 16384))
    _rank = int(rank)
    if host is None:
        import socket

        host = socket.gethostname()
    _host = host
    _install_compile_recorder()
    from . import export as _export
    from . import flight as _flight

    _export.register_trace_endpoint()
    _flight.note_metrics_baseline()
    return _enabled


def rank() -> int:
    return _rank


def host() -> str:
    return _host
