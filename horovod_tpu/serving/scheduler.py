"""Iteration-level (continuous-batching) request scheduler.

Orca (OSDI '22) made the case: autoregressive serving must schedule at
*iteration* granularity, not request granularity.  A static batch holds
every slot hostage to its slowest member — finished sequences keep
padding the batch, waiting requests queue behind the whole batch's
maximum length.  Continuous batching re-decides the batch every step:
finished sequences leave immediately, waiting requests join as soon as
a slot and KV blocks are free, so the decode batch stays full and
throughput tracks the token budget instead of the worst tail.

The policy here (documented in docs/SERVING.md):

* **Prefill-prioritized**: when admissible requests are waiting, the
  next step is a prefill — time-to-first-token is the latency SLO,
  and a full batch is the throughput SLO; both want admission early.
  With chunked prefill the engine packs prefill chunks INTO the decode
  step (one mixed program), so prioritizing prefill no longer stalls
  running decodes.
* **Prefix cache on admit**: the longest cached block-aligned prefix
  of each prompt is mapped straight into the new sequence's block
  table with refcount bumps (:meth:`BlockAllocator.match_prefix`) —
  zero prefill compute and zero pool writes for the shared span; only
  the uncached tail is booked against the token budget and prefilled.
  The match is capped one block short of the prompt so the prefill
  step always has a token to compute (it must emit the first token),
  and the partially-filled last block is always private — CoW by
  construction.  LIFO recompute eviction re-admits through this same
  match, so a recomputed sequence reuses whatever of its blocks
  survived in the cache instead of re-prefilling from token 0.
* **Admission gates**: the *uncached* prompt-token sum of one prefill
  batch is capped by ``token_budget`` (bounds outstanding prefill work
  so decode latency can't spike arbitrarily), the decode batch by the
  largest padding tier, and block allocation must leave ``watermark``
  free blocks (headroom so running sequences can keep growing without
  immediate eviction thrash).
* **LIFO eviction (recompute-style)**: when a growing sequence needs a
  block and the pool is empty, the most recently admitted sequence is
  preempted — its blocks are freed and it re-queues *with the tokens it
  already generated* (vLLM's recompute preemption), so its re-prefill
  reproduces the exact cache state and generation continues token-for-
  token identically (greedy decode is deterministic; the oracle test
  pins this across evict boundaries).

Everything here is host-side bookkeeping over the
:class:`~horovod_tpu.serving.kv_cache.BlockAllocator`; the device work
happens in :mod:`horovod_tpu.serving.engine`.

Tensor sharding never reaches this module BY DESIGN (docs/SERVING.md
sharding section): every decision here — admission, prefix matching,
CoW publication, eviction — is a pure function of token ids and pool
geometry (block count/size), and kv-head sharding changes neither, so
one unsharded scheduler loop drives any shard factor and the block
tables it emits replicate bit-for-bit across chips.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Deque, List, Optional, Tuple

import numpy as np

from .. import trace
from ..metrics import instruments as _instr
from .kv_cache import PREFIX_HASH_ROOT, BlockAllocator, blocks_for


@dataclasses.dataclass
class Request:
    """One generation request as submitted by the client."""

    id: int
    prompt: np.ndarray  # int32 token ids, 1-D
    max_new_tokens: int
    eos_id: Optional[int] = None
    arrival: float = 0.0  # open-loop load injection timestamp (bench)
    #: latency budget in seconds from ``arrival`` (None/0 = none):
    #: once spent, the request is shed pre-admission or cancelled
    #: in flight — tokens the client stopped waiting for are never
    #: computed (``HVD_TPU_SERVE_DEADLINE`` sets the engine default)
    deadline_s: Optional[float] = None
    #: propagated trace context (fleet router -> replica -> engine ->
    #: scheduler): rides every span this request touches so one id
    #: follows it across components (docs/TRACING.md)
    trace_id: Optional[str] = None
    #: per-request speculative lookahead override: None = the engine's
    #: configured ``spec_k``, 0 = speculation off for this request, k>0
    #: = draft up to k tokens per decode step (docs/SERVING.md)
    spec_k: Optional[int] = None


@dataclasses.dataclass
class Sequence:
    """A request's live serving state.

    ``context`` is what the next prefill must write: the prompt, plus —
    after an eviction — the tokens already generated (recompute
    preemption re-prefills prompt+generated and resumes decoding).
    """

    req: Request
    context: np.ndarray
    generated: List[int] = dataclasses.field(default_factory=list)
    blocks: List[int] = dataclasses.field(default_factory=list)
    staged: object = None  # device-resident padded prompt row (staging queue)
    first_token_at: Optional[float] = None
    last_token_at: Optional[float] = None
    #: context tokens whose K/V are already in the cache (prefix-cache
    #: hits at admit + chunks computed since); == len(context) once
    #: prefill is complete and the sequence is decoding
    prefilled: int = 0
    #: of ``prefilled``, how many came from prefix-cache hits at admit
    cached_len: int = 0
    #: chain hashes of this stream's full blocks (hashes depend only on
    #: token ids, so the list survives eviction/readmission unchanged)
    block_hashes: List[int] = dataclasses.field(default_factory=list)
    #: how many of ``blocks`` are published in the prefix index
    published: int = 0
    #: pending speculative draft for the NEXT decode step (proposed by
    #: the engine's drafter; empty = plain one-token decode).  Never
    #: part of ``generated`` — draft tokens only join the stream after
    #: greedy verification accepts them.
    draft: List[int] = dataclasses.field(default_factory=list)
    #: lifetime speculative counters (per-request accept-rate
    #: histogram at finish; bench columns)
    spec_drafted: int = 0
    spec_accepted: int = 0

    @property
    def length(self) -> int:
        """Tokens currently in the KV cache once prefill has run."""
        return len(self.context) + len(self.generated)

    @property
    def in_decode(self) -> bool:
        """Prefill complete — the sequence decodes one token per step."""
        return self.prefilled >= len(self.context)

    @property
    def tokens_in_cache(self) -> int:
        """Tokens whose K/V are physically written (full blocks up to
        here are immutable and publishable): during prefill that is
        ``prefilled``; during decode it is ``length - 1`` — the
        *newest* generated token's K/V lands only on the NEXT step.
        This lags-one invariant survives speculative decode unchanged,
        for any number of tokens accepted per step: a verify step
        feeds [last token, k drafts] and writes their K/V at positions
        ``length-1 .. length-1+k``, but the LAST emitted token is
        always the verifier's own bonus/correction token, whose K/V
        the step never fed — it is written by the next step, exactly
        like plain decode's newest token (positions beyond the accept
        point hold rejected-draft garbage, masked by ``lens`` and
        trimmed by rollback before they could ever publish)."""
        if not self.in_decode:
            return self.prefilled
        return len(self.context) + max(len(self.generated) - 1, 0)

    @property
    def done(self) -> bool:
        n = len(self.generated) + (len(self.context) - len(self.req.prompt))
        if n >= self.req.max_new_tokens:
            return True
        eos = self.req.eos_id
        return eos is not None and len(self.generated) > 0 \
            and self.generated[-1] == eos

    def expired(self, now: float) -> bool:
        """Deadline budget spent (measured from ``arrival``)."""
        d = self.req.deadline_s
        return bool(d) and d > 0 and (now - self.req.arrival) > d


class ContinuousBatchingScheduler:
    """Admit/evict sequences against a token budget and a block pool."""

    def __init__(self, allocator: BlockAllocator, *, token_budget: int,
                 watermark: int, max_decode_batch: int,
                 max_seq_len: int):
        if token_budget < 1:
            raise ValueError(f"token_budget must be >= 1, got {token_budget}")
        if watermark < 0:
            raise ValueError(f"watermark must be >= 0, got {watermark}")
        need_one = blocks_for(max_seq_len, allocator.block_size)
        if need_one > allocator.capacity:
            raise ValueError(
                f"pool of {allocator.capacity} blocks cannot hold one "
                f"max_seq_len={max_seq_len} sequence ({need_one} blocks) — "
                f"a lone sequence could deadlock growth")
        self.allocator = allocator
        self.token_budget = int(token_budget)
        self.watermark = int(watermark)
        self.max_decode_batch = int(max_decode_batch)
        self.max_seq_len = int(max_seq_len)
        self.pending: Deque[Sequence] = collections.deque()
        self.running: List[Sequence] = []
        #: deadline-shed/cancelled sequences awaiting caller
        #: finalization (the engine publishes their partial results and
        #: drains this list every step)
        self.shed: List[Sequence] = []
        self.evictions = 0
        #: prefix-cache admit statistics (bench hit-rate columns)
        self.prefix_hit_blocks = 0
        self.prefix_lookup_blocks = 0
        #: extra waiting requests not yet in ``pending`` (the engine
        #: points this at its device-staging queue so the queue-depth
        #: gauge counts staged + pending, as documented; a standalone
        #: scheduler has no staging queue, hence 0)
        self.staged_depth = lambda: 0

    # -- bookkeeping ---------------------------------------------------------

    def queue_depth(self) -> int:
        """Requests waiting for admission: scheduler-pending plus
        device-staged-but-undrained.  THE number behind the
        ``hvd_tpu_serve_queue_depth`` gauge and the fleet router's
        least-queue-depth fallback — both must see the same sum, so
        both read it here (pinned by tests/test_serving_sharded.py)."""
        return len(self.pending) + self.staged_depth()

    def submit(self, seq: Sequence) -> None:
        self.pending.append(seq)
        self._book()

    def _book(self) -> None:
        _instr.SERVE_QUEUE_DEPTH.set(self.queue_depth())
        _instr.SERVE_KV_OCCUPANCY.set(self.allocator.occupancy())
        _instr.SERVE_KV_CACHED.set(
            self.allocator.cached_blocks / self.allocator.capacity)

    def resort_pending_by_arrival(self) -> None:
        """Re-establish arrival-order fairness in the pending queue —
        the fleet router calls this after re-dispatching an ejected
        replica's requests: the survivors' queues just absorbed
        requests that may have arrived EARLIER than ones already
        waiting, and appending them at the tail would charge the
        crash's victims the whole queue again.  Stable sort: equal
        arrivals (and the 0.0 default of bare submits) keep their
        submission order, so a no-crash workload is a no-op."""
        if len(self.pending) > 1:
            self.pending = collections.deque(
                sorted(self.pending, key=lambda s: s.req.arrival))

    def finish(self, seq: Sequence) -> None:
        """Release a completed sequence's blocks and batch slot (one
        reference each — shared prefix blocks stay alive for their
        other holders, and cached blocks park on the allocator's LRU,
        still matchable)."""
        self.running.remove(seq)
        self.allocator.free(seq.blocks)
        seq.blocks = []
        self._book()

    def _evict_one(self) -> bool:
        """Preempt the most recently admitted sequence (LIFO recompute)."""
        if len(self.running) <= 1:
            return False
        victim = self.running.pop()
        self.allocator.free(victim.blocks)
        victim.blocks = []
        # recompute preemption: re-prefill prompt + generated so far.
        # Re-admission goes through the same prefix match as any other
        # request, so whatever full blocks survived in the cache (this
        # victim's own, freshly parked, included) are remapped instead
        # of recomputed — and only the uncached tail is re-booked
        # against the token budget.
        victim.context = np.concatenate([
            victim.context, np.asarray(victim.generated, np.int32)])
        victim.generated = []
        victim.prefilled = 0
        victim.cached_len = 0
        victim.published = 0
        victim.staged = None  # host re-pads/re-stages at re-admission
        victim.draft = []  # re-drafted (identically) after re-prefill
        self.pending.appendleft(victim)
        self.evictions += 1
        _instr.SERVE_EVICTIONS.inc()
        self._book()
        return True

    # -- prefix-cache publication --------------------------------------------

    def publish_full_blocks(self, seq: Sequence) -> None:
        """Register ``seq``'s newly-FULL blocks in the prefix index
        (the engine calls this after every step).  Only blocks all
        ``block_size`` positions of which are written are published —
        the partial tail stays private (CoW) — and generated tokens
        publish too, so an evicted sequence's re-admission can match
        its own surviving blocks."""
        if not self.allocator.prefix_cache:
            return
        bs = self.allocator.block_size
        n_full = min(seq.tokens_in_cache // bs, len(seq.blocks))
        if seq.published >= n_full:
            return
        stream = seq.context if not seq.generated else np.concatenate(
            [seq.context, np.asarray(seq.generated, np.int32)])
        while seq.published < n_full:
            i = seq.published
            parent = seq.block_hashes[i - 1] if i else PREFIX_HASH_ROOT
            h = self.allocator.register(
                seq.blocks[i], parent, stream[i * bs:(i + 1) * bs])
            if len(seq.block_hashes) > i:
                seq.block_hashes[i] = h
            else:
                seq.block_hashes.append(h)
            seq.published += 1

    # -- deadlines ----------------------------------------------------------

    def _shed(self, seq: Sequence) -> None:
        self.shed.append(seq)
        _instr.SERVE_DEADLINE_EXCEEDED.inc()

    def cancel_expired(self, now: float) -> List[Sequence]:
        """Shed pending requests already past their deadline and cancel
        expired in-flight sequences — blocks release through the normal
        refcount path (shared prefix blocks survive for their other
        holders), the batch slot frees immediately.  Returns the newly
        shed sequences (also queued on :attr:`shed` for the engine's
        finalization pass)."""
        out: List[Sequence] = []
        for seq in [s for s in self.pending if s.expired(now)]:
            self.pending.remove(seq)
            self._shed(seq)
            out.append(seq)
        for seq in [s for s in self.running if s.expired(now)]:
            self.finish(seq)  # the one teardown path: slot + blocks
            self._shed(seq)
            out.append(seq)
        if out:
            self._book()
        return out

    # -- the per-step decision ----------------------------------------------

    def grow_running(self) -> None:
        """Before a decode step: every running sequence is about to gain
        at least one token — plus up to ``len(seq.draft)`` more when a
        speculative draft is pending (the verify step writes draft K/V
        at positions ``length-1 .. length-1+k`` and may emit k+1
        tokens).  Allocate tail blocks, evicting LIFO when the pool is
        dry — but speculation is strictly best-effort: a sequence whose
        *draft* is what needs the extra blocks drops the draft (that
        step decodes one token, plain) before anyone is evicted, so
        speculative lookahead can never cause an eviction that plain
        decode wouldn't have."""
        for seq in list(self.running):
            if seq not in self.running:
                continue  # evicted by an earlier iteration
            while True:
                need = blocks_for(seq.length + 1 + len(seq.draft),
                                  self.allocator.block_size)
                if need <= len(seq.blocks):
                    break
                got = self.allocator.alloc(need - len(seq.blocks))
                if got is not None:
                    seq.blocks.extend(got)
                    break
                if seq.draft:
                    seq.draft = []  # shed the speculation, not a peer
                    continue
                if not self._evict_one() or seq not in self.running:
                    break
        self._book()

    def admit(self, now: Optional[float] = None) -> List[Sequence]:
        """Admit pending sequences: token budget, decode-batch slots,
        and block watermark all permitting.  Each admitted sequence
        first matches the longest cached block-aligned prefix of its
        context — those blocks map into its table with refcount bumps
        (zero prefill compute for the span) — then allocates only the
        uncached tail's blocks, and only the *uncached* tail tokens are
        booked against the token budget (an evicted-then-readmitted
        sequence whose prefix blocks survived is NOT re-booked at full
        length).  Admitted sequences join ``running`` with
        ``prefilled = cached_len``; the engine prefills the tail in
        chunks.  With ``now``, requests already past their deadline are
        SHED instead of admitted (their prefill would compute tokens
        nobody is waiting for).  Returns the admitted batch (empty =
        nothing admitted)."""
        batch: List[Sequence] = []
        tokens = 0
        bs = self.allocator.block_size
        while self.pending:
            seq = self.pending[0]
            if now is not None and seq.expired(now):
                self.pending.popleft()
                self._shed(seq)
                continue
            ctx = len(seq.context)  # <= max_seq_len: engine validates at
            # submit and caps generation at max_seq_len
            if len(self.running) + len(batch) + 1 > self.max_decode_batch:
                break
            # longest cached prefix, capped one block short of the
            # context: the prefill step must have >= 1 token to compute
            # (it emits the first token), and the cap also keeps the
            # last, partially-filled block private — CoW by construction
            matched, hashes = self.allocator.match_prefix(
                seq.context, max_blocks=(ctx - 1) // bs)
            cached = len(matched) * bs
            tail = ctx - cached
            if batch and tokens + tail > self.token_budget:
                self.allocator.free(matched)  # undo the match's refs
                break
            need = blocks_for(ctx + 1, bs) - len(matched)
            # the watermark bypass exists ONLY for the progress
            # guarantee (an idle engine must admit SOMETHING); with
            # sequences already running, draining below the watermark
            # just sets up the admit→grow→evict thrash it prevents
            if self.allocator.free_blocks - need < self.watermark and (
                    batch or self.running):
                self.allocator.free(matched)
                break
            got = self.allocator.alloc(need)
            if got is None:
                self.allocator.free(matched)
                break
            # CoW invariant: everything the prefill will write (positions
            # >= cached) lands in freshly-allocated private blocks
            assert all(self.allocator.ref(b) == 1 for b in got)
            if self.allocator.prefix_cache:
                # booked only on successful admission (a gated-out
                # sequence re-matches next step — counting its lookups
                # every retry would skew the hit rate)
                lookup = (ctx - 1) // bs
                self.prefix_lookup_blocks += lookup
                self.prefix_hit_blocks += len(matched)
                _instr.SERVE_PREFIX_HITS.inc(len(matched))
                _instr.SERVE_PREFIX_MISSES.inc(lookup - len(matched))
            seq.blocks = matched + got
            seq.cached_len = cached
            seq.prefilled = cached
            seq.published = len(matched)
            seq.block_hashes[:len(hashes)] = hashes
            if seq.req.arrival > 0 and trace.enabled():
                # the queue phase of the request's TTFT decomposition:
                # arrival -> this admission.  The arrival rides the
                # engine clock (perf_counter in production), so the
                # duration is computed on that clock and anchored to
                # the trace clock's "now"; a bare-Sequence caller with
                # no arrival stamp records nothing
                t1 = trace.now()
                waited = max(0.0, (now if now is not None else t1)
                             - seq.req.arrival)
                trace.add_span("serve.queued", t1 - waited, t1,
                               rid=seq.req.id, cached_blocks=len(matched),
                               trace=seq.req.trace_id)
            batch.append(self.pending.popleft())
            tokens += tail
        self.running.extend(batch)
        self._book()
        return batch
