#include "controller.h"

#include <algorithm>

#include "group_table.h"
#include "message.h"

namespace hvdtpu {

namespace {
// Entries are identified by (name, process_set) everywhere — matching the
// duplicate check in c_api.cc; name alone would collide across sets.
std::string Key(const std::string& name, int32_t process_set) {
  return name + '\x1f' + std::to_string(process_set);
}
}  // namespace

bool Controller::RunLoopOnce() {
  // 1. drain newly submitted entries (reference: PopMessagesFromQueue).
  // Cache-hit signatures travel as bare positions (the reference's
  // ResponseCache bit vector); only misses are fully encoded.
  auto newly = queue_->PopAll();
  last_cycle_progress_.store(!newly.empty());
  std::vector<int64_t> hit_positions;
  std::vector<TensorTableEntry> full;
  for (auto& e : newly) {
    if (timeline_ && timeline_->active())
      timeline_->ActivityStart(e.name, "QUEUE");
    stall_->RecordPending(e);
    int64_t pos = ResponseCache::Cacheable(e) ? cache_->Query(e) : -1;
    if (pos >= 0)
      hit_positions.push_back(pos);
    else
      full.push_back(e);
    pending_.emplace(Key(e.name, e.process_set_id), e);
  }

  // 2. report to the coordinator (reference: SendReadyTensors)
  auto mine = wire::EncodeCycleRequest(hit_positions, full);
  if (!hit_positions.empty() || !full.empty())
    last_request_bytes_.store(static_cast<int64_t>(mine.size()));
  auto gathered = transport_->GatherRequests(mine);

  // 3. coordinator: account reports, build fused responses
  std::string payload;
  if (rank() == 0) {
    for (int32_t r = 0; r < static_cast<int32_t>(gathered.size()); ++r) {
      std::vector<int64_t> positions;
      std::vector<TensorTableEntry> reqs;
      if (!wire::DecodeCycleRequest(gathered[r], &positions, &reqs)) {
        if (!gathered[r].empty() && protocol_error_.empty()) {
          // a non-empty payload that fails to decode means the peer
          // speaks a different wire version (processes built from
          // different sources) or sent garbage — silently skipping it
          // would strand that rank's collectives until stall shutdown;
          // fail the fleet loudly instead
          protocol_error_ =
              "failed to decode rank " + std::to_string(r) +
              "'s negotiation payload (wire-version mismatch — were all "
              "processes built from the same sources?)";
        }
        continue;
      }
      // reconstruct position-only reports from the replicated cache
      // (reference: Controller::ComputeResponseList cache-hit path)
      for (auto pos : positions) {
        TensorTableEntry meta;
        if (cache_->GetByPosition(pos, &meta)) {
          reqs.push_back(std::move(meta));
        } else if (protocol_error_.empty()) {
          // replicated-cache divergence (e.g. per-rank cache-capacity
          // misconfiguration): unrecoverable — fail every rank loudly
          // instead of silently dropping the entry until stall shutdown
          protocol_error_ =
              "rank " + std::to_string(r) + " reported cache position " +
              std::to_string(pos) +
              " unknown to the coordinator: replicated response-cache "
              "divergence (is HVD_TPU_CACHE_CAPACITY identical on all "
              "ranks?)";
        }
      }
      for (auto& e : reqs) {
        if (e.op == OpType::JOIN) {
          // reference: Join rides the request stream; the coordinator
          // excludes joined ranks from readiness until everyone joins
          joined_ranks_.insert(r);
          last_join_rank_ = r;
          continue;
        }
        auto it = coord_table_.find(Key(e.name, e.process_set_id));
        if (it == coord_table_.end()) {
          PendingCoord pc;
          pc.meta = e;
          pc.order = order_counter_++;
          it = coord_table_.emplace(Key(e.name, e.process_set_id),
                                    std::move(pc))
                   .first;
        }
        AccountReport(&it->second, r, e);
      }
    }
    if (protocol_error_.empty()) {
      payload = wire::EncodeResponseList(BuildResponses());
    } else {
      // a no-names error response = global protocol failure: every rank
      // fails all pending entries and stops its loop
      Response fatal;
      fatal.error = protocol_error_;
      payload = wire::EncodeResponseList({fatal});
    }
  }

  // 4. broadcast the response list (reference: SendFinalTensors)
  payload = transport_->BcastResponseList(payload);
  if (transport_->failed()) {
    // peer died mid-negotiation: fail every pending entry so waiters get
    // HorovodInternalError — the elastic recovery signal (SURVEY.md §5.3).
    // The transport's failure reason NAMES the peer and the cause
    // (connection closed vs heartbeat deadline) so the error on the
    // Python side says which process to look at.
    std::string why = transport_->failure_reason();
    if (why.empty()) why = "peer died or disconnected";
    size_t n = FailAllPending(
        "negotiation transport failed: " + why, "");
    if (interrupted_.load()) return false;  // our own shutdown, not news
    if (n) {
      logger_(2, "negotiation transport failed (" + why +
                 ") with collectives in flight; background loop stopping");
    } else {
      // idle teardown: often just a peer exiting first — not an error —
      // but still NAME the cause (a heartbeat-timed-out peer detected
      // while idle must be diagnosable from this one line)
      logger_(1, "negotiation channel down while idle (" + why +
                 "); background loop stopping");
    }
    return false;
  }
  std::vector<Response> responses;
  if (!wire::DecodeResponseList(payload, &responses) && !payload.empty()) {
    // same failure class as the coordinator-side decode guard: a
    // response broadcast this process cannot parse (wire-version
    // mismatch between differently built processes) — fail loudly
    // instead of spinning idle until stall shutdown
    const std::string msg =
        "failed to decode the coordinator's response broadcast "
        "(wire-version mismatch — were all processes built from the "
        "same sources?)";
    FailAllPending(msg, msg + "; background loop stopping");
    return false;
  }

  // global protocol failure (no-names error response): fail everything
  // in flight on every rank and stop the loop
  for (const auto& resp : responses) {
    if (resp.names.empty() && !resp.error.empty()) {
      FailAllPending(resp.error, "fatal negotiation error: " + resp.error);
      return false;
    }
  }

  // 5. execute: map names to local ids, invoke the XLA executor callback
  int64_t cycle_bytes = 0;
  for (const auto& resp : responses) {
    std::vector<int64_t> local_ids;
    local_ids.reserve(resp.names.size());
    // Replicated-cache state transition: every rank — member of the
    // response's process set or not — commits the same entries in the
    // same broadcast order (response_cache.h contract: skipping any
    // would diverge position assignment).
    for (size_t i = 0; i < resp.names.size(); ++i) {
      if (i < resp.cacheable.size() && resp.cacheable[i]) {
        TensorTableEntry meta;
        meta.name = resp.names[i];
        meta.op = resp.op;
        meta.dtype = resp.dtype;
        meta.shape = resp.shapes[i];
        meta.process_set_id = resp.process_set_id;
        meta.root_rank = resp.root_rank;
        meta.prescale = resp.prescale;
        meta.postscale = resp.postscale;
        cache_->Commit(meta);
      }
    }
    // non-members hold no entries and must not participate in the set's
    // data-plane program (its mesh spans member processes only)
    auto members = SetMembers(resp.process_set_id);
    if (std::find(members.begin(), members.end(), rank()) ==
        members.end()) {
      continue;
    }
    for (size_t i = 0; i < resp.names.size(); ++i) {
      auto it = pending_.find(Key(resp.names[i], resp.process_set_id));
      if (it == pending_.end()) {
        local_ids.push_back(-1);  // joined rank: zero contribution
      } else {
        local_ids.push_back(it->second.id);
        cycle_bytes += it->second.NumBytes();
        if (timeline_ && timeline_->active()) {
          timeline_->ActivityEnd(resp.names[i], "QUEUE");
          timeline_->ActivityStart(resp.names[i], "XLA_COMM");
        }
        pending_.erase(it);
      }
      stall_->RecordDone(resp.names[i]);
    }
    executor_(resp, local_ids);
    // XLA_COMM spans END on the Python side when the result data is
    // actually ready — executor_() returning only means the async XLA
    // dispatch was issued (round-2 verdict: dispatch-time spans made
    // traces show near-zero COMM).  Error responses never reach that
    // code, so close their spans here — but only the spans actually
    // opened above (ids of -1 are join fills with no local span).
    if (timeline_ && timeline_->active() && !resp.error.empty())
      for (size_t i = 0; i < resp.names.size(); ++i)
        if (local_ids[i] != -1)
          timeline_->ActivityEnd(resp.names[i], "XLA_COMM");
  }
  if (cycle_bytes > 0) params_->Observe(cycle_bytes);
  if (!responses.empty()) last_cycle_progress_.store(true);
  if (timeline_ && timeline_->active() && !responses.empty())
    timeline_->MarkCycle();

  // 6. stall inspection (reference: StallInspector::CheckForStalledTensors)
  std::vector<std::string> warnings;
  bool shutdown = stall_->Check(&warnings);
  for (const auto& w : warnings)
    logger_(1, "possible stall: tensor " + w +
                   " submitted on this rank but not yet executed "
                   "(waiting on peers?)");
  if (shutdown) {
    // fail everything in flight so waiters raise instead of hanging —
    // naming the stuck tensors so the Python-side error says WHAT never
    // completed, not just that something did
    std::string stuck;
    for (const auto& name : stall_->PendingNames()) {
      if (!stuck.empty()) stuck += ", ";
      stuck += name;
    }
    std::string msg = "stall shutdown threshold exceeded";
    if (!stuck.empty()) msg += " (pending: " + stuck + ")";
    FailAllPending(msg, msg + "; aborting background loop");
    return false;
  }
  return true;
}

size_t Controller::FailAllPending(const std::string& error,
                                  const std::string& log_msg) {
  Response err;
  err.error = error;
  std::vector<int64_t> ids;
  for (auto& [key, e] : pending_) {
    err.names.push_back(e.name);
    err.shapes.push_back(e.shape);
    ids.push_back(e.id);
    stall_->RecordDone(e.name);
  }
  pending_.clear();
  if (!ids.empty()) executor_(err, ids);
  if (!log_msg.empty()) logger_(2, log_msg);
  return ids.size();
}

void Controller::AccountReport(PendingCoord* pc, int32_t r,
                               const TensorTableEntry& e) {
  // Cross-rank shape negotiation (reference: the per-rank tensor_sizes
  // the MPI ops use for allgather recvcounts / alltoall splits, plus the
  // "mismatched shapes across ranks must raise cleanly" contract).
  const auto& first = pc->meta;
  auto mismatch = [&](const std::string& what) {
    if (pc->error.empty())
      pc->error = "rank " + std::to_string(r) + " submitted " + e.name +
                  " with " + what + " inconsistent with other ranks";
  };
  if (e.op != first.op || e.dtype != first.dtype) mismatch("op/dtype");
  auto trailing_dims_match = [&]() {
    return e.shape.size() == first.shape.size() &&
           std::equal(e.shape.begin() + (e.shape.empty() ? 0 : 1),
                      e.shape.end(),
                      first.shape.begin() + (first.shape.empty() ? 0 : 1));
  };
  switch (e.op) {
    case OpType::ALLGATHER: {
      // dim0 may differ per rank; trailing dims must match
      if (!trailing_dims_match()) mismatch("trailing dimensions");
      pc->rank_info[r] = {e.shape.empty() ? 0 : e.shape[0]};
      break;
    }
    case OpType::ALLTOALL: {
      if (!trailing_dims_match()) mismatch("trailing dimensions");
      int64_t dim0 = e.shape.empty() ? 0 : e.shape[0];
      auto set_size =
          static_cast<int64_t>(SetMembers(e.process_set_id).size());
      if (!e.splits.empty()) {
        int64_t total = 0;
        for (auto s : e.splits) {
          if (s < 0) mismatch("negative split");
          total += s;
        }
        if (static_cast<int64_t>(e.splits.size()) != set_size ||
            total != dim0)
          mismatch("splits (length must be set size, sum must be dim0)");
      } else if (set_size > 0 && dim0 % set_size != 0) {
        // splitless even alltoall requires divisibility; catching it in
        // negotiation fails ALL ranks cleanly instead of one rank raising
        // locally while the rest enter the collective and stall
        mismatch("dim0 not divisible by world size (and no splits given)");
      }
      std::vector<int64_t> info = {dim0};
      info.insert(info.end(), e.splits.begin(), e.splits.end());
      pc->rank_info[r] = std::move(info);
      break;
    }
    default:
      // allreduce/broadcast/reducescatter/barrier: identical shapes
      if (e.shape != first.shape) mismatch("shape");
      break;
  }
  // op parameters must agree too — otherwise the first reporter's
  // root/scale silently wins on the disagreeing rank
  if (e.root_rank != first.root_rank) mismatch("root_rank");
  if (e.prescale != first.prescale || e.postscale != first.postscale)
    mismatch("prescale/postscale factors");
  if (e.group_key != first.group_key || e.group_size != first.group_size)
    mismatch("grouped-call membership");
  pc->reported.insert(r);
}

void Controller::RegisterProcessSet(int32_t set_id,
                                    std::vector<int32_t> members) {
  std::lock_guard<std::mutex> lk(sets_mu_);
  set_members_[set_id] = std::move(members);
}

void Controller::RemoveProcessSet(int32_t set_id) {
  std::lock_guard<std::mutex> lk(sets_mu_);
  set_members_.erase(set_id);
}

std::vector<int32_t> Controller::SetMembers(int32_t set_id) const {
  {
    std::lock_guard<std::mutex> lk(sets_mu_);
    auto it = set_members_.find(set_id);
    if (it != set_members_.end() && !it->second.empty()) return it->second;
  }
  std::vector<int32_t> all(size());
  for (int32_t r = 0; r < size(); ++r) all[r] = r;
  return all;
}

// Group keys carry a per-call sequence nonce (name#seq, controller.py
// group_call_seq), so a RETRY of a corrected group never matches an
// errored key — the memory only needs to outlive the slowest plausible
// straggler member of the errored call itself.  Tied to the stall
// inspector's configured warning horizon (by then a straggler is loudly
// named anyway), floored at 60 s; bounded because entries expire and
// errors are rare.
std::chrono::duration<double> Controller::ErroredGroupMemory() const {
  return std::chrono::duration<double>(
      std::max(60.0, stall_ ? stall_->warn_seconds() : 0.0));
}

void Controller::RememberErroredGroup(const std::string& group_key) {
  errored_groups_[group_key] = Clock::now();
}

std::vector<Response> Controller::BuildResponses() {
  // Grouped-call error propagation: a group whose membership mismatched
  // across ranks can NEVER complete, so every member must fail — the
  // already-reported siblings now, and members that arrive later via the
  // errored_groups_ memory.  Without this, an errored member withheld by
  // the completeness filter (or an orphan member only some ranks submit)
  // hangs the fleet instead of raising.
  for (auto& [key, pc] : coord_table_) {
    if (!pc.meta.group_key.empty() && !pc.error.empty())
      RememberErroredGroup(
          Key(pc.meta.group_key, pc.meta.process_set_id));
  }
  auto now = Clock::now();
  const auto errored_memory = ErroredGroupMemory();
  for (auto it = errored_groups_.begin(); it != errored_groups_.end();) {
    if (now - it->second > errored_memory)
      it = errored_groups_.erase(it);
    else
      ++it;
  }
  for (auto& [key, pc] : coord_table_) {
    if (!pc.meta.group_key.empty() && pc.error.empty() &&
        errored_groups_.count(
            Key(pc.meta.group_key, pc.meta.process_set_id)))
      pc.error = "member of a grouped call whose membership mismatched "
                 "across ranks";
  }

  // Ready = reported by all non-joined member ranks of the entry's
  // process set (reference: per-ProcessSet controllers count readiness
  // against their own membership).  Deterministic order: FIFO by
  // coordinator first-sight (responses preserve request arrival order
  // before fusion).  When every member has joined, remaining reported
  // entries flush with zero contributions from the joined ranks.
  // Errored GROUPED entries are always ready: an orphan member may never
  // be reported by every rank, so waiting could be forever (ranks that
  // never submitted it ignore the error response).  Ungrouped errors
  // keep the wait-for-all-reporters rule: every rank holds the entry, so
  // full reporting is guaranteed and failing everyone at once is cleaner
  // than leaving a late submitter to renegotiate against failed peers.
  std::vector<const PendingCoord*> ready;
  for (auto& [name, pc] : coord_table_) {
    if (!pc.error.empty() && !pc.meta.group_key.empty()) {
      ready.push_back(&pc);
      continue;
    }
    auto members = SetMembers(pc.meta.process_set_id);
    size_t need = 0;
    std::set<int32_t> effective;
    for (auto m : members) {
      if (joined_ranks_.find(m) == joined_ranks_.end()) {
        ++need;
        if (pc.reported.count(m)) effective.insert(m);
      }
    }
    bool is_ready =
        need > 0 ? effective.size() >= need : !pc.reported.empty();
    if (is_ready) ready.push_back(&pc);
  }
  // group atomicity (reference: GroupTable): only emit a group's entries
  // when the whole group is ready.  Keyed by the wire-carried group_key
  // (cross-rank stable) + process set — see group_table.h for why local
  // numeric ids cannot work here.  The table is per-cycle local state:
  // readiness is a function of THIS cycle's ready set only.  Errored
  // entries bypass the filter (they emit as errors regardless).
  GroupTable groups;
  for (auto* pc : ready)
    if (!pc->meta.group_key.empty() && pc->error.empty())
      groups.Observe(Key(pc->meta.group_key, pc->meta.process_set_id));
  ready.erase(
      std::remove_if(ready.begin(), ready.end(),
                     [&](const PendingCoord* pc) {
                       if (pc->meta.group_key.empty() ||
                           !pc->error.empty())
                         return false;
                       return !groups.Complete(
                           Key(pc->meta.group_key,
                               pc->meta.process_set_id),
                           pc->meta.group_size);
                     }),
      ready.end());
  std::sort(ready.begin(), ready.end(),
            [](const PendingCoord* a, const PendingCoord* b) {
              return a->order < b->order;
            });

  // fuse: same (op, dtype, process_set, scale factors) bucket up to the
  // fusion threshold (reference: Controller::FuseResponses)
  std::vector<Response> out;
  int64_t bucket_bytes = 0;
  auto fusable = [&](const Response& r, const TensorTableEntry& e) {
    return r.op == e.op && r.dtype == e.dtype &&
           r.process_set_id == e.process_set_id &&
           r.root_rank == e.root_rank && r.prescale == e.prescale &&
           r.postscale == e.postscale && e.op == OpType::ALLREDUCE;
  };
  std::vector<std::string> emitted;
  for (auto* pc : ready) {
    const auto& e = pc->meta;
    if (!pc->error.empty()) {
      // cross-rank inconsistency: fail this entry on every rank instead
      // of executing garbage (reference: clean shape-mismatch errors)
      Response r;
      r.op = e.op;
      r.dtype = e.dtype;
      r.process_set_id = e.process_set_id;
      r.names = {e.name};
      r.shapes = {e.shape};
      r.cacheable = {0};
      r.error = pc->error;
      out.push_back(std::move(r));
      emitted.push_back(Key(e.name, e.process_set_id));
      continue;
    }
    int64_t threshold = params_->fusion_threshold();
    if (!out.empty() && fusable(out.back(), e) &&
        (threshold <= 0 ? out.back().names.size() < 1  // fusion disabled
                        : bucket_bytes + e.NumBytes() <= threshold)) {
      out.back().names.push_back(e.name);
      out.back().shapes.push_back(e.shape);
      out.back().cacheable.push_back(
          static_cast<uint8_t>(ResponseCache::Cacheable(e) ? 1 : 0));
      bucket_bytes += e.NumBytes();
    } else {
      Response r;
      r.op = e.op;
      r.dtype = e.dtype;
      r.process_set_id = e.process_set_id;
      r.root_rank = e.root_rank;
      r.prescale = e.prescale;
      r.postscale = e.postscale;
      r.names = {e.name};
      r.shapes = {e.shape};
      r.cacheable = {
          static_cast<uint8_t>(ResponseCache::Cacheable(e) ? 1 : 0)};
      if (e.op == OpType::ALLGATHER || e.op == OpType::ALLTOALL) {
        // negotiated per-member extents ride the response (reference:
        // Response::tensor_sizes), indexed in set-member order; joined
        // ranks contribute zero rows
        auto members = SetMembers(e.process_set_id);
        r.rank_extents.resize(members.size());
        for (size_t mi = 0; mi < members.size(); ++mi) {
          auto info = pc->rank_info.find(members[mi]);
          if (info != pc->rank_info.end())
            r.rank_extents[mi] = info->second;
          else
            r.rank_extents[mi] = {0};
        }
      }
      out.push_back(std::move(r));
      bucket_bytes = e.NumBytes();
    }
    emitted.push_back(Key(e.name, e.process_set_id));
  }
  for (const auto& key : emitted) coord_table_.erase(key);

  // everyone joined: release the join barrier (reference: JoinOp response
  // carrying the last joining rank) and reset the joined state
  if (!joined_ranks_.empty() &&
      static_cast<int>(joined_ranks_.size()) == size()) {
    Response jr;
    jr.op = OpType::JOIN;
    jr.root_rank = last_join_rank_;
    jr.names = {"__join__"};
    jr.shapes = {{}};
    jr.cacheable = {0};
    out.push_back(std::move(jr));
    joined_ranks_.clear();
    last_join_rank_ = -1;
  }
  return out;
}

}  // namespace hvdtpu
