// The negotiation controller: the brain of the background thread.
//
// Reference parity: horovod/common/controller.h/.cc (SURVEY.md §2.1): each
// cycle every rank reports newly-pending tensors; the coordinator (rank 0)
// marks a tensor ready when ALL participating ranks have reported it,
// fuses ready tensors into Responses up to the fusion threshold, and
// broadcasts the ResponseList; every rank then executes the same fused
// collectives in the same order.  Join/Barrier ride the same protocol.
//
// TPU-native difference: "execute" means invoking the registered executor
// callback, which launches a cached compiled XLA collective — the
// controller never touches tensor bytes (SURVEY.md §7.1).
#pragma once

#include <atomic>
#include <functional>
#include <map>
#include <memory>
#include <mutex>
#include <set>
#include <unordered_map>
#include <vector>

#include "common.h"
#include "parameter_manager.h"
#include "response_cache.h"
#include "stall_inspector.h"
#include "tensor_queue.h"
#include "timeline.h"
#include "transport.h"

namespace hvdtpu {

// Executor: runs one fused Response on the data plane.  local_ids[i] is
// the local entry id for names[i], or -1 when this rank has no such entry
// (post-join zero contribution).
using Executor = std::function<void(const Response&,
                                    const std::vector<int64_t>& local_ids)>;
using Logger = std::function<void(int level, const std::string&)>;

class Controller {
 public:
  Controller(std::unique_ptr<Transport> transport, TensorQueue* queue,
             ResponseCache* cache,
             StallInspector* stall, Timeline* timeline,
             ParameterManager* params, Executor executor, Logger logger)
      : transport_(std::move(transport)),
        queue_(queue),
        cache_(cache),
        stall_(stall),
        timeline_(timeline),
        params_(params),
        executor_(std::move(executor)),
        logger_(std::move(logger)) {}

  // One coordination cycle (reference: RunLoopOnce in operations.cc).
  // Returns false when a shutdown condition tripped (stall hard-limit).
  bool RunLoopOnce();

  int rank() const { return transport_->rank(); }
  int size() const { return transport_->size(); }

  // Called by hvdtpu_shutdown before it joins the loop: unblocks a cycle
  // waiting on peers (Transport::Interrupt); the failure it causes is this
  // rank's own leaving, so it is not logged.
  void Interrupt() {
    interrupted_.store(true);
    transport_->Interrupt();
  }

  // Process-set membership (process ranks), mirrored from the Python
  // registry on every process (reference: ProcessSetTable).  Readiness for
  // a set's tensors is counted against its members, not the world.
  void RegisterProcessSet(int32_t set_id, std::vector<int32_t> members);
  void RemoveProcessSet(int32_t set_id);
  std::vector<int32_t> SetMembers(int32_t set_id) const;

  // Size of this rank's last non-empty cycle request payload — the
  // observable for the steady-state bit-vector bypass (a cached cycle is
  // O(positions) bytes; a miss cycle carries full encodings).
  int64_t last_request_bytes() const { return last_request_bytes_.load(); }

  // Heartbeat deadlines missed on the negotiation transport (0 on the
  // loopback transport) — scraped into hvd_tpu_heartbeat_misses_total.
  long long heartbeat_misses() const {
    return transport_->heartbeat_misses();
  }

  // Whether the last cycle did anything (popped new entries or executed
  // responses).  Gates the background loop's sleep-skip: progress means
  // more work is likely imminent (piggyback the next request on the
  // response just handled); NO progress — e.g. every rank blocked on a
  // straggler — must sleep, or the fleet busy-spins the negotiation
  // channel for the whole wait.
  bool last_cycle_progress() const { return last_cycle_progress_.load(); }

 private:
  struct PendingCoord {  // coordinator-side per-name state
    TensorTableEntry meta;
    std::set<int32_t> reported;
    int64_t order;  // FIFO tie-break for deterministic fusion order
    // per-rank negotiated extents (allgather dim0s / alltoall splits)
    std::map<int32_t, std::vector<int64_t>> rank_info;
    // first cross-rank consistency violation (mismatched shapes, bad
    // splits): emitted as an error Response so every rank raises cleanly
    std::string error;
  };

  std::vector<Response> BuildResponses();
  void AccountReport(PendingCoord* pc, int32_t r, const TensorTableEntry& e);
  void RememberErroredGroup(const std::string& group_key);
  // Fail every in-flight entry with `error` (waiters raise
  // HorovodInternalError) and log `log_msg` at error level (skipped when
  // empty); returns how many entries were failed.  Every unrecoverable
  // negotiation exit shares this so the bookkeeping (stall RecordDone,
  // pending_ clear) cannot drift between copies.
  size_t FailAllPending(const std::string& error,
                        const std::string& log_msg);
  std::chrono::duration<double> ErroredGroupMemory() const;

  std::atomic<int64_t> last_request_bytes_{0};
  std::atomic<bool> last_cycle_progress_{false};
  std::atomic<bool> interrupted_{false};
  // coordinator-side unrecoverable negotiation failure (e.g. replicated
  // cache divergence); broadcast as a no-names error response
  std::string protocol_error_;

  std::unique_ptr<Transport> transport_;
  TensorQueue* queue_;
  ResponseCache* cache_;
  StallInspector* stall_;
  Timeline* timeline_;
  ParameterManager* params_;
  Executor executor_;
  Logger logger_;

  // local entries awaiting a response, by name
  std::unordered_map<std::string, TensorTableEntry> pending_;
  // coordinator state (rank 0 only)
  std::map<std::string, PendingCoord> coord_table_;
  // Groups whose membership mismatched across ranks: an errored group can
  // never complete, so every member — including a straggler that lands
  // cycles AFTER the error emitted (enqueue loop straddling a cycle
  // boundary, or a briefly frozen peer) — must fail instead of waiting on
  // the completeness filter.  Keys carry a per-call nonce (name#seq), so
  // a corrected RETRY under the same user name has a fresh key and can
  // never be poisoned; the time bound only caps memory.
  std::unordered_map<std::string, Clock::time_point> errored_groups_;
  std::set<int32_t> joined_ranks_;
  int32_t last_join_rank_ = -1;
  int64_t order_counter_ = 0;
  // set id -> member process ranks (absent/empty = all ranks)
  mutable std::mutex sets_mu_;
  std::unordered_map<int32_t, std::vector<int32_t>> set_members_;
};

}  // namespace hvdtpu
