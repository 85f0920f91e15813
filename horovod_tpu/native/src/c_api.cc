// Flat C API + background thread loop.
//
// Reference parity: horovod/common/operations.h/.cc (SURVEY.md §2.1
// "Background loop & C API"): InitializeHorovodOnce spawns the background
// thread, RunLoopOnce drives one coordination cycle, Enqueue* feeds the
// TensorQueue, and the flat C surface (horovod_init / horovod_rank / ...)
// is what the Python shim dlopens.  Consumed from Python via ctypes
// (native/controller.py), the pybind11-free binding path.
#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstdio>
#include <cstring>
#include <memory>
#include <mutex>
#include <set>
#include <string>
#include <thread>

#include "chaos.h"
#include "common.h"
#include "controller.h"
#include "parameter_manager.h"
#include "response_cache.h"
#include "stall_inspector.h"
#include "tensor_queue.h"
#include "timeline.h"
#include "tcp_transport.h"
#include "transport.h"

namespace hvdtpu {
namespace {

// Executor callback into Python: one call per fused Response.
// ids[i] == -1 when this rank holds no entry for names[i]; the rank then
// synthesizes a zero contribution from shape_dims/shape_ndims (join fill).
// extents: flattened per-member negotiated extents (allgather dim0s /
// alltoall splits) with extent_lens[m] values for member m; n_extent_ranks
// is 0 for ops that negotiate no shapes.
typedef void (*ExecCallback)(void* user, int op, int dtype, int process_set,
                             int root_rank, double prescale, double postscale,
                             const int64_t* ids, int n_ids,
                             const int64_t* shape_dims, const int* shape_ndims,
                             const int64_t* extents, const int* extent_lens,
                             int n_extent_ranks, const char* error);

struct GlobalState {
  // Reference analog: horovod/common/global_state.h HorovodGlobalState.
  std::unique_ptr<TensorQueue> queue;
  std::unique_ptr<ResponseCache> cache;
  std::unique_ptr<StallInspector> stall;
  std::unique_ptr<Timeline> timeline;
  std::unique_ptr<ParameterManager> params;
  std::unique_ptr<Controller> controller;
  std::thread background;
  std::atomic<bool> shutdown{false};
  std::atomic<bool> initialized{false};
  // set when the background loop exits (stall shutdown / transport death):
  // the library is dead — reject new work so callers raise instead of hang
  std::atomic<bool> loop_dead{false};
  std::atomic<int64_t> next_id{1};
  ExecCallback exec_cb = nullptr;
  void* exec_user = nullptr;
  std::mutex init_mu;
  // Names claimed from enqueue until their response executed (reference:
  // the tensor-table duplicate check spans the whole entry lifetime, not
  // just the queue window).
  std::mutex names_mu;
  std::set<std::string> active_names;
  // enqueue -> background-loop wakeup: the idle sleep is a CV wait so a
  // new submission is picked up immediately instead of waiting out the
  // remainder of the cycle interval (up to cycle_time_ms of pure
  // latency on every cold submission; PERF.md r5)
  std::mutex wake_mu;
  std::condition_variable wake_cv;
};

GlobalState* g() {
  static GlobalState state;
  return &state;
}

void BackgroundThreadLoop() {
  // Reference: BackgroundThreadLoop in operations.cc — cycle, then sleep
  // the (possibly autotuned) cycle time.  The sleep is SKIPPED when the
  // cycle just made progress (new submissions popped or responses
  // executed) or more work is already queued: in-flight ops never pay
  // the idle-poll interval — the next request piggybacks on the
  // response broadcast just handled (round-4 eager latency; PERF.md).
  // Progress-gating bounds the spin: a rank merely WAITING (stall,
  // straggler peer, join barrier) makes no progress and sleeps, so the
  // fleet cannot busy-loop the negotiation channel through a stall.
  auto* s = g();
  while (!s->shutdown.load()) {
    if (!s->controller->RunLoopOnce()) {
      s->loop_dead.store(true);
      break;
    }
    if (s->queue->Size() > 0 || s->controller->last_cycle_progress())
      continue;
    auto ms = s->params->cycle_time_ms();
    // interruptible idle wait: hvdtpu_enqueue* notifies, so a fresh
    // submission starts negotiating immediately; peers' cycles align via
    // the blocking GatherRequests/Bcast transport either way
    std::unique_lock<std::mutex> lk(s->wake_mu);
    s->wake_cv.wait_for(
        lk, std::chrono::duration<double, std::milli>(ms),
        [s] { return s->queue->Size() > 0 || s->shutdown.load(); });
  }
}

void DefaultLog(int level, const std::string& msg) {
  std::fprintf(stderr, "[%s] hvd_tpu_core: %s\n",
               level >= 2 ? "ERROR" : "WARNING", msg.c_str());
}

}  // namespace
}  // namespace hvdtpu

extern "C" {

using hvdtpu::DataType;
using hvdtpu::OpType;
using hvdtpu::Response;
using hvdtpu::TensorTableEntry;

int hvdtpu_init(int rank, int size, const char* coord_host, int coord_port,
                double cycle_time_ms, long long fusion_threshold,
                int cache_capacity, const char* timeline_path,
                double stall_warn_sec, double stall_shutdown_sec,
                int autotune, const char* autotune_log) {
  auto* s = hvdtpu::g();
  std::lock_guard<std::mutex> lk(s->init_mu);
  if (s->initialized.load()) return 0;
  s->queue = std::make_unique<hvdtpu::TensorQueue>();
  // 0 disables the cache (HOROVOD_CACHE_CAPACITY=0 semantics); negative
  // means "unset" -> reference default 1024
  s->cache = std::make_unique<hvdtpu::ResponseCache>(
      cache_capacity >= 0 ? cache_capacity : 1024);
  s->stall = std::make_unique<hvdtpu::StallInspector>(stall_warn_sec,
                                                      stall_shutdown_sec);
  // always constructed (stable pointer for the controller); inactive
  // until Open — env-configured path opens now, hvdtpu_start_timeline
  // can open one later (reference: horovod_start_timeline)
  s->timeline = std::make_unique<hvdtpu::Timeline>(rank);
  if (timeline_path && timeline_path[0])
    s->timeline->Open(timeline_path);
  s->params = std::make_unique<hvdtpu::ParameterManager>(
      fusion_threshold, cycle_time_ms,
      autotune_log ? autotune_log : "");
  if (autotune) s->params->EnableTuning();

  auto executor = [s](const Response& resp,
                      const std::vector<int64_t>& ids) {
    {
      // release names before the callback resolves futures: a caller that
      // wakes from wait() may immediately resubmit the same name.
      // key matches enqueue: (name, process_set) — same-named tensors on
      // different process sets are distinct entries (reference semantics)
      std::lock_guard<std::mutex> lk(s->names_mu);
      for (const auto& n : resp.names)
        s->active_names.erase(n + "\x1f" +
                              std::to_string(resp.process_set_id));
    }
    if (s->exec_cb) {
      std::vector<int64_t> extents;
      std::vector<int> extent_lens;
      for (const auto& ext : resp.rank_extents) {
        extent_lens.push_back(static_cast<int>(ext.size()));
        extents.insert(extents.end(), ext.begin(), ext.end());
      }
      std::vector<int64_t> shape_dims;
      std::vector<int> shape_ndims;
      for (const auto& shp : resp.shapes) {
        shape_ndims.push_back(static_cast<int>(shp.size()));
        shape_dims.insert(shape_dims.end(), shp.begin(), shp.end());
      }
      s->exec_cb(s->exec_user, static_cast<int>(resp.op),
                 static_cast<int>(resp.dtype), resp.process_set_id,
                 resp.root_rank, resp.prescale, resp.postscale, ids.data(),
                 static_cast<int>(ids.size()), shape_dims.data(),
                 shape_ndims.data(), extents.data(), extent_lens.data(),
                 static_cast<int>(extent_lens.size()),
                 resp.error.empty() ? nullptr : resp.error.c_str());
    }
  };
  // Transport choice (reference: controller selection in operations.cc):
  // single process -> loopback; launcher-driven multi-process world ->
  // TCP star rooted at rank 0 (coord_host:coord_port from tpurun).
  std::unique_ptr<hvdtpu::Transport> transport;
  if (size > 1 && coord_host && coord_host[0]) {
    auto tcp = std::make_unique<hvdtpu::TcpTransport>(coord_host, coord_port,
                                                      rank, size);
    if (tcp->failed()) return 1;  // rendezvous failed
    transport = std::move(tcp);
  } else {
    transport = std::make_unique<hvdtpu::LoopbackTransport>();
  }
  s->controller = std::make_unique<hvdtpu::Controller>(
      std::move(transport), s->queue.get(), s->cache.get(),
      s->stall.get(), s->timeline.get(), s->params.get(), executor,
      hvdtpu::DefaultLog);
  s->shutdown.store(false);
  s->loop_dead.store(false);
  s->background = std::thread(hvdtpu::BackgroundThreadLoop);
  s->initialized.store(true);
  return 0;
}

void hvdtpu_set_exec_callback(void (*cb)(void*, int, int, int, int, double,
                                         double, const int64_t*, int,
                                         const int64_t*, const int*,
                                         const int64_t*, const int*, int,
                                         const char*),
                              void* user) {
  hvdtpu::g()->exec_cb = cb;
  hvdtpu::g()->exec_user = user;
}

int hvdtpu_register_process_set(int set_id, const int* members, int n) {
  auto* s = hvdtpu::g();
  if (!s->initialized.load()) return -1;
  std::vector<int32_t> m(members, members + (n > 0 ? n : 0));
  s->controller->RegisterProcessSet(set_id, std::move(m));
  return 0;
}

int hvdtpu_remove_process_set(int set_id) {
  auto* s = hvdtpu::g();
  if (!s->initialized.load()) return -1;
  s->controller->RemoveProcessSet(set_id);
  return 0;
}

long long hvdtpu_enqueue(long long entry_id, const char* name, int op,
                         int dtype, const long long* shape, int ndim,
                         int process_set, const char* group_key,
                         int group_size, int root_rank,
                         double prescale, double postscale,
                         const long long* splits, int n_splits) {
  // entry_id is caller-assigned so the Python side can register its future
  // BEFORE the entry becomes visible to the background thread — otherwise
  // a fast cycle could execute and drop the id between the enqueue call
  // returning and the future registration (wait() would hang forever).
  auto* s = hvdtpu::g();
  if (!s->initialized.load()) return -2;
  if (s->loop_dead.load()) return -3;  // background loop died
  {
    std::lock_guard<std::mutex> lk(s->names_mu);
    if (!s->active_names
             .insert(std::string(name) + "\x1f" +
                     std::to_string(process_set))
             .second)
      return -1;  // duplicate
  }
  TensorTableEntry e;
  e.id = entry_id > 0 ? entry_id : s->next_id.fetch_add(1);
  e.name = name;
  e.op = static_cast<OpType>(op);
  e.dtype = static_cast<DataType>(dtype);
  e.shape.assign(shape, shape + ndim);
  e.process_set_id = process_set;
  e.group_key = group_key ? group_key : "";
  e.group_size = group_size;
  e.root_rank = root_rank;
  e.prescale = prescale;
  e.postscale = postscale;
  if (splits && n_splits > 0) e.splits.assign(splits, splits + n_splits);
  e.enqueued_at = hvdtpu::Clock::now();
  int64_t id = e.id;
  if (!s->queue->Add(std::move(e))) {
    // roll the name claim back (mirror of hvdtpu_enqueue_n): a rejected
    // entry never executes, so nothing would ever release the name and
    // every later submission under it would be refused as a duplicate
    std::lock_guard<std::mutex> lk(s->names_mu);
    s->active_names.erase(std::string(name) + "\x1f" +
                          std::to_string(process_set));
    return -1;  // duplicate name pending
  }
  {
    // lock-then-notify: without the lock the wake can land between the
    // loop's predicate check and its block and be lost — the submission
    // would wait out the full cycle interval again
    std::lock_guard<std::mutex> wk(s->wake_mu);
  }
  s->wake_cv.notify_one();
  return id;
}

long long hvdtpu_enqueue_n(int n, const long long* entry_ids,
                           const char* const* names, int op,
                           const int* dtypes, const long long* shapes_flat,
                           const int* ndims, int process_set,
                           const char* group_key, int group_size,
                           const int* root_or_rops, double prescale,
                           double postscale) {
  // Batched enqueue: one GIL release, one names check, one queue lock for
  // the whole batch — the entries become visible to the background loop
  // atomically, so a grouped call or an optimizer's backward-burst of
  // gradients negotiates in ONE cycle (see TensorQueue::AddN).
  // All-or-nothing: on any duplicate name nothing is enqueued.
  auto* s = hvdtpu::g();
  if (!s->initialized.load()) return -2;
  if (s->loop_dead.load()) return -3;
  std::vector<std::string> inserted;
  inserted.reserve(n);
  {
    std::lock_guard<std::mutex> lk(s->names_mu);
    for (int i = 0; i < n; ++i) {
      std::string key =
          std::string(names[i]) + "\x1f" + std::to_string(process_set);
      if (!s->active_names.insert(key).second) {
        for (const auto& k : inserted) s->active_names.erase(k);
        return -1;  // duplicate (incl. within the batch)
      }
      inserted.push_back(std::move(key));
    }
  }
  std::vector<hvdtpu::TensorTableEntry> batch;
  batch.reserve(n);
  size_t shape_off = 0;
  auto now = hvdtpu::Clock::now();
  for (int i = 0; i < n; ++i) {
    hvdtpu::TensorTableEntry e;
    e.id = entry_ids[i] > 0 ? entry_ids[i] : s->next_id.fetch_add(1);
    e.name = names[i];
    e.op = static_cast<hvdtpu::OpType>(op);
    e.dtype = static_cast<hvdtpu::DataType>(dtypes[i]);
    e.shape.assign(shapes_flat + shape_off, shapes_flat + shape_off + ndims[i]);
    shape_off += ndims[i];
    e.process_set_id = process_set;
    e.group_key = group_key ? group_key : "";
    e.group_size = group_size;
    e.root_rank = root_or_rops[i];
    e.prescale = prescale;
    e.postscale = postscale;
    e.enqueued_at = now;
    batch.push_back(std::move(e));
  }
  if (!s->queue->AddN(std::move(batch))) {
    std::lock_guard<std::mutex> lk(s->names_mu);
    for (const auto& k : inserted) s->active_names.erase(k);
    return -1;  // duplicate pending entry
  }
  {
    std::lock_guard<std::mutex> wk(s->wake_mu);  // see hvdtpu_enqueue
  }
  s->wake_cv.notify_one();
  return 0;
}

void hvdtpu_shutdown() {
  auto* s = hvdtpu::g();
  std::lock_guard<std::mutex> lk(s->init_mu);
  if (!s->initialized.load()) return;
  // flip initialized first so concurrent enqueues are rejected before the
  // loop is joined; components are NOT freed here (a racing enqueue that
  // slipped past the flag must never touch freed memory) — the next init
  // replaces them.
  s->initialized.store(false);
  s->shutdown.store(true);
  {
    std::lock_guard<std::mutex> wk(s->wake_mu);
  }
  s->wake_cv.notify_one();  // wake an idle loop so join() is immediate
  // ...and a loop blocked on peers inside a cycle: without this, join()
  // returns only when a peer's process dies (a four-process TPU job hung
  // at exit that way, every rank waiting for another to die first)
  if (s->controller) s->controller->Interrupt();
  if (s->background.joinable()) s->background.join();
  if (s->timeline) s->timeline->Close();
  s->loop_dead.store(false);
  s->exec_cb = nullptr;
  {
    std::lock_guard<std::mutex> nlk(s->names_mu);
    s->active_names.clear();
  }
  s->initialized.store(false);
}

int hvdtpu_initialized() { return hvdtpu::g()->initialized.load() ? 1 : 0; }

// 1 once the background loop exited (stall shutdown / transport death):
// the liveness bit /healthz reports (every further enqueue returns -3).
int hvdtpu_loop_dead() { return hvdtpu::g()->loop_dead.load() ? 1 : 0; }

long long hvdtpu_cache_hits() {
  auto* s = hvdtpu::g();
  return s->initialized.load() ? s->cache->hits() : 0;
}

long long hvdtpu_cache_misses() {
  auto* s = hvdtpu::g();
  return s->initialized.load() ? s->cache->misses() : 0;
}

long long hvdtpu_last_request_bytes() {
  auto* s = hvdtpu::g();
  return s->initialized.load() ? s->controller->last_request_bytes() : 0;
}

long long hvdtpu_fusion_threshold() {
  auto* s = hvdtpu::g();
  return s->initialized.load() ? s->params->fusion_threshold() : -1;
}

double hvdtpu_cycle_time_ms() {
  auto* s = hvdtpu::g();
  return s->initialized.load() ? s->params->cycle_time_ms() : -1.0;
}

int hvdtpu_autotune_active() {
  auto* s = hvdtpu::g();
  return s->initialized.load() && s->params->tuning() ? 1 : 0;
}

void hvdtpu_autotune_inject(double score) {
  // Test hook: drive one search step with a synthetic score for the
  // current configuration (lets tests assert the tuner converges on a
  // known score surface without waiting out real sample windows).
  auto* s = hvdtpu::g();
  if (s->initialized.load()) s->params->Advance(score);
}

int hvdtpu_pending_count() {
  auto* s = hvdtpu::g();
  return s->initialized.load()
             ? static_cast<int>(s->stall->PendingCount())
             : 0;
}

// -- chaos (fault injection) + liveness ------------------------------------
//
// The Python layer (horovod_tpu/chaos) parses HVD_TPU_CHAOS, filters by
// rank, derives per-rule stream seeds, and exports every transport.*
// rule here BEFORE hvdtpu_init builds the transport; the engine is a
// process-global singleton so configuration is valid outside init.

int hvdtpu_chaos_set(const char* site, int action, double prob,
                     long long at, long long after, long long times,
                     double delay_sec, int exit_code, const char* fuse,
                     unsigned long long seed) {
  if (site == nullptr || site[0] == '\0') return 1;
  if (action < 1 || action > 6) return 1;
  hvdtpu::chaos::Rule rule;
  rule.action = static_cast<hvdtpu::chaos::Action>(action);
  rule.prob = prob;
  rule.at = at;
  rule.after = after;
  rule.times = times;
  rule.delay_sec = delay_sec;
  rule.exit_code = exit_code;
  rule.fuse = fuse ? fuse : "";
  rule.rng = seed ? seed : 1;
  hvdtpu::chaos::Engine::Get().Set(site, rule);
  return 0;
}

void hvdtpu_chaos_clear() { hvdtpu::chaos::Engine::Get().Clear(); }

long long hvdtpu_chaos_injections() {
  return hvdtpu::chaos::Engine::Get().injections();
}

// Heartbeat deadlines missed by peers on the negotiation channel
// (scraped into hvd_tpu_heartbeat_misses_total at collection time).
long long hvdtpu_heartbeat_misses() {
  auto* s = hvdtpu::g();
  return s->initialized.load() && s->controller
             ? s->controller->heartbeat_misses()
             : 0;
}

void hvdtpu_timeline_activity(const char* tensor, const char* activity,
                              int begin) {
  auto* s = hvdtpu::g();
  if (!s->initialized.load() || !s->timeline || !s->timeline->active())
    return;
  if (begin)
    s->timeline->ActivityStart(tensor, activity);
  else
    s->timeline->ActivityEnd(tensor, activity);
}

// Fusion-buffer pack: concatenate n contiguous byte buffers into dst and
// zero the tail up to dst_bytes (the power-of-two pad).  Called from the
// exec callback through ctypes, which RELEASES the GIL for the duration —
// the training thread keeps running while the background thread memcpys
// (reference: the batched fusion-buffer memcpy kernels of
// cuda_kernels.cu, host-side here because the buffer feeds a compiled
// XLA collective).
void hvdtpu_pack(const void** srcs, const long long* nbytes, int n,
                 char* dst, long long dst_bytes) {
  long long off = 0;
  for (int i = 0; i < n; ++i) {
    std::memcpy(dst + off, srcs[i], static_cast<size_t>(nbytes[i]));
    off += nbytes[i];
  }
  if (off < dst_bytes)
    std::memset(dst + off, 0, static_cast<size_t>(dst_bytes - off));
}

// Runtime timeline control (reference: horovod_start_timeline /
// horovod_stop_timeline in operations.cc).  Returns 0 on success, 1 when
// already active / not initialized / unopenable.
int hvdtpu_start_timeline(const char* path) {
  auto* s = hvdtpu::g();
  if (!s->initialized.load() || !s->timeline || !path || !path[0]) return 1;
  return s->timeline->Open(path) ? 0 : 1;
}

int hvdtpu_stop_timeline() {
  auto* s = hvdtpu::g();
  if (!s->initialized.load() || !s->timeline) return 1;
  s->timeline->Close();
  return 0;
}

}  // extern "C"
