// TCP star transport: cross-process negotiation channel.
//
// Reference parity: the Gloo controller's rendezvous + gather/bcast
// (horovod/common/gloo/gloo_controller.cc, SURVEY.md §2.1): rank 0 is the
// coordinator; every cycle non-roots send their encoded request lists and
// receive the fused response list back.  Where the reference rendezvouses
// through an HTTP KV store hosted by the launcher, this transport dials a
// socket the tpurun launcher allocated (HVD_TPU_NATIVE_PORT) — same
// topology, one fewer moving part.  Loopback RTT ~100us against a 1ms
// cycle keeps negotiation off the critical path.
//
// POSIX sockets only; failures poison the transport and surface as
// HorovodInternalError on the Python side (the elastic recovery signal,
// SURVEY.md §5.3).
#pragma once

#include <arpa/inet.h>
#include <netdb.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <sys/socket.h>
#include <sys/time.h>
#include <unistd.h>

#include <atomic>
#include <cerrno>
#include <chrono>
#include <cstdlib>
#include <cstring>
#include <memory>
#include <mutex>
#include <random>
#include <string>
#include <thread>
#include <vector>

#include "chaos.h"
#include "secret.h"
#include "thread_pool.h"
#include "transport.h"

namespace hvdtpu {

class TcpTransport : public Transport {
 public:
  // rank 0 binds+listens on port and accepts size-1 peers; others connect
  // with retry until timeout (rendezvous races with process startup).
  //
  // When HVD_TPU_SECRET is set (the tpurun launcher always sets a fresh
  // per-job nonce), the hello is a mutual HMAC challenge-response
  // (secret.h) — an unauthenticated peer reaching the port cannot join
  // or poison negotiation, and a port-squatting rogue coordinator is
  // rejected by the workers (reference: secret.py's HMAC-signed RPC,
  // SURVEY.md §2.4).
  //
  // Hello wire: worker sends rank(4, LE) + auth-mode flag(1: 0x01 when it
  // holds a secret); the coordinator answers with its own flag byte.  A
  // secret/no-secret MISMATCH (half-configured job) is therefore detected
  // on the first exchange and rejected with a clear error on both sides —
  // before the flag existed, a mismatched fleet hung until the rendezvous
  // timeout with no hint at the cause (one side waiting for challenge
  // bytes the other never sends).
  //
  // Steady state (authenticated mode): every negotiation frame carries an
  // HMAC-SHA256 trailer under a per-connection key derived from the hello
  // challenges — key = HMAC(secret, "frame" + Cw + Cr) — over a direction
  // byte ('C' coordinator->worker / 'W' worker->coordinator, blocking
  // reflection), a per-direction monotonic sequence number (blocking
  // replay/reorder), and the payload.  Closes the round-5 ADVICE gap: the
  // hello proved identity but left post-handshake frames open to
  // injection by anyone who could splice the TCP stream.  A bad MAC
  // poisons the transport exactly like a peer death — FailAllPending on
  // the Python side, never a silently accepted forged response.
  // Liveness (round-7 fault-tolerance work): every process runs a tiny
  // heartbeat thread that writes a 4-byte HB frame on each established
  // control connection every HVD_TPU_HEARTBEAT_INTERVAL seconds, and
  // steady-state reads carry a HVD_TPU_HEARTBEAT_TIMEOUT receive
  // deadline.  A peer that is HUNG (process alive, loop frozen — SIGSTOP,
  // GIL wedge, frozen VM) stops producing both cycle frames and
  // heartbeats, so the deadline expires and pending collectives fail
  // FAST with a named-peer error instead of waiting out the stall
  // inspector; a peer merely BUSY (minutes-long XLA compile inside the
  // exec callback) keeps heartbeating from this independent thread and is
  // never false-positived.  Interval/timeout <= 0 disables both (legacy
  // blocking reads).  HB frames are liveness-only: no payload, no MAC,
  // no sequence — any byte injection on the stream already desyncs the
  // MAC'd framing, so they add no authenticated-mode attack surface.
  TcpTransport(const std::string& host, int port, int rank, int size,
               double timeout_sec = 60.0)
      : rank_(rank), size_(size) {
    const char* sec = std::getenv("HVD_TPU_SECRET");
    secret_ = sec ? sec : "";
    hb_interval_ = EnvSeconds("HVD_TPU_HEARTBEAT_INTERVAL", 5.0);
    hb_timeout_ = EnvSeconds("HVD_TPU_HEARTBEAT_TIMEOUT", 30.0);
    if (hb_interval_ <= 0.0 || hb_timeout_ <= 0.0) {
      hb_interval_ = hb_timeout_ = 0.0;
    } else if (hb_timeout_ < 3.0 * hb_interval_) {
      // a deadline tighter than a few beat periods false-positives
      // healthy-but-idle peers on ordinary jitter; widen it and say so
      double widened = 3.0 * hb_interval_;
      std::fprintf(stderr,
                   "[WARNING] hvd_tpu_core: HVD_TPU_HEARTBEAT_TIMEOUT "
                   "(%.1fs) < 3x interval (%.1fs); raising the deadline "
                   "to %.1fs\n",
                   hb_timeout_, hb_interval_, widened);
      hb_timeout_ = widened;
    }
    if (rank == 0) {
      // the beacon must start BEFORE the accept loop finishes: an
      // already-connected worker arms its read deadline immediately,
      // and a straggler peer booting slower than the deadline would
      // otherwise make that worker false-positive rank 0 as hung on
      // every cold start (AcceptPeers hands each accepted conn to the
      // running beacon under the conn's send mutex)
      peers_ = std::vector<Conn>(static_cast<size_t>(size_));
      if (hb_interval_ > 0.0)
        hb_thread_ = std::thread([this] { HeartbeatLoop(); });
      AcceptPeers(port, timeout_sec);
    } else {
      ConnectToRoot(host, port, timeout_sec);
      if (!failed_ && hb_interval_ > 0.0)
        hb_thread_ = std::thread([this] { HeartbeatLoop(); });
    }
  }

  ~TcpTransport() override {
    hb_stop_.store(true);
    if (hb_thread_.joinable()) hb_thread_.join();
    for (auto& peer : peers_)
      if (peer.fd >= 0) ::close(peer.fd);
    if (root_.fd >= 0) ::close(root_.fd);
    if (listen_fd_ >= 0) ::close(listen_fd_);
  }

  int rank() const override { return rank_; }
  int size() const override { return size_; }
  bool failed() const override { return failed_; }

  std::string failure_reason() const override {
    std::lock_guard<std::mutex> lk(reason_mu_);
    return failure_reason_;
  }

  long long heartbeat_misses() const override { return hb_misses_.load(); }

  void Interrupt() override {
    // connections are published before the controller exists and closed
    // only by the destructor, so the fds are stable here
    for (auto& peer : peers_)
      if (peer.fd >= 0) ::shutdown(peer.fd, SHUT_RDWR);
    if (root_.fd >= 0) ::shutdown(root_.fd, SHUT_RDWR);
  }

  std::vector<std::string> GatherRequests(const std::string& mine) override {
    if (failed_) return {};
    if (rank_ == 0) {
      // per-peer reads run on the pool so the cycle latency is the
      // slowest peer, not the sum of all peers (reference analog:
      // ThreadPool use in horovod/common — SURVEY.md §2.1)
      std::vector<std::string> all(size_);
      all[0] = mine;
      std::vector<std::future<bool>> done;
      for (int r = 1; r < size_; ++r) {
        done.push_back(pool_.Submit([this, r, &all] {
          return ReadFrame(&peers_[r], &all[r]);
        }));
      }
      bool ok = true;
      for (auto& f : done) ok = f.get() && ok;
      if (!ok) {
        failed_ = true;
        return {};
      }
      return all;
    }
    if (!WriteFrame(&root_, mine)) failed_ = true;
    return {};
  }

  std::string BcastResponseList(const std::string& payload) override {
    if (failed_) return {};
    if (rank_ == 0) {
      std::vector<std::future<bool>> done;
      for (int r = 1; r < size_; ++r) {
        done.push_back(pool_.Submit([this, r, &payload] {
          return WriteFrame(&peers_[r], payload);
        }));
      }
      bool ok = true;
      for (auto& f : done) ok = f.get() && ok;
      if (!ok) {
        failed_ = true;
        return {};
      }
      return payload;
    }
    std::string out;
    if (!ReadFrame(&root_, &out)) {
      failed_ = true;
      return {};
    }
    return out;
  }

 private:
  // Per-connection steady-state state.  ``mac_key`` is empty in
  // unauthenticated mode (frames travel bare, as before the round-6
  // change); the sequence counters are per-direction so a recorded frame
  // cannot be replayed or reordered within either stream.  ``send_mu``
  // serializes the heartbeat thread against the cycle writer — a frame
  // and a heartbeat must never interleave on the wire.
  struct Conn {
    int fd = -1;
    std::string mac_key;
    uint64_t send_seq = 0;
    uint64_t recv_seq = 0;
    int peer_rank = -1;
    std::unique_ptr<std::mutex> send_mu = std::make_unique<std::mutex>();
  };

  // Length-field sentinel marking a heartbeat frame (real frames are
  // capped at 256 MB, far below this).
  static constexpr uint32_t kHeartbeatFrame = 0xFFFFFFFFu;

  // Parse a seconds knob; a value that is not a number falls back to
  // the default WITH a warning (mirrors common/retry.py env_float) —
  // atof would silently return 0 and turn a typo into "liveness off".
  static double EnvSeconds(const char* name, double dflt) {
    const char* v = std::getenv(name);
    if (v == nullptr || *v == '\0') return dflt;
    char* end = nullptr;
    double parsed = std::strtod(v, &end);
    if (end == v) {
      std::fprintf(stderr,
                   "[WARNING] hvd_tpu_core: %s=%s is not a number; "
                   "using %.1f\n",
                   name, v, dflt);
      return dflt;
    }
    return parsed;
  }

  // The per-connection frame key, bound to BOTH hello challenges so
  // neither side alone controls it and every connection (even a
  // reconnecting same-rank peer) gets a fresh key.
  std::string DeriveFrameKey(const std::string& cw,
                             const std::string& cr) const {
    return secret::HmacSha256(secret_, "frame" + cw + cr);
  }

  void AcceptPeers(int port, double timeout_sec) {
    listen_fd_ = ::socket(AF_INET, SOCK_STREAM, 0);
    int one = 1;
    ::setsockopt(listen_fd_, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
    sockaddr_in addr{};
    addr.sin_family = AF_INET;
    addr.sin_addr.s_addr = INADDR_ANY;
    addr.sin_port = htons(static_cast<uint16_t>(port));
    if (::bind(listen_fd_, reinterpret_cast<sockaddr*>(&addr),
               sizeof(addr)) != 0 ||
        ::listen(listen_fd_, size_) != 0) {
      failed_ = true;
      return;
    }
    auto deadline = Clock::now() +
                    std::chrono::duration_cast<Clock::duration>(
                        std::chrono::duration<double>(timeout_sec));
    for (int accepted = 0; accepted < size_ - 1;) {
      if (Clock::now() > deadline) {
        RecordFailure("rendezvous timed out: only " +
                      std::to_string(accepted) + " of " +
                      std::to_string(size_ - 1) + " peers connected");
        failed_ = true;
        return;
      }
      // poll before accept so the rendezvous deadline is enforced even
      // when a peer never connects (a blocking accept would pin rank 0
      // forever while the other ranks give up in ConnectToRoot)
      pollfd pfd{listen_fd_, POLLIN, 0};
      int ready = ::poll(&pfd, 1, /*ms=*/250);
      if (ready <= 0 || !(pfd.revents & POLLIN)) continue;
      int fd = ::accept(listen_fd_, nullptr, nullptr);
      if (fd < 0) continue;
      SetNoDelay(fd);
      // bounded hello: a connector that stalls mid-handshake (slowloris)
      // must not pin the accept loop past the rendezvous deadline
      SetRecvTimeout(fd, 5.0);
      int32_t peer_rank = -1;
      if (!ReadAll(fd, &peer_rank, 4) || peer_rank <= 0 ||
          peer_rank >= size_) {
        ::close(fd);
        continue;
      }
      uint8_t peer_auth = 0;
      uint8_t my_auth = secret_.empty() ? 0 : 1;
      if (!ReadAll(fd, &peer_auth, 1) || !WriteAll(fd, &my_auth, 1)) {
        ::close(fd);
        continue;
      }
      if ((peer_auth != 0) != (my_auth != 0)) {
        // half-configured job: reject NOW with a clear error instead of
        // one side hanging in a handshake read the other never feeds
        std::fprintf(
            stderr,
            "[ERROR] hvd_tpu_core: auth-mode mismatch on negotiation "
            "hello from rank %d (coordinator %s HVD_TPU_SECRET, peer "
            "%s) — set the same secret on every process\n",
            peer_rank, my_auth ? "has" : "lacks",
            peer_auth ? "has" : "lacks");
        ::close(fd);
        continue;  // keep listening: a lone rogue must not kill the job
      }
      std::string frame_key;
      if (!secret_.empty() && !AuthenticatePeer(fd, peer_rank, &frame_key)) {
        // unauthenticated peer on the negotiation port: reject the
        // connection, keep listening for the real rank (the rogue must
        // not consume the rank slot)
        ::close(fd);
        continue;
      }
      // steady state: reads carry the heartbeat deadline (0 = blocking)
      SetRecvTimeout(fd, hb_timeout_);
      {
        // the beacon thread is already live: publish the conn under its
        // send mutex so the first heartbeat can't race the field writes
        std::lock_guard<std::mutex> lk(*peers_[peer_rank].send_mu);
        peers_[peer_rank].fd = fd;
        peers_[peer_rank].mac_key = frame_key;
        peers_[peer_rank].peer_rank = peer_rank;
      }
      ++accepted;
    }
  }

  // Coordinator side of the mutual handshake; false = reject.
  // Wire: <- rank(4) + flag(1) already read, -> flag(1) already sent;
  // <- Cw(16); -> Cr(16) + HMAC(secret, "coord" + Cw)(32);
  // <- HMAC(secret, "rank" + rank + Cr)(32).
  // On success ``*frame_key`` holds the steady-state MAC key.
  bool AuthenticatePeer(int fd, int32_t peer_rank, std::string* frame_key) {
    std::string cw(16, '\0');
    if (!ReadAll(fd, &cw[0], cw.size())) return false;
    std::string cr;
    if (!secret::RandomChallenge(&cr)) {
      std::fprintf(stderr,
                   "[ERROR] hvd_tpu_core: no entropy source for the "
                   "auth challenge; rejecting peer\n");
      return false;
    }
    std::string my_proof = secret::HmacSha256(secret_, "coord" + cw);
    if (!WriteAll(fd, cr.data(), cr.size()) ||
        !WriteAll(fd, my_proof.data(), my_proof.size()))
      return false;
    std::string proof(32, '\0');
    if (!ReadAll(fd, &proof[0], proof.size())) return false;
    std::string want = secret::HmacSha256(
        secret_, "rank" + std::string(reinterpret_cast<char*>(&peer_rank),
                                      4) + cr);
    if (!secret::MacEqual(proof, want)) return false;
    *frame_key = DeriveFrameKey(cw, cr);
    return true;
  }

  // Worker side of the mutual handshake; false = tear down and fail.
  // On success ``*frame_key`` holds the steady-state MAC key.
  bool AuthenticateToRoot(int fd, std::string* frame_key) {
    std::string cw;
    if (!secret::RandomChallenge(&cw)) {
      std::fprintf(stderr,
                   "[ERROR] hvd_tpu_core: no entropy source for the "
                   "auth challenge; failing the handshake\n");
      return false;
    }
    if (!WriteAll(fd, cw.data(), cw.size())) return false;
    std::string cr(16, '\0'), coord_proof(32, '\0');
    if (!ReadAll(fd, &cr[0], cr.size()) ||
        !ReadAll(fd, &coord_proof[0], coord_proof.size()))
      return false;
    std::string want = secret::HmacSha256(secret_, "coord" + cw);
    if (!secret::MacEqual(coord_proof, want)) return false;  // rogue root
    int32_t my_rank = rank_;
    std::string proof = secret::HmacSha256(
        secret_, "rank" + std::string(reinterpret_cast<char*>(&my_rank),
                                      4) + cr);
    if (!WriteAll(fd, proof.data(), proof.size())) return false;
    *frame_key = DeriveFrameKey(cw, cr);
    return true;
  }

  void ConnectToRoot(const std::string& host, int port, double timeout_sec) {
    auto deadline = Clock::now() +
                    std::chrono::duration_cast<Clock::duration>(
                        std::chrono::duration<double>(timeout_sec));
    // Exponential backoff with full jitter between attempts (mirrors
    // common/retry.py): a whole fleet restarting after a failure must
    // not hammer rank 0's pending listen queue in lockstep — the fixed
    // 100 ms poll this replaces synchronized every worker's retries.
    std::mt19937_64 jitter_rng{std::random_device{}()};
    std::uniform_real_distribution<double> uniform(0.0, 1.0);
    int attempt = 0;
    auto backoff = [&] {
      double cap = std::min(1.0, 0.05 * static_cast<double>(1 << std::min(
          attempt, 10)));
      ++attempt;
      std::this_thread::sleep_for(
          std::chrono::duration<double>(cap * uniform(jitter_rng)));
    };
    while (Clock::now() < deadline) {
      addrinfo hints{}, *res = nullptr;
      hints.ai_family = AF_INET;
      hints.ai_socktype = SOCK_STREAM;
      if (::getaddrinfo(host.c_str(), std::to_string(port).c_str(), &hints,
                        &res) != 0 ||
          res == nullptr) {
        backoff();
        continue;
      }
      int fd = ::socket(res->ai_family, res->ai_socktype, res->ai_protocol);
      if (fd >= 0 && ::connect(fd, res->ai_addr, res->ai_addrlen) == 0) {
        ::freeaddrinfo(res);
        SetNoDelay(fd);
        // bounded handshake on the worker side too: a port-squatter
        // that accepts and then sends nothing must not pin the worker
        // past its rendezvous deadline (mirror of the coordinator's
        // slowloris guard)
        SetRecvTimeout(fd, 5.0);
        int32_t my_rank = rank_;
        uint8_t my_auth = secret_.empty() ? 0 : 1;
        uint8_t root_auth = 0;
        if (WriteAll(fd, &my_rank, 4) && WriteAll(fd, &my_auth, 1) &&
            ReadAll(fd, &root_auth, 1)) {
          if ((root_auth != 0) != (my_auth != 0)) {
            // half-configured job: fail NOW with a clear error — without
            // the flag this worker would block in the handshake until
            // the rendezvous timeout with no hint at the cause
            std::fprintf(
                stderr,
                "[ERROR] hvd_tpu_core: auth-mode mismatch on negotiation "
                "hello (this rank %s HVD_TPU_SECRET, coordinator %s) — "
                "set the same secret on every process\n",
                my_auth ? "has" : "lacks", root_auth ? "has" : "lacks");
            ::close(fd);
            failed_ = true;
            return;
          }
          std::string frame_key;
          if (secret_.empty() || AuthenticateToRoot(fd, &frame_key)) {
            // steady state: heartbeat deadline on reads (0 = blocking)
            SetRecvTimeout(fd, hb_timeout_);
            root_.fd = fd;
            root_.mac_key = frame_key;
            root_.peer_rank = 0;
            return;
          }
        }
        ::close(fd);
        failed_ = true;
        return;
      }
      if (fd >= 0) ::close(fd);
      ::freeaddrinfo(res);
      backoff();
    }
    RecordFailure("rendezvous with the coordinator at " + host + ":" +
                  std::to_string(port) + " timed out");
    failed_ = true;
  }

  static void SetNoDelay(int fd) {
    int one = 1;
    ::setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
  }

  static void SetRecvTimeout(int fd, double sec) {
    timeval tv{};
    tv.tv_sec = static_cast<time_t>(sec);
    tv.tv_usec = static_cast<suseconds_t>((sec - tv.tv_sec) * 1e6);
    ::setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &tv, sizeof(tv));
  }

  // Read outcome: distinguishes the heartbeat deadline expiring (peer
  // alive-but-silent or frozen) from the connection closing (peer died)
  // so the failure reason can name what actually happened.
  enum class IoRc { kOk, kClosed, kTimeout };

  static IoRc ReadAllRc(int fd, void* buf, size_t n) {
    char* p = static_cast<char*>(buf);
    while (n > 0) {
      ssize_t got = ::recv(fd, p, n, 0);
      if (got > 0) {
        p += got;
        n -= static_cast<size_t>(got);
        continue;
      }
      if (got == 0) return IoRc::kClosed;
      if (errno == EINTR) continue;
      if (errno == EAGAIN || errno == EWOULDBLOCK) return IoRc::kTimeout;
      return IoRc::kClosed;
    }
    return IoRc::kOk;
  }

  static bool ReadAll(int fd, void* buf, size_t n) {
    return ReadAllRc(fd, buf, n) == IoRc::kOk;
  }

  static bool WriteAll(int fd, const void* buf, size_t n) {
    const char* p = static_cast<const char*>(buf);
    while (n > 0) {
      ssize_t sent = ::send(fd, p, n, MSG_NOSIGNAL);
      if (sent <= 0) return false;
      p += sent;
      n -= static_cast<size_t>(sent);
    }
    return true;
  }

  // The MAC input: direction byte + LE64 sequence number + payload.
  // Direction is the SENDER's role ('C' = coordinator, 'W' = worker), so
  // a frame echoed back at its author never verifies; the sequence is
  // per-direction monotonic, so capture-and-replay (or reorder) of a
  // validly MAC'd frame fails too.
  static std::string FrameMac(const std::string& key, char dir,
                              uint64_t seq, const std::string& payload) {
    char hdr[9];
    hdr[0] = dir;
    for (int i = 0; i < 8; ++i)
      hdr[1 + i] = static_cast<char>(seq >> (8 * i));
    return secret::HmacSha256(key, std::string(hdr, 9) + payload);
  }

  char SendDir() const { return rank_ == 0 ? 'C' : 'W'; }
  char RecvDir() const { return rank_ == 0 ? 'W' : 'C'; }

  // First failure cause wins (concurrent pool reads can fail together);
  // read by failure_reason() for the named-peer FailAllPending error.
  void RecordFailure(const std::string& why) {
    std::lock_guard<std::mutex> lk(reason_mu_);
    if (failure_reason_.empty()) failure_reason_ = why;
  }

  bool ReadFailed(const Conn* conn, IoRc rc) {
    if (rc == IoRc::kTimeout) {
      hb_misses_.fetch_add(1);
      RecordFailure(
          "peer rank " + std::to_string(conn->peer_rank) +
          " sent nothing (not even heartbeats) for " +
          std::to_string(static_cast<int>(hb_timeout_)) +
          "s — process hung or frozen");
    } else {
      RecordFailure("connection to peer rank " +
                    std::to_string(conn->peer_rank) +
                    " closed (process died or disconnected)");
    }
    return false;
  }

  // Steady-state frame wire: len(4, LE) + payload + MAC(32, authenticated
  // mode only).  A bad length, short read, deadline expiry, or MAC
  // mismatch returns false, which the callers translate into transport
  // failure (FailAllPending on the Python side) — a tampered or injected
  // frame can fail the job but never feed it a forged negotiation
  // payload.  Heartbeat frames (length == kHeartbeatFrame) are consumed
  // transparently: each one proves the peer alive and re-arms the
  // receive deadline.
  bool ReadFrame(Conn* conn, std::string* out) {
    auto act = chaos::Decide("transport.frame.recv");
    if (act == chaos::Action::kRaise) {
      RecordFailure("chaos-injected receive failure");
      return false;
    }
    for (;;) {
      uint32_t len = 0;
      IoRc rc = ReadAllRc(conn->fd, &len, 4);
      if (rc != IoRc::kOk) return ReadFailed(conn, rc);
      if (len == kHeartbeatFrame) continue;  // liveness-only frame
      if (len > (256u << 20)) {
        RecordFailure("oversized frame from peer rank " +
                      std::to_string(conn->peer_rank));
        return false;
      }
      out->resize(len);
      if (len != 0) {
        rc = ReadAllRc(conn->fd, out->data(), len);
        if (rc != IoRc::kOk) return ReadFailed(conn, rc);
      }
      if (act == chaos::Action::kCorrupt) chaos::CorruptPayload(out);
      if (act == chaos::Action::kDrop) {
        // simulated message loss: discard this frame (and its MAC) and
        // wait for the next one — the peers' protocol states now skew,
        // which is exactly the desync the recovery path must survive
        if (!conn->mac_key.empty()) {
          std::string mac(32, '\0');
          rc = ReadAllRc(conn->fd, &mac[0], mac.size());
          if (rc != IoRc::kOk) return ReadFailed(conn, rc);
          ++conn->recv_seq;
        }
        act = chaos::Action::kNone;
        continue;
      }
      if (conn->mac_key.empty()) return true;
      std::string mac(32, '\0');
      rc = ReadAllRc(conn->fd, &mac[0], mac.size());
      if (rc != IoRc::kOk) return ReadFailed(conn, rc);
      std::string want =
          FrameMac(conn->mac_key, RecvDir(), conn->recv_seq, *out);
      if (!secret::MacEqual(mac, want)) {
        std::fprintf(stderr,
                     "[ERROR] hvd_tpu_core: bad MAC on steady-state "
                     "negotiation frame (seq %llu) — tampered or injected "
                     "traffic on the control channel; failing the "
                     "transport\n",
                     static_cast<unsigned long long>(conn->recv_seq));
        RecordFailure(
            "bad MAC on a negotiation frame from peer rank " +
            std::to_string(conn->peer_rank) +
            " (tampered or corrupted control traffic)");
        return false;
      }
      ++conn->recv_seq;
      return true;
    }
  }

  bool WriteFrame(Conn* conn, const std::string& payload) {
    auto act = chaos::Decide("transport.frame.send");
    if (act == chaos::Action::kRaise) {
      RecordFailure("chaos-injected send failure");
      return false;
    }
    if (act == chaos::Action::kDrop) return true;  // simulated loss
    // MAC over the ORIGINAL payload, then (under chaos corrupt) flip one
    // bit of what actually travels: the receiver sees a genuine
    // corruption — MAC mismatch in authenticated mode, a garbled
    // encoding otherwise — and must take the clean failure path.
    const std::string* body = &payload;
    std::string corrupted;
    if (act == chaos::Action::kCorrupt) {
      if (payload.empty() && conn->mac_key.empty()) {
        // nothing to flip and no MAC to break: inject as a transport
        // failure — a fault the engine counted must actually happen
        // (mirrors the Python engine's corrupt-without-payload rule)
        RecordFailure(
            "chaos-injected corruption (empty unauthenticated frame)");
        return false;
      }
      corrupted = payload;
      if (!corrupted.empty()) {
        chaos::CorruptPayload(&corrupted);
        body = &corrupted;
      }
    }
    std::lock_guard<std::mutex> lk(*conn->send_mu);
    uint32_t len = static_cast<uint32_t>(body->size());
    if (!WriteAll(conn->fd, &len, 4)) return false;
    if (!body->empty() && !WriteAll(conn->fd, body->data(), body->size()))
      return false;
    if (conn->mac_key.empty()) return true;
    std::string mac =
        FrameMac(conn->mac_key, SendDir(), conn->send_seq, payload);
    if (act == chaos::Action::kCorrupt && body == &payload)
      mac[0] ^= 0x01;  // empty payload: corrupt the MAC instead
    if (!WriteAll(conn->fd, mac.data(), mac.size())) return false;
    ++conn->send_seq;
    return true;
  }

  // Periodic liveness beacon, independent of the negotiation loop: a
  // rank blocked for minutes inside the exec callback (first-touch XLA
  // compile) still heartbeats; a frozen process does not.
  void HeartbeatLoop() {
    auto last = Clock::now();
    while (!hb_stop_.load()) {
      std::this_thread::sleep_for(std::chrono::milliseconds(50));
      if (std::chrono::duration<double>(Clock::now() - last).count() <
          hb_interval_)
        continue;
      last = Clock::now();
      if (rank_ == 0) {
        for (auto& peer : peers_) SendHeartbeat(&peer);
      } else {
        SendHeartbeat(&root_);
      }
    }
  }

  void SendHeartbeat(Conn* conn) {
    // fd checked under the send mutex: on rank 0 this thread runs while
    // AcceptPeers is still publishing connections
    std::lock_guard<std::mutex> lk(*conn->send_mu);
    if (conn->fd < 0) return;
    uint32_t magic = kHeartbeatFrame;
    // failures are ignored: the cycle path owns failure detection and
    // reporting; a dead fd just stops beaconing
    WriteAll(conn->fd, &magic, 4);
  }

  int rank_;
  int size_;
  std::string secret_;
  int listen_fd_ = -1;
  Conn root_;
  std::vector<Conn> peers_;
  bool failed_ = false;
  // liveness (see constructor comment)
  double hb_interval_ = 0.0;
  double hb_timeout_ = 0.0;
  std::thread hb_thread_;
  std::atomic<bool> hb_stop_{false};
  std::atomic<long long> hb_misses_{0};
  mutable std::mutex reason_mu_;
  std::string failure_reason_;
  // IO pool sized for a per-host controller star (reference default: 4)
  ThreadPool pool_{4};
};

}  // namespace hvdtpu
