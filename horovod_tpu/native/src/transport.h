// Negotiation transport: how ranks exchange Request/Response payloads.
//
// Reference parity: the controller transports of SURVEY.md §2.1 —
// MPIController (MPI_Gatherv/MPI_Bcast) and GlooController (gloo gather /
// HTTP store).  TPU-native mapping (§5.8): the in-process world needs no
// transport at all (LoopbackTransport), and multi-process worlds talk over
// a host-side TCP star rooted at rank 0 (tcp_transport.h) — the JAX
// coordination-service analog for the C++ side, bootstrapped by the
// tpurun launcher the same way horovodrun exports the Gloo rendezvous
// address.
#pragma once

#include <string>
#include <vector>

namespace hvdtpu {

class Transport {
 public:
  virtual ~Transport() = default;
  virtual int rank() const = 0;
  virtual int size() const = 0;

  // Rank 0 receives every rank's encoded request list (index == rank);
  // other ranks send `mine` and receive an empty vector.
  // Reference: MPIController::SendReadyTensors / RecvReadyTensors.
  virtual std::vector<std::string> GatherRequests(const std::string& mine) = 0;

  // Rank 0 broadcasts `payload`; every rank returns the broadcast value.
  // Reference: MPIController::SendFinalTensors / RecvFinalTensors.
  virtual std::string BcastResponseList(const std::string& payload) = 0;

  // True when the transport failed mid-collective => HorovodInternalError
  // on the Python side (elastic recovery hook).
  virtual bool failed() const { return false; }

  // Human-readable cause of the failure, naming the peer when known
  // ("peer rank 2 missed heartbeats for 30s") — surfaced verbatim in the
  // FailAllPending error so operators see WHICH process to look at
  // instead of a generic "transport failed".  Empty when not failed or
  // the cause is unknown.
  virtual std::string failure_reason() const { return ""; }

  // Heartbeat read-deadline expiries observed (TCP transport only).
  virtual long long heartbeat_misses() const { return 0; }

  // Shutdown: make every thread blocked in (or later entering) a frame
  // read or write on this rank's connections fail as on a closed
  // connection.  The cycle is lockstep and has no goodbye message, so a
  // loop that leaves it would otherwise keep its peers — and a loop still
  // inside it keep itself — blocked until some process DIES; exit must
  // not depend on the order in which processes manage to die.
  virtual void Interrupt() {}
};

// Single-process world: negotiation degenerates to identity.
class LoopbackTransport : public Transport {
 public:
  int rank() const override { return 0; }
  int size() const override { return 1; }
  std::vector<std::string> GatherRequests(const std::string& mine) override {
    return {mine};
  }
  std::string BcastResponseList(const std::string& payload) override {
    return payload;
  }
};

}  // namespace hvdtpu
