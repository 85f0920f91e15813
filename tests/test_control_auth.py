"""Control-plane authentication + ssh fan-out tests.

Reference analog: the HMAC-signed driver/task RPC of
horovod/runner/common/util/{secret,network}.py and the mocked-ssh
launcher tests of test/single/test_run.py (SURVEY.md §2.4, §4).  Covers:

  * wire_auth sign/verify round-trip and tamper rejection;
  * the elastic driver dropping unsigned/forged control messages;
  * the native TCP star rejecting a secret-less rogue peer while the
    authenticated fleet still forms and completes;
  * ``_launch_ssh`` driven end-to-end through a PATH-shimmed ``ssh``
    that execs locally: arg construction, env plumbing (incl. the job
    secret), rank-0 host addressing, and exit-code lockstep reaping.
"""

import json
import os
import socket
import stat
import struct
import subprocess
import sys
import threading
import time

import pytest

import horovod_tpu.runner.launch as launch
from horovod_tpu.common import wire_auth
from envguards import native_child_env, native_lib_path

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "integration", "launcher_worker.py")
NATIVE_LIB = native_lib_path(REPO)


# -- wire_auth unit ----------------------------------------------------------


def test_sign_verify_roundtrip():
    secret = wire_auth.make_secret()
    msg = {"type": "rendezvous", "worker_id": 3}
    signed = wire_auth.sign_message(msg, secret)
    assert "hmac" in signed
    out = wire_auth.verify_message(signed, secret)
    assert out == msg


def test_verify_rejects_tamper_and_missing():
    secret = wire_auth.make_secret()
    signed = wire_auth.sign_message({"type": "assignment", "rank": 0},
                                    secret)
    tampered = dict(signed)
    tampered["rank"] = 1
    assert wire_auth.verify_message(tampered, secret) is None
    assert wire_auth.verify_message({"type": "assignment"}, secret) is None
    wrong = wire_auth.sign_message({"type": "assignment", "rank": 0},
                                   wire_auth.make_secret())
    assert wire_auth.verify_message(wrong, secret) is None


def test_no_secret_passthrough():
    msg = {"type": "register"}
    assert wire_auth.sign_message(msg, None) == msg
    assert wire_auth.verify_message(msg, None) == msg


# -- elastic driver rejects forged messages ---------------------------------


def test_elastic_driver_drops_unsigned_register(monkeypatch):
    from horovod_tpu.runner.elastic_driver import ElasticDriver

    monkeypatch.setenv(wire_auth.SECRET_ENV, wire_auth.make_secret())
    driver = ElasticDriver(command=["true"], discovery=None, min_np=1)
    host, port = driver._start_server()
    try:
        # unsigned register: the driver must close the socket unacted
        s = socket.create_connection(("127.0.0.1", port), timeout=10)
        s.sendall((json.dumps(
            {"type": "register", "worker_id": 0}) + "\n").encode())
        s.settimeout(10)
        assert s.recv(1) == b""  # server closed on us
        s.close()
        assert driver._notify_socks == {}

        # signed register: accepted and retained as the notify channel
        s2 = socket.create_connection(("127.0.0.1", port), timeout=10)
        s2.sendall((json.dumps(wire_auth.sign_message(
            {"type": "register", "worker_id": 0},
            wire_auth.job_secret())) + "\n").encode())
        deadline = time.time() + 10
        while time.time() < deadline and 0 not in driver._notify_socks:
            time.sleep(0.05)
        assert 0 in driver._notify_socks
        s2.close()
    finally:
        driver._shutdown = True
        driver._server.close()


# -- auth-mode mismatch fails fast ------------------------------------------


def test_auth_mode_mismatch_fails_fast():
    """A secret-carrying worker dialing a secret-less coordinator must
    reject the hello IMMEDIATELY with a clear error (the auth-mode flag
    byte), not hang until the rendezvous timeout.  Drives the native
    TcpTransport directly over ctypes against a fake coordinator socket —
    no jax, no fleet."""
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]
    lib_path = NATIVE_LIB
    if not os.path.exists(lib_path):
        pytest.skip("native core not built")
    code = f"""
import ctypes, sys, time
lib = ctypes.CDLL({lib_path!r})
lib.hvdtpu_init.restype = ctypes.c_int
lib.hvdtpu_init.argtypes = [
    ctypes.c_int, ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
    ctypes.c_double, ctypes.c_longlong, ctypes.c_int, ctypes.c_char_p,
    ctypes.c_double, ctypes.c_double, ctypes.c_int, ctypes.c_char_p,
]
t0 = time.time()
rc = lib.hvdtpu_init(1, 2, b"127.0.0.1", {port}, 1.0, 1 << 20, 16, b"",
                     0.0, 0.0, 0, b"")
elapsed = time.time() - t0
print("RC", rc, "ELAPSED", elapsed, flush=True)
sys.exit(0 if rc != 0 and elapsed < 30 else 1)
"""
    env = native_child_env()
    env["HVD_TPU_SECRET"] = wire_auth.make_secret()
    proc = subprocess.Popen(
        [sys.executable, "-c", code], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    try:
        srv.settimeout(30)
        conn, _ = srv.accept()
        conn.settimeout(30)
        hello = b""
        while len(hello) < 5:  # rank(4) + auth flag(1)
            chunk = conn.recv(5 - len(hello))
            if not chunk:
                break
            hello += chunk
        assert struct.unpack("<i", hello[:4])[0] == 1
        assert hello[4:5] == b"\x01"  # worker advertises auth
        conn.sendall(b"\x00")         # coordinator: no secret
        out, err = proc.communicate(timeout=60)
        assert proc.returncode == 0, (out, err)
        assert "auth-mode mismatch" in err
        conn.close()
    finally:
        srv.close()
        if proc.poll() is None:
            proc.kill()
            proc.wait()


# -- steady-state frame MAC: tamper rejection --------------------------------


def _hmac(key: bytes, msg: bytes) -> bytes:
    import hashlib
    import hmac as _hmac_mod

    return _hmac_mod.new(key, msg, hashlib.sha256).digest()


def _frame_mac(key: bytes, direction: bytes, seq: int,
               payload: bytes) -> bytes:
    return _hmac(key, direction + struct.pack("<Q", seq) + payload)


def _recv_exact(conn, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = conn.recv(n - len(buf))
        if not chunk:
            raise ConnectionError(f"EOF after {len(buf)}/{n} bytes")
        buf += chunk
    return buf


def test_steady_state_frame_tamper_rejected():
    """Round-5 ADVICE closure: frames AFTER the authenticated hello are
    MAC'd under a per-connection key derived from the challenge exchange.
    A fake coordinator that passes the full handshake (it knows the
    secret) but then corrupts one steady-state frame's MAC must kill the
    worker's transport — while a correctly MAC'd frame keeps it alive
    (proving the rejection is the tamper check, not protocol drift).
    Drives the native TcpTransport over ctypes; no jax, no fleet."""
    secret = wire_auth.make_secret()
    skey = secret.encode()
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]
    lib_path = NATIVE_LIB
    if not os.path.exists(lib_path):
        pytest.skip("native core not built")
    code = f"""
import ctypes, sys, time
lib = ctypes.CDLL({lib_path!r})
lib.hvdtpu_init.restype = ctypes.c_int
lib.hvdtpu_init.argtypes = [
    ctypes.c_int, ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
    ctypes.c_double, ctypes.c_longlong, ctypes.c_int, ctypes.c_char_p,
    ctypes.c_double, ctypes.c_double, ctypes.c_int, ctypes.c_char_p,
]
rc = lib.hvdtpu_init(1, 2, b"127.0.0.1", {port}, 5.0, 1 << 20, 16, b"",
                     0.0, 0.0, 0, b"")
print("INIT", rc, flush=True)
if rc != 0:
    sys.exit(2)
deadline = time.time() + 60
while time.time() < deadline:
    if lib.hvdtpu_loop_dead():
        print("LOOP_DEAD", flush=True)
        lib.hvdtpu_shutdown()  # join the (dead) background loop cleanly
        sys.exit(0)
    time.sleep(0.05)
print("LOOP_STILL_ALIVE", flush=True)
sys.exit(3)
"""
    env = native_child_env()
    env["HVD_TPU_SECRET"] = secret
    proc = subprocess.Popen(
        [sys.executable, "-c", code], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    try:
        srv.settimeout(30)
        conn, _ = srv.accept()
        conn.settimeout(30)
        # ---- hello + mutual challenge-response (coordinator role) ----
        hello = _recv_exact(conn, 5)
        assert struct.unpack("<i", hello[:4])[0] == 1
        assert hello[4:5] == b"\x01"
        conn.sendall(b"\x01")  # we hold the secret too
        cw = _recv_exact(conn, 16)
        cr = os.urandom(16)
        conn.sendall(cr + _hmac(skey, b"coord" + cw))
        proof = _recv_exact(conn, 32)
        assert proof == _hmac(
            skey, b"rank" + struct.pack("<i", 1) + cr
        ), "worker's hello proof diverged from the documented wire"
        frame_key = _hmac(skey, b"frame" + cw + cr)

        # ---- steady state: worker sends one MAC'd request per cycle ----
        def read_worker_frame(expect_seq):
            (length,) = struct.unpack("<I", _recv_exact(conn, 4))
            payload = _recv_exact(conn, length)
            mac = _recv_exact(conn, 32)
            assert mac == _frame_mac(
                frame_key, b"W", expect_seq, payload
            ), "worker frame MAC diverged from the documented construction"
            return payload

        read_worker_frame(0)
        # control: a correctly MAC'd (empty) response keeps the loop alive
        conn.sendall(struct.pack("<I", 0)
                     + _frame_mac(frame_key, b"C", 0, b""))
        read_worker_frame(1)  # next cycle arrives => transport survived
        assert proc.poll() is None

        # tamper: same frame, one MAC bit flipped => transport must die
        bad = bytearray(_frame_mac(frame_key, b"C", 1, b""))
        bad[0] ^= 0x01
        conn.sendall(struct.pack("<I", 0) + bytes(bad))

        out, err = proc.communicate(timeout=60)
        assert proc.returncode == 0, (out, err)
        assert "LOOP_DEAD" in out
        assert "bad MAC" in err
        conn.close()
    finally:
        srv.close()
        if proc.poll() is None:
            proc.kill()
            proc.wait()


def test_replayed_frame_rejected():
    """A validly MAC'd frame captured and re-sent must fail: the MAC is
    bound to the per-direction sequence number."""
    secret = wire_auth.make_secret()
    skey = secret.encode()
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]
    lib_path = NATIVE_LIB
    if not os.path.exists(lib_path):
        pytest.skip("native core not built")
    code = f"""
import ctypes, sys, time
lib = ctypes.CDLL({lib_path!r})
lib.hvdtpu_init.restype = ctypes.c_int
lib.hvdtpu_init.argtypes = [
    ctypes.c_int, ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
    ctypes.c_double, ctypes.c_longlong, ctypes.c_int, ctypes.c_char_p,
    ctypes.c_double, ctypes.c_double, ctypes.c_int, ctypes.c_char_p,
]
rc = lib.hvdtpu_init(1, 2, b"127.0.0.1", {port}, 5.0, 1 << 20, 16, b"",
                     0.0, 0.0, 0, b"")
if rc != 0:
    sys.exit(2)
deadline = time.time() + 60
while time.time() < deadline:
    if lib.hvdtpu_loop_dead():
        lib.hvdtpu_shutdown()  # join the (dead) background loop cleanly
        sys.exit(0)
    time.sleep(0.05)
sys.exit(3)
"""
    env = native_child_env()
    env["HVD_TPU_SECRET"] = secret
    proc = subprocess.Popen(
        [sys.executable, "-c", code], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    try:
        srv.settimeout(30)
        conn, _ = srv.accept()
        conn.settimeout(30)
        _recv_exact(conn, 5)
        conn.sendall(b"\x01")
        cw = _recv_exact(conn, 16)
        cr = os.urandom(16)
        conn.sendall(cr + _hmac(skey, b"coord" + cw))
        _recv_exact(conn, 32)
        frame_key = _hmac(skey, b"frame" + cw + cr)

        def skip_worker_frame():
            (length,) = struct.unpack("<I", _recv_exact(conn, 4))
            _recv_exact(conn, length + 32)

        skip_worker_frame()
        first = struct.pack("<I", 0) + _frame_mac(frame_key, b"C", 0, b"")
        conn.sendall(first)          # valid at seq 0
        skip_worker_frame()
        conn.sendall(first)          # replay at seq 1: stale MAC
        out, err = proc.communicate(timeout=60)
        assert proc.returncode == 0, (out, err)
        assert "bad MAC" in err
        conn.close()
    finally:
        srv.close()
        if proc.poll() is None:
            proc.kill()
            proc.wait()


# -- native star rejects rogue peers ----------------------------------------


@pytest.mark.integration
def test_native_star_rejects_secretless_peer():
    """A peer without the job secret must be rejected by rank 0's accept
    loop WITHOUT consuming the rank slot: the rogue sees EOF after its
    bad proof, and the authenticated 2-proc job still completes.

    Rank 0 stops accepting once its one real peer is in, and both ranks
    reach the negotiation port together (jax.distributed.initialize is a
    barrier), so a rogue racing the real rank 1 loses almost always.  The
    order is therefore forced: rank 1 is pointed at a relay port that
    only starts listening after the rogue has seen its rejection."""
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    secret = wire_auth.make_secret()

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    coord_port, native_port = free_port(), free_port()
    relay = socket.socket()
    relay.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    relay.bind(("127.0.0.1", 0))     # bound, NOT listening: refuses
    relay_port = relay.getsockname()[1]

    def pump(src, dst):
        try:
            while True:
                chunk = src.recv(65536)
                if not chunk:
                    break
                dst.sendall(chunk)
        except OSError:
            pass
        finally:
            for sock in (src, dst):
                try:
                    sock.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass

    def relay_rank1():
        relay.listen(1)
        relay.settimeout(120)
        try:
            down, _ = relay.accept()
            up = socket.create_connection(("127.0.0.1", native_port))
        except OSError:
            return
        threading.Thread(target=pump, args=(up, down), daemon=True).start()
        pump(down, up)

    procs = []
    try:
        for rank in range(2):
            wenv = dict(env)
            wenv.update({
                "HVD_TPU_COORDINATOR": f"127.0.0.1:{coord_port}",
                "HVD_TPU_NATIVE_PORT": str(
                    native_port if rank == 0 else relay_port),
                "HVD_TPU_NUM_PROCESSES": "2",
                "HVD_TPU_PROCESS_ID": str(rank),
                "HVD_TPU_LOCAL_RANK": str(rank),
                "HVD_TPU_LOCAL_SIZE": "2",
                "HVD_TPU_SECRET": secret,
            })
            procs.append(subprocess.Popen(
                [sys.executable, WORKER, "2"], env=wenv, cwd=REPO,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            ))

        # rogue: connect to the negotiation port as "rank 1" with a
        # garbage proof; must observe rejection (EOF), not admission
        rejected = False
        deadline = time.time() + 120
        while not rejected and time.time() < deadline:
            try:
                s = socket.create_connection(
                    ("127.0.0.1", native_port), timeout=1)
            except OSError:
                time.sleep(0.1)
                continue
            try:
                s.settimeout(10)
                s.sendall(struct.pack("<i", 1))       # claim rank 1
                s.sendall(b"\x01")                    # auth-mode flag: yes
                s.sendall(b"\x00" * 16)               # challenge Cw
                hdr = b""
                while len(hdr) < 49:                  # flag + Cr + proof
                    chunk = s.recv(49 - len(hdr))
                    if not chunk:
                        break
                    hdr += chunk
                if len(hdr) == 49:
                    assert hdr[0:1] == b"\x01"        # coord is secured
                    s.sendall(b"\x00" * 32)           # forged proof
                    if s.recv(1) == b"":
                        rejected = True
            except OSError:
                pass  # server tore the socket down mid-handshake: also
                # a rejection, but retry for the clean EOF observation
            finally:
                s.close()
            time.sleep(0.1)
        assert rejected, "rogue peer was never cleanly rejected"
        threading.Thread(target=relay_rank1, daemon=True).start()

        for p in procs:
            out, err = p.communicate(timeout=240)
            assert p.returncode == 0, (out, err)
    finally:
        relay.close()
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()


# -- fake-ssh launch path ----------------------------------------------------


_FAKE_SSH = """#!/bin/bash
# PATH-shimmed ssh (reference technique: mocked ssh in test/single/
# test_run.py): consume ssh flags, log host+command, exec locally.
args=()
while [[ $# -gt 0 ]]; do
  case "$1" in
    -o) shift 2;;
    -p) shift 2;;
    *) args+=("$1"); shift;;
  esac
done
host="${args[0]}"
cmd="${args[1]}"
printf '%s\\t%s\\n' "$host" "$cmd" >> "$FAKE_SSH_LOG"
exec bash -c "$cmd"
"""


@pytest.fixture
def fake_ssh(tmp_path, monkeypatch):
    bindir = tmp_path / "bin"
    bindir.mkdir()
    shim = bindir / "ssh"
    shim.write_text(_FAKE_SSH)
    shim.chmod(shim.stat().st_mode | stat.S_IEXEC)
    log = tmp_path / "ssh.log"
    log.write_text("")
    monkeypatch.setenv("PATH", f"{bindir}:{os.environ['PATH']}")
    monkeypatch.setenv("FAKE_SSH_LOG", str(log))
    return log


@pytest.mark.integration
def test_launch_ssh_end_to_end(fake_ssh, monkeypatch):
    """_launch_ssh over two non-local 'hosts' (loopback aliases), driven
    through the shim: collectives must pass on both ranks, the secret and
    coordination env must travel in the remote command line, and rank 0
    must be addressed at the first host."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    knob_env = {
        "PYTHONPATH": REPO,
        "JAX_PLATFORMS": "cpu",
        "TF_CPP_MIN_LOG_LEVEL": "3",
        "LAUNCHER_WORKER_MULTIHOST": "1",
    }
    hosts = [("127.0.1.1", 1), ("127.0.2.1", 1)]
    rc = launch._launch_ssh(
        [sys.executable, WORKER, "2"], hosts, 2, knob_env,
        ssh_port=None, verbose=True, disable_native=False,
    )
    assert rc == 0
    lines = [ln for ln in fake_ssh.read_text().splitlines() if ln]
    assert len(lines) == 2
    # the two shims run concurrently and append in either order: pin which
    # host got which rank, not which line was written first
    placed = {ln.split("\t")[0]: int(
        ln.split("HVD_TPU_PROCESS_ID=", 1)[1].split()[0]) for ln in lines}
    assert placed == {"127.0.1.1": 0, "127.0.2.1": 1}
    for ln in lines:
        cmd = ln.split("\t", 1)[1]
        # env plumbing: coordinator on the FIRST host and the full
        # coordination set exported into the remote command — but the
        # secret must NOT be on the argv (world-readable cmdline); it
        # arrives via ssh stdin through the read/export preamble
        assert "HVD_TPU_COORDINATOR=127.0.1.1:" in cmd
        assert "HVD_TPU_SECRET=" not in cmd
        assert "IFS= read -r HVD_TPU_SECRET" in cmd
        assert "HVD_TPU_NUM_PROCESSES=2" in cmd
        assert f"cd {os.getcwd()}" in cmd


@pytest.mark.integration
def test_launch_ssh_lockstep_reap(fake_ssh):
    """First nonzero exit must reap the remaining remote workers
    (monitor_lockstep on the ssh path): rank 1 exits 7 immediately while
    rank 0 would sleep for a minute — the launch must return 7 fast."""
    prog = ("import os,sys,time; "
            "sys.exit(7) if os.environ['HVD_TPU_PROCESS_ID']=='1' "
            "else time.sleep(60)")
    t0 = time.time()
    rc = launch._launch_ssh(
        [sys.executable, "-c", prog],
        [("127.0.1.1", 1), ("127.0.2.1", 1)], 2, {},
        ssh_port=None, verbose=False, disable_native=False,
    )
    assert rc == 7
    assert time.time() - t0 < 30
