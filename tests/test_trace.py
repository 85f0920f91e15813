"""Tests for horovod_tpu.trace — the span recorder, Chrome export,
/trace control endpoint, cross-rank merge, flight recorder, and the
analysis ``trace`` pass (ISSUE 15); the device names, their reducer
(``trace/device.py``) and the entry path's two sites (ISSUE 24).

The endpoint tests bind an ephemeral port explicitly (tier-1 never
binds a port outside these tests — the exposition opt-in discipline
from test_metrics.py).
"""

import json
import logging
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from horovod_tpu import trace
from horovod_tpu.metrics import exposition
from horovod_tpu.metrics.registry import MetricsRegistry
from horovod_tpu.trace import export as trace_export
from horovod_tpu.trace import device as trace_device
from horovod_tpu.trace import flight


@pytest.fixture(autouse=True)
def _tracing_on():
    """Every test starts (and leaves) the recorder enabled — the
    process default."""
    trace.configure(enabled=True)
    yield
    trace.configure(enabled=True)


# -- recorder core -----------------------------------------------------------


def test_span_event_add_span_record():
    t0 = trace.now()
    with trace.span("train.step", step=7):
        time.sleep(0.002)
    trace.event("chaos.inject", site="elastic.commit", action="kill")
    t1 = trace.now()  # read once: two reads are >1 us apart under load
    trace.add_span("serve.queued", t1 - 0.25, t1, rid=987654)
    # the retroactive queued span STARTS before t0 — widen the window;
    # other suites' engines may have recorded at these sites too, so
    # select THIS test's records by their args
    recs = trace.snapshot(since=t0 - 0.5)
    step = [r for r in recs if r[0] == "train.step"
            and r[3] == {"step": 7}]
    assert step and step[0][2] >= 0.002 and step[0][4]  # dur + tid
    inject = [r for r in recs if r[0] == "chaos.inject"
              and (r[3] or {}).get("site") == "elastic.commit"]
    assert inject and inject[-1][2] is None  # instant: no duration
    queued = [r for r in recs if r[0] == "serve.queued"
              and (r[3] or {}).get("rid") == 987654]
    assert queued and abs(queued[0][2] - 0.25) < 1e-6


def test_disabled_recorder_records_nothing():
    trace.configure(enabled=False)
    t0 = trace.now()
    with trace.span("train.step", step=1):
        pass
    trace.event("serve.finish", rid=0)
    trace.add_span("serve.queued", t0, trace.now())
    assert trace.snapshot(since=t0) == []
    trace.configure(enabled=True)
    with trace.span("train.step", step=2):
        pass
    assert len(trace.snapshot(since=t0)) == 1


def test_ring_is_bounded_and_keeps_newest():
    r = trace._Ring(8, "t")
    for i in range(20):
        r.append(("s", float(i), 0.0, None))
    recs = r.records()
    assert len(recs) == 8
    assert [rec[1] for rec in recs] == [float(i) for i in range(12, 20)]


def test_main_ring_survives_worker_thread_churn():
    """Regression: ring-registry eviction must retire DEAD threads'
    rings only — 100 short-lived recording threads once evicted the
    main thread's ring, silently losing every later training span."""
    def rec():
        with trace.span("serve.step", kind="decode"):
            pass

    before = len(trace._rings)
    for _ in range(100):
        t = threading.Thread(target=rec)
        t.start()
        t.join()
    t0 = trace.now()
    trace.event("chaos.inject", site="elastic.commit", action="kill")
    assert any(r[0] == "chaos.inject" for r in trace.snapshot(since=t0))
    # dead rings are BOUNDED: the newest 64 are always kept (a
    # just-dead thread's final spans are flight-recorder evidence) and
    # older dead rings retire, so 100 churned threads add at most 64 —
    # while alive threads' rings (other tests may leak parked ones)
    # are never evicted at any age
    assert len(trace._rings) <= before + 67


def test_profiler_span_unifies_into_recorder():
    """The controller's spans: one ``trace.span`` puts the ring record
    at the catalogued site and carries the timeline's name into an
    XPlane capture (there is no second emitter)."""
    from horovod_tpu.native import controller

    t0 = trace.now()
    with trace.span("collective.enqueue",
                    _xname=controller._xname("grad_3", "ENQUEUE"),
                    name="grad_3") as sp:
        assert sp.xname == "hvd_tpu::grad_3::ENQUEUE"
    with trace.span("collective.exec",
                    _xname=controller._xname("ALLREDUCE", "XLA_COMM"),
                    name="ALLREDUCE"):
        pass
    sites = {r[0]: r[3] for r in trace.snapshot(since=t0)}
    assert sites.get("collective.enqueue") == {"name": "grad_3"}
    assert sites.get("collective.exec") == {"name": "ALLREDUCE"}


def test_span_set_adds_args_known_at_the_end():
    t0 = trace.now()
    with trace.span("train.create_state", params=3) as sp:
        sp.set(compiles=2)
    (rec,) = [r for r in trace.snapshot(since=t0)
              if r[0] == "train.create_state"]
    assert rec[3] == {"params": 3, "compiles": 2}


def test_step_annotation_args_reach_the_bridge_not_the_ring(monkeypatch):
    made = []

    class FakeAnnotation:
        def __init__(self, name, **kwargs):
            made.append((name, kwargs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace, "_annotation_cls", lambda: FakeAnnotation)
    t0 = trace.now()
    with trace.span("train.step", _xargs={"_r": 1, "step_num": 41},
                    step=41, epoch=0):
        pass
    assert made == [("hvd_tpu::train.step", {"_r": 1, "step_num": 41})]
    (rec,) = [r for r in trace.snapshot(since=t0) if r[0] == "train.step"]
    assert rec[3] == {"step": 41, "epoch": 0}
    # HVD_TPU_TRACE=0 with an explicit name keeps the annotation
    trace.configure(enabled=False)
    trace.span("train.step", _xname="x", _xargs={"step_num": 1})
    assert made[-1] == ("x", {"step_num": 1})


def test_trace_context_ids_are_unique():
    ids = {trace.new_trace_id() for _ in range(100)}
    assert len(ids) == 100


# -- chrome export -----------------------------------------------------------


def _assert_valid_chrome(doc):
    assert isinstance(doc["traceEvents"], list)
    for e in doc["traceEvents"]:
        assert isinstance(e["name"], str) and "ph" in e
        if e["ph"] == "X":
            assert "ts" in e and "dur" in e
        if e["ph"] == "i":
            assert "ts" in e
    json.dumps(doc)  # must be serializable as-is


def test_chrome_trace_export_shape():
    t0 = trace.now()
    with trace.span("serve.step", kind="decode", batch=2, rids=[0, 1]):
        pass
    trace.event("serve.finish", rid=0, tokens=3)
    doc = trace_export.chrome_trace(since=t0, pid=5)
    _assert_valid_chrome(doc)
    names = [e["name"] for e in doc["traceEvents"]]
    assert "process_name" in names and "thread_name" in names
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert spans and all(e["pid"] == 5 for e in spans)
    # timestamps are epoch microseconds (merge axis)
    assert abs(spans[0]["ts"] / 1e6 - time.time()) < 60


def test_write_dump_roundtrip(tmp_path):
    with trace.span("train.step", step=1):
        pass
    path = trace_export.write_dump(str(tmp_path / "rank0.json"))
    with open(path) as f:
        doc = json.load(f)
    _assert_valid_chrome(doc)
    assert doc["metadata"]["format"].startswith("horovod_tpu.trace/")


# -- cross-rank merge --------------------------------------------------------


def _synthetic_rank_dump(rank, clock_skew_us, steps=(1, 2, 3)):
    events = []
    for s in steps:
        events.append({"name": "train.step", "ph": "X", "pid": 0, "tid": 1,
                       "ts": 1e12 + s * 1e5 + clock_skew_us,
                       "dur": 5e4, "args": {"step": s}})
    events.append({"name": "serve.finish", "ph": "i", "pid": 0, "tid": 1,
                   "ts": 1e12 + clock_skew_us, "args": {"rid": rank}})
    return {"traceEvents": events, "metadata": {"rank": rank}}


def test_merge_ranks_step_boundary_alignment():
    a = _synthetic_rank_dump(0, 0.0)
    b = _synthetic_rank_dump(1, 7.5e6)  # 7.5 s of wall-clock skew
    merged = trace_export.merge_ranks([a, b])
    assert merged["metadata"]["ranks"] == [0, 1]
    off = merged["metadata"]["clock_offsets_us"]["1"]
    assert abs(off + 7.5e6) < 1.0  # skew recovered from step anchors
    starts = {}
    for e in merged["traceEvents"]:
        if e["name"] == "train.step":
            starts.setdefault(e["args"]["step"], []).append(
                (e["pid"], e["ts"]))
    for step, pairs in starts.items():
        ts = {pid: t for pid, t in pairs}
        assert abs(ts[0] - ts[1]) < 1.0  # aligned after the shift
    # non-step events shifted by the same offset (pid stamped too)
    fins = [e for e in merged["traceEvents"] if e["name"] == "serve.finish"]
    assert {e["pid"] for e in fins} == {0, 1}


def test_merge_ranks_without_common_steps_merges_raw():
    a = _synthetic_rank_dump(0, 0.0, steps=(1, 2))
    b = _synthetic_rank_dump(1, 123.0, steps=(8, 9))
    merged = trace_export.merge_ranks([a, b])
    assert merged["metadata"]["clock_offsets_us"]["1"] == 0.0


# -- TTFT decomposition ------------------------------------------------------


def test_request_decomposition_sums_terms():
    recs = [
        ("serve.queued", 0.0, 0.10, {"rid": 4}, "t"),
        ("serve.prefill_chunk", 0.1, 0.20, {"rid": 4, "chunk": 16}, "t"),
        ("serve.prefill_chunk", 0.3, 0.10, {"rid": 4, "chunk": 8}, "t"),
        ("serve.prefill_chunk", 0.3, 9.99, {"rid": 5, "chunk": 8}, "t"),
        ("serve.first_decode", 0.4, 0.05, {"rid": 4}, "t"),
        ("serve.first_token", 0.45, None, {"rid": 4, "ttft": 0.47}, "t"),
    ]
    d = trace_export.request_decomposition(recs, 4)
    assert abs(d["sum_s"] - 0.45) < 1e-9
    assert abs(d["err_s"] - 0.02) < 1e-9
    assert trace_export.request_decomposition(recs, 5) is None  # no TTFT
    # a re-admission's second queued span must not displace the first
    recs.append(("serve.queued", 0.5, 5.0, {"rid": 4}, "t"))
    assert trace_export.request_decomposition(recs, 4)["queued_s"] == 0.10


def test_engine_ttft_decomposition_real_spans():
    """A real (tiny) serving burst: per-request spans decompose TTFT
    within tolerance, and a router-style trace id propagates engine ->
    scheduler -> every span of the request."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import (
        Transformer, TransformerConfig,
    )
    from horovod_tpu.serving.engine import ServeConfig, ServingEngine

    cfg = TransformerConfig(
        vocab_size=64, num_layers=1, num_heads=2, head_dim=8,
        max_seq_len=32, dtype=jnp.float32, attention_impl="dot",
        causal=True)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    eng = ServingEngine(cfg, params,
                        serve=ServeConfig(decode_tiers=(1, 2),
                                          token_budget=128))
    t0 = trace.now()
    rid = eng.submit(np.arange(1, 9), 3, trace_id="t0-abc-1")
    eng.run()
    recs = trace.snapshot(since=t0)
    d = trace_export.request_decomposition(recs, rid)
    assert d is not None
    assert d["err_s"] <= max(0.05, 0.5 * d["measured_ttft_s"])
    tagged = [r for r in recs
              if r[3] and r[3].get("trace") == "t0-abc-1"]
    tagged_sites = {r[0] for r in tagged}
    assert "serve.queued" in tagged_sites  # scheduler saw the context
    assert {"serve.first_token", "serve.finish"} <= tagged_sites


# -- the /trace endpoint -----------------------------------------------------


def test_trace_endpoint_roundtrip_and_alias():
    trace_export.register_trace_endpoint()
    with trace.span("train.step", step=42):
        pass
    srv = exposition.MetricsHTTPServer(0, registry=MetricsRegistry())
    try:
        base = f"http://127.0.0.1:{srv.port}"
        for path in ("/trace", "/control/trace"):
            resp = urllib.request.urlopen(base + path, timeout=10)
            assert resp.status == 200
            doc = json.loads(resp.read().decode())
            _assert_valid_chrome(doc)
            assert any(e["name"] == "train.step"
                       for e in doc["traceEvents"])
        # ?since bounds the window: a far-future cut returns no spans
        resp = urllib.request.urlopen(
            f"{base}/trace?since={trace.now() + 1e6}", timeout=10)
        doc = json.loads(resp.read().decode())
        assert all(e["ph"] == "M" for e in doc["traceEvents"])
    finally:
        srv.close()


def test_trace_endpoint_concurrent_scrape_while_recording():
    trace_export.register_trace_endpoint()
    srv = exposition.MetricsHTTPServer(0, registry=MetricsRegistry())
    errors = []
    stop = threading.Event()

    def scrape():
        url = f"http://127.0.0.1:{srv.port}/trace"
        while not stop.is_set():
            try:
                doc = json.loads(
                    urllib.request.urlopen(url, timeout=10).read())
                _assert_valid_chrome(doc)
            except Exception as e:  # noqa: BLE001 - surface in the test
                errors.append(e)
                return

    try:
        threads = [threading.Thread(target=scrape) for _ in range(3)]
        for t in threads:
            t.start()
        for i in range(2000):
            with trace.span("serve.step", kind="decode", batch=i % 8):
                pass
            trace.event("serve.finish", rid=i)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        assert not errors, errors
    finally:
        stop.set()
        srv.close()


def test_deny_remote_gate():
    assert not exposition._deny_remote("127.0.0.1")
    assert not exposition._deny_remote("127.3.2.1")
    assert not exposition._deny_remote("::1")
    assert exposition._deny_remote("10.0.0.5")
    os.environ["HVD_TPU_CONTROL_REMOTE"] = "1"
    try:
        assert not exposition._deny_remote("10.0.0.5")
    finally:
        os.environ.pop("HVD_TPU_CONTROL_REMOTE", None)


def test_trace_endpoint_loopback_only_403(monkeypatch):
    """The PR-13 rule on the NEW endpoint: a non-loopback client gets
    403 (every local connection source-routes from 127.0.0.1, so the
    unit-tested gate is forced remote for the integration half)."""
    trace_export.register_trace_endpoint()
    monkeypatch.setattr(exposition, "_deny_remote", lambda ip: True)
    srv = exposition.MetricsHTTPServer(0, registry=MetricsRegistry())
    try:
        base = f"http://127.0.0.1:{srv.port}"
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(f"{base}/trace", timeout=10)
        assert exc.value.code == 403
        # the read-only scrape surface stays open to everyone
        assert urllib.request.urlopen(
            f"{base}/metrics", timeout=10).status == 200
    finally:
        srv.close()


# -- flight recorder ---------------------------------------------------------


def test_flight_dump_disabled_without_dir(monkeypatch):
    monkeypatch.delenv("HVD_TPU_TRACE_BUNDLE_DIR", raising=False)
    assert flight.maybe_dump("chaos_kill") is None


def test_flight_bundle_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("HVD_TPU_TRACE_BUNDLE_DIR", str(tmp_path))
    monkeypatch.setenv("HVD_TPU_TRACE_BUNDLE_SECONDS", "60")
    flight._last_dump.clear()
    flight.note_metrics_baseline()
    from horovod_tpu.metrics import instruments as _instr

    _instr.CHAOS_INJECTIONS.labels("elastic.commit", "kill").inc()
    trace.event("chaos.inject", site="elastic.commit", action="kill")
    path = flight.maybe_dump("chaos_kill", extra={"site": "elastic.commit"})
    assert path and os.path.exists(path)
    bundle = flight.read_bundle(path)
    assert bundle["reason"] == "chaos_kill"
    assert bundle["extra"] == {"site": "elastic.commit"}
    assert any(
        e["name"] == "chaos.inject"
        and e.get("args", {}).get("site") == "elastic.commit"
        for e in bundle["trace"]["traceEvents"])
    # the metric delta since the baseline is in the bundle
    deltas = bundle["metric_deltas"]
    key = [k for k in deltas
           if k.startswith("hvd_tpu_chaos_injections_total")
           and "elastic.commit" in k]
    assert key and deltas[key[0]] == 1.0
    # checksum really guards the payload
    raw = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(raw[:-10] + bytes([raw[-10] ^ 0x40]) + raw[-9:])
    with pytest.raises(ValueError):
        flight.read_bundle(path)


def test_flight_dump_rate_limited(tmp_path, monkeypatch):
    monkeypatch.setenv("HVD_TPU_TRACE_BUNDLE_DIR", str(tmp_path))
    flight._last_dump.clear()
    assert flight.maybe_dump("rollback") is not None
    # stacked response paths (rollback -> exec-restart) dump ONCE
    assert flight.maybe_dump("restart") is None


def test_routine_dump_never_suppresses_a_crash_dump(tmp_path, monkeypatch):
    """An autoscaler slo_breach bundle moments before a quarantine must
    NOT cost the black box its crash evidence — the 2 s rate limit is
    per class, and routine never suppresses crash."""
    monkeypatch.setenv("HVD_TPU_TRACE_BUNDLE_DIR", str(tmp_path))
    flight._last_dump.clear()
    assert flight.maybe_dump("slo_breach") is not None
    assert flight.maybe_dump("quarantine") is not None  # crash: dumps
    assert flight.maybe_dump("slo_breach") is None      # routine: limited


def test_flight_bundle_retention_cap(tmp_path, monkeypatch):
    """An oscillating fleet dumps one slo_breach bundle per scale-out —
    the retention cap keeps the newest N so the directory is bounded."""
    monkeypatch.setenv("HVD_TPU_TRACE_BUNDLE_DIR", str(tmp_path))
    monkeypatch.setenv("HVD_TPU_TRACE_BUNDLE_KEEP", "3")
    for i in range(6):
        flight._last_dump.clear()  # bypass the 2 s dedupe
        assert flight.maybe_dump("slo_breach") is not None
    left = sorted(n for n in os.listdir(tmp_path)
                  if n.startswith("bundle-"))
    assert len(left) == 3
    # the NEWEST survive (counter suffix ascends within a process)
    assert all(int(n.rsplit("-", 1)[1].split(".")[0]) >= 4
               for n in left), left


# -- structured logging ------------------------------------------------------


def test_structured_log_context_and_json_formatter():
    from horovod_tpu.utils import logging as hvd_logging

    hvd_logging.set_log_context(rank=3, step=17)
    rec = logging.LogRecord("horovod_tpu", logging.WARNING, "f.py", 1,
                            "hello %s", ("world",), None)
    assert hvd_logging._ContextFilter().filter(rec)
    assert rec.rank == 3 and rec.step == 17 and rec.host
    out = json.loads(hvd_logging._JsonFormatter().format(rec))
    assert out["msg"] == "hello world"
    assert out["rank"] == 3 and out["step"] == 17
    assert out["level"] == "WARNING"
    hvd_logging.set_log_context(rank="-", step="-")


# -- the entry path's sites (ISSUE 24) ---------------------------------------


def _tiny_step():
    """A compiled two-layer MLP step over the 8-device CPU mesh: state,
    the jitted step, a batch."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu import training

    class Mlp(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(4)(nn.relu(nn.Dense(16)(x)))

    hvd.init()
    model, optimizer = Mlp(), optax.adamw(1e-3)
    x = jnp.ones((hvd.size(), 8), jnp.float32)
    y = jnp.zeros((hvd.size(),), jnp.int32)
    state = training.replicate_state(training.create_train_state(
        model, optimizer, jax.random.PRNGKey(0), x[:1]))
    return state, training.data_parallel_train_step(model, optimizer), x, y


def test_create_state_and_replicate_sites_record_their_args():
    t0 = trace.now()
    state, _, _, _ = _tiny_step()
    recs = {r[0]: r for r in trace.snapshot(since=t0)
            if r[0] in ("train.create_state", "train.replicate")}
    created = recs["train.create_state"][3]
    # Dense(8->16) + Dense(16->4), kernels and biases
    assert created["params"] == 8 * 16 + 16 + 16 * 4 + 4
    assert created["compiles"] >= 0 and created["compile_s"] >= 0.0
    assert recs["train.create_state"][2] > 0
    import jax

    want = sum(x.nbytes for x in jax.tree_util.tree_leaves(state))
    assert recs["train.replicate"][3] == {"bytes": want} and want > 0
    assert recs["train.replicate"][1] >= (recs["train.create_state"][1]
                                          + recs["train.create_state"][2])


def test_create_state_untraced_records_nothing_and_returns_the_same():
    import jax

    trace.configure(enabled=False)
    t0 = trace.now()
    off, _, _, _ = _tiny_step()
    trace.configure(enabled=True)
    on, _, _, _ = _tiny_step()
    assert not [r for r in trace.snapshot(since=t0)
                if r[0] == "train.create_state" and r[1] < t0]
    for a, b in zip(jax.tree_util.tree_leaves(off),
                    jax.tree_util.tree_leaves(on)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- start-up on the ring; the process's one compile recorder (ISSUE 37) ------


def _ours(listeners):
    return [f for f in listeners
            if getattr(f, "__module__", "") == "horovod_tpu.trace"]


def test_a_compile_inside_a_span_is_a_record_inside_its_extents():
    import jax
    import jax.numpy as jnp

    def never_compiled_before_issue37(x):
        return x * 37.0 + 1.0

    t0 = trace.now()
    before = trace.compile_totals()
    with trace.span("train.step", step=370):
        jax.jit(never_compiled_before_issue37)(jnp.ones((3, 7))).block_until_ready()
    recs = trace.snapshot(since=t0 - 1.0)
    (span,) = [r for r in recs if r[0] == "train.step"
               and (r[3] or {}).get("step") == 370]
    (mine,) = [r for r in recs if r[0] == "jax.compile"
               and r[3]["fun"] == "jit(never_compiled_before_issue37)"]
    assert mine[4] == span[4]                       # the thread that asked
    assert span[1] <= mine[1] and mine[1] + mine[2] <= span[1] + span[2]
    assert mine[2] > 0 and mine[3]["cached"] in (False, True)
    assert trace_export.enclosing(recs, mine, "train.step") == span
    # the totals moved by what the ring holds of this stretch
    delta = trace.compile_delta(before)
    inside = [r for r in recs if r[0] == "jax.compile" and r[1] >= span[1]]
    assert delta["compiles"] == len(inside) >= 1
    assert delta["compile_s"] == pytest.approx(sum(r[2] for r in inside))
    assert delta["cache_hits"] == sum(r[3]["cached"] for r in inside)


def test_compile_span_stamps_the_totals_difference_and_is_null_when_off():
    import jax
    import jax.numpy as jnp

    t0 = trace.now()
    with trace.compile_span("train.optimizer_init", kind="issue37") as sp:
        jax.jit(lambda x: x - 37.5)(jnp.ones((5,))).block_until_ready()
        sp.set(extra=1)
    (rec,) = [r for r in trace.snapshot(since=t0)
              if r[0] == "train.optimizer_init"]
    inside = [r for r in trace.snapshot(since=t0) if r[0] == "jax.compile"]
    assert rec[3]["kind"] == "issue37" and rec[3]["extra"] == 1
    assert rec[3]["compiles"] == len(inside) >= 1
    assert rec[3]["compile_s"] == pytest.approx(sum(r[2] for r in inside))
    trace.configure(enabled=False)
    t1 = trace.now()
    before = trace.compile_totals()
    with trace.compile_span("train.optimizer_init") as sp:
        assert sp is None
        jax.jit(lambda x: x - 38.5)(jnp.ones((5,))).block_until_ready()
    assert trace.compile_totals() == before        # the listener returns at once
    trace.configure(enabled=True)
    assert trace.snapshot(since=t1) == []


def test_a_compile_forced_inside_fit_epoch_gives_its_step_number():
    import jax.numpy as jnp

    from horovod_tpu import training

    state, step, x, y = _tiny_step()
    base = int(state.step)
    wide = (jnp.concatenate([x, x]), jnp.concatenate([y, y]))  # another shape
    t0 = trace.now()
    state, _ = training.fit_epoch(step, state, [(x, y), (x, y), wide, wide])
    recs = trace.snapshot(since=t0)
    steps = [trace_export.enclosing(recs, r, "train.step")[3]["step"]
             for r in recs if r[0] == "jax.compile"
             and r[3]["fun"] == "jit(_step)"]
    # the first step compiles, the third compiles again; no other does
    assert steps == [base + 1, base + 3]


def test_model_and_optimizer_init_lie_inside_create_state_and_hold_its_compiles():
    t0 = trace.now()
    _tiny_step()
    recs = trace.snapshot(since=t0)
    by = {r[0]: r for r in recs if r[0] in (
        "train.create_state", "train.model_init", "train.optimizer_init")}
    parent, model, optim = (by["train.create_state"], by["train.model_init"],
                            by["train.optimizer_init"])
    for child in (model, optim):
        assert child[4] == parent[4]
        assert parent[1] <= child[1]
        assert child[1] + child[2] <= parent[1] + parent[2]
        assert trace_export.enclosing(recs, child) == parent
    assert model[1] + model[2] <= optim[1]
    for key in ("compiles", "cache_hits"):
        assert model[3][key] + optim[3][key] == parent[3][key]
    assert model[3]["compile_s"] + optim[3]["compile_s"] == pytest.approx(
        parent[3]["compile_s"])
    # and the ring says the same: a jax.compile record each
    inside = [r for r in recs if r[0] == "jax.compile"
              and trace_export.enclosing(recs, r, "train.create_state")]
    assert len(inside) == parent[3]["compiles"]


def test_zero_train_setup_records_the_same_split():
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu import training

    t0 = trace.now()
    training.zero_train_setup(
        nn.Dense(4), optax.adam(1e-3), jax.random.PRNGKey(0),
        jnp.ones((1, 8), jnp.float32))
    sites = [r[0] for r in trace.snapshot(since=t0)
             if r[0].startswith("train.")]
    assert sites == ["train.model_init", "train.optimizer_init"]


def test_the_export_nests_a_compile_under_the_span_that_holds_it():
    import jax
    import jax.numpy as jnp

    t0 = trace.now()
    with trace.span("train.step", step=371):
        jax.jit(lambda x: x / 37.1)(jnp.ones((2,))).block_until_ready()
    events = [e for e in trace_export.chrome_trace(since=t0)["traceEvents"]
              if e.get("ph") == "X"]
    (outer,) = [e for e in events if e["name"] == "train.step"]
    inner = [e for e in events if e["name"] == "jax.compile"]
    assert inner and all(e["tid"] == outer["tid"] for e in inner)
    for e in inner:     # inside the parent's extents, written after it
        assert outer["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1e-3
        assert events.index(outer) < events.index(e)
        assert e["cat"] == "jax" and "fun" in e["args"]
    # equal starts: the longer span first, as viewers nest by extents
    recs = [("hvd.init.topology", 5.0, 1.0, None, "t"),
            ("hvd.init", 5.0, 2.0, None, "t")]
    names = [e["name"] for e in trace_export.chrome_trace(records=recs)
             ["traceEvents"] if e.get("ph") == "X"]
    assert names == ["hvd.init", "hvd.init.topology"]


def test_the_tool_prints_the_set_up_table_from_the_ring():
    """``tools/profile_capture.py``'s set-up table (the formatter lives in
    the tool, its only caller): a row a start-up span, nested by depth,
    the compile records summed, a recompile named with its step."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "profile_capture", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "profile_capture.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    paid = {"compiles": 2, "compile_s": 1.5, "cache_hits": 1}
    recs = [
        ("hvd.init", 0.0, 3.0, dict(paid, compiles=0), "t"),
        ("hvd.init.controller", 1.0, 2.0,
         dict(paid, native=True, built=True, build_s=1.9), "t"),
        ("train.model_init", 4.0, 2.0, paid, "t"),
        ("jax.compile", 4.1, 1.25, {"fun": "jit(init)", "cached": False}, "t"),
        ("jax.compile", 5.5, 0.25, {"fun": "jit(init)", "cached": True}, "t"),
        ("train.step", 7.0, 1.0, {"step": 12}, "t"),
        ("jax.compile", 7.1, 0.5, {"fun": "jit(_step)", "cached": False}, "t"),
    ]
    rows = tool.format_startup(recs).splitlines()
    assert [r.split()[0] for r in rows[1:5]] == [
        "hvd.init", "hvd.init.controller", "train.model_init", "jax.compile"]
    assert rows[2].startswith("    hvd.init.controller") and "build_s 1.9" in rows[2]
    assert "2 compiles 1.500 s, 1 from the cache" in rows[3]
    assert "3 records: 2 compiled, 1 loaded" in rows[4] and "in 0.250 s" in rows[4]
    (step,) = [r for r in rows if "jit(_step)" in r]
    assert step.endswith("compiled inside train.step 12")


def test_install_from_env_registers_one_recorder_however_often():
    from jax._src import monitoring     # the public module lists nothing

    for _ in range(3):
        trace.install_from_env(rank=0)
        trace.compile_totals()
    assert len(_ours(monitoring.get_event_duration_listeners())) == 1
    assert _ours(monitoring.get_event_listeners()) == []
    # and nothing else of the package listens for compile events
    other = [f for f in monitoring.get_event_duration_listeners()
             if getattr(f, "__module__", "").startswith("horovod_tpu")
             and f not in _ours([f])]
    assert other == []
    from horovod_tpu import training

    assert not hasattr(training, "_compile_totals")


_STARTUP_SCRIPT = r"""
import json, os, sys, tempfile
import horovod_tpu as hvd
from horovod_tpu import trace
import jax, jax.numpy as jnp
from jax._src import monitoring
jax.config.update("jax_compilation_cache_dir", tempfile.mkdtemp())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
hvd.init(); hvd.init()
trace.install_from_env(rank=0)
def first(x): return x * 2.0 + 37.0
def second(x): return x * 2.0 + 37.0
first.__name__ = second.__name__ = "same_program"
with trace.span("train.step", step=1):
    jax.jit(first)(jnp.ones(4)).block_until_ready()    # compiled, written
    jax.jit(second)(jnp.ones(4)).block_until_ready()   # the same bytes: loaded
ours = lambda fs: sum(getattr(f, "__module__", "") == "horovod_tpu.trace" for f in fs)
print("RESULT " + json.dumps({
    "records": trace.snapshot(),
    "totals": trace.compile_totals()._asdict(),
    "duration_listeners": ours(monitoring.get_event_duration_listeners()),
    "event_listeners": ours(monitoring.get_event_listeners()),
    "wrapped": trace.wrapped()}))
"""


@pytest.fixture(scope="module", params=["1", "0"])
def startup_run(request):
    """A fresh process: import, ``hvd.init()`` twice, a program compiled
    and then loaded from a persistent cache of its own; under
    ``HVD_TPU_TRACE`` on and off."""
    import subprocess
    import sys

    env = dict(os.environ, HVD_TPU_TRACE=request.param, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, "-c", _STARTUP_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    (line,) = [ln for ln in out.stdout.splitlines()
               if ln.startswith("RESULT ")]
    return request.param, json.loads(line[len("RESULT "):])


def test_start_up_is_on_the_ring_and_nested(startup_run):
    flag, got = startup_run
    recs = [tuple(r) for r in got["records"]]
    if flag == "0":
        assert recs == [] and not got["wrapped"]
        return
    by = {}
    for r in recs:
        by.setdefault(r[0], []).append(r)
    for site in ("hvd.import", "hvd.init", "hvd.init.topology",
                 "hvd.init.controller"):
        assert len(by[site]) == 1, site          # the second init() is a no-op
    imp, init = by["hvd.import"][0], by["hvd.init"][0]
    topo, ctrl = by["hvd.init.topology"][0], by["hvd.init.controller"][0]
    assert imp[3]["jax_loaded"] is False and imp[2] > 0.1
    assert imp[1] + imp[2] <= init[1]
    for child in (topo, ctrl):
        assert trace_export.enclosing(recs, child) == init
        assert {"compiles", "compile_s", "cache_hits"} <= set(child[3])
    assert topo[1] + topo[2] <= ctrl[1]
    assert ctrl[3]["native"] in (True, False) and "built" in ctrl[3]
    assert ("build_s" in ctrl[3]) == ctrl[3]["built"]


def test_one_recorder_a_process_and_none_when_tracing_is_off(startup_run):
    flag, got = startup_run
    want = 1 if flag == "1" else 0
    assert got["duration_listeners"] == want
    assert got["event_listeners"] == 0          # the one listener is the whole of it
    if flag == "0":
        assert got["totals"] == trace.CompileTotals()._asdict()


def test_a_load_from_the_persistent_cache_is_told_from_a_compile(startup_run):
    flag, got = startup_run
    if flag == "0":
        assert got["records"] == []
        return
    mine = [r for r in got["records"] if r[0] == "jax.compile"
            and r[3]["fun"] == "jit(same_program)"]
    assert [r[3]["cached"] for r in mine] == [False, True]
    totals = got["totals"]
    assert set(totals) == {"compiles", "compile_s", "cache_hits"}
    assert totals["cache_hits"] >= 1
    assert totals["compiles"] == sum(r[0] == "jax.compile"
                                     for r in got["records"])
    assert totals["cache_hits"] == sum(
        r[0] == "jax.compile" and r[3]["cached"] for r in got["records"])


def test_wrapped_says_when_a_ring_has_overwritten(monkeypatch):
    monkeypatch.setattr(trace, "_rings", [])
    monkeypatch.setattr(trace, "_local", threading.local())
    monkeypatch.setattr(trace, "_ring_cap", 256)
    for i in range(256):
        trace.event("chaos.inject", n=i)
    assert not trace.wrapped()
    trace.event("chaos.inject", n=256)
    assert trace.wrapped()


# -- device names and their reducer (ISSUE 24) -------------------------------


@pytest.mark.parametrize("op_name,want", [
    ("jit(_step)/shard_map/jvp(forward)/Mlp/Dense_0/dot_general",
     ("forward", False)),
    ("jit(_step)/jvp(forward)/Transformer/layer_0/transpose",
     ("forward", False)),           # a jnp transpose is not the transform
    ("jit(_step)/shard_map/transpose(jvp(forward))/Mlp/Dense_0/dot_general",
     ("backward", False)),
    ("jit(_step)/transpose(jvp(forward))/Block/checkpoint/"
     "rematted_computation/dot_general", ("backward", True)),
    ("jit(_step)/jvp(forward)/Block/checkpoint/dot_general",
     ("forward", False)),           # recompute only beneath the backward
    # jax 0.9 under jax.checkpoint: a second jvp(forward) BENEATH the
    # transpose is the block linearised again: backward, and of it only
    # rematted_computation is the forward made again
    ("jit(_step)/shard_map/transpose(jvp(forward))/jvp(forward)/Transformer/"
     "layer_0/linear_attn/checkpoint/gdn/dot_general", ("backward", False)),
    ("jit(_step)/shard_map/transpose(jvp(forward))/jvp(forward)/Transformer/"
     "layer_0/linear_attn/checkpoint/rematted_computation/gdn/tanh",
     ("backward", True)),
    ("jit(_step)/transpose(jvp(forward))/jvp(forward)/remat2",
     ("backward", False)),
    ("jit(_step)/shard_map/exchange/psum", ("exchange", False)),
    ("jit(_step)/shard_map/optimizer/mul", ("optimizer", False)),
    ("jit(_step)/shard_map/optimizer/exchange/reduce_scatter",
     ("exchange", False)),          # the innermost scope names the phase
    ("jit(_step)/shard_map/add", ("unattributed", False)),
    ("", ("unattributed", False)),
])
def test_classify_op_name(op_name, want):
    assert trace_device.classify(op_name) == want


def test_a_checkpointed_block_in_a_compiled_step_is_backward_beneath_the_transpose():
    """What jax 0.9 really writes (not a made-up path): the reducer read a
    rematerialised mixer's backward as forward until ISSUE 37's first phase
    row of Qwen3-Next showed it."""
    import re

    import jax
    import jax.numpy as jnp

    def loss(w, x):
        with jax.named_scope("forward"):
            @jax.checkpoint
            def block(x):
                with jax.named_scope("gdn"):
                    return jnp.tanh(x @ w) @ w
            return jnp.sum(block(x) ** 2)

    text = jax.jit(jax.value_and_grad(loss)).lower(
        jnp.ones((8, 8)), jnp.ones((4, 8))).as_text(debug_info=True)
    names = set(re.findall(r'loc\("(jit\(loss\)/[^"]*)"', text))
    beneath = [n for n in names if "/transpose(jvp(forward))/" in n]
    assert any("/jvp(forward)/checkpoint/" in n for n in beneath)
    assert all(trace_device.classify(n)[0] == "backward" for n in beneath)
    remade = [n for n in beneath if "/rematted_computation/" in n]
    assert remade and all(
        trace_device.classify(n) == ("backward", True) for n in remade)
    own = [n for n in beneath if "/checkpoint/gdn/" in n]
    assert own and all(
        trace_device.classify(n) == ("backward", False) for n in own)
    above = [n for n in names if n not in beneath and "forward" in n]
    assert above and all(
        trace_device.classify(n) == ("forward", False) for n in above)


def test_phase_table_classifies_a_compiled_cpu_step():
    state, step, x, y = _tiny_step()
    text = step.lower(state, x, y).compile().as_text()
    table = trace_device.phase_table(text)
    lines = {}
    for line in text.splitlines():
        m = trace_device._INSTR_RE.match(line)
        if m:
            lines[m.group(1)] = line
    # every instruction of the text is in the table, under a known phase
    assert set(table) == set(lines)
    assert {v[0] for v in table.values()} == set(trace_device.PHASES)
    # what stays unattributed is what the compiler made or moved
    # (parameters, constants, copies: no op_name) or what sits outside
    # every scope by its own op_name (the step counter); never an
    # instruction whose op_name names a scope
    for name, (phase, _, _) in table.items():
        if phase == "unattributed":
            op_name = trace_device._OP_NAME_RE.search(lines[name])
            assert not op_name or not any(
                s in op_name.group(1) for s in trace.DEVICE_SCOPES), lines[name]
    # the scopes reached the instructions that do the work
    by_opcode = {}
    for name, line in lines.items():
        for opcode in ("dot(", "all-reduce("):
            if f" {opcode}" in line:
                by_opcode.setdefault(opcode, set()).add(table[name][0])
    assert by_opcode["dot("] == {"forward", "backward"}
    assert by_opcode["all-reduce("] == {"exchange"}
    # the serialized module, which a capture carries, reads the same
    proto = step.lower(state, x, y).compile().runtime_executable() \
        .hlo_modules()[0].as_serialized_hlo_module_proto()
    assert trace_device.phase_table(proto) == table


def test_phase_table_fallbacks_for_instructions_without_a_scoped_op_name():
    text = """HloModule m
%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %a = f32[4]{0} add(%p, %p), metadata={op_name="jit(_step)/jvp(forward)/add"}
  %b = f32[4]{0} add(%a, %p), metadata={op_name="jit(_step)/jvp(forward)/add"}
  %o = f32[4]{0} multiply(%b, %p), metadata={op_name="jit(_step)/optimizer/mul"}
  ROOT %c = f32[4]{0} convert(%o)
}
ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_step)/convert.9"}
  %copy.1 = f32[4]{0} copy(%fusion.1)
  %copy.2 = f32[4]{0} copy(%x)
  %mul.1 = f32[4]{0} multiply(%copy.1, %copy.1), metadata={op_name="jit(_step)/optimizer/mul"}
  ROOT %add.2 = f32[4]{0} add(%copy.1, %mul.1)
}
"""
    table = trace_device.phase_table(text)
    # most of its callee; the optimizer work fused into it is named
    assert table["fusion.1"] == ("forward", False, ("optimizer",))
    assert table["copy.1"] == ("forward", False, ())     # its operand's
    assert table["copy.2"] == ("unattributed", False, ())
    assert table["mul.1"] == ("optimizer", False, ())
    assert table["add.2"] == ("unattributed", False, ())  # operands disagree


def test_phase_table_gives_an_unnamed_instruction_the_phase_its_users_agree_on():
    """The compiler rewrites the loss's backward scatter, and the cast of
    its result, with no metadata at all (Kimi's ``fusion.119`` ->
    ``reshape.500``, 2.2 ms a step; my chip run, PR 37): both feed the
    head's backward products alone.  A copy of a weight that the forward and
    the backward both read stays unattributed."""
    text = """HloModule m
%fused_computation.119 (p0: f32[8], p1: s32[2]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %p1 = s32[2]{0} parameter(1)
  ROOT %scatter.5 = f32[8]{0} scatter(%p0, %p1), to_apply=%region
}
ENTRY %main (w: f32[8], labels: s32[2]) -> f32[8] {
  %w = f32[8]{0} parameter(0)
  %labels = s32[2]{0} parameter(1)
  %zeros = f32[8]{0} broadcast(%w)
  %copy.7 = f32[8]{0} copy(%w)
  %fwd.1 = f32[8]{0} add(%copy.7, %copy.7), metadata={op_name="jit(_step)/jvp(forward)/add"}
  %fusion.119 = f32[8]{0} fusion(%zeros, %labels), kind=kCustom, calls=%fused_computation.119
  %reshape.500 = f32[8]{0} reshape(%fusion.119)
  %dx.1 = f32[8]{0} multiply(%reshape.500, %copy.7), metadata={op_name="jit(_step)/transpose(jvp(forward))/mul"}
  ROOT %dw.1 = f32[8]{0} multiply(%reshape.500, %fwd.1), metadata={op_name="jit(_step)/transpose(jvp(forward))/mul"}
}
"""
    table = trace_device.phase_table(text)
    assert table["reshape.500"] == ("backward", False, ())
    assert table["fusion.119"] == ("backward", False, ())   # through its user
    assert table["copy.7"] == ("unattributed", False, ())   # users disagree
    assert table["fwd.1"][0] == "forward" and table["dw.1"][0] == "backward"


def test_reduce_phases_sums_to_the_busy_time():
    """A hand-made event list: nested, overlapping and idle stretches;
    two devices with different step counts."""
    devices = {
        "0": {"steps": 2, "ops": [
            ("fusion.1", 0.0, 100.0),
            ("while.1", 100.0, 300.0),
            ("fusion.2", 120.0, 50.0),      # nested in the while
            ("psum.1", 200.0, 100.0),       # nested in the while
            ("copy.1", 500.0, 20.0),        # after an idle gap; not in the table
            ("fusion.3", 510.0, 90.0),      # overlaps the copy
        ]},
        "1": {"steps": 1, "ops": [("fusion.1", 0.0, 250.0)]},
    }
    table = {"fusion.1": ("forward", False, ()),
             "while.1": ("backward", False, ("optimizer",)),
             "fusion.2": ("backward", True, ()),
             "psum.1": ("exchange", False, ()),
             "fusion.3": ("optimizer", False, ())}
    r = trace_device.reduce_phases(devices, table)
    assert r["devices"] == 2 and r["steps"] == 1
    # device 0: union 0..400 and 500..600 = 500 ns over 2 steps; device 1:
    # 250 ns over 1; the mean of 250 and 250 ns, in ms
    assert r["busy_ms"] == pytest.approx(250e-6)
    assert r["sum_ms"] == pytest.approx(r["busy_ms"], rel=1e-12)
    want = {"forward": (50 + 250) / 2, "backward": (150 + 50) / 2 / 2,
            "exchange": 100 / 2 / 2, "optimizer": 90 / 2 / 2,
            "unattributed": 10 / 2 / 2}
    for phase, ns in want.items():
        assert r["phases"][phase] == pytest.approx(ns * 1e-6), phase
    assert r["recompute_ms"] == pytest.approx(50 / 2 / 2 * 1e-6)
    # the while's own 150 ns sit in a fusion that also holds optimizer work
    assert r["shared_ms"] == {
        "backward+optimizer": pytest.approx(150 / 2 / 2 * 1e-6)}
    assert "also hold optimizer work" in trace_device.format_phases(r)
    assert r["unattributed_top"] == [["copy.1", pytest.approx(2.5e-6)]]
    # without a table everything is unattributed, and the sum holds
    r = trace_device.reduce_phases(devices)
    assert r["sum_ms"] == pytest.approx(r["busy_ms"], rel=1e-12)
    assert r["phases"]["unattributed"] == pytest.approx(r["busy_ms"])
    assert "largest unattributed" in trace_device.format_phases(r)


_HIDDEN_TABLE = {
    "fusion.1": ("backward", False, ()),
    "fusion.2": ("backward", False, ("exchange",)),   # a partner fusion
    "fusion.3": ("optimizer", False, ()),
    "psum.1": ("exchange", False, ()),
    "divide.1": ("exchange", False, ()),
    "async-collective-start.4": ("exchange", False, ()),
    "async-collective-done.4": ("exchange", False, ()),
    "all-reduce-start": ("exchange", False, ()),
    "all-reduce-done": ("exchange", False, ()),
}


@pytest.mark.parametrize("ops,in_flight,hidden", [
    pytest.param(
        # a synchronous all-reduce: in flight while it runs, alone
        [("fusion.1", 0.0, 100.0), ("psum.1", 100.0, 60.0),
         ("divide.1", 160.0, 10.0), ("fusion.3", 170.0, 30.0)],
        70.0, 0.0, id="synchronous_nothing_beside_it"),
    pytest.param(
        # an asynchronous pair around a partner fusion and an idle gap:
        # in flight 100..200, of which fusion.2 covers 110..170
        [("fusion.1", 0.0, 100.0), ("async-collective-start.4", 100.0, 10.0),
         ("fusion.2", 110.0, 60.0), ("async-collective-done.4", 180.0, 20.0),
         ("fusion.3", 200.0, 30.0)],
        100.0, 60.0, id="asynchronous_behind_a_fusion"),
    pytest.param(
        # one of each in one step, and the generic start/done names; the
        # second pair has nothing between its start and its done
        [("async-collective-start.4", 0.0, 10.0), ("fusion.2", 10.0, 50.0),
         ("fusion.1", 60.0, 20.0), ("async-collective-done.4", 80.0, 5.0),
         ("psum.1", 90.0, 40.0),
         ("all-reduce-start", 130.0, 5.0), ("all-reduce-done", 135.0, 25.0),
         ("fusion.3", 160.0, 40.0)],
        85.0 + 40.0 + 30.0, 70.0, id="one_hidden_one_exposed"),
    pytest.param(
        # a done without its start in the window (the capture began
        # mid-step) is its own interval only
        [("fusion.1", 0.0, 50.0), ("async-collective-done.4", 50.0, 20.0)],
        20.0, 0.0, id="done_without_a_start"),
])
def test_exchange_hidden_is_the_in_flight_time_beside_other_work(
        ops, in_flight, hidden):
    devices = {"0": {"steps": 1, "ops": ops},
               # a second device, twice the steps, the same events twice
               "1": {"steps": 2, "ops": ops + [
                   (n, s + 1000.0, d) for n, s, d in ops]}}
    r = trace_device.reduce_phases(devices, _HIDDEN_TABLE)
    assert r["exchange_in_flight_ms"] == pytest.approx(in_flight * 1e-6)
    assert r["exchange_hidden_ms"] == pytest.approx(hidden * 1e-6, abs=1e-15)
    # the phases still partition the busy time; exchange is what the
    # exchange operations themselves took (the exposed part)
    assert r["sum_ms"] == pytest.approx(r["busy_ms"], rel=1e-12)
    own = sum(d for n, _, d in ops if _HIDDEN_TABLE[n][0] == "exchange")
    assert r["phases"]["exchange"] == pytest.approx(own * 1e-6)
    text = trace_device.format_phases(r)
    assert "exchange hidden" in text
    assert f"{100 * hidden / in_flight:5.1f} % of the" in text


def test_no_exchange_no_hidden_row():
    devices = {"0": {"steps": 1, "ops": [("fusion.1", 0.0, 10.0)]}}
    r = trace_device.reduce_phases(devices, _HIDDEN_TABLE)
    assert r["exchange_in_flight_ms"] == 0.0 == r["exchange_hidden_ms"]
    assert "exchange hidden" not in trace_device.format_phases(r)


def test_a_capture_carries_its_program_and_phases_need_a_device_plane(tmp_path):
    import jax

    with pytest.raises(FileNotFoundError):
        trace_device.phase_ms(str(tmp_path))
    state, step, x, y = _tiny_step()
    state, _ = step(state, x, y)
    jax.profiler.start_trace(str(tmp_path))
    jax.block_until_ready(step(state, x, y))
    jax.profiler.stop_trace()
    # the step program's HloModuleProto is in the capture, read with no
    # protobuf library: every scope shows in its table
    hlo = trace_device.embedded_hlo(str(tmp_path))
    assert hlo is not None
    assert trace_device.embedded_hlo(str(tmp_path), "no_such_program") is None
    phases = {v[0] for v in trace_device.phase_table(hlo).values()}
    assert phases == set(trace_device.PHASES)
    # a CPU capture has no TPU device plane to take times from
    with pytest.raises(ValueError, match="capture from the chip"):
        trace_device.phase_ms(str(tmp_path))


def test_scopes_leave_the_lowered_step_untouched():
    """The device scopes are metadata: with them stripped from the
    lowered text nothing names them, and the operations of the step are
    the same with tracing on and off."""
    import re

    state, step, x, y = _tiny_step()
    on = step.lower(state, x, y).as_text()
    trace.configure(enabled=False)
    try:
        _, step_off, _, _ = _tiny_step()
        off = step_off.lower(state, x, y).as_text()
    finally:
        trace.configure(enabled=True)
    assert on == off
    assert not re.search(r"\b(forward|exchange|optimizer)\b",
                         re.sub(r"loc\(.*", "", on))


# -- the analysis `trace` pass -----------------------------------------------


def _tree(tmp_path, catalogue_sites, code, doc_sites):
    (tmp_path / "horovod_tpu" / "trace").mkdir(parents=True)
    (tmp_path / "docs").mkdir()
    cat = "SITES = (\n" + "".join(
        f'    "{s}",\n' for s in catalogue_sites) + ")\n"
    (tmp_path / "horovod_tpu" / "trace" / "__init__.py").write_text(cat)
    (tmp_path / "horovod_tpu" / "mod.py").write_text(code)
    rows = "| site | kind |\n|---|---|\n" + "".join(
        f"| `{s}` | span |\n" for s in doc_sites)
    (tmp_path / "docs" / "TRACING.md").write_text(rows)
    return str(tmp_path)


def test_trace_pass_clean_tree(tmp_path):
    from horovod_tpu.analysis import trace_sites

    root = _tree(
        tmp_path, ["train.step", "serve.finish"],
        'from . import trace\n'
        'with trace.span("train.step", step=1):\n'
        '    trace.event("serve.finish")\n',
        ["train.step", "serve.finish"])
    assert trace_sites.run(root) == []


def test_trace_pass_catches_every_drift_class(tmp_path):
    from horovod_tpu.analysis import trace_sites

    root = _tree(
        tmp_path,
        ["train.step", "dead.site", "undocumented.site"],
        'from . import trace\n'
        'trace.event("train.step")\n'
        'trace.event("undocumented.site")\n'
        'trace.add_span("rogue.site", 0, 1)\n',
        ["train.step", "ghost.site"])
    keys = {(f.key, f.file.split("/")[-1])
            for f in trace_sites.run(root)}
    assert ("rogue.site", "mod.py") in keys          # uncatalogued call
    assert ("dead.site", "__init__.py") in keys      # dead catalogue
    assert ("undocumented.site", "__init__.py") in keys  # missing doc row
    assert ("ghost.site", "TRACING.md") in keys      # stale doc row


def test_trace_pass_registered_and_repo_clean():
    from horovod_tpu import analysis

    assert "trace" in analysis.PASSES
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert analysis.PASSES["trace"](repo) == []


def _device_tree(tmp_path, scopes, kernels, code, flash, doc_rows):
    root = _tree(tmp_path, ["train.step"],
                 'from . import trace\ntrace.event("train.step")\n' + code,
                 ["train.step"])
    init = tmp_path / "horovod_tpu" / "trace" / "__init__.py"
    init.write_text(
        init.read_text()
        + "DEVICE_SCOPES = (\n" + "".join(f'    "{s}",\n' for s in scopes)
        + ")\nDEVICE_KERNELS = (\n"
        + "".join(f'    "{k}",  # a comment (with parentheses)\n'
                  for k in kernels) + ")\n")
    (tmp_path / "horovod_tpu" / "ops").mkdir()
    (tmp_path / "horovod_tpu" / "ops" / "flash_attention.py").write_text(flash)
    doc = tmp_path / "docs" / "TRACING.md"
    doc.write_text(doc.read_text() + "\n| name | kind |\n|---|---|\n" + "".join(
        f"| `{n}` | {k} | x |\n" for n, k in doc_rows))
    return root


_FLASH_OK = (
    "out = pl.pallas_call(\n    functools.partial(k, a=1),\n"
    '    name="flash_attention_fwd",\n    grid=(b * h, s // q),\n)(x)\n')


def test_trace_pass_device_names_clean_tree(tmp_path):
    from horovod_tpu.analysis import trace_sites

    root = _device_tree(
        tmp_path, ["forward", "exchange"], ["flash_attention_fwd"],
        'with jax.named_scope("forward"):\n    pass\n'
        '@jax.named_scope("exchange")\ndef f(): pass\n',
        _FLASH_OK,
        [("forward", "scope"), ("exchange", "scope"),
         ("flash_attention_fwd", "kernel")])
    assert trace_sites.run(root) == []


def test_trace_pass_catches_device_name_drift(tmp_path):
    from horovod_tpu.analysis import trace_sites

    root = _device_tree(
        tmp_path, ["forward", "dead_scope"],
        ["flash_attention_fwd", "dead_kernel"],
        'with jax.named_scope("forward"):\n    pass\n'
        'with jax.named_scope("rogue_scope"):\n    pass\n',
        _FLASH_OK
        + 'o = pl.pallas_call(k, name="rogue_kernel", grid=(1,))(x)\n'
        + "o = pl.pallas_call(k, grid=(1,))(x)\n",
        [("forward", "scope"), ("ghost_scope", "scope"),
         ("flash_attention_fwd", "kernel"), ("ghost_kernel", "kernel")])
    keys = {(f.key, f.file.split("/")[-1]) for f in trace_sites.run(root)}
    assert ("rogue_scope", "mod.py") in keys             # uncatalogued scope
    assert ("rogue_kernel", "flash_attention.py") in keys
    assert ("dead_scope", "__init__.py") in keys          # no call site / no row
    assert ("dead_kernel", "__init__.py") in keys
    assert ("ghost_scope", "TRACING.md") in keys          # stale doc rows
    assert ("ghost_kernel", "TRACING.md") in keys
    assert ("pallas_call", "flash_attention.py") in keys  # an unnamed kernel


def test_device_names_catalogue_matches_the_code():
    """DEVICE_KERNELS are the names the kernels carry: the attention kernels
    each start with the prefix the benchmark's one pattern reads them all by,
    and no other kernel does (the grouped products must stay out of
    ``flash_attention_ms``)."""
    from horovod_tpu.analysis import trace_sites

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert tuple(trace_sites.catalogue(repo, "DEVICE_KERNELS")) == \
        trace.DEVICE_KERNELS
    assert tuple(trace_sites.catalogue(repo, "DEVICE_SCOPES")) == \
        trace.DEVICE_SCOPES
    assert [k for k in trace.DEVICE_KERNELS if not k.startswith("flash_attention_")] \
        == ["grouped_matmul", "grouped_matmul_t", "gated_delta_kkt",
            "gated_delta_fwd", "gated_delta_bwd", "gdn_conv_norm_fwd",
            "gdn_conv_norm_bwd", "gdn_gated_norm_fwd", "gdn_gated_norm_bwd",
            "conv_bias_silu_fwd", "conv_bias_silu_bwd", "gated_group_norm_fwd",
            "gated_group_norm_bwd", "ssd_scan_fwd", "ssd_scan_bwd",
            "rope_fwd", "rope_bwd"]
    assert not os.path.exists(
        os.path.join(repo, "horovod_tpu", "utils", "profiler.py"))


# -- parts of the forward scope; the routed layer's names (PR 28) -------------


def test_subscope_catalogue_matches_the_code_and_names_no_phase():
    from horovod_tpu.analysis import trace_sites

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert tuple(trace_sites.catalogue(repo, "DEVICE_SUBSCOPES")) == \
        trace.DEVICE_SUBSCOPES == ("router", "experts", "mla", "shared_experts",
                                   "gdn", "gated_delta", "mamba", "in_proj", "conv",
                                   "ssd", "gated_norm", "out_proj", "latent_down",
                                   "latent_up", "attn_rope", "attn_window",
                                   "attn_full", "attn_gate", "attn_docmask")
    assert "flash_attention_bwd_dkv_bd" in trace.DEVICE_KERNELS
    assert not set(trace.DEVICE_SUBSCOPES) & set(trace.DEVICE_SCOPES)
    path = "jit(_step)/shard_map/transpose(jvp(forward))/T/layer_0/moe/experts/x"
    assert trace_device.classify(path) == ("backward", False)
    assert trace_sites.run(repo) == []


@pytest.mark.parametrize("rows,missing", [
    ([("forward", "scope"), ("router", "subscope")], None),
    ([("forward", "scope")], ("router", "__init__.py")),          # no docs row
    ([("forward", "scope"), ("router", "subscope"),
      ("ghost", "subscope")], ("ghost", "TRACING.md")),           # stale docs row
])
def test_trace_pass_holds_subscopes_like_scopes(tmp_path, rows, missing):
    from horovod_tpu.analysis import trace_sites

    root = _device_tree(
        tmp_path, ["forward"], ["flash_attention_fwd"],
        'with jax.named_scope("forward"):\n    pass\n'
        'with jax.named_scope("router"):\n    pass\n',
        _FLASH_OK, rows + [("flash_attention_fwd", "kernel")])
    init = tmp_path / "horovod_tpu" / "trace" / "__init__.py"
    init.write_text(init.read_text() + 'DEVICE_SUBSCOPES = (\n    "router",\n)\n')
    keys = {(f.key, f.file.split("/")[-1]) for f in trace_sites.run(root)}
    assert keys == (set() if missing is None else {missing})


def _routed_step_text(latent=False):
    import jax
    import jax.numpy as jnp
    import optax

    import horovod_tpu as hvd
    from horovod_tpu import training
    from horovod_tpu.models.transformer import (
        Transformer, TransformerConfig, block_diffusion_loss, next_token_loss,
    )

    hvd.init()
    routed = dict(vocab_size=32, num_layers=1, num_heads=2, max_seq_len=32,
                  dtype=jnp.float32, num_experts=4, num_experts_per_tok=2,
                  moe_intermediate_size=8, held_experts=(0, 2))
    tokens = jnp.zeros((hvd.size(), 16), jnp.int32)
    if latent:   # latent attention and shared experts beside the routed sum
        cfg = TransformerConfig(
            hidden_size=16, kv_lora_rank=8, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, num_shared_experts=1,
            router_scoring="sigmoid", router_selection_bias=True, **routed)
        labels, loss_fn = tokens, next_token_loss
    else:
        cfg = TransformerConfig(head_dim=8, block_diffusion=2, **routed)
        labels = (tokens[:, :8], jnp.ones((hvd.size(), 8), jnp.float32))
        loss_fn = block_diffusion_loss
    model, optimizer = Transformer(cfg), optax.adamw(1e-3)
    state = training.replicate_state(training.create_train_state(
        model, optimizer, jax.random.PRNGKey(0), tokens[:1]))
    step = training.data_parallel_train_step(
        model, optimizer, loss_fn=loss_fn)
    return step.lower(state, tokens, labels).compile().as_text()


@pytest.mark.parametrize("latent", [False, True], ids=["routed", "latent"])
def test_subscope_table_finds_the_routed_layer_in_a_compiled_step(latent):
    text = _routed_step_text(latent)
    parts = trace_device.subscope_table(text)
    assert set(parts.values()) == set(
        trace.DEVICE_SUBSCOPES[:4] if latent else trace.DEVICE_SUBSCOPES[:2])
    phases = trace_device.phase_table(text)
    # a part lies inside the forward scope or its transpose (or is an
    # operation the compiler left unnamed, which takes its operand's part
    # and no phase), never in another phase
    assert {phases[name][0] for name in parts} <= {
        "forward", "backward", "unattributed"}
    assert {"forward", "backward"} <= {phases[name][0] for name in parts}
    events = {"0": {"steps": 1, "ops": [
        (name, 10.0 * i, 5.0) for i, name in enumerate(sorted(parts))]}}
    result = trace_device.reduce_phases(events, phases, parts)
    assert abs(sum(result["subscopes"].values()) - result["busy_ms"]) < 1e-12
    assert all(result["subscopes"][part] > 0 for part in set(parts.values()))
    assert "of which router" in trace_device.format_phases(result)
    assert "subscopes" not in trace_device.reduce_phases(events, phases)


def test_embedded_hlo_is_the_step_loaded_last_not_another_step(tmp_path):
    """The capture's metadata plane holds every program of the process, in
    no order: an earlier ``jit_step`` must not stand in for the step."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        return x * 2 + 1

    @jax.jit
    def my_step_fn(x):
        return x * 3

    step(jnp.ones(4)).block_until_ready()
    my_step_fn(jnp.ones(4)).block_until_ready()
    state, train_step, x, y = _tiny_step()
    state, _ = train_step(state, x, y)
    jax.profiler.start_trace(str(tmp_path))
    jax.block_until_ready(train_step(state, x, y))
    jax.profiler.stop_trace()
    phases = {v[0] for v in trace_device.phase_table(
        trace_device.embedded_hlo(str(tmp_path))).values()}
    assert phases == set(trace_device.PHASES)
    assert trace_device._program_id("jit__step(55)") == 55
    assert trace_device._program_id("jit__step") == -1
