"""Tests for horovod_tpu.trace — the span recorder, Chrome export,
/trace control endpoint, cross-rank merge, flight recorder, and the
analysis ``trace`` pass (ISSUE 15).

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
from horovod_tpu.trace import flight
from horovod_tpu.utils import profiler


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
    t0 = trace.now()
    with profiler.span("grad_3", "ENQUEUE"):
        pass
    with profiler.span("ALLREDUCE", "XLA_COMM"):
        pass
    sites = {r[0]: r[3] for r in trace.snapshot(since=t0)}
    assert sites.get("collective.enqueue") == {"name": "grad_3"}
    assert sites.get("collective.exec") == {"name": "ALLREDUCE"}


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
