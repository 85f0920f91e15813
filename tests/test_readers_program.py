"""The benchmark's readers of the program's own trace
(``benchmark/readers_program.py``, ISSUE 37): ``program_span`` against a ring
the test fills, ``program_phase`` against ``reduce_phases`` on a small
synthetic set of device events and a table, and the eleven metric files that
name them, through the harness's own ``per_layer``.
"""

import json
import os
import threading

import pytest

from benchmark import harness, readers, readers_program
from horovod_tpu import trace
from horovod_tpu.trace import device as trace_device

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW = ("import_s", "hvd_init_s", "model_init_s", "model_init_compiles",
       "step_compile_s", "step_cache_hits", "forward_ms", "backward_ms",
       "optimizer_ms", "unattributed_ms", "recompute_ms")
STEP = r"^jit\(_step\)$"


@pytest.fixture
def ring(monkeypatch):
    """A ring of the test's own: the worker's has other tests' records."""
    trace.configure(enabled=True)
    monkeypatch.setattr(trace, "_rings", [])
    monkeypatch.setattr(trace, "_local", threading.local())
    monkeypatch.setattr(trace, "_ring_cap", 256)
    yield
    trace.configure(enabled=True)


def _readings(trace_dir=None, steps=0):
    return readers.Readings(config={}, traffic={}, peaks={}, chips=1,
                            rows_per_step=1, trace_dir=trace_dir,
                            steps_traced=steps)


def _fill():
    """What a run's set-up leaves on the ring, at made-up times."""
    trace.add_span("hvd.import", 1.0, 3.5, jax_loaded=True, compiles=0,
                   compile_s=0.0, cache_hits=0)
    trace.add_span("hvd.init", 4.0, 5.25, compiles=0, compile_s=0.0, cache_hits=0)
    trace.add_span("jax.compile", 6.0, 6.5, fun="jit(add)", cached=False)
    trace.add_span("jax.compile", 6.5, 7.25, fun="jit(_normal)", cached=False)
    trace.add_span("train.model_init", 5.5, 8.0, compiles=2, compile_s=1.25,
                   cache_hits=0)
    trace.add_span("jax.compile", 10.0, 14.0, fun="jit(_step)", cached=True)
    # the reference's own programs, after the window: not the step's
    trace.add_span("jax.compile", 50.0, 52.0, fun="jit(_step_of_reference)",
                   cached=False)
    trace.add_span("jax.compile", 52.0, 53.0, fun="jit(run)", cached=False)


@pytest.mark.parametrize("spec,want", [
    ({"site": "hvd.import"}, 2.5),
    ({"site": "hvd.init"}, 1.25),
    ({"site": "train.model_init"}, 2.5),
    ({"site": "train.model_init", "arg": "compiles"}, 2.0),
    ({"site": "train.model_init", "arg": "compile_s", "scale": 1e3}, 1250.0),
    ({"site": "jax.compile"}, 0.5 + 0.75 + 4.0 + 2.0 + 1.0),
    ({"site": "jax.compile", "fun": STEP}, 4.0),
    ({"site": "jax.compile", "fun": STEP, "arg": "cached"}, 1.0),
    ({"site": "jax.compile", "fun": r"^jit\(add\)$", "arg": "cached"}, 0.0),
    ({"site": "jax.compile", "fun": "_step"}, 6.0),     # a pattern, searched
    ({"site": "train.replicate"}, None),                 # no such record
    ({"site": "jax.compile", "fun": r"^jit\(nothing\)$"}, None),
    ({"site": "hvd.init", "arg": "params"}, None),       # a record without the arg
])
def test_program_span_reads_the_ring(ring, spec, want):
    _fill()
    got = readers_program.program_span(_readings(), spec)
    assert got == (None if want is None else pytest.approx(want))


def test_program_span_says_nothing_of_a_wrapped_ring_or_an_older_program(
        ring, monkeypatch):
    _fill()
    spec = {"site": "hvd.import"}
    assert readers_program.program_span(_readings(), spec) == pytest.approx(2.5)
    for i in range(300):            # the ring holds 256: the import is gone
        trace.event("chaos.inject", n=i)
    assert trace.wrapped()
    assert readers_program.program_span(_readings(), spec) is None
    # a program from before the recorder: no wrapped(), none of the sites
    monkeypatch.delattr(trace, "wrapped")
    assert readers_program.program_span(_readings(), spec) is None


def test_program_span_reads_what_the_program_itself_records(ring):
    """Not made-up records: the recorder's own, from a compile in a span."""
    import jax
    import jax.numpy as jnp

    def _issue37_reader_step(x):
        return x * 3.0 - 37.0

    with trace.compile_span("train.model_init"):
        jax.jit(_issue37_reader_step)(jnp.ones((4,))).block_until_ready()
    r = _readings()
    mine = {"site": "jax.compile", "fun": r"^jit\(_issue37_reader_step\)$"}
    secs = readers_program.program_span(r, mine)
    assert secs is not None and secs > 0
    assert readers_program.program_span(r, dict(mine, arg="cached")) in (0.0, 1.0)
    compiles = readers_program.program_span(
        r, {"site": "train.model_init", "arg": "compiles"})
    assert compiles >= 1
    assert readers_program.program_span(r, {"site": "train.model_init"}) >= secs


# -- the phases ----------------------------------------------------------------

# instruction -> (phase, recompute, also): what phase_table gives
TABLE = {
    "fusion.1": ("forward", False, ()),
    "flash_attention_fwd.2": ("forward", False, ()),
    "fusion.3": ("backward", False, ("optimizer",)),
    "fusion.4": ("backward", True, ()),
    "while.5": ("backward", False, ()),
    "fusion.6": ("optimizer", False, ()),
    "all-reduce.7": ("exchange", False, ()),
}


def _device_events():
    """Two steps on one device, nanoseconds: a while over its body, a
    rematerialised fusion, an operation no scope names."""
    step = [("fusion.1", 0.0, 4e6), ("flash_attention_fwd.2", 4e6, 2e6),
            ("while.5", 6e6, 5e6), ("fusion.4", 7e6, 3e6),   # inside the while
            ("fusion.3", 11e6, 6e6), ("all-reduce.7", 17e6, 0.5e6),
            ("fusion.6", 17.5e6, 1.5e6), ("copy.8", 19.5e6, 1e6)]
    ops = step + [(n, s + 25e6, d) for n, s, d in step]
    return {"0": {"steps": 2, "ops": ops}}


@pytest.fixture
def capture(monkeypatch, tmp_path):
    want = trace_device.reduce_phases(_device_events(), TABLE)
    calls = []

    def phase_ms(path, table=None, module="step"):
        calls.append(path)
        return want

    monkeypatch.setattr(trace_device, "phase_ms", phase_ms)
    readers_program._phases.cache_clear()
    yield str(tmp_path), want, calls
    readers_program._phases.cache_clear()


def test_program_phase_is_the_reducers_split_and_sums_to_busy(capture, capsys):
    trace_dir, want, calls = capture
    r = _readings(trace_dir, steps=2)
    got = {ph: readers_program.program_phase(r, {"phase": ph})
           for ph in trace_device.PHASES}
    assert got == pytest.approx({
        "forward": 6.0, "backward": 11.0, "exchange": 0.5, "optimizer": 1.5,
        "unattributed": 1.0})
    assert got == pytest.approx(want["phases"])
    assert sum(got.values()) == pytest.approx(want["busy_ms"]) == pytest.approx(20.0)
    # what the backward made again lies inside it
    remade = readers_program.program_phase(r, {"key": "recompute_ms"})
    assert remade == pytest.approx(3.0) and remade <= got["backward"]
    assert readers_program.program_phase(
        r, {"phase": "forward", "scale": 1e-3}) == pytest.approx(6e-3)
    assert calls == [trace_dir]                  # one reduction a run
    line = capsys.readouterr().out
    assert line.startswith("# phases") and "copy.8" in line   # unattributed, named


def test_program_phase_gives_nothing_without_a_capture_or_a_device_plane(
        capture, monkeypatch):
    trace_dir, _, calls = capture
    spec = {"phase": "forward"}
    assert readers_program.program_phase(_readings(None, 0), spec) is None
    assert readers_program.program_phase(_readings(trace_dir, 0), spec) is None
    assert calls == []

    def no_plane(path, table=None, module="step"):
        raise ValueError("no TPU device plane")

    monkeypatch.setattr(trace_device, "phase_ms", no_plane)
    assert readers_program.program_phase(_readings(trace_dir, 3), spec) is None
    assert readers_program.program_phase(
        _readings(trace_dir, 3), {"phase": "no_such_phase"}) is None


# -- the metric files, through the harness -------------------------------------


def test_the_new_metrics_are_appended_with_their_cells():
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW[0])         # later PRs append their own after these
    assert tuple(names[first:first + len(NEW)]) == NEW
    cells = [w["name"] for w in bench["workloads"]]
    for m in bench["per_layer"][first:first + len(NEW)]:
        want = ([c for c in cells if c.startswith("qwen3-next")]
                if m["name"] == "recompute_ms" else cells)
        assert m["workloads"] == want, m["name"]
        ring_metric = NEW.index(m["name"]) < 6
        assert m["source"] == ("program_counter" if ring_metric else "device_trace")
        assert (m["layer"], m["moves"]) == (
            ("entry and state", "setup_s") if ring_metric
            else ("compiled train step", "mfu"))
        spec = harness.load_json(ROOT, "benchmark", "metrics", m["name"] + ".json")
        assert spec["reader"].startswith("benchmark.readers_program:")


@pytest.mark.parametrize("cell_name", [
    "internlm2-1.8b-s4096-1chip", "qwen3-next-80b-a3b-s8192-1chip"])
def test_per_layer_reports_every_new_metric_of_a_cell(ring, capture, cell_name):
    trace_dir, want, _ = capture
    _fill()
    cell = harness.load_cell(cell_name)
    cell.per_layer = [m for m in cell.per_layer if m in NEW]
    assert ("recompute_ms" in cell.per_layer) == cell_name.startswith("qwen3")
    out = harness.per_layer(cell, _readings(trace_dir, steps=2))
    got = {k: v for k, (v, _) in out.items()}
    assert set(got) == set(cell.per_layer)
    assert got["import_s"] == pytest.approx(2.5)
    assert got["hvd_init_s"] == pytest.approx(1.25)
    assert got["model_init_s"] == pytest.approx(2.5)
    assert got["model_init_compiles"] == 2
    assert got["step_compile_s"] == pytest.approx(4.0)    # not the reference's
    assert got["step_cache_hits"] == 1
    phases = sum(got[f"{ph}_ms"] for ph in (
        "forward", "backward", "optimizer", "unattributed"))
    assert phases + want["phases"]["exchange"] == pytest.approx(want["busy_ms"])
    assert out["model_init_compiles"][1] == "count" and out["forward_ms"][1] == "ms"
    json.dumps(out)


def test_an_older_program_leaves_the_ring_metrics_out_and_nothing_fails(
        ring, capture, monkeypatch):
    """The driver lays these files over the parent's checkout: no span, no
    counter, so no value and no error."""
    trace_dir, _, _ = capture
    trace.add_span("train.create_state", 1.0, 2.0, params=3, compiles=4,
                   compile_s=0.5)          # what the parent's ring holds
    monkeypatch.delattr(trace, "wrapped")
    cell = harness.load_cell("internlm2-1.8b-s4096-1chip")
    cell.per_layer = [m for m in cell.per_layer if m in NEW]
    out = harness.per_layer(cell, _readings(trace_dir, steps=2))
    assert sorted(out) == ["backward_ms", "forward_ms", "optimizer_ms",
                           "unattributed_ms"]
