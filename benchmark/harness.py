"""The harness: one cell, one seed, one run.

``run_cell`` is what ``run.py`` calls once it has found a chip; the tests
and ``selftest.py`` call it with CPU devices and tiny configurations.  The
order of a run:

  set-up   ``hvd.init()``; the program's own entry path
           (``training.create_train_state -> replicate_state ->
           data_parallel_train_step``); the benchmark's weights and batch
           from the seed put in the state's place; the step compiled once;
           the first ``check.steps`` steps driven through that compiled step
           and their readings kept.  ``setup_s`` ends here.
  window   the same compiled step and the same state, dispatched one ahead,
           for ``--seconds``; no compile may happen in it.
  check    the plain reference trains the same steps from the same seed, after
           the program's state is freed; every number compared is printed
           beside its limit.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import statistics
import sys
import time
from dataclasses import dataclass, field

from benchmark import check_module, check_name, resolve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
OUT_DIR = os.path.join(ROOT, ".bench_out")

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(*parts):
    print(*parts, flush=True)


def load_json(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    chips: int
    end_to_end: list = field(default_factory=list)   # metric names, this cell
    per_layer: list = field(default_factory=list)


def check_steps(cell: Cell) -> int:
    """Steps the output check follows: the configuration's, unless the
    traffic states fewer (a cell whose reference would outlast the window)."""
    return cell.traffic.get("check_steps", cell.config["check"]["steps"])


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with its configuration and
    traffic files, found by the names there."""
    bench = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    (cfg,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    cell = Cell(
        name=name, config_name=w["config"], config=load_json(root, cfg["file"]),
        traffic_name=w["traffic"],
        traffic=load_json(root, "benchmark", "traffic", w["traffic"] + ".json"),
        chips=w["chips"],
        end_to_end=[m["name"] for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m["name"] for m in bench["per_layer"] if _applies(m, name)])
    check_names(cell, root)
    return cell


def check_names(cell: Cell, root: str = ROOT):
    """Before any device work: every name of the cell that is not one of a
    table's (it holds a colon) obeys the rule of ``benchmark/__init__.py``.
    Nothing is imported but a family of that form, whose ``reference`` has to
    obey it too; a name with no colon meets its table when the run needs it."""
    named = [("family", cell.config["family"]),
             ("FLOP function", cell.config["flops"]["function"])]
    for metric in cell.per_layer:
        spec = load_json(root, "benchmark", "metrics", metric + ".json")
        named.append(("reader", spec["reader"]))
        if "flops_function" in spec:
            named.append(("FLOP function", spec["flops_function"]))
    for what, name in named:
        if ":" in name:
            check_name(name, what)
    if ":" in cell.config["family"]:
        check_module(resolve(cell.config["family"], {}, "family").reference, "reference")


class CompileMeter:
    """Counts backend compiles and their seconds through jax.monitoring (a
    persistent-cache hit is reported under the same event, as the time it
    took to load).  Copied from chip_smoke.py (PR 21)."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, duration, **_):
        if name == _COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def snapshot(self):
        return self.count, self.seconds


def device_record(devices) -> dict:
    import jax

    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())}


def peaks_for(kind: str) -> dict:
    table = load_json(HERE, "peaks.json")
    if kind not in table:
        raise KeyError(f"no published peaks for device_kind {kind!r}; add it to "
                       f"benchmark/peaks.json with its source (have {sorted(table)})")
    return table[kind]


# -- set-up -------------------------------------------------------------------


@dataclass
class Prepared:
    """What set-up hands to the window: one compiled step and its state."""

    cell: Cell
    mesh: object
    call: object              # the compiled step: (state, inputs, labels) -> (state, loss)
    state: object
    inputs: object
    labels: object
    rows: int                 # rows of the global batch
    samples_per_step: int
    first: dict               # losses, grad_norms, delta_norms of the first steps
    spans: dict
    meter: CompileMeter
    compile_seconds: float
    memory_bytes: int
    xla_flops: float | None
    kernel_in_step: bool | None


def _memory_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _xla_flops(compiled):
    try:
        cost = compiled.cost_analysis()
    except Exception as e:  # informational line only; not every backend offers it
        log(f"# cost_analysis unavailable: {type(e).__name__}: {e}")
        return None
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return float(cost["flops"]) if cost and "flops" in cost else None


def prepare(cell: Cell, seed: int, devices, meter: CompileMeter | None = None) -> Prepared:
    """Set-up: everything up to the first timed step (see the module's text)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu import training

    from benchmark import families, weights
    from benchmark.reference.chain import leaf_paths, tree_delta_norms, tree_norms

    meter = meter or CompileMeter()
    c0, s0 = meter.snapshot()
    spans = {}
    t = time.perf_counter()
    hvd.init()
    spans["hvd_init"] = time.perf_counter() - t

    config, traffic = cell.config, cell.traffic
    fam = families.family(config)
    if len(jax.devices()) == cell.chips and list(devices) == list(jax.devices()):
        mesh = hvd.world_mesh()
    else:
        mesh = Mesh(np.array(list(devices)[:cell.chips]), (hvd.WORLD_AXIS,))
    replicated = NamedSharding(mesh, P())
    by_row = NamedSharding(mesh, P(hvd.WORLD_AXIS))
    rows = traffic["samples_per_chip"] * cell.chips
    key = weights.seed_key(seed)
    k_batch, k_params, k_init = jax.random.split(key, 3)

    t = time.perf_counter()
    inputs, labels = jax.jit(
        lambda k: fam.batch(k, config, traffic, rows),
        out_shardings=(by_row, by_row))(k_batch)
    jax.block_until_ready((inputs, labels))
    spans["batch"] = time.perf_counter() - t

    model = fam.model(config)
    optimizer = families.optimizer(config["optimizer"])

    # the program's own entry path, as a job pays it; its values are not kept.
    # The sample is a host array, as chip_smoke.py's: model.init runs op by op
    # and a Mosaic kernel cannot take an input that is spread over the mesh
    t = time.perf_counter()
    sample = np.asarray(inputs[:1])
    state = training.create_train_state(model, optimizer, k_init, sample)
    state = training.replicate_state(state, mesh)
    jax.block_until_ready(state)
    spans["init"] = time.perf_counter() - t

    # the benchmark's weights in their place: the reference gets the same
    t = time.perf_counter()
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state.params)
    batch_stats = state.batch_stats
    state = None  # free the program's values before the seeded ones are made

    def make_params():
        return weights.make_params(shapes, k_params, config["init"], replicated)

    params = make_params()
    opt_state = jax.jit(optimizer.init, out_shardings=replicated)(params)
    state = training.TrainState(
        step=jax.device_put(np.zeros((), np.int32), replicated), params=params,
        opt_state=opt_state, batch_stats=batch_stats)
    jax.block_until_ready(state)
    spans["weights"] = time.perf_counter() - t

    t = time.perf_counter()
    step = training.data_parallel_train_step(
        model, optimizer, mesh=mesh, **families.step_options(config, traffic))
    lowered = step.lower(state, inputs, labels)
    kernel = None
    if fam.expects_kernel(config) and devices[0].platform == "tpu":
        kernel = "tpu_custom_call" in lowered.as_text()
    call = lowered.compile()
    spans["compile_step"] = time.perf_counter() - t
    memory = _memory_bytes(call)
    xla_flops = _xla_flops(call)

    # the first steps, through the window's own call and feed
    t = time.perf_counter()
    n_first = check_steps(cell)
    losses, grad_norms, first_gradient = [], None, None
    for i in range(n_first):
        state, loss = call(state, inputs, labels)
        losses.append(float(loss))
        if i == 0:
            tree, factor = families.first_gradient(state.opt_state, config["optimizer"])
            grad_norms = {k: v * factor for k, v in tree_norms(tree).items()}
            # to the host: the device has no room for a second copy in the window
            first_gradient = dict(zip(leaf_paths(tree), [
                jax.device_get(x) * np.float32(factor)
                for x in jax.tree_util.tree_leaves(tree)]))
    delta_norms = tree_delta_norms(state.params, make_params())
    spans["first_steps"] = time.perf_counter() - t
    c1, s1 = meter.snapshot()
    log(f"# set-up spans (s): {json.dumps({k: round(v, 3) for k, v in spans.items()})}")
    log(f"# set-up compiles: {c1 - c0} in {s1 - s0:.2f} s")
    return Prepared(
        cell=cell, mesh=mesh, call=call, state=state, inputs=inputs, labels=labels,
        rows=rows,
        samples_per_step=rows * fam.samples_per_row(traffic),
        first={"losses": losses, "grad_norms": grad_norms, "delta_norms": delta_norms,
               "first_gradient": first_gradient},
        spans=spans, meter=meter, compile_seconds=s1 - s0, memory_bytes=memory,
        xla_flops=xla_flops, kernel_in_step=kernel)


# -- the window ---------------------------------------------------------------


@dataclass
class Window:
    done: list          # host clock at the completion of each step
    losses: list
    compiles: int
    trace_dir: str | None = None
    steps_traced: int = 0


def _drive(p: Prepared, until, done: list, losses: list):
    """Dispatch one ahead: dispatch step i+1, then wait for step i's loss
    and take the clock.  ``until(now, n)`` ends it; the last step is waited
    for before returning."""
    from jax.profiler import TraceAnnotation

    clock = time.perf_counter
    with TraceAnnotation("bench.dispatch"):
        p.state, pending = p.call(p.state, p.inputs, p.labels)
    n = 0
    while True:
        with TraceAnnotation("bench.dispatch"):
            p.state, ahead = p.call(p.state, p.inputs, p.labels)
        with TraceAnnotation("bench.wait_loss"):
            pending.block_until_ready()
        now = clock()
        done.append(now)
        losses.append(pending)
        pending = ahead
        n += 1
        if until(now, n):
            break
    with TraceAnnotation("bench.wait_loss"):
        pending.block_until_ready()
    done.append(clock())
    losses.append(pending)


def run_window(p: Prepared, seconds: float, trace: bool) -> Window:
    import jax

    c0, _ = p.meter.snapshot()
    done, losses = [], []
    start = time.perf_counter()
    trace_dir, steps_traced = None, 0
    if trace:
        # a short traced stretch after the window has started, in this run only
        lead = min(seconds / 3.0, 5.0)
        _drive(p, lambda now, n: now - start >= lead, done, losses)
        trace_dir = os.path.join(OUT_DIR, "trace", p.cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        want = p.cell.traffic["trace_steps"]
        jax.profiler.start_trace(trace_dir)
        try:
            traced = []
            _drive(p, lambda now, n: n >= want - 1, traced, losses)
        finally:
            jax.profiler.stop_trace()
        steps_traced = len(traced)
        done = []  # spans across the traced stretch would time the profiler
    _drive(p, lambda now, n: now - start >= seconds, done, losses)
    c1, _ = p.meter.snapshot()
    return Window(done=done, losses=[float(x) for x in losses], compiles=c1 - c0,
                  trace_dir=trace_dir, steps_traced=steps_traced)


def end_to_end(p: Prepared, w: Window, setup_s: float, peaks: dict) -> dict:
    """Every end-to-end metric of the run: name -> (value, unit)."""
    import numpy as np

    from benchmark import families

    fam = families.family(p.cell.config)
    steps = len(w.done) - 1
    elapsed = w.done[-1] - w.done[0]
    per_s = steps * p.samples_per_step / elapsed
    k = p.cell.traffic["span_steps"]
    spans = [(w.done[i + k] - w.done[i]) / k * 1e3 for i in range(len(w.done) - k)]
    p90 = float(np.percentile(spans, 90))
    required = families.flops_per_sample(p.cell.config, p.cell.traffic)
    mfu = 100.0 * per_s * required / (p.cell.chips * peaks["bf16_flops"])
    log(f"# window: {steps} steps in {elapsed:.3f} s; step ms median "
        f"{statistics.median(spans):.3f}, p90 {p90:.3f}, "
        f"max {max(spans):.3f} over {len(spans)} spans of {k} steps")
    return {
        "setup_s": (setup_s, "s"),
        fam.throughput_metric: (per_s, f"{fam.sample_unit}/s"),
        "step_ms_p90": (p90, "ms"),
        "mfu": (mfu, "%"),
    }


# -- the output check -----------------------------------------------------------


def run_reference(cell: Cell, seed: int, device, precision: str = "float32",
                  other_first_gradient: dict | None = None,
                  keep_first_gradient: bool = False) -> dict:
    """The plain reference's readings for the cell's first steps, on one
    device, from the seed alone (and, given another first gradient, how far
    its own lies from it)."""
    import jax

    from benchmark import families, weights
    from benchmark.reference import chain

    config, traffic = cell.config, cell.traffic
    fam = families.family(config)
    ref = families.reference(config)
    rows = traffic["samples_per_chip"] * cell.chips
    k_batch, k_params, _ = jax.random.split(weights.seed_key(seed), 3)
    with jax.default_device(device), jax.default_matmul_precision("highest"):
        inputs, labels = jax.jit(lambda k: fam.batch(k, config, traffic, rows))(k_batch)
        model = fam.model(config)
        shapes = jax.eval_shape(
            lambda k, x: model.init(k, x)["params"], jax.random.PRNGKey(0), inputs[:1])

        def make_params():
            return weights.make_params(shapes, k_params, config["init"])

        stages, loss_backward = ref.build(config, traffic)
        return chain.train_steps(
            stages, loss_backward, dict(make_params()), make_params, inputs, labels,
            config["optimizer"], check_steps(cell), precision,
            other_first_gradient, keep_first_gradient)


def _median_leaf(norms: dict) -> float:
    """The median leaf's norm, over the leaves whose norm is not exactly zero
    (a residual branch behind a zero scale has an exactly zero first
    gradient: more than half of ResNet-50's leaves)."""
    positive = [v for v in norms.values() if v > 0]
    if not positive:
        raise ValueError("every leaf of the reference has a zero norm")
    return statistics.median(positive)


def worst_leaf_gap(got: dict, want: dict):
    """The largest gap between a leaf's norm and the reference's, against
    the reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero).  Returns (gap, leaf)."""
    if set(got) != set(want):
        raise ValueError(f"leaves differ: {sorted(set(got) ^ set(want))[:6]}")
    floor = _median_leaf(want)
    gaps = {k: abs(got[k] - want[k]) / max(want[k], floor) for k in want}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def worst_leaf_diff(diff: dict, want: dict, leaves: str = ""):
    """The largest norm of a leaf's difference from the reference's, against
    the same scale as ``worst_leaf_gap``, over the leaves whose path matches
    ``leaves``.  Returns (share, leaf)."""
    import re

    floor = _median_leaf(want)
    shares = {k: diff[k] / max(want[k], floor) for k in want if re.search(leaves, k)}
    leaf = max(shares, key=shares.get)
    return shares[leaf], leaf


def compare(first: dict, ref: dict, limits: dict, diff_norms: dict | None = None,
            diff_leaves: str = "") -> list:
    """Each number compared, beside its limit.  ``diff_norms``: per leaf, the
    norm of the difference between the two first gradients, where taken."""
    rows = []
    for i, (a, b) in enumerate(zip(first["losses"], ref["losses"]), 1):
        rows.append({"name": f"loss_gap_step{i}", "value": abs(a - b) / abs(b),
                     "limit": limits.get("loss_gap"), "detail": f"{a:.6f} vs {b:.6f}"})
    for name, key in (("grad_norm_gap", "grad_norms"), ("delta_norm_gap", "delta_norms")):
        gap, leaf = worst_leaf_gap(first[key], ref[key])
        rows.append({"name": name, "value": gap, "limit": limits.get(name), "detail": leaf})
    if diff_norms is not None:
        share, leaf = worst_leaf_diff(diff_norms, ref["grad_norms"], diff_leaves)
        rows.append({"name": "grad_diff_gap", "value": share,
                     "limit": limits.get("grad_diff_gap"), "detail": leaf})
    for r in rows:
        r["ok"] = (math.isfinite(r["value"])
                   and (r["limit"] is None or r["value"] <= r["limit"]))
    return rows


# -- per-layer metrics ----------------------------------------------------------


def per_layer(cell: Cell, readings) -> dict:
    """name -> (value, unit) for the cell's per-layer metrics that found
    something to read, each through the reader its own file names."""
    from benchmark import readers

    units = {m["name"]: m["unit"] for m in load_json(ROOT, "BENCHMARK.json")["per_layer"]}
    out = {}
    for name in cell.per_layer:
        spec = load_json(HERE, "metrics", name + ".json")
        value = readers.reader(spec["reader"])(readings, spec)
        if value is not None:
            readings.values[name] = value
            out[name] = (value, units[name])
    return out


# -- one run --------------------------------------------------------------------


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float | None = None) -> dict:
    """Set-up, window and check; returns the result object of the last line."""
    from benchmark import families, readers, trace as tr

    t_start = time.perf_counter() if t_start is None else t_start
    p = prepare(cell, seed, devices)
    record = device_record(devices)
    peaks = peaks_for(record["kind"]) if record["platform"] == "tpu" else {
        "bf16_flops": float("nan"), "hbm_bytes_per_s": float("nan")}
    required = families.flops_per_sample(cell.config, cell.traffic) * p.samples_per_step
    log(f"# required FLOPs a step (benchmark/flops.py): {required:.4e}; XLA's "
        f"cost_analysis of the compiled step, a cross-check only: "
        f"{'unavailable' if p.xla_flops is None else format(p.xla_flops * cell.chips, '.4e')}")
    log(f"# first losses: {p.first['losses']}")
    setup_s = time.perf_counter() - t_start

    w = run_window(p, seconds, trace)
    stats = [d.memory_stats() or {} for d in p.mesh.devices.flat]
    memory_peak = max([p.memory_bytes] + [s.get("peak_bytes_in_use", 0) for s in stats])
    log(f"# memory: compiled step {p.memory_bytes} B (arguments + outputs - aliased + "
        f"temporaries); memory_stats peak {[s.get('peak_bytes_in_use') for s in stats]}")
    log(f"# window losses: first {w.losses[0]:.5f}, last {w.losses[-1]:.5f}")
    failed = sum(1 for x in w.losses if not math.isfinite(x))
    metrics = end_to_end(p, w, setup_s, peaks)

    # free the program's state, then the reference from the seed alone
    first, first_device = p.first, p.mesh.devices.flat[0]
    p.state = p.call = p.inputs = p.labels = None
    t = time.perf_counter()
    check = cell.config["check"]
    ref = run_reference(cell, seed, first_device,
                        other_first_gradient=first["first_gradient"])
    log(f"# reference: {time.perf_counter() - t:.2f} s (not in setup_s)")
    rows = compare(first, ref, check["limits"], ref.get("grad_diff_norms"),
                   check.get("diff_leaves", ""))
    rows.append({"name": "compiles_in_window", "value": w.compiles, "limit": 0,
                 "ok": w.compiles == 0, "detail": ""})
    rows.append({"name": "non_finite_losses", "value": failed, "limit": 0,
                 "ok": failed == 0, "detail": ""})
    if p.kernel_in_step is not None:
        rows.append({"name": "kernel_missing_from_step", "value": int(not p.kernel_in_step),
                     "limit": 0, "ok": p.kernel_in_step, "detail": "tpu_custom_call"})
    for r in rows:
        log(f"# check {r['name']}: {r['value']:.6g} (limit {r['limit']}) "
            f"{'ok' if r['ok'] else 'NOT OK'} {r['detail']}")
    for r in rows:  # the numbers compared, each beside its limit: stderr's last lines
        print(f"check {r['name']}: {r['value']!r} (limit {r['limit']})", file=sys.stderr)
    sys.stderr.flush()

    result = {"correct": all(r["ok"] for r in rows), "attempted": len(w.losses),
              "failed": failed, "device": dict(record, memory_peak_bytes=int(memory_peak))}
    if trace:
        t = tr.load_xplane(tr.find_xplane(w.trace_dir))
        readings = readers.Readings(
            config=cell.config, traffic=cell.traffic, peaks=peaks, chips=cell.chips,
            rows_per_step=p.rows, spans=p.spans, compile_seconds=p.compile_seconds,
            trace=t, trace_dir=w.trace_dir, steps_traced=w.steps_traced)
        result["metrics"] = per_layer(cell, readings)
        result["device"].update(busy_s=tr.busy_ns(t) / 1e9, window_s=tr.window_ns(t) / 1e9)
        result["breakdown"] = {"device_ops": tr.top_ops(t), "idle_gaps": tr.idle_gaps(t)}
        log(f"# trace: {w.steps_traced} steps traced; programs run per device "
            f"{ {d: len(v) for d, v in t.modules.items()} }; end-to-end in this traced run "
            f"(not reported): { {k: round(v[0], 4) for k, v in metrics.items()} }")
    else:
        result["metrics"] = {k: v for k, v in metrics.items() if k in cell.end_to_end}
    result["metrics"] = {k: {"value": v, "unit": u} for k, (v, u) in result["metrics"].items()}
    # last in the line: each number compared, beside its limit
    result["checks"] = {
        r["name"]: {"value": r["value"] if math.isfinite(r["value"]) else str(r["value"]),
                    "limit": r["limit"]} for r in rows}
    return result
