#!/usr/bin/env python
"""Trace-recorder benchmark: the ISSUE-15 exactness/overhead bars.

Every leg emits ONE bench-style JSON line on stdout (human summary on
stderr) — the flash_bench/guard_bench contract.  Legs:

  * ``trace_oracle`` — the SAME compiled train step driven through
    ``training.fit_epoch`` with tracing ON vs OFF: state and loss must
    be BIT-identical (tracing is host-side bookkeeping; it never
    touches the program).
  * ``trace_collectives`` — StableHLO collective inventory of the
    train step built with tracing on vs off: the lowered text must be
    IDENTICAL (hash-compared), so added collectives are EXACTLY 0 and
    added compiles are structurally 0 — the acceptance bars.
  * ``trace_overhead`` — median per-step wall time, tracing ON vs OFF,
    measured in INTERLEAVED A/B rounds (the guard_bench idiom: drift on
    a contended box cancels out of the ratio).  Bar:
    ``overhead_frac <= 0.02`` at default settings.  Only meaningful in
    the full run (the smoke step is ~ms and aliases timer noise).
  * ``trace_serve`` — a traced serving burst: the ``/trace``-shape
    Chrome export must be VALID trace-event JSON (every event carries
    name/ph/ts; complete events carry dur), steady state stays
    compile-free with tracing on AND off (zero extra programs), greedy
    tokens are identical either way, and the per-request TTFT
    decomposition (queued + prefill chunks + first decode) sums to the
    measured TTFT within tolerance.

Usage:
  trace_bench.py            # full legs — what the CI trace-smoke job runs
  trace_bench.py --smoke    # tiny fast pass: oracle/collectives/export
                            # meaningful, overhead_frac is NOT
"""

import argparse
import hashlib
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu import trace, training  # noqa: E402
from horovod_tpu.common.retry import env_int  # noqa: E402
from horovod_tpu.models.transformer import (  # noqa: E402
    Transformer, TransformerConfig,
)
from horovod_tpu.serving.engine import ServeConfig, ServingEngine  # noqa: E402
from horovod_tpu.trace import export as trace_export  # noqa: E402

ITERS = env_int("HVD_TPU_BENCH_ITERS", 20)
WARMUP = env_int("HVD_TPU_BENCH_WARMUP", 3)

_COLLECTIVE_RE = re.compile(
    r"stablehlo\.(all_reduce|all_gather|reduce_scatter|"
    r"collective_permute|all_to_all)")


def _emit(row):
    row["t_end"] = round(time.time(), 3)
    print(json.dumps(row), flush=True)


def _say(msg):
    print(f"[trace_bench] {msg}", file=sys.stderr, flush=True)


def _copy(state):
    return jax.tree_util.tree_map(jnp.copy, state)


def _build(smoke):
    cfg = TransformerConfig(
        vocab_size=256,
        num_layers=2 if smoke else 4,
        num_heads=4 if smoke else 8,
        head_dim=16 if smoke else 32,
        max_seq_len=64 if smoke else 128,
        dtype=jnp.float32,
        attention_impl="dot",
        causal=True,
    )
    model = Transformer(cfg)
    batch = 4 if smoke else 16
    rs = np.random.RandomState(0)
    x = rs.randint(1, cfg.vocab_size, size=(batch, cfg.max_seq_len)
                   ).astype(np.int32)
    y = rs.randint(0, cfg.vocab_size, size=(batch, cfg.max_seq_len)
                   ).astype(np.int32)
    opt = optax.adamw(1e-3)
    state = training.replicate_state(training.create_train_state(
        model, opt, jax.random.PRNGKey(0), x[:1]))
    step = training.data_parallel_train_step(model, opt, guard=False)
    return cfg, step, state, x, y


def _fit(step, state, x, y, n):
    """n steps through fit_epoch (the traced loop) on a list loader."""
    return training.fit_epoch(step, state, [(x, y)] * n)


def run_train_legs(args, t_start):
    _, step, state, x, y = _build(args.smoke)

    # -- trace_oracle: bit-identical state + loss ----------------------------
    trace.configure(enabled=True)
    sa, la = _fit(step, _copy(state), x, y, 3)
    trace.configure(enabled=False)
    sb, lb = _fit(step, _copy(state), x, y, 3)
    trace.configure(enabled=True)
    bit_exact = float(la) == float(lb)
    for pa, pb in zip(jax.tree_util.tree_leaves(sa.params),
                      jax.tree_util.tree_leaves(sb.params)):
        if not np.array_equal(np.asarray(pa), np.asarray(pb)):
            bit_exact = False
    _emit({"bench": "trace_oracle", "steps": 3, "bit_exact": bit_exact,
           "t_start": t_start})
    _say(f"oracle bit_exact={bit_exact}")

    # -- trace_collectives: identical lowered program ------------------------
    def lowered():
        return step.lower(_copy(state), x, y).as_text()

    trace.configure(enabled=True)
    text_on = lowered()
    trace.configure(enabled=False)
    text_off = lowered()
    trace.configure(enabled=True)
    n_on = len(_COLLECTIVE_RE.findall(text_on))
    n_off = len(_COLLECTIVE_RE.findall(text_off))
    same = (hashlib.sha256(text_on.encode()).hexdigest()
            == hashlib.sha256(text_off.encode()).hexdigest())
    _emit({
        "bench": "trace_collectives",
        "collectives_traced": n_on,
        "collectives_untraced": n_off,
        "added_collectives": n_on - n_off,
        "stablehlo_identical": same,
        "t_start": t_start,
    })
    _say(f"collectives traced={n_on} untraced={n_off} identical={same}")

    # -- trace_overhead: interleaved A/B -------------------------------------
    k = 4  # steps per round: the per-epoch base sync amortizes like prod
    sa, sb = _copy(state), _copy(state)
    for _ in range(max(1, WARMUP // 2)):
        trace.configure(enabled=True)
        sa, _ = _fit(step, sa, x, y, k)
        trace.configure(enabled=False)
        sb, _ = _fit(step, sb, x, y, k)
    t_on, t_off = [], []
    for _ in range(max(1, ITERS)):
        trace.configure(enabled=True)
        t0 = time.perf_counter()
        sa, _ = _fit(step, sa, x, y, k)
        jax.block_until_ready(sa.params)
        t1 = time.perf_counter()
        trace.configure(enabled=False)
        sb, _ = _fit(step, sb, x, y, k)
        jax.block_until_ready(sb.params)
        t2 = time.perf_counter()
        t_on.append((t1 - t0) / k)
        t_off.append((t2 - t1) / k)
    trace.configure(enabled=True)
    ms_on = float(np.median(t_on) * 1e3)
    ms_off = float(np.median(t_off) * 1e3)
    overhead = (ms_on - ms_off) / ms_off
    _emit({
        "bench": "trace_overhead",
        "step_ms_traced": round(ms_on, 3),
        "step_ms_untraced": round(ms_off, 3),
        "overhead_frac": round(overhead, 4),
        "iters": ITERS,
        "t_start": t_start,
    })
    _say(f"overhead {overhead * 100:.2f}% ({ms_off:.1f} -> {ms_on:.1f} ms)")


def _valid_chrome(doc) -> bool:
    evs = doc.get("traceEvents")
    if not isinstance(evs, list) or not evs:
        return False
    for e in evs:
        if not isinstance(e.get("name"), str) or "ph" not in e:
            return False
        if e["ph"] in ("X", "i") and "ts" not in e:
            return False
        if e["ph"] == "X" and "dur" not in e:
            return False
    return True


def run_serve_leg(args, t_start):
    cfg = TransformerConfig(
        vocab_size=128, num_layers=1, num_heads=2, head_dim=16,
        max_seq_len=64, dtype=jnp.float32, attention_impl="dot",
        causal=True)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    serve = ServeConfig(decode_tiers=(1, 2, 4), token_budget=512,
                        prefill_chunk=16)
    n_req = 6 if args.smoke else 16
    rs = np.random.RandomState(7)

    def run_burst(eng):
        rids = [eng.submit(rs.randint(1, 100, size=rs.randint(4, 33)),
                           int(rs.randint(2, 6))) for _ in range(n_req)]
        toks = eng.run()
        return rids, {r: toks[r].tolist() for r in rids}

    trace.configure(enabled=True)
    eng_on = ServingEngine(cfg, params, serve=serve)
    eng_on.warmup()
    progs_warm = eng_on.program_count
    since = trace.now()
    rids, toks_on = run_burst(eng_on)
    compile_free_on = eng_on.program_count == progs_warm
    recs = trace.snapshot(since=since)

    trace.configure(enabled=False)
    eng_off = ServingEngine(cfg, params, serve=serve)
    eng_off.warmup()
    rs = np.random.RandomState(7)  # same request stream
    _, toks_off = run_burst(eng_off)
    compile_free_off = eng_off.program_count == progs_warm
    trace.configure(enabled=True)

    tokens_identical = toks_on == toks_off

    doc = trace_export.chrome_trace(since=since, records=recs)
    valid = _valid_chrome(doc)

    decomp = [d for d in (trace_export.request_decomposition(recs, r)
                          for r in rids) if d is not None]
    max_err = max((d["err_s"] for d in decomp), default=None)
    max_rel = max((d["err_s"] / max(d["measured_ttft_s"], 1e-9)
                   for d in decomp), default=None)
    _emit({
        "bench": "trace_serve",
        "requests": n_req,
        "events": len(doc["traceEvents"]),
        "valid_trace_json": valid,
        "tokens_identical": tokens_identical,
        "compile_free_traced": compile_free_on,
        "compile_free_untraced": compile_free_off,
        "programs": progs_warm,
        "ttft_decomp_requests": len(decomp),
        "ttft_decomp_max_err_s": (None if max_err is None
                                  else round(max_err, 4)),
        "ttft_decomp_max_rel_err": (None if max_rel is None
                                    else round(max_rel, 4)),
        "t_start": t_start,
    })
    _say(f"serve valid={valid} tokens_identical={tokens_identical} "
         f"decomp n={len(decomp)} max_err={max_err}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CPU-safe pass (CI; overhead_frac not "
                    "meaningful)")
    args = ap.parse_args(argv)
    from horovod_tpu.utils import compile_cache

    compile_cache.enable()

    hvd.init()
    t_start = round(time.time(), 3)
    run_train_legs(args, t_start)
    run_serve_leg(args, t_start)
    return 0


if __name__ == "__main__":
    sys.exit(main())
