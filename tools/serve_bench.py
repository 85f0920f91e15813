#!/usr/bin/env python
"""Continuous-batching serving benchmark (PERF.md rounds 8 + 9).

Generates synthetic OPEN-LOOP loads — requests arrive on their own
clock, independent of completions, the way real traffic does — and
drives them through ``horovod_tpu.serving``:

  continuous   the ServingEngine: iteration-level admit/evict over the
               paged KV cache (Orca-style), requests staged to device
               through the DevicePrefetcher while steps compute;
  static       the pre-Orca baseline (``ServingEngine.run_static``):
               fixed request batches held until every member finishes,
               contiguous worst-case KV reservations.  Batches start
               only once all members have ARRIVED (honest open-loop
               head-of-line blocking);
  prefix_off / prefix_on
               the round-9 shared-prefix A/B: N requests over K prompt
               templates (the shared-system-prompt production shape)
               on ONE shared engine, prefix cache toggled between legs
               — same params, same compiled tier programs, so the A/B
               isolates the CACHE.  Emits TTFT p50/p99,
               ``prefix_hit_rate`` and ``prefill_tokens_computed``;
  unchunked / chunked
               the round-9 burst A/B: a steady decode load with a
               long-prompt burst injected mid-run, once on an engine
               that prefills whole prompts and once on one that
               streams them in ``HVD_TPU_SERVE_PREFILL_CHUNK``-token
               chunks packed beside the decode batch.  Emits the
               steady requests' inter-token decode-gap p50/p99 and the
               spike ratio — chunking's claim is the flat p99;
  multichip    the round-10 tensor-sharded A/B (--shards, default 8,
               smoke 2): one model head-sharded over the virtual ICI
               mesh vs the single-device engine on the same templated
               load — token-identity asserted, per-chip decode read
               bytes and psum stream both modeled AND measured from
               the lowered StableHLO (modeled == measured or the leg
               fails).
  spec_base_* / spec_on_*
               the round-15 speculative-decoding A/B: the same load
               driven through a plain engine and one with the
               prompt-lookup drafter on (fresh engines — the spec
               menu differs), once on a TEMPLATE-HEAVY load (periodic
               prompts, the n-gram drafter's home turf) and once on
               ADVERSARIAL-RANDOM text (the drafter's worst case —
               the bit-identity guarantee is the claim there).  Emits
               ``acceptance_rate``, drafted/accepted/rolled-back
               token counts, ``tokens_per_step`` and the verify-span
               trace columns; byte-identical outputs asserted per
               load before reporting.

Greedy sampling everywhere, so the bench asserts token-for-token
identical outputs across every A/B before it reports a single number
(the oracle from tests/test_serving.py, run on the bench's own load —
including bit-identical streams with the prefix cache on vs off).

Every leg emits ONE bench-style JSON line on stdout (human summary on
stderr).  Scheduling, caching and chunking wins are CPU-measurable —
they are steps/tokens saved, not FLOPs saved — so the smoke legs run
in CI; the ``kv_model`` leg carries the modeled per-decode-step K/V
read bytes (paged + GQA + window + page-tier gather vs a contiguous
max-seq MHA cache), pinning the memory-traffic claim that needs a chip
to measure in wall-clock.

Usage:
  serve_bench.py                # full CPU-host run (more requests)
  serve_bench.py --smoke        # tiny CI leg (see .github/workflows)
  serve_bench.py --requests N --rate R --batch B --seed S
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

# expose the virtual multichip world BEFORE jax can be imported (the
# MULTICHIP sharded leg needs the devices; the single-device legs are
# unaffected — they run on device 0): raw parse, same bootstrap as
# collective_bench/transformer_bench
try:  # contract-ok: env -- bootstrap runs before the package's env_int is importable
    _WORLD = max(1, int(os.environ.get("HVD_TPU_BENCH_WORLD", "") or 8))
except ValueError:
    _WORLD = 8
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + f" --xla_force_host_platform_device_count={_WORLD}"
    ).strip()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from horovod_tpu.models.transformer import (  # noqa: E402
    Transformer, TransformerConfig,
)
from horovod_tpu.ops.comm_model import (  # noqa: E402
    measured_tier_bytes, modeled_serve_psum_bytes, serve_gather_read_bytes,
)
from horovod_tpu.serving import (  # noqa: E402
    Request, ServeConfig, ServingEngine, modeled_decode_read_bytes,
)
from horovod_tpu import trace  # noqa: E402
from horovod_tpu.trace import export as trace_export  # noqa: E402


def _percentile(xs, p):
    return float(np.percentile(np.asarray(xs, np.float64), p)) if xs else 0.0


def build_load(rs, n, *, p_lo, p_hi, gen_short, gen_long, frac_long):
    """The skewed load continuous batching exists for: most requests
    generate a few tokens, a minority generate many — in a static batch
    the minority holds every slot hostage."""
    load = []
    for _ in range(n):
        plen = int(rs.randint(p_lo, p_hi + 1))
        if rs.random_sample() < frac_long:
            gen = int(gen_long)
        else:
            gen = int(rs.randint(1, gen_short + 1))
        prompt = rs.randint(1, 120, size=plen).astype(np.int32)
        load.append((prompt, gen))
    return load


def build_prefix_load(rs, n, *, templates, t_len, s_lo, s_hi, gen):
    """N requests over K shared prompt templates — the dominant
    production shape (shared system prompts, few-shot headers) the
    prefix cache exists for."""
    temps = [rs.randint(1, 120, size=t_len).astype(np.int32)
             for _ in range(templates)]
    load = []
    for _ in range(n):
        t = temps[rs.randint(templates)]
        suffix = rs.randint(
            1, 120, size=rs.randint(s_lo, s_hi + 1)).astype(np.int32)
        load.append((np.concatenate([t, suffix]), int(rs.randint(1, gen + 1))))
    return load


def _ttfts(token_log):
    first = {}
    for rid, emit, arr in token_log:
        if rid not in first:
            first[rid] = emit - arr
    return list(first.values())


def _leg_stats(leg, token_log, wall_s, results):
    lats = [emit - arr for (_rid, emit, arr) in token_log]
    ttfts = _ttfts(token_log)
    # wall-clock leg extents so bench rows correlate with trace dumps /
    # flight bundles from the same run (epoch seconds, the export axis)
    t_end = time.time()
    return {
        "bench": "serve",
        "leg": leg,
        "requests": len(results),
        "tokens": len(token_log),
        "wall_s": round(wall_s, 4),
        "t_start": round(t_end - wall_s, 3),
        "t_end": round(t_end, 3),
        "throughput_tokens_per_s": round(len(token_log) / wall_s, 2),
        "p50_token_latency_s": round(_percentile(lats, 50), 4),
        "p99_token_latency_s": round(_percentile(lats, 99), 4),
        "ttft_p50_s": round(_percentile(ttfts, 50), 4),
        "ttft_p99_s": round(_percentile(ttfts, 99), 4),
    }


def run_continuous(eng, load, interarrival, leg="continuous", id_base=0):
    """One open-loop continuous leg; ``load`` is [(prompt, gen)] or
    [(prompt, gen, due_offset_s)] for non-uniform arrival (bursts)."""
    eng.token_log = []
    hits0 = eng.scheduler.prefix_hit_blocks
    look0 = eng.scheduler.prefix_lookup_blocks
    comp0 = eng.prefill_tokens_computed
    trace_t0 = trace.now()
    t0 = time.perf_counter()

    def source():
        for i, item in enumerate(load):
            prompt, gen = item[0], item[1]
            due = t0 + (item[2] if len(item) > 2 else i * interarrival)
            now = time.perf_counter()
            if due > now:
                time.sleep(due - now)
            # arrival = the open-loop injection time (due), NOT the
            # yield time: when staging backpressure pulls the generator
            # late, that queueing delay belongs IN the latency — the
            # static leg stamps due, and the A/B must match
            yield Request(id=id_base + i, prompt=prompt, max_new_tokens=gen,
                          arrival=due)

    eng.attach_source(source())
    results = eng.run()
    wall = time.perf_counter() - t0
    results = {rid - id_base: results[rid]
               for rid in (id_base + i for i in range(len(load)))}
    row = _leg_stats(leg, eng.token_log, wall, results)
    row["kv_occupancy"] = round(eng.allocator.peak_occupancy, 4)
    row["evictions"] = eng.scheduler.evictions
    row["compiled_programs"] = eng.program_count
    lookups = eng.scheduler.prefix_lookup_blocks - look0
    hits = eng.scheduler.prefix_hit_blocks - hits0
    row["prefix_hit_rate"] = round(hits / lookups, 4) if lookups else 0.0
    row["prefill_tokens_computed"] = eng.prefill_tokens_computed - comp0
    # per-request TTFT decomposition from the leg's OWN spans (queued +
    # prefill chunks + first decode must sum to the measured TTFT —
    # docs/TRACING.md; the CI smoke asserts the tolerance).  Requests
    # whose early spans the ring already overwrote are skipped.
    if trace.enabled():
        recs = trace.snapshot(since=trace_t0)
        decomp = [d for d in
                  (trace_export.request_decomposition(recs, id_base + i)
                   for i in range(len(load))) if d is not None]
        row["ttft_decomp_requests"] = len(decomp)
        row["ttft_decomp_max_err_s"] = (
            round(max(d["err_s"] for d in decomp), 4) if decomp else None)
    return row, results


def run_static(eng, load, interarrival, batch):
    eng.token_log = []
    comp0 = eng.prefill_tokens_computed
    t0 = time.perf_counter()
    results = {}
    for at in range(0, len(load), batch):
        chunk = []
        for i in range(at, min(at + batch, len(load))):
            prompt, gen = load[i]
            due = t0 + i * interarrival
            now = time.perf_counter()
            if due > now:  # the batch waits for its slowest arrival
                time.sleep(due - now)
            chunk.append(Request(id=i, prompt=prompt, max_new_tokens=gen,
                                 arrival=due))
        results.update(eng.run_static(chunk, batch))
    wall = time.perf_counter() - t0
    row = _leg_stats("static", eng.token_log, wall, results)
    row["kv_occupancy"] = round(eng.allocator.peak_occupancy, 4)
    row["evictions"] = 0
    row["compiled_programs"] = eng.program_count
    row["prefix_hit_rate"] = 0.0
    row["prefill_tokens_computed"] = eng.prefill_tokens_computed - comp0
    return row, results


def _decode_gaps(token_log, steady_ids):
    """Inter-token gaps of the steady requests — the latency a decode
    user feels while someone else's long prompt streams in."""
    last = {}
    gaps = []
    for rid, emit, _arr in token_log:
        if rid in steady_ids and rid in last:
            gaps.append(emit - last[rid])
        last[rid] = emit
    return gaps


def run_burst_leg(cfg, params, serve_cfg, steady, burst, steady_ids, leg):
    """One chunked-vs-unchunked burst leg on a FRESH engine (the chunk
    tier menu differs between the two, so programs can't be shared the
    way the prefix A/B shares them).  The steady load runs once WITHOUT
    the burst first — the same engine's no-burst decode-gap p99 is the
    denominator of the flatness claim (``flatness_x``: how much the
    burst moved the steady requests' p99 inter-token latency)."""
    eng = ServingEngine(cfg, params, serve=serve_cfg)
    warmed = eng.warmup()
    run_continuous(eng, steady, None, leg="baseline", id_base=500000)
    nb_gaps = _decode_gaps(
        eng.token_log, {500000 + i for i in range(len(steady))})
    p99_nb = _percentile(nb_gaps, 99)
    row, results = run_continuous(eng, steady + burst, None, leg=leg)
    gaps = _decode_gaps(eng.token_log, steady_ids)
    p50, p99 = _percentile(gaps, 50), _percentile(gaps, 99)
    row["p50_decode_gap_s"] = round(p50, 4)
    row["p99_decode_gap_s"] = round(p99, 4)
    row["p99_decode_gap_noburst_s"] = round(p99_nb, 4)
    row["decode_gap_spike_x"] = round(p99 / p50, 2) if p50 else 0.0
    row["flatness_x"] = round(p99 / p99_nb, 2) if p99_nb else 0.0
    row["compile_free"] = row.pop("compiled_programs") == warmed
    return row, results


def kv_model_leg(cfg, serve_cfg, context_len, page_tiers):
    ctx_pages = -(-context_len // serve_cfg.block_size)
    tier = next((t for t in page_tiers if t >= ctx_pages), page_tiers[-1])
    m = modeled_decode_read_bytes(
        context_len,
        block_size=serve_cfg.block_size,
        num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads or cfg.num_heads,
        head_dim=cfg.head_dim,
        num_layers=cfg.num_layers,
        window=cfg.window,
        dtype_bytes=jnp.dtype(cfg.dtype).itemsize,
        max_seq_len=cfg.max_seq_len,
        gather_pages=tier if cfg.window is None else None,
    )
    full_width = modeled_decode_read_bytes(
        context_len,
        block_size=serve_cfg.block_size,
        num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads or cfg.num_heads,
        head_dim=cfg.head_dim,
        num_layers=cfg.num_layers,
        window=cfg.window,
        dtype_bytes=jnp.dtype(cfg.dtype).itemsize,
        max_seq_len=cfg.max_seq_len,
    )
    return {
        "bench": "serve",
        "leg": "kv_model",
        "t_start": round(time.time(), 3),
        "t_end": round(time.time(), 3),
        "context_len": context_len,
        "kv_occupancy": None,  # schema parity with the measured legs
        "throughput_tokens_per_s": None,
        "p99_token_latency_s": None,
        # kernel reads (the _kb_range block-skip term) AND the gather
        # copy this engine materializes first — now bounded by the live
        # max-context PAGE TIER instead of max_blocks (the round-8
        # honest second term, closed); gathered_bytes_untiered keeps
        # the old max_blocks-wide number for comparison
        "paged_read_bytes_per_decode_step": m["paged_bytes"],
        "gathered_bytes_per_decode_step": m["gathered_bytes"],
        "gathered_bytes_untiered": full_width["gathered_bytes"],
        "full_read_bytes_per_decode_step": m["full_bytes"],
        "pages_read": m["pages_read"],
        "pages_gathered": m["pages_gathered"],
        "read_reduction_x": round(m["full_bytes"] / m["paged_bytes"], 2),
        "gather_reduction_x": round(m["full_bytes"] / m["gathered_bytes"], 2),
    }


def run_multichip_leg(shards, n_requests, seed):
    """The tensor-sharded A/B (ISSUE 12): ONE model over ``shards``
    chips of the ICI mesh — kv heads + the paged pool head-sharded,
    Megatron FFN, one psum per sublayer — against a single-device
    engine on the SAME templated load.  The oracle (token-identical
    streams) is asserted before any number is reported; the byte
    columns carry modeled AND StableHLO-measured per-chip decode reads
    and psum stream (the PR-7 modeled == measured idiom), which is the
    CPU-measurable form of the claim (per-chip HBM decode reads cut by
    the shard factor — the wall-clock twin needs a chip)."""
    kv = max(2, shards)  # kv heads are the shard seam: kv % shards == 0
    cfg = TransformerConfig(
        vocab_size=128, num_layers=2, num_heads=2 * kv, num_kv_heads=kv,
        head_dim=16, max_seq_len=96, dtype=jnp.float32,
        attention_impl="dot", causal=True)
    serve = dict(block_size=8, num_blocks=0, token_budget=256, watermark=2,
                 prefill_tiers=(32,), decode_tiers=(1, 2, 4),
                 prefill_chunk=8)
    params = params_for(cfg)
    rs = np.random.RandomState(seed + 2)
    load = build_prefix_load(rs, n_requests, templates=4, t_len=24,
                             s_lo=2, s_hi=8, gen=6)

    def drive(eng):
        t0 = time.perf_counter()
        ids = [eng.submit(p, max_new_tokens=g) for p, g in load]
        out = eng.run()
        return [out[r] for r in ids], time.perf_counter() - t0

    single = ServingEngine(cfg, params, serve=ServeConfig(**serve))
    single.warmup()
    ref_out, _ = drive(single)
    eng = ServingEngine(cfg, params,
                        serve=ServeConfig(shards=shards, **serve))
    warmed = eng.warmup()
    out, wall = drive(eng)
    for i, (a, b) in enumerate(zip(out, ref_out)):  # the standing oracle
        if not np.array_equal(a, b):
            print(f"MULTICHIP ORACLE MISMATCH on request {i}",
                  file=sys.stderr)
            return None

    # modeled == measured on the decode program the engine dispatches:
    # per-chip page-gather reads and the per-step psum stream, at the
    # largest decode tier over a half-max-context page tier
    bt = max(eng.decode_tiers)
    ctx_ref = cfg.max_seq_len // 2
    pt = next(t for t in eng.page_tiers
              if t >= -(-ctx_ref // serve["block_size"]))
    rows = {}
    for name, e, s in (("shard1", single, 1), ("sharded", eng, shards)):
        txt = e.lowered_decode_text(batch_tier=bt, pages=pt)
        m = modeled_decode_read_bytes(
            ctx_ref, block_size=serve["block_size"],
            num_heads=cfg.num_heads, num_kv_heads=kv,
            head_dim=cfg.head_dim, num_layers=cfg.num_layers,
            dtype_bytes=4, max_seq_len=cfg.max_seq_len,
            gather_pages=pt, shards=s)
        psum = modeled_serve_psum_bytes(
            bt, 1, cfg.d_model, cfg.num_layers, s, "float32")
        measured_reads = serve_gather_read_bytes(txt)["gather_bytes"]
        measured_psum = measured_tier_bytes(txt, [0] * s)["ici_bytes"]
        if measured_reads != bt * m["gathered_bytes"] or \
                measured_psum != psum["stream_bytes"]:
            print(f"MULTICHIP MODEL MISMATCH ({name}): reads "
                  f"{measured_reads} vs {bt * m['gathered_bytes']}, psum "
                  f"{measured_psum} vs {psum['stream_bytes']}",
                  file=sys.stderr)
            return None
        rows[name] = (m, psum, measured_reads, measured_psum)
    m, psum, meas_r, meas_p = rows["sharded"]
    m1, _, meas_r1, _ = rows["shard1"]
    toks = sum(len(t) for t in out)
    row = {
        "bench": "serve",
        "leg": "multichip",
        "t_start": round(time.time() - wall, 3),
        "t_end": round(time.time(), 3),
        "n_devices": jax.device_count(),
        "shard_factor": shards,
        "requests": len(load),
        "tokens": toks,
        "wall_s": round(wall, 4),
        "throughput_tokens_per_s": round(toks / wall, 2),
        "compile_free": eng.program_count == warmed,
        "kv_occupancy": round(eng.allocator.peak_occupancy, 4),
        "prefix_hit_rate": round(
            eng.scheduler.prefix_hit_blocks
            / max(eng.scheduler.prefix_lookup_blocks, 1), 4),
        # per-chip decode reads at (bt, page tier): the Pope et al.
        # HBM-bound stream the shard factor divides
        "per_chip_decode_read_bytes_modeled": bt * m["gathered_bytes"],
        "per_chip_decode_read_bytes_measured": meas_r,
        "shard1_decode_read_bytes_modeled": bt * m1["gathered_bytes"],
        "shard1_decode_read_bytes_measured": meas_r1,
        "read_reduction_x": round(meas_r1 / meas_r, 2),
        # the price of the reduction: one psum per sublayer on ICI
        "psum_bytes_per_step_modeled": psum["stream_bytes"],
        "psum_bytes_per_step_measured": meas_p,
        "psum_count_per_step": psum["psum_count"],
        "pool_bytes_per_shard": eng.pool_bytes_per_shard,
        "shard_psum_bytes_total": eng.shard_psum_bytes,
    }
    return row


def build_spec_loads(rs, n, *, motif, tiles, gen):
    """The speculative A/B's two loads.  TEMPLATE-HEAVY: prompts are a
    short motif tiled several times (the repetitive agent/template
    traffic prompt-lookup drafting exists for — trailing n-grams recur,
    so drafts come from the sequence's own history).  ADVERSARIAL-
    RANDOM: i.i.d. uniform tokens — the drafter's worst case, and the
    leg's claim is that outputs are STILL bit-identical (speculation
    can waste compute, never move values; what acceptance survives
    here comes from the generated tail, not the prompt)."""
    motifs = [rs.randint(1, 120, size=motif).astype(np.int32)
              for _ in range(3)]
    template = []
    for _ in range(n):
        m = motifs[int(rs.randint(len(motifs)))]
        prompt = np.tile(m, tiles)[:int(motif * tiles - rs.randint(3))]
        template.append((prompt.astype(np.int32),
                         int(rs.randint(gen // 2, gen + 1))))
    random_load = [
        (rs.randint(1, 120, size=int(rs.randint(8, motif * tiles))
                    ).astype(np.int32),
         int(rs.randint(gen // 2, gen + 1)))
        for _ in range(n)]
    return template, random_load


def run_spec_leg(cfg, params, serve_cfg, load, leg, id_base):
    """One speculative A/B leg on a FRESH engine (spec on adds the
    verify-width programs to the menu, so the engines can't share a
    warmup the way the prefix A/B does).  Arrivals are immediate
    (interarrival 0): the A/B measures steps, not pacing."""
    eng = ServingEngine(cfg, params, serve=serve_cfg)
    warmed = eng.warmup()
    trace_t0 = trace.now()
    row, res = run_continuous(eng, load, 0.0, leg=leg, id_base=id_base)
    row["compile_free"] = row.pop("compiled_programs") == warmed
    row["drafted_tokens"] = eng.spec_drafted_tokens
    row["accepted_tokens"] = eng.spec_accepted_tokens
    row["rolled_back_tokens"] = eng.spec_rolled_back_tokens
    row["acceptance_rate"] = round(
        eng.spec_accepted_tokens / eng.spec_drafted_tokens, 4) \
        if eng.spec_drafted_tokens else 0.0
    # tokens emitted per verified row: 1 (the verifier's bonus or
    # correction token) + the accepted run — the speculative claim in
    # one number (1.0 exactly on the baseline legs)
    row["tokens_per_step"] = round(
        1.0 + eng.spec_accepted_tokens / eng.spec_verified_rows, 3) \
        if eng.spec_verified_rows else 1.0
    if trace.enabled():
        spans = [r for r in trace.snapshot(since=trace_t0)
                 if r[0] == "serve.spec_verify"]
        rollbacks = [r for r in trace.snapshot(since=trace_t0)
                     if r[0] == "serve.spec_rollback"]
        row["spec_verify_spans"] = len(spans)
        row["spec_verify_total_s"] = round(
            sum(r[2] or 0.0 for r in spans), 4)
        row["spec_rollback_events"] = len(rollbacks)
    return row, res


def run_spec_legs(args):
    """The round-15 speculative A/B: spec off vs on, on template-heavy
    and adversarial-random loads (build_spec_loads).  Asserts the
    bit-identity oracle per load before reporting."""
    if args.smoke:
        n, gen, motif, tiles, k = 14, 48, 6, 5, 6
    else:
        n, gen, motif, tiles, k = 40, 80, 8, 6, 6
    cfg = TransformerConfig(
        vocab_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_seq_len=motif * tiles + gen + 16,
        dtype=jnp.float32, attention_impl="dot", causal=True)
    params = params_for(cfg)
    # one decode tier and 16-token blocks keep each fresh engine's
    # warmup menu tiny (the A/B pays it twice per load); generation
    # dominates the leg, which is what speculation accelerates
    serve_kw = dict(
        block_size=16, num_blocks=0, token_budget=4 * cfg.max_seq_len,
        watermark=2, prefill_tiers=(motif * tiles + 2,),
        decode_tiers=(4,), prefill_chunk=0)
    rs = np.random.RandomState(args.seed + 3)
    template, random_load = build_spec_loads(
        rs, n, motif=motif, tiles=tiles, gen=gen)

    rows = []
    for name, load, base in (("template", template, 600000),
                             ("random", random_load, 700000)):
        base_row, base_res = run_spec_leg(
            cfg, params, ServeConfig(**serve_kw), load,
            f"spec_base_{name}", base)
        spec_row, spec_res = run_spec_leg(
            cfg, params, ServeConfig(spec=True, spec_k=k, **serve_kw),
            load, f"spec_on_{name}", base + 50000)
        for i in range(n):  # drafts move compute, never values
            if not np.array_equal(base_res[i], spec_res[i]):
                print(f"SPEC ORACLE MISMATCH ({name}) on request {i}",
                      file=sys.stderr)
                return None
        spec_row["speedup_vs_base"] = round(
            spec_row["throughput_tokens_per_s"]
            / max(base_row["throughput_tokens_per_s"], 1e-9), 2)
        rows += [base_row, spec_row]
    return rows


def _drive_router(router, load, arrivals, t0=None):
    """Open-loop drive of a FleetRouter: submit each request at its
    arrival offset, stepping the fleet in between (the router is
    single-threaded by design — this loop IS the front end)."""
    t0 = time.perf_counter() if t0 is None else t0
    n = len(load)
    gids = [None] * n
    i = 0
    while True:
        now = time.perf_counter()
        while i < n and now >= t0 + arrivals[i]:
            prompt, gen = load[i]
            gids[i] = router.submit(prompt, gen, arrival=t0 + arrivals[i])
            i += 1
            now = time.perf_counter()
        busy = router.step()
        if i >= n and not busy and not router._placed:
            break
        if not busy and i < n:
            time.sleep(max(0.0, t0 + arrivals[i] - time.perf_counter()))
    return gids, time.perf_counter() - t0


def _fleet_row(leg, router, gids, wall):
    ttfts = router.all_ttfts()
    hits, lookups = router.prefix_stats()
    toks = sum(len(router.results[g]) for g in gids)
    peaks = [r.peak_queue_depth for r in router.replicas + router.retired]
    return {
        "bench": "serve",
        "leg": leg,
        "t_start": round(time.time() - wall, 3),
        "t_end": round(time.time(), 3),
        "requests": len(gids),
        "tokens": toks,
        "wall_s": round(wall, 4),
        "throughput_tokens_per_s": round(toks / wall, 2),
        "replicas": len(router.replicas) + len(router.retired),
        "ttft_p50_s": round(_percentile(ttfts, 50), 4),
        "ttft_p99_s": round(_percentile(ttfts, 99), 4),
        "prefix_hit_rate": round(hits / lookups, 4) if lookups else 0.0,
        "per_replica_peak_queue_depth": peaks,
        "routed": dict(router.route_counts),
        "compile_free": router.all_compile_free(),
    }


def run_fleet_legs(args):
    """The PR-13 fleet A/B (docs/FLEET.md): N in-process replicas
    under a ramping open-loop load over shared templates, routed
    round-robin vs prefix-affinity (fresh replicas per leg, same
    params, same load), plus an SLO-driven scale leg (start at 1
    replica, the queue-depth policy grows the fleet under the ramp,
    drains it back as load falls).  Every leg asserts the standing
    oracle — placement moves time, never tokens — and zero
    post-warmup compiles on EVERY replica before reporting."""
    from horovod_tpu.fleet.policy import Target, TargetTrackingPolicy
    from horovod_tpu.fleet.router import FleetRouter

    if args.smoke:
        n, replicas, templates, t_len, s_hi, gen = 72, 2, 6, 48, 8, 6
        rate_lo, rate_hi = 100.0, 1200.0
    else:
        n, replicas, templates, t_len, s_hi, gen = 160, 3, 8, 96, 12, 8
        rate_lo, rate_hi = 60.0, 900.0
    cfg = TransformerConfig(
        vocab_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_seq_len=2 * t_len, dtype=jnp.float32,
        attention_impl="dot", causal=True)
    params = params_for(cfg)
    serve_kw = dict(block_size=16, num_blocks=0, token_budget=256,
                    watermark=2, prefill_tiers=(t_len + 16,),
                    decode_tiers=(1, 2, 4), prefill_chunk=16)

    def build_engine():
        return ServingEngine(cfg, params, serve=ServeConfig(**serve_kw))

    rs = np.random.RandomState(args.seed)
    temps = [rs.randint(1, 120, size=t_len).astype(np.int32)
             for _ in range(templates)]
    load = []
    for _ in range(n):
        t = temps[int(rs.randint(templates))]
        sfx = rs.randint(1, 120,
                         size=int(rs.randint(2, s_hi + 1))).astype(np.int32)
        load.append((np.concatenate([t, sfx]),
                     int(rs.randint(1, gen + 1))))
    # the load RAMP: interarrival shrinks linearly rate_lo -> rate_hi,
    # so queueing builds through the leg — the regime where placement
    # (and, in the scale leg, capacity) decides the TTFT tail
    arrivals = []
    t = 0.0
    for i in range(n):
        rate = rate_lo + (rate_hi - rate_lo) * i / max(n - 1, 1)
        t += 1.0 / rate
        arrivals.append(t)

    rows = []
    outs = {}
    for mode, leg in (("round_robin", "fleet_rr"),
                      ("affinity", "fleet_affinity")):
        router = FleetRouter(build_engine, replicas=replicas, mode=mode)
        gids, wall = _drive_router(router, load, arrivals)
        rows.append(_fleet_row(leg, router, gids, wall))
        outs[leg] = [router.results[g] for g in gids]
    for i, (a, b) in enumerate(zip(outs["fleet_rr"],
                                   outs["fleet_affinity"])):
        if not np.array_equal(a, b):  # placement moves time, not values
            print(f"FLEET ORACLE MISMATCH on request {i}", file=sys.stderr)
            return None
    rr, aff = rows[0], rows[1]
    aff["affinity_vs_rr"] = {
        "hit_rate_x": round(aff["prefix_hit_rate"]
                            / max(rr["prefix_hit_rate"], 1e-9), 3),
        "ttft_p99_x": round(rr["ttft_p99_s"]
                            / max(aff["ttft_p99_s"], 1e-9), 3),
    }

    # -- the SLO-driven scale leg: start at 1 accepting replica with
    # warm spares parked; the queue-depth policy grows the fleet under
    # the ramp (unpark = instant, the engines compiled before t0) and
    # drains it back once the queue empties at the tail
    policy = TargetTrackingPolicy(
        [Target("queue_depth", 3.0)], min_size=1, max_size=replicas,
        deadband=0.1, scale_in_at=0.3, hysteresis=40, cooldown_s=0.3)
    router = FleetRouter(build_engine, replicas=1, mode="affinity",
                         policy=policy, spares=replicas - 1)
    gids, wall = _drive_router(router, load, arrivals)
    # idle tail: keep ticking the policy so the empty queue scales the
    # fleet back in and the drain/retire path runs for real
    tail_deadline = time.perf_counter() + 3.0
    while time.perf_counter() < tail_deadline and (
            router.size > 1
            or any(r.state == "draining" for r in router.replicas)):
        router.step()
    row = _fleet_row("fleet_scale", router, gids, wall)
    row["scale_out_events"] = sum(
        1 for d, _ in router.scale_events if d == "out")
    row["scale_in_events"] = sum(
        1 for d, _ in router.scale_events if d == "in")
    row["max_replicas"] = max([1] + [s for d, s in router.scale_events
                                     if d == "out"])
    row["final_replicas"] = router.size
    row["retired_replicas"] = len(router.retired)
    for i, out in enumerate(outs["fleet_rr"]):
        if not np.array_equal(out, router.results[gids[i]]):
            print(f"FLEET SCALE ORACLE MISMATCH on request {i}",
                  file=sys.stderr)
            return None
    rows.append(row)

    # -- the ISSUE-18 recovery leg: same load, one replica killed
    # mid-ramp.  One strike ejects (HVD_TPU_FLEET_REPLICA_ERRORS=1);
    # in-flight work migrates warm off the live KV export, queued work
    # re-disperses cold, hedging is armed.  The oracle stays
    # token-identical vs the fault-free legs and the row carries the
    # recovery columns CI asserts (migration_ms, hedge_rate).
    os.environ["HVD_TPU_FLEET_REPLICA_ERRORS"] = "1"
    os.environ["HVD_TPU_SERVE_HEDGE"] = "1"
    try:
        router = FleetRouter(build_engine, replicas=replicas,
                             mode="affinity")
        victim = router.replicas[0]
        orig_step = victim.engine.step
        state = {"n": 0}

        def flaky_step(*a, **k):
            state["n"] += 1
            if state["n"] == 25:  # mid-ramp: the victim is mid-decode
                raise RuntimeError("bench-injected replica loss")
            return orig_step(*a, **k)

        victim.engine.step = flaky_step
        gids, wall = _drive_router(router, load, arrivals)
    finally:
        os.environ.pop("HVD_TPU_FLEET_REPLICA_ERRORS", None)
        os.environ.pop("HVD_TPU_SERVE_HEDGE", None)
    row = _fleet_row("fleet_recovery", router, gids, wall)
    row["migrations"] = len(router.recovery)
    row["migrations_warm"] = sum(
        1 for x in router.recovery if x["path"] == "warm")
    row["migration_ms"] = round(router.migration_ms(), 3)
    row["hedge_rate"] = round(router.hedge_rate(), 4)
    if not router.recovery:
        print("FLEET RECOVERY LEG: the ejection migrated nothing",
              file=sys.stderr)
        return None
    for i, out in enumerate(outs["fleet_rr"]):
        if not np.array_equal(out, router.results[gids[i]]):
            print(f"FLEET RECOVERY ORACLE MISMATCH on request {i}",
                  file=sys.stderr)
            return None
    rows.append(row)
    return rows


def _fleet_decode_gaps(router):
    """p99 inter-token gap across the fleet, from each engine's own
    token log (request ids are engine-local, and gaps are intra-id, so
    per-engine logs compose without remapping).  In the disaggregated
    fleet a request's first token lands in the prefill engine's log
    and the rest in the decode engine's — the one-token prefill-side
    entry contributes no gap, which is exactly right: the metric is
    the cadence a decode user FEELS, and the handoff pause shows up as
    the decode engine's first intra-id gap measured from arrival."""
    gaps = []
    for r in router.replicas + router.retired:
        if r.engine is not None and r.engine.token_log:
            gaps.extend(_decode_gaps(
                r.engine.token_log,
                {rid for rid, _e, _a in r.engine.token_log}))
    return gaps


def _arm_token_logs(router):
    for r in router.replicas:
        r.engine.token_log = []


def run_disagg_legs(args):
    """The disaggregated prefill/decode A/B (ROADMAP item 2,
    docs/FLEET.md): the same templated open-loop load through a
    classic mixed fleet of N replicas and a two-tier fleet that puts
    a prefill replica IN FRONT of the same N as a decode tier —
    iso-decode-capacity, the Splitwise framing: the claim under test
    is that offloading prompt work to a prefill tier keeps the decode
    cadence flat without costing aggregate tokens/s, so the A/B holds
    the decode fleet fixed and disaggregation adds its tier the way a
    deployment would.  The load is decode-heavy (long generations
    under a prompt-arrival ramp — prompts keep landing while earlier
    requests decode, the interference regime chunking only bounds).
    Asserted before a single number prints: token-identity across the
    legs, zero post-warmup compiles on BOTH tiers, and the warm
    handoff bytes' modeled == measured equality (comm_model idiom).
    With ``--shards 2`` a second pair reruns both legs with every
    tier tensor-sharded over 2 virtual chips and re-asserts identity
    against its own sharded mixed baseline."""
    from horovod_tpu.fleet.router import FleetRouter
    from horovod_tpu.ops.comm_model import modeled_kvsnap_bytes

    if args.smoke:
        n, decode_replicas, templates, t_len, s_hi = 48, 2, 6, 48, 8
        gen_lo, gen_hi = 12, 24
        rate_lo, rate_hi = 60.0, 400.0
    else:
        n, decode_replicas, templates, t_len, s_hi = 160, 3, 8, 96, 12
        gen_lo, gen_hi = 16, 32
        rate_lo, rate_hi = 40.0, 300.0
    cfg = TransformerConfig(
        vocab_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=16, max_seq_len=2 * t_len, dtype=jnp.float32,
        attention_impl="dot", causal=True)
    params = params_for(cfg)
    serve_kw = dict(block_size=16, num_blocks=0, token_budget=256,
                    watermark=2, prefill_tiers=(t_len + 16,),
                    decode_tiers=(1, 2, 4), prefill_chunk=16)

    rs = np.random.RandomState(args.seed)
    temps = [rs.randint(1, 120, size=t_len).astype(np.int32)
             for _ in range(templates)]
    load = []
    for _ in range(n):
        t = temps[int(rs.randint(templates))]
        sfx = rs.randint(1, 120,
                         size=int(rs.randint(2, s_hi + 1))).astype(np.int32)
        load.append((np.concatenate([t, sfx]),
                     int(rs.randint(gen_lo, gen_hi + 1))))
    arrivals = []
    t = 0.0
    for i in range(n):
        rate = rate_lo + (rate_hi - rate_lo) * i / max(n - 1, 1)
        t += 1.0 / rate
        arrivals.append(t)

    def legs_for(shards, suffix):
        def build(role="both"):
            return ServingEngine(
                cfg, params, serve=ServeConfig(shards=shards, **serve_kw),
                role=role)

        # mixed baseline: the decode tier's size, classic single tier
        router = FleetRouter(build, replicas=decode_replicas,
                             mode="affinity")
        _arm_token_logs(router)
        gids, wall = _drive_router(router, load, arrivals)
        mixed = _fleet_row(f"fleet_mixed{suffix}", router, gids, wall)
        mixed["p99_decode_gap_s"] = round(
            _percentile(_fleet_decode_gaps(router), 99), 4)
        mixed_out = [router.results[g] for g in gids]

        # the disaggregated fleet: 1 prefill + N decode
        router = FleetRouter(build, replicas=decode_replicas,
                             mode="affinity", prefill_replicas=1)
        _arm_token_logs(router)
        gids, wall = _drive_router(router, load, arrivals)
        row = _fleet_row(f"fleet_disagg{suffix}", router, gids, wall)
        row["p99_decode_gap_s"] = round(
            _percentile(_fleet_decode_gaps(router), 99), 4)
        row["handoffs"] = router.handoffs["warm"] + router.handoffs["cold"]
        row["handoffs_warm"] = router.handoffs["warm"]
        hand_ms = [x["ms"] for x in router.handoff_records]
        row["handoff_ms_p50"] = round(_percentile(hand_ms, 50), 3)
        row["handoff_ms_p99"] = round(_percentile(hand_ms, 99), 3)
        row["migrated_kv_bytes"] = router.migrated_bytes
        modeled = sum(
            modeled_kvsnap_bytes(
                x["blocks"], serve_kw["block_size"], cfg.num_layers,
                cfg.num_kv_heads, cfg.head_dim, "float32")["wire_bytes"]
            for x in router.handoff_records if x["path"] == "warm")
        row["migrated_kv_bytes_modeled"] = modeled
        pre = [r for r in router.replicas + router.retired
               if r.tier == "prefill"]
        dec = [r for r in router.replicas + router.retired
               if r.tier == "decode"]
        row["compile_free_prefill"] = all(r.compile_free for r in pre)
        row["compile_free_decode"] = all(r.compile_free for r in dec)
        row["compile_free"] = (row["compile_free_prefill"]
                               and row["compile_free_decode"])
        disagg_out = [router.results[g] for g in gids]

        for i, (a, b) in enumerate(zip(mixed_out, disagg_out)):
            if not np.array_equal(a, b):  # tiers move time, not tokens
                print(f"DISAGG ORACLE MISMATCH{suffix} on request {i}",
                      file=sys.stderr)
                return None
        if row["handoffs_warm"] < 1:
            print(f"DISAGG LEG{suffix}: no warm handoff crossed the wire",
                  file=sys.stderr)
            return None
        if row["migrated_kv_bytes"] != modeled:
            print(f"DISAGG KVSNAP BYTES{suffix}: measured "
                  f"{row['migrated_kv_bytes']} != modeled {modeled}",
                  file=sys.stderr)
            return None
        if not row["compile_free"]:
            print(f"DISAGG LEG{suffix}: a tier compiled post-warmup",
                  file=sys.stderr)
            return None
        return [mixed, row]

    rows = legs_for(1, "")
    if rows is None:
        return None
    if args.shards and args.shards > 1:
        more = legs_for(args.shards, f"_shard{args.shards}")
        if more is None:
            return None
        rows += more
    return rows


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI pass (CPU; scheduling is the claim)")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--rate", type=float, default=None,
                    help="request arrivals per second (open loop)")
    ap.add_argument("--batch", type=int, default=8,
                    help="static-baseline batch size AND max decode batch")
    ap.add_argument("--shards", type=int, default=None,
                    help="tensor-shard factor of the MULTICHIP leg "
                         "(default 8, smoke 2; 0 skips the leg)")
    ap.add_argument("--fleet", action="store_true",
                    help="run ONLY the fleet router legs (rr vs "
                         "prefix-affinity A/B + SLO scale leg)")
    ap.add_argument("--disagg", action="store_true",
                    help="run ONLY the disaggregated prefill/decode "
                         "A/B (mixed vs two-tier fleet; --shards 2 "
                         "adds a tensor-sharded pair)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from horovod_tpu.utils import compile_cache

    compile_cache.enable()

    if args.disagg:
        rows = run_disagg_legs(args)
        if rows is None:
            return 1
        for row in rows:
            print(json.dumps(row))
        mixed, dis = rows[0], rows[1]
        print(
            f"disagg 1+{dis['replicas'] - 1}: "
            f"{dis['handoffs_warm']}/{dis['handoffs']} handoffs warm, "
            f"p50 {dis['handoff_ms_p50']}ms, "
            f"{dis['migrated_kv_bytes']} KV B migrated "
            f"(modeled == measured); decode-gap p99 "
            f"{dis['p99_decode_gap_s']}s vs mixed "
            f"{mixed['p99_decode_gap_s']}s at "
            f"{dis['throughput_tokens_per_s']} vs "
            f"{mixed['throughput_tokens_per_s']} tok/s; oracle "
            f"token-identical, prefill/decode compile-free="
            f"{dis['compile_free_prefill']}/{dis['compile_free_decode']}",
            file=sys.stderr)
        return 0

    if args.fleet:
        rows = run_fleet_legs(args)
        if rows is None:
            return 1
        for row in rows:
            print(json.dumps(row))
        rr, aff, sc, rec = rows[0], rows[1], rows[2], rows[3]
        print(
            f"fleet x{rr['replicas']}: affinity hit rate "
            f"{aff['prefix_hit_rate']} vs rr {rr['prefix_hit_rate']} "
            f"({aff['affinity_vs_rr']['hit_rate_x']}x), TTFT p99 "
            f"{aff['ttft_p99_s']}s vs {rr['ttft_p99_s']}s "
            f"({aff['affinity_vs_rr']['ttft_p99_x']}x); scale leg "
            f"peaked at {sc['max_replicas']} replicas "
            f"({sc['scale_out_events']} out / "
            f"{sc['scale_in_events']} in); recovery leg migrated "
            f"{rec['migrations']} requests ({rec['migrations_warm']} warm) "
            f"in {rec['migration_ms']}ms avg at hedge rate "
            f"{rec['hedge_rate']}; oracle token-identical, "
            f"all replicas compile-free={aff['compile_free'] and rr['compile_free'] and sc['compile_free'] and rec['compile_free']}",
            file=sys.stderr)
        return 0

    if args.smoke:
        n = args.requests or 40
        rate = args.rate or 200.0
        cfg = TransformerConfig(
            vocab_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
            head_dim=16, max_seq_len=96, dtype=jnp.float32,
            attention_impl="dot", causal=True)
        gen_long = 56
        n_prefix, t_len, s_hi, chunk = 24, 48, 8, 8
        n_steady, n_burst, burst_len = 4, 3, 88
        steady_gen, burst_at, burst_bt = 60, 0.2, 8
    else:
        n = args.requests or 96
        rate = args.rate or 100.0
        cfg = TransformerConfig(
            vocab_size=512, num_layers=4, num_heads=8, num_kv_heads=2,
            head_dim=32, max_seq_len=256, dtype=jnp.float32,
            attention_impl="dot", causal=True)
        gen_long = 96
        n_prefix, t_len, s_hi, chunk = 64, 128, 16, 32
        n_steady, n_burst, burst_len = 6, 4, 240
        steady_gen, burst_at, burst_bt = 160, 1.0, 12

    rs = np.random.RandomState(args.seed)
    load = build_load(rs, n, p_lo=4, p_hi=24, gen_short=4,
                      gen_long=gen_long, frac_long=0.2)
    interarrival = 1.0 / rate

    serve_cfg = ServeConfig(
        block_size=16, num_blocks=0, token_budget=4 * cfg.max_seq_len,
        watermark=2,
        # one intake tier (all prompts fit 32; the engine appends
        # max_seq_len for post-evict re-prefills) keeps the warmup menu
        # small without changing what the measured legs execute
        prefill_tiers=(32,),
        decode_tiers=tuple(sorted({t for t in (1, 2, 4, 8, 16, 32)
                                   if t < args.batch} | {args.batch})))
    eng = ServingEngine(cfg, params_for(cfg), serve=serve_cfg)

    # pre-compile the WHOLE tier menu: a mid-traffic XLA compile is a
    # multi-second p99 spike, and the bounded menu is what makes
    # warming it tractable (the executable-cache discipline under test)
    t0 = time.perf_counter()
    warmed = eng.warmup()
    print(f"warmup: {warmed} tier programs in "
          f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)

    cont_row, cont_res = run_continuous(eng, load, interarrival)
    eng.allocator.peak_occupancy = 0.0
    stat_row, stat_res = run_static(eng, load, interarrival, args.batch)

    # the oracle, on the bench's own load: same greedy tokens both ways
    for i in range(n):
        if not np.array_equal(cont_res[i], stat_res[i]):
            print(f"ORACLE MISMATCH on request {i}", file=sys.stderr)
            return 1

    cont_row["speedup_vs_static"] = round(
        cont_row["throughput_tokens_per_s"]
        / max(stat_row["throughput_tokens_per_s"], 1e-9), 2)

    # -- round 9: shared-prefix A/B on the SAME engine (same programs) --
    prefix_load = build_prefix_load(
        rs, n_prefix, templates=4, t_len=t_len, s_lo=2, s_hi=s_hi, gen=4)
    prefix_rows = []
    prefix_outs = []
    for leg, enabled, base in (("prefix_off", False, 100000),
                               ("prefix_on", True, 200000)):
        eng.allocator.prefix_cache = enabled
        eng.allocator.clear_cache()
        eng.allocator.peak_occupancy = 0.0
        row, res = run_continuous(eng, prefix_load, interarrival, leg=leg,
                                  id_base=base)
        prefix_rows.append(row)
        prefix_outs.append(res)
    for i in range(n_prefix):  # the prefix-cache bit-identity oracle
        if not np.array_equal(prefix_outs[0][i], prefix_outs[1][i]):
            print(f"PREFIX ORACLE MISMATCH on request {i}", file=sys.stderr)
            return 1
    for row in (cont_row, stat_row, prefix_rows[0], prefix_rows[1]):
        # steady state must be all executable-cache hits
        row["compile_free"] = row.pop("compiled_programs") == warmed

    # -- round 9: chunked-prefill burst A/B (fresh engine per leg) ------
    # the HoL shape: a few LONG-LIVED decoders admitted at t~0, then a
    # burst of long prompts arriving TOGETHER mid-decode — slots and
    # budget are sized so the whole burst admits in one wave, which on
    # the unchunked engine is one monopolizing whole-prompt prefill
    # step stalling every decoder (the round-8 p50 queueing term), and
    # on the chunked engine is a stream of bounded chunks the decode
    # batch rides alongside
    burst_rs = np.random.RandomState(args.seed + 1)
    steady_load = [
        (burst_rs.randint(1, 120, size=8).astype(np.int32),
         steady_gen, i * 0.01) for i in range(n_steady)]
    burst_only = [
        (burst_rs.randint(1, 120, size=burst_len).astype(np.int32),
         2, burst_at) for _ in range(n_burst)]
    steady_ids = set(range(n_steady))
    # one decode tier: every step pads to the full batch either way, so
    # the A/B stays fair while each fresh engine warms a tiny menu
    burst_base = dict(
        block_size=16, num_blocks=0, token_budget=4 * cfg.max_seq_len,
        watermark=2, prefill_tiers=(32,), decode_tiers=(burst_bt,))
    unchunked_row, un_res = run_burst_leg(
        cfg, eng.params, ServeConfig(prefill_chunk=0, **burst_base),
        steady_load, burst_only, steady_ids, "unchunked")
    chunked_row, ch_res = run_burst_leg(
        cfg, eng.params, ServeConfig(prefill_chunk=chunk, **burst_base),
        steady_load, burst_only, steady_ids, "chunked")
    for i in range(n_steady + n_burst):  # chunks move time, not values
        if not np.array_equal(un_res[i], ch_res[i]):
            print(f"CHUNK ORACLE MISMATCH on request {i}", file=sys.stderr)
            return 1

    kv_row = kv_model_leg(cfg, serve_cfg, context_len=cfg.max_seq_len // 2,
                          page_tiers=eng.page_tiers)

    # -- round 10: the tensor-sharded MULTICHIP leg ---------------------
    shards = args.shards if args.shards is not None else (
        2 if args.smoke else 8)
    mc_rows = []
    if shards > 1:
        mc = run_multichip_leg(shards, 12 if args.smoke else 32, args.seed)
        if mc is None:
            return 1
        mc_rows.append(mc)

    # -- round 15: the speculative-decoding A/B -------------------------
    spec_rows = run_spec_legs(args)
    if spec_rows is None:
        return 1

    for row in (cont_row, stat_row, prefix_rows[0], prefix_rows[1],
                unchunked_row, chunked_row, kv_row, *mc_rows,
                *spec_rows):
        print(json.dumps(row))
    on, off = prefix_rows[1], prefix_rows[0]
    print(
        f"continuous {cont_row['throughput_tokens_per_s']} tok/s "
        f"(p99 {cont_row['p99_token_latency_s']}s) vs static "
        f"{stat_row['throughput_tokens_per_s']} tok/s "
        f"(p99 {stat_row['p99_token_latency_s']}s) — "
        f"{cont_row['speedup_vs_static']}x; prefix cache TTFT p50 "
        f"{off['ttft_p50_s']}s -> {on['ttft_p50_s']}s at hit rate "
        f"{on['prefix_hit_rate']} ({off['prefill_tokens_computed']} -> "
        f"{on['prefill_tokens_computed']} prefill tokens); burst decode-gap "
        f"p99 {unchunked_row['p99_decode_gap_s']}s unchunked -> "
        f"{chunked_row['p99_decode_gap_s']}s chunked; paged decode reads "
        f"{kv_row['read_reduction_x']}x fewer K/V bytes", file=sys.stderr)
    if mc_rows:
        mc = mc_rows[0]
        print(
            f"multichip x{mc['shard_factor']}: per-chip decode reads "
            f"{mc['shard1_decode_read_bytes_measured']} -> "
            f"{mc['per_chip_decode_read_bytes_measured']} B "
            f"({mc['read_reduction_x']}x, modeled == measured) at "
            f"{mc['psum_bytes_per_step_measured']} psum B/step on ICI; "
            f"oracle token-identical, compile_free={mc['compile_free']}",
            file=sys.stderr)
    sp_t, sp_r = spec_rows[1], spec_rows[3]
    print(
        f"speculative: template-heavy "
        f"{spec_rows[0]['throughput_tokens_per_s']} -> "
        f"{sp_t['throughput_tokens_per_s']} tok/s "
        f"({sp_t['speedup_vs_base']}x) at acceptance "
        f"{sp_t['acceptance_rate']} "
        f"({sp_t['tokens_per_step']} tok/step); adversarial-random "
        f"{sp_r['speedup_vs_base']}x at acceptance "
        f"{sp_r['acceptance_rate']} — bit-identical both ways, "
        f"compile_free={sp_t['compile_free'] and sp_r['compile_free']}",
        file=sys.stderr)
    return 0


def params_for(cfg):
    model = Transformer(cfg)
    rng = jax.random.PRNGKey(0)
    return model.init(rng, jnp.zeros((1, 8), jnp.int32),
                      train=False)["params"]


if __name__ == "__main__":
    sys.exit(main())
