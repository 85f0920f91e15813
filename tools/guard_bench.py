#!/usr/bin/env python
"""Integrity-guard benchmark: the ISSUE-14 exactness/overhead bars.

Every leg emits ONE bench-style JSON line on stdout (human summary on
stderr) — the flash_bench/collective_bench contract.  Legs:

  * ``guard_overhead`` — median step wall time of the SAME
    data-parallel train step built guard-off vs guard-on (the on-device
    digest + finite sentinel are the only delta; the cadence host sync
    is amortized by ``HVD_TPU_GUARD_CADENCE``).  The acceptance bar:
    ``overhead_frac <= 0.02`` at the default cadence (CI asserts it).
    CPU-host numbers are interpret-grade for absolute time but the
    RATIO is the claim; the chip leg is not measured yet.
  * ``guard_collectives`` — StableHLO collective inventory (the PR-7
    ``measured_tier_bytes`` idiom's instruction scan) of three
    programs: baseline (guard=False), guard DISABLED via
    ``HVD_TPU_GUARD=0`` (must be the baseline inventory: EXACTLY 0
    added collectives — the acceptance bar), and guard ENABLED (also 0
    added: the digest folds are local; the exchange rides the host
    control plane at cadence).
  * ``guard_oracle`` — the standing exactness discipline: the guarded
    step's state and loss BIT-identical to the unguarded step over
    several steps when no fault fires.

Usage:
  guard_bench.py            # full legs — what the CI guard-smoke job
                            # runs: the overhead ratio is only
                            # meaningful when the step dwarfs timing
                            # noise (~400 ms here vs ~10 ms in smoke)
  guard_bench.py --smoke    # tiny fast pass: oracle + collectives
                            # legs meaningful, overhead_frac is NOT
"""

import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

try:  # contract-ok: env -- bootstrap runs before the package's env_int is importable
    _WORLD = max(1, int(os.environ.get("HVD_TPU_BENCH_WORLD", "") or 2))
except ValueError:
    _WORLD = 2
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + f" --xla_force_host_platform_device_count={_WORLD}"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu import training  # noqa: E402
from horovod_tpu.common.retry import env_int  # noqa: E402
from horovod_tpu.models.transformer import (  # noqa: E402
    Transformer, TransformerConfig,
)

ITERS = env_int("HVD_TPU_BENCH_ITERS", 20)
WARMUP = env_int("HVD_TPU_BENCH_WARMUP", 3)

_COLLECTIVE_RE = re.compile(
    r"stablehlo\.(all_reduce|all_gather|reduce_scatter|"
    r"collective_permute|all_to_all)")


def _emit(row):
    print(json.dumps(row), flush=True)


def _say(msg):
    print(f"[guard_bench] {msg}", file=sys.stderr, flush=True)


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
    return model, opt, state, x, y


def _loss(logits, labels):
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, labels).mean()


def _copy(state):
    return jax.tree_util.tree_map(jnp.copy, state)


def _timed_ab(plain, guarded, state, x, y):
    """Median step time of each program, measured in INTERLEAVED A/B
    rounds (one unguarded step, one guarded step, repeat): slow drift
    on a shared/contended box (thermal, noisy neighbors) hits both
    sides of every round equally, so the RATIO — the claim — stays
    stable where back-to-back blocks would alias the drift onto one
    side."""
    sa = _copy(state)
    sb = _copy(state)
    for _ in range(WARMUP):
        sa = plain(sa, x, y)[0]
        sb = guarded(sb, x, y)[0]
    jax.block_until_ready((sa.params, sb.params))
    t_plain, t_guard = [], []
    for _ in range(max(1, ITERS)):
        t0 = time.perf_counter()
        sa = plain(sa, x, y)[0]
        jax.block_until_ready(sa.params)
        t1 = time.perf_counter()
        sb = guarded(sb, x, y)[0]
        jax.block_until_ready(sb.params)
        t2 = time.perf_counter()
        t_plain.append(t1 - t0)
        t_guard.append(t2 - t1)
    return (float(np.median(t_plain) * 1e3),
            float(np.median(t_guard) * 1e3))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CPU-safe pass (CI)")
    args = ap.parse_args(argv)
    from horovod_tpu.utils import compile_cache

    compile_cache.enable()

    hvd.init()
    model, opt, state, x, y = _build(args.smoke)

    def build_step(guard):
        return training.data_parallel_train_step(
            model, opt, loss_fn=_loss, guard=guard)

    plain = build_step(False)
    guarded = build_step(True)

    # -- guard_oracle: bit-identical state + loss over several steps ---------
    sa, sb = _copy(state), _copy(state)
    bit_exact = True
    for _ in range(3):
        sa, la = plain(sa, x, y)
        sb, lb, _diag = guarded(sb, x, y)
        if float(la) != float(lb):
            bit_exact = False
        for pa, pb in zip(jax.tree_util.tree_leaves(sa.params),
                          jax.tree_util.tree_leaves(sb.params)):
            if not np.array_equal(np.asarray(pa), np.asarray(pb)):
                bit_exact = False
    _emit({"bench": "guard_oracle", "steps": 3, "bit_exact": bit_exact})
    _say(f"oracle bit_exact={bit_exact}")

    # -- guard_collectives: the zero-added-collectives contract --------------
    def inventory(step):
        return len(_COLLECTIVE_RE.findall(
            step.lower(_copy(state), x, y).as_text()))

    n_plain = inventory(plain)
    n_guarded = inventory(guarded)
    # the env-disabled path: guard=None defers to HVD_TPU_GUARD
    os.environ["HVD_TPU_GUARD"] = "0"
    try:
        n_disabled = inventory(build_step(None))
    finally:
        os.environ.pop("HVD_TPU_GUARD", None)
    _emit({
        "bench": "guard_collectives",
        "collectives_baseline": n_plain,
        "collectives_disabled": n_disabled,
        "collectives_guarded": n_guarded,
        "added_collectives_disabled": n_disabled - n_plain,
        "added_collectives_guarded": n_guarded - n_plain,
    })
    _say(f"collectives baseline={n_plain} disabled={n_disabled} "
         f"guarded={n_guarded}")

    # -- guard_overhead ------------------------------------------------------
    ms_plain, ms_guarded = _timed_ab(plain, guarded, state, x, y)
    overhead = (ms_guarded - ms_plain) / ms_plain
    _emit({
        "bench": "guard_overhead",
        "step_ms_unguarded": round(ms_plain, 3),
        "step_ms_guarded": round(ms_guarded, 3),
        "overhead_frac": round(overhead, 4),
        "cadence": env_int("HVD_TPU_GUARD_CADENCE", 16),
        "iters": ITERS, "world": _WORLD,
    })
    _say(f"overhead {overhead * 100:.2f}% "
         f"({ms_plain:.1f} -> {ms_guarded:.1f} ms)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
