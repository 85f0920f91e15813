#!/usr/bin/env python
"""Transformer training-step benchmark: optimizer sharding (ZeRO vs
replicated) and activation-remat policy legs.

Every leg emits ONE bench-style JSON line on stdout (human summary on
stderr) so the numbers are regression-trackable round over round —
the flash_bench contract.  Two leg families:

  * ``transformer_optim`` — the full data-parallel training step over a
    ``world``-chip mesh with either the replicated optimizer
    (``training.data_parallel_train_step`` + plain AdamW state on every
    rank) or the ZeRO-sharded one (``training.zero_train_setup``:
    reduce-scatter → shard update → allgather).  The
    ``opt_state_bytes_per_rank`` column is MEASURED from the live state
    arrays (sharded leaves divided by world), so the 1/world_size ZeRO
    saving is pinned even on a CPU box where wall-clock is
    interpret-grade; the chip wall-clock legs have not been
    re-run on today's code.
  * ``transformer_remat`` — single-device step time per activation-remat
    policy, with the ``modeled_activation_bytes`` column from
    ``models.transformer.modeled_activation_bytes`` (the capacity
    arithmetic PERF.md round 6 calls "remat territory"; pinned by
    tests/test_remat_policies.py).

``HVD_TPU_BENCH_ITERS`` / ``HVD_TPU_BENCH_WARMUP`` override iteration
counts; ``HVD_TPU_BENCH_WORLD`` sets the mesh width for the optim legs
(CPU boxes get that many virtual host devices; docs/running.md).

Usage:
  transformer_bench.py                  # chip legs: optim pair + remat sweep
  transformer_bench.py --optim zero     # one optimizer leg
  transformer_bench.py --remat none,full,dots,dots_no_batch
  transformer_bench.py --smoke          # tiny CPU-safe pass of all legs (CI)
"""

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# the optim legs shard the batch over a mesh: on CPU-only boxes expose
# HVD_TPU_BENCH_WORLD virtual host devices (raw parse: this must run
# BEFORE jax — and therefore the package — can be imported)
try:
    _WORLD = max(1, int(os.environ.get("HVD_TPU_BENCH_WORLD", "") or 8))
except ValueError:
    _WORLD = 8
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + f" --xla_force_host_platform_device_count={_WORLD}"
    ).strip()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from horovod_tpu import training  # noqa: E402
from horovod_tpu.common.retry import env_int  # noqa: E402
from horovod_tpu.common.topology import WORLD_AXIS  # noqa: E402
from horovod_tpu.models.transformer import (  # noqa: E402
    Transformer, TransformerConfig, modeled_activation_bytes,
)
from horovod_tpu.optim import (  # noqa: E402
    sharded_state_bytes_per_rank, state_bytes,
)

ITERS = env_int("HVD_TPU_BENCH_ITERS", 20)
WARMUP = env_int("HVD_TPU_BENCH_WARMUP", 3)


def emit(rec, human=""):
    print(json.dumps(rec))
    if human:
        print(human, file=sys.stderr)


def _config(args):
    if args.smoke:
        return dict(vocab_size=256, num_layers=2, num_heads=2, head_dim=16,
                    max_seq_len=64, dtype=jnp.float32), 8, 64
    return dict(vocab_size=32000, num_layers=12, num_heads=12, head_dim=64,
                max_seq_len=args.seq, dtype=jnp.bfloat16), args.batch, args.seq


def _data(batch, seq, vocab):
    rs = np.random.RandomState(0)
    tok = jnp.asarray(rs.randint(0, vocab, (batch, seq)))
    tgt = jnp.asarray(rs.randint(0, vocab, (batch, seq)))
    return tok, tgt


def _timed(step_once, iters, warmup):
    """Chained iterations with a scalar fetch as the sync."""
    loss = None
    for _ in range(warmup):
        loss = step_once()
    if loss is not None:  # warmup=0: nothing to sync yet
        float(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step_once()
    final = float(loss)
    dt = (time.perf_counter() - t0) / iters
    assert np.isfinite(final), f"non-finite loss {final}"
    return dt


def run_optim_leg(kind, args, remat="none"):
    cfg_kw, batch, seq = _config(args)
    world = min(_WORLD, jax.device_count())
    batch = max(batch, world)
    batch -= batch % world  # P(axis) batch sharding needs divisibility
    cfg = TransformerConfig(remat_policy=remat, **cfg_kw)
    model = Transformer(cfg)
    mesh = Mesh(np.array(jax.devices()[:world]), (WORLD_AXIS,))
    tok, tgt = _data(batch, seq, cfg.vocab_size)
    inner = optax.adamw(1e-3)
    rng = jax.random.PRNGKey(0)

    if kind == "zero":
        state, step, ospecs = training.zero_train_setup(
            model, inner, rng, tok[:1], mesh=mesh)
        opt_bytes = sharded_state_bytes_per_rank(
            state.opt_state, ospecs, world)
    else:
        state = training.create_train_state(model, inner, rng, tok[:1])
        step = training.data_parallel_train_step(model, inner, mesh=mesh)
        opt_bytes = state_bytes(state.opt_state)

    box = {"state": state}

    def once():
        box["state"], loss = step(box["state"], tok, tgt)
        return loss

    dt = _timed(once, ITERS, WARMUP)
    n_params = sum(
        x.size for x in jax.tree_util.tree_leaves(box["state"].params))
    rec = {
        "bench": "transformer_optim",
        "optim": kind,
        "world": world,
        "batch": batch,
        "seq": seq,
        "remat": remat,
        "step_ms": round(dt * 1e3, 2),
        "tokens_per_s": round(batch * seq / dt, 1),
        "params": int(n_params),
        "opt_state_bytes_per_rank": int(opt_bytes),
        # per-rank, like the opt-state column: the step shards the
        # global batch over the world axis
        "modeled_activation_bytes": int(
            modeled_activation_bytes(cfg, batch // world)["total_bytes"]),
        "backend": jax.default_backend(),
    }
    emit(rec, f"[optim] {kind:10s} world {world}: step {dt*1e3:8.1f} ms  "
              f"opt state/rank {opt_bytes/1e6:.2f} MB")
    return rec


def run_remat_leg(policy, args):
    cfg_kw, batch, seq = _config(args)
    cfg = TransformerConfig(remat_policy=policy, **cfg_kw)
    model = Transformer(cfg)
    tok, tgt = _data(batch, seq, cfg.vocab_size)
    variables = model.init(jax.random.PRNGKey(0), tok[:1])
    opt = optax.adamw(1e-3)

    @jax.jit
    def step(params, opt_state, tok, tgt):
        def loss_fn(p):
            logits = model.apply({"params": p}, tok)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, tgt).mean()
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    box = {"p": variables["params"], "o": opt.init(variables["params"])}

    def once():
        box["p"], box["o"], loss = step(box["p"], box["o"], tok, tgt)
        return loss

    dt = _timed(once, ITERS, WARMUP)
    modeled = modeled_activation_bytes(cfg, batch)
    none_cfg = TransformerConfig(remat_policy="none", **cfg_kw)
    rec = {
        "bench": "transformer_remat",
        "policy": policy,
        "batch": batch,
        "seq": seq,
        "step_ms": round(dt * 1e3, 2),
        "tokens_per_s": round(batch * seq / dt, 1),
        "modeled_activation_bytes": int(modeled["total_bytes"]),
        "modeled_activation_bytes_none": int(
            modeled_activation_bytes(none_cfg, batch)["total_bytes"]),
        "backend": jax.default_backend(),
    }
    emit(rec, f"[remat] {policy:14s}: step {dt*1e3:8.1f} ms  "
              f"modeled act {modeled['total_bytes']/1e6:.1f} MB")
    return rec


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--optim", choices=["zero", "replicated", "both"],
                   default=None, help="optimizer-sharding legs")
    p.add_argument("--remat", default=None,
                   help="comma list of remat policies to sweep")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=2048)
    p.add_argument("--smoke", action="store_true",
                   help="tiny CPU-safe pass of all legs (CI)")
    args = p.parse_args()
    from horovod_tpu.utils import compile_cache

    compile_cache.enable()
    print("backend:", jax.default_backend(), file=sys.stderr)

    failed = False
    def leg(fn, *leg_args):
        # one OOM/compile-failure leg must not kill the sweep — the
        # remaining legs (e.g. the remat policy that DOES fit) still
        # emit their regression-tracked JSON lines
        nonlocal failed
        label = f"{fn.__name__}:{leg_args[0]}"
        try:
            fn(*leg_args)
        except Exception as e:
            if "Ran out of memory" in str(e) or "RESOURCE_EXHAUSTED" in str(e):
                print(f"[{label}] OOM (hbm exceeded)", file=sys.stderr)
            else:
                traceback.print_exc()
                print(f"[{label}] FAILED ({type(e).__name__})",
                      file=sys.stderr)
                failed = True
    if args.optim or args.smoke or (args.remat is None):
        kinds = (["zero", "replicated"]
                 if args.optim in (None, "both") else [args.optim])
        for kind in kinds:
            leg(run_optim_leg, kind, args)
    if args.remat or args.smoke or (args.optim is None):
        policies = (args.remat.split(",") if args.remat
                    else ["none", "dots", "dots_no_batch", "full"])
        if args.smoke and not args.remat:
            policies = ["none", "dots_no_batch"]
        for pol in policies:
            leg(run_remat_leg, pol, args)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
