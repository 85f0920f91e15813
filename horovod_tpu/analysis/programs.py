"""Lowered-program contract verifier (pass #8, ``programs``).

The other seven passes read SOURCE; this one reads the PROGRAMS — the
StableHLO modules the framework actually dispatches — and machine-checks
the invariants the docs promise in prose:

* **zero-added-collectives** — the integrity guard and the tracer are
  pure observers: ``guard=False`` vs ``HVD_TPU_GUARD=0`` lowers
  byte-identical, ``guard=True`` and trace on/off add exactly 0
  collective instructions (docs/FAULT_TOLERANCE.md, docs/TRACING.md).
* **serving DCN-exclusion** — no collective of any serving step program
  (decode / mixed / speculative, every tier) carries a replica group
  spanning >1 slice: the token loop never touches DCN
  (docs/SERVING.md sharding section).
* **modeled == measured** — ``ops/comm_model``'s modeled per-tier bytes
  equal the lowered module's collective inventory, per tier program and
  for the hierarchical allreduce (docs/COLLECTIVES.md).
* **zero-recompile** — under a randomized request load, every program
  key the engine dispatches is in the warmup menu: the tier product is
  the whole compiled set, no mid-traffic XLA compile ever
  (docs/SERVING.md menu contract).

Unlike the bare-box passes this one needs jax, so it is GATED: inside
``run_all``/``tools/check.py`` it reports nothing unless
``HVD_TPU_VERIFY_PROGRAMS=1`` is set (and jax imports).  The heavy path
has two front doors — ``tools/verify_programs.py`` (its own CI job) and
the ``analysis``-marked tests in tests/test_program_contracts.py.  The
check helpers themselves are dependency-light (regex + comm_model's
numpy parser) so the self-tests can feed them synthetic drift.

Suppression: same machinery as every pass (``contract-ok: programs --
<why>`` has nowhere to live in generated text, so use the allowlist
file with the finding's key).
"""

from __future__ import annotations

import hashlib
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from ._common import Finding

CHECK = "programs"

#: env gate: the jax-requiring verification only runs when this is "1"
#: (tools/verify_programs.py and the analysis-marked tests set it).
ENV_GATE = "HVD_TPU_VERIFY_PROGRAMS"

ENGINE_PY = "horovod_tpu/serving/engine.py"
TRAINING_PY = "horovod_tpu/training.py"
SPMD_OPS_PY = "horovod_tpu/ops/spmd_ops.py"

_COLLECTIVE_RE = re.compile(
    r"stablehlo\.(all_reduce|all_gather|reduce_scatter|"
    r"collective_permute|all_to_all)")


def collective_count(lowered_text: str) -> int:
    """Collective instructions in one lowered (StableHLO) module."""
    return len(_COLLECTIVE_RE.findall(lowered_text))


def _digest(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


# -- pure check helpers (synthetic-testable without jax) ---------------------


def check_byte_identical(name: str, baseline: str, candidate: str,
                         file: str = TRAINING_PY) -> List[Finding]:
    """The strongest no-op claim: the two lowered modules are the SAME
    bytes (the guard_bench/trace_bench sha256 idiom)."""
    if _digest(baseline) == _digest(candidate):
        return []
    added = collective_count(candidate) - collective_count(baseline)
    return [Finding(
        CHECK, file, 0, f"byte-identical:{name}",
        f"{name}: lowered programs differ (sha256 mismatch, "
        f"{added:+d} collective(s)) — the no-op path must lower "
        "byte-identical to the baseline",
    )]


def check_added_collectives(name: str, baseline: str, candidate: str,
                            budget: int = 0,
                            file: str = TRAINING_PY) -> List[Finding]:
    """The candidate program may add at most ``budget`` (default 0)
    collective instructions over the baseline."""
    added = collective_count(candidate) - collective_count(baseline)
    if added <= budget:
        return []
    return [Finding(
        CHECK, file, 0, f"added-collectives:{name}",
        f"{name}: {added} collective(s) added over the baseline "
        f"(budget {budget}) — observers must not grow the collective "
        "inventory (the exchange rides the host control plane)",
    )]


def check_dcn_exclusion(name: str, lowered_text: str,
                        slice_ids: Sequence[int],
                        file: str = ENGINE_PY) -> List[Finding]:
    """No collective replica group of a serving program may span >1
    slice of ``slice_ids`` — DCN stays out of the token loop."""
    from ..ops.comm_model import measured_tier_bytes

    out: List[Finding] = []
    inv = measured_tier_bytes(lowered_text, slice_ids)
    for op in inv["ops"]:
        if op["tier"] == "dcn":
            out.append(Finding(
                CHECK, file, 0, f"serve-dcn:{name}:{op['op']}",
                f"{name}: {op['op']} (payload {op['payload_bytes']} B, "
                f"group size {op['group_size']}) spans >1 slice — a "
                "serving step collective crossed onto DCN; the token "
                "loop must stay inside one ICI slice "
                "(docs/SERVING.md)",
            ))
    return out


def check_menu_keys(name: str, warmed: Iterable[tuple],
                    dispatched: Iterable[tuple],
                    file: str = ENGINE_PY) -> List[Finding]:
    """Every program key dispatched under load must be in the warmup
    menu — an off-menu key is a mid-traffic XLA compile."""
    extra = sorted(set(dispatched) - set(warmed), key=repr)
    return [Finding(
        CHECK, file, 0, f"off-menu:{name}:{'-'.join(map(str, key))}",
        f"{name}: program key {key!r} dispatched but never warmed — a "
        "mid-traffic compile (multi-second p99 spike); the tier menu "
        "must cover every reachable (kind, tier...) combination",
    ) for key in extra]


def check_modeled_measured(name: str, modeled: Dict[str, int],
                           measured: Dict[str, int],
                           file: str = SPMD_OPS_PY) -> List[Finding]:
    """Per-tier modeled bytes must equal the lowered inventory, key by
    key (keys present in ``modeled`` are compared)."""
    out: List[Finding] = []
    for tier, want in modeled.items():
        got = measured.get(tier)
        if got != want:
            out.append(Finding(
                CHECK, file, 0, f"model-mismatch:{name}:{tier}",
                f"{name}: modeled {tier} = {want} B but the lowered "
                f"program measures {got} B — comm_model and the "
                "compiled collective inventory disagree "
                "(docs/COLLECTIVES.md byte model)",
            ))
    return out


# -- the PASSES entry --------------------------------------------------------


def run(root: str) -> List[Finding]:
    """Gated: bare boxes (tools/check.py, the <10s lint job) see an
    empty pass; ``HVD_TPU_VERIFY_PROGRAMS=1`` + importable jax runs the
    full program verification."""
    if os.environ.get(ENV_GATE, "") != "1":
        return []
    try:
        import jax  # noqa: F401
    except Exception:
        return [Finding(
            CHECK, "pyproject.toml", 0, "no-jax",
            f"{ENV_GATE}=1 but jax is not importable — run this pass "
            "from an environment with the framework installed "
            "(tools/verify_programs.py)",
        )]
    return verify(root)


# -- the jax-requiring verification ------------------------------------------


def _serve_load(rs, n: int, max_seq_len: int) -> List[Tuple[list, int]]:
    """Randomized (prompt, max_new_tokens) pairs with a templated
    prefix mix (prefix-cache hits AND misses both exercised)."""
    templates = [list(rs.randint(1, 100, size=rs.randint(4, 20)))
                 for _ in range(4)]
    load = []
    for _ in range(n):
        head = templates[rs.randint(len(templates))] if rs.rand() < 0.5 \
            else []
        tail = list(rs.randint(1, 100, size=rs.randint(2, 12)))
        prompt = (head + tail)[:max_seq_len // 2]
        gen = int(rs.randint(1, 9))
        load.append((prompt, gen))
    return load


def _drive(eng, load) -> None:
    import numpy as np

    ids = [eng.submit(np.asarray(p, np.int32), max_new_tokens=g)
           for p, g in load]
    eng.run()
    assert all(r in eng.results for r in ids)


def _verify_serving(shards_list: Sequence[int], requests: int,
                    seed: int) -> List[Finding]:
    """Engines per shard count (+ one speculative): warmup the whole
    menu, inventory every program family's lowering (DCN-exclusion +
    modeled == measured psum stream), then the zero-recompile lint
    under the randomized load."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models.transformer import TransformerConfig
    from ..ops.comm_model import (measured_tier_bytes,
                                  modeled_serve_psum_bytes)
    from ..serving import ServeConfig, ServingEngine

    findings: List[Finding] = []
    # virtual 2-slice split of the 8-device world: the deployment
    # mapping DCN-exclusion is checked against (a serving mesh only
    # ever takes one slice's chips, so any group crossing the split
    # is a real violation)
    n_dev = jax.device_count()
    world_slices = [d // max(n_dev // 2, 1) for d in range(n_dev)]

    kv = max(2, max(shards_list))
    cfg = TransformerConfig(
        vocab_size=128, num_layers=2, num_heads=2 * kv, num_kv_heads=kv,
        head_dim=16, max_seq_len=96, dtype=jnp.float32,
        attention_impl="dot", causal=True)
    serve = dict(block_size=8, num_blocks=0, token_budget=256,
                 watermark=2, prefill_tiers=(32,), decode_tiers=(1, 2, 4),
                 prefill_chunk=8)
    from ..models.transformer import Transformer
    params = Transformer(cfg).init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32),
        train=False)["params"]

    legs: List[Tuple[str, ServeConfig, int]] = []
    for s in shards_list:
        legs.append((f"shards{s}", ServeConfig(shards=s, **serve),
                     requests if s == min(shards_list)
                     else max(requests // 4, 16)))
    legs.append(("spec", ServeConfig(spec=True, spec_k=3, **serve),
                 max(requests // 4, 16)))

    for name, scfg, n_req in legs:
        eng = ServingEngine(cfg, params, serve=scfg)
        eng.warmup()
        warmed = set(eng._progs)
        # every program FAMILY's lowering: DCN-exclusion + modeled ==
        # measured psum stream, per tier the engine can dispatch
        for bt in eng.decode_tiers:
            pt = eng.page_tiers[0]
            txt = eng.lowered_decode_text(batch_tier=bt, pages=pt)
            findings += check_dcn_exclusion(
                f"{name}:decode:b{bt}:p{pt}", txt, world_slices)
            modeled = modeled_serve_psum_bytes(
                bt, 1, cfg.d_model, cfg.num_layers, eng.shards,
                "float32")
            measured = measured_tier_bytes(txt, [0] * max(eng.shards, 1))
            findings += check_modeled_measured(
                f"{name}:decode:b{bt}", {"ici": modeled["stream_bytes"]},
                {"ici": measured["ici_bytes"]}, file=ENGINE_PY)
            for c in eng.chunk_tiers:
                mtxt = eng.lowered_mixed_text(batch_tier=bt, chunk_tier=c)
                findings += check_dcn_exclusion(
                    f"{name}:mixed:b{bt}:c{c}", mtxt, world_slices)
                mmod = modeled_serve_psum_bytes(
                    bt, c, cfg.d_model, cfg.num_layers, eng.shards,
                    "float32")
                mmeas = measured_tier_bytes(mtxt,
                                            [0] * max(eng.shards, 1))
                findings += check_modeled_measured(
                    f"{name}:mixed:b{bt}:c{c}",
                    {"ici": mmod["stream_bytes"]},
                    {"ici": mmeas["ici_bytes"]}, file=ENGINE_PY)
            if eng.spec_w:
                stxt = eng.lowered_mixed_text(
                    batch_tier=bt, chunk_tier=eng.spec_w,
                    pages=eng.page_tiers[0])
                findings += check_dcn_exclusion(
                    f"{name}:spec:b{bt}:w{eng.spec_w}", stxt,
                    world_slices)
        # zero-recompile lint: the randomized load must dispatch only
        # warmed keys (and actually compile nothing new)
        rs = np.random.RandomState(seed + len(name))
        _drive(eng, _serve_load(rs, n_req, cfg.max_seq_len))
        findings += check_menu_keys(name, warmed, set(eng._progs))
        if eng.program_count != len(warmed):
            findings.append(Finding(
                CHECK, ENGINE_PY, 0, f"recompile:{name}",
                f"{name}: program_count grew {len(warmed)} -> "
                f"{eng.program_count} under load — a mid-traffic "
                "compile slipped past the menu",
            ))
    return findings


def _verify_training() -> List[Finding]:
    """Guard/trace byte-identity and zero-added-collectives (plain and
    ZeRO steps) — all on lowered text, no execution."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from .. import trace
    from ..models.transformer import Transformer, TransformerConfig
    from .. import training

    findings: List[Finding] = []
    cfg = TransformerConfig(
        vocab_size=64, num_layers=2, num_heads=4, head_dim=8,
        max_seq_len=16, dtype=jnp.float32, attention_impl="dot",
        causal=True)
    model = Transformer(cfg)
    world = jax.device_count()
    batch = max(world, 8)
    rs = np.random.RandomState(0)
    x = rs.randint(1, cfg.vocab_size,
                   size=(batch, cfg.max_seq_len)).astype(np.int32)
    y = rs.randint(0, cfg.vocab_size,
                   size=(batch, cfg.max_seq_len)).astype(np.int32)
    opt = optax.adamw(1e-3)
    state = training.replicate_state(training.create_train_state(
        model, opt, jax.random.PRNGKey(0), x[:1]))

    def lowered(step):
        return step.lower(state, x, y).as_text()

    def build(guard):
        return training.data_parallel_train_step(model, opt, guard=guard)

    plain_txt = lowered(build(False))
    # env-disabled (guard=None defers to HVD_TPU_GUARD) must be the
    # SAME bytes as guard=False — the observer leaves no residue
    os.environ["HVD_TPU_GUARD"] = "0"
    try:
        disabled_txt = lowered(build(None))
    finally:
        os.environ.pop("HVD_TPU_GUARD", None)
    findings += check_byte_identical("guard-disabled", plain_txt,
                                     disabled_txt)
    findings += check_added_collectives("guard-enabled", plain_txt,
                                        lowered(build(True)))

    # trace on/off: hash-identical lowering (the trace_bench idiom)
    trace.configure(enabled=True)
    on_txt = lowered(build(False))
    trace.configure(enabled=False)
    off_txt = lowered(build(False))
    trace.configure(enabled=True)
    findings += check_byte_identical("trace-on-off", on_txt, off_txt)

    # ZeRO: the guarded step adds 0 collectives over the unguarded one
    def zero_txt(guard):
        st, step, _specs = training.zero_train_setup(
            model, optax.adamw(1e-3), jax.random.PRNGKey(0), x[:1],
            guard=guard)
        return step.lower(st, x, y).as_text()

    findings += check_added_collectives("zero-guard", zero_txt(False),
                                        zero_txt(True))
    return findings


def _verify_hierarchical() -> List[Finding]:
    """modeled_collective_bytes == measured_tier_bytes on the lowered
    hierarchical allreduce over the topology's 2-D mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from ..common import basics
    from ..common.topology import DCN_AXIS, ICI_AXIS
    from ..ops import spmd_ops
    from ..ops.comm_model import (measured_tier_bytes, mesh_slice_ids,
                                  modeled_collective_bytes)
    from ..ops.reduce_ops import Sum

    world = jax.device_count()
    n_ici = max(world // 2, 1)
    if world < 4 or world % n_ici:
        return []
    os.environ["HVD_TPU_SLICE_SIZE"] = str(n_ici)
    try:
        topo = basics._require_init().topology
        hmesh = topo.hierarchical_mesh()
        numel = 4096
        x = jnp.asarray(np.arange(world * numel, dtype=np.float32)
                        .reshape(world, numel))
        fn = jax.jit(jax.shard_map(
            lambda t: spmd_ops.hierarchical_allreduce(t, op=Sum),
            mesh=hmesh, in_specs=P((DCN_AXIS, ICI_AXIS)),
            out_specs=P((DCN_AXIS, ICI_AXIS)), check_vma=False))
        measured = measured_tier_bytes(fn.lower(x).as_text(),
                                       mesh_slice_ids(hmesh))
        modeled = modeled_collective_bytes((numel,), world, n_ici)
        return check_modeled_measured(
            "hierarchical-allreduce",
            {"ici": modeled["ici_bytes"], "dcn": modeled["dcn_bytes"]},
            {"ici": measured["ici_bytes"], "dcn": measured["dcn_bytes"]})
    finally:
        os.environ.pop("HVD_TPU_SLICE_SIZE", None)


def verify(root: str = ".", shards: Sequence[int] = (1, 2),
           requests: int = 512, seed: int = 0) -> List[Finding]:
    """The full jax-requiring verification — every invariant in the
    module docstring.  ``root`` is accepted for PASSES signature
    parity; the programs are built from the installed package, not
    read from disk."""
    import horovod_tpu as hvd

    if not hvd.is_initialized():
        hvd.init()
    findings: List[Finding] = []
    findings += _verify_training()
    findings += _verify_hierarchical()
    findings += _verify_serving(tuple(shards), requests, seed)
    return findings
