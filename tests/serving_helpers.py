"""What the serving test files share (tests/test_serving*.py): the tiny
model each file builds once, the one-at-a-time reference decode that IS
the oracle, and the loads, mesh and clocked engine their cases draw on.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models.transformer import Transformer, TransformerConfig
from horovod_tpu.serving import ServeConfig, ServingEngine


@pytest.fixture(scope="module")
def model_and_params():
    cfg = TransformerConfig(
        vocab_size=97, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=8, max_seq_len=64, dtype=jnp.float32,
        attention_impl="dot", causal=True)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32), train=False)["params"]
    return cfg, model, params


def ref_decode(model, params, prompt, n, eos_id=None):
    """One-at-a-time full-context greedy decode (no cache at all)."""
    toks = list(np.asarray(prompt))
    out = []
    for _ in range(n):
        x = jnp.asarray(np.asarray(toks, np.int32))[None]
        logits = model.apply({"params": params}, x, train=False)
        t = int(jnp.argmax(logits[0, -1].astype(jnp.float32)))
        toks.append(t)
        out.append(t)
        if eos_id is not None and t == eos_id:
            break
    return np.asarray(out, np.int32)


def _prompts(rs, n, lo=3, hi=20):
    return [rs.randint(1, 97, size=rs.randint(lo, hi)).astype(np.int32)
            for _ in range(n)]


def _templated_load(rs, n, templates, lo=3, hi=41):
    """Randomized load where ~half the prompts start with one of the
    shared templates — the dominant production shape (shared system
    prompts / few-shot headers) the prefix cache exists for."""
    load = []
    for _ in range(n):
        suffix = rs.randint(1, 97, size=rs.randint(lo, hi)).astype(np.int32)
        if rs.random_sample() < 0.5:
            t = templates[rs.randint(len(templates))]
            prompt = np.concatenate([t, suffix])[:57]  # < max_seq_len-gen
        else:
            prompt = suffix
        load.append((prompt, int(rs.randint(1, 7))))
    return load


def _template_prompts(rs, n, t_len=19, s_lo=2, s_hi=6):
    template = rs.randint(1, 97, size=t_len).astype(np.int32)
    return [np.concatenate([
        template, rs.randint(1, 97, size=rs.randint(s_lo, s_hi))
        .astype(np.int32)]) for _ in range(n)]


def _shard_mesh(n):
    from horovod_tpu.parallel import tensor_shard_mesh

    return tensor_shard_mesh("tp", n)


def _deadline_engine(cfg, params, clock, **kw):
    serve = ServeConfig(block_size=8, num_blocks=0, token_budget=128,
                        watermark=2, decode_tiers=(1, 2, 4), **kw)
    return ServingEngine(cfg, params, serve=serve, clock=clock)
