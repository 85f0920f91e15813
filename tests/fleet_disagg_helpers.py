"""What the disaggregated-fleet test files share (tests/test_fleet_disagg*.py):
the one-layer model with its engine builder, built once a file, and the
prompts long enough for a warm handoff.
"""

import numpy as np
import pytest


@pytest.fixture(scope="module")
def disagg_pieces():
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import (
        Transformer, TransformerConfig,
    )
    from horovod_tpu.serving import ServeConfig, ServingEngine

    cfg = TransformerConfig(
        vocab_size=97, num_layers=1, num_heads=2, num_kv_heads=2,
        head_dim=8, max_seq_len=48, dtype=jnp.float32,
        attention_impl="dot", causal=True)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32), train=False)["params"]
    serve = ServeConfig(block_size=8, num_blocks=0, token_budget=128,
                        watermark=2, prefill_tiers=(32,),
                        decode_tiers=(1, 2), prefill_chunk=8)

    def build(role="both"):
        return ServingEngine(cfg, params, serve=serve, role=role)

    return cfg, params, serve, build


def _prompts(seed, n, lo=9, hi=14):
    """>= 9 tokens each: at least one FULL block at block_size=8, so
    prefill-complete exports always have a warm-path chain."""
    rs = np.random.RandomState(seed)
    return [rs.randint(1, 90, size=rs.randint(lo, hi)).astype(np.int32)
            for _ in range(n)]
