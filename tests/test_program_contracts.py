"""The jax-requiring half of the ``programs`` analysis pass.

tests/test_static_analysis.py proves the pure check helpers catch
injected drift (slice-spanning collective, off-menu key, byte drift);
this file runs the REAL verification legs against the framework's own
lowered programs — the same code path ``tools/verify_programs.py``
(the program-verify CI job) runs at full scale, here scaled down so
tier-1 stays fast:

* training leg — guard/trace byte-identity, zero added collectives
  (plain + ZeRO);
* hierarchical leg — modeled == measured per-tier bytes of the
  two-level allreduce over the 8-device virtual world;
* serving leg — DCN-exclusion + modeled == measured psum stream per
  tier program + the zero-recompile lint, on a small randomized load.

Marker: ``analysis`` (these ARE the contract checker, jax flavor).
"""

import pytest

from horovod_tpu.analysis import programs

pytestmark = pytest.mark.analysis


def _render(findings):
    return "\n".join(f.render() for f in findings)


def test_training_program_contracts():
    findings = programs._verify_training()
    assert not findings, _render(findings)


def test_hierarchical_allreduce_modeled_equals_measured():
    findings = programs._verify_hierarchical()
    assert not findings, _render(findings)


@pytest.mark.slow
def test_serving_program_contracts_small_load():
    # shards 1 AND 2 plus the speculative engine; the load is small —
    # the 512-request sweep is the program-verify CI job's
    # (tools/verify_programs.py defaults)
    findings = programs._verify_serving((1, 2), requests=24, seed=0)
    assert not findings, _render(findings)
