"""Tests of the benchmark itself, at sizes the CPU holds.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

Not part of the repository's tier-1 suite (that collects ``tests/``).
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "benchmark")):
    if p not in sys.path:
        sys.path.insert(0, p)
