"""Test configuration: force an 8-device virtual CPU mesh.

Reference test-strategy parity (SURVEY.md §4): the reference simulates
multi-node on one box via N processes + Gloo over loopback; the TPU-native
equivalent is one process with N virtual CPU devices
(``--xla_force_host_platform_device_count``) — per-rank semantics are then
exercised through ``hvd.run_per_rank`` (shard_map), reproducing the
``horovodrun -np N pytest`` per-rank pattern in-process.

The suite always runs on the CPU: JAX_PLATFORMS is set before jax is
imported and the config is pinned again after, so an inherited
JAX_PLATFORMS or a plugin cannot move it.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent XLA compilation cache: the suite builds hundreds of
# fresh ServingEngine / mesh instances whose programs lower to
# byte-identical HLO, and per-instance jit closures defeat jax's
# in-memory cache — the disk cache dedupes the XLA compile step both
# within a run and across runs on the same machine.  Semantics-free
# (lowering, engine program counters, and StableHLO pins are all
# upstream of the XLA compile).  Opt out: HVD_TPU_TEST_JAX_CACHE=0.
if os.environ.get("HVD_TPU_TEST_JAX_CACHE", "1") != "0":
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR", "/tmp/hvd_tpu_xla_cache")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _hvd_init():
    import horovod_tpu as hvd

    hvd.init()
    assert hvd.size() == 8, (
        f"expected 8 virtual CPU devices, got {hvd.size()} "
        f"(backend={jax.default_backend()})"
    )
    yield


# A compiled CPU program is loaded as one object a kernel, three memory
# mappings each (code, constants, data), and jax's caches keep every program
# of a worker's earlier modules: a worker that has run a few heavy files
# holds 60,000 mappings, and at the kernel's limit (vm.max_map_count,
# 65,530) the next load segfaults inside ``deserialize_executable``.  So a
# module that ends with the process past half the limit drops jax's caches
# (the programs come back from the persistent cache above when wanted).
_MAPPINGS_TO_DROP_AT = 30_000


@pytest.fixture(scope="module", autouse=True)
def _bounded_memory_mappings():
    yield
    try:
        with open("/proc/self/maps") as maps:
            mappings = sum(1 for _ in maps)
    except OSError:  # no procfs: nothing to bound
        return
    if mappings > _MAPPINGS_TO_DROP_AT:
        jax.clear_caches()
