"""Environment selection for the ctypes integration tests."""

import os


# -- native-library selection (sanitizer reruns) ------------------------------
#
# The ctypes fault/auth tests drive whichever core library these two
# variables select, so the same tests re-run unchanged against the
# TSan/ASan builds (tools/rebuild_native.sh --sanitize=...; see
# docs/ANALYSIS.md).  The sanitizer runtimes must be the FIRST loaded
# DSO, hence the child-side LD_PRELOAD hook.

NATIVE_LIB_ENV = "HVD_TPU_TEST_NATIVE_LIB"
CHILD_PRELOAD_ENV = "HVD_TPU_TEST_CHILD_PRELOAD"


def native_lib_path(repo: str) -> str:
    """Path of the core library under test: the committed/production
    build unless HVD_TPU_TEST_NATIVE_LIB points at an instrumented one."""
    return os.environ.get(NATIVE_LIB_ENV) or os.path.join(
        repo, "horovod_tpu", "native", "libhvd_tpu_core.so")


def native_child_env() -> dict:
    """os.environ copy for a ctypes child process, with the sanitizer
    runtime LD_PRELOADed when a rerun requests it (dlopen'ing a
    TSan/ASan-instrumented .so requires its runtime to be loaded first
    — static-TLS/shadow setup fails otherwise)."""
    env = os.environ.copy()
    preload = env.get(CHILD_PRELOAD_ENV)
    if preload:
        # prepend: the sanitizer runtime must come first, but any
        # preload already in force (jemalloc, profiler shims) stays
        existing = env.get("LD_PRELOAD")
        env["LD_PRELOAD"] = (f"{preload}:{existing}" if existing
                             else preload)
    return env
