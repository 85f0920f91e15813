"""Native C++ core loader.

Reference parity: horovod/common/basics.py loading the compiled
``mpi_lib_v2`` extension (SURVEY.md §2.1 'HorovodBasics').  The native
library (``libhvd_tpu_core.so``, built from ``horovod_tpu/native/src``)
holds the background controller: TensorQueue, negotiation Controller,
ResponseCache, FusionBufferManager accounting, Timeline writer,
StallInspector and ParameterManager — the C++ components SURVEY.md §7.1
requires as native, dispatching into XLA executables owned by the Python
engine.

The library is built from source at the first ``hvd.init()`` of a
checkout (git carries no binary).  A Python controller with the same
interface stands in only where it is CHOSEN: ``HVD_TPU_DISABLE_NATIVE``,
or a multi-process world started without the launcher's negotiation
port.  When the native core is wanted, a build or load that fails raises.
"""

from __future__ import annotations

import os

from ..common.topology import Topology
from ..utils.env_parser import Config
from ..utils.logging import get_logger

_LIB_NAME = "libhvd_tpu_core.so"


class PyFallbackController:
    """Interface-compatible stand-in where the native core is not wanted.

    Single-controller SPMD needs no negotiation (every collective is a
    deterministic compiled program), so the fallback only tracks lifecycle.
    """

    is_native = False

    def __init__(self, topology: Topology, config: Config):
        self._topology = topology
        self._config = config
        self._shutdown = False

    def shutdown(self) -> None:
        self._shutdown = True


def _lib_path() -> str:
    return os.path.join(os.path.dirname(__file__), _LIB_NAME)


_build_attempted = False
# seconds `make` took in this process when it made the library anew (its
# mtime moved); None when the library was found fresh, or nothing was built
_built_s = None


def build_args() -> dict:
    """What the ``hvd.init.controller`` span says of the build: ``built``,
    and ``build_s`` when the core was compiled in this process."""
    if _built_s is None:
        return {"built": False}
    return {"built": True, "build_s": _built_s}


def _maybe_build() -> None:
    """Lazy build: run make once per process; make itself decides staleness
    from source timestamps, so edited sources always rebuild (reference
    analog: setup.py's build_ext compiling the CMake tree — §2.5; here a
    plain Makefile, no third-party deps).

    Processes that start together (launcher ranks, test workers) take
    turns on a lock over the Makefile, so one builds and the rest find the
    library fresh.  Without a toolchain an existing library is used as it
    is; a failed build, or no library and no toolchain, raises."""
    global _build_attempted, _built_s
    if _build_attempted:
        return
    import fcntl
    import shutil
    import subprocess
    import time

    def mtime():
        try:
            return os.stat(_lib_path()).st_mtime_ns
        except OSError:
            return None

    src = os.path.join(os.path.dirname(__file__), "src")
    if shutil.which("make") and shutil.which("g++"):
        with open(os.path.join(src, "Makefile")) as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            before, t0 = mtime(), time.perf_counter()
            try:
                subprocess.run(["make"], cwd=src, check=True,
                               capture_output=True, text=True, timeout=300)
            except subprocess.CalledProcessError as e:
                raise RuntimeError(
                    f"native core build failed:\n{e.stderr[-4000:]}") from e
            if mtime() != before:
                _built_s = time.perf_counter() - t0
    elif not os.path.exists(_lib_path()):
        raise RuntimeError(
            f"native core: {_lib_path()} is not built and make/g++ are not "
            "on PATH (set HVD_TPU_DISABLE_NATIVE=1 to choose the python "
            "controller)")
    _build_attempted = True


def load_controller(topology: Topology, config: Config):
    """Load the native controller, or the Python one where it is chosen.

    Reference: horovod/common/basics.py __init__ (extension dlopen) +
    horovod_init (operations.cc).
    """
    if os.environ.get("HVD_TPU_DISABLE_NATIVE", "0") in ("1", "true"):
        return PyFallbackController(topology, config)
    if topology.num_processes > 1 and not os.environ.get(
        "HVD_TPU_NATIVE_PORT"
    ):
        # multi-process world without the launcher's negotiation channel:
        # per-rank loopback controllers would make fusion timing-dependent
        # and diverge the ranks' XLA programs — use the deterministic
        # Python path instead (launch via tpurun to get the native core).
        get_logger().info(
            "multi-process world without HVD_TPU_NATIVE_PORT; using the "
            "python controller (launch with tpurun for the native core)"
        )
        return PyFallbackController(topology, config)
    _maybe_build()
    from .controller import NativeController  # deferred: needs lib

    return NativeController(_lib_path(), topology, config)
