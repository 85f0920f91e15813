"""Environment-variable configuration.

Reference parity: horovod/common/utils/env_parser.cc + SURVEY.md §5.6 — env
is the single source of truth at init time; the launcher CLI and YAML config
file both converge on these variables.  Knob names keep the reference's
spelling with an ``HVD_TPU_`` prefix (the launcher also accepts the classic
``HOROVOD_`` spelling for drop-in compatibility).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional


def _get(name: str, default: Optional[str] = None) -> Optional[str]:
    """Look up ``HVD_TPU_<name>`` falling back to ``HOROVOD_<name>``."""
    v = os.environ.get(f"HVD_TPU_{name}")
    if v is None:
        v = os.environ.get(f"HOROVOD_{name}")
    return v if v is not None else default


def _get_int(name: str, default: int) -> int:
    v = _get(name)
    try:
        return int(v) if v is not None else default
    except ValueError:
        return default


def _get_int_validated(name: str, default: int) -> int:
    """Strict integer knob: a set-but-garbage or negative value is a
    configuration ERROR, not a silent default.  Used for the fusion/
    bucket byte thresholds, where a typo'd ``64MB`` or a negative value
    would otherwise silently fall through to the one-bucket-per-tensor
    path and tank collective efficiency without any signal."""
    v = _get(name)
    if v is None:
        return default
    # name the variable the user ACTUALLY set — the error must point at
    # the HOROVOD_* compatibility alias when that is where the value
    # came from, or "unset it" sends them after the wrong knob
    var = (
        f"HVD_TPU_{name}"
        if os.environ.get(f"HVD_TPU_{name}") is not None
        else f"HOROVOD_{name}"
    )
    try:
        value = int(v)
    except ValueError:
        raise ValueError(
            f"{var} must be an integer (bytes/count), got "
            f"{v!r} — unset it or pass a plain integer"
        ) from None
    if value < 0:
        raise ValueError(
            f"{var} must be >= 0, got {value} "
            f"(0 disables fusion: one bucket per tensor)"
        )
    return value


def _get_float(name: str, default: float) -> float:
    v = _get(name)
    try:
        return float(v) if v is not None else default
    except ValueError:
        return default


def _get_bool(name: str, default: bool) -> bool:
    v = _get(name)
    if v is None:
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


@dataclasses.dataclass
class Config:
    """Runtime knobs, mirroring the reference's ~40 HOROVOD_* env vars
    (SURVEY.md §5.6).  Only the knobs meaningful on TPU are kept; the rest
    are accepted and ignored by the launcher for compatibility."""

    # Tensor fusion (horovod/common/fusion_buffer_manager.cc):
    fusion_threshold_bytes: int = 64 * 1024 * 1024  # HOROVOD_FUSION_THRESHOLD
    # Background controller cycle (horovod/common/operations.cc RunLoopOnce):
    cycle_time_ms: float = 1.0  # HOROVOD_CYCLE_TIME
    # Response cache (horovod/common/response_cache.cc):
    cache_capacity: int = 1024  # HOROVOD_CACHE_CAPACITY
    # Timeline (horovod/common/timeline.cc):
    timeline_filename: str = ""  # HOROVOD_TIMELINE
    timeline_mark_cycles: bool = False  # HOROVOD_TIMELINE_MARK_CYCLES
    # Stall inspector (horovod/common/stall_inspector.cc):
    stall_check_disable: bool = False  # HOROVOD_STALL_CHECK_DISABLE
    stall_warning_time_seconds: float = 60.0  # HOROVOD_STALL_CHECK_TIME_SECONDS
    stall_shutdown_time_seconds: float = 0.0  # HOROVOD_STALL_SHUTDOWN_TIME_SECONDS
    # Autotune (horovod/common/parameter_manager.cc):
    autotune: bool = False  # HOROVOD_AUTOTUNE
    autotune_log: str = ""  # HOROVOD_AUTOTUNE_LOG
    # The torch bridge's bucketed submission (torch/optimizer.py,
    # docs/tensor-fusion.md): bucket size of its BucketSchedule (0 = one
    # bucket per tensor).
    overlap_bucket_bytes: int = 4 * 1024 * 1024  # HVD_TPU_OVERLAP_BUCKET_BYTES
    # Hierarchical allreduce (nccl_operations.cc NCCLHierarchicalAllreduce):
    hierarchical_allreduce: bool = False  # HOROVOD_HIERARCHICAL_ALLREDUCE
    # DCN-hop wire format for routed hierarchical allreduces
    # (compression.DcnCompression; "" = full precision):
    dcn_wire_dtype: str = ""  # HVD_TPU_DCN_WIRE_DTYPE
    # Elastic:
    elastic: bool = False  # HOROVOD_ELASTIC
    # Logging:
    log_level: str = "warning"  # HOROVOD_LOG_LEVEL
    # TPU specific: dispatch collectives via XLA (the only backend; kept for
    # BASELINE.json's HOROVOD_TPU_OPERATIONS=XLA contract).
    tpu_operations: str = "XLA"

    @staticmethod
    def from_env() -> "Config":
        return Config(
            fusion_threshold_bytes=_get_int_validated(
                "FUSION_THRESHOLD", 64 * 1024 * 1024),
            cycle_time_ms=_get_float("CYCLE_TIME", 1.0),
            cache_capacity=_get_int("CACHE_CAPACITY", 1024),
            timeline_filename=_get("TIMELINE", "") or "",
            timeline_mark_cycles=_get_bool("TIMELINE_MARK_CYCLES", False),
            stall_check_disable=_get_bool("STALL_CHECK_DISABLE", False),
            stall_warning_time_seconds=_get_float("STALL_CHECK_TIME_SECONDS", 60.0),
            stall_shutdown_time_seconds=_get_float("STALL_SHUTDOWN_TIME_SECONDS", 0.0),
            autotune=_get_bool("AUTOTUNE", False),
            autotune_log=_get("AUTOTUNE_LOG", "") or "",
            overlap_bucket_bytes=_get_int_validated(
                "OVERLAP_BUCKET_BYTES", 4 * 1024 * 1024),
            hierarchical_allreduce=_get_bool("HIERARCHICAL_ALLREDUCE", False),
            dcn_wire_dtype=(_get("DCN_WIRE_DTYPE", "") or "").lower(),
            elastic=_get_bool("ELASTIC", False),
            log_level=(_get("LOG_LEVEL", "warning") or "warning").lower(),
            tpu_operations=(_get("TPU_OPERATIONS", "XLA") or "XLA").upper(),
        )
