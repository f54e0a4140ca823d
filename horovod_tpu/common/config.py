"""Environment-variable configuration surface.

Keeps the reference's env-var names verbatim so scripts written against the
reference keep working (reference: horovod/common/operations.h:56-66 and the
parsing block horovod/common/operations.cc:1707-1909).

All values are read lazily at ``hvd.init()`` time into a :class:`Config`
snapshot, so tests can monkeypatch ``os.environ`` before init.
"""

from __future__ import annotations

import dataclasses
import os

# Reference defaults: 64 MB fusion threshold, 5 ms cycle time
# (horovod/common/operations.cc:1846, operations.h:56-60).
DEFAULT_FUSION_THRESHOLD = 64 * 1024 * 1024
DEFAULT_CYCLE_TIME_MS = 5.0
# HOROVOD_OVERLAP values (see horovod_tpu.jax.fusion.resolve_overlap).
OVERLAP_MODES = ("auto", "on", "off")
# HOROVOD_HIERARCHICAL values (horovod_tpu.jax.fusion.
# resolve_hierarchical): run each fused bucket as the two-level
# intra-slice reduce-scatter -> inter-slice exchange -> intra-slice
# all-gather ladder instead of one flat psum. "auto" (default) engages
# only when the device set spans a DCN boundary (multiple slices, or
# multiple processes — parallel.mesh.slice_topology); "on" forces the
# ladder with HOROVOD_HIERARCHICAL_INNER_SIZE (or chips-per-process)
# as the fast-domain size; "off" is the flat collective.
HIERARCHICAL_MODES = ("auto", "on", "off")
# Reference: FUSION_BUFFER_ATOMIC_UNIT alignment (operations.h:52-54).
FUSION_BUFFER_ATOMIC_UNIT = 64
# Reference: STALL_WARNING_TIME 60s (operations.cc:258).
DEFAULT_STALL_WARNING_SECS = 60.0
# Bounded deadline on native-lane collective completion
# (HOROVOD_NEGOTIATION_TIMEOUT, seconds). 0 = reference behavior: warn
# on stalls, wait forever. Non-zero: NativeCore.wait raises a typed
# HorovodTimeoutError past the deadline instead of hanging silently —
# the elastic supervisor (horovod_tpu/elastic/) converts that into a
# relaunch from the last snapshot.
DEFAULT_NEGOTIATION_TIMEOUT_SECS = 0.0
# Elastic snapshot cadence (steps between host-RAM snapshots). Sized so
# a ~1 ms/100 MB d2h snapshot against a ~20 ms step stays well under a
# 2% overhead budget at the default; docs/elastic.md has the cadence
# math (HOROVOD_SNAPSHOT_EVERY).
DEFAULT_SNAPSHOT_EVERY = 100
# Supervisor health-watchdog deadline (HOROVOD_WATCHDOG_TIMEOUT,
# seconds): a rank whose per-window-boundary heartbeat goes stale past
# this is killed, classified "stalled" and the job relaunched from the
# last snapshot. FINITE by default — unlike HOROVOD_NEGOTIATION_TIMEOUT
# (0 = wait forever, the reference's semantics), a silent stall under
# --elastic must terminate. Must exceed the slowest window-boundary
# interval; 300 s covers real training windows with wide margin.
# 0 disables the watchdog.
DEFAULT_WATCHDOG_TIMEOUT_SECS = 300.0


def _env_bool(name: str) -> bool:
    v = os.environ.get(name, "")
    return v not in ("", "0", "false", "False", "FALSE")


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    try:
        return float(v)
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    try:
        return int(v)
    except ValueError:
        return default


def _env_choice(name: str, default: str, choices) -> str:
    v = os.environ.get(name, "").strip().lower()
    return v if v in choices else default


@dataclasses.dataclass
class Config:
    """Snapshot of every runtime knob, read once at init."""

    # Gradient-bucket fusion threshold in bytes (HOROVOD_FUSION_THRESHOLD).
    fusion_threshold: int = DEFAULT_FUSION_THRESHOLD
    # Backward-overlapped bucket collectives (HOROVOD_OVERLAP=auto|on|off):
    # issue per-bucket reductions in reverse bucket order, start-all/
    # unpack-later, so XLA's async collective scheduling can hide them
    # under remaining backward compute. "auto" (default) engages whenever
    # the plan has >= 2 buckets and degrades to the legacy single-pass
    # emission otherwise; never changes numerics (docs/tensor-fusion.md).
    overlap: str = "auto"
    # Coordinator cycle time in ms — only meaningful for the native eager
    # backend; the XLA path has no background loop (HOROVOD_CYCLE_TIME).
    cycle_time_ms: float = DEFAULT_CYCLE_TIME_MS
    # Chrome-trace timeline output path (HOROVOD_TIMELINE).
    timeline_path: str = ""
    # Autotuner (HOROVOD_AUTOTUNE / HOROVOD_AUTOTUNE_LOG).
    autotune: bool = False
    autotune_log: str = ""
    # Stall detection (HOROVOD_STALL_CHECK_DISABLE).
    stall_check_disable: bool = False
    stall_warning_secs: float = DEFAULT_STALL_WARNING_SECS
    # Native collective completion deadline (HOROVOD_NEGOTIATION_TIMEOUT,
    # seconds; 0 = wait forever, the reference's semantics).
    negotiation_timeout_secs: float = DEFAULT_NEGOTIATION_TIMEOUT_SECS
    # Elastic snapshot cadence (HOROVOD_SNAPSHOT_EVERY, steps).
    snapshot_every: int = DEFAULT_SNAPSHOT_EVERY
    # Supervisor health-watchdog deadline (HOROVOD_WATCHDOG_TIMEOUT,
    # seconds; 0 disables). Stale-heartbeat workers are killed and the
    # incident classified "stalled".
    watchdog_timeout_secs: float = DEFAULT_WATCHDOG_TIMEOUT_SECS
    # Hierarchical bucket collectives (HOROVOD_HIERARCHICAL=auto|on|off):
    # each fused bucket runs the two-level intra-slice reduce-scatter ->
    # inter-slice DCN exchange -> intra-slice all-gather ladder. "auto"
    # keys off a multi-slice/DCN-present device set (HIERARCHICAL_MODES
    # above; horovod_tpu/jax/fusion.py resolve_hierarchical).
    hierarchical: str = "auto"
    # Hierarchical collectives (legacy boolean spelling): on TPU this
    # selects the explicit two-level ladder (reduce-scatter in the fast
    # domain, cross-reduce, all-gather) rather than NCCL+MPI staging
    # (reference semantics: operations.cc:1284-1436 allreduce,
    # :929-1032 allgather). HOROVOD_HIERARCHICAL_ALLREDUCE=1 is read as
    # HOROVOD_HIERARCHICAL=on.
    hierarchical_allreduce: bool = False
    hierarchical_allgather: bool = False
    # Fast-domain (ICI) size for the hierarchical ladder. 0 = auto: the
    # chips-per-process count (the reference's local_comm split,
    # operations.cc:1760-1797). TPU-native extension knob
    # (HOROVOD_HIERARCHICAL_INNER_SIZE) so single-host jobs can pin the
    # ICI/DCN boundary explicitly.
    hierarchical_inner_size: int = 0
    # Log level (HOROVOD_LOG_LEVEL: trace|debug|info|warning|error|fatal).
    log_level: str = "warning"
    log_hide_time: bool = False

    @classmethod
    def from_env(cls) -> "Config":
        return cls(
            fusion_threshold=_env_int(
                "HOROVOD_FUSION_THRESHOLD", DEFAULT_FUSION_THRESHOLD
            ),
            overlap=_env_choice("HOROVOD_OVERLAP", "auto", OVERLAP_MODES),
            cycle_time_ms=_env_float("HOROVOD_CYCLE_TIME", DEFAULT_CYCLE_TIME_MS),
            timeline_path=os.environ.get("HOROVOD_TIMELINE", ""),
            autotune=_env_bool("HOROVOD_AUTOTUNE"),
            autotune_log=os.environ.get("HOROVOD_AUTOTUNE_LOG", ""),
            stall_check_disable=_env_bool("HOROVOD_STALL_CHECK_DISABLE"),
            stall_warning_secs=_env_float(
                "HOROVOD_STALL_WARNING_TIME", DEFAULT_STALL_WARNING_SECS
            ),
            negotiation_timeout_secs=_env_float(
                "HOROVOD_NEGOTIATION_TIMEOUT",
                DEFAULT_NEGOTIATION_TIMEOUT_SECS,
            ),
            snapshot_every=_env_int(
                "HOROVOD_SNAPSHOT_EVERY", DEFAULT_SNAPSHOT_EVERY
            ),
            watchdog_timeout_secs=_env_float(
                "HOROVOD_WATCHDOG_TIMEOUT", DEFAULT_WATCHDOG_TIMEOUT_SECS
            ),
            hierarchical=_env_choice(
                "HOROVOD_HIERARCHICAL", "auto", HIERARCHICAL_MODES
            ),
            hierarchical_allreduce=_env_bool("HOROVOD_HIERARCHICAL_ALLREDUCE"),
            hierarchical_allgather=_env_bool("HOROVOD_HIERARCHICAL_ALLGATHER"),
            hierarchical_inner_size=_env_int(
                "HOROVOD_HIERARCHICAL_INNER_SIZE", 0
            ),
            log_level=os.environ.get("HOROVOD_LOG_LEVEL", "warning").lower(),
            log_hide_time=_env_bool("HOROVOD_LOG_HIDE_TIME"),
        )


def round_to_atomic_unit(nbytes: int) -> int:
    """Round a buffer size up to the fusion atomic unit.

    Mirrors the reference's FUSION_BUFFER_ATOMIC_UNIT sizing rule
    (horovod/common/operations.cc:742-764) so bucket boundaries stay aligned
    for the TPU lane width as well (64 B = 16 f32 lanes).
    """
    unit = FUSION_BUFFER_ATOMIC_UNIT
    return (nbytes + unit - 1) // unit * unit
