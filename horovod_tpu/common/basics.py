"""Lifecycle + topology API: init/shutdown/rank/size/local_rank/local_size.

Parity surface of the reference's ``HorovodBasics``
(horovod/common/__init__.py:51-154) and the C init API
(horovod/common/operations.cc:2413-2468), bound to the TPU pod topology
instead of MPI_COMM_WORLD:

* ``init()``            -> record jax device/process topology, build the
                           default 1-D "hvd" mesh, start aux subsystems.
* ``rank()/size()``     -> chip-granular (see state.py docstring); inside an
                           SPMD region rank() is the traced mesh index.
* ``local_rank()/local_size()``  -> position within this host/process.
* ``mpi_threads_supported()``    -> False (no MPI anywhere), kept for parity.
"""

from __future__ import annotations

import atexit
import os
from typing import Optional, Sequence

from horovod_tpu.common.config import Config
from horovod_tpu.common.exceptions import InvalidArgumentError
from horovod_tpu.common.state import current_spmd_axis, global_state


def init(comm: Optional[Sequence[int]] = None, devices=None) -> None:
    """Initialize the framework.

    ``comm`` optionally restricts the job to a subset of ranks, mirroring
    ``horovod_init(ranks, nranks)`` (reference operations.cc:1728-1746).
    Ranks are chips on the SPMD lane, so ``comm=[0, 2]`` builds the
    "hvd" mesh from chips 0 and 2 of the global device order and
    ``size()`` becomes 2.

    ``devices`` optionally restricts the mesh to an explicit device list
    (TPU extension; the chip-level analogue of the ranks subset).

    Safe to call more than once (reference InitializeHorovodOnce,
    operations.cc:2384-2401).
    """
    state = global_state()
    with state.lock:
        if state.initialized:
            return
        from horovod_tpu.utils import timeline

        timeline.install_compile_listener()
        age = _process_age_s()
        if age is not None:
            # interpreter, imports and whatever reached the backend before
            # the program's first line: set-up that no span of ours covers
            timeline.gauge("hvd.init.process_age_s", age)
        with timeline.span("hvd.init"):
            _init(state, comm, devices)


def _process_age_s() -> Optional[float]:
    """Seconds since this process started, by ``/proc``; ``None`` where
    there is none."""
    try:
        with open("/proc/self/stat") as f:
            # the fields after the command, which may hold spaces
            started = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
    except (OSError, ValueError, IndexError):
        return None
    return up - started / os.sysconf("SC_CLK_TCK")


def _init(state, comm, devices) -> None:
    """``init`` proper, under the state's lock."""
    import jax

    # Multi-host: when the launcher provides a jax coordinator
    # (HOROVOD_JAX_COORDINATOR, set by `hvdrun --jax`), join the jax
    # distributed runtime BEFORE the first backend query so every
    # process sees the global device set — the analogue of the
    # reference joining MPI_COMM_WORLD at init (operations.cc:1724).
    # TPU pods that pre-initialize via the runtime env need nothing
    # here, and single-process usage stays zero-config.
    jax_coord = os.environ.get("HOROVOD_JAX_COORDINATOR", "")
    if jax_coord and os.environ.get("HOROVOD_SIZE"):
        # Skip only when the distributed runtime is ALREADY up (e.g.
        # the TPU pod runtime); a connect failure must propagate —
        # swallowing it would leave this rank world-size 1 while its
        # peers block on the barrier, with zero diagnostics.
        if not jax.distributed.is_initialized():
            jax.distributed.initialize(
                coordinator_address=jax_coord,
                num_processes=int(os.environ["HOROVOD_SIZE"]),
                process_id=int(os.environ.get("HOROVOD_RANK", "0")),
            )
    state.config = Config.from_env()
    state.devices = list(devices) if devices is not None else list(jax.devices())
    if comm is not None:
        # Ranks are chips on the SPMD lane, so the reference's
        # rank-subset semantics (horovod_init(ranks, nranks),
        # operations.cc:1728-1746) map to subsetting the mesh device
        # list: hvd.init(comm=[0, 2]) builds a 2-chip job from chips
        # 0 and 2 of the global order.
        bad = [r for r in comm if not 0 <= r < len(state.devices)]
        if bad:
            raise InvalidArgumentError(
                f"comm ranks {bad} out of range for "
                f"{len(state.devices)} devices"
            )
        state.devices = [state.devices[r] for r in comm]
        if jax.process_count() > 1 and not any(
            getattr(d, "process_index", 0) == jax.process_index()
            for d in state.devices
        ):
            # A process owning NO chip of the subset has no rank; two
            # such processes would otherwise both report rank 0 and
            # double-run every rank-0-gated action (checkpoint writes,
            # logs). Exclude the process at launch instead.
            raise InvalidArgumentError(
                "hvd.init(comm=...) selected no chips owned by this "
                "process; multi-host subsets must cover every "
                "participating process (exclude the others at the "
                "launcher level)."
            )
    state.process_index = jax.process_index()
    state.process_count = jax.process_count()
    if devices is not None or comm is not None:
        local_indices = [
            i
            for i, d in enumerate(state.devices)
            if getattr(d, "process_index", 0) == jax.process_index()
        ]
        state.local_device_count = len(local_indices)
        state.global_device_count = len(state.devices)
        state.first_device_index = local_indices[0] if local_indices else 0
    else:
        state.local_device_count = jax.local_device_count()
        state.global_device_count = jax.device_count()
        state.first_device_index = jax.process_index() * jax.local_device_count()
    state.subset_ranks = list(comm) if comm is not None else None

    from jax.sharding import Mesh
    import numpy as np

    from horovod_tpu.parallel.logical import DATA_AXIS

    state.mesh = Mesh(np.asarray(state.devices), (DATA_AXIS,))

    from horovod_tpu.utils.timeline import Timeline

    state.timeline = Timeline(
        state.config.timeline_path or None,
        enabled_rank=state.process_index == 0,
    )

    if state.config.autotune:
        # HOROVOD_AUTOTUNE on the SPMD lane: sweep the fusion threshold
        # against measured step rate (reference parameter_manager.h:
        # 211-217 scoring semantics; see horovod_tpu/jax/autotune.py).
        from horovod_tpu.jax.autotune import StepAutotuner

        # Log on process 0 only (the reference gated tuner logging to
        # the coordinator rank); every process still RUNS the tuner so
        # generations stay in lockstep.
        state.autotuner = StepAutotuner(
            state.config,
            log_path=(
                state.config.autotune_log
                if state.process_index == 0
                else ""
            ),
        )

    state.initialized = True
    atexit.register(shutdown)


def shutdown() -> None:
    """Coordinated shutdown (reference horovod_shutdown,
    operations.cc:2425-2439). Flushes the timeline and drops state."""
    state = global_state()
    with state.lock:
        if not state.initialized:
            return
        if state.timeline is not None:
            state.timeline.close()
        if state.autotuner is not None:
            state.autotuner.close()
            state.autotuner = None
        if state.native is not None:
            state.native.shutdown()
            state.native = None
        state.initialized = False
        state.mesh = None
        state.devices = []


def is_initialized() -> bool:
    return global_state().initialized


def size() -> int:
    """Total number of chips in the job (reference horovod_size,
    operations.cc:2448, where the unit was one process == one GPU)."""
    state = global_state()
    state.require_init()
    return state.global_device_count


def local_size() -> int:
    """Chips attached to this process (reference horovod_local_size,
    operations.cc:2456)."""
    state = global_state()
    state.require_init()
    return state.local_device_count


def rank():
    """Global rank.

    Inside an SPMD region: the traced chip index along the "hvd" mesh axis.
    Outside: the global index of this process's first chip (so rank()==0
    selects the logging/checkpointing process, reference horovod_rank
    operations.cc:2441).
    """
    state = global_state()
    state.require_init()
    axis = current_spmd_axis()
    if axis is not None:
        from jax import lax

        return lax.axis_index(axis)
    return state.first_device_index


def local_rank():
    """Rank within this process/host (reference horovod_local_rank,
    operations.cc:2444). Traced inside SPMD regions."""
    state = global_state()
    state.require_init()
    axis = current_spmd_axis()
    if axis is not None:
        from jax import lax

        # Assumes a uniform chips-per-process layout (true for every TPU
        # slice topology; device subsets that break it would need a
        # per-process constant, which would diverge the SPMD programs).
        return lax.axis_index(axis) % max(state.local_device_count, 1)
    return 0


def process_rank() -> int:
    """Index of this process (TPU extension; == jax.process_index())."""
    state = global_state()
    state.require_init()
    return state.process_index


def process_count() -> int:
    """Number of processes (TPU extension; == jax.process_count())."""
    state = global_state()
    state.require_init()
    return state.process_count


def mpi_threads_supported() -> bool:
    """Parity shim for horovod_mpi_threads_supported (operations.cc:2462-2468).

    There is no MPI in this framework; always False.
    """
    global_state().require_init()
    return False


def mesh():
    """The default 1-D device mesh with axis name "hvd"."""
    state = global_state()
    state.require_init()
    return state.mesh


def check_extension(ext_name: str, ext_env_var: str, path=None) -> None:
    """Parity shim for HorovodBasics.check_extension
    (reference horovod/common/__init__.py:43-48): raise if a binding was
    disabled at build time. All of our bindings are pure-config, so the
    only failure mode is an explicit opt-out via the env var."""
    if os.environ.get(ext_env_var, "") in ("0", "false", "False"):
        raise ImportError(
            f"Extension {ext_name} has been disabled via {ext_env_var}"
        )
