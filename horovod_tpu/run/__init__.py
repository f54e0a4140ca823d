"""horovod_tpu.run — the job launcher.

Role parity with the reference's two launch paths:

* ``horovodrun``-style CLI (``python -m horovod_tpu.run -np N cmd...``) —
  the reference delegated this to ``mpirun`` (docs/running.md); here the
  launcher owns process placement directly.
* ``horovod_tpu.run.run(fn, np=N)`` — the ``horovod.spark.run`` analogue
  (reference spark/__init__.py:80-196): ship a pickled function to N
  workers, run it, collect per-rank results, fail fast on any error.

Each worker gets the Horovod environment (HOROVOD_RANK/SIZE/LOCAL_RANK/
LOCAL_SIZE/CONTROLLER/SECRET), replacing the reference's MPI-provided
COMM_WORLD (operations.cc:1748-1797). Multi-host: ``-H host:n,...`` execs
workers over ssh with the same env (driver must be reachable).
"""

from __future__ import annotations

import os
import random
import shlex
import signal
import socket
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import dataclasses

from horovod_tpu.run.driver import (Driver, WorkerExit, classify_exit,
                                    EXIT_CLEAN, EXIT_PREEMPTED,
                                    EXIT_RESIZED, EXIT_USAGE)
from horovod_tpu.run.network import make_secret_key


class LaunchError(RuntimeError):
    def __init__(self, message: str, failures: Optional[dict] = None):
        super().__init__(message)
        self.failures = failures or {}


@dataclasses.dataclass
class JobResult:
    """Outcome of one :func:`launch_job` attempt, with PER-WORKER exit
    codes instead of the single collapsed code the kill-all used to
    return. ``trigger`` is the first worker observed failing (the one
    whose death caused the kill-all); the other ranks' codes then
    reflect the supervisor's SIGTERM, not their own fault.
    ``stalled_ranks`` maps each rank the health watchdog killed for a
    stale heartbeat to the observed heartbeat age (the time-to-detect
    evidence the elastic recovery metrics stamp). ``pre_kill_codes``
    holds every non-clean exit observed BEFORE the kill-all — these
    ranks died on their own, so (unlike ``exit_codes``, polluted by
    the teardown SIGTERMs) they tell the elastic supervisor how many
    workers were actually lost when it decides the shrink size."""

    exit_codes: Dict[int, Optional[int]]
    trigger: Optional[WorkerExit] = None
    stalled_ranks: Dict[int, float] = dataclasses.field(
        default_factory=dict)
    pre_kill_codes: Dict[int, int] = dataclasses.field(
        default_factory=dict)

    @property
    def code(self) -> int:
        return self.trigger.code if self.trigger is not None else EXIT_CLEAN

    @property
    def category(self) -> str:
        """clean | usage | preempted | resized | stalled | crashed —
        the trigger worker's classification (run.driver.classify_exit,
        plus the watchdog's stalled mark)."""
        if self.trigger is not None:
            return self.trigger.category
        return classify_exit(self.code)

    def describe(self) -> str:
        if self.trigger is None:
            return "all ranks exited cleanly"
        return (f"rank {self.trigger.rank} "
                f"{self.trigger.category} (exit {self.trigger.code}); "
                "per-rank codes "
                + str({r: c for r, c in sorted(self.exit_codes.items())}))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("0.0.0.0", 0))
        return s.getsockname()[1]


def _worker_env(base: Dict[str, str], rank: int, size: int, local_rank: int,
                local_size: int, controller: str, driver: str,
                secret_hex: str,
                jax_coordinator: str = "") -> Dict[str, str]:
    env = dict(base)
    env.update({
        "HOROVOD_RANK": str(rank),
        "HOROVOD_SIZE": str(size),
        "HOROVOD_LOCAL_RANK": str(local_rank),
        "HOROVOD_LOCAL_SIZE": str(local_size),
        "HOROVOD_CONTROLLER": controller,
        "HOROVOD_DRIVER": driver,
        "HOROVOD_SECRET": secret_hex,
    })
    if jax_coordinator:
        # hvd.init() joins the jax distributed runtime at this address
        # before its first backend query, so every process sees the
        # GLOBAL device set (horovod_tpu/common/basics.py).
        env["HOROVOD_JAX_COORDINATOR"] = jax_coordinator
    return env


def _local_tpu_chips() -> List[str]:
    """Device nodes of this host's TPU chips (what libtpu opens), found
    without importing jax — the launcher must never hold a chip. On a
    v5e host a chip is a PCI function of Google's vendor id (0x1ae0;
    device 0x0063, class 0xff0000) whose IOMMU group has a
    ``/dev/vfio/<group>`` node; earlier generations show as
    ``/dev/accel<n>``. A VFIO group alone says nothing — GPU and NIC
    passthrough make them too — and the vendor alone is also the cloud's
    virtual NIC (class 0x02) and disk (class 0x01)."""
    import glob

    chips = glob.glob("/dev/accel[0-9]*")
    for dev in glob.glob("/sys/bus/pci/devices/*"):
        try:
            with open(os.path.join(dev, "vendor")) as f:
                vendor = f.read().strip()
            with open(os.path.join(dev, "class")) as f:
                pci_class = f.read().strip()
            group = os.path.basename(os.readlink(
                os.path.join(dev, "iommu_group")))
        except OSError:
            continue
        node = os.path.join("/dev/vfio", group)
        if (vendor == "0x1ae0" and pci_class[:4] not in ("0x01", "0x02")
                and os.path.exists(node)):
            chips.append(node)
    return sorted(chips)


def _refuse_shared_chips(placements: List[tuple],
                         env: Dict[str, str]) -> None:
    """The SPMD lane is one process per host (docs/tpus.md): every local
    rank gets the same environment, so each would open every chip of
    the host, and a chip belongs to one process at a time. Nothing here
    assigns chips per local rank — refuse at launch instead of letting
    rank 1 fail or hang inside libtpu. Ranks that never open the chips
    (the torch and TensorFlow bindings over the native core, CPU JAX)
    say so with ``JAX_PLATFORMS=cpu``."""
    local = [p for p in placements
             if p[0] is None or p[0] in ("localhost", "127.0.0.1")]
    platforms = env.get("JAX_PLATFORMS", "")
    if len(local) < 2 or (platforms and "tpu" not in platforms.split(",")):
        return
    chips = _local_tpu_chips()
    if chips:
        raise LaunchError(
            f"{len(local)} local ranks on a host with {len(chips)} TPU "
            f"chip(s) ({', '.join(chips)}): the SPMD lane is one process "
            "per host — one process drives every chip (hvd.init() "
            "meshes jax.devices()), and a chip belongs to one process "
            "at a time. Launch one rank per host (-np 1, or -H "
            "host:1,...). Ranks that do not open the chips (torch or "
            "TensorFlow over the native core, JAX on the CPU) opt out "
            "by launching with JAX_PLATFORMS=cpu in the environment.")


def _parse_hosts(hosts: str) -> List[tuple]:
    """Parse ``host1:4,host2:4`` into [(host, slots), ...]
    (reference horovodrun -H syntax)."""
    out = []
    for part in hosts.split(","):
        part = part.strip()
        if not part:
            continue
        host, _, slots = part.partition(":")
        out.append((host, int(slots) if slots else 1))
    return out


def _spawn_local(cmd: Sequence[str], env: Dict[str, str]) -> subprocess.Popen:
    # New process group so one kill() reaps the whole rank's tree
    # (reference safe_shell_exec process-group discipline).
    return subprocess.Popen(list(cmd), env=env, start_new_session=True)


# Machine-local variables never forwarded to remote ranks; everything else
# in the job env goes over so all ranks of one job see one environment.
_SSH_ENV_DENY = ("SSH_", "DISPLAY", "HOSTNAME", "PWD", "OLDPWD", "SHLVL",
                 "TMPDIR", "XDG_", "DBUS_", "HOME", "LOGNAME", "USER", "_")


_SSH_READY_MARKER = b"__HVD_ECHO_OFF__"


def _spawn_ssh(host: str, cmd: Sequence[str],
               env: Dict[str, str]) -> subprocess.Popen:
    exports = " ".join(
        f"{k}={shlex.quote(v)}" for k, v in env.items()
        if not k.startswith(_SSH_ENV_DENY) and k != "HOROVOD_SECRET"
        and "\n" not in v)
    # The HMAC secret must never appear on a command line (argv is world-
    # readable via /proc on the remote host); ship it over stdin instead.
    # The -tt pty would echo that stdin line back into the launcher's
    # stdout (and thus scrollback/job logs), so the remote disables echo
    # and prints a marker; the launcher only writes the secret AFTER the
    # marker arrives (writing earlier would race the stty and be echoed
    # by the default line discipline).
    marker = _SSH_READY_MARKER.decode()
    remote = (f"stty -echo 2>/dev/null; printf '{marker}\\n'; "
              "IFS= read -r HOROVOD_SECRET && export HOROVOD_SECRET && "
              f"cd {shlex.quote(os.getcwd())} && env {exports} "
              + " ".join(shlex.quote(c) for c in cmd))
    # -tt forces a pty so killing the local ssh client HUPs the remote
    # process tree — the fail-fast kill works across hosts.
    proc = subprocess.Popen(["ssh", "-tt", "-o", "BatchMode=yes", host,
                             remote], start_new_session=True,
                            stdin=subprocess.PIPE, stdout=subprocess.PIPE)

    def feed_secret_then_pump():
        out = proc.stdout
        line = b""
        while True:  # wait for the echo-off marker (or early EOF)
            ch = out.read(1)
            if not ch:
                return  # ssh died before the marker; supervisor reaps it
            if ch == b"\n":
                if _SSH_READY_MARKER in line:
                    break
                sys.stdout.buffer.write(line + b"\n")
                sys.stdout.buffer.flush()
                line = b""
            else:
                line += ch
        try:
            proc.stdin.write((env.get("HOROVOD_SECRET", "") + "\n").encode())
            proc.stdin.flush()
        except (BrokenPipeError, OSError):
            return
        while True:  # stream the worker's output to the launcher's stdout
            chunk = out.read(4096)
            if not chunk:
                return
            sys.stdout.buffer.write(chunk)
            sys.stdout.buffer.flush()

    pump = threading.Thread(target=feed_secret_then_pump, daemon=True)
    pump.start()
    proc._hvd_pump_thread = pump  # joined by _drain_output at job end
    return proc


def _drain_output(procs: List[subprocess.Popen], timeout: float = 5.0) -> None:
    """Join ssh stdout pump threads so the tail of remote worker output is
    flushed to the launcher's stdout before launch_command returns."""
    deadline = time.monotonic() + timeout
    for p in procs:
        t = getattr(p, "_hvd_pump_thread", None)
        if t is not None:
            t.join(max(0.1, deadline - time.monotonic()))


def _kill_all(procs: List[subprocess.Popen]) -> None:
    for p in procs:
        if p.poll() is None:
            try:
                os.killpg(os.getpgid(p.pid), signal.SIGTERM)
            except (ProcessLookupError, PermissionError):
                pass
    deadline = time.monotonic() + 5
    for p in procs:
        try:
            p.wait(max(0.1, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            try:
                os.killpg(os.getpgid(p.pid), signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
    # Reap the SIGKILLed stragglers so callers (notably the --restarts
    # relaunch loop) never start a new attempt while an old local worker
    # still holds its device lock or checkpoint file. SIGKILL cannot be
    # blocked; the wait only stalls on uninterruptible (D-state) I/O, so
    # bound it and report rather than hang the launcher.
    reap_deadline = time.monotonic() + 10
    for p in procs:
        if p.poll() is None:
            try:
                p.wait(max(0.1, reap_deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                print(f"hvdrun: worker pid {p.pid} did not exit after "
                      "SIGKILL (uninterruptible I/O?); proceeding",
                      file=sys.stderr, flush=True)


def spawn_worker(cmd: Sequence[str],
                 env: Optional[Dict[str, str]] = None) -> subprocess.Popen:
    """Spawn ONE supervised worker process — the single-process lane of
    :func:`launch_job`'s placement discipline (its own session/process
    group, so one ``killpg`` reaps the worker's whole tree). The caller
    owns supervision and classification; the serving fleet
    (:mod:`horovod_tpu.serve.fleet`, ``transport="process"``) pairs
    this with :class:`~horovod_tpu.run.driver.WorkerExit` /
    :func:`~horovod_tpu.run.driver.classify_exit` so replica and
    training incidents speak one taxonomy."""
    return _spawn_local(cmd, dict(env if env is not None
                                  else os.environ))


def spawn_worker_ssh(host: str, cmd: Sequence[str],
                     env: Optional[Dict[str, str]] = None
                     ) -> subprocess.Popen:
    """Spawn ONE supervised worker on a REMOTE host over ssh — the
    multi-host lane of :func:`spawn_worker`, used by the serving
    fleet's ``transport="tcp"`` placement. Reuses the launcher's ssh
    discipline (:func:`_spawn_ssh`): ``-tt`` forces a pty so killing
    the returned LOCAL ssh client's process group
    (:func:`kill_worker` / :func:`terminate_worker`) HUPs the remote
    process tree — the fail-fast kill works across hosts — and the
    ``HOROVOD_SECRET`` entry of ``env`` ships over stdin after an
    echo-off marker, never on the remote argv (world-readable via
    /proc). Caveat the caller owns: the returned Popen is the ssh
    CLIENT, so its exit code is the remote command's only when the
    remote exits normally — a signal-killed remote (or a dead ssh
    session) reports 255/-signum, and the fleet classifies those from
    its own evidence instead (docs/serving.md "Multi-host fleet")."""
    return _spawn_ssh(host, list(cmd),
                      dict(env if env is not None else os.environ))


def kill_worker(proc: subprocess.Popen,
                timeout: float = 5.0) -> Optional[int]:
    """SIGKILL one worker's process group and reap it (bounded — a
    D-state wait must not hang the caller; see :func:`_kill_all`).
    Returns the observed exit code, or None when the process could not
    be reaped within ``timeout``."""
    if proc.poll() is None:
        try:
            os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        try:
            proc.wait(timeout)
        except subprocess.TimeoutExpired:
            return None
    return proc.returncode


def terminate_worker(proc: subprocess.Popen,
                     term_timeout: float = 2.0,
                     kill_timeout: float = 5.0) -> Optional[int]:
    """Graceful-teardown escalation for one worker: SIGTERM the process
    group, wait ``term_timeout``, SIGKILL stragglers, reap — the
    :func:`_kill_all` ladder, single-process edition (the fleet's
    ``close()`` uses it after the shutdown RPC so a wedged replica can
    never zombie). Returns the exit code, or None if unreapable."""
    if proc.poll() is None:
        try:
            os.killpg(os.getpgid(proc.pid), signal.SIGTERM)
        except (ProcessLookupError, PermissionError):
            pass
        try:
            proc.wait(term_timeout)
        except subprocess.TimeoutExpired:
            return kill_worker(proc, kill_timeout)
    return proc.returncode


def launch_command(cmd: Sequence[str], np: int,
                   hosts: Optional[str] = None,
                   env: Optional[Dict[str, str]] = None,
                   jax_distributed: bool = False) -> int:
    """Run ``cmd`` as an N-rank job; returns the job's exit code
    (back-compat wrapper over :func:`launch_job`)."""
    return launch_job(cmd, np, hosts=hosts, env=env,
                      jax_distributed=jax_distributed).code


def launch_job(cmd: Sequence[str], np: int,
               hosts: Optional[str] = None,
               env: Optional[Dict[str, str]] = None,
               jax_distributed: bool = False,
               watchdog=None) -> JobResult:
    """Run ``cmd`` as an N-rank job; returns a :class:`JobResult` with
    per-worker exit codes and the classified trigger failure.

    Fails fast: the first non-zero rank kills the rest (the reference
    relied on mpirun for exactly this) — but unlike the reference's
    collapsed mpirun code, the result records WHICH rank died and HOW
    (clean / usage / preempted / resized / stalled / crashed), so the
    elastic supervisor can decide relaunch-vs-fail per incident.

    ``watchdog`` (an :class:`~horovod_tpu.elastic.supervisor.
    HealthWatchdog` or anything with its ``check(ranks) -> {rank:
    age}``) rides this supervision poll: ranks it reports as
    heartbeat-stale are SIGKILLed here and their exits marked
    *stalled* — a silently-hung worker becomes an ordinary classified
    incident instead of an eternal wait.

    ``jax_distributed``: also stand up a jax coordination service address
    (HOROVOD_JAX_COORDINATOR) so each worker's ``hvd.init()`` joins one
    global jax device mesh — the SPMD lane spanning all workers' chips,
    the way mpirun+NCCL spanned all GPUs in the reference.
    """
    base_env = dict(env if env is not None else os.environ)
    secret_hex = make_secret_key().hex()

    placements: List[tuple] = []  # (host or None, local_rank, local_size)
    if hosts:
        parsed = _parse_hosts(hosts)
        total = sum(s for _, s in parsed)
        if total != np:
            raise LaunchError(f"-H slots ({total}) != -np ({np})")
        for host, slots in parsed:
            for lr in range(slots):
                placements.append((host, lr, slots))
    else:
        placements = [(None, r, np) for r in range(np)]
    _refuse_shared_chips(placements, base_env)

    first_host = placements[0][0]
    if first_host is None or first_host in ("localhost", "127.0.0.1"):
        controller_host = "127.0.0.1"
        controller_port = _free_port()  # rank 0 binds on this machine
    else:
        # Rank 0 binds on a remote host we cannot probe; pick from the
        # high ephemeral range and let its init report a bind conflict.
        controller_host = first_host
        controller_port = random.randint(20000, 59999)
    controller = f"{controller_host}:{controller_port}"
    jax_coordinator = ""
    if jax_distributed:
        jax_port = controller_port
        while jax_port == controller_port:  # two services, two ports
            jax_port = (_free_port() if controller_host == "127.0.0.1"
                        else random.randint(20000, 59999))
        jax_coordinator = f"{controller_host}:{jax_port}"

    procs: List[subprocess.Popen] = []
    try:
        for rank, (host, local_rank, local_size) in enumerate(placements):
            wenv = _worker_env(base_env, rank, np, local_rank, local_size,
                               controller, "", secret_hex, jax_coordinator)
            if host is None or host in ("localhost", "127.0.0.1"):
                procs.append(_spawn_local(cmd, wenv))
            else:
                procs.append(_spawn_ssh(host, cmd, wenv))
        # Supervise: poll until all exit or one fails.
        stalled: Dict[int, float] = {}
        while True:
            codes = [p.poll() for p in procs]
            if watchdog is not None:
                live = [r for r, c in enumerate(codes) if c is None]
                for rank, age in watchdog.check(live).items():
                    # A stale heartbeat means the worker is silently
                    # wedged — possibly mid-collective, where SIGTERM's
                    # graceful drain would hang too. SIGKILL converts
                    # the hang into a classifiable incident.
                    print(f"hvdrun: health watchdog: rank {rank} "
                          f"heartbeat stale for {age:.1f}s (timeout "
                          f"{watchdog.timeout:g}s) — killing the "
                          "stalled worker", file=sys.stderr, flush=True)
                    stalled[rank] = age
                    watchdog.kills[rank] = age
                    try:
                        os.killpg(os.getpgid(procs[rank].pid),
                                  signal.SIGKILL)
                    except (ProcessLookupError, PermissionError):
                        pass
                if stalled:
                    codes = [p.poll() for p in procs]
            bad_ranks = [r for r, c in enumerate(codes)
                         if c not in (None, 0)]
            if bad_ranks:
                # The lowest failing rank at this poll is the trigger;
                # its code (not the peers' kill-all SIGTERMs) classifies
                # the incident — a watchdog-killed rank wins the tie so
                # the incident is classed *stalled*, not by whatever
                # exit its SIGKILL raced. Record every code observed
                # BEFORE the kill so self-inflicted exits stay
                # distinguishable.
                first = min((r for r in bad_ranks if r in stalled),
                            default=bad_ranks[0])
                trigger = WorkerExit(first, codes[first],
                                     stalled=first in stalled)
                pre_kill = {r: c for r, c in enumerate(codes)
                            if c not in (None, 0)}
                _kill_all(procs)
                _drain_output(procs)
                return JobResult(
                    exit_codes={r: p.poll()
                                for r, p in enumerate(procs)},
                    trigger=trigger, stalled_ranks=dict(stalled),
                    pre_kill_codes=pre_kill)
            if all(c == 0 for c in codes):
                _drain_output(procs)
                return JobResult(
                    exit_codes=dict(enumerate(codes)), trigger=None)
            time.sleep(0.05)
    except KeyboardInterrupt:
        _kill_all(procs)
        raise
    finally:
        if any(p.poll() is None for p in procs):
            _kill_all(procs)


def run(fn, args: tuple = (), kwargs: Optional[dict] = None, np: int = 1,
        env: Optional[Dict[str, str]] = None,
        start_timeout: float = 120.0,
        run_timeout: float = 600.0) -> List[Any]:
    """Run ``fn(*args, **kwargs)`` on ``np`` local ranks; returns the list
    of per-rank return values, rank-ordered (reference horovod.spark.run
    semantics, spark/__init__.py:80-196)."""
    base_env = dict(env if env is not None else os.environ)
    _refuse_shared_chips([(None, r, np) for r in range(np)], base_env)
    key = make_secret_key()
    driver = Driver(np, key, fn=fn, args=args, kwargs=kwargs)
    secret_hex = key.hex()
    controller = f"127.0.0.1:{_free_port()}"
    # Publish EVERY candidate endpoint (loopback + per-NIC addresses);
    # each worker probes for the first one that answers an authenticated
    # Ping before registering — reference Spark interface discovery
    # (spark/__init__.py:33-39,123-140). Local-only today, but ssh-remote
    # workers get the multi-NIC story for free.
    from horovod_tpu.run.network import candidate_addresses

    driver_addr = ",".join(candidate_addresses(driver.port))

    procs: List[subprocess.Popen] = []
    try:
        for rank in range(np):
            wenv = _worker_env(base_env, rank, np, rank, np, controller,
                               driver_addr, secret_hex)
            procs.append(_spawn_local(
                [sys.executable, "-m", "horovod_tpu.run.task_exec"], wenv))
        if not driver.wait_registered(start_timeout):
            raise LaunchError(
                f"timed out after {start_timeout}s waiting for "
                f"{np} workers to register")

        def worker_died():
            return any(p.poll() not in (None, 0) for p in procs)

        results = driver.wait_results(run_timeout, should_abort=worker_died)
        failures = {r: res.payload for r, res in results.items()
                    if not res.success}
        if failures:
            first = min(failures)
            raise LaunchError(
                f"rank {first} failed:\n{failures[first]}", failures)
        if len(results) < np:
            dead = [r for r, p in enumerate(procs)
                    if p.poll() not in (None, 0)]
            if dead:
                raise LaunchError(
                    f"rank(s) {dead} exited without reporting "
                    f"(exit codes {[procs[r].poll() for r in dead]})")
            raise LaunchError(
                f"timed out after {run_timeout}s: only {len(results)}/{np} "
                "ranks reported")
        return [results[r].payload for r in range(np)]
    finally:
        _kill_all(procs)
        driver.close()


__all__ = ["run", "launch_command", "launch_job", "JobResult",
           "WorkerExit", "classify_exit", "LaunchError",
           "spawn_worker", "spawn_worker_ssh", "kill_worker",
           "terminate_worker",
           "EXIT_CLEAN", "EXIT_PREEMPTED", "EXIT_RESIZED", "EXIT_USAGE"]
