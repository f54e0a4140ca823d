"""Cross-process serving fleet (serve/worker.py + fleet transport=process).

Two lanes over the SAME fleet code paths:

* **stub lane (fast)** — real OS processes speaking the real framed
  protocol, but the worker is tests/serve_stub_worker.py (launched
  ``python -S``, ~30 ms start, no jax): covers the whole recovery
  matrix — genuine SIGKILL + reap + classification, torn-frame
  kill-mid-write, RPC deadline expiry, watchdog-caught stalls,
  close() escalation on a wedged worker, startup crashes — with the
  stub's context-hash "model" standing in for greedy decoding (next
  token depends on the full context, so redispatch continuation is
  bit-exact for the same reason it is on the real engine);
* **real-worker lane (slow)** — ``python -m horovod_tpu.serve.worker``
  end to end: greedy streams pinned BIT-IDENTICAL to ``lm_decode``
  across a real mid-run SIGKILL, a watchdog-classified stall, and a
  worker killed mid-write of a collect reply.
"""

import os
import signal
import sys
import time

import numpy as np
import pytest

from horovod_tpu.serve import (FleetConfig, ProcessReplica, ServeConfig,
                               ServeFleet)
from tests.serve_stub_worker import VOCAB, expected_stream, params_salt

HERE = os.path.dirname(os.path.abspath(__file__))
STUB = os.path.join(HERE, "serve_stub_worker.py")

#: The stub never runs an engine off these, but the fleet ships them
#: to every worker incarnation as the wire params artifact (the
#: digest-derived salt below is the stub's "weights") and reads Lmax
#: (admission geometry) off them.
STUB_PARAMS = {"pos": np.zeros((64, 4), np.float32)}
#: Salt every stub incarnation decodes with once the fleet's wire-init
#: push lands — expected_stream(p, n, SALT) matching IS the proof the
#: artifact arrived over the transport, digest-intact.
SALT = params_salt(STUB_PARAMS)


def _stub_cmd(extra_env=None, extra_args=(), per_rid_env=None):
    """worker_cmd hook launching the protocol stub with ``python -S``
    (no site-packages, no jax import — ~30 ms).
    ``per_rid_env`` applies to a replica's FIRST incarnation only —
    fault hooks must not re-fire on the relaunched worker."""

    def cmd(rid, sock_path, default):
        dcmd, denv = default
        hb_dir = dcmd[dcmd.index("--heartbeat-dir") + 1]
        argv = [sys.executable, "-S", STUB, "--socket", sock_path,
                "--rank", str(rid), "--heartbeat-dir", hb_dir,
                "--slots", "2"] + list(extra_args)
        env = dict(denv)
        env.update(extra_env or {})
        if f"r{rid}-1.sock" in sock_path:
            env.update((per_rid_env or {}).get(rid, {}))
        return argv, env

    return cmd


def _stub_fleet(worker_cmd=None, **fleet_kw):
    fleet_kw.setdefault("replicas", 2)
    fleet_kw.setdefault("transport", "process")
    fleet_kw.setdefault("backoff_base", 0.01)
    fleet_kw.setdefault("rpc_deadline", 10.0)
    return ServeFleet(STUB_PARAMS,
                      ServeConfig(page_size=8, num_pages=32,
                                  decode_slots=2, prefill_chunk=4),
                      FleetConfig(**fleet_kw),
                      worker_cmd=worker_cmd or _stub_cmd())


def _prompts(n, base=3):
    return [list(range(base + i, base + i + 4 + i % 3)) for i in range(n)]


def _assert_reaped(fl):
    for rep in fl.replicas:
        assert isinstance(rep, ProcessReplica)
        assert rep.proc.poll() is not None, (
            f"replica {rep.id} pid {rep.proc.pid} not reaped (zombie)")


def _run_until(fl, reqs, timeout=30.0):
    t0 = time.monotonic()
    while not fl.idle and time.monotonic() - t0 < timeout:
        fl.run(max_steps=fl.steps + 50)
        if not fl.idle:
            time.sleep(0.01)
    assert fl.idle, [r.state for r in reqs]


class TestStubFleet:
    def test_clean_run_streams_exact_and_close_reaps(self):
        fl = _stub_fleet()
        try:
            prompts = _prompts(5)
            reqs = [fl.submit(np.asarray(p, np.int32), 4 + i % 3)
                    for i, p in enumerate(prompts)]
            _run_until(fl, reqs)
            for p, r in zip(prompts, reqs):
                assert r.state == "finished"
                assert r.output == expected_stream(p, r.orig_max_new, SALT)
            f = fl.stats()["fleet"]
            assert f["transport"] == "process"
            assert f["rpc_ms"]["calls"] > 0
            assert f["rpc_ms"]["p50"] is not None
            assert f["transport_incidents"] == {}
        finally:
            fl.close()
        _assert_reaped(fl)
        fl.close()   # idempotent

    def test_real_sigkill_classified_and_redispatched_exact(self):
        fl = _stub_fleet(worker_cmd=_stub_cmd(
            extra_args=["--tick-s", "0.02"]))   # slow ticks: kill mid-run
        try:
            prompts = _prompts(6)
            reqs = [fl.submit(np.asarray(p, np.int32), 8)
                    for p in prompts]
            for _ in range(4):
                fl.step()
            victim = fl.replicas[1]
            pid = victim.proc.pid
            fl.arm_fault_plan("kill:replica=1,at=0s")
            _run_until(fl, reqs)
            # the fault was a GENUINE SIGKILL of a real OS process
            assert victim.proc.poll() == -signal.SIGKILL or \
                fl.incidents[0]["code"] == -signal.SIGKILL
            f = fl.stats()["fleet"]
            assert f["incidents_by_class"] == {"crashed": 1}
            assert f["incidents"][0]["code"] == -signal.SIGKILL
            assert f["redispatched"] >= 1
            for p, r in zip(prompts, reqs):
                assert r.state == "finished"
                # at-most-once + bit-exact continuation across the kill
                assert r.output == expected_stream(p, 8, SALT), (
                    pid, r.redispatches, r.output)
            assert any(r.redispatches for r in reqs)
        finally:
            fl.close()
        _assert_reaped(fl)

    def test_torn_frame_mid_write_routed_to_drain(self):
        fl = _stub_fleet(worker_cmd=_stub_cmd(
            extra_args=["--tick-s", "0.02"],
            per_rid_env={1: {"HVD_SERVE_WORKER_TORN_COLLECT_AFTER": "4"}}))
        try:
            prompts = _prompts(6)
            reqs = [fl.submit(np.asarray(p, np.int32), 8)
                    for p in prompts]
            _run_until(fl, reqs)
            f = fl.stats()["fleet"]
            # exactly one torn-frame incident, classified through the
            # real reaped exit code (the stub os._exit(1)s mid-write)
            assert f["transport_incidents"].get("FrameError") == 1, f
            assert f["incidents_by_class"] == {"crashed": 1}
            assert f["incidents"][0]["transport_error"] == "FrameError"
            for p, r in zip(prompts, reqs):
                assert r.state == "finished"
                assert r.output == expected_stream(p, 8, SALT)
        finally:
            fl.close()
        _assert_reaped(fl)

    def test_rpc_deadline_expiry_is_replica_death(self):
        """A worker that never comes up (startup sleep >> deadline)
        resolves as DeadlineExceeded -> death path -> budget -> failed
        fleet sheds, inside the deadline budget — never a hang."""
        fl = _stub_fleet(replicas=1, max_restarts=0, rpc_deadline=0.4,
                         spawn_timeout=0.4,
                         worker_cmd=_stub_cmd(
                             extra_args=["--startup-delay", "30"]))
        try:
            r = fl.submit(np.asarray([1, 2, 3], np.int32), 4)
            t0 = time.monotonic()
            while fl.alive and time.monotonic() - t0 < 10:
                fl.step()
                time.sleep(0.01)
            assert not fl.alive
            assert time.monotonic() - t0 < 10
            f = fl.stats()["fleet"]
            assert f["transport_incidents"].get("DeadlineExceeded") == 1
            assert r.state == "rejected" and \
                r.reject_reason == "overloaded"
        finally:
            fl.close()
        _assert_reaped(fl)

    def test_startup_crash_classified_before_first_heartbeat(self):
        """The troubleshooting-entry shape: a worker that dies on
        startup (before bind, before any heartbeat) is classified
        crashed via its real exit code and consumes restart budget."""
        fl = _stub_fleet(replicas=1, max_restarts=1,
                         worker_cmd=_stub_cmd(
                             extra_env={"HVD_SERVE_WORKER_FAIL_START":
                                        "3"}))
        try:
            r = fl.submit(np.asarray([1, 2, 3], np.int32), 4)
            t0 = time.monotonic()
            while fl.alive and time.monotonic() - t0 < 20:
                fl.step()
                time.sleep(0.01)
            f = fl.stats()["fleet"]
            # the initial spawn AND the budgeted relaunch both crash
            assert f["incidents_by_class"] == {"crashed": 2}, f
            assert all(i["code"] == 3 for i in f["incidents"])
            assert f["failed"] == 1
            assert f["restarts_used"] == 1
            assert r.state == "rejected"
            # no heartbeat was ever written for the dead incarnations
            assert not any(n.startswith("hb-") for n in
                           os.listdir(fl.heartbeat_dir))
        finally:
            fl.close()
        _assert_reaped(fl)

    def test_stall_watchdog_kills_and_relaunches(self):
        """A stalled WORKER PROCESS stops stepping and heartbeating
        while its RPC thread stays up: only the stale heartbeat — the
        real PR-9 HealthWatchdog — catches it, classified stalled."""
        fl = _stub_fleet(watchdog_timeout=0.6,
                         worker_cmd=_stub_cmd(
                             extra_args=["--tick-s", "0.01"]))
        try:
            prompts = _prompts(6)
            reqs = [fl.submit(np.asarray(p, np.int32), 12)
                    for p in prompts]
            for _ in range(3):
                fl.step()
            fl.arm_fault_plan("stall:replica=0,at=0s")
            _run_until(fl, reqs, timeout=30.0)
            f = fl.stats()["fleet"]
            assert f["incidents_by_class"] == {"stalled": 1}, f
            assert f["detect_s"] is not None and f["detect_s"] >= 0.6
            for p, r in zip(prompts, reqs):
                assert r.state == "finished"
                assert r.output == expected_stream(p, 12, SALT)
        finally:
            fl.close()
        _assert_reaped(fl)

    def test_close_reaps_a_wedged_worker(self):
        """The shutdown-hardening satellite: close() must reap a
        replica whose engine loop is genuinely wedged by a stall fault
        (graceful RPC first, SIGTERM -> SIGKILL escalation if needed),
        leave no zombies, and be idempotent."""
        fl = _stub_fleet(worker_cmd=_stub_cmd(
            extra_args=["--tick-s", "0.01"]))
        try:
            reqs = [fl.submit(np.asarray([1, 2, 3], np.int32), 50)]
            for _ in range(3):
                fl.step()
            fl.arm_fault_plan("stall:replica=0,at=0s")
            for _ in range(3):
                fl.step()
            time.sleep(0.1)   # let the wedge take hold
            assert reqs[0].state != "finished"
        finally:
            fl.close()
        _assert_reaped(fl)
        fl.close()   # idempotent
        with pytest.raises(RuntimeError, match="closed"):
            fl.step()

    def test_constructor_spawn_failure_reaps_partial_fleet(self):
        """A failed spawn mid-__init__ must not orphan the worker
        processes already running (close() is unreachable when the
        constructor raises)."""
        spawned = []
        base = _stub_cmd()

        def cmd(rid, sock_path, default):
            if rid == 1:
                raise OSError("no such worker binary")
            argv, env = base(rid, sock_path, default)
            spawned.append(sock_path)
            return argv, env

        with pytest.raises(OSError, match="no such worker binary"):
            _stub_fleet(worker_cmd=cmd)
        assert spawned   # replica 0 really was launched first
        # ...and its process did not outlive the failed constructor
        import subprocess

        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            # exec form: pgrep excludes itself (a shell wrapper would
            # self-match on the pattern in its own cmdline)
            ps = subprocess.run(["pgrep", "-f", "serve_stub_worker.py"],
                                capture_output=True, text=True)
            live = ps.stdout.split()
            if not live:
                break
            time.sleep(0.05)
        assert not live, live

    def test_slow_fault_rides_the_rpc(self):
        fl = _stub_fleet(worker_cmd=_stub_cmd(
            extra_args=["--tick-s", "0.01"]))
        try:
            fl.arm_fault_plan("slow:replica=0,at=0s,factor=3")
            reqs = [fl.submit(np.asarray([5, 6, 7], np.int32), 4)]
            _run_until(fl, reqs)
            assert reqs[0].output == expected_stream([5, 6, 7], 4, SALT)
            assert fl.stats()["fleet"]["incidents_by_class"] == {}
        finally:
            fl.close()
        _assert_reaped(fl)


NEW_PARAMS = {"pos": np.ones((64, 4), np.float32) * 3.0}
NEW_SALT = params_salt(NEW_PARAMS)


def _run_update_until_done(fl, reqs, timeout=30.0):
    t0 = time.monotonic()
    while (not fl.idle or fl.update_active) \
            and time.monotonic() - t0 < timeout:
        if not fl.step():
            time.sleep(0.005)
    assert fl.idle and not fl.update_active, (
        [r.state for r in reqs], fl.update_active)


class TestStubRollingUpdate:
    """The versioned rolling update over REAL worker OS processes (the
    protocol stub): drain → chunked wire push → digest verify →
    readmit, one replica at a time, with the transfer fault lanes.
    NEW_PARAMS differ from STUB_PARAMS, so the salt CHANGES across the
    version boundary — a stream that mixed versions mid-decode would
    match neither expected_stream(..., SALT) nor (..., NEW_SALT)."""

    def test_update_rolls_both_replicas_streams_never_mix(self):
        assert SALT != NEW_SALT
        fl = _stub_fleet(worker_cmd=_stub_cmd(
            extra_args=["--tick-s", "0.02"]))
        try:
            prompts = _prompts(6)
            reqs = [fl.submit(np.asarray(p, np.int32), 8)
                    for p in prompts]
            for _ in range(3):
                fl.step()
            assert fl.update_params(NEW_PARAMS) == 2
            with pytest.raises(RuntimeError, match="in progress"):
                fl.update_params(NEW_PARAMS)
            late = [fl.submit(np.asarray(p, np.int32), 6)
                    for p in _prompts(3, base=40)]
            _run_update_until_done(fl, reqs + late)
            f = fl.stats()["fleet"]
            assert f["params_version"] == 2
            assert f["incidents_by_class"] == {}, f
            per = f["per_replica"]
            assert all(r["version"] == 2 for r in per), per
            shas = {r["params_sha"] for r in per}
            assert len(shas) == 1 and None not in shas
            # 2 spawn wire-inits + 2 update pushes (tests run with
            # no bench-style metrics reset)
            assert f["params_push"]["pushes"] == 4
            assert f["params_push"]["retries"] == 0
            # EVERY stream is entirely one version's output — the pin:
            # a mixed stream would match neither reference.
            for p, r in zip(prompts + _prompts(3, base=40),
                            reqs + late):
                assert r.state == "finished"
                n = r.orig_max_new
                old = expected_stream(p, n, SALT)
                new = expected_stream(p, n, NEW_SALT)
                assert r.output in (old, new), (p, r.output)
            # ...and a request submitted AFTER the roll completed can
            # only decode under the new version.
            post = fl.submit(np.asarray([9, 9, 9], np.int32), 5)
            _run_update_until_done(fl, [post])
            assert post.output == expected_stream([9, 9, 9], 5,
                                                  NEW_SALT)
        finally:
            fl.close()
        _assert_reaped(fl)

    def test_transfer_tear_classified_retry_resumes(self):
        """kill-the-wire mid-push: the transfer: fault tears the FIRST
        push attempt; the fleet classifies it, backs off, reconnects,
        resumes from the worker's verified offset — exactly one
        transfer retry, NO replica death, digests verified."""
        fl = _stub_fleet(worker_cmd=_stub_cmd(
            extra_args=["--tick-s", "0.02"]),
            push_chunk_bytes=64)
        try:
            reqs = [fl.submit(np.asarray(p, np.int32), 6)
                    for p in _prompts(4)]
            fl.arm_fault_plan("transfer:replica=0,at=0s")
            fl.update_params(NEW_PARAMS)
            _run_update_until_done(fl, reqs)
            f = fl.stats()["fleet"]
            assert f["params_push"]["retries"] == 1, f["params_push"]
            assert f["transfer_incidents"] == {"ConnectionLost": 1}, f
            assert f["incidents_by_class"] == {}, f
            assert all(r["version"] == 2 for r in f["per_replica"])
            # the update was armed before the first tick, so the spawn
            # wire-inits already shipped the v2 artifact: 2 pushes
            assert f["params_push"]["pushes"] == 2
        finally:
            fl.close()
        _assert_reaped(fl)

    def test_corrupt_chunk_is_typed_checksum_retry(self):
        """A bit-flipped chunk must be REJECTED by the worker's
        per-chunk CRC (typed ChecksumError riding back as the remote
        error), retried, and the committed artifact digest-verified —
        a corrupted transfer can never become a silently wrong
        model."""
        fl = _stub_fleet(worker_cmd=_stub_cmd(
            extra_args=["--tick-s", "0.02"]),
            push_chunk_bytes=64)
        try:
            reqs = [fl.submit(np.asarray(p, np.int32), 6)
                    for p in _prompts(4)]
            fl.arm_fault_plan("corrupt:replica=1,at=0s")
            fl.update_params(NEW_PARAMS)
            _run_update_until_done(fl, reqs)
            f = fl.stats()["fleet"]
            assert f["params_push"]["retries"] == 1, f["params_push"]
            assert f["transfer_incidents"] == {"ChecksumError": 1}, f
            assert f["incidents_by_class"] == {}, f
            shas = {r["params_sha"] for r in f["per_replica"]}
            assert len(shas) == 1 and None not in shas
        finally:
            fl.close()
        _assert_reaped(fl)

    def test_kill_mid_push_consumes_budget_then_relaunch_updates(self):
        """A worker that DIES mid-push (not just a torn wire) exhausts
        the push's retry budget fast (the process is observably dead),
        takes the classified replica-death path, and its relaunch
        wire-inits straight onto the NEW version."""
        fl = _stub_fleet(worker_cmd=_stub_cmd(
            extra_args=["--tick-s", "0.02"],
            per_rid_env={0: {"HVD_STUB_DIE_ON_PUSH_CHUNK": "2"}}),
            push_chunk_bytes=64, max_restarts=2)
        try:
            reqs = [fl.submit(np.asarray(p, np.int32), 6)
                    for p in _prompts(4)]
            # let the doomed worker finish its spawn-time wire init
            # (the die-hook counts push_chunk calls: the init push is
            # chunk 1, the update push dies)... the init itself is
            # chunk 1+2 with 64B chunks, so it dies DURING INIT —
            # which is fine: a startup-window death is the same lane.
            _run_update_until_done(fl, reqs, timeout=30.0)
            f = fl.stats()["fleet"]
            # the death was classified and budgeted, and the final
            # state is a fully-updated fleet (the relaunch wire-inits
            # from the current artifact)
            assert f["incidents_by_class"].get("crashed", 0) >= 1, f
            assert f["restarts_used"] >= 1
            assert all(r["version"] is not None
                       for r in f["per_replica"] if r["state"] == "healthy")
            for r in reqs:
                assert r.state == "finished"
        finally:
            fl.close()
        _assert_reaped(fl)


# ---------------------------------------------------------------- real


def _lm_setup():
    import jax

    from horovod_tpu.models import parallel_lm as plm

    V, LMAX = 64, 64
    params = plm.init_lm_params(jax.random.PRNGKey(0), V, LMAX, 2, 2,
                                8, 32)
    cfg = ServeConfig(page_size=8, num_pages=32, decode_slots=2,
                      prefill_chunk=4)
    return params, cfg, V


def _lm_ref(params, prompt, steps):
    import jax.numpy as jnp

    from horovod_tpu.models import parallel_lm as plm

    return list(np.asarray(
        plm.lm_decode(params, jnp.asarray(prompt)[None], steps))[0])


def _lm_prompts(v, n):
    import jax

    return [np.asarray(jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(100), i), (8 + i,), 0, v),
        np.int32) for i in range(n)]


def _warm(fl):
    for _ in range(len(fl.replicas)):
        fl.submit(np.asarray([1, 2], np.int32), 2)
    fl.run()
    fl.reset_metrics()


class TestRealWorkerE2E:
    """python -m horovod_tpu.serve.worker end to end (slow: each worker
    spawn pays the jax import + first-step compile)."""

    def test_kill_redispatch_bit_exact_vs_lm_decode(self):
        params, cfg, V = _lm_setup()
        fl = ServeFleet(params, cfg,
                        FleetConfig(replicas=2, transport="process",
                                    backoff_base=0.01),
                        worker_env={"JAX_PLATFORMS": "cpu"})
        try:
            _warm(fl)
            prompts = _lm_prompts(V, 6)
            reqs = [fl.submit(p, 10) for p in prompts]
            for _ in range(4):
                fl.step()
            fl.arm_fault_plan("kill:replica=1,at=0s")
            fl.run()
            f = fl.stats()["fleet"]
            assert f["incidents_by_class"] == {"crashed": 1}
            assert f["incidents"][0]["code"] == -signal.SIGKILL
            assert f["transport"] == "process"
            assert f["rpc_ms"]["p50"] is not None
            for p, r in zip(prompts, reqs):
                assert r.state == "finished"
                assert r.output == _lm_ref(params, p, 10)
        finally:
            fl.close()
        _assert_reaped(fl)

    def test_stall_watchdog_classified_relaunch(self):
        params, cfg, V = _lm_setup()
        # The watchdog timeout must exceed the worst single worker
        # tick INCLUDING a compile (docs/serving.md "Process fleet").
        fl = ServeFleet(params, cfg,
                        FleetConfig(replicas=2, transport="process",
                                    backoff_base=0.01,
                                    watchdog_timeout=8.0),
                        worker_env={"JAX_PLATFORMS": "cpu"})
        try:
            _warm(fl)
            prompts = _lm_prompts(V, 4)
            reqs = [fl.submit(p, 16) for p in prompts]
            for _ in range(3):
                fl.step()
            fl.arm_fault_plan("stall:replica=0,at=0s")
            fl.run()
            f = fl.stats()["fleet"]
            assert f["incidents_by_class"] == {"stalled": 1}, f
            assert f["detect_s"] >= 8.0
            for p, r in zip(prompts, reqs):
                assert r.state == "finished"
                assert r.output == _lm_ref(params, p, 16)
        finally:
            fl.close()
        _assert_reaped(fl)

    def test_tcp_partition_host_down_bit_exact_vs_lm_decode(self):
        """Round-14 acceptance, real-worker edition: a 2-replica fleet
        on loopback TCP, the whole host network-partitioned mid-run —
        ONE classified host_down incident, both workers reaped and
        relaunched, and every greedy stream still bit-identical to
        lm_decode (the redispatch pin is transport-agnostic)."""
        params, cfg, V = _lm_setup()
        fl = ServeFleet(params, cfg,
                        FleetConfig(replicas=2, transport="tcp",
                                    backoff_base=0.01, max_restarts=4,
                                    rpc_deadline=60.0),
                        worker_env={"JAX_PLATFORMS": "cpu"})
        try:
            _warm(fl)
            prompts = _lm_prompts(V, 6)
            reqs = [fl.submit(p, 10) for p in prompts]
            for _ in range(4):
                fl.step()
            fl.arm_fault_plan("partition:host=0,at=0s,secs=2")
            fl.run()
            f = fl.stats()["fleet"]
            assert f["transport"] == "tcp"
            assert f["incidents_by_class"] == {"host_down": 1}, f
            assert f["host_incidents"] == 1
            assert f["failed"] == 0
            assert f["rpc_ms"]["p50"] is not None
            for p, r in zip(prompts, reqs):
                assert r.state == "finished"
                assert r.output == _lm_ref(params, p, 10)
        finally:
            fl.close()
        _assert_reaped(fl)

    def test_tcp_rolling_update_torn_push_bit_exact_vs_lm_decode(self):
        """Round-15 acceptance, real-worker edition: a 2-replica
        loopback-TCP fleet (params/config over the wire only) rolls to
        a new weights version mid-traffic with the FIRST push attempt
        torn; the push classifies exactly one transfer retry and
        resumes, both replicas digest-verify the new version, every
        request finishes, and — the update re-pushing the same params
        content — every greedy stream is bit-identical to lm_decode
        within its pinned version."""
        params, cfg, V = _lm_setup()
        fl = ServeFleet(params, cfg,
                        FleetConfig(replicas=2, transport="tcp",
                                    backoff_base=0.01, max_restarts=4,
                                    push_chunk_bytes=16384),
                        worker_env={"JAX_PLATFORMS": "cpu"})
        try:
            _warm(fl)
            prompts = _lm_prompts(V, 6)
            reqs = [fl.submit(p, 10) for p in prompts]
            for _ in range(3):
                fl.step()
            fl.arm_fault_plan("transfer:replica=0,at=0s")
            fl.update_params(params)
            t0 = time.monotonic()
            while (not fl.idle or fl.update_active) \
                    and time.monotonic() - t0 < 120:
                if not fl.step():
                    time.sleep(0.005)
            f = fl.stats()["fleet"]
            assert f["params_push"]["retries"] == 1, f["params_push"]
            assert sum(f["transfer_incidents"].values()) == 1, f
            assert f["incidents_by_class"] == {}, f
            assert f["params_version"] == 2
            per = f["per_replica"]
            assert all(r["version"] == 2 for r in per), per
            assert len({r["params_sha"] for r in per}) == 1
            for p, r in zip(prompts, reqs):
                assert r.state == "finished"
                assert r.output == _lm_ref(params, p, 10)
        finally:
            fl.close()
        _assert_reaped(fl)

    def test_kill_mid_write_torn_frame_redispatch_exact(self):
        """The satellite's e2e pin: a worker killed MID-WRITE of a
        collect reply leaves half a frame on the wire; the codec
        detects it (typed FrameError, no hang, no mis-parse), the
        fleet drains + redispatches, and every greedy stream is still
        bit-identical to lm_decode."""
        params, cfg, V = _lm_setup()

        def cmd(rid, sock_path, default):
            argv, env = default
            if rid == 1 and "r1-1" in sock_path:   # first incarnation
                env = dict(env,
                           HVD_SERVE_WORKER_TORN_COLLECT_AFTER="12")
            return argv, env

        fl = ServeFleet(params, cfg,
                        FleetConfig(replicas=2, transport="process",
                                    backoff_base=0.01),
                        worker_env={"JAX_PLATFORMS": "cpu"},
                        worker_cmd=cmd)
        try:
            _warm(fl)
            prompts = _lm_prompts(V, 6)
            reqs = [fl.submit(p, 20) for p in prompts]
            fl.run()
            f = fl.stats()["fleet"]
            assert f["transport_incidents"].get("FrameError") == 1, f
            assert f["incidents_by_class"] == {"crashed": 1}
            assert f["incidents"][0]["transport_error"] == "FrameError"
            for p, r in zip(prompts, reqs):
                assert r.state == "finished"
                assert r.output == _lm_ref(params, p, 20)
        finally:
            fl.close()
        _assert_reaped(fl)
