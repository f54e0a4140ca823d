"""horovod_tpu.elastic: snapshots, manifests, signals, fault injection,
exit-code classification, supervised restart — and the end-to-end
acceptance path: a fault-injected `hvdrun --elastic` job that loses a
rank mid-run and still finishes bit-exactly equal to the fault-free run.
"""

import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import elastic
from horovod_tpu.common.exceptions import HorovodTimeoutError
from horovod_tpu.elastic.faults import FaultPlanError
from horovod_tpu.flax.checkpoint import CheckpointManager
from horovod_tpu.run import (JobResult, WorkerExit, classify_exit,
                             launch_job, _kill_all, _spawn_local)
from horovod_tpu.run.driver import EXIT_PREEMPTED, EXIT_USAGE

REPO = Path(__file__).resolve().parent.parent


def _clean_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("JAX_PLATFORMS", None)
    env.pop("HOROVOD_FAULT_PLAN", None)
    return env


# ----------------------------------------------------------------- fixtures


def _toy_step():
    def step_fn(state, batch):
        g = batch["x"] * state["w"]
        return ({"w": state["w"] - 0.1 * g, "step": state["step"] + 1},
                {"loss": jnp.sum(state["w"])})

    def batch_for(step):
        return {"x": jnp.float32(step % 5 + 1)}

    init = {"w": jnp.float32(2.0), "step": jnp.int32(0)}
    return step_fn, batch_for, init


# ---------------------------------------------------------------- FaultPlan


class TestFaultPlan:
    def test_parse_grammar(self):
        plan = elastic.parse_fault_plan(
            "kill:rank=1,step=7; stall:rank=2,step=12,secs=0.5;"
            "preempt:rank=0,step=3,attempt=1;exit:rank=0,step=2,code=9")
        kinds = [a.kind for a in plan]
        assert kinds == ["kill", "stall", "preempt", "exit"]
        assert plan[0].rank == 1 and plan[0].step == 7
        assert plan[0].attempt == 0  # default: first launch only
        assert plan[1].secs == 0.5
        assert plan[2].attempt == 1
        assert plan[3].code == 9
        assert elastic.parse_fault_plan("") == []
        assert elastic.parse_fault_plan("  ;  ") == []

    @pytest.mark.parametrize("bad", [
        "explode:rank=0,step=1",          # unknown kind
        "kill:rank=0",                    # missing step
        "kill:step=3",                    # missing rank
        "kill:rank=zero,step=1",          # non-numeric
        "kill:rank=0,step=1,flavor=spicy",  # unknown key
        "kill rank=0 step=1",             # no colon
    ])
    def test_parse_rejects(self, bad):
        with pytest.raises(FaultPlanError):
            elastic.parse_fault_plan(bad)

    def test_injector_filters_rank_and_attempt(self):
        plan = elastic.parse_fault_plan(
            "exit:rank=0,step=5;exit:rank=1,step=5;"
            "exit:rank=0,step=9,attempt=1")
        inj = elastic.FaultInjector(plan, rank=0, attempt=1)
        assert [a.step for a in inj.pending] == [9]
        inj0 = elastic.FaultInjector(plan, rank=1, attempt=0)
        assert [a.step for a in inj0.pending] == [5]

    def test_exit_action_fires_once_at_boundary(self):
        plan = elastic.parse_fault_plan("exit:rank=0,step=5,code=7")
        inj = elastic.FaultInjector(plan, rank=0, attempt=0)
        inj.maybe_inject(4)  # below the step: nothing
        with pytest.raises(SystemExit) as ei:
            inj.maybe_inject(6)  # first boundary past step=5
        assert ei.value.code == 7
        inj.maybe_inject(7)  # consumed: does not re-fire

    def test_stall_action_sleeps_bounded(self):
        plan = elastic.parse_fault_plan("stall:rank=0,step=1,secs=0.2")
        inj = elastic.FaultInjector(plan, rank=0, attempt=0)
        t0 = time.monotonic()
        inj.maybe_inject(1)
        assert 0.15 <= time.monotonic() - t0 < 5.0

    def test_preempt_action_triggers_handler_not_signal(self):
        handler = elastic.PreemptionHandler(install=False)
        inj = elastic.FaultInjector(
            elastic.parse_fault_plan("preempt:rank=0,step=2"),
            rank=0, attempt=0)
        inj.maybe_inject(2, preemption=handler)
        assert handler.triggered

    def test_env_construction(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_FAULT_PLAN", "kill:rank=3,step=11")
        monkeypatch.setenv("HOROVOD_RANK", "3")
        monkeypatch.setenv("HOROVOD_ELASTIC_RESTART", "0")
        inj = elastic.FaultInjector.from_env()
        assert [a.kind for a in inj.pending] == ["kill"]

    def test_parse_resize(self):
        plan = elastic.parse_fault_plan(
            "resize:rank=0,step=7,n=1;resize:rank=0,step=3,n=4,attempt=1")
        assert [a.n for a in plan] == [1, 4]
        assert elastic.resize_requests(plan) == {0: 1, 1: 4}
        assert "n=1" in str(plan[0])

    @pytest.mark.parametrize("bad", [
        "resize:rank=0,step=7",          # n missing
        "resize:rank=0,step=7,n=0",      # empty world
        "kill:rank=0,step=7,n=2",        # n on a non-resize kind
        # two resizes on one attempt: relaunch size would be ambiguous
        "resize:rank=0,step=3,n=1;resize:rank=1,step=9,n=2",
    ])
    def test_parse_resize_rejects(self, bad):
        with pytest.raises(FaultPlanError):
            elastic.parse_fault_plan(bad)

    def test_resize_action_triggers_handler_with_resized_code(self):
        handler = elastic.PreemptionHandler(install=False)
        inj = elastic.FaultInjector(
            elastic.parse_fault_plan("resize:rank=0,step=2,n=1"),
            rank=0, attempt=0)
        inj.maybe_inject(2, preemption=handler)
        assert handler.triggered
        assert handler.exit_code == elastic.EXIT_RESIZED

    def test_resize_action_without_handler_exits_resized(self):
        inj = elastic.FaultInjector(
            elastic.parse_fault_plan("resize:rank=0,step=2,n=1"),
            rank=0, attempt=0)
        with pytest.raises(SystemExit) as ei:
            inj.maybe_inject(2)
        assert ei.value.code == elastic.EXIT_RESIZED


# ----------------------------------------------------------------- manifest


class TestManifest:
    def test_round_trip_and_latest(self, tmp_path):
        d = str(tmp_path)
        m1 = elastic.ResumeManifest(step=3, world_size=2, rank=0,
                                    cursor={"epoch": 0, "offset": 12},
                                    rng_key=[1, 2])
        m2 = elastic.ResumeManifest(step=6, world_size=2, rank=0,
                                    cursor={"epoch": 0, "offset": 24})
        elastic.write_manifest(d, m1)
        elastic.write_manifest(d, m2)
        assert elastic.manifest_steps(d) == [3, 6]
        latest = elastic.latest_manifest(d)
        assert latest.step == 6 and latest.cursor["offset"] == 24
        old = elastic.read_manifest(d, 3)
        assert old.rng_key == [1, 2]
        assert np.array_equal(old.rng(), np.asarray([1, 2], np.uint32))

    def test_latest_survives_torn_pointer(self, tmp_path):
        d = str(tmp_path)
        elastic.write_manifest(d, elastic.ResumeManifest(step=4))
        (tmp_path / "MANIFEST").write_text("manifest-999.json\n")  # torn
        assert elastic.latest_manifest(d).step == 4

    def test_empty_directory(self, tmp_path):
        assert elastic.latest_manifest(str(tmp_path)) is None
        assert elastic.manifest_steps(str(tmp_path)) == []


# --------------------------------------------------------------- snapshotter


class TestSnapshotter:
    def test_cadence_and_double_buffer(self, tmp_path):
        snap = elastic.Snapshotter(every=2)
        w = jnp.arange(4.0)
        taken = [s for s in range(1, 7)
                 if snap.maybe(s, {"w": w * s, "s": jnp.int32(s)})]
        assert taken == [2, 4, 6]
        # Async double buffer: the newest snapshot is pending; `latest`
        # commits it and returns the step-6 state.
        step, state = snap.latest
        assert step == 6
        np.testing.assert_array_equal(np.asarray(state["w"]),
                                      np.asarray(w * 6))
        assert snap.stats["snapshots"] == 3
        assert snap.stats["last_ms"] is not None

    def test_window_alignment_enforced(self):
        snap = elastic.Snapshotter(every=10)
        snap.check_alignment(5)  # 10 % 5 == 0: fine
        with pytest.raises(ValueError, match="window"):
            snap.check_alignment(3)

    def test_spill_cadence_and_restore(self, tmp_path):
        with CheckpointManager(str(tmp_path), backend="numpy") as mngr:
            snap = elastic.Snapshotter(mngr, every=1, spill_every=2)
            template = {"w": jnp.zeros(3)}
            for s in range(1, 5):
                snap.maybe(s, {"w": jnp.arange(3.0) + s},
                           cursor={"offset": s})
            # Snapshots 1-4; every 2nd spills: steps 2 and 4 on disk.
            assert mngr.all_steps() == [2, 4]
            state, manifest = snap.restore(template)
            assert manifest.step == 4 and manifest.cursor["offset"] == 4
            np.testing.assert_array_equal(np.asarray(state["w"]),
                                          np.arange(3.0) + 4)

    def test_flush_is_synchronous_final_snapshot(self, tmp_path):
        with CheckpointManager(str(tmp_path), backend="numpy") as mngr:
            snap = elastic.Snapshotter(mngr, every=100, spill_every=100)
            snap.flush(7, {"w": jnp.float32(3.0)}, cursor=7,
                       rng_key=np.asarray([5, 6], np.uint32))
            assert mngr.all_steps() == [7]
            m = elastic.latest_manifest(str(tmp_path))
            assert m.step == 7 and m.rng_key == [5, 6]

    def test_restore_walks_past_missing_checkpoint(self, tmp_path):
        with CheckpointManager(str(tmp_path), backend="numpy") as mngr:
            snap = elastic.Snapshotter(mngr, every=1, spill_every=1)
            snap.take(3, {"w": jnp.float32(1.0)}, sync=True)
            # A manifest whose checkpoint never committed (crash between
            # the spill phases) must not wedge the resume.
            elastic.write_manifest(str(tmp_path),
                                   elastic.ResumeManifest(step=9))
            state, manifest = snap.restore({"w": jnp.float32(0.0)})
            assert manifest.step == 3
            assert float(np.asarray(state["w"])) == 1.0

    def test_ram_only_without_manager(self):
        snap = elastic.Snapshotter(every=1)
        snap.take(1, {"w": jnp.float32(1.0)})
        assert snap.restore({"w": jnp.float32(0.0)}) is None
        assert snap.latest[0] == 1


# ------------------------------------------------------------------ signals


class TestPreemptionHandler:
    def test_real_sigterm_sets_flag_only(self):
        with elastic.PreemptionHandler() as handler:
            assert not handler.check()
            os.kill(os.getpid(), signal.SIGTERM)
            deadline = time.monotonic() + 5
            while not handler.triggered and time.monotonic() < deadline:
                time.sleep(0.01)
            assert handler.triggered and handler.signum == signal.SIGTERM
        # Context exit restored the previous disposition.
        assert signal.getsignal(signal.SIGTERM) != handler._on_signal

    def test_finalize_drains_snapshots_and_exits_preempted(self, tmp_path):
        with CheckpointManager(str(tmp_path), backend="numpy") as mngr:
            snap = elastic.Snapshotter(mngr, every=100)
            handler = elastic.PreemptionHandler(install=False)
            handler.trigger()
            codes = []
            handler.finalize(snap, 5, {"w": jnp.float32(2.0)},
                             _exit=codes.append, cursor={"offset": 20})
            assert codes == [EXIT_PREEMPTED]
            assert mngr.all_steps() == [5]
            assert elastic.latest_manifest(str(tmp_path)).step == 5


# ----------------------------------------------------- exit classification


class TestExitClassification:
    @pytest.mark.parametrize("code,cat", [
        (0, "clean"),
        (2, "usage"),
        (EXIT_PREEMPTED, "preempted"),
        (-signal.SIGTERM, "preempted"),
        (elastic.EXIT_RESIZED, "resized"),
        (1, "crashed"),
        (3, "crashed"),
        (-signal.SIGKILL, "crashed"),
        (-signal.SIGSEGV, "crashed"),
    ])
    def test_classify(self, code, cat):
        assert classify_exit(code) == cat
        assert WorkerExit(0, code).category == cat

    def test_watchdog_kill_classifies_stalled(self):
        """The raw code is the watchdog's SIGKILL; the stalled mark —
        set only by the launcher when ITS watchdog did the killing —
        overrides the would-be 'crashed' classification."""
        assert WorkerExit(1, -signal.SIGKILL, stalled=True).category \
            == "stalled"
        assert WorkerExit(1, -signal.SIGKILL).category == "crashed"

    def test_launch_job_reports_per_rank_codes(self):
        """The satellite contract: worker exit codes propagate
        distinctly instead of collapsing into the kill-all."""
        script = ("import os, sys, time\n"
                  "if os.environ['HOROVOD_RANK'] == '1':\n"
                  f"    sys.exit({EXIT_PREEMPTED})\n"
                  "time.sleep(30)\n")
        result = launch_job([sys.executable, "-c", script], np=2,
                            env=_clean_env())
        assert result.trigger.rank == 1
        assert result.code == EXIT_PREEMPTED
        assert result.category == "preempted"
        # Rank 0 was healthy; its code is the supervisor's SIGTERM, and
        # the per-rank map keeps both distinguishable.
        assert result.exit_codes[1] == EXIT_PREEMPTED
        assert result.exit_codes[0] != EXIT_PREEMPTED
        assert "rank 1" in result.describe()

    def test_launch_job_clean(self):
        result = launch_job([sys.executable, "-c", "pass"], np=2,
                            env=_clean_env())
        assert result.trigger is None and result.category == "clean"
        assert result.exit_codes == {0: 0, 1: 0}

    def test_kill_all_reaps_process_group(self):
        """The kill-all path itself (satellite): TERM -> KILL -> reap,
        bounded."""
        env = _clean_env()
        procs = [_spawn_local(
            [sys.executable, "-c", "import time; time.sleep(60)"], env)
            for _ in range(2)]
        assert all(p.poll() is None for p in procs)
        t0 = time.monotonic()
        _kill_all(procs)
        assert time.monotonic() - t0 < 30
        assert all(p.poll() is not None for p in procs)


# ------------------------------------------------------------ native timeout


class TestNativeTimeout:
    class _StalledLib:
        def hvdtpu_poll(self, handle):
            return 0

        def hvdtpu_rank(self):
            return 3

    class _DoneLib:
        def hvdtpu_poll(self, handle):
            return 1

        def hvdtpu_wait(self, handle):
            return 0

        def hvdtpu_rank(self):
            return 0

    def _core(self, lib, default_timeout=0.0):
        from horovod_tpu.native import NativeCore

        core = NativeCore.__new__(NativeCore)
        core.lib = lib
        core._live = {}
        core._names = {7: "grad.allreduce.bucket0"}
        core._default_timeout = default_timeout
        return core

    def test_stalled_wait_raises_typed_error_with_rank_and_tensor(self):
        core = self._core(self._StalledLib())
        t0 = time.monotonic()
        with pytest.raises(HorovodTimeoutError) as ei:
            core.wait(7, timeout=0.2)
        assert time.monotonic() - t0 < 5  # bounded, never a silent hang
        assert ei.value.rank == 3
        assert ei.value.tensor_name == "grad.allreduce.bucket0"
        assert "grad.allreduce.bucket0" in str(ei.value)
        assert "rank 3" in str(ei.value)

    def test_env_default_timeout_applies(self):
        core = self._core(self._StalledLib(), default_timeout=0.1)
        with pytest.raises(HorovodTimeoutError):
            core.wait(7)  # no explicit timeout: the env default bounds it

    def test_completed_wait_unaffected_by_timeout(self):
        core = self._core(self._DoneLib())
        core.wait(7, timeout=5.0)  # polls true immediately; no error


# --------------------------------------------------------------- supervisor


def _result(codes, trigger=None, pre_kill=None):
    return JobResult(exit_codes=codes, trigger=trigger,
                     pre_kill_codes=pre_kill if pre_kill is not None
                     else ({trigger.rank: trigger.code}
                           if trigger is not None else {}))


class TestSupervisor:
    def _fake_launch(self, outcomes, seen_envs, seen_np=None):
        outcomes = list(outcomes)

        def launch(cmd, np, hosts=None, env=None, jax_distributed=False,
                   **kw):
            seen_envs.append(dict(env or {}))
            if seen_np is not None:
                seen_np.append(np)
            return outcomes.pop(0)

        return launch

    def test_crash_relaunches_then_clean(self):
        envs = []
        rc = elastic.supervise(
            ["prog"], np=2, max_restarts=1,
            _launch=self._fake_launch([
                _result({0: -9, 1: -15}, WorkerExit(0, -9)),
                _result({0: 0, 1: 0}),
            ], envs))
        assert rc == 0 and len(envs) == 2
        assert envs[0]["HOROVOD_ELASTIC_RESTART"] == "0"
        assert envs[1]["HOROVOD_ELASTIC_RESTART"] == "1"
        assert all(e["HOROVOD_ELASTIC"] == "1" for e in envs)

    def test_crash_budget_exhausted_returns_code(self):
        envs = []
        rc = elastic.supervise(
            ["prog"], np=2, max_restarts=1,
            _launch=self._fake_launch([
                _result({0: -9}, WorkerExit(0, -9)),
                _result({0: 1}, WorkerExit(0, 1)),
            ], envs))
        assert rc == 1 and len(envs) == 2

    def test_usage_error_never_relaunches(self):
        envs = []
        rc = elastic.supervise(
            ["prog"], np=2, max_restarts=5,
            _launch=self._fake_launch(
                [_result({0: 2}, WorkerExit(0, 2))], envs))
        assert rc == EXIT_USAGE and len(envs) == 1

    def test_preemptions_relaunch_for_free(self):
        envs = []
        rc = elastic.supervise(
            ["prog"], np=2, max_restarts=0,
            _launch=self._fake_launch([
                _result({0: EXIT_PREEMPTED}, WorkerExit(0, EXIT_PREEMPTED)),
                _result({0: -15}, WorkerExit(0, -15)),
                _result({0: 0}),
            ], envs))
        assert rc == 0 and len(envs) == 3

    def test_count_preemptions_restores_strict_budget(self):
        envs = []
        rc = elastic.supervise(
            ["prog"], np=2, max_restarts=1, count_preemptions=True,
            _launch=self._fake_launch([
                _result({0: EXIT_PREEMPTED}, WorkerExit(0, EXIT_PREEMPTED)),
                _result({0: EXIT_PREEMPTED}, WorkerExit(0, EXIT_PREEMPTED)),
            ], envs))
        assert rc == EXIT_PREEMPTED and len(envs) == 2

    # ------------------------------------------------ resize/shrink/grow

    def test_resize_exit_relaunches_at_plan_size_for_free(self):
        """EXIT_RESIZED on attempt A relaunches at the resize clause's
        n — read supervisor-side from the SAME fault plan — without
        consuming the restart budget."""
        envs, nps = [], []
        rc = elastic.supervise(
            ["prog"], np=2, max_restarts=0, min_np=1,
            env={"HOROVOD_FAULT_PLAN": "resize:rank=0,step=7,n=1"},
            _launch=self._fake_launch([
                _result({0: elastic.EXIT_RESIZED, 1: -15},
                        WorkerExit(0, elastic.EXIT_RESIZED)),
                _result({0: 0}),
            ], envs, nps))
        assert rc == 0
        assert nps == [2, 1]
        assert envs[1]["HOROVOD_ELASTIC_RESTART"] == "1"

    def test_resize_out_of_bounds_fails_fast(self):
        with pytest.raises(ValueError, match="bounds"):
            elastic.supervise(
                ["prog"], np=2, max_restarts=0, min_np=1, max_np=2,
                env={"HOROVOD_FAULT_PLAN": "resize:rank=0,step=7,n=5"},
                _launch=self._fake_launch([], []))

    def test_preemption_shrinks_to_survivors(self):
        """With --min-np below the current world, a preemption
        relaunches at np-1 (the reclaimed worker is not coming back)
        instead of burning attempts retrying full size; crashes keep
        the size (the host is still there)."""
        envs, nps = [], []
        rc = elastic.supervise(
            ["prog"], np=3, max_restarts=1, min_np=1,
            _launch=self._fake_launch([
                _result({1: EXIT_PREEMPTED}, WorkerExit(1, EXIT_PREEMPTED)),
                _result({0: -9}, WorkerExit(0, -9)),
                _result({0: 0}),
            ], envs, nps))
        assert rc == 0
        assert nps == [3, 2, 2]   # shrink on preempt, hold on crash

    def test_whole_host_loss_shrinks_to_true_survivors(self):
        """Review regression: two ranks reclaimed in the same poll
        (whole-host loss) both appear in pre_kill_codes; the shrink
        removes BOTH, not just the trigger."""
        envs, nps = [], []
        rc = elastic.supervise(
            ["prog"], np=4, max_restarts=0, min_np=1,
            _launch=self._fake_launch([
                _result({2: EXIT_PREEMPTED, 3: EXIT_PREEMPTED},
                        WorkerExit(2, EXIT_PREEMPTED),
                        pre_kill={2: EXIT_PREEMPTED, 3: EXIT_PREEMPTED}),
                _result({0: 0}),
            ], envs, nps))
        assert rc == 0 and nps == [4, 2]

    def test_capacity_never_overrides_explicit_resize(self):
        """Review regression: a validated resize: request is the
        operator's word — the slots-file probe must not second-guess
        it on the resize relaunch (it resumes authority afterwards)."""
        envs, nps = [], []
        rc = elastic.supervise(
            ["prog"], np=2, max_restarts=0, min_np=1, max_np=4,
            capacity_fn=lambda: 4,
            env={"HOROVOD_FAULT_PLAN": "resize:rank=0,step=7,n=1"},
            _launch=self._fake_launch([
                _result({0: elastic.EXIT_RESIZED},
                        WorkerExit(0, elastic.EXIT_RESIZED)),
                _result({0: 0}),
            ], envs, nps))
        assert rc == 0 and nps == [2, 1]

    def test_metrics_exit_code_is_none_on_exception(self, tmp_path):
        """Review regression: an exception unwinding supervise (^C, a
        launcher crash) must not stamp the metrics record as a clean
        exit-0 run."""
        import json as _json

        path = tmp_path / "metrics.tsv"

        def boom(cmd, np, **kw):
            raise RuntimeError("launcher died")

        with pytest.raises(RuntimeError):
            elastic.supervise(["prog"], np=2, metrics_path=str(path),
                              _launch=boom)
        rec = _json.loads(path.read_text().split("\t", 2)[2])
        assert rec["elastic"]["exit_code"] is None

    def test_fixed_world_without_min_np_never_shrinks(self):
        envs, nps = [], []
        rc = elastic.supervise(
            ["prog"], np=2, max_restarts=0,
            _launch=self._fake_launch([
                _result({0: EXIT_PREEMPTED}, WorkerExit(0, EXIT_PREEMPTED)),
                _result({0: 0}),
            ], envs, nps))
        assert rc == 0 and nps == [2, 2]

    def test_capacity_fn_grows_back_when_capacity_returns(self):
        """The capacity probe is the fleet's truth: each relaunch
        clamps to min(available, max_np), so a shrunken world grows
        back on a later restart."""
        envs, nps = [], []
        capacity = iter([1, 4])
        rc = elastic.supervise(
            ["prog"], np=2, max_restarts=0, min_np=1, max_np=4,
            capacity_fn=lambda: next(capacity),
            _launch=self._fake_launch([
                _result({0: EXIT_PREEMPTED}, WorkerExit(0, EXIT_PREEMPTED)),
                _result({0: EXIT_PREEMPTED}, WorkerExit(0, EXIT_PREEMPTED)),
                _result({0: 0}),
            ], envs, nps))
        assert rc == 0
        assert nps == [2, 1, 4]

    def test_slots_file_capacity_reads_and_degrades(self, tmp_path):
        path = tmp_path / "slots"
        fn = elastic.slots_file_capacity(str(path))
        assert fn() is None          # missing: capacity unknown
        path.write_text("3\n")
        assert fn() == 3
        path.write_text("soon\n")
        assert fn() is None          # malformed: keep current size

    def test_stalled_consumes_budget_like_crash(self):
        envs = []
        rc = elastic.supervise(
            ["prog"], np=2, max_restarts=0,
            _launch=self._fake_launch([
                _result({1: -9}, WorkerExit(1, -9, stalled=True)),
            ], envs))
        assert rc == -9 and len(envs) == 1

    def test_world_bounds_validated(self):
        with pytest.raises(ValueError, match="min_np"):
            elastic.supervise(["prog"], np=2, min_np=3,
                              _launch=self._fake_launch([], []))

    def test_recovery_metrics_json_line(self, tmp_path):
        """The satellite contract: one PERF_RUNS.tsv-format line with
        restarts-by-class, the world trajectory and timings."""
        import json as _json

        path = tmp_path / "metrics.tsv"
        envs = []
        rc = elastic.supervise(
            ["prog"], np=2, max_restarts=1, min_np=1,
            metrics_path=str(path),
            env={"HOROVOD_FAULT_PLAN": "resize:rank=0,step=7,n=1"},
            _launch=self._fake_launch([
                _result({0: elastic.EXIT_RESIZED},
                        WorkerExit(0, elastic.EXIT_RESIZED)),
                _result({0: 0}),
            ], envs))
        assert rc == 0
        stamp, lane, payload = \
            path.read_text().strip().split("\t", 2)
        assert lane == "elastic_supervise"
        rec = _json.loads(payload)
        assert rec["value"] == 1 and rec["unit"] == "relaunches"
        e = rec["elastic"]
        assert e["restarts_by_class"] == {"resized": 1}
        assert e["world"] == [2, 1] and e["final_np"] == 1

    def test_heartbeat_dir_namespaced_per_supervisor(self, tmp_path):
        """Regression (round-12 satellite): HOROVOD_HEARTBEAT_DIR is
        exported to workers, so two supervisors sharing one base dir on
        one host used to watch EACH OTHER's hb-<rank> files — a foreign
        rank's touches keep a stalled local rank 'alive' forever. Each
        supervise() must export a unique per-instance subdirectory."""
        base = str(tmp_path / "hb")
        exported = []
        for _ in range(2):
            envs = []
            rc = elastic.supervise(
                ["prog"], np=1, watchdog_timeout=30.0,
                heartbeat_dir=base,
                _launch=self._fake_launch([_result({0: 0})], envs))
            assert rc == 0
            exported.append(envs[0]["HOROVOD_HEARTBEAT_DIR"])
        assert exported[0] != exported[1]
        for d in exported:
            assert os.path.dirname(d) == base
            # ...and each call removed ITS dir on exit: looping over
            # supervise() must not accumulate orphan dirs in the base.
            assert not os.path.exists(d)
        assert os.listdir(base) == []

    def test_disabled_watchdog_drops_inherited_heartbeat_dir(self):
        """With the watchdog off, an INHERITED heartbeat dir (e.g. from
        an outer supervisor) must not be forwarded: this job's workers
        would otherwise touch the outer watchdog's files and mask its
        stall detection."""
        envs = []
        rc = elastic.supervise(
            ["prog"], np=1, watchdog_timeout=0.0,
            env={"HOROVOD_HEARTBEAT_DIR": "/tmp/outer-supervisor-hb"},
            _launch=self._fake_launch([_result({0: 0})], envs))
        assert rc == 0
        assert "HOROVOD_HEARTBEAT_DIR" not in envs[0]

    def test_namespaced_heartbeat_dir_helper_unique(self, tmp_path):
        from horovod_tpu.elastic.signals import namespaced_heartbeat_dir

        a = namespaced_heartbeat_dir(str(tmp_path))
        b = namespaced_heartbeat_dir(str(tmp_path))
        assert a != b and os.path.isdir(a) and os.path.isdir(b)
        assert os.path.dirname(a) == str(tmp_path)
        # no base: a fresh private tempdir, still unique
        c = namespaced_heartbeat_dir(None)
        d = namespaced_heartbeat_dir(None)
        assert c != d and os.path.isdir(c) and os.path.isdir(d)


# ------------------------------------------------------------ resize remap


class TestResizeRemap:
    def _src(self, rank, size, n=512, batch=4):
        return elastic.ShardedBatchSource(
            {"x": np.arange(float(n), dtype=np.float32)},
            batch_size=batch, rank=rank, size=size, seed=0)

    def test_global_stream_is_contiguous_prefix(self):
        """The coverage contract: the union over ranks of one step's
        positions is a contiguous watermark block, so the global stream
        is world-size-independent."""
        for size in (1, 2, 4):
            srcs = [self._src(r, size) for r in range(size)]
            for step in (0, 3, 7):
                union = np.sort(np.concatenate(
                    [s.global_positions(step) for s in srcs]))
                start = srcs[0].consumed_samples(step)
                np.testing.assert_array_equal(
                    union, np.arange(start, start + 4 * size))

    def test_shrink_remap_always_exact(self):
        src2, src1 = self._src(0, 2), self._src(0, 1)
        for step in range(1, 12):
            new = src1.resume_step(src2.cursor(step))
            assert src1.consumed_samples(new) \
                == src2.consumed_samples(step)

    def test_grow_remap_exact_on_even_boundaries(self):
        src2, src4 = self._src(0, 2), self._src(0, 4)
        assert src4.resume_step(src2.cursor(8)) == 4
        with pytest.raises(ValueError, match="global batch"):
            src4.resume_step(src2.cursor(7))   # 56 samples, G_new=16

    def test_remap_accepts_manifest_and_crosses_epochs(self):
        src2 = self._src(0, 2, n=64)   # 8 steps/epoch at size 2
        src1 = self._src(0, 1, n=64)   # 16 steps/epoch at size 1
        m = elastic.ResumeManifest(step=11, world_size=2,
                                   cursor=src2.cursor(11))
        assert src1.resume_step(m) == 22
        # An exact epoch boundary rolls into the next epoch.
        assert src1.resume_step(src2.cursor(8)) == 16

    def test_remap_rejects_cursorless_manifest(self):
        src1 = self._src(0, 1)
        with pytest.raises(ValueError, match="cursor"):
            src1.resume_step(elastic.ResumeManifest(step=5, cursor=5))

    def test_same_world_remap_is_identity(self):
        src = self._src(1, 2)
        assert src.resume_step(src.cursor(9)) == 9

    def test_cross_epoch_remap_rejects_mismatched_epoch_geometry(self):
        """Review regression: past epoch 0, whole epochs must line up
        between the worlds — n=10/B=1 consumes 12 samples/epoch at
        size 3 but 10 at size 2, so a divisible within-epoch offset
        must still be rejected (silent replay otherwise)."""
        src3 = self._src(0, 3, n=10, batch=1)
        src2 = self._src(0, 2, n=10, batch=1)
        cur = src3.cursor(src3.steps_per_epoch + 2)   # epoch 1, off 2
        assert cur["epoch"] == 1
        with pytest.raises(ValueError, match="epoch"):
            src2.resume_step(cur)
        # Epoch 0 of the same geometry pair still remaps fine.
        assert src2.resume_step(src3.cursor(2)) == 3   # g=6 -> step 3

    def test_snapshotter_world_defaults_from_env(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_RANK", "3")
        monkeypatch.setenv("HOROVOD_SIZE", "4")
        snap = elastic.Snapshotter(every=1)
        assert snap.rank == 3 and snap.world_size == 4


# ---------------------------------------------------------- reshard resume


class TestReshardResume:
    """The Snapshotter/loop world-size-mismatch behavior: what used to
    be an implicit dead end is now the reshard path — a mismatched
    manifest resumes through the cursor remap + on_resize hook, and
    only a remap-less resume is rejected (with the reshard pointer)."""

    def _train(self, tmp_path, src, steps, world_size, **kw):
        def step_fn(state, batch):
            g = jnp.mean(batch["x"])
            return ({"w": state["w"] - 0.01 * g,
                     "step": state["step"] + 1},
                    {"loss": state["w"]})

        init = {"w": jnp.float32(2.0), "step": jnp.int32(0)}
        m = CheckpointManager(str(tmp_path), backend="numpy")
        return elastic.run_elastic(
            step_fn, init, src.batch_at if src is not None
            else (lambda s: {"x": jnp.float32(s)}),
            steps, manager=m, snapshot_every=3,
            world_size=world_size, rank=0, **kw)

    def test_reshard_resume_remaps_and_rescales(self, tmp_path):
        arrays = {"x": np.arange(64, dtype=np.float32)}
        src2 = elastic.ShardedBatchSource(arrays, batch_size=4, rank=0,
                                          size=2, seed=0)
        self._train(tmp_path, src2, 6, 2)     # manifest: step 6 @ world 2
        m = elastic.latest_manifest(str(tmp_path))
        assert m.step == 6 and m.world_size == 2
        assert m.cursor["size"] == 2          # source cursor recorded

        src1 = elastic.ShardedBatchSource(arrays, batch_size=4, rank=0,
                                          size=1, seed=0)
        resizes = []

        def on_resize(old, new, state):
            resizes.append((old, new))
            return dict(state, w=state["w"] * 2)

        state, _, resumed = self._train(tmp_path, src1, 24, 1,
                                        on_resize=on_resize)
        # 6 steps @ world 2 = 48 samples = 12 steps @ world 1; the
        # default remap came from the batch source itself.
        assert resumed == 12
        assert resizes == [(2, 1)]
        # The resized run wrote a world-1 manifest at its end.
        assert elastic.latest_manifest(str(tmp_path)).world_size == 1

    def test_mismatch_without_remap_is_rejected_with_pointer(
            self, tmp_path):
        arrays = {"x": np.arange(64, dtype=np.float32)}
        src2 = elastic.ShardedBatchSource(arrays, batch_size=4, rank=0,
                                          size=2, seed=0)
        self._train(tmp_path, src2, 6, 2)
        with pytest.raises(ValueError, match="reshard"):
            self._train(tmp_path, None, 24, 1)

    def test_resume_manager_is_the_restore_authority(self, tmp_path):
        """A rank with no history of its own (a grown world's new rank)
        restores from the authority directory while spilling to its
        own."""
        step_fn, batch_for, init = _toy_step()
        auth = CheckpointManager(str(tmp_path / "rank0"), backend="numpy")
        elastic.run_elastic(step_fn, init, batch_for, 6, manager=auth,
                            snapshot_every=3, world_size=1, rank=0)
        own = CheckpointManager(str(tmp_path / "rank2"), backend="numpy")
        s, _, resumed = elastic.run_elastic(
            step_fn, init, batch_for, 12, manager=own, snapshot_every=3,
            world_size=1, rank=2,
            resume_manager=CheckpointManager(str(tmp_path / "rank0"),
                                             backend="numpy"))
        assert resumed == 6
        # ... and its own spills landed in its own directory.
        assert elastic.latest_manifest(str(tmp_path / "rank2")).step == 12

    def test_heartbeat_touched_at_boundaries(self, tmp_path,
                                             monkeypatch):
        monkeypatch.setenv("HOROVOD_HEARTBEAT_DIR", str(tmp_path / "hb"))
        step_fn, batch_for, init = _toy_step()
        elastic.run_elastic(step_fn, init, batch_for, 4,
                            snapshot_every=2)
        hb = tmp_path / "hb" / "hb-0"
        assert hb.exists()
        assert hb.read_text().split()[1] == "4"   # last boundary stamped


# --------------------------------------------------------------- watchdog


class TestHealthWatchdog:
    def test_stale_detection_and_throttle(self, tmp_path):
        from horovod_tpu.elastic.signals import Heartbeat

        hb = Heartbeat(str(tmp_path), rank=0)
        hb.touch(3)
        os.utime(hb.path, (time.time() - 10, time.time() - 10))
        wd = elastic.HealthWatchdog(str(tmp_path), timeout=2.0,
                                    interval=0.0)
        stale = wd.check([0, 1])
        assert set(stale) == {0} and stale[0] > 2.0   # rank 1: no file
        wd.kills[0] = stale[0]
        assert wd.check([0, 1]) == {}                 # already killed
        wd.reset()
        assert set(wd.check([0])) == {0}              # re-armed

    def test_fresh_heartbeat_not_stale(self, tmp_path):
        from horovod_tpu.elastic.signals import Heartbeat

        Heartbeat(str(tmp_path), rank=0).touch(1)
        wd = elastic.HealthWatchdog(str(tmp_path), timeout=30.0,
                                    interval=0.0)
        assert wd.check([0]) == {}

    def test_launch_job_kills_stalled_worker(self, tmp_path):
        """The integration contract: a worker that beats once then goes
        silent is killed by the watchdog riding the supervision poll,
        and the incident is classified *stalled* (with the observed
        heartbeat age as time-to-detect evidence)."""
        hb_dir = tmp_path / "hb"
        hb_dir.mkdir()
        script = (
            "import os, time\n"
            "rank = os.environ['HOROVOD_RANK']\n"
            "if rank == '0':\n"
            "    open(os.path.join(os.environ['HOROVOD_HEARTBEAT_DIR'],"
            " 'hb-0'), 'w').write('0')\n"
            "time.sleep(60)\n")
        env = _clean_env()
        env["HOROVOD_HEARTBEAT_DIR"] = str(hb_dir)
        wd = elastic.HealthWatchdog(str(hb_dir), timeout=1.0,
                                    interval=0.1)
        t0 = time.monotonic()
        result = launch_job([sys.executable, "-c", script], np=2,
                            env=env, watchdog=wd)
        assert time.monotonic() - t0 < 30
        assert result.trigger.rank == 0 and result.trigger.stalled
        assert result.category == "stalled"
        assert result.stalled_ranks[0] > 1.0


# ------------------------------------------------------------- elastic loop


class TestRunElastic:
    def test_resume_is_bit_exact_plain(self, tmp_path):
        step_fn, batch_for, init = _toy_step()
        m_full = CheckpointManager(str(tmp_path / "full"), backend="numpy")
        s_full, met_full, r0 = elastic.run_elastic(
            step_fn, init, batch_for, 12, manager=m_full,
            snapshot_every=3)
        assert r0 == 0
        # Interrupted run: 6 steps, then a fresh invocation to 12 —
        # exactly what a relaunch does.
        m = CheckpointManager(str(tmp_path / "ckpt"), backend="numpy")
        _, met_a, _ = elastic.run_elastic(
            step_fn, init, batch_for, 6, manager=m, snapshot_every=3)
        s_b, met_b, resumed = elastic.run_elastic(
            step_fn, init, batch_for, 12, manager=m, snapshot_every=3)
        assert resumed == 6
        assert float(np.asarray(s_b["w"])) == float(np.asarray(s_full["w"]))
        traj_full = {s: float(m_["loss"]) for s, m_ in met_full}
        traj_ab = {s: float(m_["loss"]) for s, m_ in met_a + met_b}
        assert traj_ab == traj_full  # identical loss trajectory

    def test_resume_is_bit_exact_windowed(self, tmp_path):
        step_fn, batch_for, init = _toy_step()
        m_full = CheckpointManager(str(tmp_path / "full"), backend="numpy")
        s_full, met_full, _ = elastic.run_elastic(
            step_fn, init, batch_for, 12, manager=m_full,
            snapshot_every=3, steps_per_dispatch=3)
        m = CheckpointManager(str(tmp_path / "ckpt"), backend="numpy")
        elastic.run_elastic(step_fn, init, batch_for, 6, manager=m,
                            snapshot_every=3, steps_per_dispatch=3)
        s_b, met_b, resumed = elastic.run_elastic(
            step_fn, init, batch_for, 12, manager=m,
            snapshot_every=3, steps_per_dispatch=3)
        assert resumed == 6
        assert float(np.asarray(s_b["w"])) == float(np.asarray(s_full["w"]))
        # Window metric means replay identically too.
        full = {s: float(m_["loss"]) for s, m_ in met_full}
        replay = {s: float(m_["loss"]) for s, m_ in met_b}
        for s, v in replay.items():
            assert full[s] == v

    def test_finished_run_reinvocation_is_noop_resume(self, tmp_path):
        step_fn, batch_for, init = _toy_step()
        m = CheckpointManager(str(tmp_path), backend="numpy")
        s1, _, _ = elastic.run_elastic(step_fn, init, batch_for, 6,
                                       manager=m, snapshot_every=3)
        s2, met2, resumed = elastic.run_elastic(
            step_fn, init, batch_for, 6, manager=m, snapshot_every=3)
        assert resumed == 6 and met2 == []
        assert float(np.asarray(s2["w"])) == float(np.asarray(s1["w"]))

    def test_preemption_at_boundary_saves_and_exits_75(self, tmp_path):
        step_fn, batch_for, init = _toy_step()
        m = CheckpointManager(str(tmp_path), backend="numpy")
        handler = elastic.PreemptionHandler(install=False)
        inj = elastic.FaultInjector(
            elastic.parse_fault_plan("preempt:rank=0,step=4"),
            rank=0, attempt=0)
        with pytest.raises(SystemExit) as ei:
            elastic.run_elastic(step_fn, init, batch_for, 12, manager=m,
                                snapshot_every=2, injector=inj,
                                preemption=handler)
        assert ei.value.code == EXIT_PREEMPTED
        manifest = elastic.latest_manifest(str(tmp_path))
        assert manifest.step == 4  # drained + snapshotted at the boundary
        # And the relaunch resumes exactly there, to the same final state.
        s_resumed, _, resumed = elastic.run_elastic(
            step_fn, init, batch_for, 12, manager=m, snapshot_every=2)
        m_full = CheckpointManager(str(tmp_path / "full"), backend="numpy")
        s_full, _, _ = elastic.run_elastic(step_fn, init, batch_for, 12,
                                           manager=m_full, snapshot_every=2)
        assert resumed == 4
        assert float(np.asarray(s_resumed["w"])) == \
            float(np.asarray(s_full["w"]))

    def test_sharded_batch_source_cursor(self):
        root = np.random.RandomState(0)
        src = elastic.ShardedBatchSource(
            {"x": root.normal(size=(40, 2)).astype(np.float32)},
            batch_size=4, rank=1, size=2, seed=3)
        assert src.steps_per_epoch == 5
        cur = src.cursor(7)
        assert cur == {"epoch": 1, "offset": 8, "rank": 1, "size": 2}
        # Deterministic in the step — the whole resume argument.
        np.testing.assert_array_equal(src.batch_at(7)["x"],
                                      src.batch_at(7)["x"])
        # Disjoint from the other rank's shard at the same step.
        other = elastic.ShardedBatchSource(
            {"x": src.arrays["x"]}, batch_size=4, rank=0, size=2, seed=3)
        assert not np.array_equal(src.batch_at(0)["x"],
                                  other.batch_at(0)["x"])

    def test_prebuilt_snapshotter_resumes_too(self, tmp_path):
        """The composable path — run_elastic(snapshotter=Snapshotter(
        manager=...)) with no manager kwarg — must resume and final-
        flush exactly like the manager kwarg path (review finding: the
        gates used to check the kwarg only)."""
        step_fn, batch_for, init = _toy_step()
        mngr = CheckpointManager(str(tmp_path), backend="numpy")
        elastic.run_elastic(
            step_fn, init, batch_for, 6,
            snapshotter=elastic.Snapshotter(mngr, every=3))
        assert elastic.latest_manifest(str(tmp_path)).step == 6
        s2, _, resumed = elastic.run_elastic(
            step_fn, init, batch_for, 12,
            snapshotter=elastic.Snapshotter(mngr, every=3))
        assert resumed == 6
        m_full = CheckpointManager(str(tmp_path / "full"),
                                   backend="numpy")
        s_full, _, _ = elastic.run_elastic(
            step_fn, init, batch_for, 12, manager=m_full,
            snapshot_every=3)
        assert float(np.asarray(s2["w"])) == float(np.asarray(s_full["w"]))

    def test_flush_with_state_requires_step(self):
        snap = elastic.Snapshotter(every=1)
        with pytest.raises(ValueError, match="step"):
            snap.flush(state={"w": jnp.float32(1.0)})

    def test_misaligned_cadence_rejected(self, tmp_path):
        step_fn, batch_for, init = _toy_step()
        with pytest.raises(ValueError, match="window"):
            elastic.run_elastic(
                step_fn, init, batch_for, 12,
                manager=CheckpointManager(str(tmp_path), backend="numpy"),
                snapshot_every=4, steps_per_dispatch=3)


# ------------------------------------------------------------ flax binding


class TestElasticSnapshotCallback:
    def _loop_pieces(self):
        import horovod_tpu.flax as hvd_flax

        def step_fn(state, batch):
            return ({"w": state["w"] - 0.1 * batch["x"],
                     "step": state["step"] + 1},
                    {"loss": jnp.sum(state["w"])})

        def data_fn(epoch):
            for i in range(4):
                yield {"x": jnp.float32(i + 1)}

        init = {"w": jnp.float32(1.0), "step": jnp.int32(0)}
        return hvd_flax, step_fn, data_fn, init

    def test_cadence_snapshots_and_final_flush(self, tmp_path,
                                               monkeypatch):
        monkeypatch.setenv("HOROVOD_HEARTBEAT_DIR",
                           str(tmp_path / "hb"))
        hvd_flax, step_fn, data_fn, init = self._loop_pieces()
        with CheckpointManager(str(tmp_path), backend="numpy") as mngr:
            snap = elastic.Snapshotter(mngr, every=4, spill_every=1)
            loop = hvd_flax.TrainLoop(
                init, step_fn, data_fn,
                callbacks=[hvd_flax.ElasticSnapshotCallback(snap)])
            loop.fit(epochs=2)  # 8 steps: cadence spill at 4, flush at 8
            assert mngr.all_steps() == [4, 8]
            # The keras-lane face feeds the watchdog too: the per-rank
            # heartbeat was touched at every batch boundary.
            assert (tmp_path / "hb" / "hb-0").exists()
            restored, manifest = snap.restore(init)
            assert manifest.step == 8
            np.testing.assert_array_equal(np.asarray(restored["w"]),
                                          np.asarray(loop.state["w"]))

    def test_preemption_mid_fit_saves_and_exits(self, tmp_path):
        hvd_flax, step_fn, data_fn, init = self._loop_pieces()
        with CheckpointManager(str(tmp_path), backend="numpy") as mngr:
            snap = elastic.Snapshotter(mngr, every=100)
            handler = elastic.PreemptionHandler(install=False)

            class TriggerAtStep3(hvd_flax.Callback):
                def on_batch_end(self, batch, logs=None):
                    if int(self.loop.state["step"]) == 3:
                        handler.trigger()

            loop = hvd_flax.TrainLoop(
                init, step_fn, data_fn,
                callbacks=[TriggerAtStep3(),
                           hvd_flax.ElasticSnapshotCallback(
                               snap, preemption=handler)])
            with pytest.raises(SystemExit) as ei:
                loop.fit(epochs=2)
            assert ei.value.code == EXIT_PREEMPTED
            assert elastic.latest_manifest(str(tmp_path)).step == 3


# ------------------------------------------------------------------- e2e


def _last_wins(path: Path) -> dict:
    out = {}
    for line in path.read_text().splitlines():
        step, value = line.split()
        out[int(step)] = value
    return out


def _run_elastic_job(tmp_path, tag, steps, every, k, fault=None,
                     expect_rc=0, env_extra=None):
    out = tmp_path / f"{tag}-out"
    ckpt = tmp_path / f"{tag}-ckpt"
    cmd = [sys.executable, "-m", "horovod_tpu.run", "-np", "2",
           "--elastic", "--max-restarts", "1"]
    if fault:
        cmd += ["--fault-plan", fault]
    cmd += [sys.executable, str(REPO / "tests" / "elastic_worker.py"),
            str(out), str(ckpt), str(steps), str(every), str(k)]
    env = _clean_env()
    env.update(env_extra or {})
    proc = subprocess.run(cmd, env=env, cwd=str(REPO),
                          timeout=600, capture_output=True, text=True)
    assert proc.returncode == expect_rc, (proc.stdout[-2000:],
                                          proc.stderr[-2000:])
    return out, proc


def _run_resize_job(tmp_path, tag, total_samples, np_, fault,
                    min_np=1, max_np=None, every=4, k=1):
    out = tmp_path / f"{tag}-out"
    ckpt = tmp_path / f"{tag}-ckpt"
    cmd = [sys.executable, "-m", "horovod_tpu.run", "-np", str(np_),
           "--elastic", "--max-restarts", "1", "--min-np", str(min_np)]
    if max_np is not None:
        cmd += ["--max-np", str(max_np)]
    cmd += ["--fault-plan", fault,
            sys.executable,
            str(REPO / "tests" / "elastic_resize_worker.py"),
            str(out), str(ckpt), str(total_samples), str(every), str(k)]
    proc = subprocess.run(cmd, env=_clean_env(), cwd=str(REPO),
                          timeout=600, capture_output=True, text=True)
    assert proc.returncode == 0, (proc.stdout[-2000:],
                                  proc.stderr[-2000:])
    return out, proc


def _check_sample_coverage(samples_path: Path, total_samples: int,
                           n=512, batch=4, seed=0):
    """Replay rank 0's lineage and assert the no-drop/no-duplicate
    contract: at each attempt, entries at or past the attempt's resume
    watermark belong to a discarded lineage; what remains must cover
    the global permutation prefix exactly once."""
    attempts = {}
    for line in samples_path.read_text().splitlines():
        parts = line.split()
        if parts[0] != "S":
            continue
        a, size, step, watermark = map(int, parts[1:5])
        ids = [int(x) for x in parts[5:]]
        attempts.setdefault(a, []).append((watermark, size, ids))
    assert attempts, "no sample log lines"
    consumed = {}   # dataset id -> watermark of the consuming step
    for a in sorted(attempts):
        w0 = min(w for w, _, _ in attempts[a])
        for id_, w in list(consumed.items()):
            if w >= w0:
                del consumed[id_]   # discarded lineage
        for w, size, ids in sorted(attempts[a]):
            assert len(ids) == batch * size
            for id_ in ids:
                assert id_ not in consumed, \
                    f"sample {id_} consumed twice (at {consumed[id_]} " \
                    f"and {w})"
                consumed[id_] = w
    final = attempts[max(attempts)]
    final_w = max(w + len(ids) for w, _, ids in final)
    assert final_w == total_samples
    assert len(consumed) == total_samples
    # The consumed ids ARE the world-independent global stream: the
    # seeded epoch permutation's prefix (single epoch by construction).
    from horovod_tpu.data.sharding import shard_indices

    assert total_samples <= n
    stream = shard_indices(n, epoch=0, rank=0, size=1, shuffle=True,
                           seed=seed)[:total_samples]
    assert set(consumed) == {int(x) for x in stream}


class TestEndToEnd:
    """Acceptance: `hvdrun --elastic --max-restarts 1` with a fault plan
    killing rank 1 mid-run resumes from the snapshot and finishes with a
    bit-exact final state and loss trajectory vs. the fault-free run."""

    @pytest.mark.parametrize("k", [1, 3])
    def test_kill_rank1_resumes_bit_exact(self, tmp_path, k):
        steps, every = 18, 3
        clean_out, _ = _run_elastic_job(tmp_path, f"clean{k}", steps,
                                        every, k)
        fault_out, proc = _run_elastic_job(
            tmp_path, f"fault{k}", steps, every, k,
            fault="kill:rank=1,step=7")
        # The supervisor actually classified the SIGKILL and relaunched.
        assert "crashed" in proc.stderr
        assert "relaunching all 2 rank(s)" in proc.stderr
        for rank in (0, 1):
            clean_final = (clean_out / f"rank{rank}.final").read_text()
            fault_final = (fault_out / f"rank{rank}.final").read_text()
            # Same weights bit-for-bit (the digest covers every leaf).
            assert clean_final.split()[0] == fault_final.split()[0]
            # The interrupted+resumed trajectory equals the fault-free
            # one at every step it recorded (repr equality = bit-exact).
            clean_traj = _last_wins(clean_out / f"rank{rank}.traj")
            fault_traj = _last_wins(fault_out / f"rank{rank}.traj")
            assert fault_traj == clean_traj
        # The killed rank really did resume from a mid-run snapshot.
        assert "resumed=0" not in (fault_out / "rank1.final").read_text()

    def test_malformed_fault_plan_is_usage_error(self, tmp_path):
        proc = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.run", "-np", "1",
             "--elastic", "--fault-plan", "explode:rank=0",
             sys.executable, "-c", "pass"],
            env=_clean_env(), cwd=str(REPO), timeout=120,
            capture_output=True, text=True)
        assert proc.returncode == 2
        assert "fault plan" in proc.stderr

    def test_resize_outside_world_bounds_is_usage_error(self, tmp_path):
        proc = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.run", "-np", "2",
             "--elastic", "--fault-plan", "resize:rank=0,step=7,n=1",
             sys.executable, "-c", "pass"],   # no --min-np: bounds [2,2]
            env=_clean_env(), cwd=str(REPO), timeout=120,
            capture_output=True, text=True)
        assert proc.returncode == 2
        assert "bounds" in proc.stderr

    def test_stall_fault_terminates_via_watchdog(self, tmp_path):
        """The acceptance gap this PR closes: a stall: fault with no
        secs (= hang forever) used to wedge the job until
        HOROVOD_NEGOTIATION_TIMEOUT (default: forever). The heartbeat
        watchdog now kills the silent rank, classifies the incident
        *stalled*, and the relaunch finishes the run."""
        out, proc = _run_elastic_job(
            tmp_path, "stall", 18, 3, 1,
            fault="stall:rank=1,step=5",
            env_extra={"HOROVOD_WATCHDOG_TIMEOUT": "2"})
        assert "health watchdog" in proc.stderr
        assert "stalled" in proc.stderr
        assert "relaunching" in proc.stderr
        # Both ranks finished after the relaunch; rank 1 resumed from a
        # mid-run snapshot rather than restarting cold.
        for rank in (0, 1):
            assert (out / f"rank{rank}.final").exists()
        assert "resumed=0" not in (out / "rank1.final").read_text()


class TestEndToEndResize:
    """The resize acceptance path: `hvdrun --elastic --min-np 1 -np 2
    --fault-plan "resize:rank=0,step=7,n=1"` shrinks to np=1, resumes
    from the manifest through the cursor remap, finishes, and every
    global sample index is consumed exactly once across the resize —
    plus run-determinism given the same resize schedule, and the
    slow-marked full shrink/grow matrix."""

    TOTAL = 128   # global samples: 16 steps @ np2, 32 @ np1, 8 @ np4

    def test_shrink_2_to_1_coverage(self, tmp_path):
        fault = "resize:rank=0,step=7,n=1"
        out_a, proc = _run_resize_job(tmp_path, "shrink-a", self.TOTAL,
                                      2, fault)
        assert "resized" in proc.stderr
        assert "resizing world 2 -> 1" in proc.stderr
        # The worker really went through the reshard remap: 7 steps @
        # world 2 = 56 samples = step 14 @ world 1.
        final = (out_a / "rank0.final").read_text()
        assert "resumed=14" in final
        # The LR rescale hook fired on the world change.
        assert any(line.startswith("Z 2 1 ")
                   for line in (out_a / "rank0.samples")
                   .read_text().splitlines())
        _check_sample_coverage(out_a / "rank0.samples", self.TOTAL)

    @pytest.mark.slow
    def test_shrink_determinism_given_same_schedule(self, tmp_path):
        """Two identical resize schedules reproduce the trajectory, the
        sample stream and the final state bit-for-bit (RNG folding and
        the cursor remap are pure functions of (step, rank, world))."""
        fault = "resize:rank=0,step=7,n=1"
        out_a, _ = _run_resize_job(tmp_path, "det-a", self.TOTAL,
                                   2, fault)
        out_b, _ = _run_resize_job(tmp_path, "det-b", self.TOTAL,
                                   2, fault)
        for name in ("rank0.traj", "rank0.samples", "rank0.final"):
            assert (out_a / name).read_text() \
                == (out_b / name).read_text(), name

    @pytest.mark.slow
    def test_shrink_4_to_2_coverage(self, tmp_path):
        out, proc = _run_resize_job(
            tmp_path, "shrink42", self.TOTAL, 4,
            "resize:rank=0,step=6,n=2")
        assert "resizing world 4 -> 2" in proc.stderr
        # 6 steps @ world 4 = 96 samples = step 12 @ world 2.
        assert "resumed=12" in (out / "rank0.final").read_text()
        _check_sample_coverage(out / "rank0.samples", self.TOTAL)

    @pytest.mark.slow
    def test_grow_2_to_4_coverage(self, tmp_path):
        out, proc = _run_resize_job(
            tmp_path, "grow24", self.TOTAL, 2,
            "resize:rank=0,step=8,n=4", max_np=4)
        assert "resizing world 2 -> 4" in proc.stderr
        # 8 steps @ world 2 = 64 samples = step 4 @ world 4; the grown
        # world's brand-new ranks restored from rank 0's manifest.
        for rank in range(4):
            final = out / f"rank{rank}.final"
            assert final.exists()
            assert "resumed=4" in final.read_text()
        _check_sample_coverage(out / "rank0.samples", self.TOTAL)
