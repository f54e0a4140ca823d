"""Contract tests for the inference bench lanes: tools/serve_bench.py
(record shape, --ab, --require-finished) and the decode_bench
satellite fixes (shared model construction, --steps validation)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = ["--layers", "2", "--d-model", "64", "--heads", "2",
        "--vocab", "128", "--requests", "6", "--rate", "50",
        "--prompt-min", "4", "--prompt-max", "12",
        "--new-min", "2", "--new-max", "6", "--decode-slots", "2",
        "--prefill-chunk", "4", "--page-size", "8"]


def _run(script, *argv, check=True):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", script), *argv],
        capture_output=True, text=True, env=env, timeout=600)
    if check:
        assert p.returncode == 0, p.stderr[-2000:]
    return p


class TestServeBenchContract:
    def test_continuous_record_contract(self):
        """The CI smoke lane's contract (tools/check.sh): one JSON
        line with tokens/s/chip, p50/p99 TTFT, p50/p99 per-token
        latency, page occupancy — all requests finished and the greedy
        streams pinned against lm_decode."""
        p = _run("serve_bench.py", *TINY, "--pin-exact",
                 "--require-finished")
        rec = json.loads(p.stdout.strip().splitlines()[-1])
        assert rec["metric"] == "serve_continuous_tokens_per_sec_per_chip"
        assert rec["unit"] == "tokens/sec/chip"
        assert rec["value"] > 0
        s = rec["serve"]
        assert s["by_state"] == {"finished": 6}
        for key in ("p50", "p99"):
            assert s["ttft_ms"][key] is not None
            assert s["tbt_ms"][key] is not None
        assert 0 < s["pages"]["occupancy_max"] <= 1
        assert rec["config"]["policy"] == "fcfs"

    def test_ab_record_carries_both_sides(self):
        p = _run("serve_bench.py", *TINY, "--ab")
        rec = json.loads(p.stdout.strip().splitlines()[-1])
        assert rec["metric"] == "serve_ab_tokens_per_sec_per_chip"
        ab = rec["serve"]["ab"]
        assert ab["static"]["tokens_per_sec_per_chip"] > 0
        assert ab["continuous_over_static"] is not None

    def test_attention_paged_record_contract(self):
        """--attention paged: same record contract, all greedy streams
        still pinned against lm_decode, plus the kernel's traffic
        accounting stamped (live-page bytes strictly below the gather
        path's)."""
        p = _run("serve_bench.py", *TINY, "--attention", "paged",
                 "--pin-exact", "--require-finished")
        rec = json.loads(p.stdout.strip().splitlines()[-1])
        assert rec["config"]["attention"] == "paged"
        a = rec["serve"]["attention"]
        assert a["mode"] == "paged"
        assert 0 < a["kv_fetch_frac"] < 1
        assert a["kv_bytes_per_step_paged"] < \
            a["kv_bytes_per_step_gather"]

    def test_ab_attention_record_carries_both_sides(self):
        """--ab-attention: one record with the paged side as headline,
        the gather side + the paged_over_gather throughput ratio under
        serve.ab_attention, and the static byte accounting on BOTH
        sides."""
        p = _run("serve_bench.py", *TINY, "--requests", "4",
                 "--ab-attention")
        rec = json.loads(p.stdout.strip().splitlines()[-1])
        assert rec["metric"] == \
            "serve_ab_attention_tokens_per_sec_per_chip"
        assert rec["config"]["attention"] == "ab"
        s = rec["serve"]
        assert s["attention"]["mode"] == "paged"
        ab = s["ab_attention"]
        assert ab["gather"]["attention"]["mode"] == "gather"
        assert ab["gather"]["tokens_per_sec_per_chip"] > 0
        assert ab["paged_over_gather"] is not None
        for side in (s, ab["gather"]):
            assert 0 < side["attention"]["kv_fetch_frac"] < 1

    def test_ab_attention_is_exclusive_with_other_modes(self):
        for extra in (["--ab"], ["--static"]):
            p = _run("serve_bench.py", *TINY, "--ab-attention", *extra,
                     check=False)
            assert p.returncode == 2, (extra, p.stderr[-300:])

    def test_ab_prefix_record_contract(self):
        """--ab-prefix (round-16 acceptance, single-engine edition):
        the many-users-one-system-prompt workload runs cold THEN
        cached, the cached side must actually save prefill tokens with
        exactly one cold prefill for the shared prefix, every greedy
        stream is bit-identical off vs on AND pinned against lm_decode,
        and the record stamps both sides + the hit accounting."""
        p = _run("serve_bench.py", *TINY, "--ab-prefix",
                 "--pin-exact", "--require-finished")
        rec = json.loads(p.stdout.strip().splitlines()[-1])
        assert rec["metric"] == "serve_ab_prefix_tokens_per_sec_per_chip"
        s = rec["serve"]
        assert s["mode"] == "ab_prefix"
        assert s["by_state"] == {"finished": 6}
        pb = s["prefix"]
        assert pb["hit_rate"] > 0
        assert pb["prefill_tokens_saved"] > 0
        assert pb["cow_copies"] == 0     # decode never lands on shared
        ab = s["ab_prefix"]
        assert ab["off"]["prefix"] is None   # explicit off-side stamp
        assert ab["off"]["by_state"] == {"finished": 6}
        assert ab["system_prompt_tokens"] == 32   # auto: 4 pages
        assert ab["unique_prefixes"] == 1         # one system prompt
        assert ab["cold_prefills"] == 1           # exactly one cold
        assert ab["exact_pin"]["identical"] is True
        assert ab["exact_pin"]["compared"] == 6
        assert rec["config"]["prefix_caching"] == "ab"
        assert rec["config"]["system_prompt_len"] == 32

    def test_ab_prefix_is_exclusive_with_other_modes(self):
        for extra in (["--ab"], ["--static"], ["--ab-attention"],
                      ["--prefix"],
                      ["--fleet", "2", "--fault-plan",
                       "kill:replica=1,at=50%"]):
            p = _run("serve_bench.py", *TINY, "--ab-prefix", *extra,
                     check=False)
            assert p.returncode == 2, (extra, p.stderr[-300:])

    def test_ab_tp_record_contract(self):
        """--ab-tp (round-18 acceptance): the identical workload runs
        unsharded then head-sharded over dp=1,tp=4; the bench aborts
        unless every greedy stream is bit-identical and the sharded
        side's per-chip KV bytes are at most 1/tp — so a passing run
        IS the exactness+bandwidth evidence, and the record stamps
        serve.tp{degree, kv_bytes_per_chip, tp_over_single}."""
        p = _run("serve_bench.py", *TINY, "--heads", "4",
                 "--mesh", "dp=1,tp=4", "--ab-tp",
                 "--pin-exact", "--require-finished")
        rec = json.loads(p.stdout.strip().splitlines()[-1])
        assert rec["metric"] == "serve_ab_tp_tokens_per_sec_per_chip"
        s = rec["serve"]
        assert s["mode"] == "ab_tp"
        assert s["by_state"] == {"finished": 6}
        assert s["attention"]["tp"] == 4
        tp = s["tp"]
        assert tp["degree"] == 4 and tp["mesh"] == "dp=1,tp=4"
        assert tp["exact_pin"]["identical"] is True
        assert tp["exact_pin"]["compared"] == 6
        assert tp["kv_bytes_per_chip"] == pytest.approx(
            tp["kv_bytes_per_chip_single"] / 4, rel=1e-3)
        assert tp["tp_over_single"] is not None
        assert rec["config"]["mesh"] == "dp=1,tp=4"

    def test_ab_tp_arg_validation(self):
        # --ab-tp without a mesh, with another A/B, a mesh that
        # resolves to tp=1, and mesh+fleet are all argparse errors
        for argv in (["--ab-tp"],
                     ["--mesh", "dp=1,tp=2", "--ab-tp", "--ab"],
                     ["--mesh", "dp=1", "--ab-tp"],
                     ["--mesh", "garbage", "--ab-tp"],
                     ["--mesh", "dp=1,tp=2", "--fleet", "2"]):
            p = _run("serve_bench.py", *TINY, *argv, check=False)
            assert p.returncode == 2, (argv, p.stderr[-300:])


    def test_ab_spec_record_contract(self):
        """--ab-spec (round 19): one record, speculative side as the
        headline, the non-spec side under serve.ab_spec.base, the
        greedy streams of BOTH sides pinned bit-identical
        (exact_pin.identical), and the full-depth draft's
        deterministic accounting: accept_rate exactly 1.0,
        tokens_per_step > 1."""
        p = _run("serve_bench.py", *TINY, "--speculate", "4",
                 "--draft-layers", "2", "--ab-spec", "--pin-exact",
                 "--require-finished")
        rec = json.loads(p.stdout.strip().splitlines()[-1])
        assert rec["metric"] == "serve_ab_spec_tokens_per_sec_per_chip"
        assert rec["config"]["speculate_k"] == "ab"
        s = rec["serve"]
        assert s["mode"] == "ab_spec"
        assert s["spec"]["k"] == 4 and s["spec"]["draft_layers"] == 2
        ab = s["ab_spec"]
        assert ab["k"] == 4 and ab["draft_layers"] == 2
        assert ab["base"]["spec"] is None
        assert ab["base"]["tokens_per_sec_per_chip"] > 0
        assert ab["exact_pin"]["identical"] is True
        assert ab["exact_pin"]["compared"] == 6
        # draft depth == target depth (TINY has 2 layers): the draft
        # IS the target, so acceptance is total by construction
        assert ab["accept_rate"] == 1.0
        assert ab["tokens_per_step"] > 1.0
        assert ab["spec_over_base"] is not None

    def test_ab_spec_arg_validation(self):
        # --ab-spec without --speculate, with every other A/B mode,
        # with a fleet, plus the bare spec-knob misuses are all
        # argparse errors
        for argv in (["--ab-spec"],
                     ["--speculate", "2", "--ab-spec", "--ab"],
                     ["--speculate", "2", "--ab-spec", "--static"],
                     ["--speculate", "2", "--ab-spec",
                      "--ab-attention"],
                     ["--speculate", "2", "--ab-spec", "--ab-prefix"],
                     ["--speculate", "2", "--ab-spec", "--fleet", "2"],
                     ["--speculate", "-1"],
                     ["--draft-layers", "1"]):
            p = _run("serve_bench.py", *TINY, *argv, check=False)
            assert p.returncode == 2, (argv, p.stderr[-300:])

    def test_require_finished_fails_loudly(self):
        # capacity of ONE page (8 positions): several drawn requests
        # can never fit and hard-reject -> --require-finished exits 1
        p = _run("serve_bench.py", *TINY, "--num-pages", "2",
                 "--require-finished", check=False)
        assert p.returncode != 0
        assert "not all requests finished" in (p.stderr + p.stdout)

    def test_bad_args_are_argparse_errors(self):
        for bad in (["--rate", "0"], ["--requests", "0"],
                    ["--prompt-min", "9", "--prompt-max", "4"]):
            p = _run("serve_bench.py", *TINY[:-2], *bad, check=False)
            assert p.returncode == 2, (bad, p.stderr[-300:])


class TestFleetBenchContract:
    def test_fleet_fault_ab_record_contract(self):
        """The round-12 acceptance e2e: --fleet 2 with a mid-run
        replica kill runs clean THEN faulted on the identical workload,
        pins every both-finished greedy stream bit-identical, classes
        the incident, and stamps the recovery metrics."""
        p = _run("serve_bench.py", *TINY, "--rate", "200",
                 "--fleet", "2", "--fault-plan", "kill:replica=1,at=50%",
                 "--pin-exact", "--require-finished")
        rec = json.loads(p.stdout.strip().splitlines()[-1])
        assert rec["metric"] == \
            "serve_fleet_fault_ab_tokens_per_sec_per_chip"
        s = rec["serve"]
        assert s["mode"] == "fleet_fault_ab"
        assert s["by_state"] == {"finished": 6}
        f = s["fleet"]
        assert f["incidents_by_class"] == {"crashed": 1}
        assert f["replicas"] == 2
        # never FAILED (budget 2); whether the relaunch landed before
        # the fleet drained is timing, so only pin the invariant
        assert f["failed"] == 0
        inc = f["incidents"][0]
        assert inc["category"] == "crashed" and inc["code"] == -9
        ab = s["fleet_ab"]
        assert ab["redispatch_pin"]["identical"] is True
        assert ab["redispatch_pin"]["compared"] == 6
        assert ab["clean"]["by_state"] == {"finished": 6}
        assert ab["p99_ttft_clean_ms"] is not None
        assert ab["p99_ttft_faulted_ms"] is not None
        assert rec["config"]["fleet"]["replicas"] == 2
        assert rec["config"]["fleet"]["fault_plan"] == \
            "kill:replica=1,at=50%"

    def test_fleet_process_transport_record_contract(self):
        """The round-13 acceptance e2e: the same fault A/B with one
        worker OS process per replica — the kill SIGKILLs a REAL
        process (incident code -9 from the reaped exit), the record
        stamps transport='process' + per-RPC overhead + transport
        incident counts, and no worker process survives the bench."""
        def worker_pids():
            ps = subprocess.run(
                ["pgrep", "-f", "horovod_tpu.serve.worker"],
                capture_output=True, text=True)
            return set(ps.stdout.split())

        pre = worker_pids()   # other jobs' workers are not ours to judge
        p = _run("serve_bench.py", *TINY, "--rate", "200",
                 "--fleet", "2", "--fleet-transport", "process",
                 "--fault-plan", "kill:replica=1,at=50%",
                 "--pin-exact", "--require-finished")
        rec = json.loads(p.stdout.strip().splitlines()[-1])
        s = rec["serve"]
        assert s["mode"] == "fleet_fault_ab"
        assert s["by_state"] == {"finished": 6}
        f = s["fleet"]
        assert f["transport"] == "process"
        assert f["rpc_ms"]["calls"] > 0
        assert f["rpc_ms"]["p50"] is not None
        assert f["rpc_ms"]["p99"] is not None
        assert f["incidents_by_class"] == {"crashed": 1}
        inc = f["incidents"][0]
        assert inc["category"] == "crashed" and inc["code"] == -9
        ab = s["fleet_ab"]
        assert ab["redispatch_pin"]["identical"] is True
        assert ab["redispatch_pin"]["compared"] == 6
        # both A/B sides stamp the transport evidence
        assert ab["clean"]["fleet"]["transport"] == "process"
        assert ab["clean"]["fleet"]["rpc_ms"]["calls"] > 0
        assert rec["config"]["fleet"]["transport"] == "process"
        # no zombie/orphan workers survive the bench process (scoped:
        # only NEW pids count — a concurrent job's workers are not
        # this bench's leak)
        leaked = worker_pids() - pre
        assert not leaked, leaked

    def test_fleet_clean_record_contract(self):
        p = _run("serve_bench.py", *TINY, "--fleet", "2",
                 "--pin-exact", "--require-finished")
        rec = json.loads(p.stdout.strip().splitlines()[-1])
        assert rec["metric"] == "serve_fleet_tokens_per_sec_per_chip"
        s = rec["serve"]
        assert s["mode"] == "fleet"
        assert s["by_state"] == {"finished": 6}
        f = s["fleet"]
        assert f["incidents"] == [] and f["redispatched"] == 0
        assert f["healthy"] == 2
        assert "fleet_ab" not in s

    def test_fleet_ab_prefix_record_contract(self):
        """--fleet 2 --ab-prefix: the cold pin tightens to one cold
        prefill per (prefix, REPLICA) — rendezvous routing sends every
        prefix-mate to one home unless saturation spills, and each
        replica that serves the prefix pays for it exactly once."""
        p = _run("serve_bench.py", *TINY, "--fleet", "2", "--ab-prefix",
                 "--pin-exact", "--require-finished")
        rec = json.loads(p.stdout.strip().splitlines()[-1])
        assert rec["metric"] == "serve_ab_prefix_tokens_per_sec_per_chip"
        s = rec["serve"]
        assert s["mode"] == "ab_prefix"
        assert s["by_state"] == {"finished": 6}
        pb = s["fleet"]["prefix"]
        assert pb["hits"] > 0 and pb["prefill_tokens_saved"] > 0
        ab = s["ab_prefix"]
        assert ab["off"]["fleet"]["prefix"] is None
        assert ab["unique_prefixes"] == 1
        # one cold prefill per replica the prefix landed on, no more
        assert ab["cold_prefills"] == ab["replica_homes"] >= 1
        assert ab["exact_pin"]["identical"] is True
        assert ab["exact_pin"]["compared"] == 6

    def test_fleet_arg_validation(self):
        cases = [
            # faults address replicas: need --fleet
            ["--fault-plan", "kill:replica=0,at=1s"],
            # replica outside the fleet
            ["--fleet", "2", "--fault-plan", "kill:replica=5,at=1s"],
            # malformed plan dies in argparse, not mid-run
            ["--fleet", "2", "--fault-plan", "explode:replica=0,at=1s"],
            # a stall with no watchdog would hang the lane forever
            ["--fleet", "2", "--fault-plan", "stall:replica=0,at=1s"],
            # one A/B per record
            ["--fleet", "2", "--ab"],
            ["--fleet", "2", "--ab-attention"],
            ["--fleet", "2", "--static"],
        ]
        for bad in cases:
            p = _run("serve_bench.py", *TINY, *bad, check=False)
            assert p.returncode == 2, (bad, p.stderr[-300:])


class TestDecodeBenchSatellites:
    def test_steps_zero_is_an_argparse_error(self):
        """The satellite fix: --steps 0 must die in argparse, not as a
        downstream scan/shape failure."""
        p = _run("decode_bench.py", "--steps", "0", check=False)
        assert p.returncode == 2
        assert "--steps must be >= 1" in p.stderr

    def test_negative_iters_rejected(self):
        p = _run("decode_bench.py", "--iters", "0", check=False)
        assert p.returncode == 2

    def test_shared_builder_shapes(self):
        """decode_bench and serve_bench build the SAME model through
        tools.lm_common (the A/B precondition)."""
        import argparse

        from tools.lm_common import (add_model_args, build_params,
                                     validate_model_args)

        ap = argparse.ArgumentParser()
        add_model_args(ap)
        args = ap.parse_args(["--layers", "2", "--d-model", "64",
                              "--heads", "2", "--vocab", "128"])
        validate_model_args(ap, args)
        params = build_params(args, max_len=32)
        assert len(params["layers"]) == 2
        assert params["embed"].shape == (128, 64)
        assert params["pos"].shape == (32, 64)
        assert params["layers"][0]["wqkv"].shape == (64, 3, 2, 32)
        assert params["layers"][0]["wup"].shape == (64, 256)

    def test_d_model_heads_divisibility_error(self):
        p = _run("decode_bench.py", "--d-model", "100", "--heads", "12",
                 check=False)
        assert p.returncode == 2
        assert "divisible" in p.stderr
