"""The rehearsal: every kind of cell end to end at toy sizes on the CPU, from a
copy of ``benchmarks/`` to which the toy configuration, cells and metric were
added as new files only; and the timed path broken underneath, once for each
fault a training cell can have, with ``correct`` seen to come out false.

Each case is a process of its own (a cell fixes how many devices JAX may
see), about a quarter of a minute each for the LM and a minute for ResNet-50.
"""

import pytest

import toy

DEVICE_METRICS = ("step_mfu_pct", "device_idle_pct", "device_step_ms",
                  "peak_hbm_gib", "collective_ms_per_step")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("bench_copy"))
    toy.make_tree(path)
    return path


def _well_formed(result, trace):
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert list(result)[-1] == "compared"
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] > 0 and result["failed"] == 0
    for name, m in result["metrics"].items():
        assert set(m) == {"value", "unit"} and m["value"] > 0, name
        # a CPU run writes no number under the name of a device metric
        assert not name.startswith(DEVICE_METRICS), name
    for name, c in result["compared"].items():
        assert set(c) == {"value", "limit", "where"}, name
    if trace:
        assert "setup_s" not in result["metrics"]
        assert "busy_s" not in result["device"]      # no chip in the trace
    else:
        assert "setup_s" in result["metrics"]
        assert len(result["metrics"]) == 2


@pytest.mark.parametrize("cell, trace", [
    ("toy_lm_1chip", 0), ("toy_lm_1chip", 1), ("toy_lm_dp4", 0),
    ("toy_resnet_1chip", 0)])
def test_a_toy_cell_runs_end_to_end(root, cell, trace):
    result, err = toy.drive(root, cell, trace=trace, seed=2 ** 31 + 11)
    _well_formed(result, trace)
    assert result["correct"], err[-2000:]
    if trace:
        assert "steps_per_s.toy" in result["metrics"]   # the metric added
    last = [l for l in err.splitlines() if l.startswith("[bench]   ")]
    assert {l.split()[1] for l in last} == set(result["compared"])


@pytest.mark.parametrize("cell, fault, caught_by", [
    ("toy_lm_1chip", "state_unchanged", "delta_gap"),
    ("toy_lm_1chip", "half_batch", "grad_gap"),
    ("toy_lm_1chip", "loss_altered", "loss1_gap"),
    ("toy_lm_dp4", "no_exchange", "grad_gap"),
    ("toy_resnet_1chip", "state_unchanged", "delta_gap"),
    ("toy_resnet_1chip", "half_batch", "grad_gap"),
])
def test_a_broken_timed_path_comes_out_not_correct(root, cell, fault,
                                                   caught_by):
    result, _ = toy.drive(root, cell, fault=fault)
    assert result["correct"] is False
    c = result["compared"][caught_by]
    assert c["value"] > c["limit"]


def test_without_a_chip_the_command_exits_non_zero_and_prints_no_result():
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("HVD_TPU_FORCE_CPU", None)
    done = subprocess.run(
        [sys.executable, os.path.join(toy.BENCHMARKS, "run.py"), "--workload",
         "resnet50_bs128_1chip", "--seed", "1", "--seconds", "1", "--trace",
         "0"], capture_output=True, text=True, env=env, cwd=toy.REPO,
        timeout=300)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "no TPU" in done.stderr
