"""``check_manifest.py``: the committed manifest passes, and each rule that
has refused a PR (or would) is seen to refuse."""

import copy
import json
import os

import pytest

from benchmarks import check_manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture()
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_committed_manifest_passes(manifest):
    size = os.path.getsize(os.path.join(ROOT, "BENCHMARK.json"))
    assert check_manifest.check(manifest, ROOT, size) == []


def _faults(manifest, edit):
    m = copy.deepcopy(manifest)
    edit(m)
    return check_manifest.check(m, ROOT)


def _set(section, index, key, value):
    def edit(m):
        m[section][index][key] = value
    return edit


@pytest.mark.parametrize("edit, says", [
    (_set("per_layer", 0, "layer", "SPMD harness"), "layer"),     # PR 22
    (_set("per_layer", 0, "name", "has space"), "name"),
    (_set("per_layer", 0, "unit", "tokens per second"), "unit"),
    (_set("per_layer", 0, "moves", "no_such_metric"), "moves"),
    (_set("per_layer", 0, "moves", "tok_per_s_per_chip"), "does not report"),
    (_set("per_layer", 0, "why", "not allowed"), "keys not allowed"),
    (_set("per_layer", 0, "source", "stopwatch"), "source"),
    (_set("per_layer", 0, "name", "no_reader.img"), "no metrics/no_reader.img"),
    (_set("end_to_end", 0, "bound", 0.2), "bound"),
    (_set("end_to_end", 0, "source", "program_counter"), "source"),
    (_set("workloads", 0, "chips", 2), "chips"),
    (_set("workloads", 0, "why", "x" * 201), "why"),
    (_set("configs", 0, "source", "x" * 201), "source"),
    (_set("configs", 0, "file", "bench.py"), "not under paths"),
    (_set("configs", 0, "reduced", ["hidden_size"]), "width"),
    (lambda m: m.__setitem__("run_seconds", 52), "run_seconds"),
    (lambda m: m.__setitem__("command", ["python3", "bench.py/x"]),
     "outside paths"),
    (lambda m: m["end_to_end"].pop(), "setup_s"),
])
def test_a_broken_manifest_is_refused(manifest, edit, says):
    faults = _faults(manifest, edit)
    assert any(says in f for f in faults), faults


def test_at_most_a_quarter_of_the_cells_but_always_one_may_ask_for_four():
    assert [check_manifest.four_chip_allowance(n) for n in (1, 3, 4, 7, 8, 24)] \
        == [1, 1, 1, 1, 2, 6]


def test_a_second_four_chip_cell_of_three_is_refused(manifest):
    def edit(m):
        for w in m["workloads"][:2]:
            w["chips"] = 4
    faults = _faults(manifest, edit)
    assert any("ask for 4 chips" in f for f in faults), faults
