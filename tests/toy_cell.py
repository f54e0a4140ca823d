"""What the rehearsals of a configuration's cell share (``test_trinity_cell``,
``test_ouro_cell``): the configuration at a toy size, its cell and the
manifest's new entries added to a copy of ``benchmarks/`` as new files only."""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_TESTS = os.path.join(REPO, "benchmarks", "tests")
for _path in (REPO, BENCH_TESTS):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from benchmarks import check_manifest  # noqa: E402


def add_toy_cell(root, name, config, cell, new_metrics):
    """``toy.make_tree(root)`` plus ``configs/<name>.json``, ``workloads/
    <name>_1chip.json`` and the manifest's entries for them; the cell is
    appended to every metric that lists the copy's one-chip toy LM (where
    nothing is there to read, as in a dense model, a reader returns
    nothing)."""
    import toy

    toy.make_tree(root)
    dst = os.path.join(root, "benchmarks")
    cell_name = name + "_1chip"
    for rel, body in ((f"configs/{name}.json", config),
                      (f"workloads/{cell_name}.json", cell)):
        path = os.path.join(dst, rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            json.dump(body, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": name, "source": "toy", "reduced": config["reduced"],
         "file": f"benchmarks/configs/{name}.json", "why": "toy"})
    manifest["workloads"].append(
        {"name": cell_name, "config": name, "traffic": cell["traffic"],
         "chips": 1, "why": "toy"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "toy_lm_1chip" in m.get("workloads", []):
            m["workloads"].append(cell_name)
    assert {m["name"] for m in manifest["per_layer"]} >= set(new_metrics)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    assert check_manifest.check(manifest, root) == []


def drive_toy_cell(root, cell_name, **kw):
    """``toy.drive`` for a cell :func:`add_toy_cell` added."""
    import toy

    toy.CELLS.setdefault(cell_name, {"chips": 1})
    try:
        return toy.drive(root, cell_name, **kw)
    finally:
        toy.CELLS.pop(cell_name, None)
