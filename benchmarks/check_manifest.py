#!/usr/bin/env python3
"""Check ``BENCHMARK.json`` against the rules a manifest is refused by, before
any run: names, units, lengths, keys, which metric moves which, that every
file a cell needs exists, and how many cells may ask for four chips.

    python3 benchmarks/check_manifest.py [path/to/BENCHMARK.json]

Prints every fault it finds and exits 1, or prints ``ok`` and exits 0.
"""

import json
import os
import re
import sys

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state_size", "proj",
               "head_dim", "head_size", "expansion", "experts_per_tok")
MAX_BYTES = 64 * 1024
MAX_RUN_SECONDS = 51


def _line(text, what, faults):
    if not isinstance(text, str) or not 1 <= len(text) <= 200 \
            or "\n" in text or "\t" in text:
        faults.append(f"{what}: 1 to 200 characters on one line, no tab")


def _keys(entry, need, what, faults, optional=("workloads",)):
    extra = set(entry) - need - set(optional)
    missing = need - set(entry)
    if extra:
        faults.append(f"{what}: keys not allowed: {sorted(extra)}")
    if missing:
        faults.append(f"{what}: keys missing: {sorted(missing)}")
    return not missing


def four_chip_allowance(cells: int) -> int:
    """A quarter of the cells, rounded down, but always one."""
    return max(1, cells // 4)


def check(manifest: dict, root: str, size: int = 0) -> list:
    faults = []
    if size > MAX_BYTES:
        faults.append(f"the file has {size} bytes, over {MAX_BYTES}")
    if set(manifest) != TOP_KEYS:
        faults.append(f"top-level keys are {sorted(manifest)}, "
                      f"not {sorted(TOP_KEYS)}")
        return faults

    paths = manifest["paths"]
    if not 1 <= len(paths) <= 16:
        faults.append("paths: 1 to 16 directories")
    for p in paths:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            faults.append(f"paths: {p!r} is not a plain relative path")
        elif not os.path.isdir(os.path.join(root, p)):
            faults.append(f"paths: {p!r} is not a directory")
    command = manifest["command"]
    if not 1 <= len(command) <= 32:
        faults.append("command: 1 to 32 strings")
    for word in command:
        _line(word, f"command word {word!r}", faults)
        if word.startswith("/") or ".." in word.split("/"):
            faults.append(f"command: {word!r} leaves the repo")
        if "/" in word and not any(
                word == p or word.startswith(p + "/") for p in paths):
            faults.append(f"command: {word!r} is outside paths")
    secs = manifest["run_seconds"]
    if not isinstance(secs, int) or not 1 <= secs <= MAX_RUN_SECONDS:
        faults.append(f"run_seconds: a whole number from 1 to "
                      f"{MAX_RUN_SECONDS}")

    def under_paths(f):
        return any(f.startswith(p + "/") for p in paths)

    def names_of(entries, what):
        seen = set()
        for e in entries:
            n = e.get("name")
            if not isinstance(n, str) or not NAME.match(n):
                faults.append(f"{what} name {n!r}: letters, digits, _ . -, "
                              f"at most 64, not starting with . or -")
            if n in seen:
                faults.append(f"{what} name {n!r} appears twice")
            seen.add(n)
        return seen

    configs = manifest["configs"]
    if not 1 <= len(configs) <= 24:
        faults.append("configs: 1 to 24")
    config_names = names_of(configs, "config")
    files = set()
    for c in configs:
        what = f"config {c.get('name')}"
        if not _keys(c, CONFIG_KEYS, what, faults, optional=()):
            continue
        _line(c["source"], what + " source", faults)
        _line(c["why"], what + " why", faults)
        if not under_paths(c["file"]) or not PATH.match(c["file"]):
            faults.append(f"{what}: file {c['file']!r} is not under paths")
        elif not os.path.isfile(os.path.join(root, c["file"])):
            faults.append(f"{what}: file {c['file']!r} does not exist")
        else:
            try:
                body = json.load(open(os.path.join(root, c["file"])))
            except ValueError as e:
                faults.append(f"{what}: {c['file']}: {e}")
            else:
                if sorted(body.get("reduced", [])) != sorted(c["reduced"]):
                    faults.append(f"{what}: reduced differs from its file's")
        if c["file"] in files:
            faults.append(f"{what}: file is another configuration's too")
        files.add(c["file"])
        if len(c["reduced"]) > 16:
            faults.append(f"{what}: reduced has over 16 keys")
        for key in c["reduced"]:
            if not NAME.match(key):
                faults.append(f"{what}: reduced key {key!r} is no name")
            if key.endswith(("_dim", "_rank")) or any(
                    w in key for w in WIDTH_WORDS):
                faults.append(f"{what}: reduced names a width: {key!r}")

    cells = manifest["workloads"]
    if not 1 <= len(cells) <= 24:
        faults.append("workloads: 1 to 24")
    cell_names = names_of(cells, "workload")
    pairs = set()
    for w in cells:
        what = f"workload {w.get('name')}"
        if not _keys(w, WORKLOAD_KEYS, what, faults, optional=()):
            continue
        _line(w["why"], what + " why", faults)
        if w["config"] not in config_names:
            faults.append(f"{what}: no config {w['config']!r}")
        if not NAME.match(str(w["traffic"])):
            faults.append(f"{what}: traffic {w['traffic']!r} is no name")
        if w["chips"] not in (1, 4):
            faults.append(f"{what}: chips is 1 or 4")
        if (w["config"], w["traffic"]) in pairs:
            faults.append(f"{what}: its config and traffic appear twice")
        pairs.add((w["config"], w["traffic"]))
        cell_file = None
        for p in paths:
            f = os.path.join(root, p, "workloads", w["name"] + ".json")
            if os.path.isfile(f):
                cell_file = f
        if cell_file is None:
            faults.append(f"{what}: no workloads/{w['name']}.json under paths")
        else:
            body = json.load(open(cell_file))
            for key in ("config", "chips", "traffic"):
                if body.get(key) != w[key]:
                    faults.append(f"{what}: {key} differs from its file's")
    used = {w.get("config") for w in cells}
    for n in config_names - used:
        faults.append(f"config {n}: used by no cell")
    four = sum(1 for w in cells if w.get("chips") == 4)
    if four > four_chip_allowance(len(cells)):
        faults.append(f"{four} of {len(cells)} cells ask for 4 chips; at most "
                      f"{four_chip_allowance(len(cells))} may")

    def metric(m, keys, what):
        ok = _keys(m, keys, what, faults)
        if "unit" in m and not UNIT.match(str(m["unit"])):
            faults.append(f"{what}: unit {m['unit']!r}: 1 to 16 of letters, "
                          f"digits, _ / % . -")
        if m.get("better") not in ("lower", "higher"):
            faults.append(f"{what}: better is lower or higher")
        if m.get("source") not in SOURCES:
            faults.append(f"{what}: source is one of {SOURCES}")
        for c in m.get("workloads", []):
            if c not in cell_names:
                faults.append(f"{what}: lists no such cell {c!r}")
        if "workloads" in m and not m["workloads"]:
            faults.append(f"{what}: an empty workloads list")
        return ok

    def reader_exists(name, what):
        """``metrics/<name>.py``, or ``metrics/<name>.json`` naming the file
        of a reader that several metrics share."""
        for p in paths:
            at = os.path.join(root, p, "metrics", name)
            if os.path.isfile(at + ".py"):
                return
            if os.path.isfile(at + ".json"):
                with open(at + ".json") as f:
                    reader = json.load(f).get("reader", "")
                if not os.path.isfile(os.path.join(root, p, reader)):
                    faults.append(f"{what}: metrics/{name}.json names no "
                                  f"reader under {p}/: {reader!r}")
                return
        faults.append(f"{what}: no metrics/{name}.py or .json under paths")

    e2e = manifest["end_to_end"]
    if not 1 <= len(e2e) <= 16:
        faults.append("end_to_end: 1 to 16")
    e2e_names = names_of(e2e, "end_to_end metric")
    reports = {c: set() for c in cell_names}     # cell -> e2e metrics
    for m in e2e:
        what = f"end_to_end metric {m.get('name')}"
        if not metric(m, E2E_KEYS, what):
            continue
        reader_exists(m["name"], what)
        if m["source"] not in ("host_clock", "device_trace"):
            faults.append(f"{what}: source is host_clock or device_trace")
        b = m["bound"]
        if not isinstance(b, (int, float)) or not 0.01 <= b <= 0.1:
            faults.append(f"{what}: bound from 0.01 to 0.1")
        for c in m.get("workloads", cell_names):
            if c in reports:
                reports[c].add(m["name"])
    if "setup_s" not in e2e_names:
        faults.append("end_to_end: setup_s is missing")
    for c, got in sorted(reports.items()):
        if "setup_s" not in got:
            faults.append(f"workload {c}: does not report setup_s")
        if len(got - {"setup_s"}) < 1:
            faults.append(f"workload {c}: reports no end-to-end metric "
                          f"besides setup_s")

    layers = manifest["per_layer"]
    if not 1 <= len(layers) <= 128:
        faults.append("per_layer: 1 to 128")
    layer_names = names_of(layers, "per_layer metric")
    for n in layer_names & e2e_names:
        faults.append(f"metric name {n!r} is in both lists")
    has_layer = set()
    for m in layers:
        what = f"per_layer metric {m.get('name')}"
        if not metric(m, LAYER_KEYS, what):
            continue
        reader_exists(m["name"], what)
        if not NAME.match(str(m["layer"])):
            faults.append(f"{what}: layer {m['layer']!r} must be 1 to 64 "
                          f"characters from letters, digits, _ . -")
        if m["moves"] not in e2e_names:
            faults.append(f"{what}: moves {m['moves']!r}, which is no "
                          f"end-to-end metric")
            continue
        if "roofline" in m["name"] or "mfu" in m["name"]:
            if m["unit"] != "%":
                faults.append(f"{what}: a share of a peak has the unit %")
        listed = m.get("workloads")
        for c in (listed if listed is not None else
                  [c for c in cell_names if m["moves"] in reports[c]]):
            if c in reports and m["moves"] not in reports[c]:
                faults.append(f"{what}: cell {c} does not report "
                              f"{m['moves']}")
            has_layer.add(c)
    for c in sorted(cell_names - has_layer):
        faults.append(f"workload {c}: reports no per-layer metric")
    return faults


def main():
    here = os.path.dirname(os.path.abspath(__file__))
    path = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        os.path.dirname(here), "BENCHMARK.json")
    with open(path) as f:
        text = f.read()
    faults = check(json.loads(text), os.path.dirname(os.path.abspath(path)),
                   len(text.encode()))
    for fault in faults:
        print(fault)
    print("ok" if not faults else f"{len(faults)} fault(s)")
    sys.exit(1 if faults else 0)


if __name__ == "__main__":
    main()
