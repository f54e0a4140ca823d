"""CLI: ``python -m tools.hvdverify`` — the static verification gate.

Exit status mirrors hvdlint: 0 when every finding is suppressed (or no
findings exist), 1 otherwise — so ``python -m tools.hvdverify --sweep``
is a CI gate (tools/check.sh --verify wires it in; the pytest pin is
tests/test_hvdverify.py::test_repo_sweep_is_clean).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Sequence

# The sweep traces under an 8-device virtual CPU mesh (no chips, no
# compilation), set before jax loads.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8").strip()


def main(argv: Sequence[str] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.hvdverify",
        description="jaxpr-level collective-schedule & sharding verifier "
                    "(rules HVV101-HVV105; docs/static_analysis.md).")
    parser.add_argument("--sweep", action="store_true",
                        help="verify the full program registry (CI gate)")
    parser.add_argument("--group", default="",
                        help="comma list of registry groups "
                             "(gate,optimizer,parallel,elastic)")
    parser.add_argument("--program", default="",
                        help="comma list of registry program names")
    parser.add_argument("--list", action="store_true", dest="list_programs",
                        help="print the program registry and exit")
    parser.add_argument("--list-rules", action="store_true",
                        help="print the rule catalogue and exit")
    parser.add_argument("--schedule", action="store_true",
                        help="print each program's collective schedule")
    parser.add_argument("--show-suppressed", action="store_true",
                        help="also print suppressed findings")
    parser.add_argument("--json", action="store_true",
                        help="emit one JSON object per program "
                             "(summary + findings)")
    args = parser.parse_args(argv)

    from tools.hvdverify.registry import REGISTRY, programs
    from tools.hvdverify.rules import RULES

    if args.list_rules:
        for rule_id, doc in sorted(RULES.items()):
            print(f"{rule_id}  {doc}")
        return 0
    if args.list_programs:
        for p in REGISTRY:
            marks = []
            if p.forbid_donation:
                marks.append("forbid-donation")
            if p.reconcile:
                marks.append("byte-reconciled")
            print(f"{p.name:34s} [{p.group}]"
                  + (f"  ({', '.join(marks)})" if marks else ""))
        return 0

    groups = [g.strip() for g in args.group.split(",") if g.strip()]
    names = [n.strip() for n in args.program.split(",") if n.strip()]
    if not (args.sweep or groups or names):
        parser.error("nothing to do: pass --sweep, --group or --program")

    from tools.hvdverify.core import verify_programs

    try:
        selected = programs(groups or None, names or None)
    except KeyError as e:
        parser.error(str(e))

    results = verify_programs(selected)
    active = suppressed = 0
    for res in results:
        active += len(res.active)
        suppressed += len(res.suppressed)
        if args.json:
            print(json.dumps({
                "program": res.name,
                "collectives": res.summary,
                "findings": [
                    {"rule": f.rule, "message": f.message,
                     "path": f.path, "suppressed": f.suppressed}
                    for f in res.findings],
            }))
            continue
        s = res.summary
        print(f"{res.name:34s} {s['count']:3d} collective(s) "
              f"{s['mb']:10.2f} MB  "
              f"{len(res.active)} finding(s)"
              + (f" ({len(res.suppressed)} suppressed)"
                 if res.suppressed else ""))
        shown = (res.findings if args.show_suppressed else res.active)
        for f in shown:
            print(f"  {f.format()}")
        if args.schedule:
            for op in res.schedule:
                print(f"    {op.describe()}")
    if not args.json:
        print(f"hvdverify: {len(results)} program(s), "
              f"{active} finding(s), {suppressed} suppressed")
    return 1 if active else 0


if __name__ == "__main__":
    sys.exit(main())
