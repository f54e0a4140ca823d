"""The hvdlint rule catalogue: AST checks for the distributed-training
bug classes in this repo's incident history (see tools/hvdlint/__init__.py
and docs/static_analysis.md for the case studies behind each rule).

Every rule is a function ``(tree: ast.AST) -> list[RawFinding]``; the
engine in core.py handles file walking, suppression comments, and exit
codes. Rules are deliberately heuristic — a linter for dispatch-vs-sync
or rank divergence cannot be sound AND complete — and tuned so the
historical positives fire while the repo's legitimate patterns (deadline
timers, root-prepares-payload branches, rebind-after-donation) stay
silent.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, NamedTuple, Optional, Set, Tuple


class RawFinding(NamedTuple):
    line: int
    col: int
    rule: str
    severity: str
    message: str


# ---------------------------------------------------------------- helpers

#: Wall-clock sources whose deltas are treated as timing measurements.
TIMER_CALLS = {"perf_counter", "perf_counter_ns", "monotonic", "monotonic_ns"}

#: Callables that build a compiled/async-dispatching step function; a name
#: bound to one of these becomes a "dispatch variable" in its scope.
JIT_MAKERS = {"jit", "pjit", "spmd_fn", "windowed", "make_windowed_train_step"}

#: Direct call names that asynchronously dispatch device work.
DISPATCH_NAMES = {
    "psum", "pmean", "pmin", "pmax", "psum_scatter", "all_gather",
    "all_to_all", "allreduce", "allreduce_", "allreduce_async",
    "allreduce_async_", "grouped_allreduce", "allgather", "allgather_async",
    "allgatherv", "alltoall", "reducescatter", "allreduce_sparse",
    "broadcast", "broadcast_", "broadcast_async", "broadcast_async_",
    "run_step", "train_step", "step_fn",
}

#: Calls that force (or directly perform) device synchronization:
#: dispatch is asynchronous, so HVD001 checks that one of these sits
#: inside the timed region.
SYNC_NAMES = {
    "block_until_ready", "force_device_sync", "_force_sync", "window_sync",
    "device_get", "synchronize", "wait",
}

#: Calls/attributes whose value differs per rank: branching on one of
#: these makes control flow rank-divergent.
RANK_SOURCE_NAMES = {
    "rank", "local_rank", "cross_rank", "process_index", "axis_index",
    "node_rank",
}

#: Collective operations: every rank of the world (or mesh axis) must
#: execute these the same number of times in the same order.
COLLECTIVE_NAMES = {
    "allreduce", "allreduce_", "allreduce_async", "allreduce_async_",
    "grouped_allreduce", "allgather", "allgather_async", "allgatherv",
    "broadcast", "broadcast_", "broadcast_async", "broadcast_async_",
    "alltoall", "reducescatter", "allreduce_sparse", "psum", "pmean",
    "pmin", "pmax", "psum_scatter", "all_gather", "all_to_all",
    "process_allreduce", "process_allgather", "process_broadcast",
    "barrier",
}

#: Resource-release method names: a class with any of these (or context
#: manager exit) has a deterministic cleanup path beyond __del__.
RELEASE_METHOD_NAMES = {
    "release", "close", "shutdown", "stop", "free", "destroy", "__exit__",
    "__aexit__",
}

#: Cleanup calls that must survive an exception in the preceding
#: statements — i.e. belong in a finally (or context manager), not mid-try.
CLEANUP_NAMES = {
    "shutdown", "close", "stop", "terminate", "kill", "kill_all", "cleanup",
}


def trailing_name(func: ast.AST) -> Optional[str]:
    """``jax.block_until_ready`` -> 'block_until_ready'; ``rank`` -> 'rank'."""
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def iter_scopes(tree: ast.AST) -> Iterator[ast.AST]:
    """Module + every (async) function definition."""
    yield tree
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            yield node


def scope_nodes(scope: ast.AST) -> Iterator[ast.AST]:
    """All AST nodes belonging to ``scope``, excluding nested functions
    (which are their own scopes) but including nested statements."""
    body = scope.body if isinstance(scope.body, list) else [scope.body]
    stack: List[ast.AST] = list(body)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def end_line(node: ast.AST) -> int:
    return getattr(node, "end_lineno", None) or node.lineno


# ----------------------------------------------------------------- HVD001


def check_hvd001(tree: ast.AST) -> List[RawFinding]:
    """Un-synced timing: a perf_counter/monotonic bracket whose timed
    region dispatches device work but contains no forced sync.

    Timed regions are recognized as ``t0 = time.perf_counter()`` followed
    (same scope) by a subtraction against ``t0``. Deadline arithmetic
    (``time.monotonic() + timeout``) never registers a timer variable, so
    launcher/watchdog timeouts stay silent.

    Known limitation (deliberate): brackets split across methods via
    instance attributes (``self._t0 = perf_counter()`` in one call, read
    in a later call) are out of reach — the dispatch being timed
    typically lives in a *different function or file* (the autotuner's
    probe times dispatches made by spmd.py's handle), so no single-file
    AST region exists to check. Those probes are guarded dynamically
    instead: tests/test_autotune_jax.py asserts the tuner's clock read
    happens only after a real d2h pull.
    """
    findings: List[RawFinding] = []
    for scope in iter_scopes(tree):
        nodes = list(scope_nodes(scope))
        # Dispatch variables: names bound to jit/spmd_fn/... results.
        dispatch_vars: Set[str] = set()
        for node in nodes:
            if (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)
                    and trailing_name(node.value.func) in JIT_MAKERS):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        dispatch_vars.add(tgt.id)
        # Timer variables: name -> line of the bare timer-call assignment.
        # (Two passes: scope_nodes yields AST order, not source order.)
        timer_starts: Dict[str, List[int]] = {}
        for node in nodes:
            if (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)
                    and trailing_name(node.value.func) in TIMER_CALLS):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        timer_starts.setdefault(tgt.id, []).append(
                            node.lineno)
        reads: List[Tuple[str, int]] = []  # (timer var, read line)
        for node in nodes:
            if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub):
                if (isinstance(node.right, ast.Name)
                        and node.right.id in timer_starts):
                    reads.append((node.right.id, node.lineno))
        for var, read_line in reads:
            starts = [l for l in timer_starts[var] if l < read_line]
            if not starts:
                continue
            start_line = max(starts)  # innermost bracket
            region = [
                n for n in nodes
                if isinstance(n, ast.Call)
                and start_line < n.lineno <= read_line
            ]
            has_dispatch = any(
                trailing_name(c.func) in DISPATCH_NAMES
                or (isinstance(c.func, ast.Name)
                    and c.func.id in dispatch_vars)
                for c in region
            )
            has_sync = any(
                trailing_name(c.func) in SYNC_NAMES for c in region
            )
            if has_dispatch and not has_sync:
                findings.append(RawFinding(
                    read_line, 0, "HVD001", "error",
                    f"timed region (lines {start_line}-{read_line}) "
                    "dispatches device work with no forced sync "
                    "(block_until_ready / force_device_sync) inside the "
                    "region; on an async backend this times dispatch, not "
                    "the device (the round-5 measurement bug)"))
    return findings


# ----------------------------------------------------------------- HVD002


def _mentions_rank(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            if trailing_name(sub.func) in RANK_SOURCE_NAMES:
                return True
        elif isinstance(sub, ast.Attribute):
            if sub.attr in RANK_SOURCE_NAMES:
                return True
        elif isinstance(sub, ast.Name):
            if sub.id in RANK_SOURCE_NAMES:
                return True
    return False


def _collective_calls(nodes: List[ast.AST]) -> List[ast.Call]:
    return [n for n in nodes
            if isinstance(n, ast.Call)
            and trailing_name(n.func) in COLLECTIVE_NAMES]


def _subtree_nodes(stmts: List[ast.stmt]) -> List[ast.AST]:
    out: List[ast.AST] = []
    for s in stmts:
        out.extend(ast.walk(s))
    return out


def check_hvd002(tree: ast.AST) -> List[RawFinding]:
    """Collectives under rank-divergent control flow.

    Two shapes: (a) a collective call lexically inside a branch taken
    only by some ranks — the other ranks never enter the negotiation and
    the job deadlocks; (b) a rank-guarded early ``return`` with a
    collective later in the same function — same deadlock, different
    spelling. Root-prepares-payload (``if rank()==root: buf[:] = ...``
    with the collective *outside* the branch) is the legitimate pattern
    and stays silent.
    """
    findings: List[RawFinding] = []
    for scope in iter_scopes(tree):
        nodes = list(scope_nodes(scope))
        divergent_ifs = [
            n for n in nodes
            if isinstance(n, ast.If) and _mentions_rank(n.test)
        ]
        for if_node in divergent_ifs:
            for branch in (if_node.body, if_node.orelse):
                for call in _collective_calls(_subtree_nodes(branch)):
                    findings.append(RawFinding(
                        call.lineno, call.col_offset, "HVD002", "error",
                        f"collective '{trailing_name(call.func)}' inside a "
                        f"rank-divergent branch (if at line "
                        f"{if_node.lineno}): ranks not taking this branch "
                        "never join the collective -> deadlock"))
            # (b) rank-guarded early return before a later collective.
            for branch in (if_node.body, if_node.orelse):
                rets = [s for s in branch if isinstance(s, ast.Return)]
                if not rets:
                    continue
                later = [
                    c for c in _collective_calls(nodes)
                    if c.lineno > end_line(if_node)
                ]
                if later:
                    findings.append(RawFinding(
                        rets[0].lineno, rets[0].col_offset, "HVD002",
                        "error",
                        "rank-guarded early return skips the collective "
                        f"'{trailing_name(later[0].func)}' at line "
                        f"{later[0].lineno} on some ranks -> deadlock"))
    # De-duplicate (nested ifs can report the same call twice).
    seen: Set[Tuple[int, int, str]] = set()
    out = []
    for f in findings:
        key = (f.line, f.col, f.message)
        if key not in seen:
            seen.add(key)
            out.append(f)
    return out


# ----------------------------------------------------------------- HVD003


def _donated_positions(call: ast.Call) -> Optional[Set[int]]:
    """donate_argnums positions of a jit/pjit/spmd_fn call, if static."""
    if trailing_name(call.func) not in JIT_MAKERS:
        return None
    for kw in call.keywords:
        if kw.arg != "donate_argnums":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, int):
            return {v.value}
        if isinstance(v, (ast.Tuple, ast.List)):
            out: Set[int] = set()
            for elt in v.elts:
                if (isinstance(elt, ast.Constant)
                        and isinstance(elt.value, int)):
                    out.add(elt.value)
            return out or None
    return None


def check_hvd003(tree: ast.AST) -> List[RawFinding]:
    """Use-after-donation: a variable passed at a ``donate_argnums``
    position of a locally-bound jitted callable is read again afterwards.
    XLA invalidates the donated buffer, so the read returns garbage (or
    errors) on hardware even when the CPU backend happens to tolerate
    it. Rebinding the variable from the call result (``state =
    f(state)``) is the supported pattern and kills tracking.
    """
    findings: List[RawFinding] = []
    for scope in iter_scopes(tree):
        nodes = list(scope_nodes(scope))
        donators: Dict[str, Set[int]] = {}
        for node in nodes:
            if isinstance(node, ast.Assign) and isinstance(node.value,
                                                           ast.Call):
                pos = _donated_positions(node.value)
                if pos:
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            donators[tgt.id] = pos
        if not donators:
            continue
        # All loads/stores of plain names, by line.
        loads: Dict[str, List[int]] = {}
        stores: Dict[str, List[int]] = {}
        for node in nodes:
            if isinstance(node, ast.Name):
                d = loads if isinstance(node.ctx, ast.Load) else stores
                d.setdefault(node.id, []).append(node.lineno)
        for node in nodes:
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in donators):
                continue
            call_line = node.lineno
            for i in donators[node.func.id]:
                if i >= len(node.args) or not isinstance(node.args[i],
                                                         ast.Name):
                    continue
                var = node.args[i].id
                rebinds = [l for l in stores.get(var, [])
                           if l >= call_line]
                horizon = min(rebinds) if rebinds else None
                for load_line in loads.get(var, []):
                    if load_line <= call_line:
                        continue
                    if horizon is not None and load_line >= horizon:
                        continue
                    findings.append(RawFinding(
                        load_line, 0, "HVD003", "error",
                        f"'{var}' is read after being donated to "
                        f"'{node.func.id}' (donate_argnums includes {i}) "
                        f"at line {call_line}; the donated buffer is "
                        "invalid after the call"))
    # One finding per (line, var) is enough.
    seen: Set[Tuple[int, str]] = set()
    out = []
    for f in findings:
        key = (f.line, f.message)
        if key not in seen:
            seen.add(key)
            out.append(f)
    return out


# ----------------------------------------------------------------- HVD004


def check_hvd004(tree: ast.AST) -> List[RawFinding]:
    """Resource release via ``__del__`` only: finalizer-based cleanup is
    at the mercy of GC timing (reference cycles, delayed collection)
    and is skipped entirely on interpreter teardown paths. A class
    defining ``__del__`` must also offer deterministic release
    (``release``/``close``/``shutdown``/``__exit__``/...); ``__del__``
    stays as the backstop.
    """
    findings: List[RawFinding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        methods = {
            n.name for n in node.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
        if "__del__" in methods and not (methods & RELEASE_METHOD_NAMES):
            dtor = next(n for n in node.body
                        if isinstance(n, (ast.FunctionDef,
                                          ast.AsyncFunctionDef))
                        and n.name == "__del__")
            findings.append(RawFinding(
                dtor.lineno, dtor.col_offset, "HVD004", "warning",
                f"class '{node.name}' releases resources only in "
                "__del__; add a deterministic release()/close()/"
                "context-manager path and keep __del__ as the backstop"))
    return findings


# ----------------------------------------------------------------- HVD005


def check_hvd005(tree: ast.AST) -> List[RawFinding]:
    """Cleanup in a ``try`` body that belongs in ``finally``: if any
    earlier statement in the try raises, the shutdown/close never runs
    while the except/finally paths execute — leaking the resource into
    subsequent code (the ``_dryrun_hier_dp`` leak: hvd stayed
    initialized after a failed assertion because ``hvd.shutdown()`` sat
    in the try body while only the env-var restore was in finally).

    A cleanup call that *is* the first statement of the try is the
    guarded-cleanup idiom (``try: sock.close() except OSError: pass``)
    and stays silent, as does a try whose finally (or handlers) repeat
    the same cleanup.
    """
    findings: List[RawFinding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Try) or len(node.body) < 2:
            continue
        guard_stmts = node.finalbody if node.finalbody else [
            s for h in node.handlers for s in h.body]
        if not guard_stmts and not node.handlers:
            continue
        guarded_names = {
            trailing_name(c.func)
            for c in _subtree_nodes(guard_stmts)
            if isinstance(c, ast.Call)
        }
        first_end = end_line(node.body[0])
        for call in _subtree_nodes(node.body):
            if not isinstance(call, ast.Call):
                continue
            name = trailing_name(call.func)
            if name not in CLEANUP_NAMES or name in guarded_names:
                continue
            if call.lineno <= first_end:
                continue  # guarded-cleanup idiom: try exists for the call
            where = ("finally block still runs" if node.finalbody
                     else "except handlers still run")
            findings.append(RawFinding(
                call.lineno, call.col_offset, "HVD005", "warning",
                f"'{name}()' in the try body is skipped when an earlier "
                f"statement raises, while the {where}; move the "
                "cleanup into finally (guarded by an is-active check)"))
    return findings


# ----------------------------------------------------------------- HVD006

#: Reduce-type collectives that the bucketed fusion lane
#: (grouped_allreduce / fused_reduce / DistributedOptimizer) amortizes:
#: issuing one of these PER TENSOR from a Python loop pays one
#: collective's latency + dispatch per tensor where one flat bucket
#: would pay it once (the reference built its whole fusion buffer to
#: kill exactly this pattern, operations.cc:2160-2264).
PER_TENSOR_REDUCE_NAMES = {
    "allreduce", "allreduce_", "allreduce_async", "allreduce_async_",
    "psum", "pmean", "pmin", "pmax",
}


def _target_names(target: ast.AST) -> Set[str]:
    return {sub.id for sub in ast.walk(target) if isinstance(sub, ast.Name)}


def check_hvd006(tree: ast.AST) -> List[RawFinding]:
    """Per-tensor collective in a Python loop where the bucketed fusion
    lane belongs: a ``for`` loop (or comprehension) that issues a
    reduce-type collective on the loop variable reduces each tensor as
    its own collective — one latency + dispatch charge per tensor.
    ``grouped_allreduce``/``fused_reduce`` (or the DistributedOptimizer,
    which fuses internally) packs them into flat buckets and pays it
    per bucket. Loop-invariant collectives (a per-step metric allreduce
    inside a training loop) do not mention the loop variable and stay
    silent, as do loops over steps/epochs dispatching a train step.
    """
    findings: List[RawFinding] = []
    loops: List[Tuple[Set[str], List[ast.AST]]] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.For):
            loops.append((_target_names(node.target),
                          _subtree_nodes(node.body)))
        elif isinstance(node, (ast.ListComp, ast.SetComp,
                               ast.GeneratorExp, ast.DictComp)):
            targets: Set[str] = set()
            for gen in node.generators:
                targets |= _target_names(gen.target)
            elts = ([node.key, node.value] if isinstance(node, ast.DictComp)
                    else [node.elt])
            body: List[ast.AST] = []
            for e in elts:
                body.extend(ast.walk(e))
            loops.append((targets, body))
    for targets, body in loops:
        if not targets:
            continue
        for call in body:
            if not (isinstance(call, ast.Call)
                    and trailing_name(call.func) in PER_TENSOR_REDUCE_NAMES):
                continue
            arg_names = {
                sub.id
                for a in list(call.args) + [kw.value for kw in call.keywords]
                for sub in ast.walk(a) if isinstance(sub, ast.Name)
            }
            if arg_names & targets:
                findings.append(RawFinding(
                    call.lineno, call.col_offset, "HVD006", "warning",
                    f"per-tensor collective "
                    f"'{trailing_name(call.func)}' issued inside a Python "
                    "loop over tensors: each iteration pays a full "
                    "collective latency + dispatch; fuse them with "
                    "grouped_allreduce/fused_reduce (one flat bucket per "
                    "fusion-threshold window) instead"))
    # De-duplicate (nested loops sharing a target report the call twice).
    seen: Set[Tuple[int, int]] = set()
    out = []
    for f in findings:
        key = (f.line, f.col)
        if key not in seen:
            seen.add(key)
            out.append(f)
    return out


# ----------------------------------------------------------------- HVD007

#: Filesystem-mutating call names: none of these belong in a signal
#: handler (a handler interrupts arbitrary code — possibly mid-write to
#: the same file, holding allocator/IO locks).
FS_WRITE_NAMES = {
    "write", "writelines", "write_text", "write_bytes", "replace",
    "rename", "renames", "makedirs", "mkdir", "unlink", "remove",
    "rmtree", "save", "savez", "savez_compressed", "dump", "truncate",
}

#: open() modes that mutate the filesystem.
_WRITE_MODE_CHARS = set("wax+")


def _handler_names(tree: ast.AST) -> Set[str]:
    """Function/method names registered as signal handlers via
    ``signal.signal(sig, fn)`` (or bare ``signal(sig, fn)``). SIG_DFL/
    SIG_IGN constants are not handlers."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and trailing_name(node.func) == "signal"
                and len(node.args) >= 2):
            continue
        name = trailing_name(node.args[1])
        if name and not name.startswith("SIG"):
            out.add(name)
    return out


def _open_writes(call: ast.Call) -> bool:
    if trailing_name(call.func) != "open":
        return False
    mode = None
    if len(call.args) >= 2:
        mode = call.args[1]
    for kw in call.keywords:
        if kw.arg == "mode":
            mode = kw.value
    if mode is None:
        return False  # default "r"
    return (isinstance(mode, ast.Constant) and isinstance(mode.value, str)
            and bool(set(mode.value) & _WRITE_MODE_CHARS))


def check_hvd007(tree: ast.AST) -> List[RawFinding]:
    """Blocking collective or filesystem write issued directly inside a
    signal handler.

    A handler interrupts arbitrary code: the process may be
    mid-collective (a second negotiation from handler context deadlocks
    the coordinator), mid-write to the very file the handler touches, or
    holding allocator locks. The supported pattern — the one
    ``horovod_tpu/elastic/signals.py`` is the reference for — is
    defer-to-step-boundary: the handler ONLY sets a flag; the training
    loop drains and snapshots at its next boundary, where state is
    consistent and nothing is in flight. Handlers are recognized by
    their registration (``signal.signal(sig, fn)``); flag-setting
    handlers stay silent.
    """
    findings: List[RawFinding] = []
    handlers = _handler_names(tree)
    if not handlers:
        return findings
    for node in ast.walk(tree):
        if not (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and node.name in handlers):
            continue
        for call in _subtree_nodes(node.body):
            if not isinstance(call, ast.Call):
                continue
            name = trailing_name(call.func)
            if name in COLLECTIVE_NAMES:
                findings.append(RawFinding(
                    call.lineno, call.col_offset, "HVD007", "error",
                    f"collective '{name}' issued inside signal handler "
                    f"'{node.name}': a handler interrupts arbitrary "
                    "code (possibly mid-collective) -> deadlock; set a "
                    "flag and drain/collect at the next step boundary "
                    "(the elastic signals.py pattern)"))
            elif name in FS_WRITE_NAMES or _open_writes(call):
                findings.append(RawFinding(
                    call.lineno, call.col_offset, "HVD007", "error",
                    f"filesystem write '{name}' inside signal handler "
                    f"'{node.name}': the interrupted code may hold the "
                    "same file/locks -> corruption; set a flag and "
                    "snapshot at the next step boundary (the elastic "
                    "signals.py pattern)"))
    return findings


# ----------------------------------------------------------------- HVD008

#: The physical mesh-axis names the repo once hardcoded everywhere.
#: Scoped to the data-parallel / hierarchical axes (the ones every
#: module used to spell identically); the per-module axes
#: ("tp"/"pp"/"sp"/"ep") are parameters resolved through the
#: LogicalMesh rules table.
MESH_AXIS_LITERALS = {"hvd", "ici", "dcn"}  # hvdlint: disable=HVD008 (the rule owns its vocabulary)

#: Path suffixes allowed to own specific findings. Consumed by the
#: engine (core.lint_source) since rules themselves see only the AST.
#: HVD008 has NO entry: the axis vocabulary lives solely in
#: parallel/logical.py's DATA_AXIS/ICI_AXIS/DCN_AXIS constants, whose
#: three definitions carry the one justified suppression each.
PATH_EXEMPT = {
    # The allocator's own module is the single place allowed to call
    # the strict single-holder free() fast path (COW failure cleanup);
    # everyone else must go through refcounted release().
    "HVD013": ("serve/kvcache.py",),
}


def check_hvd008(tree: ast.AST) -> List[RawFinding]:
    """Hardcoded mesh-axis string literal: a bare ``"hvd"``/``"ici"``/
    ``"dcn"`` constant names a physical mesh axis at the use site, so
    every module and harness must agree on spellings by convention
    alone. The LogicalMesh layer (``parallel/logical.py``) unwound that
    coupling: import ``DATA_AXIS``/``ICI_AXIS``/``DCN_AXIS`` or resolve
    a logical axis through the rules table (``module_axis``,
    ``LogicalMesh.spec``). This rule is a hard regression gate — there
    is no path exemption; only logical.py's three constant definitions
    carry a justified suppression.

    Only exact-match constants fire (a log message *containing* "hvd"
    is not an axis name).
    """
    findings: List[RawFinding] = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Constant)
                and isinstance(node.value, str)
                and node.value in MESH_AXIS_LITERALS):
            continue
        findings.append(RawFinding(
            node.lineno, node.col_offset, "HVD008", "warning",
            f"hardcoded mesh-axis literal '{node.value}': axis naming "
            "by string convention couples every module to every other; "
            "import the constant from parallel/logical.py (DATA_AXIS/"
            "ICI_AXIS/DCN_AXIS) or resolve a logical axis through the "
            "LogicalMesh rules table"))
    return findings


# ----------------------------------------------------------------- HVD009

#: The run.driver exit taxonomy — the contract between workers, the
#: launcher's supervision loop and the elastic supervisor: 0 clean,
#: 2 usage, 75 preempted (EX_TEMPFAIL), 76 resized. A handler exiting
#: with anything else is classified "crashed" and burns the restart
#: budget even when the exit was deliberate.
TAXONOMY_EXIT_CODES = {0, 2, 75, 76}

#: Process-exit spellings a handler might use.
EXIT_CALL_NAMES = {"exit", "_exit"}


def _exit_handler_names(tree: ast.AST) -> Set[str]:
    """Functions whose exit codes reach the supervisor from handler
    context: registered signal handlers (``signal.signal(sig, fn)``)
    and teardown callbacks (``atexit.register(fn)``)."""
    out = set(_handler_names(tree))
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and trailing_name(node.func) == "register"
                and isinstance(node.func, ast.Attribute)
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "atexit"
                and node.args):
            name = trailing_name(node.args[0])
            if name:
                out.add(name)
    return out


def check_hvd009(tree: ast.AST) -> List[RawFinding]:
    """Non-taxonomy exit code from a registered signal handler or
    supervisor callback.

    The elastic supervisor decides relaunch-vs-fail from the exit code
    alone (``run.driver.classify_exit``): 75 relaunches FREE (preempted),
    76 resizes, 2 fails fast, anything else is a *crash* that burns the
    restart budget. A handler that exits ``sys.exit(1)`` after a clean
    drain therefore turns every preemption into a budgeted crash — the
    exit code IS the recovery protocol. Handlers must exit through the
    ``EXIT_*`` constants (``run.driver`` / ``elastic.signals``). Flagged:
    ``sys.exit``/``os._exit`` with an integer (or string) literal outside
    the taxonomy, inside a function registered via ``signal.signal`` or
    ``atexit.register``. Names spelling a taxonomy constant (``EXIT_*``)
    and bare ``sys.exit()`` (= 0) stay silent.
    """
    findings: List[RawFinding] = []
    handlers = _exit_handler_names(tree)
    if not handlers:
        return findings
    for node in ast.walk(tree):
        if not (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and node.name in handlers):
            continue
        for call in _subtree_nodes(node.body):
            if not (isinstance(call, ast.Call)
                    and trailing_name(call.func) in EXIT_CALL_NAMES
                    and call.args):
                continue
            arg = call.args[0]
            bad = None
            if isinstance(arg, ast.Constant):
                if isinstance(arg.value, bool) or not isinstance(
                        arg.value, int):
                    bad = repr(arg.value)
                elif arg.value not in TAXONOMY_EXIT_CODES:
                    bad = str(arg.value)
            if bad is None:
                continue
            findings.append(RawFinding(
                call.lineno, call.col_offset, "HVD009", "error",
                f"handler '{node.name}' exits with non-taxonomy code "
                f"{bad}: the supervisor classifies this as a crash and "
                "burns the restart budget; exit through the "
                "run.driver constants (EXIT_CLEAN/EXIT_USAGE/"
                "EXIT_PREEMPTED/EXIT_RESIZED) so the incident class "
                "survives the exit"))
    return findings


# ----------------------------------------------------------------- HVD010

#: Call-name substrings that mark a loop iteration as a retry of
#: external work: relaunching a worker/replica, resubmitting a request,
#: reconnecting a channel. (Substring match: `_launch`, `relaunch`,
#: `launch_job`, `resubmit`, `reconnect`, ... all register.)
RETRY_CALL_MARKERS = (
    "launch", "relaunch", "restart", "resubmit", "submit", "retry",
    "reconnect", "respawn",
)

#: Calls that implement a backoff between attempts.
BACKOFF_CALL_NAMES = {"sleep", "backoff", "wait_backoff"}


def _is_number(node: ast.AST) -> bool:
    return (isinstance(node, ast.Constant)
            and isinstance(node.value, (int, float))
            and not isinstance(node.value, bool))


def _loop_has_counter(body_nodes: List[ast.AST]) -> bool:
    """An attempt counter: an additive augmented assignment by a
    NUMERIC literal (``attempts += 1``) or an explicit counter rebind
    (``n = n + 1``) inside the loop body. The literal requirement is
    deliberate: ``buf += chunk`` / ``data += sock.recv(n)`` are
    accumulators that bound nothing — a retry loop hiding behind one
    must still fire."""
    for n in body_nodes:
        if isinstance(n, ast.AugAssign) and isinstance(n.op, ast.Add) \
                and _is_number(n.value):
            return True
        if (isinstance(n, ast.Assign) and isinstance(n.value, ast.BinOp)
                and isinstance(n.value.op, ast.Add)):
            # Both counter spellings count the same: bare names and
            # attribute targets (self.attempts = self.attempts + 1 —
            # the AugAssign branch already accepts any target).
            tgt_names = set()
            for t in n.targets:
                if isinstance(t, ast.Name):
                    tgt_names.add(t.id)
                elif isinstance(t, ast.Attribute):
                    tgt_names.add(t.attr)
            operand_names = {s.id for s in ast.walk(n.value)
                             if isinstance(s, ast.Name)}
            operand_names |= {s.attr for s in ast.walk(n.value)
                              if isinstance(s, ast.Attribute)}
            if (tgt_names & operand_names) and (
                    _is_number(n.value.left)
                    or _is_number(n.value.right)):
                return True
    return False


def check_hvd010(tree: ast.AST) -> List[RawFinding]:
    """Retry loop with no backoff and no budget: a ``while True:``
    (or ``while 1:``) whose body re-launches/re-submits/re-connects
    external work but contains neither a sleep/backoff call nor an
    attempt counter.

    A worker that crash-loops instantly re-crashes: an unbudgeted,
    backoff-less relaunch loop turns one bad host into a busy-looping
    supervisor and one overloaded service into a retry storm (the
    thundering-herd failure mode). The supervised patterns in this repo
    — the elastic supervisor's ``max_restarts`` budget with
    ``restart_delay``, the serving fleet's fleet-wide budget with
    exponential backoff — always bound attempts AND space them out.
    Either signal silences the rule (a counted loop is assumed to be
    compared against a budget somewhere; a sleeping loop at least
    cannot spin); bounded ``for`` loops never fire.
    """
    findings: List[RawFinding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.While):
            continue
        test = node.test
        if not (isinstance(test, ast.Constant) and test.value in (True, 1)):
            continue
        # The loop's OWN scope only: a nested def/lambda in the body
        # neither retries per-iteration (its launch() call runs
        # elsewhere) nor backs the loop off (its sleep() never runs
        # here) — descending into it would mis-attribute both.
        body: List[ast.AST] = []
        stack: List[ast.AST] = list(node.body)
        while stack:
            n = stack.pop()
            body.append(n)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                continue
            stack.extend(ast.iter_child_nodes(n))
        retry_calls = [
            c for c in body
            if isinstance(c, ast.Call)
            and any(m in (trailing_name(c.func) or "").lower()
                    for m in RETRY_CALL_MARKERS)
        ]
        if not retry_calls:
            continue
        has_backoff = any(
            isinstance(c, ast.Call)
            and trailing_name(c.func) in BACKOFF_CALL_NAMES
            for c in body)
        if has_backoff or _loop_has_counter(body):
            continue
        call = retry_calls[0]
        findings.append(RawFinding(
            call.lineno, call.col_offset, "HVD010", "warning",
            f"'{trailing_name(call.func)}' retried in a 'while True:' "
            "loop with no backoff call and no attempt counter: a "
            "failing relaunch/resubmit spins at full speed forever "
            "(crash loop / retry storm); bound the attempts against a "
            "budget and back off between them (the elastic "
            "supervisor's max_restarts + restart_delay discipline)"))
    return findings


# ----------------------------------------------------------------- HVD011

#: Method names that are ALWAYS a blocking network receive (socket
#: API); these fire regardless of what the receiver is called.
#: ``accept`` belongs here since the TCP-listener round: a listener
#: blocked in accept() with no timeout can never notice shutdown —
#: the serving-fleet workers poll it in 0.25 s slices for exactly
#: that reason.
RECEIVE_CALL_NAMES = {"recv", "recvfrom", "recv_into", "recvmsg",
                      "accept"}

#: Stream-read spellings that are only a hang risk on a socket/pipe —
#: gated on the receiver's name so ordinary file ``f.read()`` stays
#: silent.
STREAM_READ_NAMES = {"read", "readline", "readlines"}

#: Receiver-name substrings that mark a read target as a socket/pipe/
#: stream (``sock.recv``, ``conn.makefile().readline``,
#: ``proc.stdout.readline``, ...).
STREAM_RECEIVER_MARKERS = (
    "sock", "conn", "pipe", "chan", "stream", "fifo", "stdout", "stderr",
)

#: Identifier substrings that mark a deadline/timeout in scope.
DEADLINE_NAME_MARKERS = ("timeout", "deadline")

#: Calls that bound a read some other way (socket timeouts, readiness
#: polling).
DEADLINE_CALL_NAMES = {"settimeout", "setdefaulttimeout", "setblocking",
                       "select", "poll"}


def _own_scope_nodes(fn: ast.AST) -> List[ast.AST]:
    """The function's OWN body nodes, excluding nested def/lambda
    bodies (a nested function's reads block in ITS scope — each def is
    judged on its own deadline discipline)."""
    out: List[ast.AST] = []
    stack: List[ast.AST] = list(fn.body)
    while stack:
        n = stack.pop()
        out.append(n)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(n))
    return out


def check_hvd011(tree: ast.AST) -> List[RawFinding]:
    """Blocking ``recv``/``read``/``readline`` on a socket or pipe with
    no timeout/deadline in scope — the silent-hang shape.

    A receive with no bound hangs FOREVER when the peer dies mid-write
    or simply stops: the reader blocks in the kernel, no exception, no
    heartbeat, nothing for a watchdog to classify — the exact failure
    the serving-fleet transport (horovod_tpu/serve/transport.py, every
    recv deadline-sliced) and the launcher wire
    (run/network.py ``Wire.read(timeout=)``) were built to never have.
    Flagged: a call whose attribute name is a socket receive
    (``recv``/``recvfrom``/...; always) or a stream read
    (``read``/``readline`` on a receiver whose name says socket/pipe:
    ``sock``, ``conn``, ``pipe``, ``stdout``, ...), inside a function
    with NO deadline discipline in scope. Silencers (either): an
    identifier containing ``timeout``/``deadline`` anywhere in the
    function (parameter, local, attribute, keyword), or a bounding
    call (``settimeout``/``select``/``poll``/...). A justified
    unbounded read (a daemon pump thread draining a child's stdout)
    suppresses with a comment explaining why it may block forever.
    """
    findings: List[RawFinding] = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        nodes = _own_scope_nodes(fn)
        sig_names = [a.arg for a in fn.args.args
                     + fn.args.kwonlyargs
                     + ([fn.args.vararg] if fn.args.vararg else [])
                     + ([fn.args.kwarg] if fn.args.kwarg else [])]
        idents = set(sig_names)
        bounded = False
        for n in nodes:
            if isinstance(n, ast.Name):
                idents.add(n.id)
            elif isinstance(n, ast.Attribute):
                idents.add(n.attr)
            elif isinstance(n, ast.keyword) and n.arg:
                idents.add(n.arg)
            elif isinstance(n, ast.Call) and \
                    trailing_name(n.func) in DEADLINE_CALL_NAMES:
                bounded = True
        if bounded or any(m in i.lower() for i in idents
                          for m in DEADLINE_NAME_MARKERS):
            continue
        for call in nodes:
            if not (isinstance(call, ast.Call)
                    and isinstance(call.func, ast.Attribute)):
                continue
            name = call.func.attr
            if name in RECEIVE_CALL_NAMES:
                shape = f"socket {name}()"
            elif name in STREAM_READ_NAMES:
                recv_name = trailing_name(call.func.value) or ""
                if not any(m in recv_name.lower()
                           for m in STREAM_RECEIVER_MARKERS):
                    continue
                shape = f"{recv_name}.{name}()"
            else:
                continue
            findings.append(RawFinding(
                call.lineno, call.col_offset, "HVD011", "error",
                f"blocking {shape} with no timeout/deadline in scope: "
                "a peer that dies mid-write (or stops sending) hangs "
                "this reader forever — silently, with nothing for a "
                "watchdog to classify; bound every receive (the "
                "serve/transport.py deadline discipline, or "
                "settimeout/select) or suppress with the reason the "
                "read may legitimately block forever"))
    return findings


# ----------------------------------------------------------------- HVD012

#: numpy artifact savers: ``np.save``/``np.savez``/... writing a
#: params/checkpoint-shaped file ALWAYS counts as an artifact write.
NUMPY_MODULE_NAMES = {"np", "numpy", "jnp"}
NUMPY_SAVER_NAMES = {"save", "savez", "savez_compressed"}

#: Receiver-name markers that make a binary ``open(..., "wb")`` an
#: ARTIFACT write (ordinary binary writes — logs, sockets dumps — stay
#: silent unless they look like weights/checkpoints).
ARTIFACT_NAME_MARKERS = (
    "param", "weight", "ckpt", "checkpoint", "snapshot", "artifact",
    "manifest", "model", "npz", "npy", "state_dict",
)

#: Calls that commit a write atomically (write-to-temp THEN rename).
COMMIT_CALL_NAMES = {"rename", "replace"}

#: Identifier markers for a digest/checksum discipline in scope.
DIGEST_NAME_MARKERS = ("sha256", "sha1", "sha512", "md5", "digest",
                       "checksum", "crc32", "crc", "blake")


def _hvd012_artifact_writes(nodes: List[ast.AST]) -> List[Tuple[ast.Call, str]]:
    out: List[Tuple[ast.Call, str]] = []
    for call in nodes:
        if not isinstance(call, ast.Call):
            continue
        f = call.func
        if isinstance(f, ast.Attribute) \
                and f.attr in NUMPY_SAVER_NAMES \
                and isinstance(f.value, ast.Name) \
                and f.value.id in NUMPY_MODULE_NAMES:
            out.append((call, f"{f.value.id}.{f.attr}"))
            continue
        if trailing_name(f) != "open" or len(call.args) < 2:
            continue
        mode = call.args[1]
        if not (isinstance(mode, ast.Constant)
                and isinstance(mode.value, str)
                and "b" in mode.value
                and ("w" in mode.value or "x" in mode.value)):
            continue
        target_idents: List[str] = []
        for n in ast.walk(call.args[0]):
            if isinstance(n, ast.Name):
                target_idents.append(n.id.lower())
            elif isinstance(n, ast.Attribute):
                target_idents.append(n.attr.lower())
            elif isinstance(n, ast.Constant) and isinstance(n.value, str):
                target_idents.append(n.value.lower())
        if any(m in t for t in target_idents
               for m in ARTIFACT_NAME_MARKERS):
            out.append((call, f"open(.., {mode.value!r})"))
    return out


def check_hvd012(tree: ast.AST) -> List[RawFinding]:
    """Artifact file written without an atomic-rename commit or digest
    check in scope — the torn-params-load shape.

    A ``np.savez(path)`` (or a binary ``open(weights_path, "wb")``
    write) that lands DIRECTLY at its final path is torn the moment
    the writer crashes, is SIGKILLed, or the disk fills mid-write —
    and a later load of that path parses the torn prefix into
    silently wrong weights (numpy containers and raw-bytes blobs both
    truncate "successfully"). The repo's own disciplines are the
    fixture negatives: the elastic manifest's two-phase commit
    (write ``.tmp`` then ``os.replace``) makes a torn write invisible,
    and the serve/params_wire.py assembler digest-verifies the whole
    artifact before its atomic rename, so a torn or corrupted file is
    a typed error, never a load. Flagged: an artifact write (numpy
    saver, or a binary ``open`` whose target names
    params/weights/checkpoint/...) in a function with NEITHER a
    ``rename``/``replace`` commit call NOR a digest identifier
    (sha256/checksum/crc/...) in scope. Either discipline silences.
    """
    findings: List[RawFinding] = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        nodes = _own_scope_nodes(fn)
        writes = _hvd012_artifact_writes(nodes)
        if not writes:
            continue
        committed = any(
            isinstance(n, ast.Call)
            and trailing_name(n.func) in COMMIT_CALL_NAMES
            for n in nodes)
        if committed:
            continue
        idents: Set[str] = set()
        for n in nodes:
            if isinstance(n, ast.Name):
                idents.add(n.id)
            elif isinstance(n, ast.Attribute):
                idents.add(n.attr)
            elif isinstance(n, ast.keyword) and n.arg:
                idents.add(n.arg)
        if any(m in i.lower() for i in idents
               for m in DIGEST_NAME_MARKERS):
            continue
        for call, label in writes:
            findings.append(RawFinding(
                call.lineno, call.col_offset, "HVD012", "error",
                f"artifact written via {label} with no atomic-rename "
                "commit and no digest check in scope: a crash (or "
                "SIGKILL) mid-write leaves a torn file a later load "
                "parses into silently wrong weights — write to a temp "
                "path and os.replace() it into place (the elastic "
                "manifest two-phase commit), or digest-verify before "
                "load (the serve/params_wire.py assembler discipline)"))
    return findings


# ----------------------------------------------------------------- HVD013

#: Identifier markers that make a ``.free(...)`` receiver a page
#: allocator (``alloc.free(...)``, ``self.cache.allocator.free(...)``).
#: A ``.free()`` on anything not named allocator-like stays silent.
ALLOCATOR_NAME_MARKER = "alloc"


def check_hvd013(tree: ast.AST) -> List[RawFinding]:
    """Direct page-allocator ``free()`` call outside serve/kvcache.py —
    the double-free / shared-page-leak shape under prefix caching.

    Since KV pages became refcounted (copy-on-write prefix caching),
    ``PageAllocator.free`` is the strict SINGLE-HOLDER fast path: it
    raises on a page any second holder still maps. Call sites outside
    the allocator's module cannot see refcounts — a page that looks
    exclusively owned may be mapped read-only into another request's
    table via a prefix hit, or pinned by the radix index's own +1 hold.
    Freeing it there either throws mid-release (the raise) or, were the
    check ever weakened, hands the page to a new request while the old
    holders still read it — silent KV corruption. Every holder outside
    serve/kvcache.py must drop pages through ``release()`` (decrement,
    free at zero), which is exactly what ``Scheduler.release`` and the
    prefix index do. ``serve/kvcache.py`` itself is path-exempt via
    ``PATH_EXEMPT``: the allocator's own COW-failure cleanup frees a
    page it just allocated and provably never shared.
    """
    findings: List[RawFinding] = []
    for call in ast.walk(tree):
        if not isinstance(call, ast.Call) \
                or not isinstance(call.func, ast.Attribute) \
                or call.func.attr != "free":
            continue
        receiver_idents = []
        for n in ast.walk(call.func.value):
            if isinstance(n, ast.Name):
                receiver_idents.append(n.id.lower())
            elif isinstance(n, ast.Attribute):
                receiver_idents.append(n.attr.lower())
        if not any(ALLOCATOR_NAME_MARKER in i for i in receiver_idents):
            continue
        findings.append(RawFinding(
            call.lineno, call.col_offset, "HVD013", "error",
            "direct page-allocator free() outside serve/kvcache.py: "
            "pages are refcounted (prefix caching shares them across "
            "requests and the radix index holds its own +1), and this "
            "call site cannot see the refcount — a shared page here is "
            "a raise at best, KV corruption at worst; drop pages via "
            "release() (decrement, free at zero) like "
            "Scheduler.release does"))
    return findings


# ----------------------------------------------------------------- HVD014

#: Socket chunk-transfer method names that ALWAYS mark a loop as a
#: chunked wire transfer, whatever the receiver is called.
CHUNK_SOCKET_CALL_NAMES = {"sendall", "sendto", "recvfrom", "recv_into"}

#: Ambiguous spellings (generators have ``.send``, queues have
#: ``.recv``): these only count when the receiver's name says
#: socket/pipe/stream (the HVD011 marker vocabulary).
CHUNK_AMBIGUOUS_CALL_NAMES = {"send", "recv"}


def check_hvd014(tree: ast.AST) -> List[RawFinding]:
    """Chunked socket send/recv loop with neither a per-chunk deadline
    nor a CRC/digest check in scope — the torn-transfer shape.

    A ``for``/``while`` loop that pumps chunks over a socket is the
    repo's hottest wire surface (weights pushes, KV-page handoffs), and
    it fails in two distinct ways the loop itself cannot see: a peer
    that stalls mid-stream hangs an unbounded loop forever (the HVD011
    hang, amplified — one chunk of thousands is enough), and a torn or
    bit-flipped chunk assembles into a silently corrupt artifact the
    importer admits as real weights/KV. The shipped discipline is
    ``serve/chunk_stream.py`` (the canonical negative): every chunk is
    framed with its own crc32, the assembled artifact is sha256-gated,
    and both sides run under the transport's absolute-deadline recv.
    Flagged: a loop whose body (nested defs excluded) calls a socket
    chunk-transfer method — ``sendall``/``sendto``/``recvfrom``/
    ``recv_into`` always; bare ``send``/``recv`` only on a receiver
    whose name says socket/pipe (``sock``, ``conn``, ``stream``, ...) —
    inside a function with NEITHER deadline discipline (an identifier
    containing ``timeout``/``deadline``, or a bounding call such as
    ``settimeout``/``select``) NOR a digest identifier
    (crc/crc32/sha256/checksum/...) in scope. Either discipline
    silences; a loop that cannot hang AND cannot tear needs both, which
    in this repo means: frame it through chunk_stream.
    """
    findings: List[RawFinding] = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        nodes = _own_scope_nodes(fn)
        sig_names = [a.arg for a in fn.args.args
                     + fn.args.kwonlyargs
                     + ([fn.args.vararg] if fn.args.vararg else [])
                     + ([fn.args.kwarg] if fn.args.kwarg else [])]
        idents = set(sig_names)
        bounded = False
        for n in nodes:
            if isinstance(n, ast.Name):
                idents.add(n.id)
            elif isinstance(n, ast.Attribute):
                idents.add(n.attr)
            elif isinstance(n, ast.keyword) and n.arg:
                idents.add(n.arg)
            elif isinstance(n, ast.Call) and \
                    trailing_name(n.func) in DEADLINE_CALL_NAMES:
                bounded = True
        if bounded or any(m in i.lower() for i in idents
                          for m in DEADLINE_NAME_MARKERS):
            continue
        if any(m in i.lower() for i in idents
               for m in DIGEST_NAME_MARKERS):
            continue
        for loop in nodes:
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            verb = None
            for call in _subtree_nodes(loop.body + loop.orelse):
                if not (isinstance(call, ast.Call)
                        and isinstance(call.func, ast.Attribute)):
                    continue
                name = call.func.attr
                if name in CHUNK_SOCKET_CALL_NAMES:
                    verb = name
                    break
                if name in CHUNK_AMBIGUOUS_CALL_NAMES:
                    recv_name = trailing_name(call.func.value) or ""
                    if any(m in recv_name.lower()
                           for m in STREAM_RECEIVER_MARKERS):
                        verb = f"{recv_name}.{name}"
                        break
            if verb is None:
                continue
            findings.append(RawFinding(
                loop.lineno, loop.col_offset, "HVD014", "error",
                f"chunked socket transfer loop ({verb}()) with no "
                "per-chunk deadline and no CRC/digest check in scope: "
                "a peer stalling mid-stream hangs the loop forever, "
                "and a torn/bit-flipped chunk assembles into silently "
                "corrupt weights/KV the importer admits as real — "
                "frame the stream through serve/chunk_stream.py "
                "(per-chunk crc32 + whole-artifact sha256 under the "
                "transport's deadline-sliced recv), or add either "
                "discipline and suppress with the reason the other "
                "cannot apply"))
    return findings


RULES = {
    "HVD001": check_hvd001,
    "HVD002": check_hvd002,
    "HVD003": check_hvd003,
    "HVD004": check_hvd004,
    "HVD005": check_hvd005,
    "HVD006": check_hvd006,
    "HVD007": check_hvd007,
    "HVD008": check_hvd008,
    "HVD009": check_hvd009,
    "HVD010": check_hvd010,
    "HVD011": check_hvd011,
    "HVD012": check_hvd012,
    "HVD013": check_hvd013,
    "HVD014": check_hvd014,
}
