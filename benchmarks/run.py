#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. It reaches the chips the cell asks for (and exits non-zero, with
no result line, where JAX finds no TPU or fewer chips), builds the program's
normal training lane through ``bench.build_parser`` / ``bench.build_lane``
over ``hvd.init``, hands it parameters and one batch drawn from ``--seed``,
and drives that one object: first through the steps the comparison follows
(the first of them compiles or loads from the cache; all of it is set-up),
then through the measured window. After the window it reads the peak memory,
frees the program's state, runs the plain reference on the same seed and
compares. The last line of standard output is the result.

The window: steady training on the one batch, which stays on the device.
The loop dispatches ``lane.run_step`` and keeps ``steps_in_flight`` steps in
flight: after dispatching step *i* it waits for the loss of step *i - 2*, as
a training loop that logs its loss does. It stops dispatching once
``--seconds`` have passed, waits for everything and reads the clock. A rate
is every step's images or tokens over that whole time.

Everything that belongs to one configuration, one cell or one metric is a
file this program finds by the name in ``BENCHMARK.json``:
``configs/<config>.json``, ``workloads/<cell>.json``, ``metrics/<metric>.py``
(``read(record)`` returns the value, or ``None`` where there is nothing to
read) or ``metrics/<metric>.json`` naming the file of a reader that several
metrics share. The configuration names its plain reference and its operation
count by file and function.
"""

import time

_PROCESS_START = time.time()

import argparse
import collections
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(ROOT, "chiprun_out")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)    # the program (bench, horovod_tpu), benchmarks
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def say(*a, **kw):
    """Narration goes to standard error (``bench.py``'s builders pass
    ``file=sys.stderr`` themselves)."""
    kw["file"] = sys.stderr
    print(*a, flush=True, **kw)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_function(rel_path: str, name: str):
    """``name`` from the file ``rel_path`` under ``benchmarks/``. Files are
    loaded as parts of the package ``benchmarks`` so that a reference can
    import its siblings."""
    rel = os.path.normpath(rel_path)
    if rel.startswith("..") or os.path.isabs(rel):
        raise ValueError(f"{rel_path!r} leaves benchmarks/")
    stem = rel[:-3] if rel.endswith(".py") else rel
    if "." in os.path.basename(stem):
        # a metric's name may hold dots, which no import statement can spell
        spec = importlib.util.spec_from_file_location(
            "benchmarks_file_" + stem.replace(os.sep, "_").replace(".", "_"),
            os.path.join(HERE, rel))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    else:
        module = importlib.import_module(
            "benchmarks." + stem.replace(os.sep, "."))
    return getattr(module, name)


def load_reader(metric: str):
    """A metric's ``read(record)``: from ``metrics/<metric>.py``, or from the
    file that ``metrics/<metric>.json`` names as its ``reader`` (metrics that
    differ only in the end-to-end metric they move share one)."""
    own = f"metrics/{metric}.py"
    if not os.path.isfile(os.path.join(HERE, own)):
        own = load_json(HERE, "metrics", metric + ".json")["reader"]
    return load_function(own, "read")


def load_cell(name: str):
    """``(manifest, cell, config)`` for the cell ``name``."""
    manifest = load_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"BENCHMARK.json has no workload {name!r}")
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == entry["config"])
    cell = load_json(HERE, "workloads", name + ".json")
    config = load_json(ROOT, cfg_entry["file"])
    for key in ("config", "chips"):
        if cell[key] != entry[key]:
            raise SystemExit(f"{name}: {key} differs between BENCHMARK.json "
                             f"and workloads/{name}.json")
    cell["name"] = name
    return manifest, cell, config


def metrics_of(manifest, cell_name: str, section: str):
    """The metrics of ``section`` that this cell reports: those that list it,
    and those with no list whose end-to-end metric the cell reports."""
    def lists(m):
        return "workloads" not in m or cell_name in m["workloads"]

    e2e = [m for m in manifest["end_to_end"] if lists(m)]
    if section == "end_to_end":
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if lists(m) and m["moves"] in reported]


def first_moment(opt_state, field: str):
    """The optimizer's first-moment tree (``mu`` of Adam, ``trace`` of
    momentum SGD), wherever the program's wrappers have put it."""
    if hasattr(opt_state, field) and hasattr(opt_state, "_fields"):
        return getattr(opt_state, field)
    if isinstance(opt_state, (tuple, list)):
        for sub in opt_state:
            found = first_moment(sub, field)
            if found is not None:
                return found
    return None


def loss_of(out):
    """The image step returns its metrics, the LM step its loss."""
    return out["loss"] if isinstance(out, dict) else out


class Compiles:
    """Counts the programs XLA is asked for, compiled or loaded from the
    persistent cache alike (JAX reports both as one event): the window may
    ask for none."""

    def __init__(self):
        import jax.monitoring
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.n += 1


def drive_window(lane, state, batch, *, seconds=None, steps=None,
                 in_flight: int, annotate: bool = False):
    """Dispatch steps for ``seconds`` (or exactly ``steps``), ``in_flight``
    of them ahead of the loss that is read. Returns the state and
    ``{"steps", "seconds", "dispatch_s", "losses", "arrivals_s"}``, the last
    the time since the window's start at which each loss was read."""
    import contextlib

    import jax

    def span(name):
        return (jax.profiler.TraceAnnotation(name) if annotate
                else contextlib.nullcontext())

    pending, losses, arrivals = collections.deque(), [], []
    n, dispatch_s = 0, 0.0
    t0 = time.perf_counter()

    def read_loss(x):
        losses.append(float(x))
        arrivals.append(time.perf_counter() - t0)

    while True:
        now = time.perf_counter()
        if (steps is not None and n >= steps) or \
                (seconds is not None and now - t0 >= seconds):
            break
        with span("bench.run_step"):
            state, out = lane.run_step(state, batch)
        dispatch_s += time.perf_counter() - now
        pending.append(loss_of(out))
        n += 1
        if len(pending) > in_flight:
            with span("bench.wait_loss"):
                read_loss(pending.popleft())
    with span("bench.drain"):
        for x in pending:
            read_loss(x)
        jax.block_until_ready(state)
    elapsed = time.perf_counter() - t0
    return state, {"steps": n, "seconds": elapsed, "dispatch_s": dispatch_s,
                   "losses": losses, "arrivals_s": arrivals}


class Program:
    """The program's training lane, built once, and what the harness needs
    to hand it a seed's parameters and batch and to read its first steps."""

    def __init__(self, config: dict, cell: dict):
        import jax
        import jax.numpy as jnp

        import bench

        from benchmarks import weights

        self.config, self.cell = config, cell
        bargs = bench.build_parser().parse_args(
            list(config["bench_args"]) + list(cell["bench_args"]))
        self.lane = lane = bench.build_lane(bargs, say)
        self.units_per_step = lane.units_per_step
        state, batch = lane.state, lane.batch
        lane.state = lane.batch = None
        self.param_shapes = weights.shapes_of(state["params"])
        self.batch_shapes = weights.shapes_of(batch)
        self.names = [weights.leaf_name(p) for p, _ in
                      jax.tree_util.tree_flatten_with_path(
                          self.param_shapes)[0]]
        self._shard_p = _shardings(state["params"])
        self._shard_b = _shardings(batch)
        for leaf in jax.tree_util.tree_leaves((state["params"], batch)):
            leaf.delete()                  # bench.py's PRNGKey(42) draw
        state["params"] = None
        self._fresh = state                # zero moments, step 0, no params
        self._state_type = type(state)
        self._opt_like = weights.shapes_of(state["opt_state"])
        self._opt_shard = _shardings(state["opt_state"])
        self._rest = {k: (jax.device_get(v), _shardings(v))
                      for k, v in state.items()
                      if k not in ("params", "opt_state")}

        @jax.jit
        def norms(tree):
            return jnp.stack([
                jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                for x in jax.tree_util.tree_leaves(tree)])

        @jax.jit
        def change_norms(params, key):
            start = weights.draw_params(key, self.param_shapes,
                                        config["kernel_gain"],
                                        config.get("draws"))
            return norms(jax.tree_util.tree_map(jnp.subtract, params, start))

        self._norms, self._change_norms = norms, change_norms

    def draw(self, seed: int, shard_p=None, shard_b=None):
        """``(params, batch)`` of ``seed``, placed as the lane places them,
        or as the shardings given say."""
        import jax

        from benchmarks import weights

        key = weights.run_key(seed)
        gain, ranges = self.config["kernel_gain"], self.config["int_ranges"]
        draws = self.config.get("draws")
        p = jax.jit(lambda k: weights.draw_params(k, self.param_shapes, gain,
                                                  draws),
                    out_shardings=shard_p or self._shard_p)(key)
        b = jax.jit(lambda k: weights.draw_batch(k, self.batch_shapes, ranges),
                    out_shardings=shard_b or self._shard_b)(key)
        return p, b

    def start(self, seed: int):
        """``(state, batch)`` at step 0. The first call hands over the lane's
        own state; a later one (several seeds in one process) makes the
        optimizer's zeros anew."""
        import jax
        import jax.numpy as jnp

        state, self._fresh = self._fresh, None
        if state is None:
            state = self._state_type(
                opt_state=jax.jit(
                    lambda: jax.tree_util.tree_map(
                        lambda s: jnp.zeros(s.shape, s.dtype), self._opt_like),
                    out_shardings=self._opt_shard)(),
                **{k: jax.device_put(v, sh)
                   for k, (v, sh) in self._rest.items()})
        state["params"], batch = self.draw(seed)
        return state, batch

    def first_steps(self, state, batch, seed: int, on_first=None):
        """Drive the compared steps through the lane's own call. Returns the
        state and the program's readings."""
        import jax

        from benchmarks import weights

        moment = self.config["first_moment"]
        norms = self._norms

        def named(vector, scale=1.0):
            return {k: float(v) * scale
                    for k, v in zip(self.names, jax.device_get(vector))}

        prog = {"losses": []}
        for i in range(self.cell["compare_steps"]):
            state, out = self.lane.run_step(state, batch)
            prog["losses"].append(float(loss_of(out)))
            if i == 0:
                prog["grad_norms"] = named(
                    norms(first_moment(state["opt_state"], moment["field"])),
                    moment["scale"])
                stats = self.config.get("batch_stats")
                if stats:
                    prog["stat_norms"] = self._stat_norms(
                        state["batch_stats"], stats, norms)
                if on_first:
                    on_first()
        prog["delta_norms"] = named(
            self._change_norms(state["params"], weights.run_key(seed)))
        jax.block_until_ready(state)
        return state, prog

    def _stat_norms(self, running, spec: dict, norms) -> dict:
        """The first step's batch statistics, from the running averages the
        state keeps after it: ``running = momentum x start + (1 - momentum)
        x batch``, with the start the configuration states for each name."""
        import jax

        from benchmarks import weights

        m = spec["momentum"]
        flat, _ = jax.tree_util.tree_flatten_with_path(running)
        names = [weights.leaf_name(p) for p, _ in flat]
        batch = [(leaf - m * spec["start"][name.rsplit("/", 1)[-1]]) / (1 - m)
                 for name, (_, leaf) in zip(names, flat)]
        return dict(zip(names, map(float, jax.device_get(norms(batch)))))

    def reference(self, seed: int, device, precision: str = "float32",
                  use_rows=None):
        """The plain reference's readings on the same seed, on one device."""
        import jax
        from jax.sharding import SingleDeviceSharding

        one = SingleDeviceSharding(device)
        params, batch = self.draw(
            seed, jax.tree_util.tree_map(lambda _: one, self.param_shapes),
            jax.tree_util.tree_map(lambda _: one, self.batch_shapes))
        spec = self.config["reference"]
        rows = jax.tree_util.tree_leaves(self.batch_shapes)[0].shape[0]
        return load_function(spec["file"], "train_steps")(
            params, batch, spec["hyper"], steps=self.cell["compare_steps"],
            precision=precision, loss_rows=rows // self.cell["chips"],
            rows_per_block=self.cell.get("reference_rows_per_block"),
            use_rows=use_rows)


def _shardings(tree):
    import jax

    return jax.tree_util.tree_map(lambda a: a.sharding, tree)


def run_cell(argv=None, look_for_chip: bool = True):
    """Everything after the command line; returns the result that ``main``
    prints. ``look_for_chip=False`` is for the tests, which drive the rest
    of a run on the CPU."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    manifest, cell, config = load_cell(args.workload)
    chips = cell["chips"]

    # Existing switch of the program: the static audit of collectives costs
    # seconds of tracing in every run and serves no step.
    os.environ.setdefault("HVD_BENCH_NO_STATIC_AUDIT", "1")

    import jax

    import horovod_tpu.jax as hvd
    from horovod_tpu.utils import compile_cache

    from benchmarks import compare

    compiles = Compiles()
    devices = jax.devices()
    if look_for_chip and devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX reports platform "
                         f"{devices[0].platform!r}; nothing was run")
    if len(devices) < chips:
        raise SystemExit(f"{cell['name']} asks for {chips} chip(s), JAX "
                         f"reports {len(devices)}; nothing was run")
    kind = devices[0].device_kind
    peaks = load_json(HERE, "peaks.json")
    if look_for_chip and kind not in peaks:
        raise SystemExit(f"device kind {kind!r} is not in peaks.json")
    used = devices[:chips]
    cache = compile_cache.enable()
    # JAX keeps no program that compiled in under a second, and the lane's
    # eager start-up is some 200 of those, compiled anew in every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    hvd.init(devices=used)
    t_chip = time.time()
    say(f"[bench] {cell['name']} seed {args.seed}: {devices[0].platform} / "
        f"{kind} x {len(devices)}, using {chips}; compile cache {cache}")

    # ------------------------------------------------------------- set-up
    program = Program(config, cell)
    lane = program.lane
    t_built = time.time()
    state, batch = program.start(args.seed)
    times = {}
    state, prog = program.first_steps(
        state, batch, args.seed,
        on_first=lambda: times.setdefault("first", time.time()))
    t_ready = time.time()
    setup = {"reach_chip_s": t_chip - _PROCESS_START,
             "build_lane_s": t_built - t_chip,
             "draw_and_first_step_s": times["first"] - t_built,
             "compare_steps_s": t_ready - times["first"],
             "compiles": compiles.n}
    setup_s = t_ready - _PROCESS_START

    # ------------------------------------------------------------- window
    compiled_before = compiles.n
    state, window = drive_window(lane, state, batch, seconds=args.seconds,
                                 in_flight=cell["steps_in_flight"])
    window["compiles"] = compiles.n - compiled_before
    window["units_per_step_per_chip"] = program.units_per_step
    window["chips"] = chips
    between = [b - a for a, b in zip([0.0] + window["arrivals_s"],
                                     window["arrivals_s"])]
    say(f"[bench] window: {window['steps']} steps in "
        f"{window['seconds']:.3f} s, a loss every "
        f"{1e3 * statistics.median(between):.2f} ms at the median and "
        f"{1e3 * max(between):.2f} at the longest, dispatch "
        f"{1e3 * window['dispatch_s'] / window['steps']:.2f} ms a step; "
        f"set-up {setup_s:.1f} s {setup}")

    traced = None
    if args.trace:
        from benchmarks import trace_reduce

        trace_dir = os.path.join(OUT, "trace", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            state, traced_window = drive_window(
                lane, state, batch, steps=cell["trace_steps"],
                in_flight=cell["steps_in_flight"], annotate=True)
        finally:
            jax.profiler.stop_trace()
        traced = trace_reduce.reduce_dir(trace_dir, chips=chips)
        if traced is None and look_for_chip:
            raise SystemExit("the trace holds no operation of any chip")
        if traced is not None:
            traced["steps"] = traced_window["steps"]
        shutil.rmtree(trace_dir, ignore_errors=True)

    # The TPU runtime counts live arrays (``peak_bytes_in_use``) apart from
    # what it reserves for running programs (``peak_bytes_reserved``: the
    # step's scratch, 4.5 of ResNet-50's 4.9 GB); a chip's peak is both.
    stats = [d.memory_stats() or {} for d in used]
    memory_peak = max(s.get("peak_bytes_in_use", 0)
                      + s.get("peak_bytes_reserved", 0) for s in stats)
    del state, batch
    lane.run_step = None

    # --------------------------------------------------------- comparison
    t_ref = time.time()
    ref = program.reference(args.seed, used[0])
    found = compare.gaps(prog, ref)
    found["compiles_in_window"] = (window["compiles"], "window")
    failed = sum(1 for x in window["losses"] if not math.isfinite(x))
    found["steps_not_finite"] = (failed, "window")
    limits = dict(cell["limits"], compiles_in_window=0, steps_not_finite=0)
    correct, compared = compare.decide(found, limits)
    reference_s = time.time() - t_ref

    # ------------------------------------------------------------- result
    flops_spec = config["flops"]
    flops_per_unit = load_function(flops_spec["file"], flops_spec["function"])(
        **flops_spec["args"], **cell.get("flops_args", {}))
    record = {"cell": cell, "config": config, "seed": args.seed,
              "window": window, "setup_s": setup_s, "setup": setup,
              "flops_per_unit": flops_per_unit, "peak": peaks.get(kind),
              "memory_peak_bytes": memory_peak,
              "memory_stats": stats,
              "trace": traced,
              "reference_s": reference_s,
              "program": prog, "reference": ref}
    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in metrics_of(manifest, cell["name"], section):
        value = load_reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": window["steps"],
              "failed": failed, "metrics": metrics, "device": device}
    if traced is not None:
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        result["breakdown"] = {"device_ops": traced["device_ops"][:10],
                               "idle_gaps": traced["idle_gaps"][:10]}
    result["compared"] = {
        name: {"value": value, "limit": limit, "where": where}
        for name, value, limit, where in compared}

    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, f"{cell['name']}.last_run.json"), "w") as f:
        json.dump(dict(record, result=result), f, indent=1, default=str)
    say(f"[bench] reference and comparison {reference_s:.1f} s; compared "
        f"(value <= limit):")
    for name, value, limit, where in compared:
        verdict = ("not compared" if limit is None
                   else "ok" if value <= limit else "OVER")
        say(f"[bench]   {name} {value:.6g} limit {limit} ({where}) {verdict}")
    return result


def main():
    result = run_cell()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
