"""A copy of ``benchmarks/`` in a temporary directory with toy cells added as
new files only: what a later PR does when it brings a configuration, a cell
and a per-layer metric, at sizes the CPU runs in seconds."""

import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCHMARKS = os.path.dirname(HERE)
REPO = os.path.dirname(BENCHMARKS)

TOY_LM = {
    "source": "toy", "reduced": [], "compute_dtype": "bfloat16",
    "bench_args": ["--model", "transformer_lm", "--lm-layers", "2",
                   "--lm-dim", "64", "--lm-heads", "4", "--vocab", "512"],
    "kernel_gain": 1.0, "int_ranges": {"tokens": 512},
    "first_moment": {"field": "mu", "scale": 10.0},
    "reference": {"file": "reference/gpt2.py", "hyper": {
        "n_head": 4, "layer_norm_eps": 1e-06, "qkv_bias": False,
        "tied_head": False,
        "optimizer": {"name": "adam", "lr": 0.0001, "b1": 0.9, "b2": 0.999,
                      "eps": 1e-08}}},
    "flops": {"file": "flops.py", "function": "lm_per_token",
              "args": {"layers": 2, "d_model": 64, "vocab": 512}},
}
TOY_RESNET = {
    "source": "toy", "reduced": [], "compute_dtype": "bfloat16",
    "bench_args": ["--model", "resnet50", "--image-size", "32"],
    "kernel_gain": 2.0, "int_ranges": {"label": 1000},
    "draws": {"ConvBN_2/scale": {"mean": 0.1, "std": 0.01}},
    "first_moment": {"field": "trace", "scale": 1.0},
    "batch_stats": {"momentum": 0.9, "start": {"mean": 0.0, "var": 1.0}},
    "reference": {"file": "reference/resnet50.py", "hyper": {
        "stages": [3, 4, 6, 3], "stride_on_3x3": True,
        "batch_norm_eps": 1e-05,
        "optimizer": {"name": "sgd_momentum", "lr": 0.01, "momentum": 0.9}}},
    "flops": {"file": "flops.py", "function": "resnet_per_image",
              "args": {"stages": [3, 4, 6, 3], "width": 64, "image": 32,
                       "classes": 1000, "stride_on_3x3": True}},
}
LIMITS_LM = {"loss1_gap": 4e-4, "loss2_gap": 4e-4, "loss3_gap": 4e-4,
             "grad_gap": 0.012, "delta_gap": 0.018}
# 8 images of 32 x 32 normalise over 8 numbers in the last stage, so the third
# loss and the worst leaf's gradient are loose here; the batch statistics and
# the second loss are what the fp8 control fails (test_control.py)
LIMITS_RESNET = {"loss1_gap": 0.05, "loss2_gap": 0.05, "loss3_gap": 0.3,
                 "stat_gap": 0.008, "stat_median_gap": 8e-4,
                 "grad_gap": 0.3, "delta_gap": 0.3}


def cell(config, chips, bench_args, limits, **more):
    return dict({"config": config, "chips": chips, "traffic": f"toy_{chips}",
                 "why": "toy", "bench_args": bench_args, "steps_in_flight": 2,
                 "compare_steps": 3, "trace_steps": 4, "limits": limits},
                **more)


CELLS = {
    "toy_lm_1chip": cell("toy_lm", 1, ["--batch-size", "4", "--seq-len", "32",
                                       "--remat"], LIMITS_LM,
                         flops_args={"seq_len": 32},
                         reference_rows_per_block=2),
    "toy_lm_dp4": cell("toy_lm", 4, ["--batch-size", "4", "--seq-len", "32",
                                     "--remat"], LIMITS_LM,
                       flops_args={"seq_len": 32},
                       reference_rows_per_block=8),
    "toy_resnet_1chip": cell("toy_resnet", 1, ["--batch-size", "8"],
                             LIMITS_RESNET),
}
TOY_METRIC = '''"""A per-layer metric a later PR might bring: steps a second."""


def read(record):
    return record["window"]["steps"] / record["window"]["seconds"]
'''


def resnet_shapes(stages, classes=1000):
    """The parameter tree of ``models/resnet.py``'s bottleneck ResNet, as
    shapes, for the tests that put the reference in the program's place."""
    import jax
    import jax.numpy as jnp

    tree = {"stem": {"kernel": (7, 7, 3, 64)},
            "head": {"kernel": (512 * 4, classes), "bias": (classes,)}}
    wide, i = 64, 0
    for stage, blocks in enumerate(stages):
        w = 64 * 2 ** stage
        for j in range(blocks):
            block = {"ConvBN_0": {"kernel": (1, 1, wide, w)},
                     "ConvBN_1": {"kernel": (3, 3, w, w)},
                     "ConvBN_2": {"kernel": (1, 1, w, 4 * w)}}
            if j == 0:
                block["proj"] = {"kernel": (1, 1, wide, 4 * w)}
            tree[f"BottleneckResNetBlock_{i}"] = block
            wide, i = 4 * w, i + 1
    for conv_bn in [tree["stem"]] + [c for name, b in tree.items()
                                     if name.startswith("Bottleneck")
                                     for c in b.values()]:
        conv_bn["scale"] = conv_bn["bias"] = (conv_bn["kernel"][-1],)
    return jax.tree_util.tree_map(
        lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32), tree,
        is_leaf=lambda x: isinstance(x, tuple))


def make_tree(root: str):
    """``root/benchmarks`` (a copy) plus toy files, and ``root/BENCHMARK.json``
    naming only the toy cells. No file of the copy is edited."""
    dst = os.path.join(root, "benchmarks")
    shutil.copytree(BENCHMARKS, dst, ignore=shutil.ignore_patterns(
        "__pycache__", "*.pyc"))
    before = {os.path.relpath(os.path.join(d, f), dst): os.path.getmtime(
        os.path.join(d, f)) for d, _, fs in os.walk(dst) for f in fs}

    def write(rel, text):
        path = os.path.join(dst, rel)
        assert not os.path.exists(path), f"{rel} would edit a file"
        with open(path, "w") as f:
            f.write(text)

    write("configs/toy_lm.json", json.dumps(TOY_LM))
    write("configs/toy_resnet.json", json.dumps(TOY_RESNET))
    for name, body in CELLS.items():
        write(f"workloads/{name}.json", json.dumps(body))
    write("metrics/steps_per_s.toy.py", TOY_METRIC)
    real = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    names = list(CELLS)
    manifest = dict(real)
    manifest["configs"] = [
        {"name": n, "source": "toy", "file": f"benchmarks/configs/{n}.json",
         "reduced": [], "why": "toy"} for n in ("toy_lm", "toy_resnet")]
    manifest["workloads"] = [
        {"name": n, "config": c["config"], "traffic": c["traffic"],
         "chips": c["chips"], "why": "toy"} for n, c in CELLS.items()]
    lm = [n for n in names if "lm" in n]
    img = [n for n in names if "resnet" in n]

    def retarget(m):
        m = dict(m)
        if "workloads" in m:
            m["workloads"] = img if m["name"].endswith(("img", "img_per_s_per_chip")) else lm
        return m

    manifest["end_to_end"] = [retarget(m) for m in real["end_to_end"]]
    manifest["per_layer"] = [retarget(m) for m in real["per_layer"]] + [
        {"name": "steps_per_s.toy", "unit": "steps/s", "better": "higher",
         "source": "host_clock", "layer": "spmd_harness",
         "moves": "tok_per_s_per_chip", "workloads": lm}]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    after = {os.path.relpath(os.path.join(d, f), dst): os.path.getmtime(
        os.path.join(d, f)) for d, _, fs in os.walk(dst) for f in fs}
    assert all(after[k] == v for k, v in before.items()), "a file was edited"
    return manifest


def drive(root, cell_name, *, seed=7, seconds=0.5, trace=0, fault=None,
          timeout=600):
    """Run one toy cell through ``drive.py``; returns ``(result, stderr)``."""
    chips = CELLS[cell_name]["chips"]
    cmd = [sys.executable, os.path.join(HERE, "drive.py"), "--root", root,
           "--repo", REPO, "--devices", str(chips)]
    if fault:
        cmd += ["--fault", fault]
    cmd += ["--", "--workload", cell_name, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS", "HVD_TPU_FORCE_CPU")}
    done = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=timeout)
    if done.returncode != 0:
        raise RuntimeError(f"{cmd}: exit {done.returncode}\n"
                           f"{done.stderr[-4000:]}")
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stderr
