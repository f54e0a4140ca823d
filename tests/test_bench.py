"""Smoke tests for the driver's bench entry (`bench.py`).

The driver runs ``python bench.py`` on real hardware at round end; these
tests pin its contract — one JSON line with metric/value/unit/vs_baseline
— on the hermetic 8-device CPU mesh so a refactor can't silently break
the recorded benchmark. Protocol anchor: reference
examples/pytorch_synthetic_benchmark.py:79-110.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run_bench(*args, timeout=600, extra_env=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    env["HVD_TPU_FORCE_CPU"] = "1"
    env.update(extra_env or {})
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench.py"), *args],
        env=env, cwd=str(REPO), capture_output=True, text=True,
        timeout=timeout)
    assert proc.returncode == 0, (
        f"bench rc={proc.returncode}\nstdout: {proc.stdout[-2000:]}\n"
        f"stderr: {proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc


def test_default_lane_contract():
    """The exact invocation the driver records (tiny sizes for CI)."""
    out, _ = _run_bench("--batch-size", "2", "--image-size", "64",
                        "--num-warmup-batches", "1",
                        "--num-batches-per-iter", "2", "--num-iters", "2")
    assert out["metric"] == "resnet50_img_per_sec_per_chip"
    assert out["unit"] == "img/sec/chip"
    assert out["value"] > 0
    assert out["vs_baseline"] > 0
    assert out["probe_tflops"] > 0


@pytest.mark.parametrize("flags", [
    pytest.param((), id="dense-default"),
    pytest.param(("--fused-ce", "--scan-layers", "--remat"), id="r3-flags"),
])
def test_lm_lane_contract(flags):
    """Long-context lane: tokens/sec with vs_baseline null. Both the
    dense default path (the lane the headline numbers come from) and
    the round-3 perf flags (--fused-ce --scan-layers --remat)
    are driven end-to-end so a regression in either path's arg wiring
    or JSON contract is caught."""
    out, proc = _run_bench(
        "--model", "transformer_lm", "--batch-size", "2",
        "--seq-len", "128", "--vocab", "512", "--lm-layers", "2",
        "--lm-dim", "64", "--lm-heads", "4", *flags,
        "--num-warmup-batches", "1", "--num-batches-per-iter", "2",
        "--num-iters", "2")
    assert out["metric"] == "transformer_lm_tokens_per_sec_per_chip"
    assert out["unit"] == "tokens/sec/chip"
    assert out["value"] > 0
    assert out["vs_baseline"] is None
    assert "tokens/sec" in proc.stderr


def test_lm_flash_attention_lane():
    """--flash-attention swaps the Pallas kernel into the LM lane (the
    flash-vs-dense A/B surface); same contract, interpret mode on CPU.
    The record now also stamps the resolved attention implementation."""
    out, _ = _run_bench(
        "--model", "transformer_lm", "--flash-attention",
        "--batch-size", "2", "--seq-len", "128", "--vocab", "256",
        "--lm-layers", "1", "--lm-dim", "64", "--lm-heads", "4",
        "--num-warmup-batches", "1", "--num-batches-per-iter", "1",
        "--num-iters", "1")
    assert out["metric"] == "transformer_lm_tokens_per_sec_per_chip"
    assert out["value"] > 0
    assert out["attention"] == "flash"


@pytest.mark.parametrize("flags", [(), ("--attention", "auto")],
                         ids=["unset", "auto"])
def test_lm_attention_auto_policy(flags):
    """An unset --attention is auto, and auto asks
    ops.attention.attention_plan with the lane's shapes: on the CPU test
    platform that is the dense reference (the kernels would be interpreted),
    and the record says so."""
    out, _ = _run_bench(
        "--model", "transformer_lm", *flags,
        "--batch-size", "2", "--seq-len", "128", "--vocab", "256",
        "--lm-layers", "1", "--lm-dim", "64", "--lm-heads", "4",
        "--num-warmup-batches", "1", "--num-batches-per-iter", "1",
        "--num-iters", "1")
    assert out["attention"] == "dense"
    assert out["flash_grid"] is None
    assert out["value"] > 0


def test_lm_flash_grid_stamp_and_full_grid_ab():
    """Flash records carry the causal-grid accounting (blocks, step
    counts, K/V bytes) and the backward the policy names, and
    --flash-full-grid / --flash-bwd pin the full grid and the other
    backward — the A/B pairs tools/hw_sweep.py queues. seq 384 tiles
    as a 3x3 grid of 128-wide blocks, so the packed walk is 6 of 9 steps."""
    from horovod_tpu.ops.attention import FLASH_BWD

    common = ("--model", "transformer_lm", "--batch-size", "2",
              "--seq-len", "384", "--vocab", "256", "--lm-layers", "1",
              "--lm-dim", "64", "--lm-heads", "4",
              "--num-warmup-batches", "1", "--num-batches-per-iter", "1",
              "--num-iters", "1")
    out, _ = _run_bench("--attention", "flash", *common)
    g = out["flash_grid"]
    assert out["attention"] == "flash" and g["truncated"]
    assert (g["block_q"], g["block_k"]) == (128, 128)
    assert (g["steps"], g["steps_full"]) == (6, 9)
    assert g["kv_bytes"] * 3 == g["kv_bytes_full"] * 2
    assert g["bwd"] == FLASH_BWD == "pallas"
    out_full, _ = _run_bench("--attention", "flash", "--flash-full-grid",
                             "--flash-bwd", "scan", *common)
    g_full = out_full["flash_grid"]
    assert not g_full["truncated"]
    assert g_full["steps"] == g_full["steps_full"] == 9
    assert g_full["bwd"] == "scan"  # the A/B lanes' pinned backward


def test_overlap_and_bucket_stamps_in_record():
    """--overlap stamps the knob AND the fused bucket plan (count / MB /
    oversize singletons — the same accounting tools/scaling_model.py
    consumes) into the JSON record, so the hw_sweep overlap A/B rows
    carry their dispatch-shape evidence; --d-model is the documented
    alias for --lm-dim (the GPT-2-medium lane spelling)."""
    out, _ = _run_bench(
        "--model", "transformer_lm", "--overlap", "on",
        "--batch-size", "2", "--seq-len", "64", "--vocab", "256",
        "--lm-layers", "1", "--d-model", "32", "--lm-heads", "2",
        "--num-warmup-batches", "1", "--num-batches-per-iter", "1",
        "--num-iters", "1")
    assert out["overlap"] == "on"
    b = out["buckets"]
    assert b["count"] >= 1 and b["total_bytes"] > 0
    assert {"total_mb", "oversize_singletons", "largest_bytes"} <= set(b)
    assert out["value"] > 0
    # The static collective audit (tools/hvdverify) rides every record:
    # the step program's reduce traffic must carry at least the bucket
    # plan's bytes (scalar metric psums ride on top), with per-kind
    # counts for the perf_summary column.
    c = out["collectives"]
    assert c["count"] >= b["count"]
    assert c["bytes"] >= b["total_bytes"]
    assert c["by_kind"] and sum(c["by_kind"].values()) == c["count"]


def test_wire_leaves_mirror_fused_reduce_compression():
    """The wire stamp's plan must be built over the SAME leaves
    fused_reduce buckets: cast compressors (bf16/fp16) halve floating
    leaves before planning; none/int8/fp8 plan the raw tree (their
    compress() is identity at bucketing time)."""
    import jax
    import jax.numpy as jnp

    from bench import wire_leaves
    from horovod_tpu.jax.compression import Compression

    leaves = [jax.ShapeDtypeStruct((64,), jnp.float32),
              jax.ShapeDtypeStruct((8,), jnp.int32)]
    for comp in (Compression.none, Compression.int8, Compression.fp8):
        assert wire_leaves(leaves, comp) is leaves
    cast = wire_leaves(leaves, Compression.bf16)
    assert cast[0].dtype == jnp.bfloat16 and cast[0].shape == (64,)
    assert cast[1].dtype == jnp.int32  # non-floating leaves untouched


def test_hierarchical_wire_stamp_in_record():
    """--hierarchical on + --compression int8 stamps the resolved ladder
    knob (mode/inner) and the per-leg wire split (ICI vs DCN operand
    bytes, DCN dtype, compression ratio) into the record — the evidence
    the hw_sweep hier/int8 A/B rows and the scaling-model predictions
    are reconciled against. The int8 error-feedback residuals ride the
    optimizer state (sharded specs), so the timed step is the REAL
    quantized exchange, not a stampede of stamps over a flat run."""
    out, _ = _run_bench(
        "--model", "transformer_lm", "--hierarchical", "on",
        "--compression", "int8",
        "--batch-size", "2", "--seq-len", "64", "--vocab", "256",
        "--lm-layers", "1", "--d-model", "32", "--lm-heads", "2",
        "--num-warmup-batches", "1", "--num-batches-per-iter", "1",
        "--num-iters", "1",
        extra_env={"HOROVOD_HIERARCHICAL_INNER_SIZE": "4"})
    assert out["hierarchical"] == {"mode": "on", "inner": 4}
    w = out["wire"]
    assert w["dtype"] == "int8" and w["ratio"] > 2.5
    assert 0 < w["dcn_bytes"] < w["ici_bytes"]
    assert {"ici_mb", "dcn_mb"} <= set(w)
    assert out["value"] > 0
    # The static audit sees the ladder: scatter + gather traffic, and
    # strictly less reduce payload than a flat psum would carry.
    c = out["collectives"]
    assert c["by_kind"].get("all_to_all") or c["by_kind"].get(
        "all_gather"), c
    # Ladder off (default auto on a single-slice mesh): stamp says so.
    out2, _ = _run_bench(
        "--model", "transformer_lm",
        "--batch-size", "2", "--seq-len", "64", "--vocab", "256",
        "--lm-layers", "1", "--d-model", "32", "--lm-heads", "2",
        "--num-warmup-batches", "1", "--num-batches-per-iter", "1",
        "--num-iters", "1")
    assert out2["hierarchical"]["inner"] == 0
    assert out2["wire"] is None


def test_snapshot_stamp_in_record():
    """--snapshot-every K measures the elastic host-RAM snapshot cost
    and stamps cadence / ms-per-snapshot / overhead%% into the record
    (ISSUE acceptance: overhead <= 2%% of step time at the default
    cadence of 100). The tiny-LM CPU lane has millisecond steps against
    a sub-millisecond state copy, so the budget holds here too."""
    out, _ = _run_bench(
        "--model", "transformer_lm", "--snapshot-every", "100",
        "--batch-size", "2", "--seq-len", "64", "--vocab", "256",
        "--lm-layers", "1", "--lm-dim", "32", "--lm-heads", "2",
        "--num-warmup-batches", "1", "--num-batches-per-iter", "1",
        "--num-iters", "1")
    s = out["snapshot"]
    assert s["every"] == 100
    assert s["ms_per_snapshot"] > 0
    assert 0 < s["overhead_pct"] <= 2.0
    assert out["value"] > 0
    # Off by default: the historical record shape gains an explicit null.
    out_off, _ = _run_bench(
        "--model", "transformer_lm", "--batch-size", "2",
        "--seq-len", "64", "--vocab", "256", "--lm-layers", "1",
        "--lm-dim", "32", "--lm-heads", "2",
        "--num-warmup-batches", "1", "--num-batches-per-iter", "1",
        "--num-iters", "1")
    assert out_off["snapshot"] is None


def test_mesh_flag_canonicalizes_and_rejects_invalid():
    """--mesh is parsed through the logical-axis vocabulary at argparse
    time: any axis order canonicalizes to the registry's spelling
    ('tp=4,dp=8' and 'dp=8,tp=4' stamp identically), an invalid config
    is a usage error (exit 2) rather than a mid-run crash, and the perf_summary mesh column renders the
    stamp (em-dash for unconfigured/pre-registry records)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_mesh_mod", REPO / "bench.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    parser = bench.build_parser()
    assert parser.parse_args(["--mesh", "tp=4,dp=8"]).mesh == "dp=8,tp=4"
    assert parser.parse_args([]).mesh is None
    with pytest.raises(SystemExit):
        parser.parse_args(["--mesh", "dp=banana"])

    from tools.perf_summary import mesh_cell

    assert mesh_cell({"mesh": "dp=8,tp=4"}) == "dp=8,tp=4"
    assert mesh_cell({"mesh": None}) == "—"
    assert mesh_cell({}) == "—"


def test_mesh_stamp_in_record():
    """--mesh stamps the canonical config into the JSON record, beside
    the device JAX reported."""
    out, _ = _run_bench(
        "--model", "transformer_lm", "--mesh", "tp=2,dp=4",
        "--batch-size", "2", "--seq-len", "64", "--vocab", "256",
        "--lm-layers", "1", "--lm-dim", "32", "--lm-heads", "2",
        "--num-warmup-batches", "1", "--num-batches-per-iter", "1",
        "--num-iters", "1")
    assert out["mesh"] == "dp=4,tp=2"
    assert out["value"] > 0
    assert out["device"] == {"platform": "cpu", "device_kind": "cpu",
                             "count": 8}


def test_compile_only_lane_contract():
    """--compile-only (the sweep's *_warm lanes): one first step, metric
    <model>_first_step_secs, vs_baseline null — the warm-cache pass big
    models run before their measured lane."""
    out, _ = _run_bench(
        "--model", "transformer_lm", "--compile-only",
        "--batch-size", "2", "--seq-len", "64", "--vocab", "256",
        "--lm-layers", "1", "--lm-dim", "32", "--lm-heads", "2")
    assert out["metric"] == "transformer_lm_first_step_secs"
    assert out["unit"] == "secs"
    assert out["value"] > 0
    assert out["vs_baseline"] is None


def test_zero_composes_with_lm_lane():
    out, _ = _run_bench(
        "--model", "transformer_lm", "--zero", "--batch-size", "2",
        "--seq-len", "64", "--vocab", "256", "--lm-layers", "1",
        "--lm-dim", "32", "--lm-heads", "2",
        "--num-warmup-batches", "1", "--num-batches-per-iter", "1",
        "--num-iters", "1")
    assert out["value"] > 0
