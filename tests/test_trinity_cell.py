"""The cell ``trinity_mini_seq4096_1chip`` as the benchmark finds it: the
manifest and the configuration's file against the rules and the catalog's
numbers, ``flops_moe.py`` against hand-worked figures, the five new readers on
made-up records, and the rehearsal: the configuration at a toy size, its cell
and its metrics added to a copy of ``benchmarks/`` as new files only and run
end to end on the CPU through ``benchmarks/run.py``."""

import copy
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (REPO, os.path.dirname(os.path.abspath(__file__))):
    if path not in sys.path:
        sys.path.insert(0, path)

import toy_cell  # noqa: E402
from benchmarks import check_manifest, flops_moe, run  # noqa: E402
from horovod_tpu.parallel import moe  # noqa: E402

CELL = "trinity_mini_seq4096_1chip"
NEW_METRICS = ["moe_row_bound_ratio.tok", "moe_gmm_ms_per_step.tok",
               "moe_gmm_roofline_pct.tok", "flash_ms_per_step.tok",
               "flash_roofline_pct.tok"]
RECOMPUTED = "recomputed_applications_per_step.tok"     # PR 33, Ouro's too
# Trinity-Mini's published config.json, as the model-configs catalog holds
# it: every number has to stand in the file unchanged unless `reduced` names
# its key
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_size": 2048,
    "intermediate_size": 6144, "load_balance_coeff": 0.001,
    "max_position_embeddings": 131072, "moe_intermediate_size": 1024,
    "n_group": 1, "num_attention_heads": 32, "num_dense_layers": 2,
    "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_key_value_heads": 4,
    "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "route_scale": 2.826, "sliding_window": 2048,
    "topk_group": 1, "vocab_size": 200192}


def _config():
    return run.load_json(REPO, "benchmarks", "configs", "trinity-mini.json")


def test_manifest_is_well_formed_and_names_the_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        text = f.read()
    manifest = json.loads(text)
    assert check_manifest.check(manifest, REPO, len(text.encode())) == []
    manifest, cell, config = run.load_cell(CELL)
    assert cell["chips"] == 1 and cell["bench_args"] == [
        "--batch-size", "2", "--seq-len", "4096", "--remat"]
    reported = {m["name"] for m in run.metrics_of(manifest, CELL,
                                                  "per_layer")}
    assert set(NEW_METRICS) | {RECOMPUTED} <= reported
    assert {"step_mfu_pct.tok", "device_step_ms.tok", "peak_hbm_gib.tok",
            "setup_lane_build_s"} <= reported
    assert "collective_ms_per_step.tok" not in reported
    for name in reported:
        assert callable(run.load_reader(name))


def test_configuration_keeps_every_published_width():
    config = _config()
    reduced = set(config["reduced"])
    assert reduced == {"num_layers", "num_dense_layers", "num_experts",
                       "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert config["published"]["num_hidden_layers"] == 32
    assert len(config["layer_types"]) == 32
    assert config["layer_types_held"] == ["sliding_attention"] * 4 \
        + ["full_attention"]
    assert config["deployment"]["chips_sharing_a_layer"] == 8
    # the lane's arguments, the reference's hyper and the operation count
    # say the same sizes
    args = dict(zip(config["bench_args"][::2], config["bench_args"][1::2]))
    hyper, flops = config["reference"]["hyper"], config["flops"]["args"]
    assert int(args["--lm-dim"]) == config["hidden_size"] == flops["d_model"]
    assert int(args["--moe-experts"]) == hyper["experts"] == 128
    assert int(args["--moe-experts-held"]) == config["num_experts"] == 16
    assert int(args["--moe-top-k"]) == hyper["top_k"] == 8
    assert int(args["--vocab"]) == config["vocab_size"] \
        == config["int_ranges"]["tokens"] == flops["vocab"]
    assert int(args["--lm-window"]) == hyper["sliding_window"] == 2048


def test_operation_counts_are_the_hand_worked_ones():
    args = _config()["flops"]["args"]
    assert flops_moe.matmul_params_per_token(**args) == 276_692_992
    assert flops_moe.keys_seen(4096, 2048) == 1536.25
    assert flops_moe.keys_seen(4096) == 2048.5
    assert flops_moe.attention_macs_per_token(**args, seq_len=4096) \
        == 67_121_152
    assert flops_moe.per_token(**args, seq_len=4096) == 2_062_884_864
    ops, nbytes = flops_moe.gmm_work(tokens_per_step=8192, **args)
    # 4 layers x 9 products x 2 x 8,192 rows x 2,048 x 1,024
    assert ops == 4 * 9 * 2 * 8192 * 2048 * 1024
    assert nbytes == 4 * 9 * 2 * (8192 * 1024 + 8192 * 2048
                                  + 16 * 2048 * 1024)
    ops, nbytes = flops_moe.flash_work(tokens_per_step=8192, seq_len=4096,
                                       **args)
    assert ops == 7 * 2 * 128 * 32 * 8192 * (4 * 1536.25 + 2048.5)
    assert nbytes == 5 * 8192 * 128 * 2 * (6 * 32 + 6 * 4)


def _record(device_ops, steps=12):
    return {"trace": {"steps": steps, "device_ops": device_ops},
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "window": {"units_per_step_per_chip": 8192},
            "config": _config(), "cell": {"flops_args": {"seq_len": 4096}}}


def test_kernel_readers_on_made_up_records():
    read = {name: run.load_reader(name) for name in NEW_METRICS}
    ops = [["fusion f32[8192,25024]", 0.5], ["ragged-dot-none", 0.24],
           ["hvd_flash_fwd", 0.36], ["hvd_flash_dkv", 0.30],
           ["hvd_flash_dq", 0.24], ["ragged-dot-metadata", 0.012]]
    record = _record(ops)
    assert read["moe_gmm_ms_per_step.tok"](record) == pytest.approx(21.0)
    assert read["flash_ms_per_step.tok"](record) == pytest.approx(75.0)
    # 1.237 TFLOP over 197 TFLOP/s is 6.279 ms; 3.849 over 197 is 19.538
    assert read["moe_gmm_roofline_pct.tok"](record) == pytest.approx(
        100 * 6.27894 / 21.0, rel=1e-4)
    assert read["flash_roofline_pct.tok"](record) == pytest.approx(
        100 * 19.53805 / 75.0, rel=1e-4)
    # a family outside the ten largest: nothing to read, and no error
    missing = _record([op for op in ops if op[0] != "hvd_flash_dq"])
    assert read["flash_ms_per_step.tok"](missing) is None
    assert read["flash_roofline_pct.tok"](missing) is None
    assert read["moe_gmm_ms_per_step.tok"](missing) == pytest.approx(21.0)
    untraced = dict(record, trace=None)
    for name in NEW_METRICS[1:]:
        assert read[name](untraced) is None


# ------------------------------------------------------------- rehearsal

TOY_ARGS = {"layer_types": ["sliding_attention"] * 4 + ["full_attention"],
            "d_model": 64, "heads": 4, "kv_heads": 2, "head_dim": 16,
            "window": 16, "dense_layers": 1, "dense_width": 96, "experts": 16,
            "experts_held": 4, "top_k": 3, "expert_width": 32,
            "shared_experts": 1, "vocab": 128}


def _toy_tree(root):
    """A copy of ``benchmarks/`` plus the configuration at a toy size, its
    cell and the manifest's new entries retargeted to it: new files only."""
    config = copy.deepcopy(_config())
    swap = {"--lm-dim": "64", "--lm-heads": "4", "--lm-kv-heads": "2",
            "--lm-head-dim": "16", "--lm-window": "16", "--lm-ffn": "96",
            "--moe-experts": "16", "--moe-experts-held": "4",
            "--moe-first-expert": "4", "--moe-top-k": "3",
            "--moe-width": "32", "--vocab": "128"}
    args = config["bench_args"]
    config["bench_args"] = [swap.get(args[i - 1], a) if i else a
                            for i, a in enumerate(args)]
    config["draws"] = {"experts_gate": {"mean": 0.0, "std": 0.125},
                       "experts_up": {"mean": 0.0, "std": 0.125},
                       "experts_down": {"mean": 0.0, "std": 0.177}}
    config["int_ranges"] = {"tokens": 128}
    config["reference"]["hyper"].update(
        heads=4, kv_heads=2, head_dim=16, sliding_window=16, experts=16,
        first_expert=4, top_k=3)
    config["flops"]["args"] = TOY_ARGS
    cell = run.load_json(REPO, "benchmarks", "workloads", CELL + ".json")
    cell.update(config="toy_trinity", traffic="toy_1", trace_steps=4,
                bench_args=["--batch-size", "2", "--seq-len", "32",
                            "--remat"], flops_args={"seq_len": 32},
                limits={"loss1_gap": 0.03, "loss2_gap": 0.03,
                        "loss3_gap": 0.03, "grad_median_gap": 0.03,
                        "delta_median_gap": 0.03})
    toy_cell.add_toy_cell(root, "toy_trinity", config, cell,
                          NEW_METRICS + [RECOMPUTED])


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_end_to_end_at_a_toy_size(tmp_path, trace):
    root = str(tmp_path)
    _toy_tree(root)
    result, err = toy_cell.drive_toy_cell(root, "toy_trinity_1chip",
                                          trace=trace, seed=2 ** 31 + 13)
    assert result["correct"], err[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["compared"]) >= {"loss1_gap", "grad_median_gap",
                                       "delta_median_gap",
                                       "compiles_in_window"}
    if trace:
        # a program counter reads on the CPU too; a device trace does not
        # 2 x 32 tokens x 3 choices x 4 of 16 experts held: 48 rows expected
        assert result["metrics"]["moe_row_bound_ratio.tok"]["value"] \
            == moe.buffer_sizes(192, 48.0)[0] / 48.0
        # the CPU reports no memory limit: all five blocks are recomputed
        assert result["metrics"][RECOMPUTED]["value"] == 5
        assert not {"moe_gmm_ms_per_step.tok", "flash_ms_per_step.tok",
                    "moe_gmm_roofline_pct.tok", "flash_roofline_pct.tok",
                    "step_mfu_pct.tok"} & set(result["metrics"])
    else:
        assert set(result["metrics"]) == {"tok_per_s_per_chip", "setup_s"}
