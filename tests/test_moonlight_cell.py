"""The cell ``moonlight_seq8192_1chip`` as the benchmark finds it: the
manifest and the configuration's file against the rules and the catalog's
numbers, ``flops_mla.py`` against hand-worked figures, the two new readers on
made-up records, and the rehearsal: the configuration at a toy size, its cell
and its metrics added to a copy of ``benchmarks/`` as new files only and run
end to end on the CPU through ``benchmarks/run.py``, then the three faults of
the mechanism planted in the reference and read false."""

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
from benchmarks import check_manifest, flops_mla, flops_moe, plant, run  # noqa: E402
from horovod_tpu.parallel import moe  # noqa: E402

CELL = "moonlight_seq8192_1chip"
NEW_METRICS = ["mla_attn_roofline_pct.tok", "mla_expanded_kv_mib_per_step.tok"]
SHARED_METRICS = ["flash_ms_per_step.tok", "moe_row_bound_ratio.tok",
                  "moe_gmm_ms_per_step.tok", "moe_gmm_roofline_pct.tok",
                  "recomputed_applications_per_step.tok"]
# Moonlight-16B-A3B's published config.json, as the model-configs catalog
# holds it: every number has to stand in the file unchanged unless `reduced`
# names its key
PUBLISHED = {
    "ep_size": 1, "first_k_dense_replace": 1, "hidden_size": 2048,
    "intermediate_size": 11264, "kv_lora_rank": 512,
    "max_position_embeddings": 8192, "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "num_attention_heads": 16,
    "num_experts_per_tok": 6, "num_hidden_layers": 27,
    "num_key_value_heads": 16, "num_nextn_predict_layers": 0,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 50000, "routed_scaling_factor": 2.446, "topk_group": 1,
    "v_head_dim": 128, "vocab_size": 163840}
NOT_NUMBERS = {"attention_bias": False, "hidden_act": "silu",
               "model_type": "deepseek_v3", "norm_topk_prob": True,
               "q_lora_rank": None, "scoring_func": "sigmoid",
               "seq_aux": True, "tie_word_embeddings": False,
               "topk_method": "noaux_tc"}


def _config():
    return run.load_json(REPO, "benchmarks", "configs",
                         "moonlight-16b-a3b.json")


def test_manifest_is_well_formed_and_names_the_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        text = f.read()
    manifest = json.loads(text)
    assert check_manifest.check(manifest, REPO, len(text.encode())) == []
    assert len(manifest["workloads"]) >= 6
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    manifest, cell, config = run.load_cell(CELL)
    assert cell["chips"] == 1 and cell["bench_args"] == [
        "--batch-size", "2", "--seq-len", "8192", "--remat"]
    assert (cell["steps_in_flight"], cell["compare_steps"],
            cell["trace_steps"], cell["reference_rows_per_block"]) \
        == (2, 3, 6, 1)
    reported = {m["name"] for m in run.metrics_of(manifest, CELL,
                                                  "per_layer")}
    assert set(NEW_METRICS) | set(SHARED_METRICS) <= reported
    assert {"step_mfu_pct.tok", "device_step_ms.tok", "peak_hbm_gib.tok",
            "device_idle_pct.tok", "setup_lane_build_s"} <= reported
    # its work function has one width; Ouro's and the exchange's are not here
    assert not {"flash_roofline_pct.tok", "collective_ms_per_step.tok",
                "loop_applications_per_step.tok"} & reported
    assert {m["name"] for m in run.metrics_of(manifest, CELL, "end_to_end")} \
        == {"tok_per_s_per_chip", "setup_s"}
    for name in reported:
        assert callable(run.load_reader(name))


def test_configuration_keeps_every_published_width():
    config = _config()
    reduced = set(config["reduced"])
    assert reduced == {"num_layers", "n_routed_experts", "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    for key, value in NOT_NUMBERS.items():
        assert config[key] == value and type(config[key]) is type(value), key
    assert config["published"]["num_hidden_layers"] == 27
    assert (config["num_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (6, 8, 20480)
    assert config["deployment"]["chips_sharing_a_layer"] == 8
    assert config["deployment"]["experts_held"] == [0, 7]
    assert config["deployment"]["parameters"]["all"] == 668_890_112
    assert {"rope_pairing", "embedding", "bias_rule", "aux_loss",
            "optimizer"} <= set(config["assumed"])
    assert any("Muon" in line for line in config["departures"])
    # the lane's arguments, the reference's hyper and the operation count
    # say the same sizes, and those are the file's
    args = dict(zip(config["bench_args"][::2], config["bench_args"][1::2]))
    hyper, flops = config["reference"]["hyper"], config["flops"]["args"]
    assert int(args["--lm-dim"]) == config["hidden_size"] == flops["d_model"]
    assert int(args["--lm-heads"]) == config["num_attention_heads"] \
        == hyper["heads"] == flops["heads"]
    assert int(args["--lm-head-dim"]) == config["qk_nope_head_dim"] \
        == hyper["nope_dim"] == flops["nope_dim"]
    assert int(args["--lm-rope-dim"]) == config["qk_rope_head_dim"] \
        == hyper["rope_dim"] == flops["rope_dim"]
    assert int(args["--lm-value-dim"]) == config["v_head_dim"] \
        == hyper["value_dim"] == flops["value_dim"]
    assert int(args["--lm-latent-dim"]) == config["kv_lora_rank"] \
        == hyper["latent_dim"] == flops["latent_dim"]
    assert float(args["--lm-rope-base"]) == config["rope_theta"] \
        == hyper["rope_theta"]
    assert int(args["--lm-ffn"]) == config["intermediate_size"] \
        == flops["dense_width"]
    assert int(args["--moe-width"]) == config["moe_intermediate_size"] \
        == flops["expert_width"]
    assert int(args["--moe-experts"]) == hyper["experts"] \
        == flops["experts"] == 64
    assert int(args["--moe-experts-held"]) == config["n_routed_experts"] \
        == flops["experts_held"]
    assert int(args["--moe-top-k"]) == config["num_experts_per_tok"] \
        == hyper["top_k"] == flops["top_k"]
    assert int(args["--moe-shared"]) == config["n_shared_experts"] \
        == flops["shared_experts"]
    assert float(args["--moe-route-scale"]) == hyper["route_scale"] \
        == config["routed_scaling_factor"]
    assert int(args["--lm-layers"]) == config["num_layers"] \
        == hyper["layers"] == len(flops["layer_types"])
    assert int(args["--vocab"]) == config["vocab_size"] \
        == config["int_ranges"]["tokens"] == flops["vocab"]
    assert "--no-lm-output-norms" in config["bench_args"] \
        and "--no-lm-embed-scale" in config["bench_args"]
    assert hyper["score_width"] == 192 and hyper["rotate_key"] is True \
        and hyper["latent_norm"] is True and hyper["embed_scale"] is False


def test_operation_counts_are_the_hand_worked_ones():
    args = _config()["flops"]["args"]
    assert flops_mla.matmul_params_per_token(**args) == 313_327_616
    assert flops_mla.matmul_params_per_token(
        **dict(args, layer_types=["latent_attention"], dense_layers=1,
               dense_width=0, vocab=0)) == 13_762_560
    assert flops_moe.keys_seen(8192) == 4096.5
    assert flops_mla.attention_macs_per_token(**args, seq_len=8192) \
        == 6 * 20_974_080
    assert flops_mla.per_token(**args, seq_len=8192) == 2_635_032_576
    ops, nbytes = flops_mla.attn_work(tokens_per_step=16384, seq_len=8192,
                                      **args)
    assert ops == 2304 * 16 * 4096.5 * 16384 * 6
    assert ops / 197e12 == pytest.approx(75.4e-3, rel=1e-3)
    assert nbytes == 6 * 16384 * 16 * 2 * (6 * 192 + 6 * 128)
    # the expert layers' readers take their sizes from the same arguments
    ops, nbytes = flops_moe.gmm_work(tokens_per_step=16384, **args)
    # 5 layers x 9 products x 2 x 12,288 rows x 2,048 x 1,408
    assert ops == 5 * 9 * 2 * 12288 * 2048 * 1408
    assert nbytes == 5 * 9 * 2 * (12288 * 1408 + 12288 * 2048
                                  + 8 * 2048 * 1408)


def _record(device_ops, steps=6):
    return {"trace": {"steps": steps, "device_ops": device_ops},
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "window": {"units_per_step_per_chip": 16384},
            "config": _config(), "cell": {"flops_args": {"seq_len": 8192}}}


def test_readers_on_made_up_records():
    read = {name: run.load_reader(name)
            for name in NEW_METRICS + SHARED_METRICS}
    ops = [["fusion bf16[2,8192,2048]", 0.5], ["ragged-dot-none", 0.36],
           ["hvd_flash_fwd", 0.48], ["hvd_flash_dkv", 0.54],
           ["hvd_flash_dq", 0.42]]
    record = _record(ops)
    assert read["flash_ms_per_step.tok"](record) == pytest.approx(240.0)
    # 14.845 TFLOP over 197 TFLOP/s is 75.355 ms
    assert read["mla_attn_roofline_pct.tok"](record) == pytest.approx(
        100 * 75.3551 / 240.0, rel=1e-4)
    assert read["moe_gmm_ms_per_step.tok"](record) == pytest.approx(60.0)
    # 3.189 TFLOP over 197 TFLOP/s is 16.187 ms
    assert read["moe_gmm_roofline_pct.tok"](record) == pytest.approx(
        100 * 16.1867 / 60.0, rel=1e-4)
    # a family outside the ten largest: nothing to read, and no error
    missing = _record([op for op in ops if op[0] != "hvd_flash_dq"])
    assert read["mla_attn_roofline_pct.tok"](missing) is None
    assert read["flash_ms_per_step.tok"](missing) is None
    for name in ("mla_attn_roofline_pct.tok", "flash_ms_per_step.tok",
                 "moe_gmm_ms_per_step.tok"):
        assert read[name](dict(record, trace=None)) is None
    assert read["mla_attn_roofline_pct.tok"](dict(record, peak=None)) is None


def test_the_gauge_reader_reads_mebibytes(monkeypatch):
    from benchmarks.metrics import program_spans

    read = run.load_reader("mla_expanded_kv_mib_per_step.tok")
    held = {"hvd.attn.latent_expanded_bytes": 6 * 16384 * (16 * 256 + 64) * 2}
    monkeypatch.setattr(program_spans, "step_gauge", held.get)
    assert read({}) == 780.0
    # a program that sets no such gauge (the parent's): nothing, no error
    monkeypatch.setattr(program_spans, "step_gauge", lambda name: None)
    assert read({}) is None


# ------------------------------------------------------------- rehearsal

TOY_ARGS = {"layer_types": ["latent_attention"] * 3, "d_model": 64,
            "heads": 4, "nope_dim": 16, "rope_dim": 8, "value_dim": 16,
            "latent_dim": 32, "dense_layers": 1, "dense_width": 96,
            "experts": 8, "experts_held": 4, "top_k": 3, "expert_width": 32,
            "shared_experts": 2, "vocab": 128}


def _toy_tree(root):
    """A copy of ``benchmarks/`` plus the configuration at a toy size, its
    cell and the manifest's new entries retargeted to it: new files only."""
    config = copy.deepcopy(_config())
    swap = {"--lm-layers": "3", "--lm-dim": "64", "--lm-heads": "4",
            "--lm-head-dim": "16", "--lm-rope-dim": "8",
            "--lm-value-dim": "16", "--lm-latent-dim": "32",
            "--lm-layer-types": "latent,latent,latent", "--lm-ffn": "96",
            "--moe-experts": "8", "--moe-experts-held": "4",
            "--moe-first-expert": "4", "--moe-top-k": "3",
            "--moe-width": "32", "--vocab": "128"}
    args = config["bench_args"]
    config["bench_args"] = [swap.get(args[i - 1], a) if i else a
                            for i, a in enumerate(args)]
    config["draws"] = {"experts_gate": {"mean": 0.0, "std": 0.125},
                       "experts_up": {"mean": 0.0, "std": 0.125},
                       "experts_down": {"mean": 0.0, "std": 0.177},
                       "embed/embedding": {"mean": 0.0, "std": 1.0}}
    config["int_ranges"] = {"tokens": 128}
    config["reference"]["hyper"].update(
        heads=4, nope_dim=16, rope_dim=8, value_dim=16, latent_dim=32,
        layers=3, experts=8, first_expert=4, top_k=3, score_width=24)
    config["flops"]["args"] = TOY_ARGS
    cell = run.load_json(REPO, "benchmarks", "workloads", CELL + ".json")
    cell.update(config="toy_moonlight", traffic="toy_1", trace_steps=4,
                bench_args=["--batch-size", "2", "--seq-len", "32",
                            "--remat"], flops_args={"seq_len": 32},
                limits={"loss1_gap": 0.03, "loss2_gap": 0.03,
                        "loss3_gap": 0.03, "grad_median_gap": 0.03,
                        "delta_median_gap": 0.03})
    toy_cell.add_toy_cell(root, "toy_moonlight", config, cell,
                          NEW_METRICS + SHARED_METRICS)
    return config, cell


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_end_to_end_at_a_toy_size(tmp_path, trace):
    root = str(tmp_path)
    _toy_tree(root)
    result, err = toy_cell.drive_toy_cell(root, "toy_moonlight_1chip",
                                          trace=trace, seed=2 ** 31 + 17)
    assert result["correct"], err[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["compared"]) >= {"loss1_gap", "grad_median_gap",
                                       "delta_median_gap",
                                       "compiles_in_window"}
    if trace:
        # program counters read on the CPU too; a device trace does not.
        # 2 x 32 tokens, 3 latent layers, a head's own key and value of 16
        # and one rope key of 8 a token, bfloat16
        assert result["metrics"]["mla_expanded_kv_mib_per_step.tok"][
            "value"] == 3 * 64 * (4 * 32 + 8) * 2 / 2 ** 20
        # 2 x 32 tokens x 3 choices x 4 of 8 experts held: 96 rows expected
        assert result["metrics"]["moe_row_bound_ratio.tok"]["value"] \
            == moe.buffer_sizes(192, 96.0)[0] / 96.0
        # the CPU reports no memory limit: all three blocks are recomputed
        assert result["metrics"][
            "recomputed_applications_per_step.tok"]["value"] == 3
        assert not {"mla_attn_roofline_pct.tok", "flash_ms_per_step.tok",
                    "moe_gmm_ms_per_step.tok", "moe_gmm_roofline_pct.tok",
                    "step_mfu_pct.tok"} & set(result["metrics"])
    else:
        assert set(result["metrics"]) == {"tok_per_s_per_chip", "setup_s"}


@pytest.fixture(scope="module")
def program(hvd, tmp_path_factory):
    """The toy configuration's lane as ``run.py`` builds it, float32."""
    config, cell = _toy_tree(str(tmp_path_factory.mktemp("toy")))
    config["bench_args"] += ["--fp32", "--attention", "dense"]
    return run.Program(config, dict(cell, chips=hvd.size(), name="toy"))


@pytest.mark.parametrize("name, setting, planted", [
    ("key_unrotated", "rotate_key=false", ("rotate_key", False)),
    ("latent_norm_left_out", "latent_norm=false", ("latent_norm", False)),
    ("scaled_by_value_width", "score_width=16", ("score_width", 16))])
def test_each_planted_fault_reads_false(program, name, setting, planted):
    """``benchmarks/plant.py`` on the toy lane: the reference with one of the
    mechanism's three faults in its ``hyper`` put in the program's place
    reads ``correct`` false under limits that the float32 program passes ten
    times over (it reads under 2e-5)."""
    import jax

    plants = plant.parse_plants([f"{name}:{setting}"])
    assert plants == {name: planted}
    program.cell = dict(program.cell, limits={
        "loss1_gap": 2e-4, "loss3_gap": 2e-4, "grad_gap": 2e-4,
        "delta_gap": 2e-3})
    line, = plant.planted(program, jax.devices()[0], [5], plants)
    assert line["kind"] == "fault_" + name and line["correct"] is False
    assert {"loss1_gap", "grad_gap"} & set(line["over"]), line
    hyper = program.config["reference"]["hyper"]
    assert (hyper["rotate_key"], hyper["latent_norm"],
            hyper["score_width"]) == (True, True, 24)
