"""Static preflight of every tools/hw_sweep.py lane: arg wiring, model
registry membership, and flag applicability — so a wiring bug can never
again cost a hardware window (round 3 lost one to an import-path bug the
CPU suite had no coverage for; these checks run in milliseconds)."""

import importlib.util
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def lanes():
    return _load("hw_sweep", REPO / "tools" / "hw_sweep.py").LANES


@pytest.fixture(scope="module")
def parser():
    return _load("bench_mod", REPO / "bench.py").build_parser()


def test_every_bench_lane_parses(lanes, parser):
    for entry in lanes:
        lane, cmd = entry[0], entry[1]
        if cmd[0] != "bench.py":
            continue
        args = parser.parse_args(cmd[1:])
        assert args is not None, lane


def test_every_lane_model_exists(lanes, parser):
    from horovod_tpu import models

    for entry in lanes:
        lane, cmd = entry[0], entry[1]
        if cmd[0] != "bench.py":
            continue
        args = parser.parse_args(cmd[1:])
        if args.model == "transformer_lm":
            continue  # build_lm_lane builds its own model
        # models.build raises for unknown names; num_classes keeps the
        # constructor cheap (no params materialized at build time).
        models.build(args.model, num_classes=10)


def test_every_lane_script_exists(lanes):
    for entry in lanes:
        cmd = entry[1]
        assert (REPO / cmd[0]).exists(), cmd[0]


def test_image_only_flags_not_on_lm_lanes(lanes, parser):
    """build_image_lane rejects LM flags and vice versa at runtime; catch a
    mis-assembled lane here instead of on the chip."""
    for entry in lanes:
        lane, cmd = entry[0], entry[1]
        if cmd[0] != "bench.py":
            continue
        args = parser.parse_args(cmd[1:])
        lm_flags = (args.fused_ce or args.scan_layers or args.remat
                    or args.flash_attention or args.flash_full_grid
                    or args.attention is not None
                    or args.flash_bwd is not None)
        if args.model != "transformer_lm":
            assert not lm_flags, f"{lane}: LM flag on an image lane"
        if args.model == "transformer_lm":
            assert not args.fused_bn, f"{lane}: --fused-bn on the LM lane"
        if args.flash_full_grid:
            # The full-grid A/B lane only means something on the flash
            # path; build_lm_lane rejects the combination at runtime.
            assert (args.flash_attention or args.attention == "flash"), \
                f"{lane}: --flash-full-grid without the flash path"


def test_serve_tp_lane_geometry_divides(lanes):
    """The serve_tp_ab lane must not fail-fast on the chip: the tp
    degree it requests has to divide the default model geometry
    (heads, mlp = 4*d_model, vocab — tools/lm_common.py defaults),
    because ServeEngine raises InvalidArgumentError at construction
    otherwise. A mis-paired lane edit dies here in milliseconds."""
    entry = next(e for e in lanes if e[0] == "serve_tp_ab")
    cmd = entry[1]
    assert cmd[0] == "tools/serve_bench.py"
    assert "--ab-tp" in cmd and "--mesh" in cmd
    mesh = cmd[cmd.index("--mesh") + 1]
    axes = dict(kv.split("=") for kv in mesh.split(","))
    tp = int(axes["tp"])
    assert tp > 1, "the A/B needs a sharded side"
    heads, d_model, vocab = 12, 768, 32000  # lm_common defaults
    assert heads % tp == 0
    assert (4 * d_model) % tp == 0
    assert vocab % tp == 0
    # every non-tensor axis must be 1 (data parallelism is the
    # fleet's job — ServeConfig rejects dp>1)
    assert all(int(v) == 1 for k, v in axes.items() if k != "tp")


def test_parser_builds_without_backend_init(parser):
    """build_parser must not initialize a backend (a parent that touches
    JAX holds the chip): bench.py defers its jax import into the bench
    functions, so building + using the parser alone must succeed with
    defaults intact."""
    args = parser.parse_args([])
    assert args.model == "resnet50" and args.seq_len == 2048
