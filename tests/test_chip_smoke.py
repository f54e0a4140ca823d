"""The chip gate, checked from the CPU: what must NOT run here, and the
one decision (where the compile cache lives) that is a pure function.

``chip_smoke.py`` itself passes only on a TPU (the driver runs it there
after every PR); its ``--rehearsal`` — the same phases at toy sizes with
interpreted kernels — is the slow-lane proof that the script's own code
still runs.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run(script, *args, timeout=120, **env_over):
    env = {k: v for k, v in os.environ.items()
           if k not in ("HVD_TPU_FORCE_CPU", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_over)
    return subprocess.run([sys.executable, str(REPO / script), *args],
                          env=env, cwd=str(REPO), capture_output=True,
                          text=True, timeout=timeout)


@pytest.mark.parametrize("script,args", [
    ("chip_smoke.py", ()),
    ("tools/profile_step.py", ("--steps", "2")),
])
def test_measuring_entry_points_refuse_the_cpu(script, args):
    """No TPU, no explicit CPU switch: non-zero exit naming the platform,
    before any compile, and no result line on stdout: a run that did not
    measure is a non-zero exit code, never a record."""
    proc = _run(script, *args)
    assert proc.returncode != 0
    assert "platform" in proc.stderr and "'cpu'" in proc.stderr
    assert not [line for line in proc.stdout.splitlines()
                if line.startswith("{")]


def test_compile_cache_dir_is_a_function_of_the_environment():
    from horovod_tpu.utils.compile_cache import cache_dir

    assert cache_dir({"JAX_COMPILATION_CACHE_DIR": "/x"}) is None
    assert cache_dir({}) == str(REPO / ".jax_cache")
    assert cache_dir({"JAX_COMPILATION_CACHE_DIR": ""}) == \
        str(REPO / ".jax_cache")


def test_an_installed_package_writes_no_cache_beside_itself(monkeypatch,
                                                            tmp_path):
    """No ``bench.py`` beside the package: not a checkout, so nothing is
    set in code (site-packages must not grow a ``.jax_cache``)."""
    from horovod_tpu.utils import compile_cache

    monkeypatch.setattr(compile_cache, "_CHECKOUT", str(tmp_path))
    assert compile_cache.cache_dir({}) is None
    assert compile_cache.cache_dir({"JAX_COMPILATION_CACHE_DIR": "/x"}) \
        is None


def test_launcher_refuses_local_ranks_that_would_share_chips(monkeypatch):
    """A chip belongs to one process: N > 1 local ranks on a chip host are
    refused at launch with the reason, never left to hang in libtpu."""
    from horovod_tpu import run

    monkeypatch.setattr(run, "_local_tpu_chips", lambda: ["/dev/vfio/3"])
    two_local = [(None, 0, 2), (None, 1, 2)]
    with pytest.raises(run.LaunchError, match="one process per host") as err:
        run._refuse_shared_chips(two_local, {})
    assert "JAX_PLATFORMS=cpu" in str(err.value)     # how to opt out
    with pytest.raises(run.LaunchError):
        run._refuse_shared_chips(two_local, {"JAX_PLATFORMS": "tpu,cpu"})
    run._refuse_shared_chips(two_local, {"JAX_PLATFORMS": "cpu"})
    run._refuse_shared_chips([(None, 0, 1), ("otherhost", 0, 1)], {})
    monkeypatch.setattr(run, "_local_tpu_chips", lambda: [])
    run._refuse_shared_chips(two_local, {})


def test_a_tpu_is_told_from_other_passed_through_devices(monkeypatch,
                                                        tmp_path):
    """What the v5e host shows (vendor 0x1ae0, class 0xff0000, a VFIO
    node for its IOMMU group) — not the cloud's NIC (same vendor), not a
    GPU behind VFIO, not a chip whose group this host was not given."""
    import glob

    from horovod_tpu import run

    for addr, vendor, pci_class, group in (
            ("0000:00:04.0", "0x1ae0", "0x020000", 4),    # NIC under VFIO
            ("0000:00:0a.0", "0x1ae0", "0xff0000", 0),    # chip, no node
            ("0000:00:0b.0", "0x1ae0", "0xff0000", 3),    # chip
            ("0000:00:06.0", "0x10de", "0x030200", 6)):   # GPU under VFIO
        (tmp_path / addr).mkdir()
        (tmp_path / addr / "vendor").write_text(vendor + "\n")
        (tmp_path / addr / "class").write_text(pci_class + "\n")
        (tmp_path / addr / "iommu_group").symlink_to(
            f"../../kernel/iommu_groups/{group}")
    nodes = {"/dev/vfio/3", "/dev/vfio/4", "/dev/vfio/6"}
    exists = os.path.exists
    monkeypatch.setattr(glob, "glob", lambda pattern: (
        [str(p) for p in tmp_path.iterdir()] if "pci" in pattern else []))
    monkeypatch.setattr(os.path, "exists",
                        lambda path: path in nodes or exists(path))
    assert run._local_tpu_chips() == ["/dev/vfio/3"]


def test_fleet_refuses_local_chip_workers(monkeypatch):
    """A router that holds the host's chips cannot give them to local
    worker processes: ServeFleet raises at construction, before any
    spawn, instead of waiting out spawn_timeout."""
    import types

    import jax

    from horovod_tpu.serve import FleetConfig, ServeConfig, ServeFleet

    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.setattr(jax, "devices",
                        lambda: [types.SimpleNamespace(platform="tpu")])
    cfg = ServeConfig(page_size=8, num_pages=8, decode_slots=1,
                      prefill_chunk=4)
    for transport in ("process", "tcp"):
        with pytest.raises(RuntimeError, match="one process"):
            ServeFleet({}, cfg, FleetConfig(replicas=1, transport=transport))


# The attention kernels' names in a compiled program: the forward and the one
# backward kernel that ``attention_plan`` answers at every cell's shapes, and
# the split it answers where the resident dQ does not fit (and a pin asks for)
FLASH_KERNELS = ("hvd_flash_fwd", "hvd_flash_bwd")
SPLIT_KERNELS = ("hvd_flash_dq", "hvd_flash_dkv")


@pytest.fixture(scope="module")
def topo():
    """A v5e host of four chips, described and not attached: libtpu compiles
    for it without a chip. Made inside a fixture, so that only the worker
    that runs this file loads the library."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu in this environment
        pytest.skip(f"no TPU compiler here: {exc}")


def test_kernels_compile_for_a_tpu_from_here(topo):
    """libtpu compiles for a v5e topology without a chip: the paged
    decode kernel (whole-head blocks, 12 heads and the 3 a tp=4 shard
    holds) and the packed-grid flash forward and backward kernels (the one
    kernel the plan answers, the dQ / dK-dV split a pin or a very long query
    side gets) go through Mosaic itself, not the interpreter."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.ops.attention import attention_plan, flash_attention
    from horovod_tpu.ops.paged_attention import paged_attention_decode

    sharding = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def compiled_text(fn, *args):
        lowered = jax.jit(fn).lower(*args)
        lowered.compile()
        return lowered.as_text()

    for heads in (12, 3):
        text = compiled_text(
            lambda q, k, v, t, n: paged_attention_decode(
                q, k, v, t, n, interpret=False),
            spec((8, heads, 64)), spec((32, 16, heads, 64)),
            spec((32, 16, heads, 64)), spec((8, 4), jnp.int32),
            spec((8,), jnp.int32))
        assert "tpu_custom_call" in text

    def flash_loss(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False,
                               bwd_impl="pallas").astype(jnp.float32).sum()

    def flash_loss_planned(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=False).astype(jnp.float32).sum()

    qkv = spec((1, 512, 2, 64), jnp.bfloat16)
    text = compiled_text(jax.grad(flash_loss, argnums=(0, 1, 2)),
                         qkv, qkv, qkv)
    assert text.count("tpu_custom_call") >= 3

    # The sparse decoder's kernels at its real widths (32 query heads of
    # 128 over 4 KV heads, 4,096 tokens, the plan's blocks of 1,024 and its
    # one-kernel backward): the windowed grouped flash kernels under their
    # profile names, and the expert layer's grouped products, which XLA:TPU
    # lowers itself.
    def windowed_loss(q, k, v):
        return flash_attention(q, k, v, causal=True, window=2048,
                               interpret=False).astype(jnp.float32).sum()

    lowered = jax.jit(jax.grad(windowed_loss, argnums=(0, 1, 2))).lower(
        spec((2, 4096, 32, 128), jnp.bfloat16),
        spec((2, 4096, 4, 128), jnp.bfloat16),
        spec((2, 4096, 4, 128), jnp.bfloat16))
    text = lowered.compile().as_text()
    for kernel in FLASH_KERNELS:
        assert f"%{kernel}" in text, kernel
    for kernel in SPLIT_KERNELS:
        assert f"%{kernel}" not in text, kernel

    # The latent layer's kernels at its real widths (16 heads, keys of 192 of
    # which the last 64 are one rope key a token, values of 128, 8,192
    # tokens, the blocks the plan picks for those widths).
    def latent_loss(q, k, v, rope_key):
        return flash_attention(q, k, v, causal=True, k_shared=rope_key,
                               interpret=False).astype(jnp.float32).sum()

    lowered = jax.jit(jax.grad(latent_loss, argnums=(0, 1, 2, 3))).lower(
        spec((2, 8192, 16, 192), jnp.bfloat16),
        spec((2, 8192, 16, 128), jnp.bfloat16),
        spec((2, 8192, 16, 128), jnp.bfloat16),
        spec((2, 8192, 64), jnp.bfloat16))
    text = lowered.compile().as_text()
    for kernel in FLASH_KERNELS:
        assert f"%{kernel}" in text, kernel

    # A query side past the one-kernel backward's VMEM budget (a Ulysses
    # shard's): the plan answers the split, which compiles as before.
    long_q = spec((1, 131072, 1, 128), jnp.bfloat16)
    assert attention_plan(131072, 131072, 1, 1, 128,
                          backend="tpu").bwd == "pallas"
    text = jax.jit(jax.grad(flash_loss_planned, argnums=(0, 1, 2))).lower(
        long_q, long_q, long_q).compile().as_text()
    for kernel in ("hvd_flash_fwd",) + SPLIT_KERNELS:
        assert f"%{kernel}" in text, kernel

    from horovod_tpu.parallel import moe

    def experts_loss(x, router, experts):
        y, _ = moe.routed_experts(x, router, experts, top_k=8,
                                  dtype=jnp.bfloat16)
        return y.astype(jnp.float32).sum()

    lowered = jax.jit(jax.grad(experts_loss, argnums=(0, 2))).lower(
        spec((8192, 2048)), spec((2048, 128)),
        {"gate": spec((16, 2048, 1024)), "up": spec((16, 2048, 1024)),
         "down": spec((16, 1024, 2048))})
    assert "%ragged-dot-none" in lowered.compile().as_text()


def test_the_scan_kernels_compile_for_a_tpu_at_the_cells_widths(topo):
    """Granite's Mamba layer at its real widths (64 heads of 64, a state of
    128, one group, chunks of 256) over the cell's 16,384 tokens: the
    chunked scan's forward and backward kernels go through Mosaic itself,
    both under the one profile name, reading the conv's output ``[1,
    16,384, 4,352]`` where it lies."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.ops import ssd

    sharding = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def loss(xbc, dt, a, d):
        return ssd.ssd(xbc, dt, a, D=d, state_dim=128, impl="pallas",
                       interpret=False).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        spec((1, 16384, 4352), jnp.bfloat16), spec((1, 16384, 64)),
        spec((64,)), spec((64,))).compile().as_text()
    named = [line for line in text.splitlines()
             if "custom-call(" in line and f"%{ssd.KERNEL}." in line]
    assert len(named) == 2, named           # the forward and the backward


def test_gpt2_cell_attention_compiles_alone_and_under_four_chips(topo):
    """GPT-2-medium's attention layer as ``attention_plan`` runs it on the
    chip (the block's fused projection ``[8, 1024, 3 x 16 x 64]`` bf16, two
    heads a program, blocks of 1,024, the one-kernel backward): the two
    kernels through Mosaic on one chip, and inside a ``shard_map`` over the
    host's four chips, 8 sequences each, as the data-parallel cell runs
    them; in float32 too, whose tiles pass the one-head programs' VMEM."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.ops.attention import (FLASH_SLAB, attention_plan,
                                           flash_attention)

    plan = attention_plan(1024, 1024, 16, 16, 64, backend="tpu")
    assert plan == ("flash", 1024, 1024, "fused", 2, FLASH_SLAB)

    def grads(qkv):
        return jax.grad(lambda x: flash_attention(
            x, causal=True, heads=16,
            interpret=False).astype(jnp.float32).sum())(qkv)

    mesh = Mesh(np.array(topo.devices), ("hvd",))
    spread = jax.shard_map(grads, mesh=mesh, in_specs=P("hvd"),
                           out_specs=P("hvd"),
                           check_vma=False)         # as hvd.spmd_fn's
    for fn, batch, sharding, dtype in (
            (grads, 8, SingleDeviceSharding(topo.devices[0]), jnp.bfloat16),
            (grads, 8, SingleDeviceSharding(topo.devices[0]), jnp.float32),
            (spread, 32, NamedSharding(mesh, P("hvd")), jnp.bfloat16)):
        qkv = jax.ShapeDtypeStruct((batch, 1024, 3 * 16 * 64), dtype,
                                   sharding=sharding)
        text = jax.jit(fn).lower(qkv).compile().as_text()
        for kernel in FLASH_KERNELS:
            assert f"%{kernel}" in text, (kernel, batch)


def test_no_copy_lies_between_a_gpt2_projection_and_the_kernel(topo):
    """A GPT-2-medium block's forward pass compiled for the v5e: the forward
    kernel's q, k and v are one and the same array, the fusion that holds
    the ``qkv`` projection's product, and what it writes goes into the
    output projection's fusion: under the attention's scope the compiled
    program holds the kernel alone, no copy, transpose or slice."""
    import functools
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.models.transformer import TransformerBlock
    from horovod_tpu.ops.attention import attend
    from horovod_tpu.utils import timeline

    one_chip = SingleDeviceSharding(topo.devices[0])
    block = TransformerBlock(16, attn_fn=functools.partial(
        attend, impl="flash", interpret=False))
    x = jax.ShapeDtypeStruct((8, 1024, 1024), jnp.bfloat16, sharding=one_chip)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: block.init(
            jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype))))
    text = jax.jit(block.apply).lower(params, x).compile().as_text()
    entry = text[text.index("ENTRY"):]
    call = re.search(r"%hvd_flash_fwd[.\d]* = .*? custom-call\(([^)]*)\)",
                     entry)
    operands = [name.strip() for name in call.group(1).split(",")]
    assert len(operands) == 5 and len(set(operands[2:])) == 1, operands
    producer = re.search(
        rf"{re.escape(operands[2])} = .*? (\S+)\(.*op_name=\"([^\"]*)\"",
        entry)
    assert producer.group(1) == "fusion" and "Dense_0" in producer.group(2)
    scoped = [line for line in entry.splitlines()
              if f"/{timeline.ATTN_FULL}/" in line]
    assert scoped and all(        # the step tables are its two constants
        re.search(r" (custom-call|get-tuple-element|constant)\(", line)
        for line in scoped), scoped


TRINITY = dict(
    layer_types=("sliding_attention", "full_attention"), heads=32, kv_heads=4,
    head_dim=128, window=2048, dense_layers=1, dense_width=6144, experts=128,
    experts_held=16, top_k=8, expert_width=1024, route_scale=2.826)
MOONLIGHT = dict(
    layer_types=("latent_attention", "latent_attention"), heads=16,
    kv_heads=16, head_dim=128, rope_dim=64, value_dim=128, latent_dim=512,
    window=0, dense_layers=1, dense_width=11264, experts=64, experts_held=8,
    top_k=6, expert_width=1408, shared_experts=2, route_scale=2.446,
    norm_outputs=False, embed_scale=False)


GRANITE = dict(
    layer_types=("mamba", "mamba"), heads=32, kv_heads=8, head_dim=64,
    window=0, dense_layers=2, dense_width=8192, experts=8, experts_held=8,
    top_k=2, expert_width=1024, ssm_heads=64, ssm_head_dim=64,
    ssm_state=128, ssm_chunk=256, ssm_impl="pallas", residual_scale=0.22,
    norm_outputs=False, embed_scale=False)


@pytest.mark.parametrize("sizes, length, reckoned", [
    (TRINITY, 4096, 998_244_352),           # 0.93 GiB; read at 0.99
    (MOONLIGHT, 8192, 1_715_470_336),       # 1.60 GiB; read at 1.06
    (GRANITE, 8192, 1_637_875_712),         # 1.53 GiB; read at 1.21
], ids=["trinity", "moonlight", "granite"])
def test_a_kept_block_compiles_and_holds_what_the_plan_reckons(
        topo, sizes, length, reckoned):
    """``SparseDecoderLM`` at a cell's widths over its 2 sequences, its first
    two blocks (the dense one, an expert one): with the first recomputed and
    the last kept the forward kernel runs three times and not four, and what
    the compiler counts for keeping the first as well is what
    ``DecoderBlock.kept_bytes`` reckons for it, to the stated factors
    (Trinity-Mini's dense block reads 0.99 and an expert block 1.23;
    Moonlight's latent ones 1.06 and 1.16; Granite's Mamba block 1.21).
    The plan stands on that sum, so
    this pins it to the compiler and not to a guess."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu import models
    from horovod_tpu.ops import attention, ssd

    one_chip = SingleDeviceSharding(topo.devices[0])
    tokens = jax.ShapeDtypeStruct((2, length), jnp.int32, sharding=one_chip)

    def compiled(remat):
        model = models.build(
            "moe_lm", vocab_size=256, embed_dim=2048, attention="flash",
            remat=remat, **sizes)
        variables = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(lambda: model.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))))

        def loss(params, buffers, tokens):
            hidden = model.apply({"params": params, **buffers}, tokens,
                                 return_hidden=True)
            return jnp.mean(jnp.square(hidden))

        buffers = {k: v for k, v in variables.items() if k == "buffers"}
        program = jax.jit(jax.grad(loss)).lower(
            variables["params"], buffers, tokens).compile()
        calls = sum("custom-call(" in line and kernel in line
                    for line in program.as_text().splitlines())
        return model, program.memory_analysis().temp_size_in_bytes, calls

    # the kernels compiled by Mosaic, as on the chip: the CPU default is the
    # interpreter; a Mamba block's kernels are the scan's, whose name its
    # two backward calls share
    ssm = "ssm_heads" in sizes
    kernel = ssd.KERNEL if ssm else "hvd_flash_fwd"
    backward = 2 if ssm else 0
    real = attention.pallas_interpret, ssd.pallas_interpret
    attention.pallas_interpret = ssd.pallas_interpret = lambda: False
    try:
        model, one_kept, calls = compiled(1)
        assert calls == 3 + backward        # two forward, one run again
        _, both_kept, calls = compiled(0)
        assert calls == 2 + backward
    finally:
        attention.pallas_interpret, ssd.pallas_interpret = real
    assert model.block(0).kept_bytes(2 * length, 2048) == reckoned
    counted = both_kept - one_kept
    assert 0.9 * counted <= reckoned <= 1.3 * counted, (reckoned, counted)


NEMOTRON = dict(
    heads=32, kv_heads=2, head_dim=128, window=0, dense_layers=0,
    dense_width=1856, experts=128, experts_held=8, top_k=6,
    expert_width=1856, shared_experts=2, route_scale=2.5,
    expert_gated=False, ssm_heads=64, ssm_head_dim=64, ssm_state=128,
    ssm_groups=8, ssm_chunk=128, ssm_impl="pallas", qk_norm=False,
    attn_gate=False, embed_scale=False, layer_types=())


@pytest.mark.parametrize("pattern, reckoned, low, high", [
    ("MM", 1_126_170_624, 0.85, 1.0),       # 1.05 GiB; read at 0.91
    ("**", 729_808_896, 1.35, 1.55),        # 0.68 GiB; read at 1.45
    ("EE", 324_534_272, 0.85, 1.05),        # 0.30 GiB; read at 0.94
], ids=["mamba", "attention", "experts"])
def test_a_kept_single_branch_layer_holds_what_the_plan_reckons(
        topo, pattern, reckoned, low, high):
    """Two single-branch layers of one kind (``MixerBlock``) at
    Nemotron-3-Nano's widths over the cell's 2 sequences of 8,192: what the
    compiler counts for keeping the first as well as the last is what
    ``MixerBlock.kept_bytes`` reckons for it, to the factors read (a Mamba
    layer 0.91, an expert layer 0.94; the attention layer 1.45, its
    log-sum-exp reckoned at 128 lanes a head as the two-branch blocks'
    is, which the compiler does not hold so in this call: the plan
    recomputes more than it must there, never less); a Mamba layer's scan
    runs its forward kernel three times and not four where the first is
    recomputed."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu import models
    from horovod_tpu.ops import attention, ssd

    one_chip = SingleDeviceSharding(topo.devices[0])
    tokens = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=one_chip)

    def compiled(remat):
        model = models.build(
            "moe_lm", vocab_size=256, embed_dim=2688, attention="flash",
            remat=remat, pattern=pattern, **NEMOTRON)
        variables = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(lambda: model.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))))

        def loss(params, buffers, tokens):
            hidden = model.apply({"params": params, **buffers}, tokens,
                                 return_hidden=True)
            return jnp.mean(jnp.square(hidden))

        buffers = {k: v for k, v in variables.items() if k == "buffers"}
        program = jax.jit(jax.grad(loss)).lower(
            variables["params"], buffers, tokens).compile()
        calls = sum("custom-call(" in line and f"%{ssd.KERNEL}." in line
                    for line in program.as_text().splitlines())
        return model, program.memory_analysis().temp_size_in_bytes, calls

    real = attention.pallas_interpret, ssd.pallas_interpret
    attention.pallas_interpret = ssd.pallas_interpret = lambda: False
    try:
        model, one_kept, calls = compiled(1)
        if pattern == "MM":
            assert calls == 3 + 2           # two forward, one run again
        _, both_kept, calls = compiled(0)
        if pattern == "MM":
            assert calls == 2 + 2
    finally:
        attention.pallas_interpret, ssd.pallas_interpret = real
    assert model.block(0).kept_bytes(2 * 8192, 2688) == reckoned
    counted = both_kept - one_kept
    assert low * counted <= reckoned <= high * counted, (reckoned, counted)


def test_rehearsal_passes():
    proc = _run("chip_smoke.py", "--rehearsal", timeout=1500)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "rehearsal" in proc.stdout and ": pass" not in proc.stdout
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last == {"ok": False, "device": {"platform": "cpu",
                                            "kind": "cpu", "count": 4}}
