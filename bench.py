"""The synthetic-data training lane of every model the benchmark measures.

No program of its own: ``benchmarks/run.py`` (the benchmark; its cells'
``bench_args`` are this parser's), ``tools/profile_step.py`` and
``chip_smoke.py`` parse ``build_parser()`` and call ``build_lane``, which
picks the model, the optimizer and the step by family (``models.build``;
``models.make_train_step`` for the image families under SGD,
``models.make_lm_train_step`` for the language models under Adam), wraps the
step in one ``hvd.spmd_fn`` handle with the train state donated, and places
the state and one reusable synthetic batch over the ``hvd`` mesh. What a
lane measures is its caller's business. The name stays because ``run.py``
imports it.

``HVD_TPU_FORCE_CPU=1`` is the test switch: importing this module then holds
JAX to an 8-device virtual CPU mesh.
"""

import argparse
import dataclasses
import functools
import os
import sys
from typing import Any, Callable

if os.environ.get("HVD_TPU_FORCE_CPU"):
    # Test switch: an 8-device virtual CPU mesh, set before jax loads.
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8").strip()

LM_MODELS = ("transformer_lm", "moe_lm", "looped_lm")


@dataclasses.dataclass
class Lane:
    """One built training lane: the compiled-on-first-call step handle
    (train state donated), the state and the one reusable synthetic
    batch, both already placed over the mesh, and what a step is worth."""

    model: Any              # the flax module the step applies
    run_step: Callable      # (state, batch) -> (state, loss | metrics)
    state: Any
    batch: Any
    units_per_step: int     # images or tokens per chip per training step
    stamp: dict             # {"attention": "dense" | "flash"} for an LM


def place(tree, specs):
    """Commit ``tree`` to the hvd mesh under ``specs`` (a pytree prefix of
    PartitionSpecs) once, before the loop. Left on the default device, a
    global batch is re-scattered from device 0 inside every timed step
    and the first donation of the state is refused. Multi-process jobs
    pass host-local shards that ``spmd_fn`` assembles itself."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.common.state import global_state

    if jax.process_count() > 1:
        return tree
    mesh = global_state().mesh
    return jax.tree_util.tree_map(
        lambda spec, sub: jax.device_put(sub, NamedSharding(mesh, spec)),
        specs, tree, is_leaf=lambda x: isinstance(x, P))


def resolve_attention(args) -> str:
    """The LM lane's attention implementation, "dense" | "flash".

    ``--attention auto``, and an unset ``--attention``, ask
    ``ops.attention.attention_plan`` with the lane's shapes: the flash
    kernels where the v5e sweep found them faster (PERF.md, PR 29), the
    dense reference elsewhere and on the CPU. ``dense`` and ``flash`` pin
    one side.
    """
    if args.attention in ("dense", "flash"):
        return args.attention
    import jax.numpy as jnp

    from horovod_tpu.ops.attention import attention_plan

    shapes = grouped_heads(args)
    if "latent" in (args.lm_layer_types or ""):
        # every head its own key and value; keys wider than values
        shapes = dict(kv_heads=args.lm_heads, head_dim=(
            shapes["head_dim"] + args.lm_rope_dim, args.lm_value_dim))
    return attention_plan(
        args.seq_len, args.seq_len, args.lm_heads, **shapes,
        dtype=jnp.float32 if args.fp32 else jnp.bfloat16).impl


def resolve_remat(model, state, batch: int, length: int, fused_ce: bool):
    """The lane's model with ``--remat`` made a count: how many of its block
    applications the backward pass runs again, the fewest that fit.

    A model of ``models/decoder.py`` (one that lists its ``applications``)
    asks ``decoder.plan_recomputation`` with what the lane can see before
    anything is compiled: the train state it has just made, the step's
    shapes, the rows of float32 logits its loss holds at a time and the
    memory the device offers. A backend that reports none (the CPU) is
    answered "every application", which is the flag's old meaning;
    ``transformer_lm`` keeps that meaning everywhere (ROADMAP D18).
    """
    if not hasattr(model, "applications"):
        return model
    import jax

    from horovod_tpu import models
    from horovod_tpu.models import decoder
    from horovod_tpu.utils import device

    state_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(state))
    plan = decoder.plan_recomputation(
        model, state["params"], batch, length, state_bytes,
        models.lm_logits_rows(model, batch * length, fused_ce),
        device.memory_limit())
    return model.clone(remat=plan)


def grouped_heads(args) -> dict:
    """KV heads and the size of a head, for every family: as many KV heads
    as query heads and ``--lm-dim / --lm-heads`` where nothing else is
    said."""
    return dict(kv_heads=args.lm_kv_heads or args.lm_heads,
                head_dim=args.lm_head_dim or args.lm_dim // args.lm_heads)


def lm_model_args(args, attention: str) -> dict:
    """What ``models.build`` takes for ``--model transformer_lm``, for
    ``--model looped_lm`` (models/decoder.py: ``--lm-layers`` blocks applied
    ``--lm-loops`` times) and for ``--model moe_lm`` (models/decoder.py: the
    arguments say which experts this chip holds and how large its slice of
    the vocabulary is; the router keeps every expert's output and its top
    ``k``)."""
    if args.model == "transformer_lm":
        from horovod_tpu.ops.attention import attend

        return dict(
            num_layers=args.lm_layers, num_heads=args.lm_heads,
            embed_dim=args.lm_dim, max_len=max(args.seq_len, 2048),
            attn_fn=functools.partial(attend, impl=attention))
    if args.model == "looped_lm":
        return dict(
            embed_dim=args.lm_dim, num_layers=args.lm_layers,
            loops=args.lm_loops, heads=args.lm_heads,
            ffn_width=args.lm_ffn or 4 * args.lm_dim,
            rope_base=args.lm_rope_base, exit_beta=args.lm_exit_beta,
            attention=attention, **grouped_heads(args))
    from horovod_tpu.models import decoder

    kinds = {"sliding": decoder.SLIDING, "full": decoder.FULL,
             "latent": decoder.LATENT, "mamba": decoder.MAMBA}
    letters = {decoder.MIXER_MAMBA, decoder.MIXER_MOE,
               decoder.MIXER_ATTENTION}
    if args.lm_pattern:
        if args.lm_layer_types or len(args.lm_pattern) != args.lm_layers \
                or set(args.lm_pattern) - letters:
            raise ValueError(
                f"--lm-pattern needs {args.lm_layers} of the letters "
                f"{''.join(sorted(letters))} and no --lm-layer-types; got "
                f"{args.lm_pattern!r}")
        names = []
    else:
        names = (args.lm_layer_types.split(",") if args.lm_layer_types
                 else ["full"] * args.lm_layers)
        if len(names) != args.lm_layers or set(names) - set(kinds):
            raise ValueError(
                f"--lm-layer-types needs {args.lm_layers} of "
                f"{sorted(kinds)}, comma-separated; got "
                f"{args.lm_layer_types!r}")
    ungated = {} if args.moe_act == "swiglu" else dict(expert_gated=False)
    held = args.moe_experts_held or args.moe_experts
    if not 0 <= args.moe_first_expert <= args.moe_experts - held:
        raise ValueError(
            f"experts {args.moe_first_expert} to "
            f"{args.moe_first_expert + held - 1} are not among the "
            f"{args.moe_experts} the router scores")
    return dict(
        embed_dim=args.lm_dim,
        layer_types=tuple(kinds[k] for k in names), heads=args.lm_heads,
        window=args.lm_window, rope_base=args.lm_rope_base,
        **grouped_heads(args), dense_layers=args.lm_dense_layers,
        dense_width=args.lm_ffn or 4 * args.lm_dim,
        experts=args.moe_experts, experts_held=held,
        first_expert=args.moe_first_expert, top_k=args.moe_top_k,
        expert_width=args.moe_width, shared_experts=args.moe_shared,
        route_scale=args.moe_route_scale, attention=attention,
        embed_scale=args.lm_embed_scale, norm_outputs=args.lm_output_norms,
        rope_dim=args.lm_rope_dim, value_dim=args.lm_value_dim,
        latent_dim=args.lm_latent_dim, qk_norm=args.lm_qk_norm,
        attn_gate=args.lm_attn_gate, attn_scale=args.lm_attn_scale,
        ssm_heads=args.ssm_heads, ssm_head_dim=args.ssm_head_dim,
        ssm_state=args.ssm_state, ssm_conv=args.ssm_conv,
        ssm_chunk=args.ssm_chunk, embed_multiplier=args.lm_embed_multiplier,
        residual_scale=args.lm_residual_scale, tie_head=args.lm_tie_head,
        logit_divisor=args.lm_logit_divisor, pattern=args.lm_pattern or "",
        ssm_groups=args.ssm_groups, **ungated)


def build_lane(args, log) -> Lane:
    """Span ``hvd.lane.build``; its children are ``hvd.lane.model_init`` and
    ``hvd.lane.train_state`` (``models.create_train_state``) and
    ``hvd.lane.place``."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu.jax as hvd
    from horovod_tpu import models
    from horovod_tpu.parallel.logical import module_axis
    from horovod_tpu.utils.timeline import span

    lm = args.model in LM_MODELS
    # a flag of the other family is a mistake in the command, not a no-op
    for flag in ("fused_bn",) if lm else ("fused_ce", "remat", "attention"):
        if getattr(args, flag):
            raise ValueError(
                f"--{flag.replace('_', '-')} applies to "
                f"{'the ResNet and Inception families' if lm else LM_MODELS}"
                f" (got --model {args.model})")
    with span("hvd.lane.build", model=args.model):
        n = hvd.size()
        dtype = jnp.float32 if args.fp32 else jnp.bfloat16
        rng = jax.random.PRNGKey(42)
        if lm:
            per_chip, length = args.batch_size or 8, args.seq_len
            attention = resolve_attention(args)
            stamp = {"attention": attention}
            model = models.build(
                args.model, vocab_size=args.vocab, dtype=dtype,
                remat=args.remat, **lm_model_args(args, attention))
            sample = jnp.zeros((1, length), jnp.int32)
            base = optax.adam(1e-4)
            make_step = functools.partial(
                models.make_lm_train_step, fused_ce=args.fused_ce,
                bias_coeff=args.moe_bias_coeff)
            shapes = {"tokens": ((per_chip * n, length), args.vocab)}
            units = per_chip * length
            said = (f"{args.lm_layers}L/{args.lm_dim}d, seq {length}, "
                    f"{attention} attention")
        else:
            per_chip, size = args.batch_size or 64, args.image_size
            stamp = {}
            model = models.build(
                args.model, num_classes=1000, dtype=dtype,
                **({"fused_bn": True} if args.fused_bn else {}))
            sample = jnp.zeros((1, size, size, 3), jnp.float32)
            base = optax.sgd(0.01, momentum=0.9)
            make_step = functools.partial(models.make_train_step,
                                          average_loss=False)
            shapes = {"image": ((per_chip * n, size, size, 3), None),
                      "label": ((per_chip * n,), 1000)}
            units = per_chip
            said = f"{size}x{size}"
        state, optimizer = models.create_train_state(rng, model, base, sample)
        if lm and args.remat:
            model = resolve_remat(model, state, per_chip, length,
                                  args.fused_ce)
        with span("hvd.lane.place"):
            # the one synthetic batch: integers below their range, or normal
            batch = {
                name: (jax.random.normal(rng, shape, jnp.float32)
                       if below is None
                       else jax.random.randint(rng, shape, 0, below))
                for name, (shape, below) in shapes.items()}
        # state_partition_specs owns the sharded-vs-replicated knowledge.
        state_spec = models.state_partition_specs(state)
        batch_spec = P(module_axis("data"))
        # One prebuilt compiled handle (no per-step cache lookup or hashing)
        # with the train state donated, so XLA updates weights and moments
        # in place instead of reallocating them every step.
        run_step = hvd.spmd_fn(
            make_step(model, optimizer),
            in_specs=(state_spec, batch_spec),
            out_specs=(state_spec, P()),
            donate_argnums=(0,),
        )
        with span("hvd.lane.place"):
            state, batch = place(state, state_spec), place(batch, batch_spec)
        log(f"Model: {args.model} ({said}), batch {per_chip}/chip, {n} chips "
            f"({jax.devices()[0].platform})", file=sys.stderr)
        return Lane(model, run_step, state, batch, units, stamp)


def build_parser():
    """The lane's arguments: a benchmark cell's ``bench_args`` are these
    (``tests/test_lane.py`` parses every cell's)."""
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--model", default="resnet50")
    parser.add_argument("--batch-size", type=int, default=None,
                        help="per-chip batch (default: 64 images, or 8 "
                             "sequences for the language models)")
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--seq-len", type=int, default=2048,
                        help="context length (language models)")
    parser.add_argument("--vocab", type=int, default=32000)
    parser.add_argument("--lm-layers", type=int, default=12)
    parser.add_argument("--lm-dim", type=int, default=768)
    parser.add_argument("--lm-heads", type=int, default=12)
    # --model moe_lm and looped_lm (models/decoder.py): grouped-query
    # attention, gated feed-forwards; moe_lm has a type a layer and this
    # chip's share of the experts, looped_lm applies its layers several
    # times. Widths are the model's; what is held here may be a share.
    parser.add_argument("--lm-kv-heads", type=int, default=None,
                        help="moe_lm, looped_lm: KV heads (default: "
                             "--lm-heads)")
    parser.add_argument("--lm-head-dim", type=int, default=None,
                        help="moe_lm, looped_lm: size of a head (default: "
                             "--lm-dim / --lm-heads)")
    parser.add_argument("--lm-rope-base", type=float, default=10000.0,
                        help="moe_lm, looped_lm: base of the rotary "
                             "positions")
    parser.add_argument("--lm-loops", type=int, default=4,
                        help="looped_lm: times the --lm-layers blocks are "
                             "applied, with the same weights; an exit "
                             "after each")
    parser.add_argument("--lm-exit-beta", type=float, default=0.1,
                        help="looped_lm: weight of the exit distribution's "
                             "entropy in the loss")
    parser.add_argument("--lm-window", type=int, default=2048,
                        help="moe_lm: window of a sliding layer")
    parser.add_argument("--lm-layer-types", default=None,
                        help="moe_lm: 'sliding' (window, rotary positions), "
                             "'full' (no positional encoding), 'latent' "
                             "(keys and values expanded from a compressed "
                             "row, one rotated key all heads share) or "
                             "'mamba' (Mamba-2's state-space mixer) a "
                             "layer, comma-separated (default: all full)")
    parser.add_argument("--lm-pattern", default=None,
                        help="moe_lm: one branch a layer, h + F(RMS(h)), "
                             "F by the layer's letter of nemotron_h's "
                             "hybrid_override_pattern: M Mamba-2's mixer, "
                             "E the expert layer, * full attention (no "
                             "positional encoding); --lm-layers letters, "
                             "in place of --lm-layer-types")
    parser.add_argument("--lm-qk-norm", default=True,
                        action=argparse.BooleanOptionalAction,
                        help="moe_lm: an RMS norm a head on q and k in a "
                             "sliding or full layer")
    parser.add_argument("--lm-attn-gate", default=True,
                        action=argparse.BooleanOptionalAction,
                        help="moe_lm: a sigmoid gate on a sliding or full "
                             "layer's output")
    parser.add_argument("--lm-attn-scale", type=float, default=None,
                        help="moe_lm: scale of a sliding or full layer's "
                             "scores (default: 1 / sqrt(head size))")
    parser.add_argument("--ssm-heads", type=int, default=64,
                        help="moe_lm, a mamba layer: heads of the scan")
    parser.add_argument("--ssm-head-dim", type=int, default=64,
                        help="moe_lm, a mamba layer: size of a head")
    parser.add_argument("--ssm-state", type=int, default=128,
                        help="moe_lm, a mamba layer: size of the state "
                             "(of B and C)")
    parser.add_argument("--ssm-groups", type=int, default=1,
                        help="moe_lm, a mamba layer: groups of B and C (head "
                             "h reads group h // (heads / groups)) and of "
                             "the gated norm")
    parser.add_argument("--ssm-conv", type=int, default=4,
                        help="moe_lm, a mamba layer: taps of the causal "
                             "depthwise conv")
    parser.add_argument("--ssm-chunk", type=int, default=256,
                        help="moe_lm, a mamba layer: tokens a chunk of the "
                             "scan")
    parser.add_argument("--lm-embed-multiplier", type=float, default=None,
                        help="moe_lm: the embedding times this number")
    parser.add_argument("--lm-residual-scale", type=float, default=1.0,
                        help="moe_lm: each branch's output times this "
                             "number before it is added to the stream")
    parser.add_argument("--lm-tie-head", action="store_true",
                        help="moe_lm: the logits from the embedding's "
                             "transpose, no head of their own")
    parser.add_argument("--lm-logit-divisor", type=float, default=1.0,
                        help="moe_lm: the logits divided by this number")
    parser.add_argument("--lm-latent-dim", type=int, default=512,
                        help="moe_lm, a latent layer: width of the "
                             "compressed row")
    parser.add_argument("--lm-rope-dim", type=int, default=64,
                        help="moe_lm, a latent layer: width of the rotated "
                             "key all heads share, beside --lm-head-dim of "
                             "a key's own")
    parser.add_argument("--lm-value-dim", type=int, default=128,
                        help="moe_lm, a latent layer: width of a value")
    parser.add_argument("--lm-output-norms", default=True,
                        action=argparse.BooleanOptionalAction,
                        help="moe_lm: an RMS norm after each branch as well "
                             "as before it (four norms a block, not two)")
    parser.add_argument("--lm-embed-scale", default=True,
                        action=argparse.BooleanOptionalAction,
                        help="moe_lm: the embedding times sqrt(--lm-dim)")
    parser.add_argument("--lm-ffn", type=int, default=None,
                        help="moe_lm, looped_lm: width of a dense layer's "
                             "gated feed-forward (default: 4 x --lm-dim)")
    parser.add_argument("--lm-dense-layers", type=int, default=1,
                        help="moe_lm: leading layers with a dense "
                             "feed-forward; the others have experts")
    parser.add_argument("--moe-experts", type=int, default=8,
                        help="moe_lm: experts the router scores")
    parser.add_argument("--moe-experts-held", type=int, default=None,
                        help="moe_lm: experts this chip holds (default: "
                             "all); tokens routed elsewhere add nothing "
                             "here, and none is dropped")
    parser.add_argument("--moe-first-expert", type=int, default=0,
                        help="moe_lm: the first expert held here")
    parser.add_argument("--moe-top-k", type=int, default=2)
    parser.add_argument("--moe-width", type=int, default=1024,
                        help="moe_lm: width of one expert")
    parser.add_argument("--moe-shared", type=int, default=1,
                        help="moe_lm: shared experts every token passes")
    parser.add_argument("--moe-route-scale", type=float, default=1.0)
    parser.add_argument("--moe-act", default="swiglu",
                        choices=("swiglu", "relu2"),
                        help="moe_lm: an expert's MLP, swiglu ((silu(x "
                             "W_gate) * x W_up) W_down) or relu2 (relu(x "
                             "W_up)^2 W_down, no gate), the shared one's "
                             "too")
    parser.add_argument("--moe-bias-coeff", type=float, default=0.001,
                        help="moe_lm: step of the selection bias's "
                             "balancing rule after every optimizer step")
    parser.add_argument("--fp32", action="store_true",
                        help="disable bfloat16 compute")
    parser.add_argument("--attention", default=None,
                        choices=("auto", "dense", "flash"),
                        help="language models' attention: auto (the "
                             "default) asks ops.attention.attention_plan "
                             "with the lane's shapes (the flash kernels "
                             "where the v5e sweep found them faster, the "
                             "dense reference elsewhere and on the CPU; "
                             "PERF.md, PR 29); dense | flash pin one side")
    parser.add_argument("--fused-ce", action="store_true",
                        help="language models: chunked fused cross-"
                             "entropy (ops/xent.py): the [B,L,vocab] "
                             "fp32 logits tensor never materializes")
    parser.add_argument("--remat", action="store_true",
                        help="language models: run blocks again in the "
                             "backward pass instead of keeping what they "
                             "computed. moe_lm, looped_lm: only as many "
                             "block applications as do not fit the device's "
                             "memory (decoder.plan_recomputation; all of "
                             "them where the backend reports no limit); "
                             "transformer_lm: every block")
    parser.add_argument("--fused-bn", action="store_true",
                        help="ResNet and Inception families: compute BN "
                             "statistics in the 1x1-conv matmul epilogue "
                             "(Pallas kernel, ops/conv_bn.py) instead of a "
                             "separate reduction pass")
    return parser
