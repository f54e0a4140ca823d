"""The hvdverify program registry: the repo's real traced programs.

Every entry builds ``(fn, abstract_args)`` for :func:`tools.hvdverify.
core.verify` — the exact code paths the driver gate, the
DistributedOptimizer, the parallel modules, and the elastic loop
execute, traced at reduced input sizes (tracing cost scales with op
count, not tensor size; the collective schedule is size-independent in
structure). Groups:

* ``gate``     — the lanes bench.py's ``build_lane`` composes: the
                 model-zoo train steps (``models.make_train_step`` for
                 resnet50/vgg16/inception_v3/vit, ``models.
                 make_lm_train_step`` for transformer_lm) through
                 ``spmd_fn`` with the state donated, plus the window /
                 overlap / ZeRO / fused-CE variants of the library's
                 own options.
* ``optimizer``— DistributedOptimizer's fused / overlap / shaped
                 emission modes, each with an HVV105 ReconcileSpec
                 pinning the traced bytes to ``plan_buckets``.
* ``dp``       — the hierarchical DP exchange (HOROVOD_HIERARCHICAL)
                 in both DCN shapes: the 2-slice ladder under overlap
                 and the int8-wire 4-slice two-stage exchange, each
                 HVV105-reconciled per ladder leg.
* ``parallel`` — all six hand-rolled sharding modules
                 (spmd collectives, tp, pipeline, ulysses,
                 ring_attention, moe), gradients included where the
                 module ships custom VJPs.
* ``composed`` — LogicalMesh-composed stacks (dp x tp, dp x
                 sp(ulysses), tp x pp) built entirely through the
                 axis-rules table, with the full HVV2xx pass: sharding
                 reconciliation (HVV201), axis vocabulary (HVV202) and
                 per-module schedule equivalence (HVV203).
* ``elastic``  — the PR-5 windowed loop program with the
                 no-donation-while-snapshot-in-flight invariant
                 enforced (``forbid_donation``).
* ``serve``    — the serving engine's mixed prefill+decode step
                 (horovod_tpu/serve/engine.py) in BOTH decode-attention
                 modes (the dense gather reference and the fused
                 paged-attention kernel), each with the
                 pages-never-donated-while-held invariant enforced
                 (``forbid_donation`` — the HVV104 class again).

Abstract state comes from ``jax.eval_shape`` over the real init
functions — zero FLOPs, no devices, runs on CPU anywhere.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Tuple

from tools.hvdverify.rules import (
    EquivalenceSpec,
    ReconcileSpec,
    ShardingSpec,
)

#: Virtual mesh size every program traces under (matches the test
#: harness's 8-device CPU mesh, tests/conftest.py).
WORLD = 8

_ELASTIC_WHY = ("the elastic windowed loop forbids state donation while "
                "async snapshot d2h copies are in flight")


@dataclasses.dataclass
class Program:
    name: str
    group: str
    build: Callable[[], Tuple[Callable, tuple]]
    forbid_donation: bool = False
    forbid_donation_why: str = ""
    reconcile: Optional[Callable[[], ReconcileSpec]] = None
    #: HVV201: zero-arg -> ShardingSpec reconciling the program's
    #: declared partition specs against the LogicalMesh rules table.
    shardings: Optional[Callable[[], ShardingSpec]] = None
    #: HVV202: zero-arg -> the LogicalMesh whose vocabulary every
    #: collective axis / sharding constraint must come from.
    logical_mesh: Optional[Callable] = None
    #: HVV203: zero-arg -> [EquivalenceSpec] pinning the composed
    #: schedule op-identical to per-module reference traces.
    equivalence: Optional[Callable[[], List[EquivalenceSpec]]] = None
    #: rule id -> justification; suppressed findings never fail the gate
    #: but are always reported (the hvdlint suppression discipline).
    suppress: Dict[str, str] = dataclasses.field(default_factory=dict)


def _require_world():
    """The sweep needs an ``WORLD``-way device set; tests/conftest.py and
    the CLI (__main__) both force the 8-device virtual CPU mesh before
    jax initializes."""
    import jax

    if len(jax.devices()) < WORLD:
        raise RuntimeError(
            f"hvdverify needs {WORLD} devices (have "
            f"{len(jax.devices())}); run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "JAX_PLATFORMS=cpu (python -m tools.hvdverify sets this "
            "up itself)")


def _init():
    import horovod_tpu.jax as hvd

    _require_world()
    hvd.init()
    return hvd


def abstractify(tree):
    """ShapeDtypeStruct twin of an arbitrary array pytree — what every
    registry program (and chip_smoke.py's collective audit) traces on:
    only shapes/dtypes matter, nothing is allocated or executed."""
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _abstract_train_state(model, optimizer, sample):
    """ShapeDtypeStruct TrainState via eval_shape — the exact pytree
    ``models.create_train_state`` builds, without running init."""
    import jax
    import jax.numpy as jnp
    from flax.core import FrozenDict, freeze

    from horovod_tpu.models import TrainState

    variables = jax.eval_shape(
        functools.partial(model.init, train=False),
        jax.random.PRNGKey(0), sample)
    params = variables["params"]
    batch_stats = freeze(variables.get("batch_stats", FrozenDict()))
    opt_state = jax.eval_shape(optimizer.init, params)
    return TrainState(
        params=params,
        batch_stats=batch_stats,
        opt_state=opt_state,
        step=jax.ShapeDtypeStruct((), jnp.int32),
    )


# ---------------------------------------------------------------- gate


def _image_lane(model_name, *, image=64, per_chip=2, overlap=None,
                zero=False, window=1, num_classes=100):
    """A driver-gate image lane: models.build -> make_train_step ->
    spmd_fn with the state donated, bench.py's ``build_lane`` composition
    (window>1 adds jax/window.py's scan over a K-stacked batch; overlap and
    zero are ``create_train_state``'s options)."""

    def build():
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import PartitionSpec as P

        from horovod_tpu import models
        from horovod_tpu.jax.window import stacked_specs, windowed

        hvd = _init()
        model = models.build(model_name, num_classes=num_classes)
        sgd = optax.sgd(0.01, momentum=0.9)
        sample = jax.ShapeDtypeStruct((1, image, image, 3), jnp.float32)
        if zero:
            from horovod_tpu.jax.zero import sharded_distributed_optimizer

            optimizer = sharded_distributed_optimizer(sgd)
        else:
            from horovod_tpu.jax.optimizer import DistributedOptimizer

            optimizer = DistributedOptimizer(sgd, overlap=overlap)
        state = _abstract_train_state(model, optimizer, sample)
        step_fn = models.make_train_step(model, optimizer,
                                         average_loss=False)
        state_spec = (models.state_partition_specs(state) if zero
                      else P())
        n = hvd.size()
        batch = {
            "image": jax.ShapeDtypeStruct(
                (per_chip * n, image, image, 3), jnp.float32),
            "label": jax.ShapeDtypeStruct((per_chip * n,), jnp.int32),
        }
        from horovod_tpu.parallel.logical import DATA_AXIS

        batch_spec = P(DATA_AXIS)
        if window > 1:
            # The scan window over a K-stacked batch (hvd.run_steps
            # stages concrete arrays; abstract tracing stacks the
            # ShapeDtypeStructs directly).
            step_fn = windowed(step_fn, window)
            batch = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct((window,) + x.shape,
                                               x.dtype), batch)
            batch_spec = stacked_specs(batch_spec)
        run = hvd.spmd_fn(
            step_fn,
            in_specs=(state_spec, batch_spec),
            out_specs=(state_spec, P()),
            donate_argnums=(0,),
        )
        return (lambda s, b: run(s, b)), (state, batch)

    return build


def _lm_lane(*, fused_ce=False, seq=256, per_chip=1, layers=4, dim=256,
             heads=4, vocab=1024):
    """The transformer_lm gate lane: ``models.make_lm_train_step``, the step
    bench.py's ``build_lane`` runs, over dense attention (the fused_ce
    variant is ``--fused-ce``'s: the loss through
    ops/xent.fused_cross_entropy)."""

    def build():
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import PartitionSpec as P

        from horovod_tpu import models
        from horovod_tpu.jax.optimizer import DistributedOptimizer
        from horovod_tpu.parallel.logical import DATA_AXIS

        hvd = _init()
        model = models.TransformerLM(
            vocab_size=vocab, num_layers=layers, num_heads=heads,
            embed_dim=dim, max_len=max(seq, 2048))
        optimizer = DistributedOptimizer(optax.adam(1e-4))
        sample = jax.ShapeDtypeStruct((1, seq), jnp.int32)
        state = _abstract_train_state(model, optimizer, sample)
        n = hvd.size()
        batch = {"tokens": jax.ShapeDtypeStruct((per_chip * n, seq),
                                                jnp.int32)}
        run = hvd.spmd_fn(
            models.make_lm_train_step(model, optimizer, fused_ce=fused_ce),
            in_specs=(P(), P(DATA_AXIS)),
            out_specs=(P(), P()),
            donate_argnums=(0,),
        )
        return (lambda s, b: run(s, b)), (state, batch)

    return build


# ------------------------------------------------------------ optimizer


def _mnist_param_leaves():
    import jax
    import jax.numpy as jnp

    from horovod_tpu import models

    model = models.MNISTNet()
    variables = jax.eval_shape(
        functools.partial(model.init, train=False),
        jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 28, 28, 1), jnp.float32))
    return jax.tree_util.tree_leaves(variables["params"])


_OPT_THRESHOLD = 64 * 1024  # multi-bucket plan on the MNIST tree


def _optimizer_mode(*, overlap, threshold=_OPT_THRESHOLD):
    """DistributedOptimizer traced in one emission mode over the MNIST
    parameter tree, inside shard_map over the "hvd" axis — the program
    tests/test_overlap.py exercises dynamically, verified statically."""

    def build():
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.jax.fusion import fused_reduce

        hvd = _init()
        leaves = _mnist_param_leaves()

        def exchange(*grads):
            return tuple(fused_reduce(
                list(grads), average=True,
                fusion_threshold=threshold,
                overlap=overlap,
                name="grads"))

        run = hvd.spmd_fn(
            exchange,
            in_specs=tuple(P() for _ in leaves),
            out_specs=tuple(P() for _ in leaves),
        )
        args = tuple(jax.ShapeDtypeStruct(l.shape, jnp.float32)
                     for l in leaves)
        return (lambda *a: run(*a)), args

    def reconcile():
        return ReconcileSpec(
            leaves=_mnist_param_leaves(),
            threshold=threshold,
            axis_size=WORLD,
        )

    return build, reconcile


def _dp_hier_mode(*, inner, compression_name):
    """The hierarchical DP exchange (PR-10 tentpole) traced in one
    emission mode over the MNIST tree: every bucket must decompose into
    intra-slice reduce-scatter -> inter-slice exchange (quantized under
    int8) -> intra-slice all-gather, HVV105-reconciled per leg.
    ``inner=4`` on the 8-way mesh is the 2-slice (all-gather DCN
    exchange) shape; ``inner=2`` the 4-slice two-stage
    all-to-all shape."""

    def build():
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.common.state import global_state
        from horovod_tpu.jax.compression import Compression
        from horovod_tpu.jax.fusion import fused_reduce

        hvd = _init()
        leaves = _mnist_param_leaves()
        compression = getattr(Compression, compression_name)

        def exchange(*grads):
            # Inner-size pinned at TRACE time (build() must not leak
            # config into later registry programs).
            st = global_state()
            saved = st.config.hierarchical_inner_size
            st.config.hierarchical_inner_size = inner
            try:
                return tuple(fused_reduce(
                    list(grads), average=True,
                    fusion_threshold=_OPT_THRESHOLD,
                    overlap="on", hierarchical="on",
                    compression=compression,
                    name="grads"))
            finally:
                st.config.hierarchical_inner_size = saved

        run = hvd.spmd_fn(
            exchange,
            in_specs=tuple(P() for _ in leaves),
            out_specs=tuple(P() for _ in leaves),
        )
        args = tuple(jax.ShapeDtypeStruct(l.shape, jnp.float32)
                     for l in leaves)
        return (lambda *a: run(*a)), args

    def reconcile():
        from horovod_tpu.jax.compression import Compression
        from horovod_tpu.jax.compression import is_dcn_wire

        import jax.numpy as jnp

        compression = getattr(Compression, compression_name)
        dcn_dtype = (jnp.dtype(compression.wire_dtype).name
                     if is_dcn_wire(compression) else None)
        return ReconcileSpec(
            leaves=_mnist_param_leaves(),
            threshold=_OPT_THRESHOLD,
            axis_size=WORLD,
            hier_inner=inner,
            dcn_dtype=dcn_dtype,
        )

    return build, reconcile


# ------------------------------------------------------------- parallel


def _submesh(axes: Dict[str, int]):
    import jax

    from horovod_tpu.parallel.mesh import make_mesh

    n = 1
    for v in axes.values():
        n *= v
    return make_mesh(axes, devices=jax.devices()[:n])


def _shmapped(fn, mesh, in_specs, out_specs):
    """Raw shard_map with the varying-axes checker off (these
    rank-programs are deliberately rank-varying; the wire bytes and the
    schedule are what hvdverify pins — same opt-out class as
    tests/test_wire_bytes.py)."""
    import jax

    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _build_parallel_spmd():
    """The hvd.* collective surface (mpi_ops) under spmd_fn: allreduce,
    grouped_allreduce, allgather, alltoall, reducescatter, broadcast —
    one program issuing each, the eager lane's SPMD twin."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    hvd = _init()

    def program(x, pair):
        a = hvd.allreduce(x, average=True)
        g = hvd.grouped_allreduce([x, 2.0 * x], average=False)
        cat = hvd.allgather(x)
        t = hvd.alltoall(jnp.tile(x, (hvd.size(), 1)))
        rs = hvd.reducescatter(jnp.tile(x, (hvd.size(), 1)),
                               average=False)
        b = hvd.broadcast(pair, root_rank=0)
        return (a + g[0] + g[1] + rs + t.mean() + b,
                cat.sum())

    run = hvd.spmd_fn(program, in_specs=(P(), P()),
                      out_specs=(P(), P()))
    x = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    pair = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    return (lambda *a: run(*a)), (x, pair)


def _build_parallel_tp():
    """Megatron MLP (column->row) WITH gradients: the custom-VJP
    conjugates (tp_region_output) put a psum in the backward — the
    walker must find it through custom_vjp_call_jaxpr."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import horovod_tpu.parallel as par

    _init()
    mesh = _submesh({"tp": 4})
    B, L, E, F = 2, 8, 16, 32

    def loss(x, wu, bu, wd, bd):
        return par.tp_mlp(x, wu, bu, wd, bd, axis="tp").sum()

    fn = _shmapped(
        jax.grad(loss, argnums=(1, 3)), mesh,
        in_specs=(P(), P(None, "tp"), P("tp"), P("tp", None), P()),
        out_specs=(P(None, "tp"), P("tp", None)))
    args = (jax.ShapeDtypeStruct((B, L, E), jnp.float32),
            jax.ShapeDtypeStruct((E, F), jnp.float32),
            jax.ShapeDtypeStruct((F,), jnp.float32),
            jax.ShapeDtypeStruct((F, E), jnp.float32),
            jax.ShapeDtypeStruct((E,), jnp.float32))
    return fn, args


def _build_parallel_pipeline():
    """GPipe schedule: the scanned tick loop rank-divergently injects/
    emits (jnp.where on axis_index — data-level, legal) and ppermutes
    every tick — the schedule must show the rotation UNconditional."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import horovod_tpu.parallel as par

    _init()
    mesh = _submesh({"pp": 4})
    D, M, Bm = 8, 6, 2
    fn = _shmapped(
        lambda ws, x: par.pipeline_apply(
            lambda w, a: jnp.tanh(a @ w), ws, x, "pp"),
        mesh, in_specs=(P("pp"), P()), out_specs=P())
    args = (jax.ShapeDtypeStruct((4, D, D), jnp.float32),
            jax.ShapeDtypeStruct((M, Bm, D), jnp.float32))
    return fn, args


def _build_parallel_ulysses():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import horovod_tpu.parallel as par

    _init()
    mesh = _submesh({"sp": 4})
    B, L, H, D = 2, 32, 4, 8
    fn = _shmapped(
        lambda q, k, v: par.ulysses_attention(q, k, v, axis="sp",
                                              causal=True),
        mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"))
    x = jax.ShapeDtypeStruct((B, L, H, D), jnp.float32)
    return fn, (x, x, x)


def _build_parallel_ring_attention():
    """The PR-3 shape this whole tool exists for: the causal dead-block
    skip is a RANK-DIVERGENT lax.cond — legal exactly because both
    branches are collective-free (the ppermute rotation stays outside,
    unconditional). HVV101 proves that property on every trace; the
    fixture corpus keeps the historical rotation-inside-the-cond variant
    as a named incident."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import horovod_tpu.parallel as par

    _init()
    mesh = _submesh({"sp": 4})
    B, L, H, D = 2, 32, 2, 4
    fn = _shmapped(
        lambda q, k, v: par.ring_attention(
            q, k, v, axis="sp", causal=True, skip_dead_blocks=True),
        mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"))
    x = jax.ShapeDtypeStruct((B, L, H, D), jnp.float32)
    return fn, (x, x, x)


def _build_parallel_moe():
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu.parallel as par

    _init()
    mesh = _submesh({"ep": 4})
    T, D, F, experts, per_chip = 64, 8, 16, 8, 2

    def share(x, router, held):
        y, _ = par.routed_experts(
            x, router, held, first=lax.axis_index("ep") * per_chip,
            top_k=2)
        return lax.psum(y, "ep")

    stacked = {"gate": P("ep"), "up": P("ep"), "down": P("ep")}
    fn = _shmapped(share, mesh, in_specs=(P(), P(), stacked), out_specs=P())
    f32 = jnp.float32
    args = (jax.ShapeDtypeStruct((T, D), f32),
            jax.ShapeDtypeStruct((D, experts), f32),
            {"gate": jax.ShapeDtypeStruct((experts, D, F), f32),
             "up": jax.ShapeDtypeStruct((experts, D, F), f32),
             "down": jax.ShapeDtypeStruct((experts, F, D), f32)})
    return fn, args


# ------------------------------------------------------------- composed
#
# LogicalMesh-composed stacks (the PR-17 tentpole): each program builds
# its mesh + every partition spec through the axis-rules table, then the
# full HVV2xx pass runs — HVV201 reconciles the declared specs against
# the table, HVV202 checks every collective/constraint axis against the
# mesh vocabulary, HVV203 pins the composed schedule op-identical to the
# per-module reference traces (built at the composed program's LOCAL
# shapes, the other strategies' axes divided out).


def _logical_mesh(config: str):
    import jax

    from horovod_tpu.parallel.logical import LogicalMesh

    _require_world()
    return LogicalMesh.from_config(config, devices=jax.devices()[:WORLD])


def _composed_dp_tp():
    """dp=2 x tp=4: the Megatron MLP under grad with the DP gradient
    exchange — the canonical 2-axis stack."""
    B, L, E, F = 4, 8, 16, 32  # global batch; local batch B/dp = 2

    def _loss(x, wu, bu, wd, bd, tp_ax):
        import horovod_tpu.parallel as par

        return par.tp_mlp(x, wu, bu, wd, bd, axis=tp_ax).sum()

    def build():
        import functools

        import jax
        import jax.numpy as jnp
        from jax import lax

        _init()
        lm = _logical_mesh("dp=2,tp=4")
        dp_ax = lm.role_axis("data")
        tp_ax = lm.role_axis("tensor")

        def step(x, wu, bu, wd, bd):
            gwu, gwd = jax.grad(
                functools.partial(_loss, tp_ax=tp_ax),
                argnums=(1, 3))(x, wu, bu, wd, bd)
            # DP gradient exchange: average over the data axis.
            n = lax.axis_size(dp_ax)
            return (lax.psum(gwu, dp_ax) / n, lax.psum(gwd, dp_ax) / n)

        fn = _shmapped(
            step, lm.mesh,
            in_specs=(lm.spec("batch"), lm.spec("embed", "mlp"),
                      lm.spec("mlp"), lm.spec("mlp", "embed"),
                      lm.spec("embed")),
            out_specs=(lm.spec("embed", "mlp"), lm.spec("mlp", "embed")))
        args = (jax.ShapeDtypeStruct((B, L, E), jnp.float32),
                jax.ShapeDtypeStruct((E, F), jnp.float32),
                jax.ShapeDtypeStruct((F,), jnp.float32),
                jax.ShapeDtypeStruct((F, E), jnp.float32),
                jax.ShapeDtypeStruct((E,), jnp.float32))
        return fn, args

    def shardings():
        lm = _logical_mesh("dp=2,tp=4")
        return ShardingSpec(mesh=lm, entries=(
            ("x", ("batch",), lm.spec("batch")),
            ("w_up", ("embed", "mlp"), lm.spec("embed", "mlp")),
            ("b_up", ("mlp",), lm.spec("mlp")),
            ("w_down", ("mlp", "embed"), lm.spec("mlp", "embed")),
            ("b_down", ("embed",), lm.spec("embed")),
        ))

    def logical_mesh():
        return _logical_mesh("dp=2,tp=4")

    def equivalence():
        import functools

        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.parallel.logical import DATA_AXIS

        def tp_ref():
            _init()
            mesh = _submesh({"tp": 4})
            fn = _shmapped(
                jax.grad(functools.partial(_loss, tp_ax="tp"),
                         argnums=(1, 3)),
                mesh,
                in_specs=(P(), P(None, "tp"), P("tp"), P("tp", None),
                          P()),
                out_specs=(P(None, "tp"), P("tp", None)))
            args = (jax.ShapeDtypeStruct((B // 2, L, E), jnp.float32),
                    jax.ShapeDtypeStruct((E, F), jnp.float32),
                    jax.ShapeDtypeStruct((F,), jnp.float32),
                    jax.ShapeDtypeStruct((F, E), jnp.float32),
                    jax.ShapeDtypeStruct((E,), jnp.float32))
            return fn, args

        def dp_ref():
            _init()
            mesh = _submesh({DATA_AXIS: 2})

            def exchange(gwu, gwd):
                n = lax.axis_size(DATA_AXIS)
                return (lax.psum(gwu, DATA_AXIS) / n,
                        lax.psum(gwd, DATA_AXIS) / n)

            fn = _shmapped(exchange, mesh, in_specs=(P(), P()),
                           out_specs=(P(), P()))
            args = (jax.ShapeDtypeStruct((E, F // 4), jnp.float32),
                    jax.ShapeDtypeStruct((F // 4, E), jnp.float32))
            return fn, args

        return [
            EquivalenceSpec(reference=tp_ref, axes=("tp",), name="tp"),
            EquivalenceSpec(reference=dp_ref, axes=("dp",),
                            axis_map={"dp": DATA_AXIS}, name="dp"),
        ]

    return build, shardings, logical_mesh, equivalence


def _composed_dp_ulysses():
    """dp=2 x sp=4: Ulysses all-to-all attention with the batch sharded
    over dp AND the sequence over sp, plus the DP loss reduction."""
    B, L, H, D = 4, 32, 4, 8  # global; local [B/2, L/4, H, D]

    def build():
        import jax
        import jax.numpy as jnp
        from jax import lax

        import horovod_tpu.parallel as par

        _init()
        lm = _logical_mesh("dp=2,sp=4")
        dp_ax = lm.role_axis("data")
        sp_ax = lm.role_axis("seq")

        def step(q, k, v):
            out = par.ulysses_attention(q, k, v, axis=sp_ax, causal=True)
            # DP loss reduction: global mean over the data axis.
            return lax.psum(out.sum(), dp_ax) / lax.axis_size(dp_ax)

        fn = _shmapped(
            step, lm.mesh,
            in_specs=(lm.spec("batch", "seq"),) * 3,
            out_specs=lm.spec())
        x = jax.ShapeDtypeStruct((B, L, H, D), jnp.float32)
        return fn, (x, x, x)

    def shardings():
        lm = _logical_mesh("dp=2,sp=4")
        return ShardingSpec(mesh=lm, entries=(
            ("q", ("batch", "seq"), lm.spec("batch", "seq")),
            ("k", ("batch", "seq"), lm.spec("batch", "seq")),
            ("v", ("batch", "seq"), lm.spec("batch", "seq")),
            ("loss", (), lm.spec()),
        ))

    def logical_mesh():
        return _logical_mesh("dp=2,sp=4")

    def equivalence():
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.parallel.logical import DATA_AXIS

        def sp_ref():
            import horovod_tpu.parallel as par

            _init()
            mesh = _submesh({"sp": 4})
            fn = _shmapped(
                lambda q, k, v: par.ulysses_attention(
                    q, k, v, axis="sp", causal=True),
                mesh, in_specs=(P(None, "sp"),) * 3,
                out_specs=P(None, "sp"))
            x = jax.ShapeDtypeStruct((B // 2, L, H, D), jnp.float32)
            return fn, (x, x, x)

        def dp_ref():
            _init()
            mesh = _submesh({DATA_AXIS: 2})
            fn = _shmapped(
                lambda s: lax.psum(s, DATA_AXIS)
                / lax.axis_size(DATA_AXIS),
                mesh, in_specs=P(), out_specs=P())
            return fn, (jax.ShapeDtypeStruct((), jnp.float32),)

        return [
            EquivalenceSpec(reference=sp_ref, axes=("sp",), name="sp"),
            EquivalenceSpec(reference=dp_ref, axes=("dp",),
                            axis_map={"dp": DATA_AXIS}, name="dp"),
        ]

    return build, shardings, logical_mesh, equivalence


def _composed_tp_pp():
    """tp=2 x pp=4: a GPipe pipeline whose every stage is a Megatron
    MLP — TP collectives inside the scanned tick loop, the PP rotation
    outside-conditional as always."""
    STAGES, M, Bm, E, F = 4, 6, 2, 8, 16

    def _stage(w, a, tp_ax):
        import jax

        import horovod_tpu.parallel as par

        h = jax.nn.gelu(par.column_parallel(a, w["wu"], axis=tp_ax))
        return par.row_parallel(h, w["wd"], axis=tp_ax)

    def build():
        import functools

        import jax
        import jax.numpy as jnp

        import horovod_tpu.parallel as par

        _init()
        lm = _logical_mesh("tp=2,pp=4")
        tp_ax = lm.role_axis("tensor")
        pp_ax = lm.role_axis("stage")

        def step(ws, x):
            return par.pipeline_apply(
                functools.partial(_stage, tp_ax=tp_ax), ws, x,
                axis=pp_ax)

        fn = _shmapped(
            step, lm.mesh,
            in_specs=({"wu": lm.spec("stage", "embed", "mlp"),
                       "wd": lm.spec("stage", "mlp", "embed")},
                      lm.spec()),
            out_specs=lm.spec())
        args = ({"wu": jax.ShapeDtypeStruct((STAGES, E, F), jnp.float32),
                 "wd": jax.ShapeDtypeStruct((STAGES, F, E),
                                            jnp.float32)},
                jax.ShapeDtypeStruct((M, Bm, E), jnp.float32))
        return fn, args

    def shardings():
        lm = _logical_mesh("tp=2,pp=4")
        return ShardingSpec(mesh=lm, entries=(
            ("wu", ("stage", "embed", "mlp"),
             lm.spec("stage", "embed", "mlp")),
            ("wd", ("stage", "mlp", "embed"),
             lm.spec("stage", "mlp", "embed")),
            ("x", (), lm.spec()),
        ))

    def logical_mesh():
        return _logical_mesh("tp=2,pp=4")

    def equivalence():
        import functools

        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        n_ticks = M + STAGES - 1

        def pp_ref():
            import horovod_tpu.parallel as par

            _init()
            mesh = _submesh({"pp": 4})
            fn = _shmapped(
                lambda ws, x: par.pipeline_apply(
                    lambda w, a: jnp.tanh(a @ w), ws, x, axis="pp"),
                mesh, in_specs=(P("pp"), P()), out_specs=P())
            args = (jax.ShapeDtypeStruct((STAGES, E, E), jnp.float32),
                    jax.ShapeDtypeStruct((M, Bm, E), jnp.float32))
            return fn, args

        def tp_ref():
            _init()
            mesh = _submesh({"tp": 2})

            def loop(wu, wd, a):
                body = functools.partial(_stage, tp_ax="tp")
                return lax.fori_loop(
                    0, n_ticks,
                    lambda i, acc: body({"wu": wu, "wd": wd}, acc), a)

            fn = _shmapped(
                loop, mesh,
                in_specs=(P(None, "tp"), P("tp", None), P()),
                out_specs=P())
            args = (jax.ShapeDtypeStruct((E, F), jnp.float32),
                    jax.ShapeDtypeStruct((F, E), jnp.float32),
                    jax.ShapeDtypeStruct((Bm, E), jnp.float32))
            return fn, args

        return [
            EquivalenceSpec(reference=pp_ref, axes=("pp",), name="pp"),
            EquivalenceSpec(reference=tp_ref, axes=("tp",), name="tp"),
        ]

    return build, shardings, logical_mesh, equivalence


# -------------------------------------------------------------- elastic


def _build_elastic_windowed_loop(per_window: int = 8):
    """The PR-5 elastic window program EXACTLY as run_elastic builds it:
    ``jax.jit(windowed(step_fn, k))`` with NO donation — an async
    snapshot may still be copying a buffer the next dispatch would
    otherwise reuse. ``forbid_donation`` turns any donating variant
    into an HVV104 finding (the regression test donates on purpose).

    ``per_window`` is the per-rank window batch: the resized-world
    entry traces the SAME loop at the post-shrink batch geometry (a
    2x-smaller world doubles nothing in the program but the batch the
    survivors each carry) so the snapshot-in-flight invariant is
    machine-checked at both world sizes the resize e2e exercises."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu import models
    from horovod_tpu.jax.window import windowed

    _init()
    model = models.MNISTNet()
    optimizer = optax.sgd(0.1, momentum=0.9)
    sample = jax.ShapeDtypeStruct((1, 28, 28, 1), jnp.float32)
    state = _abstract_train_state(model, optimizer, sample)
    step_fn = models.make_train_step(model, optimizer,
                                     average_loss=False)
    k = 4
    window_fn = jax.jit(windowed(step_fn, k))  # loop.py: NOT donated
    batch = {
        "image": jax.ShapeDtypeStruct((k, per_window, 28, 28, 1),
                                      jnp.float32),
        "label": jax.ShapeDtypeStruct((k, per_window), jnp.int32),
    }
    return (lambda s, b: window_fn(s, b)), (state, batch)


# ---------------------------------------------------------------- serve


_SERVE_WHY = ("the paged KV cache must never be donated while a request "
              "holds pages — an in-flight step reads every live "
              "request's pages, and the host keeps the pre-step arrays "
              "referenced (the elastic HVV104 invariant class, serving "
              "edition)")


def _build_serve_step(attention: str = "gather"):
    """The serving engine's MIXED prefill+decode step program exactly
    as ServeEngine jits it (horovod_tpu/serve/engine.py::serve_step):
    decode slots + the chunked-prefill lane over the paged KV arrays,
    traced on PagedKVCache's abstract twin. No collectives today (the
    single-chip engine; LogicalMesh sharding is ROADMAP item 2) — the
    verified property is the donation rule, in BOTH decode-attention
    modes: pages must never be donated while requests hold them,
    whether the step gathers the dense cache or the fused Pallas
    kernel streams pages read-only (``attention="paged"``)."""
    import functools

    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import parallel_lm as plm
    from horovod_tpu.serve import PagedKVCache, ServeConfig
    from horovod_tpu.serve.engine import serve_step

    cfg = ServeConfig(page_size=8, num_pages=16, decode_slots=2,
                      prefill_chunk=4, attention=attention)
    params = jax.eval_shape(
        lambda: plm.init_lm_params(jax.random.PRNGKey(0), 64, 32, 2, 2,
                                   8, 32))
    cache = PagedKVCache(params, cfg, abstract=True)
    pps = cache.pages_per_seq
    S, C = cfg.decode_slots, cfg.prefill_chunk
    sds = jax.ShapeDtypeStruct
    dec = {"tok": sds((S,), jnp.int32), "pos": sds((S,), jnp.int32),
           "active": sds((S,), jnp.bool_),
           "tables": sds((S, pps), jnp.int32)}
    pre = {"tokens": sds((C,), jnp.int32), "start": sds((), jnp.int32),
           "length": sds((), jnp.int32),
           "table": sds((pps,), jnp.int32)}
    # jax.jit WITHOUT donation — ServeEngine's exact spelling; a
    # donate_argnums variant is the HVV104 regression test's job.
    fn = jax.jit(functools.partial(serve_step,
                                   page_size=cfg.page_size,
                                   attention=cfg.attention))
    return (lambda p, pages, d, pr: fn(p, pages, d, pr)), \
        (params, cache.pages, dec, pre)


_SERVE_TP_MESH = "dp=1,tp=4"
#: TP-variant geometry: heads=4 so the head dim divides tp=4 (the
#: engine fail-fasts otherwise); embed stays 16 (4 heads x head_dim 4),
#: vocab 64 and mlp 32 both divide 4 for the vocab-/column-parallel
#: shards.
_SERVE_TP_GEOM = (64, 32, 2, 4, 4, 32)  # V, Lmax, layers, H, DH, FFN


def _build_serve_step_tp(attention: str = "gather"):
    """The TP-sharded serving step exactly as ServeEngine spells it
    when ``ServeConfig.mesh`` binds a tensor axis (engine.py __init__):
    ``serve_step`` under shard_map on the dp=1,tp=4 LogicalMesh —
    Megatron params via ``lm_param_specs(vocab_parallel=True)``, KV
    pages head-sharded ``P(None, None, tp, None)`` in AND out, host
    control dicts replicated, logits replicated full-vocab (the
    vocab-parallel head all-gathers, so the host sampler sees every
    column). Same donation invariant as serve.step — a live page's
    SHARDS must stay readable on every chip — plus the HVV2xx sweep:
    the declared specs must match what the rules table resolves for
    heads/mlp/vocab, and every collective must run over a mesh-defined
    axis."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.models import parallel_lm as plm
    from horovod_tpu.models.parallel_lm import lm_param_specs
    from horovod_tpu.serve import PagedKVCache, ServeConfig
    from horovod_tpu.serve.engine import serve_step

    V, LMAX, LAYERS, H, DH, FFN = _SERVE_TP_GEOM
    lm = _logical_mesh(_SERVE_TP_MESH)
    tp_ax = lm.role_axis("tensor")
    cfg = ServeConfig(page_size=8, num_pages=16, decode_slots=2,
                      prefill_chunk=4, attention=attention,
                      mesh=_SERVE_TP_MESH)
    params = jax.eval_shape(
        lambda: plm.init_lm_params(jax.random.PRNGKey(0), V, LMAX,
                                   LAYERS, H, DH, FFN))
    cache = PagedKVCache(params, cfg, abstract=True)
    pps = cache.pages_per_seq
    S, C = cfg.decode_slots, cfg.prefill_chunk
    sds = jax.ShapeDtypeStruct
    dec = {"tok": sds((S,), jnp.int32), "pos": sds((S,), jnp.int32),
           "active": sds((S,), jnp.bool_),
           "tables": sds((S, pps), jnp.int32)}
    pre = {"tokens": sds((C,), jnp.int32), "start": sds((), jnp.int32),
           "length": sds((), jnp.int32),
           "table": sds((pps,), jnp.int32)}
    param_specs = lm_param_specs(LAYERS, tp_ax, vocab_parallel=True)
    kv = P(None, None, tp_ax, None)
    step = functools.partial(serve_step, page_size=cfg.page_size,
                             attention=cfg.attention, tp=tp_ax,
                             vocab_parallel=True)
    fn = jax.jit(_shmapped(
        lambda p, pages, d, pr: step(p, pages, d, pr), lm.mesh,
        in_specs=(param_specs, kv, P(), P()),
        out_specs=(kv, P(), P())))
    return (lambda p, pages, d, pr: fn(p, pages, d, pr)), \
        (params, cache.pages, dec, pre)


def _spec_dec(sds, jnp, S, pps):
    """The speculative step's decode batch: serve_step's plus the
    speculation plane (width + the draft's in-step sampling knobs) —
    ServeEngine._build_dec's exact spec-mode shape."""
    return {"tok": sds((S,), jnp.int32), "pos": sds((S,), jnp.int32),
            "active": sds((S,), jnp.bool_),
            "tables": sds((S, pps), jnp.int32),
            "width": sds((S,), jnp.int32),
            "temp": sds((S,), jnp.float32),
            "topk": sds((S,), jnp.int32),
            "seed": sds((S,), jnp.int32),
            "sidx": sds((S,), jnp.int32)}


def _build_serve_step_spec(attention: str = "gather"):
    """The SPECULATIVE serving step exactly as ServeEngine jits it
    when ``speculate_k > 0`` (engine.py::serve_step_spec): the
    layer-skip draft's k-step propose scan + the rectangular-causal
    verify pass writing up to k+1 KV rows per slot. Same donation
    invariant as serve.step, sharpened: a speculative tick REJECTS
    rows by page arithmetic (stale rows are overwritten or causally
    masked, never erased), so the pre-step pages are the rollback
    substrate itself — donating them would destroy the very state a
    rejected window falls back to."""
    import functools

    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import parallel_lm as plm
    from horovod_tpu.serve import PagedKVCache, ServeConfig
    from horovod_tpu.serve.engine import serve_step_spec

    cfg = ServeConfig(page_size=8, num_pages=16, decode_slots=2,
                      prefill_chunk=4, attention=attention,
                      speculate_k=2, draft_layers=1)
    params = jax.eval_shape(
        lambda: plm.init_lm_params(jax.random.PRNGKey(0), 64, 32, 2, 2,
                                   8, 32))
    cache = PagedKVCache(params, cfg, abstract=True)
    pps = cache.pages_per_seq
    S, C = cfg.decode_slots, cfg.prefill_chunk
    sds = jax.ShapeDtypeStruct
    dec = _spec_dec(sds, jnp, S, pps)
    pre = {"tokens": sds((C,), jnp.int32), "start": sds((), jnp.int32),
           "length": sds((), jnp.int32),
           "table": sds((pps,), jnp.int32)}
    fn = jax.jit(functools.partial(serve_step_spec,
                                   k=cfg.speculate_k,
                                   draft_layers=cfg.draft_layers,
                                   page_size=cfg.page_size,
                                   attention=cfg.attention))
    return (lambda p, pages, d, pr: fn(p, pages, d, pr)), \
        (params, cache.pages, dec, pre)


def _build_serve_step_spec_tp():
    """The TP-sharded speculative step (ServeConfig.mesh="dp=1,tp=4",
    ``speculate_k > 0``): serve_step_spec under shard_map — the
    layer-skip draft needs NO extra sharding story (its layers ARE the
    target's first layers, so the Megatron specs and the head-sharded
    page pool cover it by construction), and the verify logits / draft
    proposals / draft logits come back replicated full-vocab like the
    base step's. Donation + the full HVV2xx sharding sweep."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.models import parallel_lm as plm
    from horovod_tpu.models.parallel_lm import lm_param_specs
    from horovod_tpu.serve import PagedKVCache, ServeConfig
    from horovod_tpu.serve.engine import serve_step_spec

    V, LMAX, LAYERS, H, DH, FFN = _SERVE_TP_GEOM
    lm = _logical_mesh(_SERVE_TP_MESH)
    tp_ax = lm.role_axis("tensor")
    cfg = ServeConfig(page_size=8, num_pages=16, decode_slots=2,
                      prefill_chunk=4, mesh=_SERVE_TP_MESH,
                      speculate_k=2, draft_layers=1)
    params = jax.eval_shape(
        lambda: plm.init_lm_params(jax.random.PRNGKey(0), V, LMAX,
                                   LAYERS, H, DH, FFN))
    cache = PagedKVCache(params, cfg, abstract=True)
    pps = cache.pages_per_seq
    S, C = cfg.decode_slots, cfg.prefill_chunk
    sds = jax.ShapeDtypeStruct
    dec = _spec_dec(sds, jnp, S, pps)
    pre = {"tokens": sds((C,), jnp.int32), "start": sds((), jnp.int32),
           "length": sds((), jnp.int32),
           "table": sds((pps,), jnp.int32)}
    param_specs = lm_param_specs(LAYERS, tp_ax, vocab_parallel=True)
    kv = P(None, None, tp_ax, None)
    step = functools.partial(serve_step_spec, k=cfg.speculate_k,
                             draft_layers=cfg.draft_layers,
                             page_size=cfg.page_size,
                             attention=cfg.attention, tp=tp_ax,
                             vocab_parallel=True)
    fn = jax.jit(_shmapped(
        lambda p, pages, d, pr: step(p, pages, d, pr), lm.mesh,
        in_specs=(param_specs, kv, P(), P()),
        out_specs=(kv, P(), P(), P(), P())))
    return (lambda p, pages, d, pr: fn(p, pages, d, pr)), \
        (params, cache.pages, dec, pre)


def _build_serve_step_prefill_pool():
    """The PREFILL pool's compiled tick under disaggregated serving
    (``FleetConfig.pools``): a prefill replica admits every request
    with ``prefill_only`` set, so its steady-state step is the
    chunked-prefill lane ALONE — ``serve_step_prefill`` (engine.py's
    public alias for the lane both step variants share), jitted over
    the abstract page pool exactly as the mixed step traces it. The
    donation stakes are sharpest here: between prefill completion and
    the decode pool's digest-verified admit, these pages are the only
    copy of the request's KV, parked in the handoff bay."""
    import functools

    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import parallel_lm as plm
    from horovod_tpu.serve import PagedKVCache, ServeConfig
    from horovod_tpu.serve.engine import serve_step_prefill

    cfg = ServeConfig(page_size=8, num_pages=16, decode_slots=2,
                      prefill_chunk=4)
    params = jax.eval_shape(
        lambda: plm.init_lm_params(jax.random.PRNGKey(0), 64, 32, 2, 2,
                                   8, 32))
    cache = PagedKVCache(params, cfg, abstract=True)
    pps = cache.pages_per_seq
    C = cfg.prefill_chunk
    sds = jax.ShapeDtypeStruct
    pre = {"tokens": sds((C,), jnp.int32), "start": sds((), jnp.int32),
           "length": sds((), jnp.int32),
           "table": sds((pps,), jnp.int32)}
    fn = jax.jit(functools.partial(serve_step_prefill,
                                   page_size=cfg.page_size))
    return (lambda p, pages, pr: fn(p, pages, pr)), \
        (params, cache.pages, pre)


def _build_serve_step_decode_pool(attention: str = "gather"):
    """The DECODE pool's compiled tick: ``serve_step`` with
    ``pre=None`` — the engine's decode-only variant, which is what a
    decode replica runs every step once the pools split (it never
    prefills; its pages arrive via the KV wire's import). Donation
    here invalidates the handoff position the import just
    digest-verified — the admitted pages ARE the request's history."""
    import functools

    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import parallel_lm as plm
    from horovod_tpu.serve import PagedKVCache, ServeConfig
    from horovod_tpu.serve.engine import serve_step

    cfg = ServeConfig(page_size=8, num_pages=16, decode_slots=2,
                      prefill_chunk=4, attention=attention)
    params = jax.eval_shape(
        lambda: plm.init_lm_params(jax.random.PRNGKey(0), 64, 32, 2, 2,
                                   8, 32))
    cache = PagedKVCache(params, cfg, abstract=True)
    pps = cache.pages_per_seq
    S = cfg.decode_slots
    sds = jax.ShapeDtypeStruct
    dec = {"tok": sds((S,), jnp.int32), "pos": sds((S,), jnp.int32),
           "active": sds((S,), jnp.bool_),
           "tables": sds((S, pps), jnp.int32)}
    step = functools.partial(serve_step, page_size=cfg.page_size,
                             attention=cfg.attention)
    fn = jax.jit(lambda p, pages, d: step(p, pages, d, None))
    return (lambda p, pages, d: fn(p, pages, d)), \
        (params, cache.pages, dec)


def _build_serve_step_decode_pool_tp():
    """The TP-sharded decode-pool tick (``ServeConfig.mesh`` binding a
    tensor axis on a decode replica): ``serve_step`` with ``pre=None``
    under shard_map — head-sharded imported pages (the KV wire
    preserves the shard layout tile-by-tile), Megatron params,
    replicated control dict and full-vocab logits. Donation of ANY
    head-shard of an imported page is the same bug, per chip — plus
    the HVV2xx sweep over the declared specs."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.models import parallel_lm as plm
    from horovod_tpu.models.parallel_lm import lm_param_specs
    from horovod_tpu.serve import PagedKVCache, ServeConfig
    from horovod_tpu.serve.engine import serve_step

    V, LMAX, LAYERS, H, DH, FFN = _SERVE_TP_GEOM
    lm = _logical_mesh(_SERVE_TP_MESH)
    tp_ax = lm.role_axis("tensor")
    cfg = ServeConfig(page_size=8, num_pages=16, decode_slots=2,
                      prefill_chunk=4, mesh=_SERVE_TP_MESH)
    params = jax.eval_shape(
        lambda: plm.init_lm_params(jax.random.PRNGKey(0), V, LMAX,
                                   LAYERS, H, DH, FFN))
    cache = PagedKVCache(params, cfg, abstract=True)
    pps = cache.pages_per_seq
    S = cfg.decode_slots
    sds = jax.ShapeDtypeStruct
    dec = {"tok": sds((S,), jnp.int32), "pos": sds((S,), jnp.int32),
           "active": sds((S,), jnp.bool_),
           "tables": sds((S, pps), jnp.int32)}
    param_specs = lm_param_specs(LAYERS, tp_ax, vocab_parallel=True)
    kv = P(None, None, tp_ax, None)
    step = functools.partial(serve_step, page_size=cfg.page_size,
                             attention=cfg.attention, tp=tp_ax,
                             vocab_parallel=True)
    # pre_logits is None in the decode-only variant; drop it so the
    # shard_map out_specs match the two real outputs.
    fn = jax.jit(_shmapped(
        lambda p, pages, d: step(p, pages, d, None)[:2], lm.mesh,
        in_specs=(param_specs, kv, P()),
        out_specs=(kv, P())))
    return (lambda p, pages, d: fn(p, pages, d)), \
        (params, cache.pages, dec)


def _serve_tp_shardings():
    """HVV201 claims for the TP step: the Megatron param placement +
    the head-sharded page pool, all resolved through the rules table
    (heads/mlp/vocab -> the tensor axis on this mesh)."""
    from jax.sharding import PartitionSpec as P

    lm = _logical_mesh(_SERVE_TP_MESH)
    tp_ax = lm.role_axis("tensor")
    return ShardingSpec(mesh=lm, entries=(
        ("kv_pages", (None, None, "heads", None),
         P(None, None, tp_ax, None)),
        ("wqkv", (None, None, "heads", None),
         P(None, None, tp_ax, None)),
        ("wo", ("heads", None, None), P(tp_ax, None, None)),
        ("w_up", (None, "mlp"), lm.spec(None, "mlp")),
        ("b_up", ("mlp",), lm.spec("mlp")),
        ("w_down", ("mlp", None), lm.spec("mlp", None)),
        ("head", (None, "vocab"), lm.spec(None, "vocab")),
    ))


def _serve_tp_logical_mesh():
    return _logical_mesh(_SERVE_TP_MESH)


# -------------------------------------------------------------- registry


def _make_registry() -> List[Program]:
    progs: List[Program] = []

    # The driver gate lanes (bench.py's composition per lane).
    progs += [
        Program("gate.resnet50", "gate", _image_lane("resnet50")),
        Program("gate.resnet50_win", "gate",
                _image_lane("resnet50", window=4)),
        Program("gate.resnet50_overlap", "gate",
                _image_lane("resnet50", overlap="on")),
        Program("gate.resnet50_zero", "gate",
                _image_lane("resnet50", zero=True)),
        Program("gate.vgg16", "gate", _image_lane("vgg16")),
        Program("gate.inception_v3", "gate",
                _image_lane("inception_v3", image=128)),
        Program("gate.vit_s16", "gate", _image_lane("vit_s16")),
        Program("gate.transformer_lm", "gate", _lm_lane()),
        Program("gate.transformer_lm_fused_ce", "gate",
                _lm_lane(fused_ce=True)),
    ]

    # DistributedOptimizer emission modes, byte-reconciled (HVV105).
    # "shaped": the default threshold puts the whole tree into one
    # bucket, whose members each go in their own shape.
    from horovod_tpu.common.config import DEFAULT_FUSION_THRESHOLD

    for mode, kwargs in (("fused", dict(overlap="off")),
                         ("overlap", dict(overlap="on")),
                         ("shaped", dict(overlap="auto",
                                         threshold=DEFAULT_FUSION_THRESHOLD))):
        build, reconcile = _optimizer_mode(**kwargs)
        progs.append(Program(f"optimizer.{mode}", "optimizer", build,
                             reconcile=reconcile))

    # The hierarchical DP exchange (PR-10): the 2-slice ladder under
    # overlap, and the int8-wire 4-slice two-stage shape — each leg
    # HVV105-reconciled against fusion.hier_bucket_layout.
    for pname, inner, comp in (("dp.hier_overlap", 4, "none"),
                               ("dp.hier_int8", 2, "int8")):
        build, reconcile = _dp_hier_mode(inner=inner,
                                         compression_name=comp)
        progs.append(Program(pname, "dp", build, reconcile=reconcile))

    # All six hand-rolled sharding modules.
    progs += [
        Program("parallel.spmd", "parallel",
                lambda: _build_parallel_spmd()),
        Program("parallel.tp", "parallel",
                lambda: _build_parallel_tp()),
        Program("parallel.pipeline", "parallel",
                lambda: _build_parallel_pipeline()),
        Program("parallel.ulysses", "parallel",
                lambda: _build_parallel_ulysses()),
        Program("parallel.ring_attention", "parallel",
                lambda: _build_parallel_ring_attention()),
        Program("parallel.moe", "parallel",
                lambda: _build_parallel_moe()),
    ]

    # LogicalMesh-composed stacks: the full HVV2xx pass (sharding
    # reconciliation, axis vocabulary, per-module schedule
    # equivalence) over the three canonical 2-axis compositions.
    for pname, factory in (("composed.dp_tp", _composed_dp_tp),
                           ("composed.dp_ulysses", _composed_dp_ulysses),
                           ("composed.tp_pp", _composed_tp_pp)):
        build, shardings, logical_mesh, equivalence = factory()
        progs.append(Program(pname, "composed", build,
                             shardings=shardings,
                             logical_mesh=logical_mesh,
                             equivalence=equivalence))

    # The elastic windowed loop + its donation invariant — at the
    # launch world size AND the post-resize (shrunken-world) batch
    # geometry, so the PR-5 snapshot-in-flight invariant is checked on
    # both sides of a resize (the reshard resume re-jits this same
    # program with the survivors' batch).
    progs.append(Program(
        "elastic.windowed_loop", "elastic",
        lambda: _build_elastic_windowed_loop(),
        forbid_donation=True,
        forbid_donation_why=_ELASTIC_WHY))
    progs.append(Program(
        "elastic.windowed_loop_resized", "elastic",
        lambda: _build_elastic_windowed_loop(per_window=16),
        forbid_donation=True,
        forbid_donation_why=_ELASTIC_WHY + (
            " — resized-world geometry: after a shrink the survivors "
            "carry the lost ranks' share of the global batch, and the "
            "re-jitted window must still never donate")))

    # The serving engine's compiled step + its page-donation invariant,
    # in both decode-attention modes (the paged variant streams pages
    # through the fused kernel READ-ONLY — same invariant class, paged
    # edition).
    progs.append(Program(
        "serve.step", "serve",
        lambda: _build_serve_step(),
        forbid_donation=True,
        forbid_donation_why=_SERVE_WHY))
    progs.append(Program(
        "serve.step_paged", "serve",
        lambda: _build_serve_step(attention="paged"),
        forbid_donation=True,
        forbid_donation_why=_SERVE_WHY))

    # The TP-sharded step (ServeConfig.mesh="dp=1,tp=4"): the same
    # page-donation invariant — shards of a live page on every chip —
    # PLUS the full HVV2xx sharding sweep (declared specs vs the rules
    # table, axis vocabulary, bound LogicalMesh), in both
    # decode-attention modes.
    progs.append(Program(
        "serve.step_tp", "serve",
        lambda: _build_serve_step_tp(),
        forbid_donation=True,
        forbid_donation_why=_SERVE_WHY + (
            " — TP edition: every chip holds a head-shard of each "
            "live page, and donation on ANY shard corrupts the "
            "replicated page table's view"),
        shardings=_serve_tp_shardings,
        logical_mesh=_serve_tp_logical_mesh))
    progs.append(Program(
        "serve.step_tp_paged", "serve",
        lambda: _build_serve_step_tp(attention="paged"),
        forbid_donation=True,
        forbid_donation_why=_SERVE_WHY + (
            " — TP edition, paged kernel per-shard under shard_map "
            "(grid head dim = H/tp)"),
        shardings=_serve_tp_shardings,
        logical_mesh=_serve_tp_logical_mesh))

    # The speculative step (ServeConfig.speculate_k > 0): the draft
    # propose scan + rectangular-causal verify pass, in both
    # decode-attention modes plus the TP-sharded composition. The
    # donation invariant is sharpened here — rejected rows roll back by
    # PAGE ARITHMETIC over the pre-step arrays, so those arrays are the
    # rollback substrate itself.
    _SPEC_WHY = _SERVE_WHY + (
        " — speculative edition: a rejected window's rows roll back "
        "by page arithmetic over the PRE-step pages; donating them "
        "destroys the state a rejection falls back to")
    progs.append(Program(
        "serve.step_spec", "serve",
        lambda: _build_serve_step_spec(),
        forbid_donation=True,
        forbid_donation_why=_SPEC_WHY))
    progs.append(Program(
        "serve.step_spec_paged", "serve",
        lambda: _build_serve_step_spec(attention="paged"),
        forbid_donation=True,
        forbid_donation_why=_SPEC_WHY + (
            " — the draft scan threads pages through its carry, so a "
            "donated pool would alias every scan step's write")))
    progs.append(Program(
        "serve.step_spec_tp", "serve",
        lambda: _build_serve_step_spec_tp(),
        forbid_donation=True,
        forbid_donation_why=_SPEC_WHY + (
            " — TP edition: head-shards of the window's rows live on "
            "every chip"),
        shardings=_serve_tp_shardings,
        logical_mesh=_serve_tp_logical_mesh))

    # The disaggregated pool steps (FleetConfig.pools): the prefill
    # pool's prefill-lane-only tick and the decode pool's pre=None
    # tick, each EXACTLY the program a pool replica runs steady-state.
    # The donation invariant is sharpest across the handoff: between
    # prefill completion and the decode pool's digest-verified admit,
    # the parked pages are the only copy of the request's KV.
    _DISAGG_WHY = _SERVE_WHY + (
        " — disaggregated edition: across the KV handoff the pages "
        "are the ONLY copy of the request's history (parked in the "
        "prefill bay, or just digest-verified into the decode "
        "allocator); a donating step tears the very bytes the wire's "
        "CRC/sha256 discipline promises to deliver")
    progs.append(Program(
        "serve.step_prefill_pool", "serve",
        lambda: _build_serve_step_prefill_pool(),
        forbid_donation=True,
        forbid_donation_why=_DISAGG_WHY))
    progs.append(Program(
        "serve.step_decode_pool", "serve",
        lambda: _build_serve_step_decode_pool(),
        forbid_donation=True,
        forbid_donation_why=_DISAGG_WHY))
    progs.append(Program(
        "serve.step_decode_pool_tp", "serve",
        lambda: _build_serve_step_decode_pool_tp(),
        forbid_donation=True,
        forbid_donation_why=_DISAGG_WHY + (
            " — TP edition: the wire preserves the head-sharded tile "
            "layout, so every chip holds a shard of each imported "
            "page"),
        shardings=_serve_tp_shardings,
        logical_mesh=_serve_tp_logical_mesh))

    return progs


REGISTRY: List[Program] = _make_registry()

#: Programs cheap enough for the fast (tier-1) sweep pin: everything
#: except the big-model gate lanes, whose tracing cost belongs to the
#: full-suite / check.sh --verify gate. The composed stacks trace at
#: toy shapes (plus their per-module reference traces), cheap enough
#: for the fast lane.
FAST_GROUPS = ("optimizer", "dp", "parallel", "composed", "elastic",
               "serve")


def programs(groups=None, names=None) -> List[Program]:
    out = REGISTRY
    if groups:
        out = [p for p in out if p.group in groups]
    if names:
        wanted = set(names)
        missing = wanted - {p.name for p in out}
        if missing:
            known = ", ".join(sorted(p.name for p in REGISTRY))
            raise KeyError(f"unknown program(s) {sorted(missing)}; "
                           f"have: {known}")
        out = [p for p in out if p.name in wanted]
    return out
