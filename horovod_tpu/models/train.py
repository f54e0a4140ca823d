"""Data-parallel training-step builder.

This is the TPU-native shape of "one training step, PyTorch" from the
reference (SURVEY §3.2; reference torch/__init__.py:95-151): forward, local
backward, cross-rank fused gradient allreduce, optimizer update. Under XLA
the whole sequence is one compiled program per chip; the reference's
background-thread negotiation and per-gradient hooks collapse into the
trace-time bucket fusion in :mod:`horovod_tpu.jax.fusion`.

Usage::

    state, optimizer = create_train_state(rng, model, optax.sgd(0.1), sample)
    step = make_train_step(model, optimizer)          # pure fn, jit/shard_map-able
    state, metrics = hvd.spmd_run(step, state, batch,
                                  in_specs=(P(), P("hvd")),
                                  out_specs=(P(), P()))

``create_train_state`` returns the (DistributedOptimizer-wrapped) optimizer
alongside the state; pass that same wrapped optimizer to
``make_train_step`` so ``opt_state`` and the update chain match.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax.core import FrozenDict, freeze, unfreeze

from horovod_tpu.common.state import current_spmd_axis
from horovod_tpu.jax import mpi_ops
from horovod_tpu.jax.compression import Compression
from horovod_tpu.jax.optimizer import DistributedOptimizer
from horovod_tpu.utils import timeline


def cross_entropy_loss(logits, labels) -> jnp.ndarray:
    """Mean softmax cross-entropy against integer labels, in fp32."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    return -jnp.mean(jnp.sum(onehot * logp, axis=-1))


class TrainState(Dict[str, Any]):
    """A plain pytree-of-arrays training state: params, batch_stats,
    opt_state, step and, for a model that keeps state which is no parameter
    and no batch statistic (a sparse layer's selection bias), buffers. Dict
    subclass so it flows through jax transforms."""


jax.tree_util.register_pytree_node(
    TrainState,
    lambda s: (tuple(s[k] for k in sorted(s)), tuple(sorted(s))),
    lambda keys, vals: TrainState(zip(keys, vals)),
)


def create_train_state(
    rng,
    model,
    optimizer: optax.GradientTransformation,
    sample_input,
    distributed: bool = True,
    compression=Compression.none,
    backward_passes_per_step: int = 1,
    zero: bool = False,
    overlap: Optional[str] = None,
    hierarchical: Optional[str] = None,
) -> Tuple[TrainState, optax.GradientTransformation]:
    """Initialize params/batch_stats and the (wrapped) optimizer state.

    ``distributed=True`` wraps ``optimizer`` in :func:`DistributedOptimizer`
    — the one-line change the reference advertised
    (reference README.md:96-141).

    ``overlap`` (auto|on|off; default HOROVOD_OVERLAP) selects the
    backward-overlapped bucket schedule for the fused gradient exchange
    (:mod:`horovod_tpu.jax.fusion`): dispatch shape only, numerics are
    bit-identical across modes. Ignored with ``zero=True`` (the ZeRO
    path is already reduce-scatter shaped).

    ``hierarchical`` (auto|on|off; default HOROVOD_HIERARCHICAL) runs
    each gradient bucket as the two-level ICI/DCN ladder; with
    ``compression=Compression.int8``/``.fp8`` the DCN leg is quantized
    and the optimizer state carries rank-local error-feedback
    residuals — feed the state through :func:`state_partition_specs`
    (it maps them to ``P("hvd")``).

    ``zero=True`` uses ZeRO-1 optimizer-state sharding instead
    (:mod:`horovod_tpu.jax.zero`): same wire bytes, optimizer state and
    update FLOPs divided by the axis size. Feed the resulting state through
    the step with :func:`state_partition_specs` so the opt-state leaves are
    physically sharded.
    """
    with timeline.span("hvd.lane.model_init"):
        # one program: run eagerly, flax's init is the whole forward pass
        # operation by operation, a program each (a minute and a half of a
        # sparse decoder's set-up), for values that the draws do not need
        variables = jax.jit(
            lambda rng, sample: model.init(rng, sample, train=False)
        )(rng, sample_input)
    params = variables["params"]
    # Deep-freeze so the state's pytree TYPES are stable against what
    # the step emits (flax's mutable= collection comes back as a plain
    # dict on some versions) — lax.scan window loops require the carry
    # structure to match exactly, not just leaf-wise.
    batch_stats = freeze(variables.get("batch_stats", FrozenDict()))
    # State that the forward pass reads and the step moves by a rule of its
    # own, outside the optimizer; only a model that has some gets the slot.
    buffers = unfreeze(variables.get("buffers", {}))
    if zero:
        from horovod_tpu.jax.zero import sharded_distributed_optimizer

        optimizer = sharded_distributed_optimizer(
            optimizer, compression=compression
        )
        if backward_passes_per_step > 1:
            optimizer = optax.MultiSteps(
                optimizer, every_k_schedule=backward_passes_per_step
            ).gradient_transformation()
    elif distributed:
        optimizer = DistributedOptimizer(
            optimizer,
            compression=compression,
            backward_passes_per_step=backward_passes_per_step,
            overlap=overlap,
            hierarchical=hierarchical,
        )
    with timeline.span("hvd.lane.train_state"):
        opt_state = optimizer.init(params)
        state = TrainState(
            params=params,
            batch_stats=batch_stats,
            opt_state=opt_state,
            step=jnp.zeros((), jnp.int32),
        )
        if buffers:
            state["buffers"] = buffers
    return state, optimizer


def apply_gradients(
    optimizer: optax.GradientTransformation,
    state: TrainState,
    grads,
    batch_stats=None,
    buffers=None,
) -> TrainState:
    """The shared update tail of every training step: optimizer update
    (the DistributedOptimizer/ZeRO wrapper performs the fused cross-rank
    gradient exchange here), parameter apply, state repack with the step
    counter advanced. ``batch_stats`` and ``buffers`` replace the state's
    where the step made new ones."""
    with jax.named_scope(timeline.UPDATE):
        updates, new_opt_state = optimizer.update(
            grads, state["opt_state"], state["params"]
        )
        params = optax.apply_updates(state["params"], updates)
    new_state = TrainState(
        state,
        params=params,
        batch_stats=state["batch_stats"] if batch_stats is None else batch_stats,
        opt_state=new_opt_state,
        step=state["step"] + 1,
    )
    if buffers is not None:
        new_state["buffers"] = buffers
    return new_state


def read_before_update(state: TrainState, reads):
    """Order a step's read-outs (loss, metrics) before its update.

    The step's state is donated, so the update writes the new parameters
    into the old ones' buffers, and only data dependence orders that write
    after a read. XLA:TPU's rematerialisation does not keep to it: near the
    HBM limit it recomputed the logits for the reported loss at the end of
    the program, from the ``lm_head`` buffer the update had already
    rewritten (the dense seq-2048 bs-8 LM step on a v5e reported 10.7850
    for a loss of 10.8904; PERF.md "Bring-up"). The barrier hands the state
    to the update only once ``reads`` exist, so nothing that produces them
    can be scheduled behind an in-place write. An identity otherwise."""
    with jax.named_scope(timeline.METRICS):
        reads, state = jax.lax.optimization_barrier((reads, state))
    return state, reads


def make_train_step(model, optimizer: optax.GradientTransformation, average_loss: bool = True):
    """Build the per-rank SPMD training step.

    The returned function takes ``(state, batch)`` where ``batch`` is the
    *per-rank* shard ``{"image": ..., "label": ...}``, and returns
    ``(new_state, metrics)``. Collectives inside (gradient psum from
    DistributedOptimizer, loss pmean) activate when run under
    ``hvd.spmd_run``; outside SPMD (single process eager) they are
    identities, matching the reference's size()==1 degradation.
    """

    def loss_fn(params, batch_stats, batch, rng):
        with jax.named_scope(timeline.FORWARD):
            outputs, mutated = model.apply(
                {"params": params, "batch_stats": batch_stats},
                batch["image"],
                train=True,
                mutable=["batch_stats"],
                rngs={"dropout": rng},
            )
        with jax.named_scope(timeline.LOSS):
            loss = cross_entropy_loss(outputs, batch["label"])
        # freeze: scan-carry type stability (see create_train_state).
        return loss, (freeze(mutated.get("batch_stats", FrozenDict())),
                      outputs)

    def train_step(state, batch):
        # Deterministic per-step dropout key, decorrelated across ranks
        # under SPMD (each rank folds in its axis index).
        rng = jax.random.fold_in(jax.random.PRNGKey(0), state["step"])
        axis = current_spmd_axis()
        if axis is not None:
            rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))
        (loss, (new_stats, logits)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state["params"], state["batch_stats"], batch, rng
        )
        with jax.named_scope(timeline.METRICS):
            accuracy = jnp.mean((jnp.argmax(logits, -1) == batch["label"]).astype(jnp.float32))
            if average_loss:
                loss = mpi_ops.allreduce(loss, average=True, name="train.loss")
                accuracy = mpi_ops.allreduce(accuracy, average=True, name="train.accuracy")
        state, metrics = read_before_update(
            state, {"loss": loss, "accuracy": accuracy})
        new_state = apply_gradients(optimizer, state, grads,
                                    batch_stats=new_stats)
        return new_state, metrics

    return train_step


def lm_logits_rows(model, tokens: int, fused_ce: bool = False) -> int:
    """Rows of float32 logits the loss of :func:`make_lm_train_step` holds at
    a time for ``tokens`` tokens a chip: all of them, or one chunk of
    ``ops/xent.py`` where the loss is chunked (``fused_ce``, or a model with
    exits)."""
    from horovod_tpu.ops.xent import T_CHUNK

    chunked = fused_ce or getattr(model, "exit_beta", None) is not None
    return min(tokens, T_CHUNK) if chunked else tokens


def make_lm_train_step(model, optimizer: optax.GradientTransformation, *,
                       fused_ce: bool = False, bias_coeff=None):
    """Build the per-rank SPMD training step of a causal language model.

    The returned function takes ``(state, batch)`` where ``batch`` is the
    *per-rank* shard ``{"tokens": [B, L] int32}`` and returns ``(new_state,
    loss)``: the mean next-token cross-entropy of this rank's shard, in
    float32, a scalar. ``fused_ce`` takes the loss through
    :func:`horovod_tpu.ops.xent.fused_cross_entropy`, so the ``[B, L, vocab]``
    float32 logits never exist. A model with exits (``exit_beta``: the looped
    LM of :mod:`horovod_tpu.models.decoder`) has one loss, the exit-weighted
    :func:`~horovod_tpu.models.decoder.exit_loss` over the exit states and
    gate logits it hands out, which is chunked by itself. A model that keeps
    ``buffers`` (a sparse
    layer's selection bias, :mod:`horovod_tpu.models.decoder`) has them read
    by the forward pass, which writes the step's expert counts beside them;
    ``bias_coeff`` is the step of the balancing rule that then moves the bias.
    """

    exit_beta = getattr(model, "exit_beta", None)
    if exit_beta is not None and fused_ce:
        raise ValueError("fused_ce chooses between two losses of a one-exit "
                         "LM; the exit loss is chunked already")

    # the name is the handle's in the spans and the profile (``step_fn#n``,
    # ``jit_step_fn``), as the lanes have recorded it so far
    def step_fn(state, batch):
        tokens = batch["tokens"]
        # A sparse layer's state (its selection bias): read by the forward
        # pass, which writes the step's expert counts beside it.
        buffers = state.get("buffers")

        def apply(params, **kw):
            if buffers is None:
                return model.apply({"params": params}, tokens, train=False,
                                   **kw), None
            out, wrote = model.apply(
                {"params": params, "buffers": buffers}, tokens, train=False,
                mutable=["buffers"], **kw)
            return out, wrote["buffers"]

        if exit_beta is not None:
            from horovod_tpu.models import decoder

            def loss_fn(params):
                with jax.named_scope(timeline.FORWARD):
                    (exits, gates), wrote = apply(params, return_hidden=True)
                with jax.named_scope(timeline.LOSS):
                    return decoder.exit_loss(
                        exits, gates, params["lm_head"]["kernel"], tokens,
                        exit_beta), wrote
        elif fused_ce:
            # Chunked fused loss (ops/xent.py): the [B, L, vocab] fp32
            # logits tensor — the step's largest single HBM sink —
            # never materializes; the vocab projection's gradient comes
            # out of the same scan.
            from horovod_tpu.models.decoder import loss_head
            from horovod_tpu.ops.xent import fused_cross_entropy

            def loss_fn(params):
                with jax.named_scope(timeline.FORWARD):
                    hidden, wrote = apply(params, return_hidden=True)
                with jax.named_scope(timeline.LOSS):
                    e = hidden.shape[-1]
                    h = hidden[:, :-1].reshape(-1, e).astype(jnp.float32)
                    # the head's kernel, or the embedding's transpose where
                    # the model ties them; the logits' divisor on h
                    kernel, divisor = loss_head(model, params)
                    if divisor != 1.0:
                        h = h / divisor
                    wv = kernel.astype(jnp.float32)
                    return fused_cross_entropy(
                        h, wv, tokens[:, 1:].reshape(-1)), wrote
        else:
            def loss_fn(params):
                with jax.named_scope(timeline.FORWARD):
                    logits, wrote = apply(params)
                with jax.named_scope(timeline.LOSS):
                    logp = jax.nn.log_softmax(
                        logits[:, :-1].astype(jnp.float32))
                    tgt = tokens[:, 1:]
                    nll = -jnp.take_along_axis(logp, tgt[..., None], -1)
                    return jnp.mean(nll), wrote

        (loss, wrote), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["params"])
        if wrote is not None:
            from horovod_tpu.models import decoder

            # summed over the data axis; one chip's counts are the step's
            axis = current_spmd_axis()
            if axis is not None and jax.lax.axis_size(axis) == 1:
                axis = None
            with jax.named_scope(timeline.UPDATE):
                wrote = decoder.update_buffers(wrote, bias_coeff, axis)
        state, loss = read_before_update(state, loss)
        return apply_gradients(optimizer, state, grads, buffers=wrote), loss

    return step_fn


def make_windowed_train_step(model, optimizer: optax.GradientTransformation,
                             steps_per_dispatch: int,
                             average_loss: bool = True):
    """Window-loop form of :func:`make_train_step`: K steps compiled
    into ONE ``lax.scan`` program (:mod:`horovod_tpu.jax.window`), so
    the host dispatches once per window instead of once per step — the
    fix for the measured 27-32% host-dispatch gap on short-step models
    (PERF.md round 5).

    The returned function takes ``(state, stacked_batches)`` where every
    batch leaf carries a leading window axis of length
    ``steps_per_dispatch`` (stage them with
    :func:`horovod_tpu.data.prefetch_windows`), and returns
    ``(new_state, metric_means)``. ``steps_per_dispatch=1`` degrades to
    exactly :func:`make_train_step`'s per-step form. For the full
    stage-and-dispatch loop use ``hvd.run_steps`` directly::

        step = make_train_step(model, optimizer)
        state, metrics = hvd.run_steps(step, state, batch_iter,
                                       steps_per_dispatch=30)
    """
    from horovod_tpu.jax.window import windowed

    return windowed(make_train_step(model, optimizer, average_loss),
                    steps_per_dispatch)


def state_partition_specs(state: TrainState):
    """Partition-spec pytree for a :class:`TrainState`: everything
    replicated except the rank-sharded optimizer-state vectors —
    ZeRO-sharded flats and hierarchical error-feedback residuals — which
    shard over the data axis resolved through the bound
    :class:`~horovod_tpu.parallel.logical.LogicalMesh` rules table
    (legacy ``P("hvd")`` when none is bound). Pass as both ``in_specs``
    and the state half of ``out_specs`` when training with
    ``create_train_state(..., zero=True)`` or with a low-bit DCN wire
    codec (``compression=Compression.int8`` / ``.fp8`` +
    hierarchical)."""
    import jax as _jax
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.jax import zero as _zero
    from horovod_tpu.jax.optimizer import (
        _AllreduceState,
        ef_state_partition_specs,
    )
    from horovod_tpu.parallel.logical import module_axis

    data_axis = module_axis("data")

    def spec_for(node):
        if isinstance(node, _zero.ZeroState):
            return _zero.state_partition_specs(node, axis_name=data_axis)
        if isinstance(node, _AllreduceState):
            return ef_state_partition_specs(node, axis_name=data_axis)
        return P()

    opt_spec = _jax.tree_util.tree_map(
        spec_for, state["opt_state"],
        is_leaf=lambda n: isinstance(n, (_zero.ZeroState,
                                         _AllreduceState)))
    return TrainState(
        {k: P() for k in state},
        opt_state=opt_spec,
    )


def make_eval_step(model):
    """Per-rank evaluation step returning summed (correct, count) so the
    caller can allreduce totals (the reference's metric-average pattern,
    examples/pytorch_mnist.py:120-133)."""

    def eval_step(state, batch):
        logits = model.apply(
            {"params": state["params"], "batch_stats": state["batch_stats"]},
            batch["image"],
            train=False,
        )
        loss = cross_entropy_loss(logits, batch["label"])
        correct = jnp.sum((jnp.argmax(logits, -1) == batch["label"]).astype(jnp.float32))
        return {"loss": loss, "correct": correct, "count": jnp.asarray(batch["label"].shape[0], jnp.float32)}

    return eval_step
