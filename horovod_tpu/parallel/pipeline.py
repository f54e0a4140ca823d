"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh axis.

Beyond-reference capability (SURVEY §2.9: no stage scheduling anywhere in
the reference). SPMD formulation: every chip runs the same program; chip
``r`` of the ``"pp"`` axis applies stage ``r``; activations hop to the
next stage with ``lax.ppermute`` each tick. With M microbatches and P
stages the schedule runs M + P - 1 ticks (the classic GPipe bubble of
(P-1)/(M+P-1)); ICI transfers overlap the next tick's compute.

Stage weights are passed stacked over the leading axis and sharded with
``in_specs=P("pp")`` so each chip holds only its stage.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.parallel.logical import module_axis


def pipeline_apply(stage_fn: Callable, stage_params: Any, x,
                   axis: Optional[str] = None, remat: bool = False):
    """Run a P-stage pipeline over microbatches inside shard_map.

    Args:
      stage_fn: ``(params_for_stage, activation) -> activation`` — the same
        callable for every stage (heterogeneous stages: dispatch on a
        param field). Activation shape must be stage-invariant.
      stage_params: this chip's stage weights (pass stacked [P, ...] with
        ``P("pp")`` in_specs; shard_map strips the leading axis — if the
        per-chip view keeps a leading singleton, it is squeezed).
      x: this call's microbatch stack [M, ...micro_shape] (replicated).
      remat: rematerialize each stage application in the backward pass
        (``jax.checkpoint``). Under autodiff the schedule stores one
        activation per tick; remat drops the intra-stage intermediates
        and recomputes them, cutting pipeline activation memory to
        ~O(ticks x activation) — the TPU-idiomatic answer to 1F1B's
        memory goal (trade FLOPs for HBM, keep the one-program SPMD
        schedule).

    Returns [M, ...out_shape]: outputs of the final stage, replicated via
    a final broadcast psum so every chip returns the same value.
    """
    axis = module_axis("stage", axis)
    if remat:
        stage_fn = jax.checkpoint(stage_fn)
    size = lax.axis_size(axis)
    rank = lax.axis_index(axis)
    M = x.shape[0]

    params = stage_params
    leaves = jax.tree_util.tree_leaves(params)
    if leaves and all(l.shape[:1] == (1,) for l in leaves):
        params = jax.tree_util.tree_map(lambda l: l[0], params)

    perm = [(i, (i + 1) % size) for i in range(size)]
    micro_shape = x.shape[1:]
    n_ticks = M + size - 1

    def tick(t, carry):
        current, outputs = carry
        # Stage 0 injects microbatch t (while t < M); other stages use the
        # activation received from the previous stage.
        inject = jnp.where(t < M, t, M - 1)
        current = jnp.where(rank == 0, x[inject], current)
        result = stage_fn(params, current)
        # The last stage emits microbatch t - (P - 1) at tick t.
        out_idx = t - (size - 1)
        emit = jnp.logical_and(rank == size - 1, out_idx >= 0)
        safe_idx = jnp.clip(out_idx, 0, M - 1)
        updated = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(emit, result,
                               lax.dynamic_index_in_dim(outputs, safe_idx,
                                                        keepdims=False)),
            safe_idx, axis=0)
        outputs = updated
        # Hop activations forward along the ring.
        current = lax.ppermute(result, axis, perm)
        return current, outputs

    from horovod_tpu.parallel._vma import match_vma

    # Zero-init carries typed exactly as the loop body types them under
    # check_vma=True: varying over the stage axis (the rank-selected
    # inject), over whatever the input varies over, and over whatever
    # the STAGE'S OUTPUT varies over — not over every axis a weight is
    # sharded on. A tp-sharded stage ends in a psum over tp, so its
    # output (and this function's result) is tp-invariant, and typing
    # the carry from the weights would make the caller's out_specs lie.
    current0 = match_vma(jnp.zeros(micro_shape, x.dtype), x, rank)
    current0 = match_vma(current0,
                         jax.eval_shape(stage_fn, params, current0))
    outputs0 = match_vma(jnp.zeros((M,) + micro_shape, x.dtype), current0)
    _, outputs = lax.fori_loop(0, n_ticks, tick, (current0, outputs0))

    # Only the last stage holds real outputs; replicate them to all chips
    # (masked psum = broadcast from the last stage). The sum rides the
    # exact-VJP conjugate: a raw psum would apply psum again in its
    # transpose and scale every upstream gradient by the stage count
    # (see parallel/tp.py tp_region_output; grad test
    # test_parallel.py::TestPipeline::test_gradients_match_sequential).
    from horovod_tpu.parallel.tp import sum_across

    mask = (rank == size - 1).astype(outputs.dtype)
    return sum_across(outputs * mask, axis)
