"""On-device multi-step training windows: amortize host dispatch.

PERF.md's pre-round profiles (2026-08-01, on a backend that no longer
exists) showed a 27-32% host-side gap on short-step models (ResNet-50:
33.8 ms wall vs 24.8 ms device; Inception V3: 32%); on this machine,
with two steps in flight, the gap is 0.04% of device time (PERF.md
section 5; ROADMAP D6 asks whether this module stays). The structural
fix is the same host/device
decoupling the reference got from its background coordinator thread
(``BackgroundThreadLoop``: the training script never blocks on the
exchange) — in XLA form: compile K training steps into ONE program with
``lax.scan``, so the host dispatches once per window and syncs once per
window instead of once per step. This is the standard JAX-on-TPU
training-loop idiom (the scan-based step loops in T5X/MaxText-class
trainers). Not yet run on the chip.

Two layers:

* :func:`windowed` — the pure transform: ``step_fn`` -> a window step
  that scans K stacked batches through it, carrying the train state and
  accumulating metric MEANS on device (one small transfer per window,
  not K).
* :func:`run_steps` — the full loop: stages K-batch windows onto the
  device double-buffered (:func:`horovod_tpu.data.prefetch_windows`, so
  host->device copies of window N+1 overlap compute of window N),
  dispatches one compiled window per K batches with the train state
  donated, and marks window boundaries on the Horovod timeline.

Numerical contract (pinned in tests/test_window.py): a K-step window is
allclose-equivalent to K sequential calls of the same ``step_fn`` —
same RNG folding (the per-step dropout key derives from the carried
``state["step"]``, which the scan advances exactly as sequential calls
do), same parameter/optimizer trajectories, same metric means.
"""

from __future__ import annotations

import functools
from typing import Any, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.parallel.logical import module_axis


def windowed(step_fn, steps_per_dispatch: int):
    """Compile ``steps_per_dispatch`` applications of ``step_fn`` into
    one scanned window step.

    ``step_fn`` must have the training-step signature
    ``(state, batch) -> (new_state, metrics)``. The returned function
    takes ``(state, stacked_batches)`` where every batch leaf carries a
    leading window axis of length K, scans the K steps on device, and
    returns ``(final_state, metric_means)`` — metrics averaged over the
    window on device, so the host sees one small result per window.

    ``steps_per_dispatch == 1`` returns ``step_fn`` unchanged (the
    identity path: no window axis, no scan, bit-identical dispatch).
    """
    k = int(steps_per_dispatch)
    if k < 1:
        raise ValueError(f"steps_per_dispatch must be >= 1, got {k}")
    if k == 1:
        return step_fn
    return _scan_window(step_fn)


def _scan_window(step_fn):
    """The scan form itself: shape-polymorphic in the window length (the
    scan length comes from the stacked input's leading axis, so one
    handle serves full windows and a shorter trailing window alike —
    jit retraces per distinct length)."""

    @functools.wraps(step_fn)
    def window_step(state, stacked_batches):
        state, stacked_metrics = jax.lax.scan(
            lambda carry, batch: step_fn(carry, batch),
            state, stacked_batches)
        metrics = jax.tree_util.tree_map(
            lambda m: jnp.mean(m, axis=0), stacked_metrics)
        return state, metrics

    return window_step


def stack_batches(batches: Iterable):
    """Stack a list of batch pytrees along a new leading window axis
    (device-side ``jnp.stack``; for the host-side double-buffered stager
    use :func:`horovod_tpu.data.prefetch_windows`)."""
    batches = list(batches)
    if not batches:
        raise ValueError("stack_batches needs at least one batch")
    return jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves),
                                  *batches)


def stacked_specs(batch_specs):
    """Shift batch partition specs under the window axis:
    ``P("hvd") -> P(None, "hvd")`` per leaf — the scan axis is
    replicated (every rank walks the same K steps), the batch sharding
    moves to axis 1."""
    return jax.tree_util.tree_map(
        lambda spec: P(None, *spec), batch_specs,
        is_leaf=lambda x: isinstance(x, P))


def run_steps(
    step_fn,
    state,
    batches: Iterable,
    steps_per_dispatch: int = 1,
    *,
    mesh=None,
    axis_name: Optional[str] = None,
    state_specs: Any = P(),
    batch_specs: Any = None,
    metric_specs: Any = P(),
    donate: bool = True,
    prefetch: int = 2,
    sync_each_window: bool = False,
) -> Tuple[Any, List[Any]]:
    """Run ``step_fn`` over ``batches`` in K-step on-device windows.

    The training-loop entry of the window API::

        state, window_metrics = hvd.run_steps(
            train_step, state, batch_iter, steps_per_dispatch=30)

    Per window of K consecutive batches: the batches are stacked on the
    host and staged to the device double-buffered (the stager keeps
    ``prefetch`` windows in flight, so window N+1's host->device copy
    overlaps window N's compute), then ONE jitted+sharded
    ``lax.scan``-of-K-steps program is dispatched with the train state
    donated — one dispatch per window instead of K, which is what
    closes the measured per-step host-dispatch gap (PERF.md pre-round).

    Returns ``(final_state, metrics)`` where ``metrics`` is one pytree
    per window: the on-device metric MEANS over that window's steps
    (with ``steps_per_dispatch == 1``, the raw per-step metrics — the
    identity path, equivalent to calling ``spmd_fn(step_fn)`` in a
    plain Python loop).

    A trailing window shorter than K (when ``len(batches)`` is not a
    multiple of K) runs as a shorter scan — every batch trains, at the
    cost of one extra compile for the tail length.

    ``sync_each_window`` forces a real device sync (and a timeline
    ``WINDOW_SYNC`` span) at every window boundary — for timing
    harnesses; training loops should leave it False and let dispatch
    pipeline across windows.
    """
    from horovod_tpu.common import state as _state
    from horovod_tpu.data.prefetch import prefetch_windows
    from horovod_tpu.parallel.spmd import spmd_fn
    from horovod_tpu.utils import timeline as _tl
    from horovod_tpu.utils.devsync import window_sync

    axis_name = module_axis("data", axis_name)
    if batch_specs is None:
        batch_specs = P(axis_name)
    k = int(steps_per_dispatch)
    if k < 1:
        raise ValueError(f"steps_per_dispatch must be >= 1, got {k}")

    st = _state.global_state()
    if mesh is None:
        st.require_init()
        mesh = st.mesh
    tl = getattr(st, "timeline", None)
    tl_on = tl is not None and tl.enabled

    # Single-spec batch trees ride the stager straight to their mesh
    # layout; pytree-of-specs batches fall back to plain device_put and
    # the dispatch reshards on entry.
    window_batch_specs = stacked_specs(batch_specs) if k > 1 else batch_specs
    sharding = (NamedSharding(mesh, window_batch_specs)
                if isinstance(window_batch_specs, P) else None)

    # ONE dispatch handle per loop: the scan form is shape-polymorphic
    # in the window length, so a trailing window shorter than K rides
    # the same handle (jit retraces once for the tail length — the one
    # extra compile the docstring prices in).
    run = spmd_fn(
        _scan_window(step_fn) if k > 1 else step_fn,
        mesh=mesh,
        axis_name=axis_name,
        in_specs=(state_specs, window_batch_specs),
        out_specs=(state_specs, metric_specs),
        donate_argnums=(0,) if donate else (),
    )

    metrics_out: List[Any] = []
    index = 0
    for window in prefetch_windows(batches, k, size=prefetch,
                                   sharding=sharding):
        length = (1 if k == 1
                  else jax.tree_util.tree_leaves(window)[0].shape[0])
        if tl_on:
            tl.mark_window(index, length)
            tl.start("hvd.window", _tl.WINDOW,
                     args={"window": index, "steps": length,
                           "span": "host_dispatch"})
        try:
            state, metrics = run(state, window)
        finally:
            if tl_on:
                tl.end("hvd.window", _tl.WINDOW)
        if sync_each_window:
            window_sync(state, timeline=tl, steps=length)
        metrics_out.append(metrics)
        index += 1
    return state, metrics_out
