"""Device-synchronization helpers for timing harnesses.

JAX dispatch is asynchronous: a timed region must end by waiting for the
device. ``jax.block_until_ready`` does that; :func:`force_device_sync`
waits by pulling one scalar to the host, which also yields a cheap
checksum. Timing harnesses call it once after warm-up and
``block_until_ready`` per timed window (tools/hvdlint HVD001 accepts
either).
"""

from __future__ import annotations


def force_device_sync(tree) -> float:
    """Pull one scalar off-device from any array leaf of ``tree``.

    Accepts a pytree (train state, grad tuple, single array). Returns
    the pulled scalar (summed in f32) so callers can also use it as a
    cheap checksum. No-op returning 0.0 when the tree has no array
    leaves.
    """
    import jax
    import jax.numpy as jnp

    leaves = [l for l in jax.tree_util.tree_leaves(tree)
              if hasattr(l, "dtype")]
    if not leaves:
        return 0.0
    leaf = leaves[0]
    if getattr(leaf, "is_fully_addressable", True) is False:
        # Multi-host: a global jax.Array spanning processes cannot be
        # consumed eagerly (jnp.sum raises on non-fully-addressable
        # input): pull this process's first addressable shard instead.
        shards = leaf.addressable_shards
        if not shards:
            return 0.0
        leaf = shards[0].data
    return float(jnp.sum(leaf.astype(jnp.float32)))


def window_sync(tree, timeline=None, track: str = "hvd.window",
                steps=None) -> float:
    """One device sync at a multi-step window boundary.

    ``block_until_ready`` + the scalar pull of
    :func:`force_device_sync`, with the whole span recorded on the Horovod
    timeline as ``WINDOW_SYNC`` when one is active — profiles of the
    window loop (horovod_tpu/jax/window.py) then attribute host time to
    dispatch vs boundary sync even though K steps share one program.
    Returns the pulled checksum scalar.
    """
    import jax

    tl_on = timeline is not None and getattr(timeline, "enabled", False)
    if tl_on:
        from horovod_tpu.utils.timeline import WINDOW_SYNC

        timeline.start(track, WINDOW_SYNC,
                       args=None if steps is None else {"steps": steps})
    try:
        jax.block_until_ready(tree)
        return force_device_sync(tree)
    finally:
        if tl_on:
            timeline.end(track, WINDOW_SYNC)
