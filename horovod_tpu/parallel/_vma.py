"""Varying-manual-axes (vma) plumbing for shard_map's static checker.

Under ``jax.shard_map(..., check_vma=True)`` (the default) every value
inside the region is typed with the set of mesh axes it varies over, and
scan carries / custom-VJP rules must produce exactly-matching types.
These helpers mark values as varying to satisfy the checker; they are
no-ops outside shard_map and under ``check_vma=False`` (``lax.pcast``
is identity-valued — it only changes the static type).
"""

from __future__ import annotations

import jax
from jax import lax


def vma_of(*arrays) -> frozenset:
    """Union of the varying mesh axes of ``arrays`` (empty outside
    shard_map / on non-traced values)."""
    union: frozenset = frozenset()
    for a in arrays:
        try:
            union = union | jax.typeof(a).vma
        except (AttributeError, TypeError):
            pass
    return union


def vma_checking() -> bool:
    """Whether the enclosing shard_map traces with check_vma=True.

    jax exposes the regional setting through its config during tracing
    (the same flag Pallas consults). There is NO safe silent fallback:
    the typed and untyped gradient regimes need opposite reductions
    (see :func:`reduce_cotangent`), so guessing wrong silently scales
    gradients by the axis size — if a jax upgrade moves the internal,
    fail loudly here instead. Pinned by
    tests/test_parallel.py::test_vma_checking_tracks_region."""
    from jax._src import config as jax_config

    flag = getattr(jax_config, "_check_vma", None)
    if flag is None:
        raise RuntimeError(
            "jax no longer exposes jax._src.config._check_vma; "
            "horovod_tpu.parallel._vma.vma_checking must be updated for "
            "this jax version (guessing would silently mis-scale "
            "gradients)")
    return bool(flag.value)


def reduce_cotangent(g, axis: str, mean: bool, invariant_loss: bool = False):
    """Reduce a replicated parameter's cotangent over ``axis``,
    correctly in BOTH shard_map gradient regimes (all cases measured in
    __graft_entry__'s closed-form gate work).

    Untyped (check_vma=False): the backward leaves this rank's partial
    in the cotangent regardless of the loss's form — apply the
    psum/pmean ourselves.

    Typed (check_vma=True): jax's machinery already reduced over every
    axis the param is invariant on, but WHAT is in hand depends on the
    loss the caller differentiated (``invariant_loss``):

    * loss already collectively meaned over ``axis`` (e.g. wrapped in
      ``lax.pmean`` inside the loss fn) -> the cotangent IS the exact
      mean-loss gradient: identity.
    * loss varying per rank (no collective inside) -> the cotangent is
      the gradient of the rank-SUM: a mean still needs the /n.

    A cotangent still varying over ``axis`` is genuinely per-rank in
    either regime — reduce it ourselves.
    """
    if not vma_checking() or axis in vma_of(g):
        return lax.pmean(g, axis) if mean else lax.psum(g, axis)
    if invariant_loss:
        return g
    return g / lax.axis_size(axis) if mean else g


def scale_sharded_cotangent(g, axis: str, invariant_loss: bool = False):
    """Normalize an axis-SHARDED param's cotangent toward the MEAN of
    the per-rank loss terms.

    No collective belongs here (ranks hold different shards — e.g.
    different experts; the backward all_to_all already routed every
    rank's contribution to the owner); only the scale differs by
    regime × loss form. The cotangent is the n-times-counted SUM of the
    per-rank terms — divide by the axis size — EXCEPT in the typed
    regime with a loss the caller already collectively meaned
    (``invariant_loss=True``), where it is the exact mean-loss gradient
    already. All cases measured in __graft_entry__'s EP closed-form
    gate and tests/test_parallel_lm.py's MoE-vs-dense check."""
    if invariant_loss and vma_checking():
        return g
    return g / lax.axis_size(axis)


def match_vma(x, *refs):
    """Mark ``x`` varying over every axis the ``refs`` vary over.

    The canonical use is typing a ``jnp.zeros`` initial scan carry to
    match the loop body's output (the checker requires carry-in ==
    carry-out types)."""
    missing = vma_of(*refs) - vma_of(x)
    if missing:
        x = lax.pcast(x, tuple(missing), to="varying")
    return x
