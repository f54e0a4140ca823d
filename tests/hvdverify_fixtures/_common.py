"""Shared plumbing for the hvdverify fixture corpus.

Each fixture module defines:

* ``build() -> (fn, args)`` — a traced program for
  :func:`tools.hvdverify.verify` (args may be ShapeDtypeStructs);
* ``EXPECT`` — tuple of rule ids the verifier must fire (empty and the
  filename carries ``_neg_`` for negatives);
* optional ``FORBID_DONATION`` (the elastic invariant) and
  ``RECONCILE`` (a zero-arg callable returning a ReconcileSpec).

Fixtures trace over sub-meshes of the test harness's 8-device virtual
CPU mesh (tests/conftest.py).
"""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P  # noqa: F401  (re-export)


def mesh(**axes):
    """A named CPU mesh over the first prod(sizes) virtual devices."""
    from horovod_tpu.parallel.mesh import make_mesh

    n = 1
    for v in axes.values():
        n *= v
    return make_mesh(dict(axes), devices=jax.devices()[:n])


def shmap(fn, m, in_specs, out_specs):
    """Raw shard_map with the varying-axes checker off (these
    rank-programs are deliberately rank-varying — hvdverify judges the
    schedule, not the replication types)."""
    return jax.shard_map(fn, mesh=m, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)
