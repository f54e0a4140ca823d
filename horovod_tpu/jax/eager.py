"""Process-level eager collectives.

The reference's eager path moved concrete tensors between OS processes over
MPI/NCCL from a background thread (operations.cc:1491-1612). The TPU-native
equivalent moves concrete host arrays between *processes* over the JAX
distributed runtime (ICI within a slice, DCN across slices) — there is no
background thread because JAX dispatch is already asynchronous.

Only used when ``jax.process_count() > 1`` (multi-host); single-process jobs
short-circuit in mpi_ops.py to the reference's size()==1 semantics, and
pure-CPU multi-process jobs use the native core (horovod_tpu.torch).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def process_allreduce(x):
    """Elementwise sum of each process's array."""
    from jax.experimental import multihost_utils

    gathered = multihost_utils.process_allgather(jnp.asarray(x))
    return jnp.sum(gathered, axis=0)


def process_allgather(x):
    """Concatenate each process's array along dim 0 (ragged allowed when
    trailing dims agree, matching reference allgatherv semantics
    operations.cc:843-925)."""
    from jax.experimental import multihost_utils

    gathered = multihost_utils.process_allgather(jnp.asarray(x))
    # process_allgather stacks along a new leading axis when shapes agree.
    return jnp.concatenate(list(gathered), axis=0) if gathered.ndim > jnp.asarray(x).ndim else gathered


def process_broadcast(x, root_rank: int):
    """Every process receives process ``root_rank``'s value.

    A true one-to-all broadcast for any root (``is_source`` selects the
    root), matching MPI_Bcast's O(bytes) per-link cost (reference
    operations.cc:1592-1612). Round-1 version allgathered for non-zero
    roots — O(size x bytes) on DCN — which is the wrong shape at pod scale.
    """
    from jax.experimental import multihost_utils

    x = jnp.asarray(x)
    return multihost_utils.broadcast_one_to_all(
        x, is_source=jax.process_index() == root_rank
    )


# --------------------------------------------------------------------------
# Scalable exchange shapes: alltoall / reducescatter compiled over a
# one-representative-device-per-process mesh. The old eager fallbacks
# (allgather-then-select, full-reduce-then-slice) moved O(size x bytes)
# per rank; these compile the REAL primitive — lax.all_to_all's pairwise
# exchange, lax.psum_scatter's ring — over the process world, so the wire
# cost has the MPI shape (O(bytes) / (n-1)/n bytes per rank) while the
# data plane rides the same distributed runtime as the other eager ops.


def _process_mesh():
    """1-D mesh with ONE representative device per process, process order."""
    import numpy as np
    from jax.sharding import Mesh

    reps = {}
    for d in jax.devices():
        reps.setdefault(d.process_index, d)
    devs = np.array([reps[i] for i in range(jax.process_count())])
    return Mesh(devs, ("proc",))


def _alltoall_on_axis(t, axis, split_axis: int, concat_axis: int):
    """Per-rank alltoall body: scatter dim ``split_axis`` splits, gather
    received splits along ``concat_axis`` (the pairwise-exchange data
    plane; equivalence vs the old allgather-then-select shape is pinned
    in tests/test_collectives.py)."""
    from jax import lax

    return lax.all_to_all(t, axis, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def _reducescatter_on_axis(t, axis):
    """Per-rank reduce-scatter body: this rank's dim-0 stripe of the
    cross-rank sum (the ring's reduce half; equivalence vs the old
    full-reduce-then-slice shape is pinned in tests/test_collectives.py)."""
    from jax import lax

    return lax.psum_scatter(t, axis, scatter_dimension=0, tiled=True)


# (cache_key, shape, dtype) -> compiled program. jit caches on callable
# identity, so the per-call closures below would otherwise retrace and
# recompile EVERY eager exchange — a per-step eager loop must pay trace
# + compile once per shape, then dispatch in microseconds. Bounded: an
# eager loop cycles a handful of shapes; evict oldest past the cap.
_EXCHANGE_CACHE: dict = {}
_EXCHANGE_CACHE_MAX = 64


def _run_over_process_mesh(body, cache_key, x, out_rows_per_proc: bool):
    """Run ``body(local_block)`` as one compiled SPMD program over the
    process mesh: each process contributes its local array as one shard
    of a stacked leading axis, takes back its own output block.
    ``cache_key`` names the exchange (op + static args) so same-shape
    calls reuse the compiled program."""
    from jax.experimental import multihost_utils
    from jax.sharding import PartitionSpec as P

    mesh = _process_mesh()
    g = multihost_utils.host_local_array_to_global_array(x[None], mesh,
                                                         P("proc"))
    out_spec = P("proc")
    key = (cache_key, x.shape, str(x.dtype), mesh.shape["proc"])
    compiled = _EXCHANGE_CACHE.pop(key, None)  # pop+reinsert = LRU touch
    if compiled is None:
        def per_rank(t):
            return body(t[0], "proc")[None] if out_rows_per_proc else body(
                t[0], "proc")

        compiled = jax.jit(jax.shard_map(
            per_rank, mesh=mesh, in_specs=P("proc"), out_specs=out_spec,
            check_vma=False))
    _EXCHANGE_CACHE[key] = compiled
    while len(_EXCHANGE_CACHE) > _EXCHANGE_CACHE_MAX:
        _EXCHANGE_CACHE.pop(next(iter(_EXCHANGE_CACHE)))
    out = compiled(g)
    local = multihost_utils.global_array_to_host_local_array(out, mesh,
                                                             out_spec)
    return local[0] if out_rows_per_proc else local


def process_alltoall(x, split_axis: int = 0, concat_axis: int = 0):
    """Pairwise alltoall across processes: process p's split ``s`` of dim
    ``split_axis`` lands on process ``s``, received splits concatenate
    along ``concat_axis`` in source order — O(bytes) sent and received
    per rank (MPI_Alltoall's shape), vs the old allgather-then-select's
    O(size x bytes)."""
    x = jnp.asarray(x)
    return _run_over_process_mesh(
        lambda t, ax: _alltoall_on_axis(t, ax, split_axis, concat_axis),
        ("alltoall", split_axis, concat_axis), x, out_rows_per_proc=True)


def process_reducescatter(x):
    """Ring reduce-scatter across processes: each process receives its
    dim-0 stripe of the elementwise cross-process SUM — (n-1)/n of the
    tensor bytes per rank, vs the old full-reduce-then-slice's whole-
    tensor allreduce. Caller divides for the averaged variant."""
    x = jnp.asarray(x)
    return _run_over_process_mesh(_reducescatter_on_axis,
                                  ("reducescatter",), x,
                                  out_rows_per_proc=False)
