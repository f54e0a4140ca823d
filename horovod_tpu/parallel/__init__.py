"""Parallelism strategies over device meshes.

The reference supported exactly one strategy — data parallelism (SURVEY §2.9)
— delegated to MPI/NCCL rings. Here DP is one axis of a general
``jax.sharding.Mesh``; this package adds the TPU-first strategies the
hardware makes natural: tensor parallelism, sequence/context parallelism
(ring attention, Ulysses all-to-all), pipeline parallelism, and expert
parallelism, plus the hierarchical ICI x DCN mesh that replaces the
reference's node-local/cross-node communicator split.
"""

from horovod_tpu.parallel.logical import (  # noqa: F401
    DATA_AXIS,
    DCN_AXIS,
    DEFAULT_RULES,
    ICI_AXIS,
    LogicalMesh,
    bind,
    current_logical_mesh,
    format_mesh_config,
    logical_partition_specs,
    module_axis,
    parse_mesh_config,
)
from horovod_tpu.parallel.spmd import axis_size, spmd, spmd_run  # noqa: F401
from horovod_tpu.parallel.mesh import (  # noqa: F401
    hierarchical_allreduce,
    hierarchical_mesh,
    make_mesh,
)
from horovod_tpu.parallel.ring_attention import ring_attention  # noqa: F401
from horovod_tpu.parallel.ulysses import ulysses_attention  # noqa: F401
from horovod_tpu.parallel.tp import (  # noqa: F401
    column_parallel,
    row_parallel,
    shard_columns,
    shard_rows,
    sum_across,
    tp_mlp,
    tp_region_input,
    tp_region_output,
)
from horovod_tpu.parallel.pipeline import pipeline_apply  # noqa: F401
from horovod_tpu.parallel.moe import (  # noqa: F401
    gated_mlp_grouped,
    route,
    routed_experts,
    update_selection_bias,
)
