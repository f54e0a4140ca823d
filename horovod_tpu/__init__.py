"""horovod_tpu: a TPU-native distributed training framework.

A ground-up rebuild of the capabilities of Horovod 0.15.2 (reference layout:
horovod/{common,tensorflow,torch,mxnet,keras,spark}) designed for TPU
hardware: SPMD over ``jax.sharding.Mesh`` device meshes, XLA collectives on
the ICI instead of MPI/NCCL rings, trace-time tensor fusion instead of a
background coordinator thread, and Pallas kernels for the hot ops.

Bindings:

* ``horovod_tpu.jax``   — flagship (also re-exported at the top level)
* ``horovod_tpu.torch`` — PyTorch CPU binding over the native C++ core
* ``horovod_tpu.tf``    — sessionless TensorFlow binding over the same core
* ``horovod_tpu.flax``  — training-loop callbacks (keras-binding analogue)
* ``horovod_tpu.parallel`` — mesh construction, TP/PP/SP/EP sharding,
  ring attention, sequence parallelism (beyond-reference, TPU-first)
"""

from horovod_tpu.version import __version__
from horovod_tpu.jax import *  # noqa: F401,F403 — flagship binding at top level
from horovod_tpu.jax import __all__ as _jax_all

__all__ = ["__version__"] + list(_jax_all)
