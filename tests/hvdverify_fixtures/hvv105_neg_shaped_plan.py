"""HVV105 negative: the SHAPED form of a multi-member bucket — both
leaves pack into one bucket of the plan, and the flat path reduces each
in its own shape: two psums under the bucket's ``hvd_allreduce_*`` scope
whose payloads sum to the bucket's bytes. The reconciliation must accept
them as that bucket (no entry carries the bucket's bytes alone, and
nothing is padded, scattered or gathered)."""

import jax.numpy as jnp
from jax import lax  # noqa: F401

from tests.hvdverify_fixtures._common import P, f32, mesh, shmap

EXPECT = ()

_THRESHOLD = 1 << 20  # both leaves pack into ONE bucket


def _leaves():
    import jax

    return [jax.ShapeDtypeStruct((16, 130), jnp.float32),
            jax.ShapeDtypeStruct((64,), jnp.float32)]


def RECONCILE():
    from tools.hvdverify.rules import ReconcileSpec

    return ReconcileSpec(leaves=_leaves(), threshold=_THRESHOLD,
                         axis_size=8)


def build():
    from horovod_tpu.common import state as _state
    from horovod_tpu.jax.fusion import fused_reduce

    import horovod_tpu.jax as hvd

    hvd.init()

    def exchange(a, b):
        tok = _state.set_spmd_axis("hvd")
        try:
            return tuple(fused_reduce([a, b], average=True,
                                      fusion_threshold=_THRESHOLD,
                                      overlap="on", name="grads"))
        finally:
            _state.reset_spmd_axis(tok)

    fn = shmap(exchange, mesh(hvd=8), in_specs=(P(), P()),
               out_specs=(P(), P()))
    return fn, (f32(16, 130), f32(64))
