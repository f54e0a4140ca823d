"""Compute ops: Pallas TPU kernels and XLA-fused building blocks."""

from horovod_tpu.ops.attention import (attend, attention_plan,
                                       dot_product_attention,
                                       flash_attention, flash_grid_info)
from horovod_tpu.ops.conv_bn import (conv1x1_bn_stats,
                                     conv1x1_prologue_bn_stats)
from horovod_tpu.ops.xent import (fused_cross_entropy,
                                  tp_vocab_cross_entropy)

__all__ = [
    "attend",
    "attention_plan",
    "dot_product_attention",
    "flash_attention",
    "flash_grid_info",
    "conv1x1_bn_stats",
    "conv1x1_prologue_bn_stats",
    "fused_cross_entropy",
    "tp_vocab_cross_entropy",
]
