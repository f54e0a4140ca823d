"""Model zoo used by the examples, benchmarks, and tests.

Families mirror the reference's published benchmark set (Inception V3,
ResNet, VGG — reference docs/benchmarks.md:5-6) plus the long-context
Transformer LM this rebuild adds as a first-class workload.
"""

from horovod_tpu.models.inception import InceptionV3
from horovod_tpu.models.mnist import MNISTNet
from horovod_tpu.models.resnet import (
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)
from horovod_tpu.models.resnet import _FAMILY as _RESNET_FAMILY
from horovod_tpu.models.train import (
    TrainState,
    apply_gradients,
    create_train_state,
    cross_entropy_loss,
    lm_logits_rows,
    make_eval_step,
    make_lm_train_step,
    make_train_step,
    make_windowed_train_step,
    read_before_update,
    state_partition_specs,
)
from horovod_tpu.models import decoder, parallel_lm
from horovod_tpu.models.decoder import LoopedDecoderLM, SparseDecoderLM
from horovod_tpu.models.transformer import TransformerBlock, TransformerLM
from horovod_tpu.models.vgg import VGG, VGG11, VGG13, VGG16, VGG19
from horovod_tpu.models.vit import ViT_B16, ViT_S16, VisionTransformer

_FAMILY = dict(_RESNET_FAMILY)
_FAMILY.update({
    "vgg11": VGG11,
    "vgg13": VGG13,
    "vgg16": VGG16,
    "vgg19": VGG19,
    "inception_v3": InceptionV3,
    "inception3": InceptionV3,
    "transformer_lm": TransformerLM,
    "moe_lm": SparseDecoderLM,
    "looped_lm": LoopedDecoderLM,
    "vit_s16": ViT_S16,
    "vit_b16": ViT_B16,
})


def build(name: str, **kwargs):
    """Construct any zoo model by torchvision-style name (the reference
    benchmark selected models via ``getattr(torchvision.models, ...)``,
    examples/pytorch_synthetic_benchmark.py:55)."""
    try:
        return _FAMILY[name.lower()](**kwargs)
    except KeyError:
        raise ValueError(
            f"Unknown model {name!r}; have {sorted(_FAMILY)}") from None


__all__ = [
    "MNISTNet",
    "ResNet",
    "ResNet18",
    "ResNet34",
    "ResNet50",
    "ResNet101",
    "ResNet152",
    "VGG",
    "VGG11",
    "VGG13",
    "VGG16",
    "VGG19",
    "InceptionV3",
    "TransformerBlock",
    "TransformerLM",
    "SparseDecoderLM",
    "LoopedDecoderLM",
    "decoder",
    "VisionTransformer",
    "ViT_S16",
    "ViT_B16",
    "build",
    "TrainState",
    "apply_gradients",
    "parallel_lm",
    "create_train_state",
    "cross_entropy_loss",
    "lm_logits_rows",
    "make_eval_step",
    "make_lm_train_step",
    "make_train_step",
    "make_windowed_train_step",
    "read_before_update",
    "state_partition_specs",
]
