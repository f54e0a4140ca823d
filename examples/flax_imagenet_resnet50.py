#!/usr/bin/env python
"""ResNet-50 "ImageNet" training with the full callback stack
(reference examples/keras_imagenet_resnet50.py).

Demonstrates the keras-binding analogue end to end: BroadcastGlobalVariables
at train start, gradual LR warmup to lr x size, staircase decay schedule,
epoch-end metric averaging, rank-0 checkpointing with resume-epoch
broadcast (reference :66-103). Data is synthetic (hermetic); swap
``data_fn`` for a real input pipeline.

Run:  python examples/flax_imagenet_resnet50.py --smoke
"""

import argparse
import os

# Test switch: an 8-device virtual CPU mesh, set before jax loads.
if os.environ.get("HVD_TPU_FORCE_CPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8").strip()
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu.jax as hvd
from horovod_tpu import flax as hvd_flax
from horovod_tpu import models
from horovod_tpu.flax import callbacks as cb


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--epochs", type=int, default=3)
    parser.add_argument("--batch-size", type=int, default=32,
                        help="per-chip batch size")
    parser.add_argument("--base-lr", type=float, default=0.0125,
                        help="per-chip lr (reference :33)")
    parser.add_argument("--warmup-epochs", type=float, default=1.0)
    parser.add_argument("--steps-per-epoch", type=int, default=8)
    parser.add_argument("--checkpoint", default="/tmp/hvd_tpu_resnet50.msgpack")
    parser.add_argument("--smoke", action="store_true",
                        help="tiny shapes for CI")
    args = parser.parse_args()

    hvd.init()
    n = hvd.size()
    log = print if hvd.rank() == 0 else (lambda *a, **k: None)

    size = 32 if args.smoke else 224
    classes = 10 if args.smoke else 1000
    model = (models.ResNet18(num_classes=classes, dtype=jnp.float32)
             if args.smoke else
             models.ResNet50(num_classes=classes, dtype=jnp.bfloat16))

    # Injectable-hyperparams optimizer so the LR callbacks can steer it;
    # lr is scaled by size, warmup ramps up to it (reference :97,136-153).
    inner = optax.inject_hyperparams(optax.sgd)(
        learning_rate=args.base_lr * n, momentum=0.9)
    rng = jax.random.PRNGKey(0)
    sample = jnp.zeros((1, size, size, 3), jnp.float32)
    state, optimizer = models.create_train_state(rng, model, inner, sample)
    train_step = models.make_train_step(model, optimizer)

    def spmd_step(state, batch):
        return hvd.spmd_run(train_step, state, batch,
                            in_specs=(P(), P("hvd")), out_specs=(P(), P()))

    global_batch = args.batch_size * n
    data_rng = np.random.RandomState(hvd.rank())

    def data_fn(epoch):
        for _ in range(args.steps_per_epoch):
            yield {
                "image": jnp.asarray(data_rng.randn(
                    global_batch, size, size, 3).astype(np.float32)),
                "label": jnp.asarray(data_rng.randint(
                    0, classes, size=global_batch)),
            }

    # Resume support: restore + re-broadcast + skip completed epochs
    # (reference :66-103 resume_from_epoch pattern).
    start_epoch = 0
    if os.path.exists(args.checkpoint):
        state = hvd_flax.load_model(args.checkpoint, state)
        start_epoch = int(hvd.broadcast_object(
            int(state["step"]) // args.steps_per_epoch, root_rank=0))
        log(f"Resuming from epoch {start_epoch}")

    class CheckpointCallback(cb.Callback):
        def on_epoch_end(self, epoch, logs=None):
            hvd_flax.save_model(args.checkpoint, self.loop.state)

    loop = hvd_flax.TrainLoop(
        state, spmd_step, data_fn,
        callbacks=[
            cb.BroadcastGlobalVariablesCallback(0),
            cb.LearningRateWarmupCallback(
                warmup_epochs=args.warmup_epochs,
                steps_per_epoch=args.steps_per_epoch, verbose=1),
            cb.LearningRateScheduleCallback(
                multiplier=lambda e: 0.1 ** (e // 30),
                start_epoch=args.warmup_epochs),
            cb.MetricAverageCallback(),
            CheckpointCallback(),
        ])
    history = loop.fit(args.epochs - start_epoch)
    log("history:", [{k: round(v, 4) for k, v in h.items()}
                     for h in history])
    return 0


if __name__ == "__main__":
    sys.exit(main())
