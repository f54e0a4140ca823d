#!/usr/bin/env python
"""Synthetic benchmark for any image model of the zoo, by Horovod's protocol
(reference examples/pytorch_synthetic_benchmark.py:79-110): warm-up batches,
then ``--num-iters`` timed groups of ``--num-batches-per-iter`` training
steps on one synthetic batch, reported as img/sec per chip +- 1.96 sigma.

    python examples/jax_synthetic_benchmark.py --model vgg16
    python examples/jax_synthetic_benchmark.py --model inception_v3 \\
        --image-size 299

For a user porting a Horovod script. The repo's own numbers come from
``benchmarks/run.py`` (docs/benchmarks.md); what this prints names the device
it ran on and is a device number only where that device is a TPU.
"""

import argparse
import os

# Test switch: an 8-device virtual CPU mesh, set before jax loads.
if os.environ.get("HVD_TPU_FORCE_CPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8").strip()
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu.jax as hvd
from horovod_tpu import models


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--model", default="resnet50")
    parser.add_argument("--batch-size", type=int, default=64,
                        help="per-chip batch size")
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--num-warmup-batches", type=int, default=10)
    parser.add_argument("--num-batches-per-iter", type=int, default=10)
    parser.add_argument("--num-iters", type=int, default=10)
    args = parser.parse_args()

    hvd.init()
    n, size = hvd.size(), args.image_size
    log = print if hvd.rank() == 0 else (lambda *a, **k: None)
    device = jax.devices()[0]
    log(f"Model: {args.model}, batch size {args.batch_size}/chip, {n} x "
        f"{device.platform} ({device.device_kind})")

    rng = jax.random.PRNGKey(0)
    model = models.build(args.model, num_classes=1000)
    state, optimizer = models.create_train_state(
        rng, model, optax.sgd(0.01, momentum=0.9),
        jnp.zeros((1, size, size, 3), jnp.float32))
    batch = {
        "image": jax.random.normal(rng, (args.batch_size * n, size, size, 3)),
        "label": jax.random.randint(rng, (args.batch_size * n,), 0, 1000),
    }
    step = hvd.spmd_fn(
        models.make_train_step(model, optimizer, average_loss=False),
        in_specs=(P(), P("hvd")), out_specs=(P(), P()), donate_argnums=(0,))

    for _ in range(args.num_warmup_batches):
        state, _ = step(state, batch)
    jax.block_until_ready(state)

    rates = []
    for i in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            state, _ = step(state, batch)
        jax.block_until_ready(state)       # the device's time, not the enqueue's
        rate = (args.batch_size * args.num_batches_per_iter
                / (time.perf_counter() - t0))
        log(f"Iter #{i}: {rate:.1f} img/sec per chip")
        rates.append(rate)
    mean, conf = float(np.mean(rates)), float(1.96 * np.std(rates))
    log(f"Img/sec per chip: {mean:.1f} +-{conf:.1f}")
    log(f"Total img/sec on {n} chip(s): {n * mean:.1f} +-{n * conf:.1f}")
    log(f"{mean:.3f}")


if __name__ == "__main__":
    main()
