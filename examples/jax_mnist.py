#!/usr/bin/env python
"""MNIST-style data-parallel training (reference examples/pytorch_mnist.py).

The canonical "single-GPU script + 4 lines = distributed" demo: init, wrap
the optimizer, broadcast initial state, shard the batch. Runs on however
many chips are visible (single chip included). The dataset is a synthetic
MNIST stand-in (class-conditional patterns + noise) so the example runs
hermetically; swap ``make_dataset`` for real MNIST loading outside the
sandbox.

Run:  python examples/jax_mnist.py [--epochs 3]
      (multi-host: the launcher sets the JAX process env first)
"""

import argparse
import os

# Test switch: an 8-device virtual CPU mesh, set before jax loads.
if os.environ.get("HVD_TPU_FORCE_CPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8").strip()
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu.jax as hvd
from horovod_tpu import models


def make_dataset(n: int, num_classes: int = 10, seed: int = 0):
    """Learnable synthetic digits: one fixed random template per class
    (shared by train and test) + per-sample gaussian noise."""
    templates = np.random.RandomState(0).randn(
        num_classes, 28, 28, 1).astype(np.float32)
    rng = np.random.RandomState(seed)
    labels = rng.randint(0, num_classes, size=n)
    images = templates[labels] + 0.3 * rng.randn(n, 28, 28, 1).astype(
        np.float32)
    return images, labels.astype(np.int32)


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--epochs", type=int, default=3)
    parser.add_argument("--batch-size", type=int, default=64,
                        help="per-chip batch size")
    parser.add_argument("--lr", type=float, default=0.005)
    parser.add_argument("--momentum", type=float, default=0.5)
    parser.add_argument("--train-size", type=int, default=4096)
    parser.add_argument("--test-size", type=int, default=1024)
    args = parser.parse_args()

    hvd.init()                                           # Horovod step 1
    n = hvd.size()
    log = print if hvd.rank() == 0 else (lambda *a, **k: None)

    model = models.MNISTNet()
    rng = jax.random.PRNGKey(42)
    sample = jnp.zeros((1, 28, 28, 1), jnp.float32)
    # Horovod step 2: DistributedOptimizer wrap (inside create_train_state)
    # with the reference's lr x size scaling (pytorch_mnist.py:106).
    state, optimizer = models.create_train_state(
        rng, model, optax.sgd(args.lr * n, momentum=args.momentum), sample)
    # Horovod step 3: broadcast initial state from rank 0.
    state = hvd.broadcast_parameters(state, root_rank=0)

    train_step = models.make_train_step(model, optimizer)
    eval_step = models.make_eval_step(model)

    def run_train(state, batch):
        return hvd.spmd_run(train_step, state, batch,
                            in_specs=(P(), P("hvd")), out_specs=(P(), P()))

    def run_eval(state, batch):
        # Per-chip sums, then cross-chip total — the reference's metric
        # averaging pattern (pytorch_mnist.py:120-133).
        def step(state, batch):
            m = eval_step(state, batch)
            return {k: hvd.allreduce(v, op=hvd.Sum, name=f"eval.{k}")
                    for k, v in m.items()}

        return hvd.spmd_run(step, state, batch,
                            in_specs=(P(), P("hvd")), out_specs=P())

    images, labels = make_dataset(args.train_size)
    test_images, test_labels = make_dataset(args.test_size, seed=1)
    global_batch = args.batch_size * n
    steps_per_epoch = args.train_size // global_batch
    if steps_per_epoch == 0:
        raise SystemExit(
            f"global batch {global_batch} ({args.batch_size}/chip x {n} "
            f"chips) exceeds --train-size {args.train_size}; lower the "
            "batch size or enlarge the dataset")

    from jax.sharding import NamedSharding

    from horovod_tpu import data as hvd_data

    # Each PROCESS iterates its own slice of every global batch
    # (iterate_sharded defaults to the process topology), with one
    # host->device transfer in flight while the previous step computes.
    # Single-process jobs scatter batches straight to their mesh layout;
    # multi-host keeps host-local arrays (spmd dispatch assembles them).
    per_process_batch = global_batch // hvd.process_count()
    batch_sharding = (
        NamedSharding(hvd.mesh(), P("hvd"))
        if hvd.process_count() == 1 else None
    )
    for epoch in range(args.epochs):
        t0 = time.time()
        epoch_batches = hvd_data.iterate_sharded(
            {"image": images, "label": labels}, per_process_batch,
            epoch=epoch)
        for batch in hvd_data.prefetch_to_device(
                epoch_batches, size=2, sharding=batch_sharding):
            state, metrics = run_train(state, batch)
        test_metrics = run_eval(state, {
            "image": jnp.asarray(test_images),
            "label": jnp.asarray(test_labels)})
        acc = float(test_metrics["correct"]) / float(test_metrics["count"])
        log(f"Epoch {epoch + 1}: loss={float(metrics['loss']):.4f} "
            f"test_acc={acc:.4f} ({time.time() - t0:.1f}s)")

    if acc < 0.9:
        log("WARNING: final accuracy below 0.9", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
