#!/usr/bin/env python
"""Torch-lane synthetic benchmark (reference
examples/pytorch_synthetic_benchmark.py:79-110 protocol).

Same measurement discipline as the reference's flagship benchmark —
synthetic data, warmup, timed groups, img/sec ± CI, cross-rank averaged
total — over the native TCP-ring core on CPU. The jax/TPU counterpart is
`examples/jax_synthetic_benchmark.py`; this script exists so the eager
torch lane has the same yardstick the reference shipped.

Run:  python -m horovod_tpu.run -np 2 python examples/torch_synthetic_benchmark.py
"""

import argparse
import sys
import time

import numpy as np
import torch
import torch.nn as nn
import torch.nn.functional as F

import horovod_tpu.torch as hvd


class SmallResNet(nn.Module):
    """A compact residual convnet — CPU-sized stand-in for the
    reference's torchvision resnet50 (not vendored here)."""

    def __init__(self, width=32, num_classes=100):
        super().__init__()
        self.stem = nn.Conv2d(3, width, 3, padding=1)
        self.b1 = nn.Conv2d(width, width, 3, padding=1)
        self.b2 = nn.Conv2d(width, width, 3, padding=1)
        self.head = nn.Linear(width, num_classes)

    def forward(self, x):
        x = F.relu(self.stem(x))
        x = F.relu(x + self.b2(F.relu(self.b1(x))))
        x = F.adaptive_avg_pool2d(x, 1).flatten(1)
        return self.head(x)


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--image-size", type=int, default=32)
    p.add_argument("--num-warmup-batches", type=int, default=2)
    p.add_argument("--num-batches-per-iter", type=int, default=5)
    p.add_argument("--num-iters", type=int, default=5)
    p.add_argument("--fp16-allreduce", action="store_true")
    args = p.parse_args()

    hvd.init()
    torch.manual_seed(1 + hvd.rank())
    torch.set_num_threads(1)

    model = SmallResNet()
    optimizer = torch.optim.SGD(model.parameters(), lr=0.01 * hvd.size(),
                                momentum=0.9)
    compression = (hvd.Compression.fp16 if args.fp16_allreduce
                   else hvd.Compression.none)
    optimizer = hvd.DistributedOptimizer(
        optimizer, named_parameters=model.named_parameters(),
        compression=compression)
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)
    hvd.broadcast_optimizer_state(optimizer, root_rank=0)

    data = torch.randn(args.batch_size, 3, args.image_size, args.image_size)
    target = torch.randint(0, 100, (args.batch_size,))

    def benchmark_step():
        optimizer.zero_grad()
        loss = F.cross_entropy(model(data), target)
        loss.backward()
        optimizer.step()

    def log(*a):
        if hvd.rank() == 0:
            print(*a, file=sys.stderr)

    log(f"Running benchmark: size {hvd.size()}, batch {args.batch_size}")
    for _ in range(args.num_warmup_batches):
        benchmark_step()

    img_secs = []
    for x in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            benchmark_step()
        elapsed = time.perf_counter() - t0
        img_sec = args.batch_size * args.num_batches_per_iter / elapsed
        log(f"Iter #{x}: {img_sec:.1f} img/sec per rank")
        img_secs.append(img_sec)

    img_sec_mean = float(np.mean(img_secs))
    img_sec_conf = float(1.96 * np.std(img_secs))
    log(f"Img/sec per rank: {img_sec_mean:.1f} +-{img_sec_conf:.1f}")
    # Total = allreduced sum of per-rank throughput (the reference
    # multiplied by size; summing tolerates heterogeneous hosts).
    total = hvd.allreduce(torch.tensor([img_sec_mean]), average=False)
    log(f"Total img/sec on {hvd.size()} rank(s): {float(total[0]):.1f}")
    if hvd.rank() == 0:
        print(f"{img_sec_mean:.1f}")
    hvd.shutdown()


if __name__ == "__main__":
    main()
