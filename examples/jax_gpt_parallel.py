#!/usr/bin/env python
"""Train the composed dp x sp x tp GPT-style LM (models/parallel_lm.py).

The flagship composition as a runnable script: one jitted shard_map
program in which the DENSE parameter pytree is sharded onto the mesh by
``lm_param_specs`` (attention heads and MLP features over tp), the
sequence axis shards over sp with exact ring attention, the batch over
dp, gradients reduce via ``reduce_grads`` (sum over sp, mean over dp —
exact: tests/test_parallel_lm.py pins this against the dense
single-device step), and SGD updates the sharded state in place.

Run:  python examples/jax_gpt_parallel.py [--smoke]
      (8 visible chips -> dp=2 x sp=2 x tp=2)
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
from jax.sharding import PartitionSpec as P

import horovod_tpu.parallel as par
from horovod_tpu.models import parallel_lm as plm


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--vocab", type=int, default=256)
    parser.add_argument("--layers", type=int, default=4)
    parser.add_argument("--heads", type=int, default=8)
    parser.add_argument("--head-dim", type=int, default=32)
    parser.add_argument("--ffn", type=int, default=1024)
    parser.add_argument("--seq-len", type=int, default=256,
                        help="global sequence length (shards over sp)")
    parser.add_argument("--batch", type=int, default=8,
                        help="global batch (shards over dp)")
    parser.add_argument("--steps", type=int, default=300)
    parser.add_argument("--lr", type=float, default=0.3)
    parser.add_argument("--fused-ce", action="store_true",
                        help="train through the chunked vocab-parallel "
                             "loss (ops/xent.py): the head shards "
                             "[E, V/tp] and the [B, L, vocab] logits "
                             "tensor never materializes")
    parser.add_argument("--smoke", action="store_true")
    args = parser.parse_args()
    if args.smoke:
        args.vocab, args.layers, args.heads = 64, 2, 4
        args.head_dim, args.ffn = 8, 64
        args.seq_len, args.batch, args.steps = 64, 4, 120

    n = len(jax.devices())
    sp = 2 if n % 2 == 0 else 1
    tp = 2 if (n // sp) % 2 == 0 else 1
    dp = n // (sp * tp)
    mesh = par.make_mesh({"dp": dp, "sp": sp, "tp": tp})
    log = print
    log(f"mesh dp={dp} x sp={sp} x tp={tp} over {n} chips "
        f"({jax.devices()[0].platform})", file=sys.stderr)
    if args.heads % max(tp, 1) or args.seq_len % max(sp, 1):
        parser.error("heads must divide by tp and seq-len by sp")

    vp = args.fused_ce and tp > 1
    if vp and args.vocab % tp:
        parser.error("--fused-ce vocab-parallel head needs vocab % tp == 0")
    rng = jax.random.PRNGKey(0)
    params = plm.init_lm_params(rng, args.vocab, args.seq_len, args.layers,
                                args.heads, args.head_dim, args.ffn)
    specs = plm.lm_param_specs(args.layers, "tp" if tp > 1 else None,
                               vocab_parallel=vp)

    # Learnable synthetic corpus: a fixed random bigram successor table,
    # so next-token NLL can fall far below the uniform-entropy floor.
    succ = np.random.RandomState(1).randint(0, args.vocab, args.vocab)
    seq = np.zeros((args.batch, args.seq_len), np.int32)
    seq[:, 0] = np.arange(args.batch) % args.vocab
    for t in range(1, args.seq_len):
        seq[:, t] = succ[seq[:, t - 1]]
    tokens = jnp.asarray(seq)

    sp_ax = "sp" if sp > 1 else None

    tp_ax = "tp" if tp > 1 else None

    def step(p, t):
        def loss_fn(p):
            if args.fused_ce:
                h = plm.lm_apply(p, t, sp=sp_ax, tp=tp_ax,
                                 return_hidden=True)
                return plm.next_token_nll_fused(
                    p, h, t, sp=sp_ax, tp=tp_ax, vocab_parallel=vp,
                    t_chunk=64)
            return plm.next_token_nll(
                plm.lm_apply(p, t, sp=sp_ax, tp=tp_ax), t, sp=sp_ax)

        loss, g = jax.value_and_grad(loss_fn)(p)
        g = plm.reduce_grads(g, dp="dp" if dp > 1 else None, sp=sp_ax)
        new_p = jax.tree_util.tree_map(lambda a, b: a - args.lr * b, p, g)
        return new_p, jax.lax.pmean(loss, "dp")

    # check_vma opt-out class 4 (docs/parallelism.md): the fused-loss
    # custom VJP returns per-rank partial dw (reduced later by
    # reduce_grads), which the strict checker's cotangent-type rule
    # rejects for the tp-sharded head; values are pinned exact vs the
    # dense step in tests/test_parallel_lm.py.
    fn = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(specs, P("dp", "sp")),
        out_specs=(specs, P()),
        check_vma=not args.fused_ce),
        donate_argnums=(0,))

    first = last = None
    for s in range(args.steps):
        params, loss = fn(params, tokens)
        if s == 0:
            first = float(loss)
        if s % max(1, args.steps // 10) == 0:
            log(f"step {s:4d}  nll {float(loss):.4f}", file=sys.stderr)
    last = float(loss)
    log(f"nll: {first:.4f} -> {last:.4f}", file=sys.stderr)
    assert last < first * 0.5, (first, last)

    # The trained model must have internalized the bigram table: greedy
    # KV-cache decode from short prompts should emit each token's true
    # successor chain (lm_decode runs single-device here; the params are
    # replicated so any chip can serve).
    prompts = tokens[:4, :2]
    gen = np.asarray(plm.lm_decode(params, prompts, 12))
    want = np.zeros_like(gen)
    prev = np.asarray(prompts[:, -1])
    for t in range(gen.shape[1]):
        prev = succ[prev]
        want[:, t] = prev
    acc = float((gen == want).mean())
    log(f"decode successor accuracy: {acc:.3f}", file=sys.stderr)
    assert acc > 0.9, acc
    print(f"{last:.6f}")


if __name__ == "__main__":
    main()
