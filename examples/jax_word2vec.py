#!/usr/bin/env python
"""Skip-gram word embeddings with sparse gradient exchange
(reference examples/tensorflow_word2vec.py).

The reference trained word2vec under plain DP, where each step's embedding
gradient is a ``tf.IndexedSlices`` — a handful of touched rows, not the
dense [vocab, dim] table — and Horovod's sparse path allreduced it as
allgather(values) + allgather(indices) (reference
tensorflow/__init__.py:72-83). This example is the TPU-native rebuild of
that story end to end:

* the whole step (row gather -> skip-gram loss -> row grads -> sparse
  cross-rank exchange -> table update) is ONE jitted SPMD program over the
  "hvd" mesh;
* gradients are taken w.r.t. the *gathered rows*, so the wire cost is
  O(batch x dim) via ``hvd.allreduce_sparse`` (two tiled all_gathers on
  ICI) instead of O(vocab x dim) for a dense psum;
* duplicate row updates accumulate exactly as IndexedSlices semantics
  require (``dense_rows=`` densify, the reference's ``sparse_as_dense``).

The corpus is synthetic and hermetic: a vocabulary partitioned into
topics, sentences drawn within a topic — so "related" words co-occur and
the learned embeddings must cluster by topic, which the example verifies
with an intra- vs inter-topic cosine-similarity margin.

Run:  python examples/jax_word2vec.py [--smoke]
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

import horovod_tpu.jax as hvd


def make_batches(vocab, topics, batch, steps, negatives, seed=0):
    """Skip-gram (center, context, negatives) triples: center and context
    come from the same topic (co-occurrence), negatives from the unigram
    distribution over the whole vocabulary."""
    rng = np.random.RandomState(seed)
    words_per_topic = vocab // topics
    topic_of = np.arange(vocab) // words_per_topic
    centers = rng.randint(0, vocab, size=(steps, batch))
    # Context: another word from the center's topic.
    offset = rng.randint(1, words_per_topic, size=(steps, batch))
    contexts = (centers // words_per_topic) * words_per_topic + (
        centers % words_per_topic + offset) % words_per_topic
    negs = rng.randint(0, vocab, size=(steps, batch, negatives))
    return centers.astype(np.int32), contexts.astype(np.int32), \
        negs.astype(np.int32), topic_of


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--vocab", type=int, default=2048)
    parser.add_argument("--dim", type=int, default=64)
    parser.add_argument("--topics", type=int, default=32)
    parser.add_argument("--batch-size", type=int, default=64,
                        help="per-chip skip-gram pairs per step")
    parser.add_argument("--negatives", type=int, default=5)
    parser.add_argument("--steps", type=int, default=2000)
    parser.add_argument("--lr", type=float, default=0.5)
    parser.add_argument("--smoke", action="store_true",
                        help="small sizes + clustering assertion (CI)")
    args = parser.parse_args()
    if args.smoke:
        args.vocab, args.dim, args.topics = 96, 16, 8
        args.steps, args.batch_size, args.lr = 400, 32, 5.0
    if args.vocab % args.topics != 0 or args.vocab // args.topics < 2:
        parser.error(
            f"--vocab ({args.vocab}) must be a multiple of --topics "
            f"({args.topics}) with at least 2 words per topic")

    hvd.init()
    n = hvd.size()
    vocab, dim, lr = args.vocab, args.dim, args.lr
    global_batch = args.batch_size * n

    rng = np.random.RandomState(1)
    params = {
        "in": jnp.asarray(
            rng.uniform(-0.5 / dim, 0.5 / dim, (vocab, dim)), jnp.float32),
        "out": jnp.zeros((vocab, dim), jnp.float32),
    }
    # Same init everywhere regardless of seed handling: root broadcasts
    # (reference broadcast_global_variables pattern).
    params = hvd.broadcast_parameters(params, root_rank=0)

    def step(params, batch):
        emb_in, emb_out = params["in"], params["out"]
        c, o, neg = batch["center"], batch["context"], batch["negatives"]

        # Loss as a function of the GATHERED rows only — so autodiff
        # produces per-row gradients (the IndexedSlices analogue), not a
        # dense [vocab, dim] scatter.
        def loss_rows(e_rows, u_pos, u_neg):
            pos = jnp.sum(e_rows * u_pos, axis=-1)
            negd = jnp.einsum("bd,bkd->bk", e_rows, u_neg)
            nll = -(jax.nn.log_sigmoid(pos) +
                    jnp.sum(jax.nn.log_sigmoid(-negd), axis=-1))
            return jnp.mean(nll)

        loss, (g_e, g_pos, g_neg) = jax.value_and_grad(
            loss_rows, argnums=(0, 1, 2))(emb_in[c], emb_out[o],
                                          emb_out[neg])

        # Sparse cross-rank exchange: O(batch x dim) wire bytes.
        d_in = hvd.allreduce_sparse(c, g_e, dense_rows=vocab, average=True)
        idx_out = jnp.concatenate([o, neg.reshape(-1)])
        val_out = jnp.concatenate([g_pos, g_neg.reshape(-1, dim)])
        d_out = hvd.allreduce_sparse(idx_out, val_out, dense_rows=vocab,
                                     average=True)
        new_params = {"in": emb_in - lr * d_in, "out": emb_out - lr * d_out}
        return new_params, hvd.allreduce(loss, average=True)

    run_step = hvd.spmd_fn(step, in_specs=(P(), P("hvd")),
                           out_specs=(P(), P()), donate_argnums=(0,))

    centers, contexts, negs, topic_of = make_batches(
        vocab, args.topics, global_batch, args.steps, args.negatives)
    log = print if hvd.rank() == 0 else (lambda *a, **k: None)
    first_loss = None
    loss = None
    for s in range(args.steps):
        batch = {
            "center": jnp.asarray(centers[s]),
            "context": jnp.asarray(contexts[s]),
            "negatives": jnp.asarray(negs[s]),
        }
        params, loss = run_step(params, batch)
        if s == 0:
            first_loss = float(loss)
        if s % max(1, args.steps // 10) == 0:
            log(f"step {s:5d}  loss {float(loss):.4f}", file=sys.stderr)
    last_loss = float(loss)
    log(f"loss: {first_loss:.4f} -> {last_loss:.4f}", file=sys.stderr)

    # Embeddings must cluster by topic: mean cosine similarity within a
    # topic should clearly beat the cross-topic mean.
    emb = np.asarray(params["in"])
    emb = emb / (np.linalg.norm(emb, axis=1, keepdims=True) + 1e-8)
    cos = emb @ emb.T
    same = topic_of[:, None] == topic_of[None, :]
    np.fill_diagonal(same, False)
    np.fill_diagonal(cos, 0.0)
    intra = cos[same].mean()
    inter = cos[~same & ~np.eye(len(cos), dtype=bool)].mean()
    log(f"cosine: intra-topic {intra:.3f}  inter-topic {inter:.3f}",
        file=sys.stderr)

    if hvd.rank() == 0:
        assert last_loss < first_loss * 0.7, (first_loss, last_loss)
        if args.smoke:
            assert intra > inter + 0.2, (intra, inter)
        print(f"{last_loss:.6f}")


if __name__ == "__main__":
    main()
