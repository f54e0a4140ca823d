"""Operations and bytes of a sparse decoder LM with latent attention, from
shapes.

The conventions of ``flops_moe.py``: two operations a multiply-add, a step is
3 x forward, recomputed operations not counted, only the multiply-adds of
matrix products counted, causal attention over the ``(S + 1) / 2`` keys a
query sees on average, the routed experts at the expectation of even routing.
What differs is the attention layer: its projections go through a compressed
row (``d -> heads x (nope + rope)`` for the queries, ``d -> latent + rope``,
``latent -> heads x (nope + value)``, ``heads x value -> d``), a key is
``nope + rope`` wide and a value ``value`` wide, so ``q k`` and ``p v`` are
products of different widths.

``per_token`` is what ``step_mfu_pct`` reads; ``attn_work`` gives
``(operations, bytes)`` a step of the attention kernels for
``mla_attn_roofline_pct.tok``; ``flops_moe.gmm_work`` reads the expert
layers' sizes from the same arguments.

Hand-worked figures the tests hold these to (the chip's share of
``moonshotai/Moonlight-16B-A3B``: 1 dense and 5 expert layers, 8 of 64
experts, 20,480 of the vocabulary, 8,192 tokens a sequence):

* 13,762,560 parameters in attention's products a layer (6,291,456 +
  1,179,648 + 2,097,152 + 4,194,304); 313,327,616 a token in all: 6 x
  13,762,560, 69,206,016 dense feed-forward, 5 x (131,072 router +
  17,301,504 shared + 0.75 x 8,650,752 routed at 6 x 8 / 64 of an expert a
  token), 41,943,040 head;
* 20,974,080 multiply-adds a token a layer in attention's two products (16 x
  (192 + 128) x 4,096.5 keys seen): 2,635,032,576 operations a token;
* the kernels: seven products a visible pair a head, four of them 192 wide
  (``q k`` forward and again backward, ``dq``, ``dk``) and three 128 (``p
  v``, ``dv``, ``dp``): 2,304 operations, 1.4845e13 a step of 16,384 tokens,
  75.4 ms at 197 TFLOP/s.
"""

from benchmarks.flops_moe import BF16, keys_seen


def matmul_params_per_token(*, layer_types, d_model, heads, nope_dim,
                            rope_dim, value_dim, latent_dim, dense_layers,
                            dense_width, experts, experts_held, top_k,
                            expert_width, shared_experts, vocab, **_) -> float:
    """Parameters whose matrix products one token passes through."""
    attention = (d_model * heads * (nope_dim + rope_dim)
                 + d_model * (latent_dim + rope_dim)
                 + latent_dim * heads * (nope_dim + value_dim)
                 + heads * value_dim * d_model)
    one_expert = 3 * d_model * expert_width
    sparse = (d_model * experts + shared_experts * one_expert
              + top_k * experts_held / experts * one_expert)
    layers = len(layer_types)
    return (layers * attention + dense_layers * 3 * d_model * dense_width
            + (layers - dense_layers) * sparse + d_model * vocab)


def attention_macs_per_token(*, layer_types, heads, nope_dim, rope_dim,
                             value_dim, seq_len, **_) -> float:
    """Multiply-adds a token of ``q k`` (keys' width) and ``p v`` (values')
    over the keys it sees, all layers, forward."""
    return len(layer_types) * heads * (nope_dim + rope_dim + value_dim) \
        * keys_seen(seq_len)


def per_token(**sizes) -> float:
    """Operations a token of one training step: ``6 x`` the parameters in
    matrix products a token passes ``+ 6 x`` attention's multiply-adds."""
    return 6 * matmul_params_per_token(**sizes) \
        + 6 * attention_macs_per_token(**sizes)


def attn_work(*, tokens_per_step, layer_types, heads, nope_dim, rope_dim,
              value_dim, seq_len, **_):
    """``(operations, bytes)`` a step of attention between its projections,
    as ``flops_moe.flash_work`` counts them, at two widths: ``q k`` forward,
    the scores again in the backward pass, ``dq`` and ``dk`` are as wide as a
    key, ``p v``, ``dv`` and ``dp`` as a value. Bytes: q, k, dq and dk at the
    keys' width a head (a key counted whole a head, however the rope key
    reaches the kernels), v, o, do and dv at the values', read or written
    once forward and once backward, bfloat16."""
    key, value = nope_dim + rope_dim, value_dim
    pairs = len(layer_types) * keys_seen(seq_len) * tokens_per_step
    ops = 2 * (4 * key + 3 * value) * heads * pairs
    per_layer = tokens_per_step * heads * BF16 * (
        (2 * key + 2 * value)               # forward: q, k, v in, o out
        + (4 * key + 4 * value))            # backward: those, do, dq, dk, dv
    return ops, len(layer_types) * per_layer
