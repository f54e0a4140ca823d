"""Operations and bytes of a sparse decoder LM's training step, from shapes.

The conventions of ``flops.py``: two operations a multiply-add, a step is 3 x
forward, recomputed operations not counted, and only the multiply-adds of
matrix products counted. Two things differ from the dense LM there. Attention
is counted **as the masks require**: a query under a causal mask sees
``(S + 1) / 2`` keys on average and under a window of ``W`` fewer still, and a
kernel that skips the masked blocks is not flattered by it, because no dense
fallback exists at these lengths. And the routed experts are counted **at the
expectation of even routing**: a token's ``top_k`` choices fall on this chip's
``experts_held`` of ``experts`` with probability ``held / experts`` each, so a
layer's grouped products see ``tokens x top_k x held / experts`` rows a step;
padded or unoccupied rows count nothing.

``per_token`` is what ``step_mfu_pct`` reads. ``gmm_work`` and ``flash_work``
give ``(operations, bytes)`` a step of the expert layers' grouped products and
of the attention kernels, for the roofline shares: the least time a v5e could
take is ``max(operations / 197e12, bytes / 819e9)`` whatever implements them.

Hand-worked figures the tests hold these to (the chip's share of
``arcee-ai/Trinity-Mini``: 1 dense and 4 expert layers, 16 of 128 experts,
25,024 of the vocabulary, 4,096 tokens a sequence):

* 276,692,992 parameters in matrix products a token: 5 x 27,262,976
  attention, 37,748,736 dense feed-forward, 4 x (262,144 router + 6,291,456
  shared + 6,291,456 routed at 8 x 16 / 128 = 1 expert a token), 51,249,152
  head;
* 12,584,960 multiply-adds a token a layer in attention's two products under
  a window of 2,048 (1,536.25 keys seen on average) and 16,781,312 under the
  full causal mask (2,048.5), 67,121,152 over the five layers:
  2,062,884,864 operations a token in all.
"""

SLIDING = "sliding_attention"
BF16 = 2


def keys_seen(seq_len: int, window=None) -> float:
    """Keys a query sees on average over a sequence: itself and those before
    it, fewer than ``window`` positions back."""
    if window is None or window >= seq_len:
        return (seq_len + 1) / 2
    return (window * (window + 1) / 2 + (seq_len - window) * window) / seq_len


def matmul_params_per_token(*, layer_types, d_model, heads, kv_heads,
                            head_dim, dense_layers, dense_width, experts,
                            experts_held, top_k, expert_width, shared_experts,
                            vocab, **_) -> float:
    """Parameters whose matrix products one token passes through."""
    attention = d_model * head_dim * (3 * heads + 2 * kv_heads)
    one_expert = 3 * d_model * expert_width
    sparse = (d_model * experts + shared_experts * one_expert
              + top_k * experts_held / experts * one_expert)
    layers = len(layer_types)
    return (layers * attention + dense_layers * 3 * d_model * dense_width
            + (layers - dense_layers) * sparse + d_model * vocab)


def attention_macs_per_token(*, layer_types, heads, head_dim, window,
                             seq_len, **_) -> float:
    """Multiply-adds a token of ``q k`` and ``p v`` over the keys it sees,
    all layers, forward."""
    return sum(2 * heads * head_dim
               * keys_seen(seq_len, window if kind == SLIDING else None)
               for kind in layer_types)


def per_token(**sizes) -> float:
    """Operations a token of one training step: ``6 x`` the parameters in
    matrix products a token passes ``+ 6 x`` attention's multiply-adds."""
    return 6 * matmul_params_per_token(**sizes) \
        + 6 * attention_macs_per_token(**sizes)


def gmm_work(*, tokens_per_step, layer_types, dense_layers, d_model, experts,
             experts_held, top_k, expert_width, **_):
    """``(operations, bytes)`` a step of the grouped products of every expert
    layer: three products forward (gate, up, down) and two backward for each
    (by the rows, by the weights), over the expected rows. Bytes: each
    product's two operands and its result once, bfloat16."""
    rows = tokens_per_step * top_k * experts_held / experts
    d, f = d_model, expert_width
    ops = 9 * 2 * rows * d * f
    narrow, wide, weights = rows * f * BF16, rows * d * BF16, \
        experts_held * d * f * BF16
    nbytes = 9 * (narrow + wide + weights)
    layers = len(layer_types) - dense_layers
    return layers * ops, layers * nbytes


def flash_work(*, tokens_per_step, layer_types, heads, kv_heads, head_dim,
               window, seq_len, **_):
    """``(operations, bytes)`` a step of attention between its projections:
    ``q k`` and ``p v`` forward; in the backward pass the scores again and
    the four products of ``dv``, ``dp``, ``dq`` and ``dk``: seven products of
    ``2 x head_dim`` operations a (query, key seen) pair a head. Bytes: q, o,
    do and dq a query head, k, v, dk and dv a KV head, read or written once
    forward and once backward, bfloat16."""
    pairs = sum(keys_seen(seq_len, window if kind == SLIDING else None)
                for kind in layer_types) * tokens_per_step
    ops = 7 * 2 * head_dim * heads * pairs
    per_layer = tokens_per_step * head_dim * BF16 * (
        (2 * heads + 2 * kv_heads)              # forward: q, k, v in, o out
        + (4 * heads + 4 * kv_heads))           # backward: + do, dq, dk, dv
    return ops, len(layer_types) * per_layer
