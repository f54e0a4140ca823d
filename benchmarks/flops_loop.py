"""Operations of a looped decoder LM's training step, from shapes.

The conventions of ``flops.py`` and ``flops_moe.py``: two operations a
multiply-add, a step is 3 x forward, recomputed operations not counted, only
the multiply-adds of matrix products counted, attention **as the causal mask
requires** (``flops_moe.keys_seen``). What the loop changes is what a token
passes: every block once an application, so ``layer_types`` lists the
**applications** (loop steps x blocks held; that is also what
``flops_moe.flash_work`` counts the attention kernels' work over), and the
head once an exit, ``loops`` times. The exit gate's one column a token (2,048
multiply-adds an exit beside 100 million) is left out.

Hand-worked figures the tests hold these to (one pipeline stage of
``ByteDance/Ouro-2.6B``: 8 blocks of 2,048 x 16 heads of 128 with a gated
feed-forward of 5,632, applied 4 times, the whole vocabulary of 49,152, 4,096
tokens a sequence):

* a block is 4 x 2,048^2 + 3 x 2,048 x 5,632 = 51,380,224 parameters in
  matrix products, the head 2,048 x 49,152 = 100,663,296: a token passes
  32 x 51,380,224 + 4 x 100,663,296 = 2,046,820,352;
* attention's two products under the causal mask (2,048.5 keys seen on
  average): 2 x 16 x 128 x 2,048.5 = 8,390,656 multiply-adds a token an
  application, 268,500,992 over the 32;
* 6 x (2,046,820,352 + 268,500,992) = 13,891,928,064 operations a token, 114
  TFLOP a step of 8,192 tokens.
"""

from benchmarks.flops_moe import keys_seen


def matmul_params_per_token(*, layer_types, loops, d_model, heads, kv_heads,
                            head_dim, ffn_width, vocab, **_) -> int:
    """Parameters whose matrix products one token passes through, each
    counted as often as it is applied."""
    block = d_model * head_dim * (2 * heads + 2 * kv_heads) \
        + 3 * d_model * ffn_width
    return len(layer_types) * block + loops * d_model * vocab


def attention_macs_per_token(*, layer_types, heads, head_dim, seq_len,
                             **_) -> float:
    """Multiply-adds a token of ``q k`` and ``p v`` over the keys it sees,
    all applications, forward."""
    return len(layer_types) * 2 * heads * head_dim * keys_seen(seq_len)


def per_token(**sizes) -> float:
    """Operations a token of one training step: ``6 x`` the parameters in
    matrix products a token passes ``+ 6 x`` attention's multiply-adds."""
    return 6 * matmul_params_per_token(**sizes) \
        + 6 * attention_macs_per_token(**sizes)
