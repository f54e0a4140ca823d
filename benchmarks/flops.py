"""Operations a training step requires, from shapes.

Two operations per multiply-add, the backward pass as twice the forward (so a
step is 3 x forward), recomputed operations not counted, and nothing but the
multiply-adds of matrix products and convolutions counted (norms, softmax,
activations and the optimizer's passes are the work of other units and are
left out, as in every published MFU). A configuration names the function and
its arguments in its ``flops`` entry; a new family brings a file of its own.

Hand-worked figures the tests hold these to:

* GPT-2-medium at 1,024 tokens: 2,422,708,224 operations a token (2.42 GFLOP).
* ResNet-50 at 224 x 224 with the stride on the 3x3: 4,089,184,256
  multiply-adds an image forward (the published 4.09 GMAC), 24.5 GFLOP an
  image for a step.
"""


def lm_per_token(*, layers: int, d_model: int, vocab: int, seq_len: int,
                 ffn_mult: int = 4) -> int:
    """``6 x (parameters in matrix products) + 12 x L x S x d``.

    A block holds ``4 d^2`` in attention (QKV and the output projection) and
    ``2 x ffn_mult x d^2`` in the feed-forward; the head holds ``d x V``. The
    embedding is a gather and counts nothing. Attention's two products are
    counted over the full S x S square, not halved for causality: a dense
    implementation does them all, and halving would flatter one that skips."""
    per_block = (4 + 2 * ffn_mult) * d_model * d_model
    matmul_params = layers * per_block + d_model * vocab
    attention = 2 * layers * seq_len * d_model     # multiply-adds a token
    return 6 * matmul_params + 6 * attention


def resnet_bottleneck_macs(*, stages, width: int, image: int, classes: int,
                           stride_on_3x3: bool) -> int:
    """Multiply-adds of one image's forward pass: every convolution's
    ``k x k x c_in x c_out`` times its output positions, plus the dense
    layer. Feature maps halve by rounding up, as ``SAME`` padding does."""
    def half(n):
        return (n + 1) // 2

    size = half(image)                                    # 7x7/2 stem
    macs = 7 * 7 * 3 * width * size * size
    size = half(size)                                     # 3x3/2 max-pool
    c_in = width
    for stage, blocks in enumerate(stages):
        mid = width * 2 ** stage
        for j in range(blocks):
            stride = 2 if stage > 0 and j == 0 else 1
            out = half(size) if stride == 2 else size
            first = size if stride_on_3x3 else out        # where the 1x1 runs
            macs += c_in * mid * first * first            # 1x1
            macs += 3 * 3 * mid * mid * out * out         # 3x3
            macs += mid * 4 * mid * out * out             # 1x1
            if c_in != 4 * mid or stride == 2:
                macs += c_in * 4 * mid * out * out        # projection
            c_in, size = 4 * mid, out
    return macs + c_in * classes


def resnet_per_image(**kw) -> int:
    return 3 * 2 * resnet_bottleneck_macs(**kw)
