"""Operations and bytes of a Mamba-2 / attention hybrid LM's training step,
from shapes.

The conventions of ``flops_moe.py``: two operations a multiply-add, a step is
3 x forward, recomputed operations not counted, only the multiply-adds of
matrix products counted (the conv's four taps, the norms and the gates are
not), causal attention over the ``(S + 1) / 2`` keys a query sees on average.
The Mamba layer's scan is counted in its chunked form (``ops/ssd.py``), the
form the architecture trains in, at the causal triangle inside a chunk of
``T``: a token's row of ``C B^T`` over the ``(T + 1) / 2`` tokens it sees in
its chunk (one group: once for all heads), and a head's ``(G . L)(Delta
x)`` over the same, ``C H`` and ``B^T (w Delta x)`` (``N x P`` each).

``per_token`` is what ``step_mfu_pct`` reads; ``ssd_work`` gives
``(operations, bytes)`` of the scan kernels' calls a step for
``ssd_roofline_pct.tok``.

Hand-worked figures the tests hold these to (the first pipeline stage of
``ibm-granite/granite-4.0-h-micro``: 9 Mamba layers and 1 attention layer,
12,544 of the vocabulary, 16,384 tokens a sequence):

* 771,883,008 parameters in matrix products a token: 9 x 76,152,832 (in
  17,432,576, out 8,388,608, MLP 50,331,648), 60,817,408 of attention
  (10,485,760 and the MLP), 25,690,112 of the tied head;
* 1,591,360 multiply-adds a token a Mamba layer in the scan (16,448 of
  ``G`` and 64 x (8,224 + 16,384)), 14,322,240 over nine; attention 32 x 128
  x 8,192.5 = 33,556,480: 4,918,570,368 operations a token in all.
"""

from benchmarks.flops_moe import BF16, keys_seen

MAMBA = "mamba"
F32 = 4
# the scan's kernels as a device profile names them (``ops/ssd.py``): the
# forward and the backward kernel share the one name, so one family
KERNELS = ("hvd_ssd_scan",)


def _counts(layer_types):
    mamba = sum(t == MAMBA for t in layer_types)
    return mamba, len(layer_types) - mamba


def matmul_params_per_token(*, layer_types, d_model, heads, kv_heads,
                            head_dim, ffn, ssm_heads, ssm_head_dim, ssm_state,
                            vocab, **_) -> int:
    """Parameters whose matrix products one token passes through."""
    inner = ssm_heads * ssm_head_dim
    mamba = d_model * (2 * inner + 2 * ssm_state + ssm_heads) \
        + inner * d_model
    attention = d_model * (heads + 2 * kv_heads) * head_dim \
        + heads * head_dim * d_model
    n_mamba, n_attention = _counts(layer_types)
    return n_mamba * mamba + n_attention * attention \
        + len(layer_types) * 3 * d_model * ffn + d_model * vocab


def scan_macs_per_token(*, layer_types, ssm_heads, ssm_head_dim, ssm_state,
                        chunk, **_) -> float:
    """Forward multiply-adds a token of the scan's products, all Mamba
    layers."""
    seen = (chunk + 1) / 2
    per_layer = seen * ssm_state + ssm_heads * (
        seen * ssm_head_dim + 2 * ssm_state * ssm_head_dim)
    return _counts(layer_types)[0] * per_layer


def attention_macs_per_token(*, layer_types, heads, head_dim, seq_len,
                             **_) -> float:
    """Multiply-adds a token of ``q k`` and ``p v`` over the keys it sees,
    all attention layers, forward."""
    return _counts(layer_types)[1] * heads * 2 * head_dim * keys_seen(seq_len)


def per_token(**sizes) -> float:
    """Operations a token of one training step: ``6 x`` the parameters in
    matrix products, the scan's and attention's multiply-adds."""
    return 6 * (matmul_params_per_token(**sizes) + scan_macs_per_token(**sizes)
                + attention_macs_per_token(**sizes))


def ssd_work(*, fwd_calls, bwd_calls, tokens_per_step, ssm_heads,
             ssm_head_dim, ssm_state, chunk, **_):
    """``(operations, bytes)`` of ``fwd_calls`` forward kernel calls (the
    recomputed ones among them) and ``bwd_calls`` backward ones a step, each
    over ``tokens_per_step`` tokens, as ``ops/ssd.py``'s kernels run their
    products: over a chunk's whole ``T x T`` square (the kernels multiply
    the masked half too) and at the 64 columns a head uses (a product's
    other 64 are the other head's zeros, not counted).

    A chunk, forward: ``C B^T`` once (``T T N``); a head ``(G . L)(Delta
    x)`` (``T T P``), ``C H`` and ``B^T (w Delta x)`` (``T N P`` each).
    Backward: ``C B^T``, ``dG B`` and ``dG^T C`` once (``3 T T N``); a
    head ``M^T dY`` and ``dY (Delta x)^T`` (``2 T T P``), C's and B's
    gradients off the states, ``B dH`` and ``C^T (e dY)`` (``4 T N P``).

    Bytes, read or written once a call: forward x | B | C and y in bfloat16,
    Delta and its running sum a head in float32, the states entering every
    chunk (``N x P`` float32 a head) written; backward those inputs, the
    states and dY read, dx, dB, dC (float32), dDelta and d(cs) written."""
    t, n, p, h = chunk, ssm_state, ssm_head_dim, ssm_heads
    chunks = tokens_per_step / t
    fwd_ops = 2 * chunks * (t * t * n + h * (t * t * p + 2 * t * n * p))
    bwd_ops = 2 * chunks * (3 * t * t * n
                            + h * (2 * t * t * p + 4 * t * n * p))
    xbc = tokens_per_step * (h * p + 2 * n) * BF16
    y = tokens_per_step * h * p * BF16
    rows = 2 * tokens_per_step * h * F32           # Delta and cs
    states = chunks * h * n * p * F32
    fwd_bytes = xbc + rows + y + states
    bwd_bytes = xbc + rows + states + y + (
        y + 2 * tokens_per_step * n * F32 + rows)
    return (fwd_calls * fwd_ops + bwd_calls * bwd_ops,
            fwd_calls * fwd_bytes + bwd_calls * bwd_bytes)
