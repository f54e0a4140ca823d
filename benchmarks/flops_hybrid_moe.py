"""Operations and bytes of a training step of a hybrid LM of single-branch
layers (``nemotron_h``: Mamba-2 with B and C in groups, grouped attention,
squared-ReLU experts), from shapes.

The conventions of ``flops_moe.py`` and ``flops_ssm.py``: two operations a
multiply-add, a step is 3 x forward, recomputed operations not counted, only
the multiply-adds of matrix products counted (the conv's taps, the norms,
the gates and the activations are not), causal attention over the ``(S + 1)
/ 2`` keys a query sees on average, the routed experts at the expectation of
even routing (``tokens x top_k x held / experts`` rows a layer). A layer is
one letter of ``pattern``: ``M`` a Mamba mixer, ``E`` an expert layer, ``*``
attention; a layer has one branch, so no MLP beside a mixer. The scan is
counted in its chunked form, as ``flops_ssm.py`` counts it, except that
``C B^T`` is made once a chunk **a group**: a token's row of it over the
``(T + 1) / 2`` tokens it sees in its chunk, ``G`` times.

``per_token`` is what ``step_mfu_pct`` reads; ``ssd_work`` gives
``(operations, bytes)`` of the scan kernels' calls a step for
``ssd_grouped_roofline_pct.tok`` and ``gmm_work`` those of the expert
layers' grouped products for ``moe_ungated_gmm_roofline_pct.tok``.

Hand-worked figures the tests hold these to (the first pipeline stage of
``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``: ``MEMEM*EME``, 8 of 128
experts, 16,384 of the vocabulary, 8,192 tokens a sequence):

* 318,431,232 parameters in matrix products a token: 4 x 38,707,200 Mamba
  (in 27,697,152, out 11,010,048), 4 x 24,041,472 expert (router 344,064,
  shared 19,955,712, routed 6 x 8 / 128 x 9,977,856 = 3,741,696),
  23,396,352 attention, 44,040,192 head;
* 1,378,816 multiply-adds a token a Mamba layer in the scan (8 x 64.5 x 128
  = 66,048 of ``G``, 64 x (4,128 + 16,384) of the heads), 5,515,264 over
  four; attention 32 x 256 x 4,096.5 = 33,558,528: 2,145,030,144 operations
  a token in all.
"""

from benchmarks.flops_moe import BF16, keys_seen

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
F32 = 4
# the scan's kernels as a device profile names them (``ops/ssd.py``)
SSD_KERNELS = ("hvd_ssd_scan",)


def matmul_params_per_token(*, pattern, d_model, heads, kv_heads, head_dim,
                            ssm_heads, ssm_head_dim, ssm_state, ssm_groups,
                            experts, experts_held, top_k, expert_width,
                            shared_width, vocab, **_) -> float:
    """Parameters whose matrix products one token passes through."""
    inner = ssm_heads * ssm_head_dim
    mamba = d_model * (2 * inner + 2 * ssm_groups * ssm_state + ssm_heads) \
        + inner * d_model
    attention = d_model * (heads + 2 * kv_heads) * head_dim \
        + heads * head_dim * d_model
    sparse = d_model * experts + 2 * d_model * shared_width \
        + top_k * experts_held / experts * 2 * d_model * expert_width
    own = {MAMBA: mamba, ATTENTION: attention, EXPERTS: sparse}
    return sum(own[kind] for kind in pattern) + d_model * vocab


def scan_macs_per_token(*, pattern, ssm_heads, ssm_head_dim, ssm_state,
                        ssm_groups, chunk, **_) -> float:
    """Forward multiply-adds a token of the scan's products, all Mamba
    layers."""
    seen = (chunk + 1) / 2
    per_layer = ssm_groups * seen * ssm_state + ssm_heads * (
        seen * ssm_head_dim + 2 * ssm_state * ssm_head_dim)
    return pattern.count(MAMBA) * per_layer


def attention_macs_per_token(*, pattern, heads, head_dim, seq_len,
                             **_) -> float:
    """Multiply-adds a token of ``q k`` and ``p v`` over the keys it sees,
    all attention layers, forward."""
    return pattern.count(ATTENTION) * heads * 2 * head_dim \
        * keys_seen(seq_len)


def per_token(**sizes) -> float:
    """Operations a token of one training step: ``6 x`` the parameters in
    matrix products, the scan's and attention's multiply-adds."""
    return 6 * (matmul_params_per_token(**sizes) + scan_macs_per_token(**sizes)
                + attention_macs_per_token(**sizes))


def ssd_work(*, fwd_calls, bwd_calls, tokens_per_step, ssm_heads,
             ssm_head_dim, ssm_state, ssm_groups, chunk, **_):
    """``(operations, bytes)`` of ``fwd_calls`` forward kernel calls (the
    recomputed ones among them) and ``bwd_calls`` backward ones a step, each
    over ``tokens_per_step`` tokens, as ``ops/ssd.py``'s kernels run their
    products: ``flops_ssm.ssd_work``'s count with the products of ``G`` (made
    and differentiated once a chunk a group) ``G`` times, and B, C and their
    gradients ``G`` groups wide.

    A chunk, forward: ``C B^T`` once a group (``G T T N``); a head ``(G .
    L)(Delta x)`` (``T T P``), ``C H`` and ``B^T (w Delta x)`` (``T N P``
    each). Backward: ``C B^T``, ``dG B`` and ``dG^T C`` once a group (``3 G
    T T N``); a head ``M^T dY`` and ``dY (Delta x)^T`` (``2 T T P``), C's and
    B's gradients off the states, ``B dH`` and ``C^T (e dY)`` (``4 T N
    P``).

    Bytes, read or written once a call: forward x | B | C and y in bfloat16,
    Delta and its running sum a head in float32, the states entering every
    chunk (``N x P`` float32 a head) written; backward those inputs, the
    states and dY read, dx, dB, dC (float32, ``G x N`` a token each), dDelta
    and d(cs) written."""
    t, n, p, h, g = chunk, ssm_state, ssm_head_dim, ssm_heads, ssm_groups
    chunks = tokens_per_step / t
    fwd_ops = 2 * chunks * (g * t * t * n + h * (t * t * p + 2 * t * n * p))
    bwd_ops = 2 * chunks * (3 * g * t * t * n
                            + h * (2 * t * t * p + 4 * t * n * p))
    xbc = tokens_per_step * (h * p + 2 * g * n) * BF16
    y = tokens_per_step * h * p * BF16
    rows = 2 * tokens_per_step * h * F32           # Delta and cs
    states = chunks * h * n * p * F32
    fwd_bytes = xbc + rows + y + states
    bwd_bytes = xbc + rows + states + y + (
        y + 2 * tokens_per_step * g * n * F32 + rows)
    return (fwd_calls * fwd_ops + bwd_calls * bwd_ops,
            fwd_calls * fwd_bytes + bwd_calls * bwd_bytes)


def gmm_work(*, tokens_per_step, d_model, experts, experts_held, top_k,
             expert_width, pattern, fwd_products=None, **_):
    """``(operations, bytes)`` a step of the expert layers' grouped products
    over the expected rows: ``fwd_products`` forward (by default two an
    ``E`` layer of ``pattern``: up and down, no gate; a recomputed layer's
    run twice) and two backward for each of an ``E`` layer's two (by the
    rows, by the weights). Bytes: each product's two operands and its result
    once, bfloat16."""
    layer_products = 2 * pattern.count(EXPERTS)
    if fwd_products is None:
        fwd_products = layer_products
    products = fwd_products + 2 * layer_products
    rows = tokens_per_step * top_k * experts_held / experts
    d, f = d_model, expert_width
    narrow, wide, weights = rows * f * BF16, rows * d * BF16, \
        experts_held * d * f * BF16
    return (products * 2 * rows * d * f,
            products * (narrow + wide + weights))
