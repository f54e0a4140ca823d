"""A first pipeline stage of a ``granitemoehybrid`` decoder
(``ibm-granite/granite-4.0-h-micro``'s family: Mamba-2 layers among grouped
attention layers, every layer dense) trained as a causal language model: the
plain reference.

The layer equations, as ``modeling_granitemoehybrid`` computes them with the
configuration's numbers and as its ``assumed`` states them (``RMS(x) = x *
rsqrt(mean(x^2) + eps) * scale``):

* embedding: ``h = Emb[tokens] * embedding_multiplier``;
* the block: ``h = h + r * mixer(RMS_1(h))``; ``h = h + r * MLP(RMS_2(h))``
  with ``r`` the ``residual_multiplier`` and ``MLP(x) = (silu(x W_gate) * (x
  W_up)) W_down``;
* the Mamba mixer, for ``a = RMS_1(h)``: ``z | xBC | dt = a W_in``; ``xBC =
  silu(conv(xBC) + b)``, the conv causal and depthwise over ``conv`` taps
  (token t sees t - 3 .. t); ``x | B | C = xBC`` (``H`` heads of ``P``, one
  group of ``N``); ``Delta = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  **the recurrence token by token, the definition and not a chunked form**:
  ``S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t B_t^T``, ``y_t = S_t C_t + D
  x_t``, a ``P x N`` state a head; ``y = RMS_g(y * silu(z))`` over ``H x P``
  (the gate before the norm); ``mixer = y W_out``; no bias;
* the attention mixer: ``q, k, v = a Wq, a Wk, a Wv`` with ``heads`` query
  heads over ``kv_heads`` KV heads of ``head_dim``, query head h reading KV
  head ``h // (heads / kv_heads)``; no positional encoding, no q/k norm, no
  gate; scores ``q k * attn_scale``, query i sees every ``j <= i``;
  ``attn = softmax(scores) v Wo``;
* output: ``logits = RMS_f(h) Emb^T / logits_scaling`` (the head tied to
  the embedding); the loss is the mean negative log-likelihood of each next
  token.

Four entries of ``hyper`` are what a fault of this mechanism turns
(``benchmarks/plant.py``): ``carry_state`` (false: the state reset at every
chunk boundary), ``skip_d`` (false: the ``D x`` skip left out),
``gate_before_norm`` (false: ``RMS_g(y) * silu(z)``), ``conv_causal``
(false: the conv's taps centred, so a token sees the next one).

Everything float32 with products at ``highest``; no kernel, nothing of
``horovod_tpu``. The recurrence runs a span of :data:`SPAN` tokens at a
time under ``jax.checkpoint`` (so its gradient holds one span's states),
the token loop unrolled :data:`UNROLL` steps a loop iteration; attention
is dense and masked a block of :data:`QUERY_ROWS` query rows at a time; the
loss a block of :data:`LOSS_ROWS` rows. Parameters arrive under the names the
benchmark drew them with (``embed/embedding``, ``DecoderBlock_<i>/{norm_attn,
mamba/{in_proj, conv1d_kernel, conv1d_bias, dt_bias, A_log, D, norm,
out_proj} | attn/{q, k, v, out}, norm_ffn, mlp}``, ``final_norm/scale``) and
keep them."""

import functools

import jax
import jax.numpy as jnp

from . import common
from .trinity import BLOCK, _mlp, _rms

QUERY_ROWS = 128        # query rows of dense attention taken at a time
LOSS_ROWS = 1024        # rows of logits taken at a time
UNROLL = 32             # tokens of the recurrence a loop iteration
SPAN = 64               # tokens of the recurrence its gradient holds at once


def _attention(a, p, *, hyper, einsum):
    b, s, _ = a.shape
    h, g, d = hyper["heads"], hyper["kv_heads"], hyper["head_dim"]
    q = einsum("bse,ef->bsf", a, p["q"]["kernel"]).reshape(b, s, g, h // g, d)
    k = einsum("bse,ef->bsf", a, p["k"]["kernel"]).reshape(b, s, g, d)
    v = einsum("bse,ef->bsf", a, p["v"]["kernel"]).reshape(b, s, g, d)
    rows = min(QUERY_ROWS, s)
    assert s % rows == 0, (s, rows)

    def some_rows(start):
        part = jax.lax.dynamic_slice_in_dim(q, start, rows, 1)
        scores = einsum("bqgrd,bkgd->bgrqk", part, k) * hyper["attn_scale"]
        seen = jnp.arange(s)[None, :] <= start + jnp.arange(rows)[:, None]
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return einsum("bgrqk,bkgd->bqgrd", weights, v)

    out = jax.lax.map(jax.checkpoint(some_rows), jnp.arange(0, s, rows))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h * d)
    return einsum("bsf,fe->bse", out, p["out"]["kernel"])


def _conv(xbc, kernel, bias, causal: bool):
    """Depthwise over the sequence: causal, token t sees t - (taps - 1) ..
    t; or centred (the planted fault)."""
    taps, s = kernel.shape[0], xbc.shape[1]
    left = taps - 1 if causal else (taps - 1) // 2
    padded = jnp.pad(xbc, ((0, 0), (left, taps - 1 - left), (0, 0)))
    return bias + sum(padded[:, i:i + s] * kernel[i] for i in range(taps))


def _recurrence(x, dt, A, B, C, *, chunk, carry_state, einsum):
    """``y_t = S_t C_t`` with ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
    B_t^T``, token by token: x ``[b, s, H, P]``, dt ``[b, s, H]``, B, C ``[b,
    s, N]``. Without ``carry_state`` the state starts from zero at every
    ``chunk`` tokens (the planted fault)."""
    b, s, heads, p = x.shape
    n = B.shape[-1]
    span = min(SPAN, chunk)
    assert s % chunk == 0 and chunk % span == 0, (s, chunk, span)

    def spans(a):       # [spans, tokens of a span, b, ...]
        return jnp.moveaxis(a.reshape(b, s // span, span, *a.shape[2:]),
                            (1, 2), (0, 1))

    def token(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        state = jnp.exp(dt_t * A)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return state, einsum("bhpn,bn->bhp", state, c_t)

    def one_span(state, inputs):
        at, inputs = inputs
        if not carry_state:
            state = jnp.where(at % chunk == 0, 0.0, state)
        return jax.lax.scan(token, state, inputs, unroll=UNROLL)

    _, ys = jax.lax.scan(jax.checkpoint(one_span),
                         jnp.zeros((b, heads, p, n), jnp.float32),
                         (jnp.arange(0, s, span),
                          tuple(spans(a) for a in (x, dt, B, C))))
    return jnp.moveaxis(ys.reshape(s, b, heads, p), 0, 1)


def _mamba(a, p, *, hyper, einsum):
    b, s, _ = a.shape
    heads, hd = hyper["ssm_heads"], hyper["ssm_head_dim"]
    n = hyper["ssm_state"]
    inner = heads * hd
    proj = einsum("bse,ef->bsf", a, p["in_proj"]["kernel"])
    z, xbc, dt = (proj[..., :inner], proj[..., inner:2 * inner + 2 * n],
                  proj[..., 2 * inner + 2 * n:])
    xbc = jax.nn.silu(_conv(xbc, p["conv1d_kernel"], p["conv1d_bias"],
                            hyper["conv_causal"]))
    x = xbc[..., :inner].reshape(b, s, heads, hd)
    bmat, cmat = xbc[..., inner:inner + n], xbc[..., inner + n:]
    delta = jax.nn.softplus(dt + p["dt_bias"])
    y = _recurrence(x, delta, -jnp.exp(p["A_log"]), bmat, cmat,
                    chunk=hyper["chunk"], carry_state=hyper["carry_state"],
                    einsum=einsum)
    if hyper["skip_d"]:
        y = y + p["D"][:, None] * x
    y = y.reshape(b, s, inner)
    gate = jax.nn.silu(z)
    eps = hyper["rms_norm_eps"]
    if hyper["gate_before_norm"]:
        y = _rms(y * gate, p["norm"], eps)
    else:
        y = _rms(y, p["norm"], eps) * gate
    return einsum("bsf,fe->bse", y, p["out_proj"]["kernel"])


def _block(h, p, *, hyper, einsum):
    eps, r = hyper["rms_norm_eps"], hyper["residual_multiplier"]
    a = _rms(h, p["norm_attn"], eps)
    if "mamba" in p:
        a = _mamba(a, p["mamba"], hyper=hyper, einsum=einsum)
    else:
        a = _attention(a, p["attn"], hyper=hyper, einsum=einsum)
    h = h + r * a
    return h + r * _mlp(_rms(h, p["norm_ffn"], eps), p["mlp"], einsum)


def _nll_rows(params, tokens, *, hyper, einsum):
    """The summed negative log-likelihood of each row's next tokens."""
    emb = params["embed"]["embedding"]
    h = emb[tokens] * hyper["embedding_multiplier"]
    block = jax.checkpoint(functools.partial(_block, hyper=hyper,
                                             einsum=einsum))
    for i in range(hyper["layers"]):
        h = block(h, params[f"{BLOCK}{i}"])
    h = _rms(h, params["final_norm"], hyper["rms_norm_eps"])
    b, s, _ = h.shape
    rows = min(LOSS_ROWS, s)
    assert s % rows == 0, (s, rows)
    # the next token of every position; the last position's is no token
    target = jnp.concatenate([tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], 1)

    def some_rows(start):
        part = jax.lax.dynamic_slice_in_dim(h, start, rows, 1)
        logits = einsum("bse,ve->bsv", part, emb) / hyper["logits_scaling"]
        logp = jax.nn.log_softmax(logits, -1)
        want = jax.lax.dynamic_slice_in_dim(target, start, rows, 1)
        nll = -jnp.take_along_axis(logp, want[..., None], -1)[..., 0]
        return jnp.where(start + jnp.arange(rows) < s - 1, nll, 0.0)

    nll = jax.lax.map(jax.checkpoint(some_rows), jnp.arange(0, s, rows))
    return nll.sum((0, 2))


def train_steps(params, batch, hyper: dict, *, steps: int, precision: str,
                loss_rows: int, rows_per_block: int, use_rows=None):
    """``reference/moonlight.py``'s ``train_steps`` for this model: the same
    arguments and readings (``losses``, ``grad_norms`` of the first
    gradient, ``delta_norms`` of the change over all steps). A batch taken
    in one block needs no sum of blocks' gradients beside Adam's moments
    (the chip holds 772 M parameters four times). ``params`` are
    consumed."""
    tokens = batch["tokens"]
    if use_rows is not None:
        tokens = tokens[:use_rows]
        loss_rows = min(loss_rows, use_rows)
    rows, s = tokens.shape
    rows_per_block = min(rows_per_block or rows, rows)
    nll_rows = functools.partial(_nll_rows, hyper=hyper,
                                 einsum=common.make_einsum(precision))
    opt_init, opt_update = common.optimizer(hyper["optimizer"])

    def mean_part(p, toks):
        nll = nll_rows(p, toks)
        return nll.sum() / (rows * (s - 1)), nll

    grad = jax.jit(jax.value_and_grad(mean_part, has_aux=True))

    @functools.partial(jax.jit, donate_argnums=(1,))
    def add_block_grad(p, acc, toks):
        (_, nll), g = jax.value_and_grad(mean_part, has_aux=True)(p, toks)
        return jax.tree_util.tree_map(jnp.add, acc, g), nll

    # Adam's moments wait on the host between steps: beside the parameters,
    # the gradient and the gradient's working set the chip has no room for
    # two more copies of 772 M parameters
    update = jax.jit(opt_update, donate_argnums=(0, 1, 2))
    sq_norms = jax.jit(common.leaf_sq_norms)
    sq_diff = jax.jit(lambda a, b: jnp.sum(jnp.square(a - b)))

    p = params
    start = jax.device_get(p)                   # waits on the host
    opt_state = jax.device_get(jax.jit(opt_init)(p))
    losses, grad_sq = [], None
    for step in range(steps):
        if rows_per_block == rows:
            (_, nll), acc = grad(p, tokens)
        else:
            acc, nll = jax.tree_util.tree_map(jnp.zeros_like, p), []
            for r in range(0, rows, rows_per_block):
                acc, part = add_block_grad(p, acc,
                                           tokens[r:r + rows_per_block])
                nll.append(part)
            nll = jnp.concatenate(nll)
        losses.append(float(nll[:loss_rows].sum() / (loss_rows * (s - 1))))
        if step == 0:
            grad_sq = sq_norms(acc)
        p, opt_state = update(p, acc, opt_state)
        opt_state = jax.device_get(opt_state)
    flat, _ = jax.tree_util.tree_flatten_with_path(p)
    start_flat = jax.tree_util.tree_leaves(start)
    delta_sq = {"/".join(k.key for k in path): sq_diff(leaf, was)
                for (path, leaf), was in zip(flat, start_flat)}
    return common.readings(losses, grad_sq, delta_sq)
