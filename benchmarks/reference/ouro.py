"""A looped decoder LM (``ByteDance/Ouro-2.6B``'s family, ``model_type``
``ouro``; arXiv:2510.25741, "Scaling Latent Reasoning via Looped Language
Models") trained as a causal language model under its exit-weighted loss: the
plain reference.

The equations, as the configuration's ``assumed`` and ``departures`` state
them (``RMS(x) = x * rsqrt(mean(x^2) + eps) * scale``):

* embedding: ``h_0 = Emb[tokens]`` (no scaling);
* one block, a sandwich of four norms: ``a = RMS_1(h)``; ``q, k, v = a Wq,
  a Wk, a Wv`` with ``H`` query heads over ``G`` KV heads of ``D`` (no bias,
  no norm on q or k, no output gate); q and k rotated over all ``D``
  dimensions (the half-split pairing of ``rotate_half``, base
  ``rope_theta``); query i sees every key ``j <= i``; query head h reads KV
  head ``h // (H / G)``; scores ``q k / sqrt(D)``, softmax; ``h = h +
  RMS_2((P v) Wo)``; ``m = RMS_3(h)``; ``h = h + RMS_4((silu(m Wg) * (m Wu))
  Wd)``;
* the loop: for ``t = 1..T`` (``total_ut_steps``) ``h`` passes blocks ``1..N``
  in order, then ``x_t = RMS_f(h)``; ``x_t`` is the exit state of step ``t``
  **and** the input of step ``t + 1`` (the final norm sits inside the loop);
  the blocks, ``RMS_f``, the head and the gate are the same parameters at
  every ``t``: a plain Python loop over the steps and, inside it, over the
  blocks;
* the exits: ``logits_t = x_t W_head``; ``lambda_t = sigmoid(x_t w_gate +
  b_gate)``; a token's exit distribution is ``p_t = lambda_t prod_{j<t} (1 -
  lambda_j)`` for ``t < T`` and ``p_T = prod_{j<T} (1 - lambda_j)``: the last
  step takes what is left, so the ``T`` sum to 1;
* the loss, for each next-token position i: ``loss_i = sum_t p_t,i nll_t,i -
  beta H(p_.,i)`` with ``nll_t,i = -log softmax(logits_t,i)[target_i]`` and
  ``H(p) = -sum_t p_t log p_t``; the step's loss is the mean over the ``B x
  (L - 1)`` positions. Gradients reach the gate through ``p`` and the stack
  through all ``T`` terms; a shared weight's gradient is the sum over its
  uses, which is what differentiating the loop gives.

Everything float32 with products at ``highest``; no kernel, no cache, nothing
of ``horovod_tpu``. Attention is dense and masked, taken a block of query
rows at a time so that the scores fit, and each exit's logits a block of rows
at a time (``jax.checkpoint`` around both and around a block application, so
that the backward pass holds one of each). Parameters arrive under the names
the benchmark drew them with (``embed/embedding``, ``DecoderBlock_<i>/
{norm_attn, attn/{q,k,v,out}, norm_attn_out, norm_ffn, mlp/{gate,up,down},
norm_ffn_out}``, ``final_norm/scale``, ``exit_gate/{kernel,bias}``,
``lm_head/kernel``) and keep them. A step takes its batch in blocks of rows
whose gradients add up. So that the whole fits on one chip, the start of the
parameters waits on the host until the change over the steps is measured, and
Adam's two moments wait there while a gradient is taken: XLA sums a shared
weight's four partial gradients in one pass at the end, so a row's gradient
program holds up to three further copies of the blocks' gradients (8.8 GB of
temporaries at the cell's sizes, beside 4.9 GB of parameters and gradient).

Departures from the published model: none in the equations; the loss is the
report's first-stage objective with ``beta`` as the configuration assumes it
(``config.json`` carries no training objective). One departure in the
arithmetic: the exit distribution is formed in logarithms (``log p_t = log
sigmoid(z_t) + sum_{j<t} log sigmoid(-z_j)``, ``p_t = exp(log p_t)``), not as
the product it is written as. By its third step at the cell's sizes the gate
saturates on some tokens of some seeds (a logit over 17), ``1 - sigmoid(z)``
then rounds to 0 in float32, ``p log p`` is ``0 * -inf`` and the written form
read a NaN loss on 3 of 31 seeds on the chip (PERF.md section 6, PR 32).
"""

import functools

import jax
import jax.numpy as jnp

from . import common

BLOCK = "DecoderBlock_"
ROWS = 512      # query rows of attention, and rows of logits, at a time


def _rms(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * p["scale"]


def _rotate(x, base):
    """``x [B, S, heads, D]`` by position: dimension i with i + D/2."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    angle = jnp.concatenate([angle, angle], -1)[None, :, None, :]
    first, second = jnp.split(x, 2, -1)
    return x * jnp.cos(angle) \
        + jnp.concatenate([-second, first], -1) * jnp.sin(angle)


def _by_row_blocks(f, s):
    """``f(start, rows)`` for every block of ``ROWS`` of the ``s`` positions,
    stacked; the backward pass recomputes a block."""
    rows = min(ROWS, s)
    assert s % rows == 0, (s, rows)
    return jax.lax.map(jax.checkpoint(lambda start: f(start, rows)),
                       jnp.arange(0, s, rows))


def _attention(a, p, *, hyper, einsum):
    b, s, _ = a.shape
    h, g, d = hyper["heads"], hyper["kv_heads"], hyper["head_dim"]

    def project(name, heads):
        return einsum("bse,ef->bsf", a, p[name]["kernel"]).reshape(
            b, s, heads, d)

    q = _rotate(project("q", h), hyper["rope_theta"])
    k = _rotate(project("k", g), hyper["rope_theta"])
    v = project("v", g)
    q = q.reshape(b, s, g, h // g, d)   # query head h reads KV head h // (H/G)

    def some_rows(start, rows):
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, 1)
        scores = einsum("bqgrd,bkgd->bgrqk", qb, k) / jnp.sqrt(1.0 * d)
        seen = jnp.arange(s)[None, :] <= start + jnp.arange(rows)[:, None]
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return einsum("bgrqk,bkgd->bqgrd", weights, v)

    out = jnp.moveaxis(_by_row_blocks(some_rows, s), 0, 1).reshape(
        b, s, h * d)
    return einsum("bsf,fe->bse", out, p["out"]["kernel"])


def _mlp(x, p, einsum):
    gate = einsum("...d,df->...f", x, p["gate"]["kernel"])
    up = einsum("...d,df->...f", x, p["up"]["kernel"])
    return einsum("...f,fd->...d", jax.nn.silu(gate) * up,
                  p["down"]["kernel"])


def _block(h, p, *, hyper, einsum):
    eps = hyper["rms_norm_eps"]
    attn = _attention(_rms(h, p["norm_attn"], eps), p["attn"], hyper=hyper,
                      einsum=einsum)
    h = h + _rms(attn, p["norm_attn_out"], eps)
    m = _mlp(_rms(h, p["norm_ffn"], eps), p["mlp"], einsum)
    return h + _rms(m, p["norm_ffn_out"], eps)


def _exit_nll(x, head, targets, einsum):
    """``nll [B, S - 1]`` of the next tokens under one exit's logits, the
    logits taken a block of rows at a time."""
    b, s, _ = x.shape
    # every position but the last has a target; the last is given target 0
    # and dropped
    padded = jnp.concatenate([targets, jnp.zeros((b, 1), targets.dtype)], 1)

    def some_rows(start, rows):
        xb = jax.lax.dynamic_slice_in_dim(x, start, rows, 1)
        tb = jax.lax.dynamic_slice_in_dim(padded, start, rows, 1)
        logp = jax.nn.log_softmax(einsum("bse,ev->bsv", xb, head), -1)
        return -jnp.take_along_axis(logp, tb[..., None], -1)[..., 0]

    nll = _by_row_blocks(some_rows, s)
    return jnp.moveaxis(nll, 0, 1).reshape(b, s)[:, :-1]


def _loss_rows(params, tokens, *, hyper, einsum):
    """The summed loss of each row's ``S - 1`` next-token positions."""
    eps, steps = hyper["rms_norm_eps"], hyper["total_ut_steps"]
    layers = sum(1 for name in params if name.startswith(BLOCK))
    block = jax.checkpoint(functools.partial(_block, hyper=hyper,
                                             einsum=einsum))
    h = params["embed"]["embedding"][tokens]
    nll, gate = [], []
    for _ in range(steps):
        for i in range(layers):
            h = block(h, params[f"{BLOCK}{i}"])
        h = _rms(h, params["final_norm"], eps)      # the exit, the next input
        nll.append(_exit_nll(h, params["lm_head"]["kernel"], tokens[:, 1:],
                             einsum))
        z = einsum("bse,eo->bso", h, params["exit_gate"]["kernel"])[..., 0] \
            + params["exit_gate"]["bias"][0]
        gate.append(z[:, :-1])
    # log p_t: log lambda_t = log sigmoid(z_t), log (1 - lambda_t) = log
    # sigmoid(-z_t); a p that underflows then gives 0 * log p = 0, no NaN
    log_left = jnp.zeros_like(gate[0])
    log_p = []
    for t in range(steps - 1):
        log_p.append(jax.nn.log_sigmoid(gate[t]) + log_left)
        log_left = log_left + jax.nn.log_sigmoid(-gate[t])
    log_p.append(log_left)              # the last step takes what is left
    p = [jnp.exp(log_p_t) for log_p_t in log_p]
    expected = sum(p_t * nll_t for p_t, nll_t in zip(p, nll))
    entropy = -sum(p_t * log_p_t for p_t, log_p_t in zip(p, log_p))
    return jnp.sum(expected - hyper["beta"] * entropy, -1)


def train_steps(params, batch, hyper: dict, *, steps: int, precision: str,
                loss_rows: int, rows_per_block: int, use_rows=None):
    """Drive ``steps`` optimizer steps on the one batch. ``params`` are
    consumed: the start is kept on the host and the device's copy is
    updated in place.

    ``loss_rows``: the reported loss is the mean over the first so many rows
    (what rank 0 of a data-parallel job reports); the gradient is that of the
    mean over all rows. ``use_rows`` plants the fault "part of the batch left
    out": only the first ``use_rows`` rows are used, the mean taken over them.

    Returns ``{"losses": [...], "grad_norms": {leaf: norm of the first
    gradient}, "delta_norms": {leaf: norm of the change over all steps}}``.
    """
    tokens = batch["tokens"]
    if use_rows is not None:
        tokens = tokens[:use_rows]
        loss_rows = min(loss_rows, use_rows)
    rows, s = tokens.shape
    rows_per_block = rows_per_block or rows
    row_losses = functools.partial(_loss_rows, hyper=hyper,
                                   einsum=common.make_einsum(precision))
    opt_init, opt_update = common.optimizer(hyper["optimizer"])

    @functools.partial(jax.jit, donate_argnums=(1,))
    def add_block_grad(p, acc, toks):
        def mean_part(p):
            each = row_losses(p, toks)
            return each.sum() / (rows * (s - 1)), each
        (_, each), g = jax.value_and_grad(mean_part, has_aux=True)(p)
        return jax.tree_util.tree_map(jnp.add, acc, g), each

    update = jax.jit(opt_update, donate_argnums=(0, 1, 2))
    sq_norms = jax.jit(common.leaf_sq_norms)
    sq_diff = jax.jit(lambda a, b: jnp.sum(jnp.square(a - b)))

    p = params
    start = jax.device_get(p)                   # waits on the host
    here = jax.tree_util.tree_leaves(p)[0].sharding
    opt_state = jax.device_get(opt_init(p))     # waits on the host
    losses, grad_sq = [], None
    for step in range(steps):
        acc = jax.tree_util.tree_map(jnp.zeros_like, p)
        each = []
        for r in range(0, rows, rows_per_block):
            acc, part = add_block_grad(p, acc, tokens[r:r + rows_per_block])
            each.append(part)
        each = jnp.concatenate(each)
        losses.append(float(each[:loss_rows].sum() / (loss_rows * (s - 1))))
        if step == 0:
            grad_sq = sq_norms(acc)
        p, opt_state = update(p, acc, jax.device_put(opt_state, here))
        opt_state = jax.device_get(opt_state)
    flat, _ = jax.tree_util.tree_flatten_with_path(p)
    start_flat = jax.tree_util.tree_leaves(start)
    delta_sq = {"/".join(k.key for k in path): sq_diff(leaf, was)
                for (path, leaf), was in zip(flat, start_flat)}
    return common.readings(losses, grad_sq, delta_sq)
