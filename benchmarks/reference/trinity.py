"""A chip's share of an ``afmoe`` decoder (``arcee-ai/Trinity-Mini``'s family)
trained as a causal language model: the plain reference.

The layer equations, as the configuration's ``assumed`` and ``departures``
state them (``RMS(x) = x * rsqrt(mean(x^2) + eps) * scale``):

* embedding: ``h = Emb[tokens] * sqrt(d)`` (``mup_enabled``);
* attention: ``a = RMS_1(h)``; ``q, k, v, g = a Wq, a Wk, a Wv, a Wg`` with
  ``H`` query heads and ``G`` KV heads of ``D``; ``q = RMS_q(q)``, ``k =
  RMS_k(k)`` over ``D``; in a ``sliding_attention`` layer q and k are rotated
  (all ``D`` dimensions, the half-split pairing of ``rotate_half``) and
  query i sees keys j with ``0 <= i - j < window``; in a ``full_attention``
  layer nothing is rotated and i sees every ``j <= i``; query head h reads
  KV head ``h // (H / G)``; scores ``q k / sqrt(D)``, softmax;
  ``o = (P v) * sigmoid(g)``; ``attn = o Wo``; no bias anywhere;
* the block: ``h = h + RMS_2(attn)``; ``m = RMS_3(h)``; ``h = h +
  RMS_4(F(m))``; ``F`` is ``MLP_dense`` in the leading dense layers and the
  expert layer after them; ``MLP_w(x) = (silu(x W_gate) * (x W_up)) W_down``;
* the expert layer: ``s = sigmoid(x W_r)`` over all ``E`` experts; the chosen
  set is the top ``k`` of ``s + b`` (``b`` the selection bias, no gradient);
  ``w_e = route_scale * s_e / (sum over the chosen of s + 1e-20)``;
  ``y = MLP_shared(x) + sum over the chosen experts **held here** of w_e
  MLP_e(x)``: a plain loop over the held experts, each applied to every token
  under a mask. What the absent experts would add is left out (the chip's
  share of the deployment), and no token is dropped;
* after each optimizer step, an expert layer: ``c_e`` = tokens of the step
  that chose e; ``delta = coeff * sign(mean(c) - c)``; ``delta -=
  mean(delta)``; ``b += delta``;
* output: ``RMS_f(h) W_head``; the loss is the mean negative log-likelihood
  of each next token.

Everything float32 with products at ``highest``; no kernel, no cache, nothing
of ``horovod_tpu``. Attention is dense and masked, taken a block of query
rows at a time so that the scores fit. Parameters arrive under the names the
benchmark drew them with (``embed/embedding``, ``DecoderBlock_<i>/{norm_attn,
attn/{q,k,v,gate,out,q_norm,k_norm}, norm_attn_out, norm_ffn, mlp | moe/
{router, experts_gate, experts_up, experts_down, shared}, norm_ffn_out}``,
``final_norm/scale``, ``lm_head/kernel``) and keep them. A step takes its
batch in blocks of rows whose gradients and expert counts add up; the start
of the parameters waits on the host until the change over the steps is
measured, so that the whole fits beside its own Adam state on one chip.
"""

import functools

import jax
import jax.numpy as jnp

from . import common

BLOCK = "DecoderBlock_"
QUERY_ROWS = 512        # query rows of dense attention taken at a time


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


def _mlp(x, p, einsum):
    gate = einsum("...d,df->...f", x, p["gate"]["kernel"])
    up = einsum("...d,df->...f", x, p["up"]["kernel"])
    return einsum("...f,fd->...d", jax.nn.silu(gate) * up,
                  p["down"]["kernel"])


def _attention(a, p, *, hyper, window, einsum):
    b, s, _ = a.shape
    h, g, d = hyper["heads"], hyper["kv_heads"], hyper["head_dim"]
    eps = hyper["rms_norm_eps"]

    def project(name, heads):
        return einsum("bse,ef->bsf", a, p[name]["kernel"]).reshape(
            b, s, heads, d)

    q = _rms(project("q", h), p["q_norm"], eps)
    k = _rms(project("k", g), p["k_norm"], eps)
    v = project("v", g)
    if window is not None:
        q, k = _rotate(q, hyper["rope_theta"]), _rotate(k, hyper["rope_theta"])
    # query head h reads KV head h // (H / G)
    q = q.reshape(b, s, g, h // g, d)
    rows = min(QUERY_ROWS, s)
    assert s % rows == 0, (s, rows)

    def some_rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, 1)
        scores = einsum("bqgrd,bkgd->bgrqk", qb, k) / jnp.sqrt(1.0 * d)
        qi = start + jnp.arange(rows)[:, None]
        kj = jnp.arange(s)[None, :]
        seen = kj <= qi
        if window is not None:
            seen &= qi - kj < window
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return einsum("bgrqk,bkgd->bqgrd", weights, v)

    out = jax.lax.map(jax.checkpoint(some_rows), jnp.arange(0, s, rows))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h * d)
    out = out * jax.nn.sigmoid(einsum("bse,ef->bsf", a, p["gate"]["kernel"]))
    return einsum("bsf,fe->bse", out, p["out"]["kernel"])


def _experts(x, p, bias, *, hyper, einsum):
    """``(y, counts)`` of the expert layer for tokens ``x [..., d]``."""
    k, first = hyper["top_k"], hyper["first_expert"]
    scores = jax.nn.sigmoid(einsum("...d,de->...e", x, p["router"]))
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), k)
    picked = jnp.take_along_axis(scores, chosen, -1)
    weights = hyper["route_scale"] * picked \
        / (picked.sum(-1, keepdims=True) + 1e-20)
    counts = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1]),
                     axis=tuple(range(chosen.ndim)))
    y = _mlp(x, p["shared"], einsum) if "shared" in p else 0.0

    def one_expert(total, held):
        w_gate, w_up, w_down, e = held
        weight = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
        out = _mlp(x, {"gate": {"kernel": w_gate}, "up": {"kernel": w_up},
                       "down": {"kernel": w_down}}, einsum)
        return total + weight[..., None] * out, None

    held = p["experts_gate"].shape[0]
    routed, _ = jax.lax.scan(
        jax.checkpoint(one_expert), jnp.zeros_like(x),
        (p["experts_gate"], p["experts_up"], p["experts_down"],
         jnp.arange(held)))
    return y + routed, counts


def _block(h, p, bias, *, kind, hyper, einsum):
    eps = hyper["rms_norm_eps"]
    window = hyper["sliding_window"] if kind == "sliding_attention" else None
    attn = _attention(_rms(h, p["norm_attn"], eps), p["attn"], hyper=hyper,
                      window=window, einsum=einsum)
    h = h + _rms(attn, p["norm_attn_out"], eps)
    m = _rms(h, p["norm_ffn"], eps)
    if "moe" in p:
        m, counts = _experts(m, p["moe"], bias, hyper=hyper, einsum=einsum)
    else:
        m, counts = _mlp(m, p["mlp"], einsum), None
    return h + _rms(m, p["norm_ffn_out"], eps), counts


def _nll_rows(params, biases, tokens, *, hyper, einsum):
    """``(summed negative log-likelihood of each row's next tokens, {expert
    layer: counts})``."""
    d = params["embed"]["embedding"].shape[-1]
    h = params["embed"]["embedding"][tokens]
    if hyper["embed_scale"]:
        h = h * jnp.sqrt(1.0 * d)
    counts = {}
    for i, kind in enumerate(hyper["layer_types"]):
        name = f"{BLOCK}{i}"
        block = jax.checkpoint(functools.partial(
            _block, kind=kind, hyper=hyper, einsum=einsum))
        h, seen = block(h, params[name], biases.get(name))
        if seen is not None:
            counts[name] = seen
    h = _rms(h, params["final_norm"], hyper["rms_norm_eps"])
    logits = einsum("bse,ev->bsv", h, params["lm_head"]["kernel"])
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0].sum(-1)
    return nll, counts


def _next_bias(bias, counts, coeff):
    delta = coeff * jnp.sign(jnp.mean(counts) - counts)
    return bias + (delta - jnp.mean(delta))


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
    nll_rows = functools.partial(_nll_rows, hyper=hyper,
                                 einsum=common.make_einsum(precision))
    opt_init, opt_update = common.optimizer(hyper["optimizer"])

    @functools.partial(jax.jit, donate_argnums=(2,))
    def add_block_grad(p, biases, acc, toks):
        def mean_part(p):
            nll, counts = nll_rows(p, biases, toks)
            return nll.sum() / (rows * (s - 1)), (nll, counts)
        (_, (nll, counts)), g = jax.value_and_grad(mean_part,
                                                   has_aux=True)(p)
        return jax.tree_util.tree_map(jnp.add, acc, g), nll, counts

    update = jax.jit(opt_update, donate_argnums=(0, 1, 2))
    sq_norms = jax.jit(common.leaf_sq_norms)
    sq_diff = jax.jit(lambda a, b: jnp.sum(jnp.square(a - b)))
    next_bias = jax.jit(functools.partial(
        _next_bias, coeff=hyper["load_balance_coeff"]))

    p = params
    start = jax.device_get(p)                   # waits on the host
    biases = {k: jnp.zeros((hyper["experts"],), jnp.float32)
              for k in p if "moe" in p[k]}
    opt_state = opt_init(p)
    losses, grad_sq = [], None
    for step in range(steps):
        acc = jax.tree_util.tree_map(jnp.zeros_like, p)
        nll, counts = [], None
        for r in range(0, rows, rows_per_block):
            acc, part, seen = add_block_grad(p, biases, acc,
                                             tokens[r:r + rows_per_block])
            nll.append(part)
            counts = seen if counts is None else jax.tree_util.tree_map(
                jnp.add, counts, seen)
        nll = jnp.concatenate(nll)
        losses.append(float(nll[:loss_rows].sum() / (loss_rows * (s - 1))))
        if step == 0:
            grad_sq = sq_norms(acc)
        p, opt_state = update(p, acc, opt_state)
        biases = {k: next_bias(biases[k], counts[k]) for k in biases}
    flat, _ = jax.tree_util.tree_flatten_with_path(p)
    start_flat = jax.tree_util.tree_leaves(start)
    delta_sq = {"/".join(k.key for k in path): sq_diff(leaf, was)
                for (path, leaf), was in zip(flat, start_flat)}
    return common.readings(losses, grad_sq, delta_sq)
