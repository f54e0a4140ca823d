"""A chip's share of a ``deepseek_v3`` decoder with latent attention
(``moonshotai/Moonlight-16B-A3B``'s family, ``q_lora_rank: null``) trained as
a causal language model: the plain reference.

The layer equations, as ``modeling_deepseek.py`` computes them in the expanded
form that training uses and as the configuration's ``assumed`` and
``departures`` state them (``RMS(x) = x * rsqrt(mean(x^2) + eps) * scale``):

* embedding: ``h = Emb[tokens]``, no multiplier;
* attention, ``H`` heads, for ``a = RMS_1(h)``: ``q = a W_q``, a head's
  ``nope_dim + rope_dim`` columns ``q_nope | q_pe``; ``c | kr = a W_kva``
  with ``c`` the compressed row of ``latent_dim`` and ``kr`` one rope key of
  ``rope_dim`` a token; ``c = RMS_c(c)``; ``kv = c W_kvb``, a head's
  ``nope_dim + value_dim`` columns ``k_nope | v``; ``q_pe`` and ``kr`` are
  rotated by position (all ``rope_dim`` dimensions, the half-split pairing of
  ``rotate_half``, base ``rope_theta``, no scaling); a head's key is ``k_nope
  | kr``, the same ``kr`` for all heads; scores ``q k / sqrt(score_width)``
  with ``score_width = nope_dim + rope_dim``, query i sees every ``j <= i``,
  softmax; ``attn = (P v, all heads) W_o``; no bias, no gate, no q/k norm;
* the block: ``h = h + attn``; ``h = h + F(RMS_2(h))``: two norms a block;
  ``F`` is ``MLP_dense`` in the leading dense layers and the expert layer
  after them;
* the expert layer, the bias rule after each optimizer step, the output and
  the loss: ``reference/trinity.py``'s, whose functions are used here (sigmoid
  scores over all ``E`` experts, the top ``k`` of ``s + b``, weights the
  chosen experts' unbiased ``s`` over their sum times ``route_scale``, the
  shared experts as one gated MLP every token passes, the part of the chosen
  experts **held here** and nothing for the absent ones, no token dropped).

Three entries of ``hyper`` are what a fault of this mechanism turns
(``benchmarks/plant.py``): ``rotate_key`` (false: the rope key left
unrotated), ``latent_norm`` (false: the norm on the compressed row left out)
and ``score_width`` (128: scores scaled by the values' width).

Everything float32 with products at ``highest``; no kernel, no cache, nothing
of ``horovod_tpu``. 16 heads x 8,192 x 8,192 float32 scores are 4.3 GB a
sequence a layer, so attention is dense and masked a block of query rows at a
time, recomputed in the gradient, and a step takes its batch a block of rows
at a time. Parameters arrive under the names the benchmark drew them with
(``embed/embedding``, ``DecoderBlock_<i>/{norm_attn, attn/{q, kv_a, kv_norm,
kv_b, out}, norm_ffn, mlp | moe/{router, experts_gate, experts_up,
experts_down, shared}}``, ``final_norm/scale``, ``lm_head/kernel``) and keep
them.
"""

import functools

import jax
import jax.numpy as jnp

from . import common
from .trinity import BLOCK, QUERY_ROWS, _experts, _mlp, _next_bias, _rms, \
    _rotate


def _attention(a, p, *, hyper, einsum):
    b, s, _ = a.shape
    h, dn, dr = hyper["heads"], hyper["nope_dim"], hyper["rope_dim"]
    dv, rank = hyper["value_dim"], hyper["latent_dim"]
    q = einsum("bse,ef->bsf", a, p["q"]["kernel"]).reshape(b, s, h, dn + dr)
    row = einsum("bse,ef->bsf", a, p["kv_a"]["kernel"])
    c, kr = row[..., :rank], row[..., None, rank:]          # kr [b, s, 1, dr]
    if hyper["latent_norm"]:
        c = _rms(c, p["kv_norm"], hyper["rms_norm_eps"])
    kv = einsum("bsr,rf->bsf", c, p["kv_b"]["kernel"]).reshape(
        b, s, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_nope, q_pe = q[..., :dn], _rotate(q[..., dn:], hyper["rope_theta"])
    if hyper["rotate_key"]:
        kr = _rotate(kr, hyper["rope_theta"])
    rows = min(QUERY_ROWS, s)
    assert s % rows == 0, (s, rows)

    def some_rows(start):
        def part(x):
            return jax.lax.dynamic_slice_in_dim(x, start, rows, 1)

        scores = (einsum("bqhd,bkhd->bhqk", part(q_nope), k_nope)
                  + einsum("bqhd,bkd->bhqk", part(q_pe), kr[:, :, 0])) \
            / jnp.sqrt(1.0 * hyper["score_width"])
        seen = jnp.arange(s)[None, :] <= start + jnp.arange(rows)[:, None]
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return einsum("bhqk,bkhd->bqhd", weights, v)

    out = jax.lax.map(jax.checkpoint(some_rows), jnp.arange(0, s, rows))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h * dv)
    return einsum("bsf,fe->bse", out, p["out"]["kernel"])


def _block(h, p, bias, *, hyper, einsum):
    eps = hyper["rms_norm_eps"]
    h = h + _attention(_rms(h, p["norm_attn"], eps), p["attn"], hyper=hyper,
                       einsum=einsum)
    m = _rms(h, p["norm_ffn"], eps)
    if "moe" in p:
        m, counts = _experts(m, p["moe"], bias, hyper=hyper, einsum=einsum)
    else:
        m, counts = _mlp(m, p["mlp"], einsum), None
    return h + m, counts


def _nll_rows(params, biases, tokens, *, hyper, einsum):
    """``(summed negative log-likelihood of each row's next tokens, {expert
    layer: counts})``."""
    h = params["embed"]["embedding"][tokens]
    if hyper["embed_scale"]:
        h = h * jnp.sqrt(1.0 * h.shape[-1])
    counts = {}
    block = jax.checkpoint(functools.partial(_block, hyper=hyper,
                                             einsum=einsum))
    for i in range(hyper["layers"]):
        name = f"{BLOCK}{i}"
        h, seen = block(h, params[name], biases.get(name))
        if seen is not None:
            counts[name] = seen
    h = _rms(h, params["final_norm"], hyper["rms_norm_eps"])
    logits = einsum("bse,ev->bsv", h, params["lm_head"]["kernel"])
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0].sum(-1)
    return nll, counts


def train_steps(params, batch, hyper: dict, *, steps: int, precision: str,
                loss_rows: int, rows_per_block: int, use_rows=None):
    """``reference/trinity.py``'s ``train_steps`` over this model's
    ``_nll_rows``: the same arguments, the same readings (``losses``,
    ``grad_norms`` of the first gradient, ``delta_norms`` of the change over
    all steps). ``params`` are consumed."""
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
