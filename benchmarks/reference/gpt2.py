"""GPT-2 (Radford et al. 2019; the block of ``openai-community/gpt2-medium``)
trained as a causal language model: the plain reference.

Pre-norm blocks, learned positions, tanh-GELU, attention scaled by
``1/sqrt(head size)`` under a causal mask, the loss the mean negative
log-likelihood of each next token. Everything float32 with products at
``highest``; no kernels, no cache. It follows the configuration's ``hyper``
entry where the program under test departs from the published block (no bias
on the QKV projection, an output head that is not tied to the embedding, the
layer-norm epsilon), so that both sides compute the same function.

Parameters arrive under the names the benchmark drew them with:
``Embed_0/embedding`` (tokens), ``Embed_1/embedding`` (positions),
``TransformerBlock_<i>/{LayerNorm_0, Dense_0 (QKV), Dense_1 (out),
LayerNorm_1, Dense_2 (up), Dense_3 (down)}``, ``LayerNorm_0`` (final) and
``lm_head/kernel``. The blocks are stacked and scanned, each recomputed in the
backward pass, and a step takes its batch in blocks of rows whose gradients
add up, so that the whole fits beside its own Adam state on one chip.
"""

import functools

import jax
import jax.numpy as jnp

from . import common

BLOCK = "TransformerBlock_"


def _split(params):
    """``(outer, stacked)``: the leaves outside the blocks, and the blocks'
    leaves stacked along a new first axis."""
    n = sum(1 for k in params if k.startswith(BLOCK))
    outer = {k: v for k, v in params.items() if not k.startswith(BLOCK)}
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[params[f"{BLOCK}{i}"] for i in range(n)])
    return {"outer": outer, "blocks": stacked}


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(0.7978845608028654
                                   * (x + 0.044715 * x ** 3)))


def _block(x, p, *, heads, eps, qkv_bias, einsum):
    b, s, e = x.shape
    h = _layer_norm(x, p["LayerNorm_0"], eps)
    qkv = einsum("bse,ef->bsf", h, p["Dense_0"]["kernel"])
    if qkv_bias:
        qkv = qkv + p["Dense_0"]["bias"]
    q, k, v = (t.reshape(b, s, heads, e // heads)
               for t in jnp.split(qkv, 3, -1))
    scores = einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(e // heads)
    causal = jnp.tril(jnp.ones((s, s), bool))
    weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    attn = einsum("bhqk,bkhd->bqhd", weights, v).reshape(b, s, e)
    x = x + einsum("bse,ef->bsf", attn, p["Dense_1"]["kernel"]) \
        + p["Dense_1"]["bias"]
    h = _layer_norm(x, p["LayerNorm_1"], eps)
    h = einsum("bse,ef->bsf", h, p["Dense_2"]["kernel"]) + p["Dense_2"]["bias"]
    h = _gelu_tanh(h)
    return x + einsum("bsf,fe->bse", h, p["Dense_3"]["kernel"]) \
        + p["Dense_3"]["bias"]


def _nll_rows(p, tokens, *, block, eps, einsum, tied_head):
    """Summed negative log-likelihood of each row's next tokens."""
    outer = p["outer"]
    s = tokens.shape[1]
    x = outer["Embed_0"]["embedding"][tokens] \
        + outer["Embed_1"]["embedding"][:s][None]
    x, _ = jax.lax.scan(
        jax.checkpoint(lambda x, layer: (block(x, layer), None)),
        x, p["blocks"])
    x = _layer_norm(x, outer["LayerNorm_0"], eps)
    head = (outer["Embed_0"]["embedding"].T if tied_head
            else outer["lm_head"]["kernel"])
    logits = einsum("bse,ev->bsv", x, head)
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0].sum(-1)


def _leaf_sq_norms(tree):
    """``{leaf name: squared norm}`` under the benchmark's names."""
    out = common.leaf_sq_norms(tree["outer"])
    flat, _ = jax.tree_util.tree_flatten_with_path(tree["blocks"])
    for path, leaf in flat:
        rows = common.sq_norm_rows(leaf)
        for i in range(rows.shape[0]):
            out[BLOCK + f"{i}/" + "/".join(k.key for k in path)] = rows[i]
    return out


def train_steps(params, batch, hyper: dict, *, steps: int, precision: str,
                loss_rows: int, rows_per_block: int, use_rows=None):
    """Drive ``steps`` optimizer steps on the one batch. ``params`` are
    consumed: their buffers are freed once they are restacked.

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
    block = functools.partial(
        _block, heads=hyper["n_head"], eps=hyper["layer_norm_eps"],
        qkv_bias=hyper["qkv_bias"], einsum=common.make_einsum(precision))
    nll_rows = functools.partial(
        _nll_rows, block=block, eps=hyper["layer_norm_eps"],
        einsum=common.make_einsum(precision), tied_head=hyper["tied_head"])
    opt_init, opt_update = common.optimizer(hyper["optimizer"])

    @functools.partial(jax.jit, donate_argnums=(1,))
    def add_block_grad(p, acc, toks):
        def mean_part(p):
            nll = nll_rows(p, toks)
            return nll.sum() / (rows * (s - 1)), nll
        (_, nll), g = jax.value_and_grad(mean_part, has_aux=True)(p)
        return jax.tree_util.tree_map(jnp.add, acc, g), nll

    update = jax.jit(opt_update, donate_argnums=(0, 2))
    sq_norms = jax.jit(_leaf_sq_norms)
    sq_diff = jax.jit(lambda a, b: _leaf_sq_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b)))

    start = jax.jit(_split)(params)
    jax.tree_util.tree_map(lambda a: a.delete(), params)   # consumed
    p = jax.tree_util.tree_map(jnp.copy, start)
    opt_state = opt_init(p)
    losses, grad_sq = [], None
    for step in range(steps):
        acc = jax.tree_util.tree_map(jnp.zeros_like, p)
        nll = []
        for r in range(0, rows, rows_per_block):
            acc, part = add_block_grad(p, acc, tokens[r:r + rows_per_block])
            nll.append(part)
        nll = jnp.concatenate(nll)
        losses.append(float(nll[:loss_rows].sum() / (loss_rows * (s - 1))))
        if step == 0:
            grad_sq = sq_norms(acc)
        p, opt_state = update(p, acc, opt_state)
    return common.readings(losses, grad_sq, sq_diff(p, start))
