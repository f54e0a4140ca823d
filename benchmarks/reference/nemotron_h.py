"""A first pipeline stage of a ``nemotron_h`` decoder
(``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``'s family: single-branch
layers of Mamba-2, grouped attention or sparse experts, by a pattern), one
chip's share of its expert layers, trained as a causal language model: the
plain reference.

The layer equations, as ``modeling_nemotron_h`` computes them with the
configuration's numbers and as its ``assumed`` states them (``RMS(x) = x *
rsqrt(mean(x^2) + eps) * scale``):

* embedding: ``h = Emb[tokens]``, no multiplier;
* layer ``i``: ``h = h + F_i(RMS_i(h))``, ``F_i`` by the layer's letter of
  ``hybrid_override_pattern``; no norm after the branch, no multiplier;
* ``M``, the Mamba mixer, for ``a = RMS_i(h)``: ``z | xBC | dt = a W_in``;
  ``xBC = silu(conv(xBC) + b)``, the conv causal and depthwise over ``conv``
  taps (token t sees t - 3 .. t); ``x | B | C = xBC`` (``H`` heads of ``P``,
  then B and C each ``G`` groups of ``N``; head h reads group ``h // (H /
  G)``); ``Delta = softplus(dt + dt_bias)`` (no clamp), ``A = -exp(A_log)``;
  **the recurrence token by token, the definition and not a chunked form**:
  ``S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t B_t^T``, ``y_t = S_t C_t + D
  x_t``, a ``P x N`` state a head; ``y = RMS_G(y * silu(z))`` (the gate
  before the norm, each of the ``G`` groups of ``H x P / G`` channels
  normalised alone, one scale over all); ``mixer = y W_out``; no bias;
* ``*``, attention: ``q, k, v = a Wq, a Wk, a Wv`` with ``heads`` query
  heads over ``kv_heads`` KV heads of ``head_dim``, query head h reading KV
  head ``h // (heads / kv_heads)``; no positional encoding, no q/k norm, no
  gate, no bias; scores ``q k / sqrt(head_dim)``, query i sees every ``j <=
  i``; ``attn = softmax(scores) v Wo``;
* ``E``, the expert layer: ``s = sigmoid(a W_r)`` over all ``E`` experts;
  the chosen set is the top ``k`` of ``s + bias`` (the bias a buffer moved
  by the balancing rule after each optimizer step); weights the chosen
  experts' unbiased ``s`` over their sum times ``route_scale``; ``F = sum
  over the chosen experts held here of w_e relu(a U_e)^2 D_e + relu(a
  U_s)^2 D_s``, nothing for the absent experts, no token dropped;
* output: ``logits = RMS_f(h) W_head`` (a head of its own); the loss is the
  mean negative log-likelihood of each next token.

Four entries of ``hyper`` are what a fault of this architecture turns
(``tests/test_nemotron_h_cell.py``, ``benchmarks/plant.py``):
``grouped_bc`` (false: every head reads B and C of group 0),
``grouped_norm`` (false: the gated norm over all ``H x P`` channels at
once), ``squared_relu`` (false: ``relu`` where ``relu^2`` is) and
``shared_gated`` (true: a gate on the shared expert, ``(silu(a U_s) * a
U_s) D_s``, its up-projection read twice since the layer has no gate of its
own).

Everything float32 with products at ``highest``; no kernel, nothing of
``horovod_tpu``. The recurrence runs a span of :data:`SPAN` tokens at a
time under ``jax.checkpoint``, the token loop unrolled :data:`UNROLL` steps
a loop iteration; attention is dense and masked a block of
:data:`QUERY_ROWS` query rows at a time; the routed experts one at a time;
the loss a block of :data:`LOSS_ROWS` rows. Parameters arrive under the
names the benchmark drew them with (``embed/embedding``,
``DecoderBlock_<i>/{norm, mamba/{in_proj, conv1d_kernel, conv1d_bias,
dt_bias, A_log, D, norm, out_proj} | attn/{q, k, v, out} | moe/{router,
experts_up, experts_down, shared/{up, down}}}``, ``final_norm/scale``,
``lm_head/kernel``) and keep them."""

import functools

import jax
import jax.numpy as jnp

from . import common
from .granite import _conv
from .trinity import BLOCK, _next_bias, _rms

QUERY_ROWS = 128        # query rows of dense attention taken at a time
LOSS_ROWS = 1024        # rows of logits taken at a time
UNROLL = 16             # tokens of the recurrence a loop iteration
SPAN = 16               # tokens of the recurrence its gradient holds at once


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
        scores = einsum("bqgrd,bkgd->bgrqk", part, k) / jnp.sqrt(1.0 * d)
        seen = jnp.arange(s)[None, :] <= start + jnp.arange(rows)[:, None]
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return einsum("bgrqk,bkgd->bqgrd", weights, v)

    out = jax.lax.map(jax.checkpoint(some_rows), jnp.arange(0, s, rows))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h * d)
    return einsum("bsf,fe->bse", out, p["out"]["kernel"])


def _recurrence(x, dt, A, B, C, *, einsum):
    """``y_t = S_t C_t`` with ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
    B_t^T``, token by token: x ``[b, s, H, P]``, dt ``[b, s, H]``, B, C ``[b,
    s, H, N]`` (each head's own)."""
    b, s, heads, p = x.shape
    n = B.shape[-1]
    span = min(SPAN, s)
    assert s % span == 0, (s, span)

    def spans(a):       # [spans, tokens of a span, b, ...]
        return jnp.moveaxis(a.reshape(b, s // span, span, *a.shape[2:]),
                            (1, 2), (0, 1))

    def token(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        state = jnp.exp(dt_t * A)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, einsum("bhpn,bhn->bhp", state, c_t)

    def one_span(state, inputs):
        return jax.lax.scan(token, state, inputs, unroll=UNROLL)

    _, ys = jax.lax.scan(jax.checkpoint(one_span),
                         jnp.zeros((b, heads, p, n), jnp.float32),
                         tuple(spans(a) for a in (x, dt, B, C)))
    return jnp.moveaxis(ys.reshape(s, b, heads, p), 0, 1)


def _mamba(a, p, *, hyper, einsum):
    b, s, _ = a.shape
    heads, hd = hyper["ssm_heads"], hyper["ssm_head_dim"]
    n, groups = hyper["ssm_state"], hyper["ssm_groups"]
    inner, gn = heads * hd, groups * n
    proj = einsum("bse,ef->bsf", a, p["in_proj"]["kernel"])
    z, xbc, dt = (proj[..., :inner], proj[..., inner:2 * inner + 2 * gn],
                  proj[..., 2 * inner + 2 * gn:])
    xbc = jax.nn.silu(_conv(xbc, p["conv1d_kernel"], p["conv1d_bias"], True))
    x = xbc[..., :inner].reshape(b, s, heads, hd)
    bmat, cmat = (xbc[..., inner + i * gn:inner + (i + 1) * gn].reshape(
        b, s, groups, n) for i in (0, 1))
    if hyper["grouped_bc"]:
        group = jnp.arange(heads) // (heads // groups)
    else:
        group = jnp.zeros(heads, jnp.int32)
    delta = jax.nn.softplus(dt + p["dt_bias"])
    y = _recurrence(x, delta, -jnp.exp(p["A_log"]), bmat[:, :, group],
                    cmat[:, :, group], einsum=einsum)
    y = (y + p["D"][:, None] * x).reshape(b, s, inner)
    gated = y * jax.nn.silu(z)
    eps = hyper["rms_norm_eps"]
    if hyper["grouped_norm"]:
        parts = gated.reshape(b, s, groups, inner // groups)
        parts = parts * jax.lax.rsqrt(
            jnp.mean(jnp.square(parts), -1, keepdims=True) + eps)
        y = parts.reshape(b, s, inner) * p["norm"]["scale"]
    else:
        y = _rms(gated, p["norm"], eps)
    return einsum("bsf,fe->bse", y, p["out_proj"]["kernel"])


def _mlp(x, up, down, *, hyper, einsum, gated=False):
    """``relu(x U)^2 D``; ``relu(x U) D`` where ``hyper`` turns the square
    off; ``(silu(x U) * x U) D`` where ``gated``."""
    u = einsum("...d,df->...f", x, up)
    if gated:
        hidden = jax.nn.silu(u) * u
    elif hyper["squared_relu"]:
        hidden = jnp.square(jax.nn.relu(u))
    else:
        hidden = jax.nn.relu(u)
    return einsum("...f,fd->...d", hidden, down)


def _experts(x, p, bias, *, hyper, einsum):
    """``(y, counts)`` of the expert layer for tokens ``x [..., d]``: the
    chosen experts held here (``p["experts_up"]``'s leading axis, from
    ``first_expert`` on) and the shared expert."""
    k, first = hyper["top_k"], hyper["first_expert"]
    scores = jax.nn.sigmoid(einsum("...d,de->...e", x, p["router"]))
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), k)
    picked = jnp.take_along_axis(scores, chosen, -1)
    weights = hyper["route_scale"] * picked \
        / (picked.sum(-1, keepdims=True) + 1e-20)
    counts = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1]),
                     axis=tuple(range(chosen.ndim)))
    mlp = functools.partial(_mlp, hyper=hyper, einsum=einsum)
    shared = mlp(x, p["shared"]["up"]["kernel"], p["shared"]["down"]["kernel"],
                 gated=hyper["shared_gated"])

    def one_expert(total, held):
        w_up, w_down, e = held
        weight = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
        return total + weight[..., None] * mlp(x, w_up, w_down), None

    held = p["experts_up"].shape[0]
    routed, _ = jax.lax.scan(
        jax.checkpoint(one_expert), jnp.zeros_like(x),
        (p["experts_up"], p["experts_down"], jnp.arange(held)))
    return shared + routed, counts


def _block(h, p, bias, *, kind, hyper, einsum):
    a = _rms(h, p["norm"], hyper["rms_norm_eps"])
    if kind == "M":
        return h + _mamba(a, p["mamba"], hyper=hyper, einsum=einsum), None
    if kind == "*":
        return h + _attention(a, p["attn"], hyper=hyper, einsum=einsum), None
    y, counts = _experts(a, p["moe"], bias, hyper=hyper, einsum=einsum)
    return h + y, counts


def _nll_rows(params, biases, tokens, *, hyper, einsum):
    """``(summed negative log-likelihood of each row's next tokens, {expert
    layer: counts})``."""
    h = params["embed"]["embedding"][tokens]
    counts = {}
    for i, kind in enumerate(hyper["pattern"]):
        name = f"{BLOCK}{i}"
        block = jax.checkpoint(functools.partial(
            _block, kind=kind, hyper=hyper, einsum=einsum))
        h, seen = block(h, params[name], biases.get(name))
        if seen is not None:
            counts[name] = seen
    h = _rms(h, params["final_norm"], hyper["rms_norm_eps"])
    head = params["lm_head"]["kernel"]
    b, s, _ = h.shape
    rows = min(LOSS_ROWS, s)
    assert s % rows == 0, (s, rows)
    # the next token of every position; the last position's is no token
    target = jnp.concatenate([tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], 1)

    def some_rows(start):
        part = jax.lax.dynamic_slice_in_dim(h, start, rows, 1)
        logp = jax.nn.log_softmax(einsum("bse,ev->bsv", part, head), -1)
        want = jax.lax.dynamic_slice_in_dim(target, start, rows, 1)
        nll = -jnp.take_along_axis(logp, want[..., None], -1)[..., 0]
        return jnp.where(start + jnp.arange(rows) < s - 1, nll, 0.0)

    nll = jax.lax.map(jax.checkpoint(some_rows), jnp.arange(0, s, rows))
    return nll.sum((0, 2)), counts


def train_steps(params, batch, hyper: dict, *, steps: int, precision: str,
                loss_rows: int, rows_per_block: int, use_rows=None):
    """``reference/trinity.py``'s ``train_steps`` for this model: the same
    arguments and readings (``losses``, ``grad_norms`` of the first
    gradient, ``delta_norms`` of the change over all steps), the selection
    bias moved after each step by the step's counts. Adam's moments wait on
    the host between steps, as ``reference/granite.py``'s do: beside the
    parameters, the gradient and its working set the chip has no room for
    two more copies of 667 M parameters. ``params`` are consumed."""
    tokens = batch["tokens"]
    if use_rows is not None:
        tokens = tokens[:use_rows]
        loss_rows = min(loss_rows, use_rows)
    rows, s = tokens.shape
    rows_per_block = min(rows_per_block or rows, rows)
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
    zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))

    p = params
    start = jax.device_get(p)                   # waits on the host
    biases = {k: jnp.zeros((hyper["experts"],), jnp.float32)
              for k in p if "moe" in p[k]}
    opt_state = jax.device_get(jax.jit(opt_init)(p))
    losses, grad_sq = [], None
    for step in range(steps):
        acc, nll, counts = zeros(p), [], None
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
        opt_state = jax.device_get(opt_state)
        biases = {k: next_bias(biases[k], counts[k]) for k in biases}
    flat, _ = jax.tree_util.tree_flatten_with_path(p)
    start_flat = jax.tree_util.tree_leaves(start)
    delta_sq = {"/".join(k.key for k in path): sq_diff(leaf, was)
                for (path, leaf), was in zip(flat, start_flat)}
    return common.readings(losses, grad_sq, delta_sq)
