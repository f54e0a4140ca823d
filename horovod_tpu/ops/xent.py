"""Chunked fused softmax-cross-entropy: the LM lane's logits never
materialize.

A causal-LM training step at GPT-2-small scale (16k tokens/chip, vocab
32k) writes a [T, V] fp32 logits tensor of ~2 GB, reads it for
log-softmax, and touches it again on the backward — on a chip whose
step is HBM-bound, the loss head alone is ~a third of the traffic
(PERF.md). :func:`token_nll` computes every token's

    -log softmax(h @ w)[target_i]

and :func:`fused_cross_entropy` their weighted sum over ``denom``,
by ``lax.scan`` over TOKEN chunks: each step computes one
[t_chunk, V] logits block, reduces it to per-token (logsumexp,
target-logit) immediately, and lets XLA recycle the block — peak live
logits memory is T/t_chunk times smaller, and the full tensor never
round-trips HBM. The backward recomputes each chunk's logits
(T·E·V MACs again — small next to the GBs of traffic saved on a
memory-bound step) and accumulates ``dw`` in an fp32 scan carry while
streaming ``dh`` out per chunk.

``weights``/``denom`` serve two kinds of caller. A sequence-parallel
loss passes per-token validity weights and the GLOBAL (psum'd) token
count so that summing the per-shard results reproduces the dense mean
exactly (models/parallel_lm.py:next_token_nll_fused). A loss that
weighs each token by something learned (the looped LM's exit
distribution over its exits, models/decoder.py:exit_loss) takes the
per-token result and weighs it itself: the weighting is arithmetic
outside the chunked scan, so it is differentiable like any operand.
``tp_vocab_cross_entropy`` is the Megatron-style variant for a head
sharded [E, V/tp] over a mesh axis.

The reference framework has no fused loss (its LM story is absent
altogether — SURVEY §5 long-context); this is TPU-first perf work in
the spirit of its fusion buffer: restructure the computation so the
interconnect — here HBM — moves as few bytes as the math allows.

Exactness (loss AND every gradient: hidden state, head, weighting) vs
the dense composition is pinned in tests/test_xent.py. Two steps run
it: ``models.make_lm_train_step`` takes a looped LM's exit loss
through :func:`token_nll` always (the benchmark's cell
``ouro_seq4096_1chip``: four exits over 49,152 columns cannot be held
as logits), and a one-exit LM's through :func:`fused_cross_entropy`
with ``fused_ce=True`` (``bench.py --fused-ce``; in no cell, ROADMAP
D15).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def _pad_rows(t_chunk, *arrays):
    """Pad the token axis of each array to a multiple of ``t_chunk`` (zero
    rows, target 0: any valid index) and cut it into chunks."""
    pad = (-arrays[0].shape[0]) % t_chunk
    out = []
    for a in arrays:
        if pad:
            a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        out.append(a.reshape((-1, t_chunk) + a.shape[1:]))
    return out


def _fill_defaults(h, weights, denom):
    if weights is None:
        weights = jnp.ones((h.shape[0],), jnp.float32)
    else:
        weights = weights.astype(jnp.float32)
    if denom is None:
        denom = jnp.sum(weights)
    return weights, jnp.asarray(denom, jnp.float32)


T_CHUNK = 512   # rows of float32 logits a chunked loss holds at a time


def _chunk_stats(hc, w, tc):
    """One chunk's per-token (lse, target_logit), fp32."""
    logits = jnp.dot(hc, w, preferred_element_type=jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
    return lse, tgt


def fused_cross_entropy(h, w, targets, t_chunk: int = T_CHUNK,
                        weights=None, denom=None):
    """Weighted NLL without materializing [T, V] logits.

    h [T, E] (any float dtype; the matmul accumulates fp32), w [E, V],
    targets [T] int32 -> scalar fp32. Defaults (weights=1, denom=T)
    give the plain mean NLL; sharded callers pass validity weights and
    a globally-reduced denom (module docstring).

    The chunked part is :func:`token_nll`; ``weights`` and ``denom``
    meet its per-token result in plain arithmetic, so a learned
    per-token weighting gets its gradient like any other operand (the
    looped LM's exit distribution, ``models.decoder.exit_loss``).
    """
    weights, denom = _fill_defaults(h, weights, denom)
    return jnp.sum(token_nll(h, w, targets, t_chunk) * weights) / denom


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def token_nll(h, w, targets, t_chunk: int = T_CHUNK):
    """``-log softmax(h @ w)[target]`` of every token, ``[T]`` fp32, holding
    one ``[t_chunk, V]`` block of logits at a time; differentiable into
    ``h`` and ``w`` (the backward pass recomputes each block)."""
    nll, _ = _token_nll_fwd(h, w, targets, t_chunk)
    return nll


def _token_nll_fwd(h, w, targets, t_chunk):
    hcs, tcs = _pad_rows(t_chunk, h, targets)

    def step(_, xs):
        lse, tgt = _chunk_stats(xs[0], w, xs[1])
        return None, lse - tgt

    _, nll = lax.scan(step, None, (hcs, tcs))
    return nll.reshape(-1)[:h.shape[0]], (h, w, targets)


def _token_nll_bwd(t_chunk, res, g):
    from horovod_tpu.parallel._vma import match_vma

    h, w, targets = res
    hcs, tcs, gcs = _pad_rows(t_chunk, h, targets, g.astype(jnp.float32))

    def step(dw_acc, xs):
        hc, tc, gc = xs
        logits = jnp.dot(hc, w, preferred_element_type=jnp.float32)
        p = jax.nn.softmax(logits, axis=-1)
        onehot = jax.nn.one_hot(tc, w.shape[1], dtype=jnp.float32)
        dl = (p - onehot) * gc[:, None]             # [t_chunk, V] fp32
        dh_c = jnp.dot(dl, w.T.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        dw_acc = dw_acc + jnp.dot(hc.astype(jnp.float32).T, dl,
                                  preferred_element_type=jnp.float32)
        return dw_acc, dh_c

    # Scan carries must be vma-typed like the body's output (e.g. a
    # sequence-parallel caller passes sp-varying h/targets/weights).
    dw0 = match_vma(jnp.zeros(w.shape, jnp.float32), h, w, targets, g)
    dw, dhs = lax.scan(step, dw0, (hcs, tcs, gcs))
    dh = dhs.reshape(-1, h.shape[1])[:h.shape[0]]
    return dh.astype(h.dtype), dw.astype(w.dtype), None


token_nll.defvjp(_token_nll_fwd, _token_nll_bwd)


# --------------------------------------------------------------------------
# Vocab-parallel (tensor-parallel head) variant.


def _vp_chunk_stats(hc, w_local, tc, axis, v_local):
    """One chunk's per-token (global lse, global target logit) when the
    vocab axis is sharded over mesh axis ``axis``."""
    logits = jnp.dot(hc, w_local, preferred_element_type=jnp.float32)
    gmax = lax.pmax(jnp.max(logits, axis=-1), axis)
    lse = gmax + jnp.log(lax.psum(
        jnp.sum(jnp.exp(logits - gmax[:, None]), axis=-1), axis))
    offset = lax.axis_index(axis) * v_local
    local_t = tc - offset
    in_range = (local_t >= 0) & (local_t < v_local)
    picked = jnp.take_along_axis(
        logits, jnp.clip(local_t, 0, v_local - 1)[:, None], axis=-1)[:, 0]
    tgt = lax.psum(jnp.where(in_range, picked, 0.0), axis)
    return lse, tgt


def tp_vocab_cross_entropy(h, w_local, targets, axis: str,
                           t_chunk: int = 512, weights=None, denom=None):
    """Megatron-style vocab-parallel CE, chunked — for use INSIDE
    ``shard_map`` where the projection weight is sharded [E, V/tp] over
    mesh axis ``axis`` and ``h``/``targets`` are replicated along it.

    Each rank computes its local [t_chunk, V/tp] logits block; the
    softmax normalizer is assembled with a pmax + psum per chunk (two
    scalars-per-token on the ICI instead of a V-wide all-gather), the
    target logit with a masked psum. Returns the GLOBAL weighted NLL —
    identical on every ``axis`` rank, exactly equal to the dense
    computation (pinned in tests/test_xent.py). The custom VJP
    recomputes blockwise: dw stays rank-local (exactly the dense dw's
    vocab slice), dh is psum-assembled across the shards.

    As with :func:`fused_cross_entropy`, ``weights``/``denom`` are
    non-differentiable bookkeeping and are ``stop_gradient``-ed at
    entry — a learnable weighting must be applied outside this op.
    """
    weights, denom = _fill_defaults(h, weights, denom)
    weights = lax.stop_gradient(weights)
    denom = lax.stop_gradient(denom)
    return _vp(h, w_local, targets, weights, denom, axis, t_chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _vp(h, w_local, targets, weights, denom, axis, t_chunk):
    loss, _ = _vp_fwd(h, w_local, targets, weights, denom, axis, t_chunk)
    return loss


def _vp_body_vma(axis, *with_axis_removed, extra=()):
    """vma set of a scan-body output whose ``axis``-variance was
    collapsed by the in-body psum/pmax, unioned with operands that
    touch the result after the collectives."""
    from horovod_tpu.parallel._vma import vma_of

    return ((vma_of(*with_axis_removed) - {axis}) | vma_of(*extra))


def _typed_zero(shape_like, vma):
    z = (jnp.float32(0.0) if shape_like is None
         else jnp.zeros(shape_like.shape, jnp.float32))
    if vma:
        z = lax.pcast(z, tuple(sorted(vma)), to="varying")
    return z


def _vp_fwd(h, w_local, targets, weights, denom, axis, t_chunk):
    hcs, tcs, wcs = _pad_rows(t_chunk, h, targets, weights)
    v_local = w_local.shape[1]

    def step(acc, xs):
        hc, tc, wc = xs
        lse, tgt = _vp_chunk_stats(hc, w_local, tc, axis, v_local)
        return acc + jnp.sum((lse - tgt) * wc), None

    # (lse, tgt) come out of psum/pmax over ``axis`` — axis-invariant —
    # but keep any OTHER variance (e.g. sp) the operands carry.
    acc0 = _typed_zero(None, _vp_body_vma(axis, h, w_local,
                                          extra=(targets, weights)))
    total, _ = lax.scan(step, acc0, (hcs, tcs, wcs))
    return total / denom, (h, w_local, targets, weights, denom)


def _vp_bwd(axis, t_chunk, res, g):
    h, w_local, targets, weights, denom = res
    hcs, tcs, wcs = _pad_rows(t_chunk, h, targets, weights)
    e = h.shape[1]
    v_local = w_local.shape[1]
    scale = g / denom

    def step(dw_acc, xs):
        hc, tc, wc = xs
        logits = jnp.dot(hc, w_local, preferred_element_type=jnp.float32)
        lse, _ = _vp_chunk_stats(hc, w_local, tc, axis, v_local)
        p = jnp.exp(logits - lse[:, None])  # local slice of the softmax
        offset = lax.axis_index(axis) * v_local
        local_t = tc - offset
        in_range = (local_t >= 0) & (local_t < v_local)
        onehot = jax.nn.one_hot(jnp.clip(local_t, 0, v_local - 1),
                                v_local, dtype=jnp.float32)
        onehot = onehot * in_range[:, None].astype(jnp.float32)
        dl = (p - onehot) * (wc * scale)[:, None]
        # h is axis-replicated, logits axis-split: dh sums the shards.
        dh_c = lax.psum(
            jnp.dot(dl, w_local.T.astype(jnp.float32),
                    preferred_element_type=jnp.float32), axis)
        dw_acc = dw_acc + jnp.dot(hc.astype(jnp.float32).T, dl,
                                  preferred_element_type=jnp.float32)
        return dw_acc, dh_c

    # The accumulator is axis-varying (each rank owns its vocab slice
    # of dw) on top of whatever variance (e.g. sp) the operands carry.
    from horovod_tpu.parallel._vma import vma_of

    dw0 = _typed_zero(w_local, vma_of(h, w_local, targets, weights,
                                      denom, g) | {axis})
    dw, dhs = lax.scan(step, dw0, (hcs, tcs, wcs))
    dh = dhs.reshape(-1, e)[:h.shape[0]]
    return (dh.astype(h.dtype), dw.astype(w_local.dtype), None,
            jnp.zeros_like(weights), jnp.zeros_like(denom))


_vp.defvjp(_vp_fwd, _vp_bwd)
