"""The pieces of today's decoder blocks, written once, and two decoder LMs
made of them: a sparse one and a looped one.

* :class:`RMSNorm`; :func:`rotary` (the half-split pairing of
  ``rotate_half``); :class:`GatedMLP` (SwiGLU);
* :class:`GroupedAttention`: ``H`` query heads over ``G`` KV heads, causal,
  under a ``window`` or over the whole sequence, and three choices that are
  the layer's own: rotary positions, an RMS norm a head on queries and keys,
  a sigmoid gate on the output;
* :class:`SparseExperts`: a router over all ``E`` experts, this chip's
  ``held`` of them (:mod:`horovod_tpu.parallel.moe`: top ``k`` of sigmoid
  scores plus a selection bias, nothing dropped) and a shared expert every
  token passes;
* :class:`DecoderBlock`: an RMS norm before **and after** each branch;
* :class:`SparseDecoderLM`: leading dense layers, then expert layers
  (``afmoe``: a window layer rotates, a full layer has no positional
  encoding at all; every layer has the q/k norms and the gate);
* :class:`LoopedDecoderLM`: a stack of blocks declared once and applied
  ``loops`` times with the same weights, an exit after each application
  (``ouro``: full attention with rotary positions, no q/k norm, no gate), and
  its loss: :func:`exit_log_distribution`, :func:`exit_loss`.

The selection bias is state and no parameter: it lives in the collection
``buffers`` beside each layer's ``expert_counts`` (how many tokens of the
last step chose each expert), which the forward pass writes where the
collection is mutable; the training step then moves the bias by
``moe.update_selection_bias`` (:func:`update_buffers`). ``bfloat16`` compute,
float32 parameters, norms, softmax, router and logits, as the other LM.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.ops.attention import attend
from horovod_tpu.parallel import moe
from horovod_tpu.utils import timeline

SLIDING, FULL = "sliding_attention", "full_attention"


class RMSNorm(nn.Module):
    """``x * rsqrt(mean(x^2) + eps) * scale`` over the last axis, in
    float32."""

    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        x = x.astype(jnp.float32)
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), -1, keepdims=True) + self.eps) * scale


def rotary(x, base: float = 10000.0):
    """Rotate ``x [B, L, heads, D]`` by its position: all ``D`` dimensions,
    dimension ``i`` paired with ``i + D/2`` (``rotate_half``), float32."""
    length, dim = x.shape[1], x.shape[-1]
    inv = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None]
    angle = jnp.concatenate([angle, angle], -1)[None, :, None, :]
    x = x.astype(jnp.float32)
    first, second = jnp.split(x, 2, -1)
    return x * jnp.cos(angle) \
        + jnp.concatenate([-second, first], -1) * jnp.sin(angle)


class GatedMLP(nn.Module):
    """``(silu(x W_gate) * (x W_up)) W_down``, no bias."""

    width: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            name=name)

        hidden = nn.silu(dense(self.width, "gate")(x)) \
            * dense(self.width, "up")(x)
        return dense(x.shape[-1], "down")(hidden)


class GroupedAttention(nn.Module):
    """Grouped-query causal attention of one layer (module docstring).
    ``attention``: ``"flash"`` (the Pallas kernels, which know the window
    and the grouping), ``"dense"`` (the masked reference) or None: what
    ``ops.attention.attention_plan`` picks for the shapes."""

    heads: int
    kv_heads: int
    head_dim: int
    window: Optional[int] = None        # None: the whole sequence
    rotary: bool = True                 # q and k rotated by position
    qk_norm: bool = False               # an RMS norm a head on q and on k
    gate: bool = False                  # o = (P v) * sigmoid(x W_gate)
    eps: float = 1e-5
    rope_base: float = 10000.0
    attention: Optional[str] = None
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, length, _ = x.shape
        h, g, d = self.heads, self.kv_heads, self.head_dim

        def project(heads, name):
            y = nn.Dense(heads * d, use_bias=False, dtype=self.dtype,
                         name=name)(x)
            return y.reshape(b, length, heads, d)

        q, k = project(h, "q"), project(g, "k")
        if self.qk_norm:
            q = RMSNorm(self.eps, name="q_norm")(q)
            k = RMSNorm(self.eps, name="k_norm")(k)
        v = project(g, "v")
        if self.gate:
            gate = nn.Dense(h * d, use_bias=False, dtype=self.dtype,
                            name="gate")(x)
        if self.rotary:
            q, k = rotary(q, self.rope_base), rotary(k, self.rope_base)
        q, k = q.astype(self.dtype), k.astype(self.dtype)
        program, _ = timeline.tracing_program()
        timeline.gauge("hvd.attn.kv_heads", g, key=program)
        if self.window is not None:
            timeline.gauge("hvd.attn.window", self.window, key=program)
        scope = (timeline.ATTN_FULL if self.window is None
                 else timeline.ATTN_WINDOW)
        with jax.named_scope(scope):
            out = attend(q, k, v, window=self.window, impl=self.attention)
        out = out.reshape(b, length, h * d)
        if self.gate:
            out = out * nn.sigmoid(gate)
        return nn.Dense(x.shape[-1], use_bias=False, dtype=self.dtype,
                        name="out")(out)


class SparseExperts(nn.Module):
    """``MLP_shared(x) + sum over the chosen experts held here of w_e
    MLP_e(x)``: the chip's share of the layer (``first_expert`` and
    ``experts_held`` say which experts are its own), nothing dropped."""

    experts: int
    experts_held: int
    first_expert: int
    top_k: int
    width: int
    route_scale: float = 1.0
    shared: int = 1                      # shared experts, of ``width`` each
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, length, d = x.shape
        held, f = self.experts_held, self.width
        router = self.param("router", nn.initializers.lecun_normal(),
                            (d, self.experts))
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=0)
        stacked = {"gate": self.param("experts_gate", init, (held, d, f)),
                   "up": self.param("experts_up", init, (held, d, f)),
                   "down": self.param("experts_down", init, (held, f, d))}
        bias = self.variable("buffers", "selection_bias", jnp.zeros,
                             (self.experts,), jnp.float32)
        seen = self.variable("buffers", "expert_counts", jnp.zeros,
                             (self.experts,), jnp.float32)
        flat = x.reshape(b * length, d)
        y, counts = moe.routed_experts(
            flat, router, stacked, bias.value, first=self.first_expert,
            top_k=self.top_k, route_scale=self.route_scale,
            dtype=self.dtype, name="/".join(self.path))
        if not self.is_initializing() \
                and self.is_mutable_collection("buffers"):
            seen.value = counts
        y = y.reshape(b, length, d)
        if self.shared:
            with jax.named_scope(timeline.MOE_SHARED):
                y = y + GatedMLP(self.shared * f, self.dtype,
                                 name="shared")(x)
        return y


class DecoderBlock(nn.Module):
    """``h += RMS_2(attention(RMS_1(h)))``; ``h += RMS_4(F(RMS_3(h)))``,
    ``F`` a :class:`GatedMLP` (``moe`` None) or :class:`SparseExperts`."""

    attn: dict                          # GroupedAttention's fields
    ffn_width: int
    moe: Optional[dict] = None          # SparseExperts' fields
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, h):
        a = RMSNorm(self.eps, name="norm_attn")(h)
        a = GroupedAttention(eps=self.eps, dtype=self.dtype, name="attn",
                             **self.attn)(a)
        h = h + RMSNorm(self.eps, name="norm_attn_out")(a).astype(h.dtype)
        m = RMSNorm(self.eps, name="norm_ffn")(h)
        if self.moe is None:
            m = GatedMLP(self.ffn_width, self.dtype, name="mlp")(m)
        else:
            m = SparseExperts(dtype=self.dtype, name="moe", **self.moe)(m)
        return h + RMSNorm(self.eps, name="norm_ffn_out")(m).astype(h.dtype)


class SparseDecoderLM(nn.Module):
    """Token ids ``[B, L]`` -> float32 logits ``[B, L, vocab]`` (or, with
    ``return_hidden``, the final norm's output for a fused loss).

    ``layer_types``: one of ``"sliding_attention"`` / ``"full_attention"`` a
    layer; the first ``dense_layers`` have a :class:`GatedMLP` of
    ``dense_width``, the others :class:`SparseExperts`. ``embed_scale``
    multiplies the embedding by ``sqrt(embed_dim)``. ``remat`` recomputes
    each block in the backward pass (for real: ``prevent_cse`` stays on, or
    XLA:TPU merges the recomputation back into the forward pass)."""

    vocab_size: int
    embed_dim: int
    layer_types: Sequence[str]
    heads: int
    kv_heads: int
    head_dim: int
    window: int
    dense_layers: int
    dense_width: int
    experts: int
    experts_held: int
    top_k: int
    expert_width: int
    first_expert: int = 0
    shared_experts: int = 1
    route_scale: float = 1.0
    embed_scale: bool = True
    eps: float = 1e-5
    rope_base: float = 10000.0
    attention: Optional[str] = None
    dtype: Any = jnp.bfloat16
    remat: bool = False

    @nn.compact
    def __call__(self, tokens, train: bool = True,
                 return_hidden: bool = False):
        del train                                   # no dropout anywhere
        h = nn.Embed(self.vocab_size, self.embed_dim, dtype=self.dtype,
                     name="embed")(tokens)
        if self.embed_scale:
            h = h * jnp.asarray(self.embed_dim ** 0.5, h.dtype)
        block = nn.remat(DecoderBlock) if self.remat else DecoderBlock
        for i, kind in enumerate(self.layer_types):
            if kind not in (SLIDING, FULL):
                raise ValueError(f"layer {i}: no layer type {kind!r}")
            attn = dict(heads=self.heads, kv_heads=self.kv_heads,
                        head_dim=self.head_dim, rope_base=self.rope_base,
                        window=self.window if kind == SLIDING else None,
                        rotary=kind == SLIDING, qk_norm=True, gate=True,
                        attention=self.attention)
            sparse = None if i < self.dense_layers else dict(
                experts=self.experts, experts_held=self.experts_held,
                first_expert=self.first_expert, top_k=self.top_k,
                width=self.expert_width, route_scale=self.route_scale,
                shared=self.shared_experts)
            h = block(attn, self.dense_width, sparse, self.eps, self.dtype,
                      name=f"DecoderBlock_{i}")(h)
        h = RMSNorm(self.eps, name="final_norm")(h)
        if return_hidden:
            return h
        return nn.Dense(self.vocab_size, use_bias=False, dtype=jnp.float32,
                        name="lm_head")(h)


# Block applications a looped model's forward pass has traced into each
# program: program -> (id of its ``hvd.spmd.dispatch`` span, [count]). A
# re-trace starts anew.
_applied: dict = {}


class LoopedDecoderLM(nn.Module):
    """``num_layers`` dense :class:`DecoderBlock` declared once and applied
    ``loops`` times with the same weights (every weight is one leaf of the
    parameters; its gradient is the sum over its uses). After each
    application the final norm gives the step's exit state ``x_t``, which is
    also the next application's input; one gate ``sigmoid(x_t w + b)`` a
    token says how much of what is left exits there.

    Token ids ``[B, L]`` -> the last exit's float32 logits ``[B, L, vocab]``
    (what a server that never exits early reads), or with ``return_hidden``
    ``(exits [loops, B, L, embed_dim], gate_logits [loops, B, L])`` in
    float32 for :func:`exit_loss`: the logits of all the exits never exist
    as one tensor. ``exit_beta`` is the loss's weight on the exit
    distribution's entropy; a step that finds it takes :func:`exit_loss`
    (``models.make_lm_train_step``). ``remat`` recomputes each block
    application in the backward pass, as :class:`SparseDecoderLM` does."""

    vocab_size: int
    embed_dim: int
    num_layers: int
    loops: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn_width: int
    exit_beta: float = 0.1
    eps: float = 1e-6
    rope_base: float = 1e6
    attention: Optional[str] = None
    dtype: Any = jnp.bfloat16
    remat: bool = False

    @nn.compact
    def __call__(self, tokens, train: bool = True,
                 return_hidden: bool = False):
        del train                                   # no dropout anywhere
        h = nn.Embed(self.vocab_size, self.embed_dim, dtype=self.dtype,
                     name="embed")(tokens)
        block = nn.remat(DecoderBlock) if self.remat else DecoderBlock
        attn = dict(heads=self.heads, kv_heads=self.kv_heads,
                    head_dim=self.head_dim, rope_base=self.rope_base,
                    attention=self.attention)
        blocks = [block(attn, self.ffn_width, None, self.eps, self.dtype,
                        name=f"DecoderBlock_{i}")
                  for i in range(self.num_layers)]
        final_norm = RMSNorm(self.eps, name="final_norm")
        # one output a token: at full precision it costs nothing, and the
        # exit distribution is read off it
        exit_gate = nn.Dense(1, dtype=jnp.float32, name="exit_gate",
                             precision=jax.lax.Precision.HIGHEST)
        program, applied = timeline.program_tally(_applied, lambda: [0])
        exits, gates = [], []
        for _ in range(self.loops):
            with jax.named_scope(timeline.LOOP_STEP):
                for apply_block in blocks:
                    h = apply_block(h)
                    applied[0] += 1
                x = final_norm(h)
            with jax.named_scope(timeline.EXIT_GATE):
                gates.append(exit_gate(x)[..., 0])
            exits.append(x)
            h = x.astype(self.dtype)
        timeline.gauge("hvd.loop.applications", applied[0], key=program)
        if return_hidden:
            return jnp.stack(exits), jnp.stack(gates)
        return nn.Dense(self.vocab_size, use_bias=False, dtype=jnp.float32,
                        name="lm_head")(exits[-1])


def exit_log_distribution(gate_logits):
    """``log p [T, ...]`` from the gates' logits ``z [T, ...]``: with
    ``lambda_t = sigmoid(z_t)``, ``p_t = lambda_t prod_{j<t} (1 - lambda_j)``
    for ``t < T`` and the last step takes what is left, ``p_T = prod_{j<T}
    (1 - lambda_j)`` (its own gate is not read), so the ``T`` sum to 1. In
    logarithms, so that a gate that saturates gives no ``log 0``."""
    z = gate_logits.astype(jnp.float32)
    # log prod_{j<t} (1 - lambda_j) for t = 1..T: nothing before the first
    stayed = jnp.concatenate(
        [jnp.zeros_like(z[:1]), jnp.cumsum(jax.nn.log_sigmoid(-z[:-1]), 0)], 0)
    return jnp.concatenate(
        [jax.nn.log_sigmoid(z[:-1]) + stayed[:-1], stayed[-1:]], 0)


def exit_loss(exits, gate_logits, head, tokens, beta: float,
              t_chunk: int = 512):
    """The looped model's training loss, a scalar: the mean over the ``B x
    (L - 1)`` next-token positions of ``sum_t p_t nll_t - beta H(p)``, with
    ``nll_t`` the negative log-likelihood of the next token under exit
    ``t``'s logits ``x_t W_head``, ``p`` the token's exit distribution
    (:func:`exit_log_distribution`) and ``H(p) = -sum_t p_t log p_t``.

    ``exits [T, B, L, E]``, ``gate_logits [T, B, L]``, ``head [E, vocab]``,
    ``tokens [B, L]``. Every exit's per-token loss comes from one pass of
    ``ops.xent.token_nll`` over the ``T x B x (L - 1)`` rows, which holds
    ``t_chunk`` rows of float32 logits at a time (the gauge
    ``hvd.exit.live_logits_bytes``) and is differentiable into the exit
    states and the head; the weighting by ``p`` is plain arithmetic, so
    gradients reach the gate through it."""
    from horovod_tpu.ops.xent import token_nll

    loops, _, _, e = exits.shape
    rows = exits[:, :, :-1].reshape(-1, e)
    program, _ = timeline.tracing_program()
    timeline.gauge("hvd.exit.live_logits_bytes",
                   4 * min(t_chunk, rows.shape[0]) * head.shape[-1],
                   key=program)
    with jax.named_scope(timeline.EXIT_LOSS):
        nll = token_nll(rows.astype(jnp.float32), head.astype(jnp.float32),
                        jnp.tile(tokens[:, 1:].reshape(-1), loops),
                        t_chunk).reshape(loops, -1)
    with jax.named_scope(timeline.EXIT_GATE):
        log_p = exit_log_distribution(
            gate_logits[:, :, :-1].reshape(loops, -1))
        p = jnp.exp(log_p)
        expected = jnp.sum(p * nll, 0)
        entropy = -jnp.sum(p * log_p, 0)
    return jnp.mean(expected - beta * entropy)


def update_buffers(buffers, coeff: float, axis: Optional[str] = None):
    """After an optimizer step: every expert layer's selection bias moved by
    its ``expert_counts`` of the step (summed over the data axis where the
    step runs on several chips), ``moe.update_selection_bias``."""
    def visit(node):
        if "selection_bias" in node:
            counts = node["expert_counts"]
            if axis is not None:
                counts = jax.lax.psum(counts, axis)
            return dict(node, selection_bias=moe.update_selection_bias(
                node["selection_bias"], counts, coeff))
        return {k: visit(v) for k, v in node.items()}

    return visit(dict(buffers)) if buffers else buffers
