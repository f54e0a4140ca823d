"""The pieces of today's decoder blocks, written once, and a sparse decoder
LM made of them.

* :class:`RMSNorm`; :func:`rotary` (the half-split pairing of
  ``rotate_half``); :class:`GatedMLP` (SwiGLU);
* :class:`GroupedAttention`: ``H`` query heads over ``G`` KV heads, an RMS
  norm a head on queries and keys, a sigmoid gate on the output, and a type
  a layer: a ``window`` with rotary positions, or full causal attention with
  no positional encoding at all;
* :class:`SparseExperts`: a router over all ``E`` experts, this chip's
  ``held`` of them (:mod:`horovod_tpu.parallel.moe`: top ``k`` of sigmoid
  scores plus a selection bias, nothing dropped) and a shared expert every
  token passes;
* :class:`DecoderBlock`: an RMS norm before **and after** each branch;
* :class:`SparseDecoderLM`: leading dense layers, then expert layers.

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
    """Gated grouped-query attention of one layer type (module docstring).
    ``attention``: ``"flash"`` (the Pallas kernels, which know the window
    and the grouping), ``"dense"`` (the masked reference) or None: what
    ``ops.attention.attention_plan`` picks for the shapes."""

    heads: int
    kv_heads: int
    head_dim: int
    window: Optional[int] = None        # None: full attention, no rotary
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

        q = RMSNorm(self.eps, name="q_norm")(project(h, "q"))
        k = RMSNorm(self.eps, name="k_norm")(project(g, "k"))
        v = project(g, "v")
        gate = nn.Dense(h * d, use_bias=False, dtype=self.dtype,
                        name="gate")(x)
        if self.window is not None:
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
        out = out.reshape(b, length, h * d) * nn.sigmoid(gate)
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
