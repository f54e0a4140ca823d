"""The pieces of today's decoder blocks, written once, and two decoder LMs
made of them: a sparse one and a looped one.

* :class:`RMSNorm` (over the last axis, or over each of its groups);
  :func:`rotary` (the half-split pairing of ``rotate_half``);
  :class:`GatedMLP` (SwiGLU); :class:`SquaredReluMLP` (no gate);
* :class:`GroupedAttention`: ``H`` query heads over ``G`` KV heads, causal,
  under a ``window`` or over the whole sequence, and three choices that are
  the layer's own: rotary positions, an RMS norm a head on queries and keys,
  a sigmoid gate on the output;
* :class:`LatentAttention`: keys and values expanded from one compressed
  row a token (``deepseek_v3`` without the query's low-rank path): a head's
  key is its own ``nope_dim`` columns beside one rotated ``rope_dim``-wide
  key that all heads share, its value ``value_dim`` wide;
* :class:`SparseExperts`: a router over all ``E`` experts, this chip's
  ``held`` of them (:mod:`horovod_tpu.parallel.moe`: top ``k`` of sigmoid
  scores plus a selection bias, nothing dropped) and a shared expert every
  token passes; the experts gated (SwiGLU) or not (squared ReLU);
* :class:`DecoderBlock`: an RMS norm before each branch and, where
  ``norm_outputs`` says so, after it;
* :class:`MixerBlock`: one branch a layer, ``h + F(RMS(h))`` with ``F`` a
  Mamba mixer, grouped attention or the expert layer;
* :class:`Mamba2Mixer`: Mamba-2's state-space mixer (in-projection, causal
  depthwise conv, the chunked scan of :mod:`horovod_tpu.ops.ssd`, the gated
  norm, out-projection);
* :class:`SparseDecoderLM`: leading dense layers, then expert layers
  (``afmoe``: a window layer rotates, a full layer has no positional
  encoding at all; every layer has the q/k norms and the gate;
  ``deepseek_v3``: latent layers, two norms a block;
  ``granitemoehybrid``: Mamba layers among full ones with no q/k norm or
  gate, every layer dense, the branches and the embedding scaled by
  numbers, the head tied to the embedding; ``nemotron_h``: a ``pattern`` of
  single-branch layers, Mamba with B and C in groups, squared-ReLU
  experts);
* :class:`LoopedDecoderLM`: a stack of blocks declared once and applied
  ``loops`` times with the same weights, an exit after each application
  (``ouro``: full attention with rotary positions, no q/k norm, no gate), and
  its loss: :func:`exit_log_distribution`, :func:`exit_loss`;
* recomputation as a plan: :func:`recompute_plan` (arithmetic over bytes),
  :func:`plan_recomputation` (what a step of one of the two models holds,
  from its shapes and the device's memory), :class:`RecomputePlan` (the
  models' ``remat``).

The selection bias is state and no parameter: it lives in the collection
``buffers`` beside each layer's ``expert_counts`` (how many tokens of the
last step chose each expert), which the forward pass writes where the
collection is mutable; the training step then moves the bias by
``moe.update_selection_bias`` (:func:`update_buffers`). ``bfloat16`` compute,
float32 parameters, norms, softmax, router and logits, as the other LM.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Sequence, Union

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.ops import ssd as ssd_op
from horovod_tpu.ops.attention import attend
from horovod_tpu.ops.xent import T_CHUNK, token_nll
from horovod_tpu.parallel import moe
from horovod_tpu.utils import timeline

SLIDING, FULL, LATENT, MAMBA = ("sliding_attention", "full_attention",
                                "latent_attention", "mamba")
# a single-branch layer's kind by its letter of ``nemotron_h``'s
# ``hybrid_override_pattern``
MIXER_MAMBA, MIXER_MOE, MIXER_ATTENTION = "M", "E", "*"


class RMSNorm(nn.Module):
    """``x * rsqrt(mean(x^2) + eps) * scale`` over the last axis, in
    float32; with ``groups`` the mean is each of that many equal groups of
    the last axis's own (``MambaRMSNormGated``'s ``group_size``), and the
    scale is one over the whole axis."""

    eps: float = 1e-5
    groups: int = 1

    @nn.compact
    def __call__(self, x):
        x = x.astype(jnp.float32)
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        if self.groups == 1:
            return x * jax.lax.rsqrt(
                jnp.mean(jnp.square(x), -1, keepdims=True) + self.eps) * scale
        parts = x.reshape(*x.shape[:-1], self.groups, -1)
        parts = parts * jax.lax.rsqrt(
            jnp.mean(jnp.square(parts), -1, keepdims=True) + self.eps)
        return parts.reshape(x.shape) * scale


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


class SquaredReluMLP(nn.Module):
    """``relu(x W_up)^2 W_down``, no gate, no bias (``nemotron_h``'s
    ``relu2`` MLP)."""

    width: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        up = nn.Dense(self.width, use_bias=False, dtype=self.dtype,
                      name="up")(x)
        hidden = jnp.square(nn.relu(up.astype(jnp.float32))).astype(up.dtype)
        return nn.Dense(x.shape[-1], use_bias=False, dtype=self.dtype,
                        name="down")(hidden)


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
    scale: Optional[float] = None       # scores' scale; None: head_dim^-0.5

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
            out = attend(q, k, v, window=self.window, impl=self.attention,
                         scale=self.scale)
        out = out.reshape(b, length, h * d)
        if self.gate:
            out = out * nn.sigmoid(gate)
        return nn.Dense(x.shape[-1], use_bias=False, dtype=self.dtype,
                        name="out")(out)


class LatentAttention(nn.Module):
    """Causal attention over keys and values expanded from a compressed row
    (``modeling_deepseek.py`` with ``q_lora_rank: null``, the expanded form
    that training uses): for a token's normed state ``x``

        q      = x W_q          a head: q_nope [nope_dim] | q_pe [rope_dim]
        c | kr = x W_kva        c [latent_dim], kr [rope_dim]: one a token
        c      = RMS(c)
        kv     = c W_kvb        a head: k_nope [nope_dim] | v [value_dim]
        q_pe, kr rotated by position
        k_h    = k_nope_h | kr  the same kr for all heads
        o_h    = softmax(q_h k_h / sqrt(nope_dim + rope_dim)) v_h, causal
        y      = (o_1 | ... | o_H) W_o

    No bias, no gate, no q/k norm. The kernels take ``kr`` as it is, one
    vector a token (``ops.attention.attend``'s ``k_shared``): no key of
    ``heads x (nope_dim + rope_dim)`` a token is written (broadcast into K
    before the call, the other way, the cell's step read 2.2 ms of 602
    longer on the v5e: PERF.md section 5). ``attention`` is
    :class:`GroupedAttention`'s."""

    heads: int
    nope_dim: int
    rope_dim: int
    value_dim: int
    latent_dim: int
    eps: float = 1e-5
    rope_base: float = 10000.0
    attention: Optional[str] = None
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, length, _ = x.shape
        h, dn, dr, dv = self.heads, self.nope_dim, self.rope_dim, \
            self.value_dim

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            name=name)

        q = dense(h * (dn + dr), "q")(x).reshape(b, length, h, dn + dr)
        q = jnp.concatenate(
            [q[..., :dn], rotary(q[..., dn:], self.rope_base).astype(q.dtype)],
            -1)
        with jax.named_scope(timeline.LATENT_COMPRESS):
            row = dense(self.latent_dim + dr, "kv_a")(x)
            c = RMSNorm(self.eps, name="kv_norm")(
                row[..., :self.latent_dim]).astype(self.dtype)
        with jax.named_scope(timeline.LATENT_EXPAND):
            kv = dense(h * (dn + dv), "kv_b")(c).reshape(b, length, h,
                                                         dn + dv)
            k_nope, v = kv[..., :dn], kv[..., dn:]
            k_rope = rotary(row[..., None, self.latent_dim:],
                            self.rope_base)[:, :, 0].astype(self.dtype)
        program, written = timeline.program_tally(_expanded, lambda: [0])
        written[0] += sum(t.size * t.dtype.itemsize
                          for t in (k_nope, v, k_rope))
        timeline.gauge("hvd.attn.latent_expanded_bytes", written[0],
                       key=program)
        with jax.named_scope(timeline.ATTN_LATENT):
            out = attend(q, k_nope, v, k_shared=k_rope,
                         scale=(dn + dr) ** -0.5, impl=self.attention)
        return dense(x.shape[-1], "out")(out.reshape(b, length, h * dv))


# Bytes of K and V the latent layers of the program being traced write for
# the kernels in a forward pass: program -> (id of its dispatch span, [bytes]).
_expanded: dict = {}


class Mamba2Mixer(nn.Module):
    """Mamba-2's mixer (``modeling_granitemoehybrid``'s and
    ``modeling_nemotron_h``'s, no bias on the projections): for a token's
    normed state ``x``, with B and C in ``groups`` groups of N

        z | xBC | dt = x W_in                 inner | inner + 2GN | heads
        xBC          = silu(conv(xBC) + b)    causal, depthwise, ``conv`` taps
        x | B | C    = xBC                    heads x head_dim | G x N | G x N
        Delta        = softplus(dt + dt_bias);  A = -exp(A_log)
        y            = ssd(x, Delta, A, B, C) + D x       (ops/ssd.py)
        y            = RMS_G(y * silu(z)) * w   the gate first; each of the G
                                                groups of inner / G alone
        out          = y W_out

    Head h reads group ``h // (heads / G)``. The scan reads x, B and C where
    the conv wrote them (``ssd``'s packed form). ``impl`` is ``ssd``'s.
    Scopes ``hvd_ssm_mixer`` (the whole mixer) and ``hvd_ssd`` (the scan);
    for the program being traced the counter ``hvd.ssd.calls`` and the
    gauges ``hvd.ssd.chunk``, ``hvd.ssd.groups`` and ``hvd.ssd.state_bytes``
    (chunk states the forward kernels write to HBM, summed over the layers
    and over the forward calls a step executes: a recomputed block's twice)
    and ``hvd.ssd.fwd_calls``."""

    heads: int
    head_dim: int
    state: int
    conv: int = 4
    chunk: int = ssd_op.CHUNK
    eps: float = 1e-5
    impl: Optional[str] = None
    dtype: Any = jnp.bfloat16
    groups: int = 1

    @nn.compact
    def __call__(self, x):
        b, length, d = x.shape
        inner = self.heads * self.head_dim
        width = inner + 2 * self.groups * self.state        # x | B | C
        with jax.named_scope(timeline.SSM_MIXER):
            proj = nn.Dense(inner + width + self.heads, use_bias=False,
                            dtype=self.dtype, name="in_proj")(x)
            z, xbc, dt = (proj[..., :inner], proj[..., inner:inner + width],
                          proj[..., inner + width:])
            kernel = self.param("conv1d_kernel",
                                nn.initializers.lecun_normal(),
                                (self.conv, width))
            bias = self.param("conv1d_bias", nn.initializers.zeros, (width,))
            padded = jnp.pad(xbc, ((0, 0), (self.conv - 1, 0), (0, 0)))
            conv = bias + sum(padded[:, i:i + length].astype(jnp.float32)
                              * kernel[i] for i in range(self.conv))
            xbc = nn.silu(conv).astype(self.dtype)
            dt_bias = self.param("dt_bias", nn.initializers.zeros,
                                 (self.heads,))
            a_log = self.param("A_log", nn.initializers.zeros, (self.heads,))
            skip = self.param("D", nn.initializers.ones, (self.heads,))
            delta = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            program, tally = timeline.program_tally(_scans, lambda: [0, 0])
            again = 1 + _recomputing[0]     # forward calls a step executes
            tally[0] += again
            tally[1] += again * ssd_op.state_bytes(
                b, length, self.heads, self.state, self.head_dim, self.chunk)
            timeline.count("hvd.ssd.calls")
            timeline.gauge("hvd.ssd.chunk", self.chunk, key=program)
            timeline.gauge("hvd.ssd.groups", self.groups, key=program)
            timeline.gauge("hvd.ssd.fwd_calls", tally[0], key=program)
            timeline.gauge("hvd.ssd.state_bytes", tally[1], key=program)
            with jax.named_scope(timeline.SSD):
                y = ssd_op.ssd(xbc, delta, -jnp.exp(a_log), D=skip,
                               state_dim=self.state, groups=self.groups,
                               chunk=self.chunk, impl=self.impl)
            gated = y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
            y = RMSNorm(self.eps, self.groups, name="norm")(gated).astype(
                self.dtype)
            return nn.Dense(d, use_bias=False, dtype=self.dtype,
                            name="out_proj")(y)


# Forward kernel calls and chunk-state bytes of the program being traced:
# program -> (id of its dispatch span, [calls, bytes]); and whether the block
# being applied is one the backward pass runs again (``_applications``).
_scans: dict = {}
_recomputing = [False]


class SparseExperts(nn.Module):
    """``MLP_shared(x) + sum over the chosen experts held here of w_e
    MLP_e(x)``: the chip's share of the layer (``first_expert`` and
    ``experts_held`` say which experts are its own), nothing dropped. An
    MLP is a :class:`GatedMLP` where ``gated`` and a
    :class:`SquaredReluMLP` where not, the shared one ``shared x width``
    wide."""

    experts: int
    experts_held: int
    first_expert: int
    top_k: int
    width: int
    route_scale: float = 1.0
    shared: int = 1                      # shared experts, of ``width`` each
    dtype: Any = jnp.bfloat16
    gated: bool = True

    @nn.compact
    def __call__(self, x):
        b, length, d = x.shape
        held, f = self.experts_held, self.width
        router = self.param("router", nn.initializers.lecun_normal(),
                            (d, self.experts))
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=0)
        stacked = {"gate": self.param("experts_gate", init, (held, d, f))} \
            if self.gated else {}
        stacked.update(up=self.param("experts_up", init, (held, d, f)),
                       down=self.param("experts_down", init, (held, f, d)))
        bias = self.variable("buffers", "selection_bias", jnp.zeros,
                             (self.experts,), jnp.float32)
        seen = self.variable("buffers", "expert_counts", jnp.zeros,
                             (self.experts,), jnp.float32)
        flat = x.reshape(b * length, d)
        y, counts = moe.routed_experts(
            flat, router, stacked, bias.value, first=self.first_expert,
            top_k=self.top_k, route_scale=self.route_scale,
            dtype=self.dtype, name="/".join(self.path),
            recomputed=_recomputing[0])
        if not self.is_initializing() \
                and self.is_mutable_collection("buffers"):
            seen.value = counts
        y = y.reshape(b, length, d)
        if self.shared:
            with jax.named_scope(timeline.MOE_SHARED):
                mlp = GatedMLP if self.gated else SquaredReluMLP
                y = y + mlp(self.shared * f, self.dtype, name="shared")(x)
        return y


class DecoderBlock(nn.Module):
    """``h += RMS_2(attention(RMS_1(h)))``; ``h += RMS_4(F(RMS_3(h)))``,
    ``F`` a :class:`GatedMLP` (``moe`` None) or :class:`SparseExperts`;
    without ``norm_outputs`` the two norms before the branches alone:
    ``h += attention(RMS_1(h))``; ``h += F(RMS_2(h))``. ``attn`` holds the
    fields of a :class:`GroupedAttention`, of a :class:`LatentAttention`
    where it names a ``latent_dim``, or of a :class:`Mamba2Mixer` (named
    ``mamba``; its norm keeps the name ``norm_attn``) where it names a
    ``state``. ``residual_scale`` multiplies each branch's output before it
    is added (Granite's ``residual_multiplier``)."""

    attn: dict                          # the attention layer's fields
    ffn_width: int
    moe: Optional[dict] = None          # SparseExperts' fields
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    norm_outputs: bool = True           # a norm after each branch too
    residual_scale: float = 1.0

    @nn.compact
    def __call__(self, h):
        def branch_output(y, name):
            if self.norm_outputs:
                y = RMSNorm(self.eps, name=name)(y)
            if self.residual_scale != 1.0:
                y = y * self.residual_scale
            return y.astype(h.dtype)

        if "state" in self.attn:
            layer, name = Mamba2Mixer, "mamba"
        else:
            layer = LatentAttention if "latent_dim" in self.attn \
                else GroupedAttention
            name = "attn"
        a = RMSNorm(self.eps, name="norm_attn")(h)
        a = layer(eps=self.eps, dtype=self.dtype, name=name, **self.attn)(a)
        h = h + branch_output(a, "norm_attn_out")
        m = RMSNorm(self.eps, name="norm_ffn")(h)
        if self.moe is None:
            m = GatedMLP(self.ffn_width, self.dtype, name="mlp")(m)
        else:
            m = SparseExperts(dtype=self.dtype, name="moe", **self.moe)(m)
        return h + branch_output(m, "norm_ffn_out")

    @nn.nowrap
    def kept_bytes(self, tokens: int, width: int) -> int:
        """What one application over ``tokens`` tokens of ``width`` keeps for
        the backward pass when it is not recomputed, in bytes, as a closed sum
        over the block: the operands of its products and kernels and the
        inputs of its norms, in the compute type. What lies between them (the
        norms' float32, the rotation, the activations' derivatives) XLA
        recomputes inside the fusions that read them, and the routed experts
        recompute themselves (``parallel/moe.py``). An estimate, which
        ``tests/test_chip_smoke.py`` holds to the compiler's count at
        Trinity-Mini's widths (0.99 of it for the dense block, 1.23 for an
        expert block), at Moonlight's (1.06 and 1.16) and at Granite's (1.21
        for a Mamba block)."""
        e = jnp.dtype(self.dtype).itemsize
        # the stream before each branch, its norm and the branch's output
        # where a norm reads it; where none does, the stream alone (by the
        # compiler's count the normed copy is then made again in the
        # products that read it)
        a_token = (6 if self.norm_outputs else 2) * width * e \
            + _mixer_kept(self.attn, e)
        if self.moe is None:
            return tokens * (a_token + 3 * self.ffn_width * e)
        return tokens * (a_token + _experts_kept(self.moe, e))


def _mixer_kept(a: dict, e: int) -> int:
    """Bytes a token that a Mamba mixer or an attention layer of fields
    ``a`` keeps for the backward pass (:meth:`DecoderBlock.kept_bytes`)."""
    if "state" in a:
        # the in-projection's output (z | xBC | dt), the conv's output and
        # the scan's y (the gated norm XLA makes again inside the
        # out-projection's fusions); Delta in float32 and the chunk states
        # the forward kernel writes
        inner = a["heads"] * a["head_dim"]
        conv = inner + 2 * a.get("groups", 1) * a["state"]
        return (inner + conv + a["heads"] + conv + inner) * e \
            + 2 * 4 * a["heads"] + inner * a["state"] * 4 // a.get(
                "chunk", ssd_op.CHUNK)
    if "latent_dim" in a:
        # the compressed row with the rope key before its norm and after,
        # then the kernels' operands: q, the expanded k and v with the one
        # rope key, the output
        row = a["latent_dim"] + a["rope_dim"]
        kept = (2 * row + a["heads"] * (
            2 * a["nope_dim"] + a["rope_dim"] + 2 * a["value_dim"])) * e
    else:
        q, kv = a["heads"] * a["head_dim"], a["kv_heads"] * a["head_dim"]
        kept = (2 * q + 2 * kv) * e             # the kernels' q, k, v, output
        if a.get("qk_norm"):
            kept += (q + kv) * e                # q and k before their norms
        if a.get("gate"):
            kept += 2 * q * e                   # its logits, the gated output
    # the kernels' log-sum-exp a head: float32, each a lane row of 128 in HBM
    return kept + a["heads"] * 128 * 4


def _experts_kept(moe: dict, e: int) -> int:
    """Bytes a token that an expert layer of fields ``moe`` keeps: the
    router's scores in float32 and a token's choices (what the routed
    experts take is the norm's output and the indices), and the shared
    expert's operands: gate, up and their product, or without a gate the
    up-projection alone (its square XLA makes again inside the
    down-projection's fusion)."""
    kept = 3 * 4 * moe["experts"] + 4 * 4 * moe["top_k"]
    shared = moe["shared"] * moe["width"] * e
    return kept + (3 * shared if moe.get("gated", True) else shared)


class MixerBlock(nn.Module):
    """One branch a layer (``nemotron_h``'s block): ``h = h + F(RMS(h))``,
    no norm after the branch, ``F`` by the layer's letter ``kind`` a
    :class:`Mamba2Mixer` (``M``, named ``mamba``), a
    :class:`GroupedAttention` (``*``, named ``attn``) or
    :class:`SparseExperts` (``E``, named ``moe``) of the fields ``fields``;
    the norm is ``norm``."""

    kind: str
    fields: dict
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, h):
        layer, name = {MIXER_MAMBA: (Mamba2Mixer, "mamba"),
                       MIXER_ATTENTION: (GroupedAttention, "attn"),
                       MIXER_MOE: (SparseExperts, "moe")}[self.kind]
        fields = dict(self.fields)
        if self.kind != MIXER_MOE:
            fields["eps"] = self.eps
        a = RMSNorm(self.eps, name="norm")(h)
        y = layer(dtype=self.dtype, name=name, **fields)(a)
        return h + y.astype(h.dtype)

    @nn.nowrap
    def kept_bytes(self, tokens: int, width: int) -> int:
        """:meth:`DecoderBlock.kept_bytes` of the one branch: the stream and
        its norm, and what the mixer or the expert layer keeps.
        ``tests/test_chip_smoke.py`` holds each kind to the compiler's count
        at Nemotron-3-Nano's widths: 0.91 of it for a Mamba layer, 0.94 for
        an expert layer, 1.45 for the attention layer (whose log-sum-exp the
        compiler does not hold at 128 lanes a head in this call)."""
        e = jnp.dtype(self.dtype).itemsize
        own = _experts_kept(self.fields, e) if self.kind == MIXER_MOE \
            else _mixer_kept(self.fields, e)
        return tokens * (2 * width * e + own)


class RecomputePlan(NamedTuple):
    """A model's ``remat`` as :func:`recompute_plan` answers it: how many of
    its block applications the backward pass runs again, what the others are
    reckoned to hold for it meanwhile and what they were allowed."""

    recomputed: int
    kept_bytes: int = 0
    budget_bytes: int = 0


def recompute_plan(kept: Sequence[int], grads: Sequence[int], carried: int,
                   resident: int, head: int, limit: Optional[int],
                   margin: int = 0) -> RecomputePlan:
    """The fewest block applications to recompute such that the step's peak,
    as summed here, and ``margin`` fit in ``limit`` bytes; with no ``limit``
    (a backend that reports none: the CPU, a described topology) every one.

    ``kept[i]`` is what application ``i`` (in the forward pass's order) holds
    for the backward pass when it is not recomputed, ``carried`` what a
    recomputed one holds (its input), ``grads[i]`` the gradients that come to
    life when the backward pass reaches application ``i`` (a weight used by
    several applications: at the last of them), ``resident`` what is there
    throughout, ``head`` the loss's working set.

    **The last applications are the ones kept**: the backward pass frees
    theirs first, while the gradients it leaves behind pile up, so the peak
    stands either at the loss (everything kept, and ``head``) or at one
    application's backward pass (what is kept up to it, its own working set
    if it is recomputed, and the gradients from it on). The compiler's count
    is not additive in the applications kept for the same reason."""
    total = len(kept)
    if not limit:
        return RecomputePlan(total)

    def peak(first):                    # applications first.. are kept
        base = resident + first * carried
        worst, held, live = base + head + sum(kept[first:]), 0, sum(grads)
        for i in range(total):          # its backward pass
            if i >= first:
                held += kept[i]         # kept[first:i + 1]
            worst = max(worst,
                        base + (held if i >= first else kept[i]) + live)
            live -= grads[i]
        return worst

    first = next((n for n in range(total) if peak(n) + margin <= limit),
                 total)
    held = sum(kept[first:])
    return RecomputePlan(first, held, max(0, limit - margin - peak(first))
                         + held)


# What :func:`plan_recomputation` leaves free of the device's memory beside
# its own sum: 2.5 GiB. The sum has read from 1.4 GiB under the compiler's
# count of a cell's whole step (Ouro's, every application recomputed: 0.65
# of it the program's code, which no shape gives away) to 1.2 GiB over it
# (Trinity's; PERF.md section 6, PR 33), and XLA:TPU's scheduler, given
# memory that is free, spends it on a faster order and cuts back by its own
# rematerialisation only at the limit, so a step planned to the brim is
# planned twice: the fullest cell that runs every day leaves 0.85 GiB of the
# 15.75 (`peak_hbm_gib.tok` 14.9, the GPT-2 cells). 1.4 + 0.85, rounded up.
RECOMPUTE_MARGIN = 5 << 29


def plan_recomputation(model, params, batch: int, length: int,
                       state_bytes: int, logits_rows: int,
                       limit: Optional[int]) -> RecomputePlan:
    """:func:`recompute_plan` for one training step of ``model`` (a model of
    this module: ``applications()``, ``block(i)``) over ``batch`` sequences
    of ``length`` tokens a chip, from what can be seen before anything is
    compiled: ``params`` (the model's, or their shapes), the bytes of the
    train state, the rows of float32 logits the loss holds at a time and the
    memory the device offers (``utils.device.memory_limit()``)."""
    tokens, width = batch * length, model.embed_dim
    e = jnp.dtype(model.dtype).itemsize

    def nbytes(tree):
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(tree))

    layers = model.applications()
    kept = [model.block(i).kept_bytes(tokens, width) for i in layers]
    blocks = {i: params[f"DecoderBlock_{i}"] for i in set(layers)}
    # a weight's gradient is whole once the backward pass has been through
    # its first use, and alive from its last
    last = {i: at for at, i in enumerate(layers)}
    grads = [nbytes(blocks[i]) if last[i] == at else 0
             for at, i in enumerate(layers)]
    # Beside the state: the gradients of what is no block (the embedding,
    # the head, the last norms), and the compute type's copy of the kernels
    # that the blocks' products read (matrices; the routed experts' stacks
    # are cast where they are recomputed), which XLA makes once for the
    # forward pass, the recomputation and the backward pass.
    outside = nbytes(params) - sum(grads)
    casts = e * sum(x.size for x in jax.tree_util.tree_leaves(blocks)
                    if len(x.shape) == 2)
    # What enters the loss, in float32, and its cotangent (a looped model:
    # every exit); the logits and theirs.
    exits = getattr(model, "loops", 1)
    head = 2 * 4 * (exits * tokens * width + logits_rows * model.vocab_size)
    return recompute_plan(kept, grads, tokens * width * e,
                          state_bytes + outside + casts, head, limit,
                          RECOMPUTE_MARGIN)


def _apply(block, h):
    return block(h)


# For real: ``prevent_cse`` stays on, or XLA:TPU merges the recomputation back
# into the forward pass.
_apply_again = nn.remat(_apply)

# Block applications traced into each program, and those of them wrapped:
# program -> (id of its ``hvd.spmd.dispatch`` span, [applied, recomputed]).
_wrapped: dict = {}


def _applications(remat, total: int, counted=("hvd.remat.applications",)):
    """``apply(block, h)`` for a forward pass of ``total`` block applications
    under the model's ``remat``: a :class:`RecomputePlan`, a count of
    applications to recompute, or a bool (all or none). The unit is one
    application, so a block applied twice may be kept once and recomputed
    once; its weights are the same leaves either way. Sets the gauges
    ``hvd.remat.*`` of the program being traced; ``counted`` are the gauges
    that read the applications traced so far."""
    if isinstance(remat, bool):
        remat = total if remat else 0
    plan = remat if isinstance(remat, RecomputePlan) else RecomputePlan(remat)
    recomputed = max(0, min(int(plan.recomputed), total))
    program, tally = timeline.program_tally(_wrapped, lambda: [0, 0])
    timeline.gauge("hvd.remat.kept_bytes", plan.kept_bytes, key=program)
    timeline.gauge("hvd.remat.budget_bytes", plan.budget_bytes, key=program)
    at = [0]

    def apply(block, h):
        again = at[0] < recomputed      # the last ones are kept
        at[0] += 1
        tally[0] += 1
        tally[1] += again
        for name in counted:
            timeline.gauge(name, tally[0], key=program)
        timeline.gauge("hvd.remat.recomputed", tally[1], key=program)
        _recomputing[0] = again
        try:
            return (_apply_again if again else _apply)(block, h)
        finally:
            _recomputing[0] = False

    return apply


class SparseDecoderLM(nn.Module):
    """Token ids ``[B, L]`` -> float32 logits ``[B, L, vocab]`` (or, with
    ``return_hidden``, the final norm's output for a fused loss).

    ``layer_types``: one of ``"sliding_attention"`` / ``"full_attention"``
    (:class:`GroupedAttention`) / ``"latent_attention"``
    (:class:`LatentAttention`: ``head_dim`` is a key's own width beside the
    shared ``rope_dim``, ``value_dim`` a value's, ``latent_dim`` the
    compressed row's) / ``"mamba"`` (:class:`Mamba2Mixer` of ``ssm_heads``
    heads of ``ssm_head_dim``, a state of ``ssm_state``, ``ssm_conv`` taps,
    chunks of ``ssm_chunk``) a layer; the first ``dense_layers`` have a
    :class:`GatedMLP` of ``dense_width``, the others :class:`SparseExperts`.
    A grouped attention layer has the q/k norms and the output gate where
    ``qk_norm`` and ``attn_gate`` say (``afmoe``: both), scores scaled by
    ``attn_scale`` (None: ``head_dim ** -0.5``), rotary positions in a
    sliding layer only. ``embed_scale`` multiplies the embedding by
    ``sqrt(embed_dim)``, ``embed_multiplier`` by a number;
    ``residual_scale`` each branch's output (:class:`DecoderBlock`);
    ``tie_head`` takes the logits from the embedding's transpose, and they
    are divided by ``logit_divisor`` (:func:`loss_head` gives a fused loss
    the same two). ``norm_outputs`` is :class:`DecoderBlock`'s.

    ``pattern`` (``nemotron_h``'s ``hybrid_override_pattern`` of the layers
    held) takes the place of ``layer_types`` and ``dense_layers``: a
    :class:`MixerBlock` a letter, ``M`` a Mamba mixer (``ssm_groups``
    groups of B and C), ``E`` the expert layer, ``*`` grouped attention with
    no positional encoding. ``expert_gated`` false makes every expert and
    the shared one a :class:`SquaredReluMLP`. ``remat``
    says how many block applications (here: blocks) the backward pass runs
    again instead of keeping what they computed: a count or a
    :class:`RecomputePlan` (the first so many; the last ones are kept),
    ``True`` all, ``False`` none. A lane's ``--remat`` fills it with
    :func:`plan_recomputation`'s answer: the fewest that fit the device's
    memory."""

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
    remat: Union[bool, int, RecomputePlan] = False
    norm_outputs: bool = True
    rope_dim: int = 0               # a latent layer's three further widths
    value_dim: int = 0
    latent_dim: int = 0
    qk_norm: bool = True            # a grouped attention layer's choices
    attn_gate: bool = True
    attn_scale: Optional[float] = None
    ssm_heads: int = 0              # a Mamba layer's widths
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_chunk: int = ssd_op.CHUNK
    ssm_impl: Optional[str] = None
    embed_multiplier: Optional[float] = None
    residual_scale: float = 1.0
    tie_head: bool = False
    logit_divisor: float = 1.0
    pattern: str = ""
    ssm_groups: int = 1
    expert_gated: bool = True

    @nn.nowrap
    def depth(self) -> int:
        """Layers: the pattern's letters, or ``layer_types``."""
        return len(self.pattern) if self.pattern else len(self.layer_types)

    @nn.nowrap
    def _experts(self) -> dict:
        """An expert layer's :class:`SparseExperts` fields."""
        fields = dict(
            experts=self.experts, experts_held=self.experts_held,
            first_expert=self.first_expert, top_k=self.top_k,
            width=self.expert_width, route_scale=self.route_scale,
            shared=self.shared_experts)
        if not self.expert_gated:
            fields["gated"] = False
        return fields

    @nn.nowrap
    def _mixer_block(self, i: int) -> MixerBlock:
        kind = self.pattern[i]
        if kind == MIXER_MAMBA:
            fields = dict(heads=self.ssm_heads, head_dim=self.ssm_head_dim,
                          state=self.ssm_state, conv=self.ssm_conv,
                          chunk=self.ssm_chunk, impl=self.ssm_impl,
                          groups=self.ssm_groups)
        elif kind == MIXER_ATTENTION:
            fields = dict(heads=self.heads, kv_heads=self.kv_heads,
                          head_dim=self.head_dim, rotary=False,
                          qk_norm=self.qk_norm, gate=self.attn_gate,
                          attention=self.attention)
            if self.attn_scale is not None:
                fields["scale"] = self.attn_scale
        elif kind == MIXER_MOE:
            fields = self._experts()
        else:
            raise ValueError(f"layer {i}: no layer letter {kind!r} "
                             f"(M, E or *)")
        return MixerBlock(kind, fields, self.eps, self.dtype,
                          name=f"DecoderBlock_{i}")

    @nn.nowrap
    def block(self, i: int) -> Union[DecoderBlock, MixerBlock]:
        """Layer ``i``'s block, by the name its parameters have."""
        if self.pattern:
            return self._mixer_block(i)
        kind = self.layer_types[i]
        if kind not in (SLIDING, FULL, LATENT, MAMBA):
            raise ValueError(f"layer {i}: no layer type {kind!r}")
        if kind == LATENT:
            attn = dict(heads=self.heads, nope_dim=self.head_dim,
                        rope_dim=self.rope_dim, value_dim=self.value_dim,
                        latent_dim=self.latent_dim, rope_base=self.rope_base,
                        attention=self.attention)
        elif kind == MAMBA:
            attn = dict(heads=self.ssm_heads, head_dim=self.ssm_head_dim,
                        state=self.ssm_state, conv=self.ssm_conv,
                        chunk=self.ssm_chunk, impl=self.ssm_impl)
        else:
            attn = dict(heads=self.heads, kv_heads=self.kv_heads,
                        head_dim=self.head_dim, rope_base=self.rope_base,
                        window=self.window if kind == SLIDING else None,
                        rotary=kind == SLIDING, qk_norm=self.qk_norm,
                        gate=self.attn_gate, attention=self.attention)
            if self.attn_scale is not None:
                attn["scale"] = self.attn_scale
        sparse = None if i < self.dense_layers else self._experts()
        return DecoderBlock(attn, self.dense_width, sparse, self.eps,
                            self.dtype, self.norm_outputs,
                            self.residual_scale, name=f"DecoderBlock_{i}")

    @nn.nowrap
    def applications(self) -> list:
        """The layer whose block each application of the forward pass
        applies, in order: every layer once."""
        return list(range(self.depth()))

    @nn.compact
    def __call__(self, tokens, train: bool = True,
                 return_hidden: bool = False):
        del train                                   # no dropout anywhere
        embed = nn.Embed(self.vocab_size, self.embed_dim, dtype=self.dtype,
                         name="embed")
        h = embed(tokens)
        if self.embed_scale:
            h = h * jnp.asarray(self.embed_dim ** 0.5, h.dtype)
        if self.embed_multiplier is not None:
            h = h * jnp.asarray(self.embed_multiplier, h.dtype)
        apply_block = _applications(self.remat, self.depth())
        for i in range(self.depth()):
            h = apply_block(self.block(i), h)
        h = RMSNorm(self.eps, name="final_norm")(h)
        if return_hidden:
            return h
        if self.tie_head:
            logits = jnp.dot(h, embed.embedding.T.astype(jnp.float32))
        else:
            logits = nn.Dense(self.vocab_size, use_bias=False,
                              dtype=jnp.float32, name="lm_head")(h)
        if self.logit_divisor != 1.0:
            logits = logits / self.logit_divisor
        return logits


def loss_head(model, params):
    """``(kernel [embed_dim, vocab], divisor)`` of a language model's loss
    head: its ``lm_head``, or the embedding's transpose where the model ties
    the two (``tie_head``); the logits are ``hidden @ kernel / divisor``."""
    if getattr(model, "tie_head", False):
        kernel = params["embed"]["embedding"].T
    else:
        kernel = params["lm_head"]["kernel"]
    return kernel, getattr(model, "logit_divisor", 1.0)


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
    (``models.make_lm_train_step``). ``remat`` is
    :class:`SparseDecoderLM`'s, and its unit one application: of the ``loops
    x num_layers`` the first so many are run again in the backward pass, so
    a block may be recomputed in one loop step and kept in another."""

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
    remat: Union[bool, int, RecomputePlan] = False

    @nn.nowrap
    def block(self, i: int) -> DecoderBlock:
        """Layer ``i``'s block, by the name its parameters have."""
        attn = dict(heads=self.heads, kv_heads=self.kv_heads,
                    head_dim=self.head_dim, rope_base=self.rope_base,
                    attention=self.attention)
        return DecoderBlock(attn, self.ffn_width, None, self.eps, self.dtype,
                            name=f"DecoderBlock_{i}")

    @nn.nowrap
    def applications(self) -> list:
        """The layer whose block each application of the forward pass
        applies, in order: the stack, ``loops`` times."""
        return list(range(self.num_layers)) * self.loops

    @nn.compact
    def __call__(self, tokens, train: bool = True,
                 return_hidden: bool = False):
        del train                                   # no dropout anywhere
        h = nn.Embed(self.vocab_size, self.embed_dim, dtype=self.dtype,
                     name="embed")(tokens)
        blocks = [self.block(i) for i in range(self.num_layers)]
        # counted in the loop, one a block applied: unrolled, so what is
        # traced is what a step executes
        apply_block = _applications(
            self.remat, self.loops * self.num_layers,
            ("hvd.remat.applications", "hvd.loop.applications"))
        final_norm = RMSNorm(self.eps, name="final_norm")
        # one output a token: at full precision it costs nothing, and the
        # exit distribution is read off it
        exit_gate = nn.Dense(1, dtype=jnp.float32, name="exit_gate",
                             precision=jax.lax.Precision.HIGHEST)
        exits, gates = [], []
        for _ in range(self.loops):
            with jax.named_scope(timeline.LOOP_STEP):
                for block in blocks:
                    h = apply_block(block, h)
                x = final_norm(h)
            with jax.named_scope(timeline.EXIT_GATE):
                gates.append(exit_gate(x)[..., 0])
            exits.append(x)
            h = x.astype(self.dtype)
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
              t_chunk: int = T_CHUNK):
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
