"""Decoder-only Transformer LM — the long-context flagship.

Beyond-reference model family (the reference's longest-context artifact is
word2vec, SURVEY §2.9): a GPT-style causal LM whose attention is pluggable
so the same network trains single-chip (flash attention on the MXU),
sequence-parallel via ring attention, or via Ulysses all-to-all — the
framework's long-context story end to end.

TPU-native choices: bf16 compute / fp32 layernorm+softmax+logits, static
shapes, pre-norm blocks, learned positional embeddings, no Python control
flow in the forward pass.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.ops.attention import attend
from horovod_tpu.utils import timeline


class TransformerBlock(nn.Module):
    num_heads: int
    dtype: Any = jnp.bfloat16
    mlp_ratio: int = 4
    # attn_fn(q, k, v) -> out, shapes [B, L, H, D]. The fn owns causality
    # and cross-shard positioning (e.g. a ring-attention closure passes
    # causal=True itself; ring/Ulysses derive offsets from the mesh axis).
    # None = causal attention using q_offset, by the implementation that
    # ops.attention.attention_plan picks for the shapes (the flash kernels
    # or the dense reference). ``attend`` itself (None, or a partial of it
    # that pins an argument) takes the fused projection whole: at heads of
    # 64 its kernels read q, k and v out of it where it lies.
    attn_fn: Optional[Callable] = None
    dropout: float = 0.0

    @nn.compact
    def __call__(self, x, train: bool = True, q_offset: int = 0):
        E = x.shape[-1]
        H = self.num_heads
        D = E // H
        h = nn.LayerNorm(dtype=jnp.float32)(x)
        qkv = nn.Dense(3 * E, use_bias=False, dtype=self.dtype)(h)
        with jax.named_scope(timeline.ATTN_FULL):
            if self.attn_fn is None:
                attn = attend(qkv, heads=H, q_offset=q_offset)
            elif getattr(self.attn_fn, "func", None) is attend:
                attn = self.attn_fn(qkv, heads=H)
            else:
                shape = (*x.shape[:-1], H, D)
                attn = self.attn_fn(*(t.reshape(shape) for t in
                                      jnp.split(qkv, 3, axis=-1)))
        x = x + nn.Dense(E, dtype=self.dtype)(attn.reshape(x.shape))

        h = nn.LayerNorm(dtype=jnp.float32)(x)
        h = nn.Dense(self.mlp_ratio * E, dtype=self.dtype)(h)
        h = nn.gelu(h)
        h = nn.Dropout(self.dropout, deterministic=not train)(h)
        return x + nn.Dense(E, dtype=self.dtype)(h)


class _CarryBlock(nn.Module):
    """TransformerBlock with a (carry, _) -> (carry, None) signature so
    ``nn.scan`` can stack it along a layer axis."""

    num_heads: int
    dtype: Any
    attn_fn: Optional[Callable]
    dropout: float
    train: bool
    q_offset: int

    @nn.compact
    def __call__(self, x, _):
        x = TransformerBlock(self.num_heads, dtype=self.dtype,
                             attn_fn=self.attn_fn, dropout=self.dropout)(
                                 x, train=self.train, q_offset=self.q_offset)
        return x, None


class TransformerLM(nn.Module):
    """Causal LM: token ids [B, L] -> logits [B, L, vocab].

    ``scan_layers`` compiles the layer stack as ONE ``lax.scan`` step
    over weight-stacked parameters instead of ``num_layers`` unrolled
    copies — XLA traces/compiles a single block, so compile time is
    ~flat in depth (the unrolled path grows linearly). Parameters change layout
    (each block param gains a leading [num_layers] axis), so the two
    layouts are not checkpoint-compatible; per-layer math is identical
    (equivalence pinned in tests/test_models.py). ``remat`` additionally
    rematerializes each block on the backward pass — activation memory
    O(1) in depth, the long-context training default.
    """

    vocab_size: int = 32000
    num_layers: int = 4
    num_heads: int = 8
    embed_dim: int = 512
    max_len: int = 2048
    dtype: Any = jnp.bfloat16
    attn_fn: Optional[Callable] = None
    dropout: float = 0.0
    scan_layers: bool = False
    remat: bool = False

    @nn.compact
    def __call__(self, tokens, train: bool = True, pos_offset: int = 0,
                 return_hidden: bool = False):
        """``pos_offset``: global position of tokens[:, 0] — sequence-
        parallel callers pass their shard's offset so positional
        embeddings and causal masks stay globally consistent.

        ``return_hidden`` skips the vocab projection and returns the
        final-LayerNorm hidden states [B, L, E] — for fused losses
        (ops/xent.py) that consume the projection weight directly and
        never materialize [B, L, vocab] logits. Init with the default
        so the Dense param exists either way."""
        x = nn.Embed(self.vocab_size, self.embed_dim,
                     dtype=self.dtype)(tokens)
        pos = pos_offset + jnp.arange(tokens.shape[1])
        x = x + nn.Embed(self.max_len, self.embed_dim,
                         dtype=self.dtype)(pos)[None]
        if self.scan_layers:
            block = _CarryBlock
            if self.remat:
                block = nn.remat(block, prevent_cse=False)
            scan = nn.scan(block,
                           variable_axes={"params": 0},
                           split_rngs={"params": True, "dropout": True},
                           length=self.num_layers)
            x, _ = scan(self.num_heads, self.dtype, self.attn_fn,
                        self.dropout, train, pos_offset,
                        name="layers")(x, None)
        else:
            blk = TransformerBlock
            if self.remat:
                # self=0, x=1: train and q_offset stay Python-static.
                blk = nn.remat(blk, prevent_cse=False,
                               static_argnums=(2, 3))
            for i in range(self.num_layers):
                # Explicit names keep the param tree identical whether
                # or not the block is remat-wrapped (nn.remat would
                # otherwise prefix the auto-name with "Checkpoint").
                x = blk(self.num_heads, dtype=self.dtype,
                        attn_fn=self.attn_fn, dropout=self.dropout,
                        name=f"TransformerBlock_{i}")(x, train, pos_offset)
        x = nn.LayerNorm(dtype=jnp.float32)(x)
        if return_hidden:
            return x
        # Explicitly named so fused losses can address the projection
        # weight (params["lm_head"]["kernel"]) without depending on
        # flax auto-numbering staying stable.
        return nn.Dense(self.vocab_size, dtype=jnp.float32,
                        use_bias=False, name="lm_head")(x)
