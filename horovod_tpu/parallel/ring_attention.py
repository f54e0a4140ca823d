"""Ring attention: exact attention over sequences sharded across chips.

First-class long-context support (beyond the reference, which scaled batch
only — SURVEY §2.9/§5). Each chip holds a sequence shard of Q, K, V; K/V
blocks rotate around the mesh axis with ``lax.ppermute`` while every chip
accumulates its queries' attention over each visiting block with the
online-softmax (flash) recurrence. Peak memory is O(L_local^2) per step
instead of O(L^2), and the ICI transfer of the next block overlaps the
current block's compute (XLA schedules the ppermute concurrently with the
einsums — the Pallas guide's ring-collective pattern). In causal mode a
visiting block entirely above this shard's diagonal skips its compute
(the ring-level twin of the flash kernels' causal grid truncation); the
rotation itself is never skipped — collectives stay rank-uniform.

Use inside ``shard_map``/``spmd_run`` with the sequence axis sharded, e.g.
``in_specs=P(None, "sp", None, None)`` for [B, L, H, D].
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.ops.attention import NEG_INF
from horovod_tpu.parallel.logical import module_axis


def ring_attention(q, k, v, axis: Optional[str] = None, causal: bool = False,
                   scale: Optional[float] = None,
                   skip_dead_blocks: bool = True):
    """Exact multi-head attention over a sequence-sharded mesh axis.

    Shapes (per chip): q, k, v [B, L_local, H, D] -> [B, L_local, H, D].
    Must run inside a shard_map region with ``axis`` active. Causal masks
    use global token positions, so results match single-chip attention on
    the gathered sequence exactly.

    ``skip_dead_blocks`` (causal only) conditionally skips the einsums
    for visiting blocks entirely above this shard's diagonal; ``False``
    runs the numerically identical unconditional masked update (the
    A/B side).
    """
    axis = module_axis("seq", axis)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    size = lax.axis_size(axis)
    rank = lax.axis_index(axis)
    B, Lq, H, D = q.shape
    Lk = k.shape[1]

    qf = q.astype(jnp.float32) * scale
    perm = [(i, (i + 1) % size) for i in range(size)]

    def step(p, carry):
        k_blk, v_blk, m, l, acc = carry
        src = (rank - p) % size  # owner of the block currently held

        def _update(operand):
            k_b, v_b, m, l, acc = operand
            s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_b.astype(jnp.float32))
            if causal:
                q_pos = rank * Lq + jnp.arange(Lq)[:, None]
                k_pos = src * Lk + jnp.arange(Lk)[None, :]
                s = jnp.where((q_pos >= k_pos)[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p_exp = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + jnp.sum(p_exp, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p_exp, v_b.astype(jnp.float32))
            return m_new, l_new, acc_new

        if causal and skip_dead_blocks:
            # The dead half of the causal ring: a visiting block whose
            # FIRST global key position is past this shard's LAST query
            # row is fully masked — skip its einsums and rescale
            # outright (same at-or-below-diagonal discipline as the
            # flash kernels' truncated grid; ~half the ring steps on a
            # causal square). Only the local compute is conditional:
            # the ppermute rotation below stays unconditional, since
            # every rank must feed the collective on every step.
            has_live = rank * Lq + Lq - 1 >= src * Lk
            m, l, acc = lax.cond(has_live, _update,
                                 lambda operand: operand[2:],
                                 (k_blk, v_blk, m, l, acc))
        else:
            m, l, acc = _update((k_blk, v_blk, m, l, acc))
        # Rotate K/V to the next chip; the final rotation returns blocks
        # home, keeping the loop body uniform for lax.fori_loop.
        k_next = lax.ppermute(k_blk, axis, perm)
        v_next = lax.ppermute(v_blk, axis, perm)
        return k_next, v_next, m, l, acc

    from horovod_tpu.parallel._vma import match_vma

    # Type the zero-init carries as varying like q/k/v so the loop body's
    # carry-out matches under check_vma=True (values unchanged).
    m0 = match_vma(jnp.full((B, H, Lq), NEG_INF, jnp.float32), q, k, v)
    l0 = match_vma(jnp.zeros((B, H, Lq), jnp.float32), q, k, v)
    acc0 = match_vma(jnp.zeros((B, H, Lq, D), jnp.float32), q, k, v)
    _, _, m, l, acc = lax.fori_loop(0, size, step, (k, v, m0, l0, acc0))

    out = acc / jnp.maximum(l, 1e-30)[..., None]  # [B, H, Lq, D]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)
