"""Attention kernels: reference jnp implementation + Pallas flash attention.

These are the single-chip building blocks under the sequence-parallel
schemes in :mod:`horovod_tpu.parallel` (ring attention rotates K/V blocks
between chips and calls a block kernel locally; Ulysses reshards heads and
calls a full local kernel). The reference framework has no attention ops —
long-context support is a first-class extension of this rebuild (SURVEY
§5 "Long-context / sequence parallelism: absent").

``flash_attention`` is a Pallas TPU kernel (online-softmax tiling so the
L x L score matrix never materializes in HBM); on the CPU test platform
it runs in interpreter mode so tests cover the same code path. Where a head
is 64 wide a kernel program serves the two heads that share a 128-lane column
block and reads q, k, v and dO, and writes o, dQ, dK and dV, in the layout the
projections use (``[B, L, columns]``, a fused ``qkv`` projection taken whole),
by index map: nothing is transposed or sliced between a projection and a
kernel (``flash_attention(..., heads=)``; :func:`attention_plan` decides from
the shapes). On the causal square
path every streamed kernel (the forward; the backward, one kernel or the dQ
and dK/dV pair) executes a PACKED at-or-below-diagonal grid — the
strictly-masked half of the (q-block,
k-block) plane never occupies a grid step, so neither its K/V DMA bytes
nor its loop overhead is paid (closing the traffic debt PERF.md's
"Streamed-causal K/V traffic tradeoff" recorded), and with the one-kernel
backward a step computes what the mask lets through: the block the diagonal
crosses in row slabs that stop at the diagonal, a block it does not cross
with no mask (``attention_plan(...).slab_rows``). A ``window`` narrows the
triangle to a band (query i sees keys j with ``0 <= i - j < window``): the
packed grid then leaves out the blocks below the band as well. K and V may
have fewer heads than Q (grouped-query attention): query head h reads KV
head ``h // (H / G)``, by the kernels' index maps and with no copy of K or V.
Keys may be wider than values (a latent layer's 192 and 128): the output and
``dv`` have the values' width, ``dq`` and ``dk`` the keys'. And the last
columns of every head's key may be one vector a token that all heads share
(``k_shared [B, L, Dr]``, a latent layer's rope key): the kernels read it by
index map as they read a grouped K, add its product with the query's last
``Dr`` columns into the same block of scores, and no ``[B, L, H, Dn + Dr]``
key is ever written.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.utils import timeline
from horovod_tpu.utils.device import pallas_interpret

NEG_INF = -1e30  # finite stand-in for -inf: exp() of it is exactly 0


def _with_shared_key(k, k_shared):
    """``k [..., Lk, G, Dn]`` with ``k_shared [..., Lk, Dr]`` appended to
    every head's key: what the kernels compute without writing it."""
    if k_shared is None:
        return k
    shared = jnp.broadcast_to(k_shared[..., None, :],
                              k.shape[:-1] + k_shared.shape[-1:])
    return jnp.concatenate([k, shared.astype(k.dtype)], -1)


def dot_product_attention(q, k, v, causal: bool = False,
                          scale: Optional[float] = None,
                          q_offset: int = 0, k_offset: int = 0,
                          window: Optional[int] = None, k_shared=None):
    """Reference attention. Shapes: q [..., Lq, H, Dk], k [..., Lk, G, Dk],
    v [..., Lk, G, Dv] with ``G`` dividing ``H`` (query head h reads KV head
    ``h // (H / G)``); the result is ``[..., Lq, H, Dv]``. With ``k_shared
    [..., Lk, Dr]`` ``k`` holds the first ``Dk - Dr`` columns of every key
    and ``k_shared`` the rest, the same for all heads.

    ``q_offset``/``k_offset`` are the global positions of the first query/
    key token — block-parallel callers (ring attention) pass their shard's
    global offset so causal masks line up across chips. ``window`` (causal
    only) lets query i see keys j with ``0 <= i - j < window``.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    k = _with_shared_key(k, k_shared)
    rep = _kv_group(q.shape[-2], k.shape[-2])
    if rep > 1:
        k, v = (jnp.repeat(t, rep, axis=-2) for t in (k, v))
    logits = jnp.einsum("...qhd,...khd->...hqk", q, k) * scale
    if causal:
        qi = q_offset + jnp.arange(q.shape[-3])[:, None]
        ki = k_offset + jnp.arange(k.shape[-3])[None, :]
        seen = qi >= ki
        if window is not None:
            seen &= qi - ki < window
        logits = jnp.where(seen, logits, NEG_INF)
    elif window is not None:
        raise ValueError("a window needs causal=True")
    weights = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("...hqk,...khd->...qhd", weights.astype(q.dtype), v)


def _kv_group(heads: int, kv_heads: int) -> int:
    """Query heads a KV head: ``H / G``."""
    if kv_heads < 1 or heads % kv_heads:
        raise ValueError(f"{kv_heads} KV heads do not divide {heads} "
                         f"query heads")
    return heads // kv_heads


# --------------------------------------------------------------------------
# Causal grid truncation policy (shared by kernels + accounting)


def _grid_truncates(causal: bool, seq_q: int, seq_k: int, q_offset: int,
                    k_offset: int, truncate: Optional[bool]) -> bool:
    """Static policy for the packed at-or-below-diagonal grid.

    It applies exactly when the mask is the standard square lower
    triangle: causal, Lq == Lk, equal global offsets. Cross-attention
    (Lq != Lk) and global-offset causal (ring shard geometry) keep the
    FULL grid with per-block compute skips — their diagonal can leave a
    q-block with zero live k-blocks, which a packed grid cannot
    represent (a block the grid never visits is never initialized or
    written). ``truncate=None`` is the auto policy; ``False`` forces
    the full grid (the truncated-vs-full A/B lanes); ``True`` asserts
    eligibility instead of silently degrading.
    """
    eligible = causal and seq_q == seq_k and q_offset == k_offset
    if truncate is None:
        return eligible
    if truncate and not eligible:
        raise ValueError(
            "truncate=True requires plain causal square attention "
            f"(causal={causal}, Lq={seq_q}, Lk={seq_k}, "
            f"q_offset={q_offset}, k_offset={k_offset}): cross-attention "
            "and offset-causal grids stay full (compute-skip only)")
    return bool(truncate)


def _first_kblock(qi, block_q: int, block_k: int, window: Optional[int],
                  maximum=max):
    """The first k-block a q-block's rows can see: block 0, or under a
    window the block that holds the first row's oldest key. Plain integers
    for the tables; the kernels pass ``jnp.maximum`` for a traced ``qi``."""
    if window is None:
        return 0
    return maximum(qi * block_q - window + 1, 0) // block_k


def _last_qblock(kb, block_q: int, block_k: int, n_qblocks: int,
                 window: Optional[int], minimum=min):
    """The last q-block that can see a k-block: the last of all, or under a
    window the block of the last row that still sees the block's last key."""
    if window is None:
        return n_qblocks - 1
    return minimum(n_qblocks - 1,
                   (kb * block_k + block_k + window - 2) // block_q)


def _kblock_span(qi, kb, block_q: int, block_k: int, n_kblocks: int,
                 window: Optional[int], packed: bool):
    """Inside a kernel: whether ``kb`` is the first and the last k-block
    that q-block ``qi`` meets on its grid: on a packed grid from
    :func:`_first_kblock` to its diagonal (whichever way the tables walk:
    k-blocks ascend for a q-block on both), on a full one from 0 to the last
    of all, live or not."""
    if not packed:
        return kb == 0, kb == n_kblocks - 1
    return (kb == _first_kblock(qi, block_q, block_k, window, jnp.maximum),
            kb == jnp.minimum(n_kblocks - 1,
                              (qi * block_q + block_q - 1) // block_k))


def _band_mask(q_pos, k_pos, window: Optional[int]):
    """Which keys a query sees: those at or before it and, under a window,
    fewer than ``window`` positions back."""
    seen = q_pos >= k_pos
    if window is not None:
        seen &= q_pos - k_pos < window
    return seen


def _block_live(qi, kb, block_q: int, block_k: int, delta: int,
                window: Optional[int]):
    """Full grids only: whether any row of q-block ``qi`` sees any key of
    k-block ``kb`` (a dead block skips its compute, not its DMA)."""
    live = qi * block_q + block_q - 1 + delta >= kb * block_k
    if window is not None:
        live &= qi * block_q + delta - (kb * block_k + block_k - 1) < window
    return live


@functools.lru_cache(maxsize=None)
def _causal_step_tables(n_qblocks: int, n_kblocks: int, block_q: int,
                        block_k: int, k_major: bool = False,
                        window: Optional[int] = None):
    """Scalar-prefetch step tables for the packed causal grid.

    Enumerates ONLY the (q-block, k-block) pairs that intersect the
    at-or-below-diagonal region (``qi*block_q + block_q - 1 >=
    kb*block_k``) — on an n x n grid with square blocks that is
    n(n+1)/2 of the n^2 full steps — and, under a ``window``, of those only
    the pairs inside the band (some row of the q-block sees some key of the
    k-block: ``0 <= i - j < window``). q-major order streams k-blocks per
    q-block (forward + dQ); ``k_major`` streams q-blocks per k-block
    (dK/dV, whose dead region is the symmetric above-diagonal half over
    the q axis). Square-causal only: every q-block's live k-blocks run
    from :func:`_first_kblock` to its diagonal and every k-block's live
    q-blocks from its diagonal to :func:`_last_qblock`, which is what the
    kernels' init/finalize conditions assume.
    """
    pairs = []
    if k_major:
        for kb in range(n_kblocks):
            # ceil((kb*bk - bq + 1) / bq) == floor(kb*bk / bq): the
            # first q-block whose last row reaches this k-block.
            last = _last_qblock(kb, block_q, block_k, n_qblocks, window)
            pairs.extend((qi, kb)
                         for qi in range((kb * block_k) // block_q,
                                         last + 1))
    else:
        for qi in range(n_qblocks):
            last = min(n_kblocks - 1,
                       (qi * block_q + block_q - 1) // block_k)
            first = _first_kblock(qi, block_q, block_k, window)
            pairs.extend((qi, kb) for kb in range(first, last + 1))
    qi_tab = np.asarray([p[0] for p in pairs], np.int32)
    kb_tab = np.asarray([p[1] for p in pairs], np.int32)
    return qi_tab, kb_tab


def flash_grid_info(seq_q: int, seq_k: int, *, causal: bool,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    q_offset: int = 0, k_offset: int = 0,
                    truncate: Optional[bool] = None,
                    head_dim: Optional[int] = None,
                    batch_heads: int = 1, dtype_bytes: int = 2,
                    window: Optional[int] = None):
    """Static grid + K/V-DMA accounting for a ``flash_attention`` call.

    Mirrors exactly the tiling (:func:`attention_plan`'s blocks) and
    truncation (:func:`_grid_truncates`) policy the kernels use, without
    tracing anything: ``tools/tpu_flash_check.py`` puts it into its
    micro A/B report so every wall-time record is attributable to a
    concrete grid, not just a block pair.

    Returns a dict: chosen blocks, grid shape, per-``batch_heads``-step
    counts (``steps`` vs ``steps_full``), ``kv_fetch_frac`` (the
    truncated/full step ratio — (n+1)/2n on a causal square grid, less
    under a ``window``), and
    — when ``head_dim`` is given — the estimated K/V bytes the grid
    DMAs in (one [block_k, head_dim] tile each for K and V per step,
    times ``batch_heads``).
    """
    block_q, block_k = _planned_blocks(seq_q, seq_k, block_q, block_k)
    bq, bk = min(block_q, seq_q), min(block_k, seq_k)
    nqb, nkb = seq_q // bq, seq_k // bk
    truncated = _grid_truncates(causal, seq_q, seq_k, q_offset, k_offset,
                                truncate)
    steps_full = nqb * nkb
    if truncated:
        qi_tab, _ = _causal_step_tables(nqb, nkb, bq, bk, window=window)
        steps = int(qi_tab.size)
    else:
        steps = steps_full
    info = {
        "block_q": bq, "block_k": bk,
        "n_qblocks": nqb, "n_kblocks": nkb,
        "truncated": truncated,
        "grid": ([batch_heads, steps] if truncated
                 else [batch_heads, nqb, nkb]),
        "steps": steps, "steps_full": steps_full,
        "kv_fetch_frac": round(steps / steps_full, 4),
        "kv_bytes": None, "kv_bytes_full": None,
    }
    if head_dim is not None:
        tile = 2 * bk * head_dim * dtype_bytes * batch_heads
        info["kv_bytes"] = steps * tile
        info["kv_bytes_full"] = steps_full * tile
    return info


# --------------------------------------------------------------------------
# Pallas flash attention


def _block_scores(q, k_blk, ks_ref, scale: float):
    """``q k^T * scale`` of one (q-block, k-block) pair, float32. Matmuls run
    in the INPUT dtype with f32 accumulation (preferred_element_type): bf16
    inputs hit the MXU's native bf16xbf16->f32 path (an f32xf32 matmul costs
    ~3 passes on TPU); f32 test inputs keep the all-f32 exactness the CI
    pins. With a shared key (``ks_ref [block_k, Dr]``) the query's last
    ``Dr`` columns meet it and the others the head's own ``k_blk``: two
    products into one block of scores."""
    if ks_ref is None:
        return jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32) * scale
    own = k_blk.shape[-1]
    return (jnp.dot(q[:, :own], k_blk.T, preferred_element_type=jnp.float32)
            + jnp.dot(q[:, own:], ks_ref[...].T,
                      preferred_element_type=jnp.float32)) * scale


def _head_lanes(shape, heads: int, h: int):
    """Which lanes of a ``[rows, 128]`` tile belong to head ``h`` of the
    ``heads`` that lie side by side in it."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return lane // (shape[1] // heads) == h


def _own_lanes(x, heads: int, h: int):
    """``x`` with the lanes of every head but ``h`` zeroed (``x`` itself in a
    one-head program): a product that contracts over the lanes then sees head
    ``h`` alone, and one that keeps them is zero outside the head's columns,
    so that the heads' results add up into one 128-lane tile."""
    if heads == 1:
        return x
    return jnp.where(_head_lanes(x.shape, heads, h), x, jnp.zeros_like(x))


def _diagonal_slabs(block: int, slab: int, window: Optional[int]):
    """The slabs of a diagonal block of ``block`` rows and keys walked
    ``slab`` rows at a time: for slab ``r`` its rows, the block's first ``(r
    + 1) x slab`` keys (the last key a row of it sees is its own), and
    ``band()``, the mask of its scores and the first column it covers:
    ``n (n + 1) / 2`` of the block's ``n^2`` sub-tiles of ``slab x slab``
    are computed (``n = block / slab``; 10 of 16 at four slabs a block).
    The slab's older keys are all seen, so its mask covers its own diagonal
    sub-tile alone, unless a window narrower than the block can leave some
    of them behind."""
    whole = window is not None and window < block
    for r in range(block // slab):
        keys = (r + 1) * slab
        first = 0 if whole else r * slab

        def band(r=r, keys=keys, first=first):
            q_pos = r * slab + jax.lax.broadcasted_iota(
                jnp.int32, (slab, 1), 0)
            k_pos = first + jax.lax.broadcasted_iota(
                jnp.int32, (1, keys - first), 1)
            return _band_mask(q_pos, k_pos, window), first

        yield slice(r * slab, (r + 1) * slab), slice(0, keys), band


def _masked(s, seen, first: int = 0):
    """Scores ``s`` with ``NEG_INF`` where ``seen``, the mask of their
    columns from ``first`` on (a multiple of 128 lanes), is False."""
    if not first:
        return jnp.where(seen, s, NEG_INF)
    return jnp.concatenate(
        [s[:, :first], jnp.where(seen, s[:, first:], NEG_INF)], axis=1)


def _interior(qi, kb, block: int, window: Optional[int]):
    """On the packed grid with square blocks: whether q-block ``qi`` sees all
    of k-block ``kb`` (its first row the block's last key and, under a
    window, its last row the block's first), so that no mask is needed."""
    seen = qi > kb
    if window is not None:
        seen &= (qi - kb) * block + block - 1 < window
    return seen


def _flash_kernel(*refs, block_k: int, n_kblocks: int, causal: bool,
                  scale: float, block_q: int, delta: int, packed: bool,
                  window: Optional[int] = None, shared: bool = False,
                  heads: int = 1, slab: Optional[int] = None):
    """One streamed-forward grid step. Two grid layouts share this body:

    * full (``packed=False``) — grid (batch*head, q-block, K-BLOCK): the
      key axis rides the grid (innermost, "arbitrary" semantics), so
      Mosaic's pipeline streams [block_k, d] K/V tiles through
      double-buffered VMEM DMA while the online-softmax state (m/l/acc)
      persists in VMEM scratch across the k steps. Causal dead blocks
      skip their COMPUTE only — their K/V DMA is pipelined regardless.
    * packed (``packed=True``) — grid (batch*head, STEP) over the
      scalar-prefetched (q-block, k-block) tables of
      :func:`_causal_step_tables`: causal square grids enumerate only
      the at-or-below-diagonal pairs, so the dead half's DMA bytes and
      loop steps never exist. Every enumerated step is live — no
      compute skip needed; the diagonal block still applies the
      in-block row mask.

    ``delta = q_offset - k_offset`` shifts the causal mask for
    global-offset callers (always 0 on the packed path, which
    _grid_truncates restricts to equal offsets).

    ``heads`` is the heads a program serves. At 2 the q, k, v and o tiles are
    128 lanes wide and hold two heads of 64 side by side (one column block
    of the projections' ``[B, L, columns]``, no transpose before the call),
    the statistics are ``[2, block_q, 1]``, and the body runs once a head
    over the same tiles and the same mask: the head's scores are ``(q with
    the other head's lanes zeroed) k^T``, the MXU pass a 64-wide contraction
    costs, and of ``p v`` the head keeps its own lanes of the accumulator.

    ``slab`` (packed square grids only; :func:`attention_plan`) makes a
    step compute what the causal edge lets through: the diagonal block is
    walked in row slabs (:func:`_diagonal_slabs`), slab ``r`` updating its
    own rows of ``m``, ``l`` and ``acc`` from its scores against the block's
    first ``(r + 1) x slab`` keys under its own mask, and a block the edge
    does not cross (:func:`_interior`) applies no mask. Under a window the
    blocks its lower edge crosses keep the whole masked body, which is every
    step's body where ``slab`` is None.

    VMEM is O(block) — the pre-streaming design mapped the FULL [Lk, d]
    K/V into each program's VMEM, which hit the 16 MB scoped limit at
    seq 16384 (tools/diag_seq16384.log: 16.25M > 16M).

    Mosaic discipline: every ref and all scratch is kept 2-D
    ([block_q, 1] for the m/l statistics, and the SAME [block_q, 1]
    shape for the lse output block — writing it as a [1, block_q] row
    would need a sublane->lane relayout inside the kernel, a classic
    Mosaic-unsupported reshape that interpret-mode CI cannot catch)."""
    from jax.experimental import pallas as pl

    if packed:
        qi_tab, kb_tab = refs[:2]
        refs = refs[2:]
        t = pl.program_id(1)
        qi = qi_tab[t]
        kb = kb_tab[t]
    else:
        qi = pl.program_id(1)
        kb = pl.program_id(2)
    q_ref, k_ref = refs[:2]
    ks_ref = refs[2] if shared else None
    v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs[2 + shared:]

    # The first step of every q-block: k-block 0 in the full layout, and in
    # the packed one the first block the tables' q-major walk gives it
    # (block 0 too, unless a window has left the oldest blocks out). A row
    # that sees no key of its first blocks gathers weights of exp(0) there,
    # which the first real score wipes out (alpha = exp(NEG_INF - m) = 0).
    first_kb = (_first_kblock(qi, block_q, block_k, window, jnp.maximum)
                if packed else 0)

    @pl.when(kb == first_kb)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def _update(q_ref, k_ref, ks_ref, v_ref, acc_scr, band, rows=None):
        """The online-softmax step of the rows ``q_ref`` holds (``acc``
        holds of them, and ``rows`` of ``m`` and ``l``: all where None)
        against the keys ``k_ref`` holds: the whole block's refs, or views
        of a slab's. ``band()`` is the mask of their scores and the first
        column it covers (:func:`_masked`), ``None`` where they see every
        key."""
        # All softmax statistics stay f32 whatever the inputs' type.
        q = q_ref[...]                              # [block_q, dk]
        k_blk = k_ref[...]                          # [block_k, dk]
        v_blk = v_ref[...]                          # [block_k, dv]
        seen = None
        for h in range(heads):
            at = (h,) if heads > 1 else (Ellipsis,)     # head h's statistics
            if rows is not None:
                at = (h, rows) if heads > 1 else (rows,)
            s = _block_scores(_own_lanes(q, heads, h), k_blk, ks_ref, scale)
            if band is not None:
                if seen is None:            # one mask for the tile's heads
                    seen, first = band()
                s = _masked(s, seen, first)
            m = m_scr[at]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            m_scr[at] = m_new
            l_scr[at] = l_scr[at] * alpha + jnp.sum(p, axis=-1,
                                                    keepdims=True)
            acc = acc_scr[...] * alpha + jnp.dot(
                p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32)
            if heads > 1:   # p v is head h's in its own lanes alone
                acc = jnp.where(_head_lanes(acc.shape, heads, h), acc,
                                acc_scr[...])
            acc_scr[...] = acc

    def _band():
        q_pos = delta + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        return _band_mask(q_pos, k_pos, window), 0

    def _compute(band=_band if causal else None):
        _update(q_ref, k_ref, ks_ref, v_ref, acc_scr, band)

    if causal and not packed:
        # A k-block strictly past this q-block's last row (or wholly
        # older than its window) is fully masked: skip its compute (its
        # DMA is pipelined regardless).
        pl.when(_block_live(qi, kb, block_q, block_k, delta,
                            window))(_compute)
    elif slab is None:
        _compute()  # packed grids enumerate live steps only
    else:
        @pl.when(qi == kb)
        def _diagonal():
            for rows, keys, band in _diagonal_slabs(block_q, slab, window):
                _update(q_ref.at[rows], k_ref.at[keys],
                        None if ks_ref is None else ks_ref.at[keys],
                        v_ref.at[keys], acc_scr.at[rows], band, rows)

        interior = _interior(qi, kb, block_q, window)
        pl.when(interior)(functools.partial(_compute, None))
        if window is not None:      # the blocks the window's edge crosses
            pl.when((qi != kb) & ~interior)(_compute)

    if packed:
        last_kb = jnp.minimum(n_kblocks - 1,
                              (qi * block_q + block_q - 1) // block_k)
    else:
        last_kb = n_kblocks - 1

    @pl.when(kb == last_kb)
    def _finalize():
        # Per-row logsumexp (scores already include `scale`): persisted
        # so the backward never re-derives it with an extra pass over
        # the key blocks. Written in the statistics' native
        # [block_q, 1] layout — no cross-lane reshape inside the kernel.
        if heads == 1:
            l = jnp.maximum(l_scr[...], 1e-30)
            o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)
            lse_ref[...] = m_scr[...] + jnp.log(l)
            return
        sums = [jnp.maximum(l_scr[h], 1e-30) for h in range(heads)]
        l = sums[0]                     # each lane's own head's sum
        for h in range(1, heads):
            l = jnp.where(_head_lanes(acc_scr.shape, heads, h), sums[h], l)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)
        for h in range(heads):
            lse_ref[h] = m_scr[h] + jnp.log(sums[h])


# Native TPU sublane tile: the f32 min tile is (8, 128), so blocks
# below 8 rows are rejected (or pathologically slow) by real Mosaic —
# interpret-mode CI would accept them and hide the hardware failure.
_MIN_BLOCK = 8


def _pick_block(cap: int, seq_len: int) -> int:
    """Largest power-of-two block <= cap that divides ``seq_len``, floored
    at the native 8-sublane tile.

    Lengths with no multiple-of-8 factor (L=100 -> 4; L=33 -> 1) are a
    caller error, not a tiling choice: raise the explicit "pad upstream"
    contract instead of emitting a sub-tile kernel that only fails once it
    reaches a chip (ADVICE r5 #1).
    """
    block = cap
    while block >= _MIN_BLOCK:
        if block <= seq_len and seq_len % block == 0:
            return block
        block //= 2
    raise ValueError(
        f"flash_attention has no legal default block tile for sequence "
        f"length {seq_len}: no divisor >= the native {_MIN_BLOCK}-sublane "
        f"TPU tile. Pad the sequence length upstream to a multiple of "
        f"{_MIN_BLOCK} (ideally 128), or pass explicit block_q/block_k.")


# --------------------------------------------------------------------------
# The one policy: implementation, blocks and backward from the static shapes


class AttentionPlan(NamedTuple):
    """What :func:`attention_plan` chose for one attention call."""

    impl: str                   # "dense" | "flash"
    block_q: Optional[int]      # the kernels' blocks; None where no legal
    block_k: Optional[int]      # block divides a length (then ``dense``)
    bwd: str                    # the kernels' backward: "fused" | "pallas"
    heads_per_program: int = 1  # heads a kernel program serves: 1 | 2
    slab_rows: Optional[int] = None     # a diagonal block's row slabs


# The policy's constants, each from ``tools/tpu_flash_check.py
# --block-sweep`` on one v5e (chip run of PR 29; PERF.md section 6 has the
# table): one causal attention layer of 8,192 tokens in bf16, forward +
# backward ms, dense against the kernels over blocks of 256 to 2,048 and
# both backwards, at 16 heads of 64 with 1,024 / 2,048 / 4,096 keys and at
# Trinity-Mini's 32 heads of 128 over 4 KV heads (4,096 keys, window 2,048
# and none).
#
# Largest block, rows and keys: 1,024 x 1,024 is the fastest tile at every
# shape (heads of 64, 1,024 keys: 2.54 against 2.59 at 512 x 512 and 4.38 at
# 256 x 256; 4,096 keys: 5.33 / 6.37 / 13.03; heads of 128, window 2,048:
# 10.25 / 11.17); a longer side under the same 4 MB of f32 scores (512 x
# 2,048, 2,048 x 512) loses 18 to 42%. Mosaic accepts it for f32 inputs and
# heads of 32 to 512 (compiled for the v5e, not run). Measured again at keys
# of 192 beside values of 128 with a shared rope key (16 heads, 8,192 keys,
# chip run of PR 34: ``--block-sweep latent_8192``): 1,024 x 1,024 27.57
# against 28.63 at 512 x 1,024, 30.41 at 512 x 2,048, 32.41 at 512 x 512 and
# 32.75 at 1,024 x 512; 2,048 x 512 and larger Mosaic refuses there for VMEM.
#
# Those ms are of calls on separate ``[B, L, H, D]`` tensors and hold the
# ``[B, L, H, D]`` to ``[B x H, L, D]`` transposes around the kernels: at heads
# of 64 and 1,024 keys the two kernels alone were 0.57 + 1.05 of the 2.54 (the
# one-kernel backward since PR 35). A layer THROUGH ``attend``, from the fused
# ``qkv`` projection ``[8, 1024, 3072]`` to what the output projection reads
# and back to the projection's gradient (``--block-sweep gpt2m_1024_layer``,
# chip run of PR 37; PERF.md section 6): one head a program 0.973 forward and
# 2.334 forward + backward, of which the kernels are 0.573 + 1.047; two heads
# a program, read where the projection wrote them, 0.644 and 1.776 (kernels
# 0.573 + 1.047: the same MXU passes in half the programs): what is left
# around them, 0.16, is the three gradients laid side by side. That is
# :func:`_planned_heads`' one measurement; dense there: 1.32 and 3.96.
FLASH_BLOCK = 1024
# Rows of the slabs a diagonal block is walked in (:func:`_planned_slab`): a
# 1,024 block's causal half computed as 4 slabs of 256 rows against 256, 512,
# 768 and 1,024 keys, 10 of its 16 sub-tiles. On one v5e (chip run of PR 39,
# a traced step; PERF.md section 6) GPT-2-medium's two kernels took 29.3 ms
# a step at 1,024 keys against 38.8 with every block whole (``hvd_flash_bwd``
# 16.63 against 25.05, ``hvd_flash_fwd`` 12.69 against 13.75), and 82.8 against
# 92.7 at 4,096 keys.
FLASH_SLAB = 256
# Smallest block the kernels are chosen at: at 256 x 256 they lose to dense
# at 1,024 keys (4.38 against 3.95) and win by 2 to 5% at 2,048 and 4,096.
FLASH_MIN_BLOCK = 512
# Fewest keys: the shortest length measured, where the kernels win by 1.55
# times (2.54 against 3.95; 2.13 times at 2,048, 2.58 at 4,096, 2.83 at
# Trinity-Mini's shapes). Below it nothing was measured: dense.
FLASH_MIN_KEYS = 1024
# The backward where the one kernel is not chosen: the dQ and dK/dV kernels
# (``bwd_impl="pallas"``, also the one kernel's second reference in tests).
# The scan over key blocks, with its [B, H, L, block] f32 slabs, takes 1.8
# (1,024 keys) to 3.1 times (4,096) their forward + backward time at equal
# blocks and 3.4 times at heads of 128; it is kept as ``bwd_impl="scan"``,
# the kernels' reference in tests.
FLASH_BWD = "pallas"
# VMEM the split's kernels compile under at every shape above: Mosaic's
# default scoped limit on the v5e, which they never raise.
FLASH_SPLIT_VMEM = 16 << 20
# The one-kernel backward's VMEM budget (:func:`fused_bwd_vmem_bytes`): 16 MiB
# plus 65,536 queries of 128 in float32 and twice in bf16, the longest query
# side measured (``--block-sweep``, chip run of PR 35; PERF.md section 6 has
# the table). Device ms of the backward at blocks of 1,024 x 1,024, one
# kernel against dQ + dK/dV: 1.048 against 1.419 at GPT-2's q [8, 1024, 16,
# 64] (0.74); 2.533 / 3.466 at 4,096 keys; 4.650 / 6.542 and 5.020 / 6.924 at
# Trinity-Mini's window and full layers (0.71, 0.73); 14.106 / 18.983 at keys
# of 192 with the shared rope key over 8,192 (0.74); 4.157 / 5.672, 8.072 /
# 10.984 and 15.901 / 21.623 at 16,384, 32,768 and 65,536 queries of 128
# (0.73 to 0.74, sums of 32, 48 and 80 MiB). It won at every one of the 54
# pairs of blocks swept as well (0.76 to 0.88 of the split's forward +
# backward ms), so nothing but this budget selects the split; past it nothing
# was measured (131,072 queries of 128 would ask Mosaic for 144 of the v5e's
# 128 MiB).
FLASH_FUSED_VMEM_BUDGET = 80 << 20


def fused_bwd_vmem_bytes(seq_q: int, key_width: int, itemsize: int) -> int:
    """VMEM of the one-kernel backward for ``seq_q`` queries of ``key_width``
    (lanes of 128): what the split's kernels hold (:data:`FLASH_SPLIT_VMEM`)
    plus dQ for all of a (batch, head) program's rows, once in float32 (the
    sum) and twice in the gradient's type (the output block, which Pallas
    double-buffers). The plan compares it with
    :data:`FLASH_FUSED_VMEM_BUDGET`; the kernel asks Mosaic for it."""
    lanes = -(-key_width // 128) * 128
    return FLASH_SPLIT_VMEM + seq_q * lanes * (4 + 2 * itemsize)


def pair_vmem_bytes(itemsize: int) -> int:
    """VMEM a two-head program holds beyond a one-head program's, at blocks
    of :data:`FLASH_BLOCK`: the second head's two blocks of statistics, each
    a 128-lane float32 column and double-buffered, and each head's copies of
    three operand tiles with the other head's lanes zeroed (3.5 MiB in bf16,
    5 in float32; the float32 kernels pass the one-head limits by 1.1 to 3.4
    MiB: compiles for a described v5e, PR 37)."""
    return FLASH_BLOCK * 128 * (2 * 2 * 4 + 3 * 2 * itemsize)


def _planned_bwd(seq_q: int, key_width: int, dtype,
                 pin: Optional[str] = None) -> str:
    """The kernels' backward for a call's shapes: the caller's ``pin``, or
    (``None`` | ``"auto"``) one kernel where its dQ fits the budget and the
    split where it does not."""
    if pin not in (None, "auto"):
        return pin
    fits = fused_bwd_vmem_bytes(
        seq_q, key_width, jnp.dtype(dtype).itemsize) <= FLASH_FUSED_VMEM_BUDGET
    return "fused" if fits else FLASH_BWD


def _planned_slab(block_q: int, block_k: int, bwd: str, packed: bool,
                  pin: Optional[int] = None) -> Optional[int]:
    """The rows of a diagonal block's slabs (:func:`_diagonal_slabs`): the
    caller's ``pin`` (0: none), or :data:`FLASH_SLAB` where the kernels walk
    the packed causal grid (``packed``) with square blocks of two slabs or
    more and the one-kernel backward. ``None`` elsewhere: every step computes
    its whole square, masked where the mask is causal (the split's kernels
    and the scan keep that body, and are the slabs' references in tests)."""
    rows = FLASH_SLAB if pin is None else pin
    if not rows:
        return None
    if (packed and bwd == "fused" and block_q == block_k
            and block_q % rows == 0 and block_q >= 2 * rows):
        return rows
    if pin is not None:
        raise ValueError(
            f"row slabs of {pin} need the packed causal grid, square blocks "
            f"of two slabs or more and the one-kernel backward (packed="
            f"{packed}, blocks {block_q} x {block_k}, bwd_impl={bwd!r})")
    return None


def _planned_heads(seq_q: int, seq_k: int, heads: int, kv_heads: int,
                   head_dim, bwd: str, shared_key: bool = False,
                   q_offset: int = 0) -> int:
    """The heads a kernel program serves: two where they fill a 128-lane
    tile between them and the kernels can read it where a projection wrote it
    (keys and values both :data:`PAIR_WIDTH` wide, an even number of heads, a
    KV head a query head, no shared key, the causal square call with no
    offset, the one-kernel backward); one everywhere else."""
    widths = head_dim if isinstance(head_dim, tuple) else (head_dim,) * 2
    paired = (widths == (PAIR_WIDTH,) * 2 and heads % 2 == 0
              and kv_heads == heads and not shared_key and seq_q == seq_k
              and not q_offset and bwd == "fused")
    return 2 if paired else 1


def attention_plan(seq_q: int, seq_k: int, heads: int, kv_heads: int,
                   head_dim, window: Optional[int] = None,
                   dtype=jnp.bfloat16,
                   backend: Optional[str] = None, *,
                   shared_key: bool = False,
                   q_offset: int = 0) -> AttentionPlan:
    """Implementation, blocks, backward and heads a program of one causal
    attention call, from what is static about it. ``head_dim`` is the one
    width of queries, keys and values, or ``(keys' width, values' width)``
    where they differ; ``shared_key`` says that the keys' last columns are
    one vector a token for all heads, ``q_offset`` where the first query
    stands. ``backend`` defaults to JAX's own.

    The kernels run on a TPU, from :data:`FLASH_MIN_KEYS` keys on, where a
    block of at least :data:`FLASH_MIN_BLOCK` divides both lengths; the
    dense reference everywhere else: the CPU test platform would interpret
    the kernels, and below those sizes nothing was measured. The sweeps found
    no dependence on the number of heads, on grouping, on the widths (64,
    128, and keys of 192 beside values of 128), on a window or on the element
    type that would change a choice, so today the answer depends on the
    lengths alone; the other arguments are what a later measurement may key
    on without a new call site.

    ``slab_rows`` is how the kernels walk a diagonal block
    (:func:`_planned_slab`): in slabs of that many rows, each against the
    keys up to its own last row, with no mask on a block the causal edge
    does not cross; ``None`` (offsets, rectangular calls or blocks, blocks
    of fewer than two slabs, the split backward) computes every block's
    whole square under the mask.

    ``heads_per_program`` is the kernels' layout (:func:`_planned_heads`):
    at heads of 64 the ``[B, L, H, 64]`` to ``[B x H, L, 64]`` transposes
    around one-head programs are copies XLA folds nowhere (a head is half a
    lane row), a layer's cost as much as its forward kernel
    (``tools/tpu_flash_check.py --block-sweep``, chip run of PR 37; PERF.md
    section 6), so two heads share a program that reads the projections'
    layout by index map; at 128 the transposes fold into the projections
    and one head a program stays.
    """
    _kv_group(heads, kv_heads)
    del window
    key_width = head_dim[0] if isinstance(head_dim, tuple) else head_dim
    bwd = _planned_bwd(seq_q, key_width, dtype)
    paired = _planned_heads(seq_q, seq_k, heads, kv_heads, head_dim, bwd,
                            shared_key, q_offset)
    try:
        block_q, block_k = _planned_blocks(seq_q, seq_k, None, None)
    except ValueError:
        return AttentionPlan("dense", None, None, bwd, paired)
    if backend is None:
        backend = jax.default_backend()
    flash = (backend == "tpu" and seq_k >= FLASH_MIN_KEYS
             and min(block_q, block_k) >= FLASH_MIN_BLOCK)
    slab = _planned_slab(block_q, block_k, bwd,
                         _grid_truncates(True, seq_q, seq_k, q_offset, 0,
                                         None))
    return AttentionPlan("flash" if flash else "dense", block_q, block_k,
                         bwd, paired, slab)


def _planned_blocks(seq_q: int, seq_k: int, block_q: Optional[int],
                    block_k: Optional[int]):
    """A kernel call's blocks: the caller's, and the plan's for one left
    out (the pad-upstream error where no legal block divides a length)."""
    if block_q is None:
        block_q = _pick_block(FLASH_BLOCK, seq_q)
    if block_k is None:
        block_k = _pick_block(FLASH_BLOCK, seq_k)
    return block_q, block_k


# The attention calls of the program being traced, as gauges keyed by that
# program (the ``program`` of the ``hvd.spmd.dispatch`` span whose call
# traces it): program -> (id of that span, calls by implementation). A
# re-trace starts anew.
_traced: dict = {}


def attend(q, k=None, v=None, *, heads: Optional[int] = None,
           window: Optional[int] = None, q_offset: int = 0,
           impl: Optional[str] = None, scale: Optional[float] = None,
           k_shared=None, **flash_args):
    """Causal attention as a model's block calls it: q ``[B, L, H, Dk]``, k
    ``[B, L, G, Dk]``, v ``[B, L, G, Dv]``, scores times ``scale`` (``Dk **
    -0.5`` unless the caller says); with ``k_shared [B, L, Dr]`` ``k`` holds
    each head's own ``Dk - Dr`` key columns and ``k_shared`` the rest, the
    same for all heads. Or, with ``heads`` and no ``k`` and ``v``, ``q`` is a
    fused projection ``[B, L, 3 x heads x D]`` (q | k | v along its columns)
    taken whole, and the result is ``[B, L, heads x D]``, what the output
    projection reads: where the plan answers two heads a program the kernels
    read and write those arrays as they stand, and elsewhere the projection
    is split here. :func:`attention_plan` picks the implementation and the
    heads a program from the shapes unless ``impl`` (``"dense"`` |
    ``"flash"``) pins the first;
    ``flash_args`` go to :func:`flash_attention` (an A/B's ``truncate``,
    ``bwd_impl`` and ``slab``). Sets the gauges ``hvd.attn.flash_calls`` /
    ``.dense_calls`` (attention calls traced into the step's program, by
    implementation), ``.fused_bwd_calls`` (those of the kernels' calls whose
    backward is the one kernel), ``.paired_calls`` (those whose programs
    serve two heads), ``.diagonal_slab_calls`` (those whose kernels walk the
    diagonal blocks in row slabs), ``.block_q`` / ``.block_k`` (the
    kernels' blocks) and ``.slab_rows`` (the slabs' rows, 0 for none)."""
    whole_projection = k is None
    if whole_projection:
        if heads is None or v is not None or q.shape[-1] % (3 * heads):
            raise ValueError(
                f"a fused projection is [B, L, 3 x heads x D] with heads "
                f"given and no k or v, got {q.shape} and heads={heads}")
        kv_heads, widths = heads, (q.shape[-1] // (3 * heads),) * 2
    else:
        heads, kv_heads = q.shape[2], k.shape[2]
        widths = (q.shape[3], v.shape[3])
    seq = q.shape[1]
    if impl is None:
        # an offset mask is outside what the policy was measured on
        impl = "dense" if q_offset else attention_plan(
            seq, seq, heads, kv_heads, widths, window, q.dtype).impl
    if impl not in ("dense", "flash"):
        raise ValueError(f"impl must be dense|flash, got {impl!r}")
    bwd = _planned_bwd(seq, widths[0], q.dtype, flash_args.get("bwd_impl"))
    paired = impl == "flash" and _planned_heads(
        seq, seq, heads, kv_heads, widths, bwd, k_shared is not None,
        q_offset) == 2
    slab = None
    if impl == "flash":
        block_q, block_k = _planned_blocks(
            seq, seq, flash_args.get("block_q"), flash_args.get("block_k"))
        slab = _planned_slab(
            min(block_q, seq), min(block_k, seq), bwd,
            _grid_truncates(True, seq, seq, q_offset, 0,
                            flash_args.get("truncate")),
            flash_args.get("slab"))
    program, calls = timeline.program_tally(
        _traced, lambda: {"flash": 0, "dense": 0, "fused_bwd": 0,
                          "paired": 0, "diagonal_slab": 0})
    calls[impl] += 1
    calls["fused_bwd"] += impl == "flash" and bwd == "fused"
    calls["paired"] += paired
    calls["diagonal_slab"] += slab is not None
    for name, n in calls.items():
        timeline.gauge(f"hvd.attn.{name}_calls", n, key=program)
    if impl == "flash":
        timeline.gauge("hvd.attn.block_q", block_q, key=program)
        timeline.gauge("hvd.attn.block_k", block_k, key=program)
        timeline.gauge("hvd.attn.slab_rows", slab or 0, key=program)
    if paired:          # the projections' layout, read where it lies
        operands = (q,) if whole_projection else tuple(
            t.reshape(*t.shape[:2], -1) for t in (q, k, v))
        out = flash_attention(*operands, causal=True, scale=scale,
                              window=window, heads=heads, **flash_args)
        return out if whole_projection else out.reshape(q.shape)
    if whole_projection:
        q, k, v = (t.reshape(*t.shape[:2], heads, -1)
                   for t in jnp.split(q, 3, axis=-1))
    if impl == "dense":
        out = dot_product_attention(q, k, v, causal=True, scale=scale,
                                    q_offset=q_offset, window=window,
                                    k_shared=k_shared)
    else:
        out = flash_attention(q, k, v, causal=True, scale=scale,
                              q_offset=q_offset, window=window,
                              k_shared=k_shared, **flash_args)
    return out.reshape(*out.shape[:2], -1) if whole_projection else out


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret",
                                             "bwd_impl", "q_offset",
                                             "k_offset", "truncate",
                                             "window", "heads", "slab"))
def flash_attention(q, k=None, v=None, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    bwd_impl: Optional[str] = None,
                    q_offset: int = 0, k_offset: int = 0,
                    truncate: Optional[bool] = None,
                    window: Optional[int] = None, k_shared=None,
                    heads: Optional[int] = None,
                    slab: Optional[int] = None):
    """Pallas flash attention. Shapes q [B, L, H, Dk], k [B, L, G, Dk],
    v [B, L, G, Dv] -> [B, L, H, Dv]; ``G`` divides ``H`` and query head h
    reads KV head ``h // (H / G)`` (grouped-query attention; K and V are
    never repeated: the kernels' index maps pick the group's block). With
    ``k_shared [B, L, Dr]`` (needs ``G == H``) ``k`` is ``[B, L, H, Dk -
    Dr]``, the columns of each key that are the head's own, and ``k_shared``
    the last ``Dr``, one vector a token for all heads, read by index map
    too. ``scale`` defaults to ``Dk ** -0.5``.

    In this form a program of the kernels serves one (batch, head): q, k, v
    and dO are transposed to ``[B x H, L, D]`` before a kernel and its
    results back after it, copies that XLA folds into the projections where
    a head is 128 wide and writes out where it is 64. ``heads`` (static; an
    even number of heads of 64, a KV head a query head, no shared key, the
    one-kernel backward) selects the other form, **two heads a program**: q,
    k and v are ``[B, L, heads x 64]``, the layout a projection writes, or q
    alone is a fused projection ``[B, L, 3 x heads x 64]`` (q | k | v along
    the columns; ``k`` and ``v`` None), and the result is ``[B, L, heads x
    64]``. A program reads the 128-lane column block that holds its two
    heads by index map from those arrays and writes its block of the result,
    and of dQ, dK and dV, the same way, so no transpose or slice lies between
    a projection and a kernel. Inside, head ``h``'s products take one
    operand with the other head's lanes zeroed: a contraction over 128 lanes
    of which 64 are zero is the MXU pass a 64-wide contraction costs, and the
    two heads' parts of a 128-lane result add up with exact zeros, so o, dQ,
    dK and dV equal the one-head programs'. :func:`attend` chooses the form
    from the shapes (:func:`attention_plan`).

    ``window`` (static; plain causal square attention only) lets query i
    see keys j with ``0 <= i - j < window``: the mask is applied inside
    every live block the band's edges cross (and in every live block where
    the plan answers no slab), and blocks wholly outside the band never
    occupy a step of the packed grid (or skip their compute on the full
    one).

    Sequence lengths must be multiples of the block sizes (pad upstream).
    Block sizes, the backward (``bwd_impl`` None or ``"auto"``) and the rows
    of a diagonal block's slabs (``slab`` None) default to
    :func:`attention_plan`'s, measured on the v5e; pass explicit values to
    override (``"fused"`` | ``"pallas"``, the split | ``"scan"``; ``slab``
    0 for none: every block's whole square under the mask).
    ``interpret`` defaults to the platform's: compiled on a TPU,
    interpreted on the CPU test platform.

    ``q_offset``/``k_offset`` (static) are the global positions of the
    first query/key token, matching :func:`dot_product_attention` — so
    sequence-parallel shims can call the kernel on a shard and keep the
    causal mask globally aligned. Plain causal square attention (equal
    offsets, Lq == Lk) executes a PACKED at-or-below-diagonal grid:
    ~(n+1)/2n of the full grid's steps, eliminating the dead half's K/V
    DMA bytes along with its loop overhead. Offset/rectangular causal
    keeps the full grid with per-block compute skips, and requires
    q_offset >= k_offset (every query row must see at least one key —
    rows with none have no defined softmax). ``truncate=False``
    forces the full grid (the truncated-vs-full A/B lanes);
    ``truncate=True`` asserts eligibility; the accounting twin is
    :func:`flash_grid_info`.

    Differentiable: the backward is one Pallas kernel (``hvd_flash_bwd``:
    dQ, dK and dV from one walk over the score blocks, five products a
    pair, dQ summed in a float32 ``[Lq, Dk]`` scratch that stays in VMEM a
    (batch, head) program) where that scratch fits
    :data:`FLASH_FUSED_VMEM_BUDGET`, and two elsewhere (the
    FlashAttention-2 dQ / dK+dV split, seven products a pair, O(block)
    VMEM per program), recomputing scores blockwise against the forward's
    persisted logsumexp — the [Lq, Lk] matrix is never materialized in
    either pass; every backward kernel rides the truncated grid (k-major
    for the one kernel and for dK/dV, whose dead region is the symmetric
    above-diagonal half over the q axis); gradient exactness vs the dense
    reference, and the one kernel's equality with the split, are pinned in
    tests/test_parallel.py::TestFlashAttention."""
    width = q.shape[-1] if heads is None else PAIR_WIDTH
    seq_k = (q if k is None else k).shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(width)
    if interpret is None:
        interpret = pallas_interpret()
    block_q, block_k = _planned_blocks(q.shape[1], seq_k, block_q, block_k)
    bwd_impl = _planned_bwd(q.shape[1], width, q.dtype, bwd_impl)
    if bwd_impl not in ("scan", "pallas", "fused"):
        raise ValueError(f"bwd_impl must be auto|scan|pallas|fused, "
                         f"got {bwd_impl!r}")
    if causal and q_offset < k_offset:
        # Query rows before the first key have NO unmasked key: their
        # softmax is undefined, and the kernels' 0-output would
        # silently diverge from the dense reference's degenerate
        # uniform-over-NEG_INF rows. A block-parallel caller whose
        # geometry straddles the diagonal this way needs partial-block
        # lse merging (the ring recurrence), not plain flash.
        raise ValueError(
            f"causal flash_attention requires q_offset >= k_offset "
            f"(got {q_offset} < {k_offset}): rows with no visible key "
            f"have no defined softmax")
    if heads is not None:
        whole = k is None and v is None     # one fused projection: q | k | v
        operands = (q,) if whole else (q, k, v)
        columns = heads * PAIR_WIDTH * (3 if whole else 1)
        if (heads < 2 or heads % 2 or k_shared is not None
                or bwd_impl != "fused"
                or any(t is None or t.shape[2:] != (columns,)
                       for t in operands)):
            raise ValueError(
                f"two heads a program need an even number of heads of "
                f"{PAIR_WIDTH} in the projections' layout (q, k, v [B, L, "
                f"heads x {PAIR_WIDTH}] or one fused [B, L, 3 x heads x "
                f"{PAIR_WIDTH}]), no shared key and the one-kernel backward; "
                f"got heads={heads}, shapes "
                f"{[getattr(t, 'shape', None) for t in (q, k, v)]}, "
                f"bwd_impl={bwd_impl!r}")
    else:
        _kv_group(q.shape[2], k.shape[2])
        own = q.shape[-1] - (0 if k_shared is None else k_shared.shape[-1])
        if k.shape[-1] != own:
            raise ValueError(f"queries of {q.shape[-1]} need keys of {own} "
                             f"a head, got k {k.shape}")
        if k_shared is not None and k.shape[2] != q.shape[2]:
            raise ValueError("a shared key needs a key head a query head, "
                             f"got {k.shape[2]} under {q.shape[2]}")
    if window is not None:
        if not (causal and q.shape[1] == seq_k
                and q_offset == k_offset) or window < 1:
            raise ValueError(
                f"a window needs plain causal square attention and a width "
                f"of at least 1 (causal={causal}, Lq={q.shape[1]}, "
                f"Lk={seq_k}, q_offset={q_offset}, "
                f"k_offset={k_offset}, window={window}): elsewhere a row "
                f"could be left with no key at all")
        if window >= seq_k:
            window = None              # the band is the whole triangle
    slab = _planned_slab(
        min(block_q, q.shape[1]), min(block_k, seq_k), bwd_impl,
        _grid_truncates(causal, q.shape[1], seq_k, q_offset, k_offset,
                        truncate), slab)
    return _flash(q, k, v, k_shared, causal, float(scale), block_q, block_k,
                  interpret, bwd_impl, int(q_offset), int(k_offset),
                  truncate, window, heads, slab)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15))
def _flash(q, k, v, k_shared, causal, scale, block_q, block_k, interpret,
           bwd_impl, q_offset, k_offset, truncate, window=None, heads=None,
           slab=None):
    out, _ = _flash_forward(q, k, v, k_shared, causal, scale, block_q,
                            block_k, interpret, q_offset, k_offset, truncate,
                            window, heads, slab)
    return out


# The kernels' names, as a device profile shows them (an instruction is
# named after its kernel): the benchmark's readers find them by these.
FWD_KERNEL = "hvd_flash_fwd"
DQ_KERNEL = "hvd_flash_dq"
DKV_KERNEL = "hvd_flash_dkv"
BWD_KERNEL = "hvd_flash_bwd"


# Two heads a program: the width at which two heads fill a 128-lane tile.
PAIR_WIDTH = 64


def _paired_operands(q, k, v, heads: int):
    """q, k and v of a two-heads-a-program call as the arrays the kernels
    read and the column block of 128 at which each one's first pair of heads
    stands: three ``[B, L, H x 64]`` arrays, or (``k`` and ``v`` None) the
    fused projection ``[B, L, 3 x H x 64]`` three times, q | k | v along its
    columns."""
    if k is None:
        return (q, q, q), (0, heads // 2, heads)
    return (q, k, v), (0, 0, 0)


def _block_specs(block_q: int, block_k: int, seq_q: int, at_q, at_k,
                 pairs: Optional[int] = None):
    """The BlockSpec makers of one kernel call: ``rows(width, col)`` for a
    block of query rows, ``keys(width, per, col)`` for a block of keys,
    ``whole(width)`` for all of a program's query rows, ``stats()`` for a
    block of per-row statistics. ``at_q`` / ``at_k`` give the q-block and the
    k-block of a grid step (off the step tables or off the grid's axes).

    One head a program (``pairs`` None): the operands are ``[B x H, L,
    width]`` and program ``bh`` reads row ``bh`` (``bh // per`` where ``per``
    query heads share a row of keys). Two heads a program: the operands are
    ``[B, L, n x 128]`` as a projection writes them, and program ``p`` of the
    ``pairs`` a batch row reads column block ``col + p % pairs`` of row ``p
    // pairs``: no copy lies between the projection and the kernel. The
    statistics are ``[B x H, L, 1]`` in both; a pair's program takes the two
    rows ``2p`` and ``2p + 1`` of them."""
    from jax.experimental import pallas as pl

    if pairs is None:
        def rows(width, col=0):
            return pl.BlockSpec((None, block_q, width),
                                lambda bh, *g: (bh, at_q(bh, *g), 0))

        def keys(width, per=None, col=0):
            if per is None:
                return pl.BlockSpec((None, block_k, width),
                                    lambda bh, *g: (bh, at_k(bh, *g), 0))
            return pl.BlockSpec((None, block_k, width),
                                lambda bh, *g: (bh // per, at_k(bh, *g), 0))

        def whole(width):
            return pl.BlockSpec((None, seq_q, width),
                                lambda bh, *g: (bh, 0, 0))

        return rows, keys, whole, lambda: rows(1)

    def tile(block, at, col):
        return pl.BlockSpec(
            (None, block, 128),
            lambda p, *g: (p // pairs, at(p, *g), col + p % pairs))

    return (lambda width, col=0: tile(block_q, at_q, col),
            lambda width, per=None, col=0: tile(block_k, at_k, col),
            lambda width: pl.BlockSpec(
                (None, seq_q, 128), lambda p, *g: (p // pairs, 0, p % pairs)),
            lambda: pl.BlockSpec((2, block_q, 1),
                                 lambda p, *g: (p, at_q(p, *g), 0)))


def _flash_forward(q, k, v, k_shared, causal, scale, block_q, block_k,
                   interpret, q_offset=0, k_offset=0, truncate=None,
                   window=None, heads=None, slab=None):
    """Returns (out, lse [B, H, Lq]). One head a program (``heads`` None): q
    ``[B, Lq, H, D]``, out ``[B, Lq, H, Dv]``; the operands are transposed
    to ``[B x H, L, D]`` around the call. Two heads a program (``heads`` the
    number of heads, each :data:`PAIR_WIDTH` wide): q, k, v and out are the
    projections' ``[B, L, H x 64]`` (or q the fused ``[B, L, 3 x H x 64]``,
    k and v None) and the kernel reads and writes them as they stand."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if heads is None:
        B, Lq, H, D = q.shape           # D: the queries' width, the keys'
        Lk, G = k.shape[1], k.shape[2]
        Dn, Dv = k.shape[-1], v.shape[-1]   # a head's own key columns; values
    else:
        (q, k, v), cols = _paired_operands(q, k, v, heads)
        B, Lq, Lk, H, G = q.shape[0], q.shape[1], k.shape[1], heads, heads
        D = Dn = Dv = PAIR_WIDTH
    shared = k_shared is not None
    rep = _kv_group(H, G)      # program bh = b*H + h reads KV row bh // rep
    block_q = min(block_q, Lq)
    block_k = min(block_k, Lk)
    assert Lq % block_q == 0 and Lk % block_k == 0, (Lq, Lk, block_q, block_k)
    delta = q_offset - k_offset
    truncated = _grid_truncates(causal, Lq, Lk, q_offset, k_offset, truncate)

    if heads is None:
        # Collapse (B, H) into the grid's first axis; put seq minor-most for
        # contiguous VMEM tiles.
        qr = q.transpose(0, 2, 1, 3).reshape(B * H, Lq, D)
        kr = k.transpose(0, 2, 1, 3).reshape(B * G, Lk, Dn)
        vr = v.transpose(0, 2, 1, 3).reshape(B * G, Lk, Dv)
        programs, per, cols = B * H, 1, (0, 0, 0)
    else:
        qr, kr, vr = q, k, v
        programs, per = B * H // 2, 2   # a program: two heads of a batch row
    keys = (kr, k_shared) if shared else (kr,)  # k_shared is [B, Lk, Dr]

    n_qblocks = Lq // block_q
    n_kblocks = Lk // block_k
    out_shape = [
        jax.ShapeDtypeStruct((B * H, Lq, Dv) if heads is None
                             else (B, Lq, H * Dv), q.dtype),
        jax.ShapeDtypeStruct((B * H, Lq, 1), jnp.float32),
    ]
    stat = (block_q, 1) if heads is None else (per, block_q, 1)
    scratch = [
        pltpu.VMEM(stat, jnp.float32),                  # running max m
        pltpu.VMEM(stat, jnp.float32),                  # running sum l
        pltpu.VMEM((block_q, per * Dv), jnp.float32),   # output accumulator
    ]
    kernel = functools.partial(
        _flash_kernel, block_k=block_k, n_kblocks=n_kblocks, causal=causal,
        scale=scale, block_q=block_q, delta=0 if truncated else delta,
        packed=truncated, window=window, shared=shared, heads=per,
        slab=slab)
    if truncated:
        at_q = lambda bh, t, qi, kb: qi[t]                  # noqa: E731
        at_k = lambda bh, t, qi, kb: kb[t]                  # noqa: E731
    else:
        at_q = lambda bh, qb, kb: qb                        # noqa: E731
        at_k = lambda bh, qb, kb: kb                        # noqa: E731
    rows, keys_of, _, stats = _block_specs(
        block_q, block_k, Lq, at_q, at_k,
        None if heads is None else H // 2)
    in_specs = [rows(D, cols[0]), keys_of(Dn, rep, cols[1])] \
        + [keys_of(D - Dn, H)] * shared + [keys_of(Dv, rep, cols[2])]
    # The lse is a [block_q, 1] column per head: the statistics' native
    # layout (see the kernel's Mosaic-discipline note); the trailing
    # singleton is dropped OUTSIDE the kernel where a relayout is just an
    # XLA reshape.
    out_specs = [rows(Dv), stats()]
    params = {} if heads is None else {
        "vmem_limit_bytes": FLASH_SPLIT_VMEM + pair_vmem_bytes(
            q.dtype.itemsize)}
    if truncated:
        qi_tab, kb_tab = _causal_step_tables(n_qblocks, n_kblocks,
                                             block_q, block_k,
                                             window=window)
        # The STEP axis enumerates only the live at-or-below-diagonal
        # (q-block, k-block) pairs — ~(n+1)/2n of the full causal grid.
        # Still sequential ("arbitrary") so the scratch-carried softmax
        # state is legal, and Mosaic double-buffers exactly the
        # [block_k, D] K/V tile DMAs the mask actually needs; the
        # block indices come off the scalar-prefetched tables.
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(programs, int(qi_tab.size)),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch,
        )
        out, lse = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"), **params),
            interpret=interpret, name=FWD_KERNEL,
        )(jnp.asarray(qi_tab), jnp.asarray(kb_tab), qr, *keys, vr)
    else:
        out, lse = pl.pallas_call(
            kernel,
            # K blocks ride the grid's INNERMOST axis: sequential
            # ("arbitrary") so the scratch-carried softmax state is
            # legal, while Mosaic double-buffers the [block_k, D] K/V
            # tile DMAs.
            grid=(programs, n_qblocks, n_kblocks),
            in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                **params),
            interpret=interpret, name=FWD_KERNEL,
        )(qr, *keys, vr)
    if heads is None:
        out = out.reshape(B, H, Lq, Dv).transpose(0, 2, 1, 3)
    return out, lse.reshape(B, H, Lq)


def _flash_fwd_vjp(q, k, v, k_shared, causal, scale, block_q, block_k,
                   interpret, bwd_impl, q_offset, k_offset, truncate,
                   window=None, heads=None, slab=None):
    o, lse = _flash_forward(q, k, v, k_shared, causal, scale, block_q,
                            block_k, interpret, q_offset, k_offset, truncate,
                            window, heads, slab)
    return o, (q, k, v, k_shared, o, lse)


def _flash_bwd_dq_kernel(*refs, causal: bool, scale: float, block_q: int,
                         block_k: int, n_kblocks: int, delta: int,
                         packed: bool, window: Optional[int] = None,
                         shared: bool = False):
    """dQ: full grid (batch*head, q-block, K-BLOCK stream) or the packed
    q-major causal grid (batch*head, STEP) — same layout split as
    :func:`_flash_kernel`. Standard FlashAttention-2 recurrence against
    the forward's persisted logsumexp:
        P_ij = exp(S_ij - lse_i);  dS_ij = P_ij * (dO_i V_j^T - D_i)
        dQ_i = sum_j dS_ij K_j * scale
    The k axis rides the grid (sequential) with the dQ accumulator in
    VMEM scratch — same O(block) VMEM shape as the forward kernel."""
    from jax.experimental import pallas as pl

    if packed:
        qi_tab, kb_tab = refs[:2]
        refs = refs[2:]
        t = pl.program_id(1)
        qi = qi_tab[t]
        kb = kb_tab[t]
    else:
        qi = pl.program_id(1)
        kb = pl.program_id(2)
    q_ref, k_ref = refs[:2]
    ks_ref = refs[2] if shared else None
    v_ref, do_ref, lse_ref, d_ref, dq_ref, dq_scr = refs[2 + shared:]

    at_first, at_last = _kblock_span(qi, kb, block_q, block_k, n_kblocks,
                                     window, packed)

    @pl.when(at_first)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    def _compute():
        # Input-dtype matmuls, f32 accumulation (see _block_scores).
        q = q_ref[...]
        k_blk = k_ref[...]
        v_blk = v_ref[...]
        do_blk = do_ref[...]
        s = _block_scores(q, k_blk, ks_ref, scale)
        if causal:
            q_pos = delta + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(_band_mask(q_pos, k_pos, window), s, NEG_INF)
        p = jnp.exp(s - lse_ref[...])                    # [bq, bk]
        dp = jnp.dot(do_blk, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - d_ref[...])
        if ks_ref is None:
            dq_scr[...] += jnp.dot(ds.astype(k_blk.dtype), k_blk,
                                   preferred_element_type=jnp.float32) * scale
        else:                   # the query's own columns, then the shared
            own, ds = k_blk.shape[-1], ds.astype(k_blk.dtype)
            dq_scr[:, :own] += jnp.dot(
                ds, k_blk, preferred_element_type=jnp.float32) * scale
            dq_scr[:, own:] += jnp.dot(
                ds, ks_ref[...], preferred_element_type=jnp.float32) * scale

    if causal and not packed:
        pl.when(_block_live(qi, kb, block_q, block_k, delta,
                            window))(_compute)
    else:
        _compute()  # packed grids enumerate live steps only

    @pl.when(at_last)
    def _finalize():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(*refs, causal: bool, scale: float, block_q: int,
                          block_k: int, n_qblocks: int, delta: int,
                          packed: bool, window: Optional[int] = None,
                          shared: bool = False, fused: bool = False,
                          n_kblocks: int = 0, heads: int = 1,
                          slab: Optional[int] = None):
    """dK/dV: full grid (batch*head, k-block, Q-BLOCK stream) or the
    packed K-MAJOR causal grid — transposing the dQ kernel's roles, so
    the truncated region is the symmetric above-diagonal half over the
    q axis (each k-block's stream starts at its diagonal q-block):
        dV_j = sum_i P_ij^T dO_i;  dK_j = sum_i dS_ij^T Q_i * scale
    With a shared key its gradient comes out a query head beside dK (the
    query's last columns' part), to be summed over heads outside.

    ``fused`` makes it the whole backward pass (:data:`BWD_KERNEL`): the
    same walk also adds ``dS_ij K_j * scale`` into q-block i's rows of a
    float32 ``[Lq, Dk]`` scratch that stays in VMEM for the whole (batch,
    head) program, so ``S``, the mask, ``P``, ``dP`` and ``dS`` are
    computed once a pair: five products where the two kernels run seven.
    K-blocks ascend on the k-major walk, so a q-block's rows are summed in
    the order the dQ kernel sums them; they are zeroed at the q-block's
    first k-block and cast into the ``[Lq, Dk]`` output block (whose index
    is constant over the program, so it leaves VMEM once) at its last: the
    dQ kernel's two conditions.

    ``heads`` (with ``fused``) is :func:`_flash_kernel`'s: at 2 every tile
    holds two heads of 64 side by side and the five products run once a
    head, each with one operand masked to the head's lanes (``q_h k^T``,
    ``dO_h v^T``, ``p^T dO_h``, ``ds^T q_h``, ``ds k_h``), so what a head
    adds to a 128-lane sum is zero outside its own columns. The last input
    is then no column of ``D = rowsum(dO . O)`` but the rows of ``O`` where
    the forward wrote them, and the kernel sums ``dO . O`` over a head's
    lanes itself: outside a kernel a sum over 64 of a row's columns is a
    relayout of the float32 products.

    ``slab`` (with ``fused``) is :func:`_flash_kernel`'s: in the diagonal
    pair slab ``r`` of the q-block adds ``p_r^T dO_r`` and ``ds_r^T q_r``
    into the k-block's first ``(r + 1) x slab`` rows of ``dV`` and ``dK``
    and ``ds_r k`` into its own rows of ``dQ``; a pair the causal edge does
    not cross applies no mask."""
    from jax.experimental import pallas as pl

    if packed:
        qi_tab, kb_tab = refs[:2]
        refs = refs[2:]
        t = pl.program_id(1)
        qi = qi_tab[t]
        kb = kb_tab[t]
        # First live q-block of this k-block's stream: the diagonal
        # (matches _causal_step_tables' k-major start).
        first_qi = (kb * block_k) // block_q
        last_qi = _last_qblock(kb, block_q, block_k, n_qblocks, window,
                               jnp.minimum)
    else:
        kb = pl.program_id(1)
        qi = pl.program_id(2)
        first_qi = 0
        last_qi = n_qblocks - 1
    q_ref, k_ref = refs[:2]
    ks_ref = refs[2] if shared else None
    v_ref, do_ref, lse_ref, d_ref = refs[2 + shared:6 + shared]
    outs = list(refs[6 + shared:])
    if fused:       # outputs (dq, dk, [dks], dv), then their scratch
        dq_ref, dq_scr = outs.pop(0), outs.pop(2 + shared)
        rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
    if shared:
        dk_ref, dks_ref, dv_ref, dk_scr, dks_scr, dv_scr = outs
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = outs

    @pl.when(qi == first_qi)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)
        if shared:
            dks_scr[...] = jnp.zeros(dks_scr.shape, jnp.float32)

    if fused:
        at_first, at_last = _kblock_span(qi, kb, block_q, block_k, n_kblocks,
                                         window, packed)

        @pl.when(at_first)
        def _init_dq():
            dq_scr[rows, :] = jnp.zeros((block_q, dq_scr.shape[-1]),
                                        jnp.float32)

    def _pair(q_ref, k_ref, ks_ref, v_ref, do_ref, dk_scr, dks_scr, dv_scr,
              rows, band, part=None):
        """The products of the query rows ``q_ref`` holds (``rows`` of dQ;
        ``part`` of the q-block's statistics, all where None) against the
        keys ``k_ref`` holds (the rows of ``dk_scr``, ``dks_scr`` and
        ``dv_scr`` given): the whole pair's refs, or views of a slab's.
        ``band()`` is the mask of their scores and the first column it
        covers (:func:`_masked`), ``None`` where they see every key."""
        # Input-dtype matmuls, f32 accumulation (see _block_scores).
        q = q_ref[...]
        k_blk = k_ref[...]
        v_blk = v_ref[...]
        do_blk = do_ref[...]

        def d_rows():
            return d_ref[...] if part is None else d_ref[part, :]

        seen = None
        if heads > 1:       # dO . O, a head's lanes of which sum to its D
            do_o = do_blk.astype(jnp.float32) * d_rows().astype(jnp.float32)
        for h in range(heads):
            at = (h,) if heads > 1 else (Ellipsis,)     # head h's statistics
            if part is not None:
                at = (h, part) if heads > 1 else (part,)
            q_h, do_h = _own_lanes(q, heads, h), _own_lanes(do_blk, heads, h)
            s = _block_scores(q_h, k_blk, ks_ref, scale)
            if band is not None:
                if seen is None:            # one mask for the tile's heads
                    seen, first = band()
                s = _masked(s, seen, first)
            p = jnp.exp(s - lse_ref[at])                     # [bq, bk]
            dv_scr[...] += jnp.dot(p.T.astype(do_blk.dtype), do_h,
                                   preferred_element_type=jnp.float32)
            dp = jnp.dot(do_h, v_blk.T, preferred_element_type=jnp.float32)
            d = d_rows() if heads == 1 else jnp.sum(
                _own_lanes(do_o, heads, h), axis=-1, keepdims=True)
            ds = p * (dp - d)
            if ks_ref is None:
                dk_scr[...] += jnp.dot(
                    ds.T.astype(q.dtype), q_h,
                    preferred_element_type=jnp.float32) * scale
            else:
                own, ds_t = k_blk.shape[-1], ds.T.astype(q.dtype)
                dk_scr[...] += jnp.dot(
                    ds_t, q[:, :own],
                    preferred_element_type=jnp.float32) * scale
                dks_scr[...] += jnp.dot(
                    ds_t, q[:, own:],
                    preferred_element_type=jnp.float32) * scale
            if not fused:
                return
            ds = ds.astype(k_blk.dtype)     # the dQ kernel's products
            if ks_ref is None:
                dq_scr[rows, :] += jnp.dot(
                    ds, _own_lanes(k_blk, heads, h),
                    preferred_element_type=jnp.float32) * scale
            else:
                dq_scr[rows, :own] += jnp.dot(
                    ds, k_blk, preferred_element_type=jnp.float32) * scale
                dq_scr[rows, own:] += jnp.dot(
                    ds, ks_ref[...],
                    preferred_element_type=jnp.float32) * scale

    def _band():
        q_pos = delta + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        return _band_mask(q_pos, k_pos, window), 0

    def _compute(band=_band if causal else None):
        _pair(q_ref, k_ref, ks_ref, v_ref, do_ref, dk_scr,
              dks_scr if shared else None, dv_scr, rows if fused else None,
              band)

    if causal and not packed:
        # Q-blocks fully ABOVE the diagonal (every q_pos < every k_pos),
        # or wholly past the window, contribute nothing to this k-block.
        pl.when(_block_live(qi, kb, block_q, block_k, delta,
                            window))(_compute)
    elif slab is None:
        _compute()  # packed grids enumerate live steps only
    else:
        assert fused, "row slabs: the one-kernel backward only"

        @pl.when(qi == kb)
        def _diagonal():
            for part, keys, band in _diagonal_slabs(block_q, slab, window):
                q_rows = pl.ds(pl.multiple_of(
                    qi * block_q + part.start, slab), slab)
                _pair(q_ref.at[part], k_ref.at[keys],
                      None if ks_ref is None else ks_ref.at[keys],
                      v_ref.at[keys], do_ref.at[part], dk_scr.at[keys],
                      dks_scr.at[keys] if shared else None, dv_scr.at[keys],
                      q_rows, band, part)

        interior = _interior(qi, kb, block_q, window)
        pl.when(interior)(functools.partial(_compute, None))
        if window is not None:      # the blocks the window's edge crosses
            pl.when((qi != kb) & ~interior)(_compute)

    @pl.when(qi == last_qi)
    def _finalize():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)
        if shared:
            dks_ref[...] = dks_scr[...].astype(dks_ref.dtype)

    if fused:
        @pl.when(at_last)
        def _finalize_dq():
            dq_ref[rows, :] = dq_scr[rows, :].astype(dq_ref.dtype)


def _flash_bwd_scan(causal, scale, block_q, block_k, interpret,
                    q_offset, k_offset, truncate, window, res, do):
    """XLA lax.scan backward (``bwd_impl="scan"``; the kernel split's
    reference in tests): one batched einsum pass per key block computing
    dq/dk/dv together over [B, H, Lq, block_k] f32 slabs, which are HBM
    round trips a block step: on the v5e it takes 1.8 to 3.4 times the
    kernel split's time at every length measured (:data:`FLASH_BWD`).
    Already grid-truncated by construction: the causal scan walks only the
    k-blocks at or below the last query row's diagonal (``truncate``
    is accepted for signature parity and ignored). Grouped K and V are
    repeated to the query heads here (the slabs are per query head anyway)
    and a ``window`` only masks: the kernel split is the path that skips."""
    del truncate  # no grid to truncate: the scan bound below early-exits
    q, k_own, v, k_shared, o, lse = res
    k = _with_shared_key(k_own, k_shared)       # written out here: a slab
    B, Lq, H, D = q.shape
    Lk, G, Dv = k.shape[1], k.shape[2], v.shape[-1]
    rep = _kv_group(H, G)
    if rep > 1:
        k, v = (jnp.repeat(t, rep, axis=2) for t in (k, v))
    bk = min(block_k, Lk)
    nkb = Lk // bk
    delta = q_offset - k_offset
    if causal:
        # Keys past the last query row's global position are dead for
        # every row; at least one block stays so the scan is non-empty.
        nkb_live = min(nkb, max(0, delta + Lq - 1) // bk + 1)
        nkb_live = max(1, nkb_live)
    else:
        nkb_live = nkb
    # Einsums run in the input dtype with f32 accumulation
    # (preferred_element_type) — bf16 inputs keep the MXU's native
    # path; f32 test inputs keep CI exactness. Softmax stats stay f32.
    f32 = jnp.float32
    d_row = jnp.sum(do.astype(f32) * o.astype(f32), axis=-1)  # [B, Lq, H]
    d_row = d_row.transpose(0, 2, 1)                           # [B, H, Lq]
    q_pos = delta + jnp.arange(Lq)[:, None]

    def bwd_step(dq, jb):
        kb = jax.lax.dynamic_slice_in_dim(k, jb * bk, bk, 1)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kb,
                       preferred_element_type=f32) * scale
        if causal:
            k_pos = jb * bk + jnp.arange(bk)[None, :]
            s = jnp.where(_band_mask(q_pos, k_pos, window)[None, None],
                          s, NEG_INF)
        vb = jax.lax.dynamic_slice_in_dim(v, jb * bk, bk, 1)
        p = jnp.exp(s - lse[..., None])                     # [B,H,Lq,bk]
        dp = jnp.einsum("bqhd,bkhd->bhqk", do, vb,
                        preferred_element_type=f32)
        ds = p * (dp - d_row[..., None])
        dq = dq + jnp.einsum("bhqk,bkhd->bqhd", ds.astype(k.dtype), kb,
                             preferred_element_type=f32) * scale
        dkb = jnp.einsum("bhqk,bqhd->bkhd", ds.astype(q.dtype), q,
                         preferred_element_type=f32) * scale
        dvb = jnp.einsum("bhqk,bqhd->bkhd", p.astype(do.dtype), do,
                         preferred_element_type=f32)
        return dq, (dkb, dvb)

    dq, (dks, dvs) = jax.lax.scan(
        bwd_step, jnp.zeros(q.shape, jnp.float32), jnp.arange(nkb_live))
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(B, nkb_live * bk, H, D)
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(B, nkb_live * bk, H, Dv)
    if nkb_live < nkb:
        pad = [(0, 0), (0, Lk - nkb_live * bk), (0, 0), (0, 0)]
        dk = jnp.pad(dk, pad)
        dv = jnp.pad(dv, pad)
    if rep > 1:
        dk, dv = (t.reshape(B, Lk, G, rep, t.shape[-1]).sum(3)
                  for t in (dk, dv))
    dks = None
    if k_shared is not None:    # the shared columns' part, over the heads
        own = k_own.shape[-1]
        dk, dks = dk[..., :own], dk[..., own:].sum(2).astype(k_shared.dtype)
    return (dq.astype(q.dtype), dk.astype(k_own.dtype), dv.astype(v.dtype),
            dks)


def _flash_bwd_pallas(causal, scale, block_q, block_k, interpret,
                      q_offset, k_offset, truncate, window, res, do,
                      fused=False, heads=None, slab=None):
    """Flash backward as one Pallas kernel (``fused``: dQ, dK and dV from
    one walk over the k-major grid, dQ summed in a float32 ``[Lq, Dk]``
    scratch that stays in VMEM a (batch, head) program; see
    :func:`_flash_bwd_dkv_kernel`) or as two (the FlashAttention-2 split):
    a dQ kernel streaming k-blocks and a dK/dV kernel streaming
    q-blocks, both against the forward's persisted logsumexp and the
    precomputed row dot D_i = rowsum(dO_i * O_i). The score matrix is
    never materialized; VMEM is O(block) per program, so the backward
    scales to the same contexts the streamed forward unlocked (the
    prior lax.scan backward materialized [B, H, Lq, block_k] slabs in
    HBM per step — 2 GB at seq 16k — and serialized the k-block walk).
    On the causal square path both kernels ride the PACKED grid of
    :func:`_causal_step_tables` (q-major for dQ, k-major for dK/dV), so
    the dead half of each grid — ~2x the K/V and Q/dO bytes actually
    needed — is never DMA'd. For causal rectangular/offset Lq != Lk the
    grids stay full and blocks entirely on the masked side of the
    diagonal skip their compute only. With grouped K and V (``G`` KV heads
    under ``H`` query heads) both kernels read the group's K/V block by
    index map; dK/dV come out a query head, in float32, and the group's
    are added up outside the kernel.

    ``heads`` (the one kernel only) is :func:`_flash_forward`'s: q, k, v, o
    and dO arrive ``[B, L, H x 64]`` (or q the fused ``[B, L, 3 x H x 64]``)
    and are read as they stand, two heads a program; dQ, dK and dV are
    written in that layout, and for the fused projection laid side by side
    into its one gradient."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q, k, v, k_shared, o, lse = res
    whole_projection = k is None
    if heads is None:
        B, Lq, H, D = q.shape
        Lk, G = k.shape[1], k.shape[2]
        Dn, Dv = k.shape[-1], v.shape[-1]
    else:
        assert fused, "two heads a program: the one-kernel backward only"
        (q, k, v), cols = _paired_operands(q, k, v, heads)
        B, Lq, Lk, H, G = q.shape[0], q.shape[1], k.shape[1], heads, heads
        D = Dn = Dv = PAIR_WIDTH
    shared = k_shared is not None
    rep = _kv_group(H, G)
    bq = min(block_q, Lq)
    bk = min(block_k, Lk)
    assert Lq % bq == 0 and Lk % bk == 0, (Lq, Lk, bq, bk)
    nqb, nkb = Lq // bq, Lk // bk
    delta = q_offset - k_offset
    truncated = _grid_truncates(causal, Lq, Lk, q_offset, k_offset, truncate)

    # lse arrives [B, H, Lq]; D_i rowsum in fp32. Both as [bh, Lq, 1]
    # columns — the statistics' native kernel layout.
    if heads is None:
        qr = q.transpose(0, 2, 1, 3).reshape(B * H, Lq, D)
        kr = k.transpose(0, 2, 1, 3).reshape(B * G, Lk, Dn)
        vr = v.transpose(0, 2, 1, 3).reshape(B * G, Lk, Dv)
        dor = do.transpose(0, 2, 1, 3).reshape(B * H, Lq, Dv)
        lser = lse.reshape(B * H, Lq, 1)
        d_row = jnp.sum(dor.astype(jnp.float32)
                        * o.transpose(0, 2, 1, 3).reshape(B * H, Lq, Dv)
                        .astype(jnp.float32), axis=-1, keepdims=True)
        programs, per, cols = B * H, 1, (0, 0, 0)
        gradient = (B * H, Lq, D), (B * H, Lk, Dn), (B * H, Lk, Dv)
    else:
        # the kernel sums dO . O a head itself, from o where it lies: a sum
        # over 64 of a row's 1,024 columns is a relayout outside a kernel
        qr, kr, vr, dor, d_row = q, k, v, do, o
        lser = lse.reshape(B * H, Lq, 1)
        programs, per = B * H // 2, 2
        gradient = (B, Lq, H * D), (B, Lk, H * Dn), (B, Lk, H * Dv)
    keys = (kr, k_shared) if shared else (kr,)

    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, causal=causal, scale=scale, block_q=bq,
        block_k=bk, n_kblocks=nkb, delta=0 if truncated else delta,
        packed=truncated, window=window, shared=shared)
    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel, causal=causal, scale=scale, block_q=bq,
        block_k=bk, n_qblocks=nqb, delta=0 if truncated else delta,
        packed=truncated, window=window, shared=shared, fused=fused,
        n_kblocks=nkb, heads=per, slab=slab)
    dq_out_shape = jax.ShapeDtypeStruct(gradient[0], q.dtype)
    dkv_dtype = jnp.float32 if rep > 1 else k.dtype    # a group's are summed
    # dK, the shared key's gradient a query head (float32: summed over the
    # heads below), dV
    dkv_out_shape = [jax.ShapeDtypeStruct(gradient[1], dkv_dtype)] \
        + [jax.ShapeDtypeStruct((B * H, Lk, D - Dn), jnp.float32)] * shared \
        + [jax.ShapeDtypeStruct(gradient[2], dkv_dtype)]
    dkv_scratch = [pltpu.VMEM((bk, per * Dn), jnp.float32)] \
        + [pltpu.VMEM((bk, D - Dn), jnp.float32)] * shared \
        + [pltpu.VMEM((bk, per * Dv), jnp.float32)]

    def call(kernel, name, k_major, out_widths, out_shape, scratch,
             whole_q=()):
        """One backward kernel over its grid. The packed causal grids read
        their (q-block, k-block) off the scalar-prefetched step tables
        (q-major steps for dQ: k-blocks stream within a q-block; k-major for
        dK/dV: q-blocks stream within a k-block, starting at the diagonal),
        the full grids off their two block axes, which the dK/dV grid
        transposes: (bh, k-block, q-stream). dQ writes a block of q rows,
        dK/dV blocks of keys, each a query head's own, ``out_widths`` wide.
        The fused kernel's dQ (``whole_q``: its width) comes first, all
        ``Lq`` rows of a query head as one block that stays a program long,
        which is why no axis but the first is parallel there and the
        kernel's VMEM limit is the plan's sum."""
        if truncated:
            at_q = lambda bh, t, qi, kb: qi[t]              # noqa: E731
            at_k = lambda bh, t, qi, kb: kb[t]              # noqa: E731
        elif k_major:
            at_q = lambda bh, j, i: i                       # noqa: E731
            at_k = lambda bh, j, i: j                       # noqa: E731
        else:
            at_q = lambda bh, i, j: i                       # noqa: E731
            at_k = lambda bh, i, j: j                       # noqa: E731

        rows, keys_of, whole, stats = _block_specs(
            bq, bk, Lq, at_q, at_k, None if heads is None else H // 2)
        ins = [rows(D, cols[0]), keys_of(Dn, rep, cols[1])] \
            + [keys_of(D - Dn, H)] * shared \
            + [keys_of(Dv, rep, cols[2]), rows(Dv), stats(),
               stats() if heads is None else rows(Dv)]
        out_specs = [whole(w) for w in whole_q] \
            + [(keys_of if k_major else rows)(w) for w in out_widths]
        params = {}
        if whole_q:
            params["vmem_limit_bytes"] = fused_bwd_vmem_bytes(
                Lq, D, q.dtype.itemsize) + (
                    0 if heads is None else pair_vmem_bytes(q.dtype.itemsize))
        if truncated:
            tables = _causal_step_tables(nqb, nkb, bq, bk, k_major=k_major,
                                         window=window)
            how = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(programs, int(tables[0].size)),
                in_specs=ins, out_specs=out_specs, scratch_shapes=scratch))
            semantics = ("parallel", "arbitrary")
        else:
            tables = ()
            how = dict(grid=(programs, nkb, nqb) if k_major
                       else (programs, nqb, nkb),
                       in_specs=ins, out_specs=out_specs,
                       scratch_shapes=scratch)
            semantics = ("parallel", "arbitrary" if whole_q else "parallel",
                         "arbitrary")
        return pl.pallas_call(
            kernel, out_shape=out_shape, interpret=interpret, name=name,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=semantics, **params), **how,
        )(*map(jnp.asarray, tables), qr, *keys, vr, dor, lser, d_row)

    if fused:
        dq, dk, *dks, dv = call(
            dkv_kernel, BWD_KERNEL, True, [Dn] + [D - Dn] * shared + [Dv],
            [dq_out_shape] + dkv_out_shape,
            [pltpu.VMEM((Lq, per * D), jnp.float32)] + dkv_scratch,
            whole_q=[D])
        if heads is not None:
            if whole_projection:    # the fused projection's one gradient
                return jnp.concatenate([dq, dk, dv], -1), None, None, None
            return dq, dk, dv, None
    else:
        dq, = call(dq_kernel, DQ_KERNEL, False, [D], [dq_out_shape],
                   [pltpu.VMEM((bq, D), jnp.float32)])
        dk, *dks, dv = call(dkv_kernel, DKV_KERNEL, True,
                            [Dn] + [D - Dn] * shared + [Dv], dkv_out_shape,
                            dkv_scratch)

    def grouped(t, like):
        """A KV head's gradient: the sum over the query heads that read it."""
        width = t.shape[-1]
        if rep > 1:
            t = t.reshape(B, G, rep, Lk, width).sum(2)
        return t.reshape(B, G, Lk, width).transpose(0, 2, 1, 3) \
            .astype(like.dtype)

    return (dq.reshape(B, H, Lq, D).transpose(0, 2, 1, 3), grouped(dk, k),
            grouped(dv, v),
            dks[0].reshape(B, H, Lk, D - Dn).sum(1).astype(k_shared.dtype)
            if shared else None)


def _flash_bwd_vjp(causal, scale, block_q, block_k, interpret, bwd_impl,
                   q_offset, k_offset, truncate, window, heads, slab, res,
                   do):
    """``bwd_impl`` arrives resolved ("scan" | "pallas" | "fused") from
    flash_attention: part of the trace key, so the selection can never
    desync from a cached trace. ``heads`` (two heads a program) and
    ``slab`` (row slabs on the diagonal) come with ``"fused"`` alone."""
    fn = {"scan": _flash_bwd_scan, "pallas": _flash_bwd_pallas,
          "fused": functools.partial(_flash_bwd_pallas, fused=True,
                                     heads=heads, slab=slab)}[bwd_impl]
    return fn(causal, scale, block_q, block_k, interpret,
              q_offset, k_offset, truncate, window, res, do)


_flash.defvjp(_flash_fwd_vjp, _flash_bwd_vjp)
