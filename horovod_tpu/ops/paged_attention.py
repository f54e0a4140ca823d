"""Fused paged-attention decode kernel: stream K/V pages, skip the gather.

vLLM's PagedAttention, rebuilt TPU-native on the machinery PR 3 shipped
in :mod:`horovod_tpu.ops.attention`: the serving engine's decode lane
(docs/serving.md) holds each request's KV cache as fixed-size pages
(``[num_pages, page_size, H, D]`` per layer per K/V) indexed by a
per-request page table, and the reference path reconstructs a dense
``[S, Lmax, H, D]`` logical cache per layer per step with a gather — so
a request at position ``t`` pays HBM traffic proportional to the
configured ``Lmax``, not to ``t``.

:func:`paged_attention_decode` kills that gather: a Pallas kernel whose
grid walks ``(slot, page-step)`` with the page tables and per-slot
lengths SCALAR-PREFETCHED (the ``PrefetchScalarGridSpec`` step-table
technique of the packed causal flash grid), so each step's K/V
``BlockSpec`` index maps straight to the slot's next PHYSICAL page —
Mosaic streams one whole ``[page_size, H, D]`` page (every head) per
step through double-buffered VMEM DMA while an online-softmax state
(m/l/acc scratch, one row per head) accumulates across the page walk.
The dense intermediate never exists, and the pages a slot streams are
exactly its ``ceil((t+1)/page_size)`` LIVE pages:

* the page axis is the grid's innermost ("arbitrary") dimension, and
  steps past a slot's last live page clamp their index map to that
  last live page — an unchanged block index, so Mosaic's pipeline
  skips the re-fetch (no DMA) and ``pl.when`` skips the compute;
* idle lanes (length 0) park their index map on the reserved null
  page 0 and never compute — the null page's CONTENTS never enter an
  attention sum (tests fill it with NaN to prove it), and live slots
  never map it at all (their table entries below ``ceil((t+1)/ps)``
  are engine-mapped real pages);
* rows past ``t`` inside the last live page are masked to
  :data:`~horovod_tpu.ops.attention.NEG_INF` before the running max,
  exactly the reference cache mask.

Block shapes: Mosaic wants a block's last two dimensions divisible by
the (8, 128) tile or equal to the array's. With ``H`` and ``D`` in the
last two positions of both the query ``[S, H, D]`` and the pages
``[P, ps, H, D]``, the only block that is legal for every head count
(12 unsharded, 3 per shard at tp=4) takes them whole. One query row per
head is no matmul: the body is a broadcast multiply and a lane
reduction over ``[ps, H, D]``, with no in-kernel transpose or
relayout.

On the CPU test platform the kernel runs in interpreter mode (the flash
discipline), so the whole path — ragged lengths, page-boundary edges,
the null page — is CI-pinned; :func:`paged_grid_info` is the static
accounting twin (the ``flash_grid_info`` pattern) that serve_bench
stamps into records and tests assert against.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from horovod_tpu.ops.attention import NEG_INF
from horovod_tpu.utils.device import pallas_interpret


def _paged_decode_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref,
                         o_ref, m_scr, l_scr, acc_scr, *,
                         page_size: int, scale: float):
    """One (slot, page-step) grid step over every head.

    ``q_ref`` is the slot's query ``[H, D]``; ``k_ref``/``v_ref`` are
    one physical page ``[page_size, H, D]`` (the index maps resolved
    the page table BEFORE the body runs — scalar prefetch); the
    online-softmax state persists in VMEM scratch across the page walk
    (grid axis 1 is sequential): ``[H, 1]`` columns for m and l,
    ``[H, D]`` for the accumulator. ``H`` and ``D`` stay in the tiled
    (sublane, lane) positions throughout: scores are a lane reduction
    kept as ``[ps, H, 1]``, and the page axis — the leading, untiled
    one — is what the softmax statistics reduce over."""
    from jax.experimental import pallas as pl

    s = pl.program_id(0)
    j = pl.program_id(1)
    live = lens_ref[s]                          # keys 0..t  (t+1 of them)
    live_pages = (live + page_size - 1) // page_size   # 0 for idle lanes

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(j < live_pages)
    def _compute():
        # All arithmetic in f32 on the VPU; the page upcasts on load.
        q = q_ref[...].astype(jnp.float32)              # [H, D]
        k = k_ref[...].astype(jnp.float32)              # [ps, H, D]
        v = v_ref[...].astype(jnp.float32)
        sc = jnp.sum(q[None] * k, axis=-1, keepdims=True) * scale
        # The cache mask: key positions past t (unwritten rows of the
        # last live page) contribute exactly zero — same NEG_INF
        # spelling as the reference kernel, applied BEFORE the running
        # max so garbage rows can never leak into the statistics.
        k_pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, sc.shape, 0)
        sc = jnp.where(k_pos < live, sc, NEG_INF)       # [ps, H, 1]
        m = m_scr[...]
        m_new = jnp.maximum(m, jnp.max(sc, axis=0))     # [H, 1]
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new[None])                   # [ps, H, 1]
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=0)
        acc_scr[...] = acc_scr[...] * alpha + jnp.sum(p * v, axis=0)

    # Idle lanes (live_pages == 0) finalize at j == 0 with the zeroed
    # scratch: a deterministic all-zero output row (discarded upstream).
    @pl.when(j == jnp.maximum(live_pages - 1, 0))
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_attention_decode(q, k_pages, v_pages, tables, lengths,
                           scale: Optional[float] = None,
                           interpret: Optional[bool] = None):
    """Decode attention for S single-token queries straight from pages.

    Shapes::

        q        [S, H, D]        one query token per decode slot
        k_pages  [P, ps, H, D]    the physical page pool (page 0 = the
        v_pages  [P, ps, H, D]    reserved null sink, never streamed)
        tables   [S, pps] int32   per-slot logical->physical page table
        lengths  [S]      int32   live keys per slot (t+1; the row at t
                                  must already be scattered into its
                                  page — the kernel is READ-ONLY over
                                  pages); 0 marks an idle lane, whose
                                  output row is zeros

    Returns ``[S, H, D]``. Equals masked softmax attention over each
    slot's first ``lengths[s]`` gathered cache rows (the engine's
    ``_gather_cache`` + ``dot_product_attention(q_offset=t)`` reference
    path — pinned in tests/test_paged_attention.py); per-slot K/V bytes
    are ``ceil((t+1)/ps)`` pages instead of the gather's ``Lmax/ps``
    (:func:`paged_grid_info` is the static accounting).

    The engine contract (docs/serving.md): every table entry below
    ``ceil((t+1)/ps)`` is a MAPPED page (never 0) — the scheduler's
    ``ensure_pages``/reserve-admission invariant.

    ``interpret`` defaults to the platform's (compiled on a TPU,
    interpreted on the CPU test platform —
    :func:`horovod_tpu.utils.device.pallas_interpret`).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, D = q.shape
    P, ps, Hk, Dk = k_pages.shape
    if (Hk, Dk) != (H, D) or v_pages.shape != k_pages.shape:
        raise ValueError(
            f"page/query shape mismatch: q {q.shape}, k_pages "
            f"{k_pages.shape}, v_pages {v_pages.shape}")
    if tables.shape[0] != S or lengths.shape != (S,):
        raise ValueError(
            f"tables {tables.shape} / lengths {lengths.shape} do not "
            f"match {S} slots")
    pps = tables.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    if interpret is None:
        interpret = pallas_interpret()

    def _page(s, j, tables, lengths):
        # The slot's next LIVE page; steps past the last live page
        # clamp to it (unchanged block index -> Mosaic skips the DMA),
        # and idle lanes (live_pages == 0) park on the null page 0
        # (their all-zero table) with compute fully skipped.
        live_pages = (lengths[s] + ps - 1) // ps
        return tables[s, jnp.minimum(j, jnp.maximum(live_pages - 1, 0))]

    kernel = functools.partial(_paged_decode_kernel, page_size=ps,
                               scale=float(scale))
    row = pl.BlockSpec((None, H, D), lambda s, j, t, ln: (s, 0, 0))
    page = pl.BlockSpec((None, ps, H, D),
                        lambda s, j, t, ln: (_page(s, j, t, ln), 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        # Page steps ride the INNERMOST axis (sequential, "arbitrary")
        # so the scratch-carried softmax state is legal while Mosaic
        # double-buffers the per-page K/V DMAs; slots are independent
        # ("parallel").
        grid=(S, pps),
        in_specs=[row, page, page],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),    # running max m
            pltpu.VMEM((H, 1), jnp.float32),    # running sum l
            pltpu.VMEM((H, D), jnp.float32),    # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(tables, jnp.int32), jnp.asarray(lengths, jnp.int32),
      q, k_pages, v_pages)


# --------------------------------------------------------------------------
# Static accounting (the flash_grid_info pattern)


def paged_grid_info(lengths: Sequence[int], *, page_size: int,
                    pages_per_seq: int, num_heads: int, head_dim: int,
                    dtype_bytes: int = 4, num_layers: int = 1,
                    tables=None, tp: int = 1):
    """Static page/byte accounting for one decode step, without tracing.

    Mirrors exactly the index-map policy :func:`paged_attention_decode`
    runs — ``tools/serve_bench.py`` stamps this into serving records
    and tests assert against it, the way ``flash_grid_info`` backs the
    flash lanes.

    ``lengths`` are the per-slot live-key counts (``t+1``; 0 = idle
    lane). Returns a dict:

    * ``pages_live`` — per-slot pages streamed, ``ceil((t+1)/ps)``
      (0 for idle lanes: their block index parks on the null page with
      no compute);
    * ``pages_full`` — the gather path's per-slot page count,
      ``pages_per_seq = Lmax/ps`` for EVERY slot, idle included (the
      dense ``[S, Lmax, H, D]`` reconstruction has no length
      awareness);
    * ``kv_bytes`` / ``kv_bytes_gather`` — K+V bytes per decode step
      per the two policies (× ``num_layers``);
    * ``kv_fetch_frac`` — the streamed/gathered byte ratio, the
      traffic-win headline;
    * ``pages_visited`` (only when ``tables`` is given) — the per-slot
      PHYSICAL page ids the kernel's index map streams; never contains
      the null page 0 for a live slot;
    * ``tp`` / ``kv_bytes_per_chip`` / ``kv_bytes_gather_per_chip`` —
      the tensor-parallel degree and each policy's PER-CHIP bytes
      under it: heads shard exactly (``num_heads % tp == 0`` is
      enforced), so per-chip traffic is byte-for-byte 1/tp of the
      totals above — the honest form of the TP bandwidth claim
      (``tp=1`` degenerates to the totals).
    """
    lens = [int(x) for x in lengths]
    if any(x < 0 for x in lens):
        raise ValueError(f"negative length in {lens}")
    if tp < 1 or num_heads % tp != 0:
        raise ValueError(
            f"tp={tp} must be >= 1 and divide num_heads={num_heads} "
            "(the head-sharded page arrays split exactly)")
    pages_live = [-(-x // page_size) for x in lens]
    if any(p > pages_per_seq for p in pages_live):
        raise ValueError(
            f"length exceeds the page table: lengths {lens}, "
            f"pages_per_seq {pages_per_seq}, page_size {page_size}")
    S = len(lens)
    tile = 2 * page_size * num_heads * head_dim * dtype_bytes * num_layers
    info = {
        "page_size": page_size,
        "pages_per_seq": pages_per_seq,
        "slots": S,
        "pages_live": pages_live,
        "pages_live_total": sum(pages_live),
        "pages_full_total": S * pages_per_seq,
        "kv_bytes": sum(pages_live) * tile,
        "kv_bytes_gather": S * pages_per_seq * tile,
        "kv_fetch_frac": (round(sum(pages_live) / (S * pages_per_seq), 4)
                          if S else None),
        "tp": tp,
        "kv_bytes_per_chip": sum(pages_live) * tile // tp,
        "kv_bytes_gather_per_chip": S * pages_per_seq * tile // tp,
    }
    if tables is not None:
        import numpy as np

        tab = np.asarray(tables)
        if tab.shape != (S, pages_per_seq):
            raise ValueError(
                f"tables {tab.shape} does not match ({S}, "
                f"{pages_per_seq})")
        info["pages_visited"] = [
            [int(p) for p in tab[s, :pages_live[s]]] for s in range(S)]
    return info
