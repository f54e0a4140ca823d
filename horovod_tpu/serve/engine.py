"""Continuous-batching serving engine: one fixed-shape compiled step.

Orca's iteration-level batching, TPU-native. Every engine step executes
ONE compiled program whose shapes never change — ``decode_slots``
single-token decode lanes plus one ``prefill_chunk``-token chunked-
prefill lane — so requests join and leave the running batch between
steps with ZERO recompilation. Two program variants compile once each
(mixed prefill+decode, and decode-only for steps with an idle prefill
lane); everything else is data:

* each decode slot attends its single query against its paged cache.
  Two selectable paths (``ServeConfig.attention``): ``gather`` (the
  default and exactness reference) reconstructs the logical cache
  ``[Lmax, H, D]`` out of the paged K/V arrays through the request's
  page-table index vector (:mod:`~horovod_tpu.serve.kvcache` — a pure
  gather, never a reshape; K and V share ONE index computation per
  lane), inserts the step's new K/V row, attends with ``q_offset = t``
  (the cache mask, exactly
  :func:`models.parallel_lm.lm_decode_step`'s spelling), and scatters
  the new row back into the pages; ``paged`` runs the same scatter
  FIRST and then streams only the slot's ``ceil((t+1)/page_size)``
  live pages through the fused Pallas kernel
  (:func:`~horovod_tpu.ops.paged_attention.paged_attention_decode`) —
  the dense intermediate never exists;
* the prefill lane runs one chunk of the current prompt through the
  RECTANGULAR-causal path — queries at global positions
  ``start..start+C-1`` over the full gathered cache with
  ``q_offset=start, k_offset=0`` (the PR-3 offset contract of
  ``ops.attention``) — writing its K/V rows through the page table;
  out-of-chunk (padded) rows scatter with ``mode="drop"`` so they
  never touch a real page.

Because both lanes reuse ``parallel_lm``'s layer functions verbatim and
masked softmax terms are exactly zero, the greedy token stream is
bit-identical to ``lm_decode`` per request (pinned in
tests/test_serve_engine.py, and CI-gated via tools/serve_bench.py
``--pin-exact``).

The page arrays are threaded through the step FUNCTIONALLY — never
donated: a live request's pages must stay readable under an in-flight
step (tools/hvdverify registers ``serve.step`` with
``forbid_donation``, the HVV104 invariant class the elastic loop
established).

**TP-sharded decode** (``ServeConfig.mesh``, e.g. ``"dp=1,tp=4"``):
the SAME step runs SPMD under ``shard_map`` over a bound
:class:`~horovod_tpu.parallel.logical.LogicalMesh` — attention heads,
MLP features and the vocab projection shard Megatron-style
(:func:`models.parallel_lm.lm_param_specs` ``vocab_parallel=True``),
the per-layer KV page arrays become ``[num_pages, page_size, H/tp,
D]`` per chip, and full-vocab f32 logits are reassembled by one tiled
all-gather (:func:`~horovod_tpu.parallel.tp.vocab_parallel_logits`)
so the host-side sampler is byte-identical to the unsharded path.
The design split: the DATA plane (K/V pages, weights) shards; the
CONTROL plane (scheduler, page tables, free-list refcounts, the radix
prefix index) stays host-side Python — one allocator makes every
decision, so "replicated across chips" holds by construction. Both
attention paths work sharded: the gather path gathers local-head
pages, and the Pallas kernel runs per-shard with its grid's head
dimension sized H/tp (the kernel is shape-polymorphic in H — no
kernel change). Greedy tokens stay bit-identical to ``lm_decode`` AND
to the tp=1 engine (tests/test_serve_engine.py; ``serve_bench
--ab-tp`` gates it in CI): each chip's dot products are exactly the
dense math's column slices, psums only add terms the dense contraction
adds, and argmax sees the identical full-vocab row.

**Speculative decoding** (``ServeConfig.speculate_k``): the compiled
step becomes :func:`serve_step_spec` — the layer-skip draft (the
target's first ``draft_layers`` layers sharing embed/head AND the
target's own KV pages, :func:`models.parallel_lm.draft_params`)
proposes up to ``k`` tokens per slot in one ``lax.scan``, and the
target verifies all ``k+1`` positions in ONE rectangular-causal pass
(``q_offset=t, k_offset=0`` — the prefill lane's exact contract). The
host keeps the longest draft/target-agreeing prefix per slot
(:func:`~horovod_tpu.serve.sampling.speculative_accept`) and emits
1..k+1 tokens per tick; rejected rows roll back by page-table
arithmetic (stale rows are overwritten by the next window or causally
masked — no erasure pass), with ``Request.spec_window`` clamping the
window inside the page grant and ``_cow_guard`` widened over the full
write range. Greedy streams stay bit-identical to ``lm_decode`` and
to the non-speculative engine — every emitted token is a target
argmax of its true prefix — across both attention modes and under TP
(tests/test_serve_engine.py; ``serve_bench --ab-spec`` gates it in
CI; hvdverify ``serve.step_spec{,_paged,_tp}`` pin the no-donation
rollback substrate).
"""

from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional

import numpy as np

from horovod_tpu.serve.config import ServeConfig
from horovod_tpu.serve.kvcache import PagedKVCache
from horovod_tpu.serve.scheduler import (
    Request,
    RequestState,
    Scheduler,
    pick_victim,
)

# --------------------------------------------------------------------------
# The compiled step program (pure; jitted once per variant).


def _gather_cache(pages_arr, table):
    """pages [P, ps, H, D] x table [pps] -> the request's contiguous
    logical cache [Lmax, H, D] (unmapped slots read the null page's
    zeros — always masked downstream). Single-array form, kept as the
    paged kernel's exactness reference; the hot path shares one index
    computation for K and V via :func:`_gather_cache_kv`."""
    g = pages_arr[table]
    return g.reshape(g.shape[0] * g.shape[1], g.shape[2], g.shape[3])


def _gather_cache_kv(pk, pv, table):
    """The K AND V gathers of one lane through ONE shared index
    computation: the page table expands to flat row indices once, and
    both page arrays gather through the same vector (the old path
    rebuilt the expansion four times per layer — K/V x decode/prefill;
    tables are the only index input, so K and V always shared it
    logically). Returns ``(k [Lmax, H, D], v [Lmax, H, D])``."""
    import jax.numpy as jnp

    P, ps = pk.shape[0], pk.shape[1]
    rows = (table[:, None] * ps
            + jnp.arange(ps, dtype=table.dtype)[None, :]).reshape(-1)
    return (pk.reshape(P * ps, pk.shape[2], pk.shape[3])[rows],
            pv.reshape(P * ps, pv.shape[2], pv.shape[3])[rows])


def _prefill_lane(params: Dict, pages, pre, *, page_size: int, tp=None,
                  vocab_parallel: bool = False):
    """The chunked-prefill pass of one step — shared verbatim by
    :func:`serve_step` and :func:`serve_step_spec`: one rectangular-
    causal chunk (queries at ``start..start+C-1`` over the full
    gathered cache, ``q_offset=start, k_offset=0``) whose K/V rows
    write through the page table via :func:`~horovod_tpu.serve.
    kvcache.append_rows` (padded rows hit the OOB sentinel and drop).
    Returns ``(new_pages, pre_logits [V])``."""
    import math

    import jax.numpy as jnp
    from jax import lax

    from horovod_tpu.models.parallel_lm import (
        _attn_out_residual,
        _ffn_residual,
        _logits,
        _project_qkv,
    )
    from horovod_tpu.ops.attention import dot_product_attention
    from horovod_tpu.serve.kvcache import append_rows

    ps = page_size
    num_pages = pages[0]["k"].shape[0]
    lmax = pre["table"].shape[0] * ps
    C = pre["tokens"].shape[0]
    start = pre["start"]
    rows = jnp.arange(C)
    row_valid = rows < pre["length"]
    # OOB sentinel drops padded/inactive rows at every scatter.
    write_page, write_off, safe_pos = append_rows(
        pre["table"], start, C, page_size=ps, num_pages=num_pages,
        valid=row_valid)
    xp = params["embed"][pre["tokens"]][None] + \
        params["pos"][safe_pos][None]                  # [1, C, E]
    new_pages = []
    for layer, page in zip(params["layers"], pages):
        pk, pv = page["k"], page["v"]
        qp, kp, vp = _project_qkv(layer, xp, tp)       # [1, C, H, D]
        # math.sqrt, exactly parallel_lm's spelling — the scale
        # must be the bit-identical float for the exactness pin.
        scale = 1.0 / math.sqrt(qp.shape[-1])
        gk, gv = _gather_cache_kv(pk, pv, pre["table"])
        # The chunk's own rows enter the gathered view (scatter —
        # row-distinct indices, padded rows dropped), then the
        # rectangular-causal attention: queries at start+i over
        # keys 0..start+i.
        ck = gk.at[jnp.where(row_valid, safe_pos, lmax)].set(
            kp[0], mode="drop")
        cv = gv.at[jnp.where(row_valid, safe_pos, lmax)].set(
            vp[0], mode="drop")
        attn = dot_product_attention(qp, ck[None], cv[None],
                                     causal=True, scale=scale,
                                     q_offset=start, k_offset=0)
        xp = _attn_out_residual(layer, attn, xp, tp)
        xp = _ffn_residual(layer, xp, tp)
        pk = pk.at[write_page, write_off].set(kp[0], mode="drop")
        pv = pv.at[write_page, write_off].set(vp[0], mode="drop")
        new_pages.append({"k": pk, "v": pv})
    last = jnp.clip(pre["length"] - 1, 0, C - 1)
    row = lax.dynamic_slice_in_dim(xp[0], last, 1, 0)   # [1, E]
    pre_logits = _logits(params, row[None], tp,
                         vocab_parallel)[0, 0]          # [V]
    return new_pages, pre_logits


#: Public alias: under disaggregated serving (FleetConfig.pools) a
#: prefill replica's steady-state tick IS the chunked-prefill lane —
#: every request it admits carries prefill_only, so the decode slots
#: never fill. hvdverify registers this as ``serve.step_prefill_pool``
#: and machine-checks the no-donation invariant on it directly: the
#: finished pages park in the handoff bay until the decode pool's
#: import digest-verifies them, so they must stay readable.
serve_step_prefill = _prefill_lane


def serve_step(params: Dict, pages, dec, pre, *, page_size: int,
               attention: str = "gather", tp=None,
               vocab_parallel: bool = False):
    """One continuous-batching step.

    ``dec``: ``tok``/``pos``/``active`` [S] + ``tables`` [S, pps];
    ``pre`` (or None for the decode-only variant): ``tokens`` [C],
    ``start``/``length`` scalars + ``table`` [pps].
    Returns ``(new_pages, dec_logits [S, V], pre_logits [V] | None)``.

    ``attention`` (static) picks the decode lane's cache path:
    ``gather`` reconstructs the dense per-slot cache and inserts the
    new row into the gathered copy (the exactness reference);
    ``paged`` scatters the new row into its page FIRST (the identical
    scatter — so the kernel stays READ-ONLY over pages and the
    no-donation invariant is untouched) and then streams only the live
    pages through :func:`~horovod_tpu.ops.paged_attention.
    paged_attention_decode`. The prefill lane keeps the full gather in
    both modes (rectangular-causal over the whole cache).

    ``tp`` (static) names the tensor axis when the step runs inside
    ``shard_map`` over head-sharded params and pages; ``vocab_parallel``
    additionally expects a column-sharded head [E, V/tp] and assembles
    full-vocab logits with one tiled all-gather — the sampler upstream
    never sees a shard.
    """
    import math

    import jax
    import jax.numpy as jnp
    from jax import lax

    from horovod_tpu.models.parallel_lm import (
        _attn_out_residual,
        _ffn_residual,
        _logits,
        _project_qkv,
    )
    from horovod_tpu.ops.attention import dot_product_attention
    from horovod_tpu.ops.paged_attention import paged_attention_decode

    if attention not in ("gather", "paged"):
        raise ValueError(
            f"attention must be 'gather' or 'paged', got {attention!r}")
    ps = page_size
    num_pages = pages[0]["k"].shape[0]
    S = dec["tok"].shape[0]
    new_pages = []

    # ---------------------------------------------------- prefill lane
    pre_logits = None
    if pre is not None:
        pages, pre_logits = _prefill_lane(params, pages, pre,
                                          page_size=ps, tp=tp,
                                          vocab_parallel=vocab_parallel)

    # ----------------------------------------------------- decode lane
    t = dec["pos"]                                      # [S]
    write_page_d = jnp.where(dec["active"],
                             dec["tables"][jnp.arange(S), t // ps],
                             num_pages)                 # OOB = dropped
    write_off_d = t % ps
    # Live keys per slot for the paged kernel (t+1; 0 = idle lane).
    lens = jnp.where(dec["active"], t + 1, 0).astype(jnp.int32)
    xd = params["embed"][dec["tok"]][:, None] + \
        params["pos"][t][:, None]                       # [S, 1, E]

    insert = jax.vmap(
        lambda c, u, tt: lax.dynamic_update_slice_in_dim(c, u, tt, 0))

    for layer, page in zip(params["layers"], pages):
        pk, pv = page["k"], page["v"]
        qd, kd, vd = _project_qkv(layer, xd, tp)        # [S, 1, H, D]
        scale = 1.0 / math.sqrt(qd.shape[-1])
        if attention == "paged":
            # Scatter the new row FIRST (the gather path's identical
            # scatter, just hoisted above the attention), then stream
            # only the live pages — the kernel reads position t back
            # from its page, so the dense [S, Lmax, H, D] intermediate
            # never exists and per-step K/V bytes are O(t), not
            # O(Lmax). Read-only kernel over pages: the no-donation
            # invariant is exactly the gather path's.
            pk = pk.at[write_page_d, write_off_d].set(kd[:, 0],
                                                      mode="drop")
            pv = pv.at[write_page_d, write_off_d].set(vd[:, 0],
                                                      mode="drop")
            attn = paged_attention_decode(
                qd[:, 0], pk, pv, dec["tables"], lens,
                scale=scale)[:, None]                   # [S, 1, H, D]
        else:
            gkd, gvd = jax.vmap(
                _gather_cache_kv, in_axes=(None, None, 0))(
                pk, pv, dec["tables"])                  # [S, Lmax, H, D]
            ckd = insert(gkd, kd, t)
            cvd = insert(gvd, vd, t)
            attn = jax.vmap(
                lambda q, k, v, tt: dot_product_attention(
                    q, k, v, causal=True, scale=scale, q_offset=tt)
            )(qd, ckd, cvd, t)                          # [S, 1, H, D]
        xd = _attn_out_residual(layer, attn, xd, tp)
        xd = _ffn_residual(layer, xd, tp)
        if attention != "paged":
            pk = pk.at[write_page_d, write_off_d].set(kd[:, 0],
                                                      mode="drop")
            pv = pv.at[write_page_d, write_off_d].set(vd[:, 0],
                                                      mode="drop")

        new_pages.append({"k": pk, "v": pv})

    dec_logits = _logits(params, xd, tp, vocab_parallel)[:, 0]  # [S, V]
    return new_pages, dec_logits, pre_logits


def serve_step_spec(params: Dict, pages, dec, pre, *, k: int,
                    draft_layers: int, page_size: int,
                    attention: str = "gather", tp=None,
                    vocab_parallel: bool = False):
    """One continuous-batching step with SPECULATIVE decoding: the
    layer-skip draft (the target's first ``draft_layers`` layers
    sharing embed/head) proposes up to ``k`` tokens per slot, and the
    target verifies all ``k+1`` positions in ONE rectangular-causal
    pass — the exact chunked-prefill shape per slot: queries at
    ``t..t+k`` over the full gathered cache, ``q_offset=t,
    k_offset=0``.

    ``dec`` extends :func:`serve_step`'s batch with the speculation
    plane: ``width`` [S] (``k_eff+1`` rows this slot verifies this
    tick — the host's budget clamp; 0 = idle lane) plus the draft's
    sampling knobs ``temp``/``topk``/``seed``/``sidx`` [S] — proposals
    are drawn IN-step, because the propose loop must feed each
    proposal to the next draft step. That loop is ONE ``lax.scan``
    (PR-1's windowing trick), so per-tick dispatch cost stays flat in
    ``k``.

    Returns ``(new_pages, ver_logits [S, k+1, V], draft_toks [S, k],
    draft_logits [S, k, V], pre_logits)``; the host applies
    :func:`~horovod_tpu.serve.sampling.speculative_accept` per slot.

    The verify window's K/V rows scatter through
    :func:`~horovod_tpu.serve.kvcache.append_rows` under the width
    mask — rows past a slot's clamp (and idle lanes) hit the OOB
    sentinel and never touch a real page — and REJECTED rows need no
    rollback pass: a stale position is either overwritten by a later
    window or causally masked (no query ever admits a key past its own
    position), and the host's ``_cow_guard`` copied any shared page
    across the whole write range BEFORE the step, so a rejected row
    can never have landed on another request's page. Pages thread
    functionally, never donated (hvdverify ``serve.step_spec``).

    ``attention`` shapes the DRAFT propose scan: ``gather`` runs the
    ``k`` single-token draft steps over per-slot gathered dense
    caches; ``paged`` scatters each draft row and streams only live
    pages through the fused kernel per step. The verify pass gathers
    in both modes (rectangular-causal over the whole cache — exactly
    the prefill lane's policy). Greedy streams are bit-identical
    either way, and to :func:`serve_step`'s.
    """
    import math

    import jax
    import jax.numpy as jnp
    from jax import lax

    from horovod_tpu.models.parallel_lm import (
        _attn_out_residual,
        _ffn_residual,
        _logits,
        _project_qkv,
    )
    from horovod_tpu.ops.attention import dot_product_attention
    from horovod_tpu.ops.paged_attention import paged_attention_decode
    from horovod_tpu.serve.kvcache import append_rows
    from horovod_tpu.serve.sampling import draft_sample_tokens

    if attention not in ("gather", "paged"):
        raise ValueError(
            f"attention must be 'gather' or 'paged', got {attention!r}")
    if k < 1:
        raise ValueError(f"speculate_k must be >= 1 in-step, got {k}")
    if not 1 <= draft_layers <= len(params["layers"]):
        raise ValueError(
            f"draft_layers={draft_layers} outside 1.."
            f"{len(params['layers'])}")
    ps = page_size
    num_pages = pages[0]["k"].shape[0]
    pps = dec["tables"].shape[1]
    lmax = pps * ps
    S = dec["tok"].shape[0]
    w = k + 1

    # ---------------------------------------------------- prefill lane
    pre_logits = None
    if pre is not None:
        pages, pre_logits = _prefill_lane(params, pages, pre,
                                          page_size=ps, tp=tp,
                                          vocab_parallel=vocab_parallel)

    t = dec["pos"]                                      # [S]
    width = dec["width"]                                # [S]; 0 = idle
    rows = jnp.arange(w)
    insert = jax.vmap(
        lambda c, u, tt: lax.dynamic_update_slice_in_dim(c, u, tt, 0))
    dlayers = params["layers"][:draft_layers]

    # ----------------------------------------------- draft propose scan
    # k single-token draft steps, one lax.scan; step i feeds token c_i
    # (c_0 = the last emitted token, c_i = proposal i) at position t+i
    # and proposes c_{i+1}. Rows the budget clamp masked off propose
    # garbage the host never reads.
    if attention == "paged":
        # Each draft step scatters its row (width-masked — a masked
        # row must never touch a real page) and streams only the live
        # pages through the fused kernel; the pages thread through the
        # scan carry so the verify pass below overwrites every row the
        # draft wrote (same tokens, all layers).
        def draft_step(carry, i):
            tok, dpages = carry
            pos = t + i                                 # [S]
            safe = jnp.clip(pos, 0, lmax - 1)
            x = params["embed"][tok][:, None] + \
                params["pos"][safe][:, None]            # [S, 1, E]
            # A draft row is needed only while a LATER proposal still
            # attends it: the last proposal row is width-2.
            ok = (i + 1) < width
            wp = jnp.where(ok,
                           dec["tables"][jnp.arange(S), safe // ps],
                           num_pages)
            wo = safe % ps
            lens = jnp.where(ok, pos + 1, 0).astype(jnp.int32)
            new_dpages = []
            for layer, (pk, pv) in zip(dlayers, dpages):
                q, kk, vv = _project_qkv(layer, x, tp)  # [S, 1, H, D]
                scale = 1.0 / math.sqrt(q.shape[-1])
                pk = pk.at[wp, wo].set(kk[:, 0], mode="drop")
                pv = pv.at[wp, wo].set(vv[:, 0], mode="drop")
                attn = paged_attention_decode(
                    q[:, 0], pk, pv, dec["tables"], lens,
                    scale=scale)[:, None]               # [S, 1, H, D]
                x = _attn_out_residual(layer, attn, x, tp)
                x = _ffn_residual(layer, x, tp)
                new_dpages.append((pk, pv))
            lg = _logits(params, x, tp, vocab_parallel)[:, 0]
            nxt = draft_sample_tokens(lg, dec["temp"], dec["topk"],
                                      dec["seed"], dec["sidx"] + i)
            return (nxt, tuple(new_dpages)), (nxt, lg)

        carry0 = (dec["tok"],
                  tuple((p["k"], p["v"]) for p in pages[:draft_layers]))
        (_, dpages), (draft_toks, draft_logits) = lax.scan(
            draft_step, carry0, jnp.arange(k))
        pages = [{"k": pk, "v": pv} for pk, pv in dpages] + \
            list(pages[draft_layers:])
    else:
        # Gather each draft layer's dense per-slot caches ONCE; the
        # scan inserts each step's row into the gathered copies (the
        # decode lane's exact idiom) and the copies are DISCARDED
        # after — the verify pass owns every row that persists.
        gks, gvs = [], []
        for page in pages[:draft_layers]:
            a, b = jax.vmap(_gather_cache_kv, in_axes=(None, None, 0))(
                page["k"], page["v"], dec["tables"])
            gks.append(a)
            gvs.append(b)

        def draft_step(carry, i):
            tok, dck, dcv = carry
            pos = t + i                                 # [S]
            safe = jnp.clip(pos, 0, lmax - 1)
            x = params["embed"][tok][:, None] + \
                params["pos"][safe][:, None]            # [S, 1, E]
            new_ck, new_cv = [], []
            for layer, ck0, cv0 in zip(dlayers, dck, dcv):
                q, kk, vv = _project_qkv(layer, x, tp)  # [S, 1, H, D]
                scale = 1.0 / math.sqrt(q.shape[-1])
                ck = insert(ck0, kk, safe)
                cv = insert(cv0, vv, safe)
                new_ck.append(ck)
                new_cv.append(cv)
                attn = jax.vmap(
                    lambda q1, k1, v1, tt: dot_product_attention(
                        q1, k1, v1, causal=True, scale=scale,
                        q_offset=tt)
                )(q, ck, cv, safe)                      # [S, 1, H, D]
                x = _attn_out_residual(layer, attn, x, tp)
                x = _ffn_residual(layer, x, tp)
            lg = _logits(params, x, tp, vocab_parallel)[:, 0]
            nxt = draft_sample_tokens(lg, dec["temp"], dec["topk"],
                                      dec["seed"], dec["sidx"] + i)
            return (nxt, tuple(new_ck), tuple(new_cv)), (nxt, lg)

        (_, _, _), (draft_toks, draft_logits) = lax.scan(
            draft_step, (dec["tok"], tuple(gks), tuple(gvs)),
            jnp.arange(k))

    draft_toks = jnp.swapaxes(draft_toks, 0, 1)         # [S, k]
    draft_logits = jnp.swapaxes(draft_logits, 0, 1)     # [S, k, V]

    # ------------------------------------------------------ verify pass
    # Window = [last emitted token, proposals] at positions t..t+k per
    # slot; ONE rectangular-causal target pass over the gathered cache
    # yields logits at every position. Width-masked rows gather-insert
    # to the Lmax drop index and page-scatter to the OOB sentinel.
    toks_w = jnp.concatenate([dec["tok"][:, None], draft_toks], 1)
    wp, wo, safe_w = jax.vmap(
        lambda tab, tt, wd: append_rows(
            tab, tt, w, page_size=ps, num_pages=num_pages,
            valid=jnp.arange(w) < wd))(dec["tables"], t, width)
    xw = params["embed"][toks_w] + params["pos"][safe_w]  # [S, w, E]
    gather_idx = jnp.where(rows[None, :] < width[:, None],
                           safe_w, lmax)                 # [S, w]
    scatter_g = jax.vmap(
        lambda g, ii, u: g.at[ii].set(u, mode="drop"))
    new_pages = []
    for layer, page in zip(params["layers"], pages):
        pk, pv = page["k"], page["v"]
        qw, kw, vw = _project_qkv(layer, xw, tp)         # [S, w, H, D]
        scale = 1.0 / math.sqrt(qw.shape[-1])
        gk, gv = jax.vmap(_gather_cache_kv, in_axes=(None, None, 0))(
            pk, pv, dec["tables"])                       # [S, Lmax, H, D]
        ck = scatter_g(gk, gather_idx, kw)
        cv = scatter_g(gv, gather_idx, vw)
        attn = jax.vmap(
            lambda q1, k1, v1, tt: dot_product_attention(
                q1, k1, v1, causal=True, scale=scale,
                q_offset=tt, k_offset=0)
        )(qw, ck, cv, t)                                 # [S, w, H, D]
        xw = _attn_out_residual(layer, attn, xw, tp)
        xw = _ffn_residual(layer, xw, tp)
        pk = pk.at[wp, wo].set(kw, mode="drop")
        pv = pv.at[wp, wo].set(vw, mode="drop")
        new_pages.append({"k": pk, "v": pv})

    ver_logits = _logits(params, xw, tp, vocab_parallel)  # [S, w, V]
    return new_pages, ver_logits, draft_toks, draft_logits, pre_logits


# --------------------------------------------------------------------------
# The host-side engine.


def resolve_tp_mesh(params: Dict, config: ServeConfig):
    """Bind ``config.mesh`` to this host's devices; fail-fast on
    everything the config string alone could not know. Returns
    ``(logical_mesh, tp_axis, tp_degree)`` — ``(None, None, 1)`` when
    the engine runs unsharded (``mesh=None`` or an all-ones mesh).

    Raises :class:`~horovod_tpu.common.exceptions.InvalidArgumentError`
    at ENGINE construction, never at first compile, when the mesh's
    device product exceeds the available devices (LogicalMesh's own
    check) or when heads / MLP features / vocab don't divide the tp
    degree (the shard shapes would be ragged)."""
    axes = config.mesh_axes()
    if not axes:
        return None, None, 1
    import jax

    from horovod_tpu.common.exceptions import InvalidArgumentError
    from horovod_tpu.parallel.logical import LogicalMesh

    lm = LogicalMesh.from_config(config.mesh, devices=jax.devices())
    tp_axis = lm.role_axis("tensor")
    tp = lm.axes.get(tp_axis, 1)
    if tp == 1:
        return None, None, 1
    layer0 = params["layers"][0]
    dims = (("num_heads", int(layer0["wqkv"].shape[2])),
            ("mlp", int(layer0["wup"].shape[1])),
            ("vocab", int(params["head"].shape[1])))
    for what, n in dims:
        if n % tp:
            raise InvalidArgumentError(
                f"ServeConfig.mesh {config.mesh!r}: {what}={n} is not "
                f"divisible by tp={tp} — the head/feature/vocab shards "
                "must split exactly (pad the model or pick a tp that "
                "divides)")
    return lm, tp_axis, tp


class ServeEngine:
    """Continuous-batching LM serving over a paged KV cache.

    ``params`` is :func:`models.parallel_lm.init_lm_params`' pytree.
    The engine owns the device page arrays, the scheduler, and the
    request lifecycle; :meth:`submit` queues work, :meth:`step` runs
    one compiled step (returns False when fully idle), :meth:`run`
    drains to idle. ``clock`` is injectable for deterministic tests.
    ``device`` pins an unsharded (tp=1) engine — parameters, pages and
    therefore its compiled step — to one device, so several replicas in
    one process each own a chip; ``None`` leaves JAX's default device.
    """

    def __init__(self, params: Dict, config: ServeConfig, *,
                 chips: int = 1, clock=time.perf_counter, device=None):
        self.config = config
        self.chips = chips
        self.clock = clock
        self.device = device
        #: Bound LogicalMesh + tensor axis + degree (mesh=None -> tp=1).
        #: Fail-fast happens HERE (device budget, divisibility), never
        #: at first compile.
        self.logical_mesh, self._tp_axis, self.tp = \
            resolve_tp_mesh(params, config)
        kv_sharding = None
        self._param_specs = None
        if self.tp > 1:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from horovod_tpu.models.parallel_lm import lm_param_specs

            # Megatron param placement + head-sharded pages: the DATA
            # plane. Specs double as the shard_map in/out_specs below.
            self._param_specs = lm_param_specs(
                len(params["layers"]), self._tp_axis,
                vocab_parallel=True)
            self._kv_spec = P(None, None, self._tp_axis, None)
            kv_sharding = NamedSharding(self.logical_mesh.mesh,
                                        self._kv_spec)
        elif device is not None:
            from jax.sharding import SingleDeviceSharding

            kv_sharding = SingleDeviceSharding(device)
        self.params = params = self._place_params(params)
        #: Speculative decoding plane (``config.speculate_k`` > 0):
        #: static k compiled into the step, layer-skip draft depth
        #: resolved against THIS model (0 = auto: half the depth, at
        #: least 1) — fail-fast at construction, never at first
        #: compile, like the tp divisibility checks above.
        self.spec_k = int(config.speculate_k)
        self.draft_layers = 0
        if self.spec_k:
            from horovod_tpu.common.exceptions import (
                InvalidArgumentError,
            )

            n_layers = len(params["layers"])
            dl = config.draft_layers or max(1, n_layers // 2)
            if not 1 <= dl <= n_layers:
                raise InvalidArgumentError(
                    f"ServeConfig.draft_layers={config.draft_layers}: "
                    f"the layer-skip draft is a prefix of the target's "
                    f"{n_layers} layers — need 1..{n_layers}")
            self.draft_layers = dl
        self.cache = PagedKVCache(params, config,
                                  kv_sharding=kv_sharding)
        if config.prefix_caching:
            from horovod_tpu.serve.prefix import PrefixIndex

            #: Radix prefix index (serve/prefix.py) — admission maps a
            #: prompt's already-filled pages read-only, prefill starts
            #: at the first miss.
            self.prefix = PrefixIndex(self.cache.allocator,
                                      config.page_size)
        else:
            self.prefix = None
        self.scheduler = Scheduler(self.cache, config,
                                   prefix=self.prefix)
        #: Copy-on-write page copies performed (the backstop — 0 in
        #: normal operation; see :meth:`_cow_guard`).
        self.cow_copies = 0
        self.slots: List[Optional[Request]] = [None] * config.decode_slots
        self.ready: List[Request] = []      # prefilled, awaiting a slot
        self.prefilling: Optional[Request] = None
        #: Disaggregated-serving handoff bay: ``prefill_only`` requests
        #: parked fully prefilled (first token emitted, pages held)
        #: until the fleet ships their KV pages to a decode replica —
        #: :meth:`export_handoff` / :meth:`release_handoff` on this
        #: side, :meth:`admit_prefilled` on the receiving one. Parked
        #: requests never decode here (the serve loop skips the bay),
        #: but they count in_flight and their deadlines still sweep.
        self.handoff: List[Request] = []
        self.finished: List[Request] = []
        self.evicted: List[Request] = []    # terminal (requeue off)
        self.timed_out: List[Request] = []  # terminal (deadline passed)
        self.occupancy_samples: List[float] = []
        #: Per-step decode-lane live-key counts (t+1 per slot, 0 =
        #: idle lane) — the raw input :func:`ops.paged_attention.
        #: paged_grid_info` aggregates into stats()["attention"], so
        #: serve_bench records carry the gather-vs-paged byte evidence
        #: on BOTH sides of the A/B (one accounting model, owned by
        #: paged_grid_info). Kept per-step (not pre-summed) so tests
        #: can pin the exact page walk; stats() aggregation is
        #: O(steps) — bench runs call it once at the end, and
        #: reset_metrics() bounds a long-lived engine.
        self.attn_len_samples: List[List[int]] = []
        self.steps = 0
        #: Speculation accounting (speculate_k > 0): per decode TICK,
        #: proposals made/accepted and tokens emitted — the inputs to
        #: stats()["spec"] (accept_rate, tokens_per_step).
        self.spec_ticks = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_emitted = 0
        self._t_start = clock()
        if self.spec_k:
            step = functools.partial(serve_step_spec,
                                     k=self.spec_k,
                                     draft_layers=self.draft_layers,
                                     page_size=config.page_size,
                                     attention=config.attention,
                                     tp=self._tp_axis,
                                     vocab_parallel=self.tp > 1)
        else:
            step = functools.partial(serve_step,
                                     page_size=config.page_size,
                                     attention=config.attention,
                                     tp=self._tp_axis,
                                     vocab_parallel=self.tp > 1)
        import jax

        # Two fixed-shape variants, compiled once each; NO donation —
        # live requests hold pages under the step (hvdverify
        # serve.step forbid_donation; the tp variants serve.step_tp
        # keep the same invariant — shards of a live page must stay
        # readable under the step on every chip).
        if self.tp > 1:
            from jax.sharding import PartitionSpec as P

            mesh = self.logical_mesh.mesh
            kv = self._kv_spec
            # dec/pre arrive replicated (P() prefix over the host
            # dicts), pages head-sharded in AND out, logits replicated
            # full-vocab (the step's all-gather makes them so).
            # The spec step returns (pages, ver_logits, draft_toks,
            # draft_logits, pre_logits) — two extra replicated outputs
            # over the base step's (pages, dec_logits, pre_logits).
            n_rep = 4 if self.spec_k else 2
            self._step_mixed = jax.jit(jax.shard_map(
                lambda p, pages, dec, pre: step(p, pages, dec, pre),
                mesh=mesh,
                in_specs=(self._param_specs, kv, P(), P()),
                out_specs=(kv,) + (P(),) * n_rep, check_vma=False))
            self._step_decode = jax.jit(jax.shard_map(
                lambda p, pages, dec: step(p, pages, dec, None),
                mesh=mesh,
                in_specs=(self._param_specs, kv, P()),
                out_specs=(kv,) + (P(),) * n_rep, check_vma=False))
        else:
            self._step_mixed = jax.jit(step)
            self._step_decode = jax.jit(
                lambda params, pages, dec: step(params, pages, dec,
                                                None))

    def _place_params(self, params: Dict) -> Dict:
        """Where the compiled step expects the weights: head / feature
        / vocab shards over the tp mesh, or whole on this engine's
        pinned device."""
        import jax

        if self.tp > 1:
            from jax.sharding import NamedSharding

            mesh = self.logical_mesh.mesh
            return jax.tree_util.tree_map(
                lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                params, self._param_specs)
        if self.device is not None:
            return jax.device_put(params, self.device)
        return params

    # ------------------------------------------------------ submission

    def submit(self, prompt, max_new_tokens: int, *,
               temperature: float = 0.0, top_k: int = 0,
               eos_token: Optional[int] = None, seed: int = 0,
               arrival: Optional[float] = None,
               ttl: Optional[float] = None) -> Request:
        """Queue one generation request; returns it (check ``state`` —
        ``rejected`` means it can never run or the queue is full).
        ``ttl`` (seconds from arrival; default ``config.default_ttl``)
        bounds how long the request may live: past it, the request is
        finished with the ``timeout`` status and its pages freed."""
        from horovod_tpu.serve.scheduler import make_request

        req = make_request(self.config, self.clock, prompt,
                           max_new_tokens, temperature=temperature,
                           top_k=top_k, eos_token=eos_token, seed=seed,
                           arrival=arrival, ttl=ttl)
        self.scheduler.submit(req)
        return req

    # ------------------------------------------------------- lifecycle

    @property
    def in_flight(self) -> int:
        return (sum(1 for s in self.slots if s is not None)
                + len(self.ready) + (1 if self.prefilling else 0)
                + len(self.handoff))

    @property
    def idle(self) -> bool:
        return (self.in_flight == 0 and not self.scheduler.queue)

    def _free_slots(self) -> int:
        return sum(1 for s in self.slots if s is None)

    def _finish(self, req: Request) -> None:
        req.state = RequestState.FINISHED
        req.t_finish = self.clock()
        self.scheduler.release(req)
        self.finished.append(req)

    def _do_evict(self, victim: Request) -> None:
        """Release a victim's pages and remove it from service; requeue
        (recompute path) or terminate per config."""
        self._remove_from_service(victim)
        victim.evictions += 1
        victim.state = RequestState.EVICTED
        if self.config.requeue_evicted:
            if not self.scheduler.requeue(victim):
                self._finish(victim)
        else:
            self.evicted.append(victim)

    def _remove_from_service(self, req: Request) -> None:
        """Release the request's pages and detach it from every service
        structure (slots, ready, prefill lane) — the shared half of
        eviction and deadline timeout."""
        self.scheduler.release(req)
        for i, s in enumerate(self.slots):
            if s is req:
                self.slots[i] = None
        self.ready = [r for r in self.ready if r is not req]
        self.handoff = [r for r in self.handoff if r is not req]
        if self.prefilling is req:
            self.prefilling = None

    def _time_out(self, req: Request, now: float) -> None:
        """Deadline epilogue: remove from service, mark terminal.
        Unlike eviction there is no requeue — the client's latency
        budget is already blown; recomputing for a dead stream would
        only steal step time from live ones."""
        self._remove_from_service(req)
        self.scheduler.drop(req)
        req.state = RequestState.TIMEOUT
        req.t_finish = now
        self.timed_out.append(req)

    def _expire_deadlines(self) -> None:
        """Sweep every live request (queued included — a request can
        blow its deadline waiting) at the top of each step; one wedged
        stream can never hold KV pages past its deadline + one step."""
        now = self.clock()
        live = ([s for s in self.slots if s is not None]
                + list(self.ready) + list(self.handoff)
                + ([self.prefilling] if self.prefilling else [])
                + list(self.scheduler.queue))
        for req in live:
            if req.expired(now):
                self._time_out(req, now)

    def _evict_for(self, requester: Request) -> bool:
        """Lazy-mode page pressure: evict the newest-admitted request
        that is not the requester (and not mid-prefill-chunk). False =
        nothing else to evict; the caller evicts the requester.
        Prefix-index-only holds go FIRST — reclaiming a cold cached
        prefix costs a future re-prefill, evicting a live request
        costs a certain recompute — and shared pages are never victims
        either way (a victim's release only frees its exclusively-held
        pages; the refcounted path keeps the rest alive)."""
        if self.prefix is not None and self.prefix.reclaim(1):
            return True
        candidates = [s for s in self.slots if s is not None] + \
            list(self.ready)
        victim = pick_victim(candidates, requester)
        if victim is None:
            return False
        self._do_evict(victim)
        return True

    # ------------------------------------------------------------ step

    def _promote_ready(self) -> None:
        for i in range(len(self.slots)):
            if self.slots[i] is None and self.ready:
                req = self.ready.pop(0)
                req.state = RequestState.DECODE
                self.slots[i] = req

    def _ensure_capacity(self) -> None:
        """Lazy admission: map pages for every position this step
        writes, evicting under pressure (reserve mode pre-granted the
        worst case — nothing to do)."""
        if self.config.admission != "lazy":
            return
        for req in list(self.slots):
            if req is None or req not in self.slots:
                continue
            # Speculation widens the write range: the verify window
            # lands rows t..t+k_eff, so every page under the WHOLE
            # window must be mapped before the step.
            last = req.next_pos + (req.spec_window(self.spec_k)
                                   if self.spec_k else 0)
            if not self.scheduler.ensure_pages(req, last,
                                               self._evict_for):
                self._do_evict(req)
        if self.prefilling is not None:
            req = self.prefilling
            chunk = min(self.config.prefill_chunk,
                        req.prompt_len - req.prefill_pos)
            last = req.prefill_pos + chunk - 1
            if not self.scheduler.ensure_pages(req, last,
                                               self._evict_for):
                self._do_evict(req)

    def _cow_guard(self) -> None:
        """Copy-on-write backstop: no page this step WRITES may be
        shared. By construction it never is — only FULL prompt pages
        are indexable, a match never covers the whole prompt, and both
        prefill (positions >= prefill_pos = matched tokens) and decode
        (positions >= prompt_len) write past every shared slot — so
        this sweep finds nothing in normal operation. It stays because
        a shared write would silently corrupt every OTHER holder's
        stream: any slip in the invariant becomes one counted page
        copy (``cow_copies``) instead of a wrong token."""
        if self.prefix is None:
            return
        for req in self.slots:
            if req is not None and req.generated:
                # Speculative ticks write the whole verify window
                # t..t+k_eff — a rejected row rolled back by page
                # arithmetic must STILL never have landed on a shared
                # page, so the guard covers the full range.
                last = req.next_pos + (req.spec_window(self.spec_k)
                                       if self.spec_k else 0)
                self._cow_range(req, req.next_pos, last)
        if self.prefilling is not None:
            req = self.prefilling
            chunk = min(self.config.prefill_chunk,
                        req.prompt_len - req.prefill_pos)
            self._cow_range(req, req.prefill_pos,
                            req.prefill_pos + chunk - 1)

    def _cow_range(self, req: Request, first_pos: int, last_pos: int
                   ) -> None:
        ps = self.config.page_size
        for slot in range(first_pos // ps, last_pos // ps + 1):
            page = int(req.page_table[slot])
            if page and self.cache.allocator.is_shared(page):
                new = self.cache.cow_page(page)
                req.page_table[slot] = new
                req.pages[req.pages.index(page)] = new
                self.cow_copies += 1

    def _build_dec(self):
        S = self.config.decode_slots
        pps = self.cache.pages_per_seq
        tok = np.zeros((S,), np.int32)
        pos = np.zeros((S,), np.int32)
        active = np.zeros((S,), bool)
        tables = np.zeros((S, pps), np.int32)
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            tok[i] = req.generated[-1]
            pos[i] = req.next_pos
            active[i] = True
            tables[i] = req.page_table
        dec = {"tok": tok, "pos": pos, "active": active,
               "tables": tables}
        if self.spec_k:
            # The speculation plane: width = k_eff+1 verify rows per
            # slot (0 = idle lane — it subsumes `active` in the spec
            # step) plus the draft's in-step sampling knobs.
            width = np.zeros((S,), np.int32)
            temp = np.zeros((S,), np.float32)
            topk = np.zeros((S,), np.int32)
            seed = np.zeros((S,), np.int32)
            sidx = np.zeros((S,), np.int32)
            for i, req in enumerate(self.slots):
                if req is None:
                    continue
                width[i] = req.spec_window(self.spec_k) + 1
                temp[i] = req.temperature
                topk[i] = req.top_k
                seed[i] = req.seed
                sidx[i] = req.sample_index
            dec.update(width=width, temp=temp, topk=topk, seed=seed,
                       sidx=sidx)
        return dec

    def _build_pre(self):
        if self.prefilling is None:
            return None, 0
        req = self.prefilling
        C = self.config.prefill_chunk
        chunk = min(C, req.prompt_len - req.prefill_pos)
        tokens = np.zeros((C,), np.int32)
        tokens[:chunk] = req.prompt[req.prefill_pos:
                                    req.prefill_pos + chunk]
        # page_table is never None here: Scheduler._admit assigns it
        # before pick_prefill returns the request.
        return {
            "tokens": tokens,
            "start": np.int32(req.prefill_pos),
            "length": np.int32(chunk),
            "table": np.asarray(req.page_table, np.int32),
        }, chunk

    def step(self) -> bool:
        """Run one compiled step; False when there was nothing to do
        (no active requests and nothing admissible in the queue)."""
        from horovod_tpu.serve.sampling import sample_tokens

        self._expire_deadlines()
        self._promote_ready()
        if self.prefilling is None:
            self.prefilling = self.scheduler.pick_prefill(
                self._free_slots(), self.in_flight)
            if self.prefilling is not None:
                # (Re-)admission stamp — pick_victim's newest-admitted-
                # first eviction order keys on this.
                self.prefilling.t_admit = self.clock()
        self._ensure_capacity()
        # Eviction may have freed slots: promote, then re-map pages for
        # the newly promoted rows. A promoted request whose next write
        # starts a fresh page slot must not reach the compiled step
        # with an unmapped (0) table entry — that row would write into
        # the reserved null page and silently corrupt its KV stream.
        # Terminates: each pass pops at least one request off `ready`
        # (evictions requeue to the scheduler, never back onto ready).
        while self.ready and any(s is None for s in self.slots):
            self._promote_ready()
            self._ensure_capacity()
        if self.prefilling is None and \
                all(s is None for s in self.slots):
            return False

        self._cow_guard()
        dec = self._build_dec()
        pre, chunk = self._build_pre()
        # Static traffic accounting for this step's decode lane (live
        # keys per slot = t+1; under speculation the verify window
        # extends the read range to t+k_eff, so live keys =
        # next_pos + spec_window + 1) — pure host data, no device sync.
        self.attn_len_samples.append(
            [0 if r is None else
             r.next_pos + (r.spec_window(self.spec_k)
                           if self.spec_k else 0) + 1
             for r in self.slots])

        import jax.numpy as jnp

        S = self.config.decode_slots
        pre_done = (self.prefilling is not None and
                    self.prefilling.prefill_pos + chunk
                    >= self.prefilling.prompt_len)

        if self.spec_k:
            from horovod_tpu.serve.sampling import speculative_accept

            if pre is None:
                pages, ver_logits, draft_toks, draft_logits, _ = \
                    self._step_decode(self.params, self.cache.pages,
                                      dec)
                pre_logits = None
            else:
                (pages, ver_logits, draft_toks, draft_logits,
                 pre_logits) = self._step_mixed(
                    self.params, self.cache.pages, dec, pre)
            self.cache.pages = pages

            ver = np.asarray(ver_logits)        # [S, k+1, V]
            dts = np.asarray(draft_toks)        # [S, k]
            dls = np.asarray(draft_logits)      # [S, k, V]
            pre_token = None
            if pre_logits is not None and pre_done:
                # The prefill lane's FIRST token is a plain 1-row
                # non-speculative draw — same sampler, same key.
                preq = self.prefilling
                pre_token = int(np.asarray(sample_tokens(
                    jnp.asarray(pre_logits)[None],
                    np.asarray([preq.temperature], np.float32),
                    np.asarray([preq.top_k], np.int32),
                    np.asarray([preq.seed], np.int32),
                    np.asarray([preq.sample_index], np.int32)))[0])
            now = self.clock()      # after the d2h pull: a real sync

            for i in range(S):
                req = self.slots[i]
                if req is None:
                    continue
                wd = int(dec["width"][i])
                emitted = speculative_accept(
                    ver[i, :wd], dts[i, :wd - 1], dls[i, :wd - 1],
                    temperature=float(req.temperature),
                    top_k=int(req.top_k), seed=int(req.seed),
                    position0=int(req.sample_index))
                self.spec_ticks += 1
                self.spec_proposed += wd - 1
                self.spec_accepted += len(emitted) - 1
                for tok in emitted:
                    self.spec_emitted += 1
                    self._accept_token(req, int(tok), now)
                    if req.state == RequestState.FINISHED:
                        # EOS (or the budget) mid-window: later
                        # emitted tokens are dropped; the stale KV
                        # rows past the cut go with the request's
                        # pages.
                        break
                if req.state == RequestState.FINISHED:
                    self.slots[i] = None
        else:
            if pre is None:
                pages, dec_logits, _ = self._step_decode(
                    self.params, self.cache.pages, dec)
                pre_logits = None
            else:
                pages, dec_logits, pre_logits = self._step_mixed(
                    self.params, self.cache.pages, dec, pre)
            self.cache.pages = pages

            # One sampler call covers the decode slots + the prefill
            # lane.
            rows = list(self.slots)
            logits = dec_logits
            if pre_logits is not None:
                rows = rows + [self.prefilling if pre_done else None]
                logits = jnp.concatenate(
                    [dec_logits, pre_logits[None]], 0)
            n = len(rows)
            temp = np.zeros((n,), np.float32)
            topk = np.zeros((n,), np.int32)
            seeds = np.zeros((n,), np.int32)
            positions = np.zeros((n,), np.int32)
            for i, req in enumerate(rows):
                if req is None:
                    continue
                temp[i] = req.temperature
                topk[i] = req.top_k
                seeds[i] = req.seed
                positions[i] = req.sample_index
            tokens = np.asarray(sample_tokens(logits, temp, topk,
                                              seeds, positions))
            now = self.clock()      # after the d2h pull: a real sync
            pre_token = (int(tokens[S])
                         if pre_logits is not None and pre_done
                         else None)

            # Decode slots: one new token each.
            for i in range(S):
                req = self.slots[i]
                if req is None:
                    continue
                self._accept_token(req, int(tokens[i]), now)
                if req.state == RequestState.FINISHED:
                    self.slots[i] = None

        # Prefill lane: advance; on completion emit the FIRST token.
        if self.prefilling is not None and pre is not None:
            req = self.prefilling
            req.prefill_pos += chunk
            if pre_done:
                if self.prefix is not None:
                    # Index the now-filled prompt pages BEFORE the
                    # first token can finish the request (max_new=1 —
                    # _finish releases its pages; the insert's retain
                    # must land while the request still holds them).
                    self.prefix.insert(req.prompt, req.page_table)
                self._accept_token(req, pre_token, now)
                self.prefilling = None
                if req.state != RequestState.FINISHED:
                    req.state = RequestState.DECODE
                    if req.prefill_only:
                        # Disaggregated handoff: park fully prefilled
                        # (pages held, first token emitted) until the
                        # fleet ships the KV pages to a decode
                        # replica. A request that finished ON its
                        # first token never reaches here — it needs no
                        # decode pool.
                        self.handoff.append(req)
                    else:
                        self.ready.append(req)

        self.occupancy_samples.append(self.cache.occupancy())
        self.steps += 1
        return True

    def _accept_token(self, req: Request, token: int, now: float
                      ) -> None:
        req.generated.append(token)
        req.output.append(token)
        if req.t_first_token is None:
            req.t_first_token = now
        req.token_times.append(now)
        if req.done_generating or req.hit_eos(self.config.eos_token):
            self._finish(req)

    # ------------------------------------- disaggregated prefill/decode

    def _handoff_req(self, rid: str) -> Request:
        for r in self.handoff:
            if r.rid == rid:
                return r
        raise KeyError(f"no parked handoff request {rid!r} — expired, "
                       "already released, or never parked here")

    def handoff_ready(self) -> List[str]:
        """rids parked in the handoff bay (prefill finished, KV pages
        ready to ship)."""
        return [r.rid for r in self.handoff]

    def export_handoff(self, rid: str) -> bytes:
        """The parked request's finished KV pages as one deterministic
        blob (:meth:`PagedKVCache.export_pages
        <horovod_tpu.serve.kvcache.PagedKVCache.export_pages>` over the
        page-table prefix covering the prompt). READ-ONLY and
        repeatable — a torn transfer re-exports identical bytes, which
        is what makes the chunk stream's resume-from-offset sound."""
        req = self._handoff_req(rid)
        n_exp = self.cache.pages_needed(req.prompt_len, 1)
        pages = [int(req.page_table[j]) for j in range(n_exp)]
        return self.cache.export_pages(pages, req.prompt_len)

    def release_handoff(self, rid: str) -> Request:
        """Drop the prefill side's hold once the decode replica has
        COMMITTED the import: pages release through the refcounted path
        (prefix-shared pages stay alive under the index) and the
        request leaves every service structure WITHOUT a terminal
        event — ownership moved, the stream did not end. Returns the
        request (the inproc fleet re-uses the very same object on the
        decode side)."""
        req = self._handoff_req(rid)
        self.scheduler.release(req)
        self.handoff = [r for r in self.handoff if r is not req]
        return req

    def admit_prefilled(self, req: Request, blob: bytes) -> None:
        """Decode-side handoff admission: import the shipped KV pages
        into THIS cache's allocator, grant the remainder of the
        request's worst-case budget (reserve discipline — admitted
        means it can run to completion), map the page table, and queue
        the request at its handoff position (``ready``, state DECODE —
        the next step promotes it into a slot and decodes token 2
        onward; token 1 was emitted prefill-side). All-or-nothing:
        :class:`~horovod_tpu.serve.kvcache.OutOfPages` or a typed
        geometry :class:`~horovod_tpu.serve.transport.FrameError`
        leaves this engine unchanged, and the caller's handoff stays
        parked on the prefill side (retry or redispatch — never a
        half-admitted request)."""
        from horovod_tpu.serve.transport import FrameError

        imported, positions = self.cache.import_pages(blob)
        try:
            if positions != req.prompt_len:
                raise FrameError(
                    f"handoff blob covers {positions} positions, "
                    f"request prompt is {req.prompt_len} — wrong blob "
                    "for this request")
            total = self.cache.pages_needed(req.prompt_len,
                                            req.max_new_tokens)
            extra = self.cache.allocator.alloc(total - len(imported))
        except BaseException:
            self.cache.allocator.release(imported)
            raise
        req.pages = list(imported) + list(extra)
        req.page_table = np.zeros(self.cache.pages_per_seq, np.int32)
        req.page_table[:total] = np.asarray(req.pages, np.int32)
        req.prefill_pos = req.prompt_len
        req.state = RequestState.DECODE
        req.t_admit = self.clock()
        self.ready.append(req)

    def update_params(self, params: Dict) -> None:
        """Swap the model weights in place — the fleet's rolling-update
        primitive. Only valid when IDLE: a live request's decode must
        never mix weights mid-stream (the fleet drains the replica
        before pushing). Geometry must match the compiled programs'
        shapes, so the jitted step variants re-trace nothing — a
        geometry change is a respawn, not an update."""
        if not self.idle:
            raise RuntimeError(
                "update_params with requests in flight — drain the "
                "engine first (the fleet's rolling update does)")
        old, new = self.params["pos"].shape, params["pos"].shape
        if tuple(old) != tuple(new):
            raise ValueError(
                f"update_params geometry mismatch: position table "
                f"{tuple(new)} vs the engine's {tuple(old)} — a "
                "geometry change needs a fresh engine, not a weight "
                "swap")
        self.params = self._place_params(params)
        if self.prefix is not None:
            # K/V rows are a function of the weights: stale-version
            # pages must never serve a new-version request.
            self.prefix.flush()

    # ------------------------------------------------------------- run

    def run(self, max_steps: Optional[int] = None) -> List[Request]:
        """Drain to idle (or ``max_steps``); returns requests finished
        so far."""
        while not self.idle:
            if max_steps is not None and self.steps >= max_steps:
                break
            if not self.step():
                break   # queue non-empty but nothing admissible
        return self.finished

    def reset_metrics(self) -> None:
        """Drop completed-work bookkeeping (the bench warmup
        discipline: compile+warm through a dummy request, then measure
        from a clean slate). Only valid when idle."""
        if not self.idle:
            raise RuntimeError("reset_metrics with requests in flight")
        self.finished = []
        self.evicted = []
        self.timed_out = []
        self.scheduler.rejected = []
        self.occupancy_samples = []
        self.attn_len_samples = []
        self.steps = 0
        self.cow_copies = 0
        self.spec_ticks = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_emitted = 0
        if self.prefix is not None:
            self.prefix.reset_metrics()
        self._t_start = self.clock()

    def stats(self) -> Dict:
        """Aggregate SLO metrics over every request seen so far."""
        from horovod_tpu.serve.metrics import summarize

        everything = (self.finished + self.evicted + self.timed_out
                      + self.ready + self.handoff
                      + [s for s in self.slots if s is not None]
                      + ([self.prefilling] if self.prefilling else [])
                      + self.scheduler.queue + self.scheduler.rejected)
        out = summarize(everything, self.clock() - self._t_start,
                        self.chips, self.occupancy_samples)
        out["attention"] = self.attention_stats()
        ps = self.prefix_stats()
        if ps is not None:
            out["prefix"] = ps
        sp = self.spec_stats()
        if sp is not None:
            out["spec"] = sp
        return out

    def spec_stats(self) -> Optional[Dict]:
        """Speculation accounting over the run (None when speculation
        is off — consumers must tolerate the key's absence, exactly
        the ``prefix`` discipline). ``accept_rate`` = accepted
        proposals over draft proposals; ``tokens_per_step`` = tokens
        emitted per per-slot speculative tick — > 1 is the whole point
        (k+1 at a perfect draft, 1 at a useless one: never slower in
        tokens, only in wasted verify FLOPs)."""
        if not self.spec_k:
            return None
        return {
            "k": self.spec_k,
            "draft_layers": self.draft_layers,
            "ticks": self.spec_ticks,
            "proposed": self.spec_proposed,
            "accepted": self.spec_accepted,
            "accept_rate":
                (round(self.spec_accepted / self.spec_proposed, 4)
                 if self.spec_proposed else None),
            "tokens_per_step":
                (round(self.spec_emitted / self.spec_ticks, 4)
                 if self.spec_ticks else None),
        }

    def prefix_stats(self) -> Optional[Dict]:
        """Prefix-cache accounting over the run (None when the cache
        is off — consumers must tolerate the key's absence: pre-prefix
        engines and stub workers never stamp it). ``hit_rate`` is
        hits over ADMITTED requests; ``prefill_tokens_saved`` the
        prompt tokens whose prefill compute a hit skipped."""
        if self.prefix is None:
            return None
        s = self.prefix.stats()
        s["hit_rate"] = (round(s["hits"] / s["lookups"], 4)
                         if s["lookups"] else None)
        s["prefill_tokens_saved"] = s["tokens_hit"]
        s["cow_copies"] = self.cow_copies
        s["pages_shared_now"] = self.cache.allocator.shared
        return s

    def step_grid_info(self, lengths: List[int]) -> Dict:
        """One step's static decode-traffic accounting — exactly
        :func:`ops.paged_attention.paged_grid_info` over this engine's
        cache geometry (the single owner of the byte model)."""
        import numpy as np

        from horovod_tpu.ops.paged_attention import paged_grid_info

        c = self.cache
        return paged_grid_info(
            lengths, page_size=self.config.page_size,
            pages_per_seq=c.pages_per_seq, num_heads=c.num_heads,
            head_dim=c.head_dim,
            dtype_bytes=np.dtype(c.dtype).itemsize,
            num_layers=c.num_layers, tp=self.tp)

    def attention_stats(self) -> Dict:
        """Decode-lane K/V traffic accounting over the run: what the
        paged kernel streams (live pages, ``ceil((t+1)/page_size)``
        per slot) vs what the gather path reconstructs (``Lmax/
        page_size`` pages per slot, every slot every step) — the
        per-step :func:`ops.paged_attention.paged_grid_info` results
        aggregated. Stamped on BOTH modes so the gather/paged A/B is
        honest on both sides; the prefill lane (full gather in both
        modes) is excluded by construction."""
        infos = [self.step_grid_info(s) for s in self.attn_len_samples]
        n = len(infos)
        total_live = sum(i["pages_live_total"] for i in infos)
        total_paged = sum(i["kv_bytes"] for i in infos)
        total_gather = sum(i["kv_bytes_gather"] for i in infos)
        # Per-chip bytes of THIS mode's policy (paged streams live
        # pages, gather reconstructs the full table): heads shard
        # exactly, so per-chip is 1/tp of the totals — the honest form
        # of the TP bandwidth claim (`serve_bench --ab-tp` pins
        # kv_bytes_per_chip <= unsharded/tp).
        total_chip = (total_paged if self.config.attention == "paged"
                      else total_gather) // self.tp
        return {
            "mode": self.config.attention,
            "decode_steps": n,
            "page_size": self.config.page_size,
            "pages_per_seq": self.cache.pages_per_seq,
            "pages_live_per_step_mean":
                round(total_live / n, 2) if n else None,
            "pages_full_per_step":
                self.config.decode_slots * self.cache.pages_per_seq,
            "kv_bytes_per_step_paged":
                round(total_paged / n, 1) if n else None,
            "kv_bytes_per_step_gather":
                total_gather // n if n else None,
            "kv_fetch_frac":
                round(total_paged / total_gather, 4) if n else None,
            "tp": self.tp,
            "kv_bytes_per_chip":
                round(total_chip / n, 1) if n else None,
        }
