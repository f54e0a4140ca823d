"""Composed-parallelism GPT-style LM: dp x sp x tp in one model.

The reference scaled batch only (SURVEY §2.9: no TP/SP anywhere); this
module is the TPU-native flagship composition the parallel/ primitives
exist for, packaged as a first-class model instead of a hand-assembled
example:

* **tp** — attention heads and MLP features shard Megatron-style
  (:mod:`horovod_tpu.parallel.tp`): column-parallel QKV/up-projection
  (no comm), row-parallel out/down-projection (one psum each);
* **sp** — the sequence axis shards across chips and attention runs the
  exact ring schedule (:mod:`horovod_tpu.parallel.ring_attention`),
  with positional embeddings and the causal mask taken at global
  positions;
* **dp** — data parallelism is the caller's batch sharding plus the
  uniform gradient pmean this module's loss helper pairs with.

Everything is pure functions over an explicit parameter pytree, the
idiom of :mod:`horovod_tpu.parallel`: build DENSE (unsharded) params
with :func:`init_lm_params`, hand them to ``shard_map`` with
:func:`lm_param_specs` as ``in_specs`` — the mesh slices the dense
arrays onto chips — and call :func:`lm_apply` inside. With
``sp=tp=None`` the same functions run the dense math on one device,
which is exactly what the exactness tests compare against.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.ops.attention import dot_product_attention
from horovod_tpu.parallel.ring_attention import ring_attention
from horovod_tpu.parallel.tp import (
    sum_across,
    tp_mlp,
    tp_region_input,
    tp_region_output,
)


def init_lm_params(rng, vocab: int, max_len: int, layers: int, heads: int,
                   head_dim: int, ffn: int, dtype=jnp.float32) -> Dict:
    """Dense (unsharded) parameter pytree. Shapes keep the head and
    feature axes explicit so the tp specs can shard them:
    wqkv [E, 3, H, Dh], wo [H, Dh, E], wup [E, F], wdn [F, E]."""
    embed_dim = heads * head_dim
    keys = jax.random.split(rng, 2 * layers + 3)

    def dense_init(key, shape, fan_in):
        return (jax.random.normal(key, shape) / math.sqrt(fan_in)).astype(dtype)

    params: Dict[str, Any] = {
        "embed": dense_init(keys[0], (vocab, embed_dim), embed_dim),
        "pos": dense_init(keys[1], (max_len, embed_dim), embed_dim),
        "layers": [],
        "ln_f": {"g": jnp.ones((embed_dim,), dtype),
                 "b": jnp.zeros((embed_dim,), dtype)},
        "head": dense_init(keys[2], (embed_dim, vocab), embed_dim),
    }
    for i in range(layers):
        ka, kb, kc = jax.random.split(keys[3 + 2 * i], 3)
        kd = keys[4 + 2 * i]
        params["layers"].append({
            "ln1": {"g": jnp.ones((embed_dim,), dtype),
                    "b": jnp.zeros((embed_dim,), dtype)},
            "wqkv": dense_init(ka, (embed_dim, 3, heads, head_dim),
                               embed_dim),
            "wo": dense_init(kb, (heads, head_dim, embed_dim), embed_dim),
            "bo": jnp.zeros((embed_dim,), dtype),
            "ln2": {"g": jnp.ones((embed_dim,), dtype),
                    "b": jnp.zeros((embed_dim,), dtype)},
            "wup": dense_init(kc, (embed_dim, ffn), embed_dim),
            "bup": jnp.zeros((ffn,), dtype),
            "wdn": dense_init(kd, (ffn, embed_dim), ffn),
            "bdn": jnp.zeros((embed_dim,), dtype),
        })
    return params


def lm_param_specs(layers: int, tp_axis: Optional[str],
                   vocab_parallel: bool = False):
    """PartitionSpec pytree matching :func:`init_lm_params`' structure.

    Pass as the params entry of ``shard_map``'s ``in_specs`` (and
    ``out_specs`` for the updated state): the mesh then slices the DENSE
    arrays — heads/features over ``tp_axis``, everything else
    replicated. ``tp_axis=None`` replicates everything.
    ``vocab_parallel`` additionally shards the vocab projection
    [E, V] over ``tp_axis`` — pair with
    :func:`next_token_nll_fused`'s vocab-parallel loss (the plain
    :func:`lm_apply` logits path assumes a replicated head)."""
    from jax.sharding import PartitionSpec as P

    t = tp_axis
    layer_spec = {
        "ln1": {"g": P(), "b": P()},
        "wqkv": P(None, None, t, None),
        "wo": P(t, None, None),
        "bo": P(),
        "ln2": {"g": P(), "b": P()},
        "wup": P(None, t),
        "bup": P(t),
        "wdn": P(t, None),
        "bdn": P(),
    }
    return {
        "embed": P(),
        "pos": P(),
        "layers": [dict(layer_spec) for _ in range(layers)],
        "ln_f": {"g": P(), "b": P()},
        "head": P(None, t) if vocab_parallel else P(),
    }


def _layernorm(x, g, b):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * lax.rsqrt(var + 1e-5)).astype(x.dtype) * g + b


def _project_qkv(layer, x, tp):
    """ln1 -> (Megatron f) -> fused QKV projection onto local heads."""
    a = _layernorm(x, layer["ln1"]["g"], layer["ln1"]["b"])
    if tp:
        # Megatron f: upstream grads must SUM the per-head-shard
        # cotangents (identity fwd, psum bwd).
        a = tp_region_input(a, tp)
    qkv = jnp.einsum("ble,ethd->blthd", a, layer["wqkv"])
    return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]


def _attn_out_residual(layer, attn, x, tp):
    """Row-parallel output projection (Megatron g) + residual."""
    proj = jnp.einsum("blhd,hde->ble", attn, layer["wo"])
    if tp:
        proj = tp_region_output(proj, tp)
    return x + proj + layer["bo"]


def _ffn_residual(layer, x, tp):
    m = _layernorm(x, layer["ln2"]["g"], layer["ln2"]["b"])
    if tp:
        m = tp_region_input(m, tp)
        return x + tp_mlp(m, layer["wup"], layer["bup"], layer["wdn"],
                          layer["bdn"], axis=tp)
    h = jax.nn.gelu(m @ layer["wup"] + layer["bup"])
    return x + h @ layer["wdn"] + layer["bdn"]


def _final_hidden(params, x):
    return _layernorm(x, params["ln_f"]["g"], params["ln_f"]["b"])


def _logits(params, x, tp=None, vocab_parallel: bool = False):
    """Final LayerNorm + vocab projection -> full-vocab logits.

    With ``vocab_parallel`` the head arrives column-sharded [E, V/tp]
    (:func:`lm_param_specs` ``vocab_parallel=True``) and the full row
    is assembled by ONE tiled all-gather
    (:func:`~horovod_tpu.parallel.tp.vocab_parallel_logits`) — the
    serving path's spelling; training-side fused losses consume the
    shard directly and never materialize this tensor."""
    h = _final_hidden(params, x)
    if vocab_parallel:
        if not tp:
            raise ValueError("vocab_parallel logits need a tp axis")
        from horovod_tpu.parallel.tp import vocab_parallel_logits

        return vocab_parallel_logits(h, params["head"], axis=tp)
    return h @ params["head"]


def lm_apply(params: Dict, tokens, sp: Optional[str] = None,
             tp: Optional[str] = None, return_hidden: bool = False):
    """Token ids [B, L_local] -> logits [B, L_local, vocab].

    Inside ``shard_map``: ``sp`` names the sequence axis (tokens arrive
    sequence-sharded; ring attention, global positions), ``tp`` the
    tensor axis (params arrive head/feature-sharded via
    :func:`lm_param_specs`). Both None = dense single-device math.

    ``return_hidden`` stops after the final LayerNorm and returns
    [B, L_local, E] — for the fused losses (:func:`next_token_nll_fused`)
    that consume ``params["head"]`` directly and never materialize the
    [B, L, vocab] logits."""
    B, L = tokens.shape
    pos_offset = lax.axis_index(sp) * L if sp else 0
    x = params["embed"][tokens]
    x = x + lax.dynamic_slice_in_dim(params["pos"], pos_offset, L, 0)[None]

    for layer in params["layers"]:
        q, k, v = _project_qkv(layer, x, tp)
        scale = 1.0 / math.sqrt(q.shape[-1])
        if sp:
            attn = ring_attention(q, k, v, axis=sp, causal=True,
                                  scale=scale)
        else:
            attn = dot_product_attention(q, k, v, causal=True, scale=scale)
        x = _attn_out_residual(layer, attn, x, tp)
        x = _ffn_residual(layer, x, tp)

    if return_hidden:
        return _final_hidden(params, x)
    return _logits(params, x)


def lm_prefill(params: Dict, prompt, tp: Optional[str] = None):
    """Full forward over the prompt, capturing each layer's K/V into
    fixed-size [B, Lmax, H, D] caches (Lmax = the position table).

    The cache-plumbing half of :func:`lm_decode`, public so serving
    paths (:mod:`horovod_tpu.serve`) and tests can compose it with
    :func:`lm_decode_step` directly. Returns ``(caches, logits_last)``:
    per-layer ``{"k", "v"}`` dicts plus the last position's logits
    [B, vocab] — what the first generated token is sampled from."""
    B, Lp = prompt.shape
    Lmax = params["pos"].shape[0]
    x = params["embed"][prompt] + params["pos"][None, :Lp]
    caches = []
    for layer in params["layers"]:
        q, k, v = _project_qkv(layer, x, tp)
        scale = 1.0 / math.sqrt(q.shape[-1])
        pad = [(0, 0), (0, Lmax - Lp), (0, 0), (0, 0)]
        caches.append({"k": jnp.pad(k, pad), "v": jnp.pad(v, pad)})
        attn = dot_product_attention(q, k, v, causal=True, scale=scale)
        x = _attn_out_residual(layer, attn, x, tp)
        x = _ffn_residual(layer, x, tp)
    return caches, _logits(params, x[:, -1:])[:, 0]


def lm_decode_step(params: Dict, caches, tok, t, tp: Optional[str] = None):
    """One KV-cache decode step: write ``tok``'s K/V at position ``t``,
    attend the new token against the masked cache, return
    ``(new_caches, logits)`` with logits [B, vocab].

    ``tok`` is [B] int32, ``t`` a (traced or static) scalar absolute
    position; caches are :func:`lm_prefill`'s fixed-shape pytree, so the
    step traces into one static program regardless of position. The
    body of :func:`lm_decode`'s scan, public for serving paths."""
    x = params["embed"][tok][:, None] + \
        lax.dynamic_slice_in_dim(params["pos"], t, 1, 0)[None]
    new_caches = []
    for layer, cache in zip(params["layers"], caches):
        q, k, v = _project_qkv(layer, x, tp)              # [B, 1, H, D]
        ck = lax.dynamic_update_slice_in_dim(cache["k"], k, t, 1)
        cv = lax.dynamic_update_slice_in_dim(cache["v"], v, t, 1)
        new_caches.append({"k": ck, "v": cv})
        scale = 1.0 / math.sqrt(q.shape[-1])
        # The reference kernel with q_offset=t IS the cache mask
        # (k_pos <= t; unwritten slots masked), keeping decode-step
        # numerics identical to prefill/lm_apply.
        attn = dot_product_attention(q, ck, cv, causal=True,
                                     scale=scale, q_offset=t)
        x = _attn_out_residual(layer, attn, x, tp)
        x = _ffn_residual(layer, x, tp)
    return new_caches, _logits(params, x)[:, 0]


def lm_decode(params: Dict, prompt, steps: int, temperature: float = 0.0,
              rng=None, tp: Optional[str] = None):
    """Autoregressive generation with a static-shape KV cache.

    TPU-idiomatic decode (beyond the reference, which predates LM
    serving): the whole loop is ONE ``lax.scan`` — per-layer K/V caches
    of fixed [B, Lmax, H, D] shape live in the carry and are written with
    ``dynamic_update_slice``, each step attends the new token against the
    masked cache, so the program compiles once regardless of prompt or
    generation length. ``temperature=0`` is greedy argmax; otherwise
    categorical sampling with ``rng``. Composes with tp (head-sharded
    params inside shard_map; decode is forward-only). Returns the
    generated ids [B, steps].

    Built from the public cache plumbing — :func:`lm_prefill` then a
    scanned :func:`lm_decode_step` — which the continuous-batching
    serving engine (:mod:`horovod_tpu.serve`) reuses with a paged cache
    layout; the greedy engine output is pinned token-exact against this
    function."""
    B, Lp = prompt.shape
    Lmax = params["pos"].shape[0]
    if Lp + steps > Lmax:
        raise ValueError(
            f"prompt ({Lp}) + steps ({steps}) exceeds the position table "
            f"({Lmax})")
    if temperature > 0 and rng is None:
        raise ValueError("temperature > 0 requires an rng key")

    caches, logits_last = lm_prefill(params, prompt, tp)

    def pick(logits, key):
        if temperature > 0:
            return jax.random.categorical(key, logits / temperature, axis=-1)
        return jnp.argmax(logits, axis=-1)

    def step(carry, i):
        caches, logits, key = carry
        key, sub = (jax.random.split(key) if key is not None
                    else (None, None))
        tok = pick(logits.astype(jnp.float32), sub)       # [B]
        t = Lp + i                                        # absolute position
        new_caches, logits = lm_decode_step(params, caches, tok, t, tp)
        return (new_caches, logits, key), tok

    key0 = rng if temperature > 0 else None
    (_, _, _), toks = lax.scan(step, (caches, logits_last, key0),
                               jnp.arange(steps))
    return toks.T  # [B, steps]


def draft_params(params: Dict, layers: int) -> Dict:
    """Layer-skip self-draft: the target's FIRST ``layers`` transformer
    layers sharing the target's embed/pos/ln_f/head — the speculative-
    decoding draft model as a zero-copy VIEW of the target pytree
    (list slice of the layer dicts; no array is copied).

    Why a view instead of a second trained artifact: the draft's K/V
    for layer ``l < layers`` are computed by exactly the target's first
    ``l+1`` layers, so the draft shares the target's KV cache rows, the
    target's tp sharding (head/feature divisibility holds by
    construction), and the target's params-distribution path — the
    serving fleet's wire transports and ``update_params`` need no
    second weight artifact. The result plugs straight into
    :func:`lm_decode_step` / :func:`lm_prefill`."""
    n = len(params["layers"])
    if not 1 <= layers <= n:
        raise ValueError(
            f"draft_params: layers={layers} outside 1..{n} (the target "
            "has that many transformer layers)")
    return {"embed": params["embed"], "pos": params["pos"],
            "layers": params["layers"][:layers],
            "ln_f": params["ln_f"], "head": params["head"]}


def lm_verify_window(params: Dict, caches, toks, t,
                     tp: Optional[str] = None):
    """Speculative-decoding verify pass: ONE rectangular-causal step
    over a ``w``-token window — write the window's K/V rows at
    positions ``t..t+w-1`` and return the logits at ALL ``w``
    positions, so a draft's ``w-1`` proposals are verified by a single
    target dispatch instead of ``w`` sequential decode steps.

    ``toks`` is [B, w] int32 (row 0 = the last emitted token, rows
    1..w-1 = the draft's proposals), ``t`` the window's first absolute
    position; caches are :func:`lm_prefill`'s fixed-shape pytree.
    Returns ``(new_caches, logits [B, w, vocab])``.

    The attention is exactly the chunked-prefill shape — queries at
    global positions ``t..t+w-1`` over the full masked cache with
    ``q_offset=t, k_offset=0`` — so greedy argmaxes match ``w``
    sequential :func:`lm_decode_step` calls (masked softmax terms are
    exactly zero), and ``w=1`` IS :func:`lm_decode_step` shape-for-
    shape. Rows past an accepted prefix need no erasure: the next
    window overwrites positions it reaches and the causal mask hides
    positions beyond its own last query."""
    w = toks.shape[1]
    x = params["embed"][toks] + \
        lax.dynamic_slice_in_dim(params["pos"], t, w, 0)[None]
    new_caches = []
    for layer, cache in zip(params["layers"], caches):
        q, k, v = _project_qkv(layer, x, tp)              # [B, w, H, D]
        ck = lax.dynamic_update_slice_in_dim(cache["k"], k, t, 1)
        cv = lax.dynamic_update_slice_in_dim(cache["v"], v, t, 1)
        new_caches.append({"k": ck, "v": cv})
        scale = 1.0 / math.sqrt(q.shape[-1])
        attn = dot_product_attention(q, ck, cv, causal=True,
                                     scale=scale, q_offset=t)
        x = _attn_out_residual(layer, attn, x, tp)
        x = _ffn_residual(layer, x, tp)
    return new_caches, _logits(params, x)                 # [B, w, V]


def lm_decode_spec(params: Dict, prompt, steps: int, *, k: int,
                   draft_layers: int, tp: Optional[str] = None):
    """Greedy speculative decoding, the model-level reference the
    serving engine's spec path is pinned against: the layer-skip draft
    (:func:`draft_params`) proposes up to ``k`` tokens per tick, the
    target verifies all proposals plus one bonus position in a single
    :func:`lm_verify_window` pass, and the longest prefix where draft
    and target argmaxes agree is kept (plus the target's token at the
    first mismatch — the correction — or one bonus token when every
    proposal matched).

    Provably bit-identical to greedy :func:`lm_decode`: every emitted
    token is ``argmax(float32 target logits | emitted prefix)``
    regardless of WHAT the draft proposed or where tick boundaries
    fall — proposals only decide how many target argmaxes one dispatch
    yields. Returns the generated ids [1, steps] (single-row: the
    accept rule makes rows diverge in length)."""
    B, Lp = prompt.shape
    if B != 1:
        raise ValueError(
            f"lm_decode_spec is single-row (got B={B}): acceptance "
            "lengths diverge per row")
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    Lmax = params["pos"].shape[0]
    if Lp + steps > Lmax:
        raise ValueError(
            f"prompt ({Lp}) + steps ({steps}) exceeds the position table "
            f"({Lmax})")
    dparams = draft_params(params, draft_layers)

    caches, logits_last = lm_prefill(params, prompt, tp)
    out = [int(jnp.argmax(logits_last.astype(jnp.float32), axis=-1)[0])]
    while len(out) < steps:
        t = Lp + len(out) - 1
        # Budget clamp: never verify past the generation budget (the
        # serving engine's page-grant bound is the same arithmetic).
        k_eff = min(k, steps - len(out) - 1)
        w = k_eff + 1
        # Draft proposals: k_eff sequential single-token steps over the
        # TARGET's first draft_layers caches (layer-skip shares rows);
        # the draft's writes land on a discarded branch of the pytree —
        # the verify pass below writes the rows that persist.
        dcaches = caches[:draft_layers]
        tok, d = out[-1], []
        for i in range(k_eff):
            dcaches, dlg = lm_decode_step(
                dparams, dcaches, jnp.full((1,), tok, jnp.int32),
                t + i, tp)
            tok = int(jnp.argmax(dlg.astype(jnp.float32), axis=-1)[0])
            d.append(tok)
        window = jnp.asarray([[out[-1]] + d], jnp.int32)      # [1, w]
        caches, vlg = lm_verify_window(params, caches, window, t, tp)
        tgt = jnp.argmax(vlg.astype(jnp.float32), axis=-1)[0]  # [w]
        for i in range(w):
            out.append(int(tgt[i]))
            if i < w - 1 and d[i] != int(tgt[i]):
                break   # correction emitted; rest of the window stale
    return jnp.asarray([out], jnp.int32)                  # [1, steps]


def stack_layers(params: Dict):
    """Split the param pytree for pipeline parallelism: the per-layer
    dicts stack into leading-axis arrays (shard with ``P(pp)`` so each
    stage chip holds one block), everything else stays replicated.
    Returns ``(rest, stacked_layers)``."""
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                     *params["layers"])
    rest = {k: v for k, v in params.items() if k != "layers"}
    return rest, stacked


def lm_pp_specs(rest: Dict, stacked):
    """Spec pytrees for :func:`lm_apply_pp` under shard_map: replicated
    ``rest``, stage-sharded layers."""
    from jax.sharding import PartitionSpec as P

    return (jax.tree_util.tree_map(lambda _: P(), rest),
            jax.tree_util.tree_map(lambda _: P("pp"), stacked))


def lm_apply_pp(rest: Dict, stacked_layers, tokens, axis: str = "pp",
                microbatches: int = 2, remat: bool = False):
    """Pipeline-parallel forward: one transformer block per stage chip
    (GPipe schedule over ``axis``, :mod:`horovod_tpu.parallel.pipeline`).

    ``stacked_layers`` leaves carry a leading [n_layers] axis sharded
    ``P(axis)`` — n_layers must equal the axis size. Embedding and head
    run replicated on every stage chip; the batch splits into
    ``microbatches``. Exactness (forward AND gradients, thanks to the
    exact-VJP pipeline sum) vs the flat :func:`lm_apply` is pinned in
    tests/test_parallel_lm.py."""
    from horovod_tpu.parallel.pipeline import pipeline_apply

    B, L = tokens.shape
    M = microbatches
    if B % M != 0:
        raise ValueError(
            f"lm_apply_pp: batch {B} must divide into microbatches={M} "
            f"(each stage tick processes one microbatch of B/M sequences)")
    leaves = jax.tree_util.tree_leaves(stacked_layers)
    n_stages = lax.axis_size(axis)
    if leaves and leaves[0].shape[0] != 1:
        # Inside shard_map with P(axis) on the stack, the per-chip view
        # keeps a length-1 leading stage axis (n_layers == axis size).
        # Anything else — a mis-sized stack, or a full stack passed
        # replicated without the P(axis) in_spec — would surface as a
        # cryptic reshape/einsum error deep inside pipeline_apply.
        raise ValueError(
            f"lm_apply_pp: per-chip stacked_layers leading dim is "
            f"{leaves[0].shape[0]}, expected 1 — pass n_layers == "
            f"'{axis}' axis size ({n_stages}) blocks sharded with "
            f"P('{axis}') (one transformer block per stage chip)")
    x = rest["embed"][tokens] + rest["pos"][None, :L]
    xm = x.reshape(M, B // M, L, x.shape[-1])

    def stage(layer, a):
        q, k, v = _project_qkv(layer, a, None)
        scale = 1.0 / math.sqrt(q.shape[-1])
        attn = dot_product_attention(q, k, v, causal=True, scale=scale)
        a = _attn_out_residual(layer, attn, a, None)
        return _ffn_residual(layer, a, None)

    out = pipeline_apply(stage, stacked_layers, xm, axis, remat=remat)
    return _logits(rest, out.reshape(B, L, x.shape[-1]))


def pp_reduce_rest_grads(g_rest: Dict, axis: str = "pp"):
    """Gradient reduction for :func:`lm_apply_pp`'s replicated params.

    The embedding/positional tables are consumed only through stage 0's
    injection, so their per-chip grads are partial (full on the stage-0
    chip, zero elsewhere) — SUM over the axis. The final layernorm and
    head run replicated on the pipeline's broadcast output, so their
    grads are already full and identical on every chip — left untouched.
    Applied to grad values (never differentiated through)."""
    from horovod_tpu.parallel._vma import reduce_cotangent

    out = dict(g_rest)
    out["embed"] = reduce_cotangent(g_rest["embed"], axis, mean=False,
                                    invariant_loss=True)
    out["pos"] = reduce_cotangent(g_rest["pos"], axis, mean=False,
                                  invariant_loss=True)
    return out


def _shifted_targets(tokens, sp: Optional[str]):
    """Next-token targets + validity weights, sequence-shard aware.

    With ``sp``, each shard's last position needs the NEXT shard's first
    token as its target — one ppermute — and the final global position
    is masked out. Returns (targets [B, L], valid [B, L] fp32)."""
    B, L = tokens.shape
    if sp:
        n = lax.axis_size(sp)
        nxt = lax.ppermute(tokens[:, :1], sp,
                           [(i, (i - 1) % n) for i in range(n)])
        tgt = jnp.concatenate([tokens[:, 1:], nxt], axis=1)
        gpos = lax.axis_index(sp) * L + jnp.arange(L)
        valid = (gpos < n * L - 1).astype(jnp.float32)[None, :]
    else:
        tgt = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        valid = (jnp.arange(L) < L - 1).astype(jnp.float32)[None, :]
    return tgt, jnp.broadcast_to(valid, tokens.shape)


def next_token_nll(logits, tokens, sp: Optional[str] = None):
    """Mean next-token negative log-likelihood, sequence-shard aware
    (:func:`_shifted_targets`); the mean is taken over the sp axis so
    every chip returns the same global value. Matches the dense shift
    exactly."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    tgt, valid = _shifted_targets(tokens, sp)
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    local_sum = jnp.sum(nll * valid)
    local_cnt = jnp.sum(valid)
    if sp:
        # sum_across, not bare psum: gradients through a raw psum get
        # scaled by the axis size (see parallel/tp.py tp_region_output).
        return sum_across(local_sum, sp) / lax.psum(local_cnt, sp)
    return local_sum / local_cnt


def next_token_nll_fused(params: Dict, hidden, tokens,
                         sp: Optional[str] = None,
                         tp: Optional[str] = None,
                         vocab_parallel: bool = False,
                         t_chunk: int = 512):
    """:func:`next_token_nll` without the [B, L, vocab] logits tensor.

    ``hidden`` is :func:`lm_apply`'s ``return_hidden=True`` output; the
    vocab projection happens inside the chunked fused loss
    (ops/xent.py), so the step's largest HBM tensor never materializes.
    With ``vocab_parallel`` the head arrives [E, V/tp]-sharded
    (:func:`lm_param_specs` ``vocab_parallel=True``) and the Megatron-
    style variant assembles the normalizer over ``tp``. Exactly equal
    to logits-then-:func:`next_token_nll` (tests/test_parallel_lm.py).
    """
    from horovod_tpu.ops.xent import (fused_cross_entropy,
                                      tp_vocab_cross_entropy)

    B, L = tokens.shape
    tgt, valid = _shifted_targets(tokens, sp)
    e = hidden.shape[-1]
    h2 = hidden.reshape(B * L, e)
    t2 = tgt.reshape(B * L)
    w2 = valid.reshape(B * L)
    cnt = jnp.sum(w2)
    denom = lax.psum(cnt, sp) if sp else cnt
    if vocab_parallel:
        if not tp:
            raise ValueError("vocab_parallel needs a tp axis")
        local = tp_vocab_cross_entropy(h2, params["head"], t2, tp,
                                       t_chunk, weights=w2, denom=denom)
    else:
        local = fused_cross_entropy(h2, params["head"], t2, t_chunk,
                                    weights=w2, denom=denom)
    # Each sp shard contributes its own tokens' share of the globally-
    # normalized sum; sum_across (not bare psum) keeps the backward
    # unscaled, as in next_token_nll.
    return sum_across(local, sp) if sp else local


def reduce_grads(grads, dp: Optional[str] = None, sp: Optional[str] = None):
    """The gradient reduction that pairs with :func:`next_token_nll`.

    * ``sp``: SUM — the loss value is already normalized by the
      sp-global token count (psum inside the nll), so each sp rank's
      backward holds only its own tokens' contribution of the full
      gradient;
    * ``dp``: MEAN — the global loss is the mean of per-dp-shard means;
    * ``tp``: nothing — tp peers see identical data, so replicated
      leaves get identical grads and sharded leaves' grads are exactly
      their slice.

    Uniform over every leaf, replicated and tp-sharded alike."""
    from horovod_tpu.parallel._vma import reduce_cotangent

    if sp:
        # next_token_nll's sum_across makes the loss sp-invariant.
        grads = jax.tree_util.tree_map(
            lambda g: reduce_cotangent(g, sp, mean=False,
                                       invariant_loss=True), grads)
    if dp:
        grads = jax.tree_util.tree_map(
            lambda g: reduce_cotangent(g, dp, mean=True), grads)
    return grads
