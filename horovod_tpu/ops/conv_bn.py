"""Fused 1x1-conv (matmul) + BatchNorm-statistics Pallas kernel.

The ResNet-class benchmark step is memory-bound: profiling (PERF.md) puts
~34% of device time in ``convert_reduce`` fusions — the bf16->f32
converts feeding the BatchNorm statistics reductions. The forward half of
that cost is a full HBM re-read of every conv output just to compute its
channel mean/variance. ResNet-50's bottleneck blocks make 36 of its 53
convolutions 1x1 — i.e. plain matmuls on the MXU — so this kernel folds
the statistics into the matmul epilogue: while each output tile is still
in VMEM it accumulates per-channel ``sum(y)`` and ``sum(y^2)`` into a
grid-resident accumulator, eliminating the separate statistics pass over
~0.9 GB of activations per forward step.

Phase 2 (prologue fusion): the bottleneck's 3x3 output is consumed ONLY
by the following 1x1, so that producer's BatchNorm apply + ReLU can run
in this matmul's PROLOGUE while the raw tile is in VMEM — the
normalized activation ``h = relu(x*a + b)`` never reaches HBM at all
(one more full write + read of a [B,H,W,F] tensor saved per block).
Both phases share ONE kernel/forward, parameterized by the optional
``(a, b)`` affine.

The reference framework has no counterpart op (its benchmark model was
stock torchvision ResNet-50, reference
examples/pytorch_synthetic_benchmark.py:24-35); this is TPU-first perf
work on the same workload, not a port.

Gradient story (exact, not approximate): the public ops return
``(y, s1, s2)`` and the BN apply of THIS layer happens outside in
regular jnp, so autodiff needs the VJP of ``inputs -> (y, s1, s2)``
where ``s1 = sum_rows(cast(y)), s2 = sum_rows(cast(y)^2)``. With
incoming cotangents ``(dy, ds1, ds2)`` the chain rule collapses to a
single per-element total

    dy_total = dy + ds1[c] + 2 * y[r, c] * ds2[c]

followed by the standard matmul gradients (and, for the prologue
variant, the elementwise affine/ReLU pullbacks, with ``h`` recomputed
from the raw input — the same bytes the unfused backward reads from the
stored activation). Exactness vs the unfused compositions is pinned in
tests/test_conv_bn.py, f64-tight.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from horovod_tpu.utils.device import pallas_interpret

# Keep the whole [K, N] weight + one [block_m, K] input tile + the f32
# accumulator resident in VMEM; fall back to the unfused path when the
# estimate exceeds this budget (v4/v5 VMEM is 16 MB; leave headroom for
# Mosaic's own buffers).
_VMEM_BUDGET_BYTES = 13 * 1024 * 1024

_BLOCK_M_CANDIDATES = (512, 448, 256, 128, 64, 32, 16, 8)


def _pick_block_m(m: int) -> Optional[int]:
    for bm in _BLOCK_M_CANDIDATES:
        if m % bm == 0:
            return bm
    return None


def fits_fused(m: int, k: int, n: int, itemsize: int = 2) -> bool:
    """Whether the fused kernel's working set fits the VMEM budget."""
    bm = _pick_block_m(m) or 256
    weight = k * n * itemsize
    x_tile = bm * k * itemsize
    y_tile = bm * n * itemsize
    acc = bm * n * 4
    return weight + x_tile + y_tile + acc <= _VMEM_BUDGET_BYTES


def _make_kernel(prologue: bool, valid_rows: Optional[int], bm: int):
    """Kernel for one M-tile: optional affine+ReLU prologue, matmul on
    the MXU, statistics in the epilogue.

    s1/s2 use a constant index map, so their [1, N] block stays resident
    in VMEM across the whole (sequential) grid — the classic Pallas
    reduction-accumulator pattern. ``valid_rows`` (set only when M was
    zero-padded to a block multiple AND a prologue runs) masks the pad
    rows back to zero AFTER the affine — relu(0*a + b) = relu(b) is
    nonzero for positive shifts and would otherwise poison the
    statistics; without a prologue, zero rows stay zero on their own.
    """

    def kernel(*refs):
        from jax.experimental import pallas as pl

        if prologue:
            x_ref, a_ref, b_ref, w_ref, y_ref, s1_ref, s2_ref = refs
        else:
            x_ref, w_ref, y_ref, s1_ref, s2_ref = refs
        i = pl.program_id(0)
        xb = x_ref[...]
        if prologue:
            # The affine runs in the storage dtype (bf16 on TPU),
            # matching the unfused ConvBN apply channel-for-channel.
            xb = jnp.maximum(xb * a_ref[...] + b_ref[...], 0)
            if valid_rows is not None:
                row = i * bm + jax.lax.broadcasted_iota(
                    jnp.int32, xb.shape, 0)
                xb = jnp.where(row < valid_rows, xb, 0)
        # f32 MXU accumulation for <=32-bit inputs; f64 only exists for
        # the float64 exactness probes in CI (TPUs have no f64 path).
        acc_t = (jnp.float64 if xb.dtype == jnp.float64 else jnp.float32)
        acc = jnp.dot(xb, w_ref[...], preferred_element_type=acc_t)
        y_ref[...] = acc.astype(y_ref.dtype)
        # Statistics over the ROUNDED output (what the unfused path sees
        # when it upcasts the stored bf16 activation), so fused and
        # unfused BN consume identical moments.
        yr = y_ref[...].astype(s1_ref.dtype)
        ps1 = jnp.sum(yr, axis=0, keepdims=True)
        ps2 = jnp.sum(yr * yr, axis=0, keepdims=True)

        @pl.when(i == 0)
        def _init():
            s1_ref[...] = ps1
            s2_ref[...] = ps2

        @pl.when(i > 0)
        def _accum():
            s1_ref[...] += ps1
            s2_ref[...] += ps2

    return kernel


def _vma_align(*arrays):
    """pcast every array onto the union of all arrays' varying mesh
    axes (shard_map check_vma=True requires dot/elementwise operands to
    agree; a replicated weight meeting batch-sharded activations needs
    the explicit cast). Returns (aligned_arrays, union)."""
    vmas = []
    for arr in arrays:
        try:
            vmas.append(jax.typeof(arr).vma)
        except (AttributeError, TypeError):
            vmas.append(frozenset())
    union = frozenset().union(*vmas)
    out = []
    for arr, vma in zip(arrays, vmas):
        missing = union - vma
        if missing:
            arr = jax.lax.pcast(arr, tuple(missing), to="varying")
        out.append(arr)
    return out, union


def _forward(x, w, a, b, interpret: bool):
    """x [M, K] (raw if a/b given), w [K, N], optional affine a/b [K] ->
    (y [M, N] x.dtype, s1 [N], s2 [N])."""
    from jax.experimental import pallas as pl

    prologue = a is not None
    m, k = x.shape
    n = w.shape[1]
    # Stats accumulate in f32 (f64 only under the CI exactness probes).
    stats_t = jnp.promote_types(jnp.float32, x.dtype)
    bm = _pick_block_m(m)
    pad = 0
    if bm is None:
        # Irregular row counts: zero rows contribute nothing to s1/s2
        # (the kernel masks them back to zero when a prologue runs) and
        # their y rows are sliced off below.
        bm = 256
        pad = (-m) % bm
        x = jnp.pad(x, ((0, pad), (0, 0)))

    operands = [x, w]
    in_specs = [
        pl.BlockSpec((bm, k), lambda i: (i, 0)),
        pl.BlockSpec((k, n), lambda i: (0, 0)),
    ]
    if prologue:
        a2 = a.reshape(1, k).astype(x.dtype)
        b2 = b.reshape(1, k).astype(x.dtype)
        operands = [x, a2, b2, w]
        in_specs = [
            pl.BlockSpec((bm, k), lambda i: (i, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),
        ]
    operands, vma = _vma_align(*operands)

    def out_struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)

    kernel = _make_kernel(prologue, m if (prologue and pad) else None, bm)
    y, s1, s2 = pl.pallas_call(
        kernel,
        grid=((m + pad) // bm,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_shape=[
            out_struct((m + pad, n), x.dtype),
            out_struct((1, n), stats_t),
            out_struct((1, n), stats_t),
        ],
        interpret=interpret,
    )(*operands)
    if pad:
        y = y[:m]
    return y, s1[0], s2[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def matmul_bn_stats(x, w, interpret: bool = False):
    """Fused ``y = x @ w`` plus channel statistics ``(sum y, sum y^2)``.

    The statistics are computed over the rounded (storage-dtype) ``y`` in
    one pass while each tile is VMEM-resident. ``interpret=True`` runs
    the same kernel through the Pallas interpreter (CPU CI).
    """
    return _forward(x, w, None, None, interpret)


def _matmul_bn_stats_fwd(x, w, interpret):
    y, s1, s2 = _forward(x, w, None, None, interpret)
    return (y, s1, s2), (x, w, y)


def _stats_cotangent_total(y, dy, ds1, ds2, acc_t):
    """Collapse the three cotangent paths into one elementwise total
    (module docstring); XLA fuses the broadcasts + add with the matmul
    operand preparation."""
    return (dy.astype(acc_t)
            + ds1[None, :].astype(acc_t)
            + 2.0 * y.astype(acc_t) * ds2[None, :].astype(acc_t))


def _matmul_bn_stats_bwd(interpret, res, cts):
    x, w, y = res
    dy, ds1, ds2 = cts
    acc_t = jnp.float64 if x.dtype == jnp.float64 else jnp.float32
    dy_total = _stats_cotangent_total(y, dy, ds1, ds2, acc_t).astype(x.dtype)
    dx = jnp.dot(dy_total, w.T, preferred_element_type=acc_t)
    dw = jnp.dot(x.T, dy_total, preferred_element_type=acc_t)
    return dx.astype(x.dtype), dw.astype(w.dtype)


matmul_bn_stats.defvjp(_matmul_bn_stats_fwd, _matmul_bn_stats_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def matmul_prologue_bn_stats(x, a, b, w, interpret: bool = False):
    """Fused ``y = relu(x*a + b) @ w`` plus channel statistics of ``y``.

    ``x`` is the RAW previous-conv output; ``a``/``b`` the folded
    BatchNorm scale/shift of that previous layer. The normalized
    activation exists only tile-by-tile in VMEM.
    """
    return _forward(x, w, a, b, interpret)


def _matmul_prologue_fwd(x, a, b, w, interpret):
    y, s1, s2 = _forward(x, w, a, b, interpret)
    return (y, s1, s2), (x, a, b, w, y)


def _matmul_prologue_bwd(interpret, res, cts):
    x, a, b, w, y = res
    dy, ds1, ds2 = cts
    acc_t = jnp.float64 if x.dtype == jnp.float64 else jnp.float32
    dy_total = _stats_cotangent_total(y, dy, ds1, ds2, acc_t).astype(x.dtype)
    # Recompute h elementwise from the raw input (one read of x — the
    # same bytes the unfused backward reads from the STORED h, so the
    # backward pays no extra HBM traffic for never materializing h).
    pre = x * a[None, :].astype(x.dtype) + b[None, :].astype(x.dtype)
    h = jnp.maximum(pre, 0)
    mask = (pre > 0).astype(x.dtype)
    dw = jnp.dot(h.T, dy_total, preferred_element_type=acc_t)
    dh = jnp.dot(dy_total, w.T, preferred_element_type=acc_t).astype(x.dtype)
    dh = dh * mask
    dx = dh * a[None, :].astype(x.dtype)
    da = jnp.sum(dh.astype(acc_t) * x.astype(acc_t), axis=0)
    db = jnp.sum(dh.astype(acc_t), axis=0)
    return (dx.astype(x.dtype), da.astype(a.dtype), db.astype(b.dtype),
            dw.astype(w.dtype))


matmul_prologue_bn_stats.defvjp(_matmul_prologue_fwd, _matmul_prologue_bwd)


def _nhwc_wrap(op, x, w, strides, interpret, *affine):
    if interpret is None:
        interpret = pallas_interpret()
    if w.ndim == 4:
        assert w.shape[:2] == (1, 1), w.shape
        w = w[0, 0]
    sh, sw = strides
    if (sh, sw) != (1, 1):
        # A strided 1x1 conv only ever reads the stride-subsampled
        # input: the same matmul over x[:, ::sh, ::sw] — a strided HBM
        # read of 1/(sh*sw) of the data, not an extra pass. (With a
        # prologue the subsample commutes with the elementwise affine.)
        x = x[:, ::sh, ::sw, :]
    bsz, hh, ww_, c = x.shape
    y, s1, s2 = op(x.reshape(bsz * hh * ww_, c), *affine, w, interpret)
    return y.reshape(bsz, hh, ww_, -1), s1, s2


def conv1x1_bn_stats(x, w, strides: Tuple[int, int] = (1, 1),
                     interpret: Optional[bool] = None):
    """1x1 NHWC convolution with fused BN statistics.

    x [B, H, W, C_in], w [1, 1, C_in, C_out] (or [C_in, C_out]) ->
    (y [B, H', W', C_out], s1 [C_out], s2 [C_out]).
    """
    return _nhwc_wrap(matmul_bn_stats, x, w, strides, interpret)


def conv1x1_prologue_bn_stats(x, a, b, w,
                              strides: Tuple[int, int] = (1, 1),
                              interpret: Optional[bool] = None):
    """NHWC wrapper of :func:`matmul_prologue_bn_stats`: ``x`` is the
    RAW producing-conv output, ``a``/``b`` its folded BN scale/shift."""
    return _nhwc_wrap(matmul_prologue_bn_stats, x, w, strides, interpret,
                      a, b)
