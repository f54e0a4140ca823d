"""Mamba-2's selective scan (the state-space duality, SSD), chunked, forward
and backward.

For each head ``h`` of ``H`` and each token ``t`` of a sequence, with the
state ``S`` a ``P x N`` matrix a head, ``Delta`` after its softplus and ``A``
negative:

    S_t = exp(Delta_t A) S_{t-1} + Delta_t x_t B_t^T        x_t [P], B_t [N]
    y_t = S_t C_t + D x_t                                   C_t [N]

The chunked form (Dao & Gu 2024, section 6): within a chunk of ``T`` tokens
with ``cs`` the running sum of ``Delta A`` from the chunk's start (inclusive)
and ``H`` the state entering it (stored ``N x P``),

    G      = C B^T                              T x T, shared by a group's heads
    L[t,u] = exp(cs_t - cs_u) where u <= t, else 0 (masked before the exp)
    y      = (G . L) (Delta x) + exp(cs) . (C H)
    H     <- exp(cs_T) H + B^T (exp(cs_T - cs) . Delta x)

and ``D x`` beside it. :func:`ssd` is the one entry point; two
implementations sit behind it, chosen as ``ops.attention.attend`` chooses,
by ``impl`` or by the platform:

* ``"pallas"``, the chip's: a forward kernel and a backward kernel
  (``jax.custom_vjp``), both named :data:`KERNEL`: one family in a device
  profile, whose ten largest families (``benchmarks/trace_reduce.py``) each
  kernel alone falls just short of in the Granite cell. Grid (batch,
  chunk, head pair); a program serves the two heads of 64 that fill one
  128-lane column block (as ``ops.attention`` pairs flash heads), each of
  its products taking one operand with the other head's lanes zeroed. The
  chunk axis is sequential and every pair's state stays in a float32 VMEM
  scratch from chunk to chunk. B and C come in ``G`` groups (head h reads
  group ``h // (H / G)``, so a group's pairs are consecutive on the grid);
  ``G`` is made once a chunk a group, by the group's first pair. x, B and C
  are read by index map from the conv's output ``[Bt, L, H x P + 2 G N]``
  where it lies (no split copies), a pair's B and C its group's column
  blocks; y is written as
  the ``[Bt, L, H x P]`` that the gated norm reads. The forward kernel also
  writes the state entering each chunk to HBM (``[Bt, L / T, H / 2, N,
  128]`` float32, 128 MiB a layer at 16,384 tokens and Granite's widths),
  the residual the backward kernel reads instead of running the recurrence
  again; the backward kernel walks the chunks in reverse carrying ``dH`` in
  VMEM, sums the gradient of ``G`` over a group's pairs in VMEM and turns it
  into the group's B's and C's once a chunk, at the group's last pair, and
  sums B's and C's gradients over the group's pairs in its output blocks. Products take the inputs' type with
  float32 accumulation; cumulative sums and exponentials are float32.
* ``"chunked"``: the same decomposition in ``jax.numpy`` (a scan over
  chunks), the CPU's path and the kernels' oracle in the tests.

``cs`` and the ``D`` skip are computed outside the kernels (elementwise,
where XLA fuses them); a length that is not a multiple of the chunk is padded
with tokens that change nothing (``Delta`` 0)."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.utils.device import pallas_interpret

CHUNK = 256             # Mamba-2's (and Granite-4.0-H's) chunk
KERNEL = "hvd_ssd_scan"  # the forward and the backward kernel's name
PAIR = 2                # heads a kernel program serves
LANES = 128
NEG_INF = -1e30         # finite: exp() of it is exactly 0


def _pallas_shapes_ok(heads: int, head_dim: int, groups: int,
                      state_dim: int) -> bool:
    """What the kernels are written for: two heads of 64 fill a 128-lane
    block, each group of B and C is read by whole pairs of heads, a state of
    128."""
    return (head_dim * PAIR == LANES and groups >= 1 and heads % groups == 0
            and (heads // groups) % PAIR == 0 and state_dim == LANES)


def ssd(x, dt, A, B=None, C=None, D=None, *, chunk: int = CHUNK,
        state_dim: Optional[int] = None, groups: int = 1,
        impl: Optional[str] = None, interpret: Optional[bool] = None):
    """The scan of module docstring's equations.

    ``x [Bt, L, H, P]`` with ``B``, ``C`` ``[Bt, L, G, N]`` (``G`` divides
    ``H``; head h reads group ``h // (H / G)``); or ``x`` the conv's output
    ``[Bt, L, H x P + 2 G N]`` (x | B | C along its columns, B and C each
    ``groups`` groups of N) with ``B`` and ``C`` None and ``state_dim`` N,
    read where it lies. ``dt [Bt,
    L, H]`` is ``Delta`` after its softplus, ``A`` and ``D`` ``[H]`` (``A``
    negative; ``D`` None: no skip). Returns y in ``x``'s type: ``[Bt, L, H,
    P]``, or ``[Bt, L, H x P]`` for the packed input. ``impl``: ``"pallas"``
    | ``"chunked"``, or None: the kernels on a TPU, ``"chunked"``
    elsewhere."""
    packed = B is None
    bt, length, heads = dt.shape
    if packed:
        if C is not None or state_dim is None:
            raise ValueError("the conv's output needs state_dim and no B, C")
        n = state_dim
        head_dim = (x.shape[-1] - 2 * groups * n) // heads
        if heads * head_dim + 2 * groups * n != x.shape[-1]:
            raise ValueError(f"{x.shape[-1]} columns are not {heads} heads "
                             f"and two states of {groups} x {n}")
    else:
        head_dim, groups, n = x.shape[-1], B.shape[2], B.shape[3]
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "chunked"
    if impl not in ("pallas", "chunked"):
        raise ValueError(f"impl must be pallas|chunked, got {impl!r}")
    pad = (-length) % chunk
    if impl == "pallas":
        if not _pallas_shapes_ok(heads, head_dim, groups, n):
            raise ValueError(
                f"the kernels take heads of {LANES // PAIR}, an even number "
                f"of them a group, and a state of {LANES}: got {heads} heads "
                f"of {head_dim}, {groups} groups, state {n}")
        xbc = x if packed else jnp.concatenate(
            [x.reshape(bt, length, -1), B.reshape(bt, length, -1),
             C.reshape(bt, length, -1)], -1)
        xbc, dtp = _pad(xbc, pad), _pad(dt, pad)
        y = _scan_pallas(xbc, dtp.astype(jnp.float32), A, heads, chunk,
                         pallas_interpret() if interpret is None
                         else interpret, groups)[:, :length]
        xs = xbc[:, :length, :heads * head_dim]
        if not packed:
            y, xs = y.reshape(x.shape), x
    else:
        if packed:
            inner = heads * head_dim
            xs = x[..., :inner].reshape(bt, length, heads, head_dim)
            gn = groups * n
            Bs, Cs = (x[..., inner + i * gn:inner + (i + 1) * gn].reshape(
                bt, length, groups, n) for i in (0, 1))
        else:
            xs, Bs, Cs = x, B, C
        y = _scan_chunked(*(_pad(t, pad) for t in (xs, dt, Bs, Cs)), A,
                          chunk)[:, :length].astype(x.dtype)
        if packed:
            y, xs = y.reshape(bt, length, -1), x[..., :heads * head_dim]
    if D is not None:
        d = jnp.repeat(D.astype(jnp.float32), head_dim) if packed \
            else D.astype(jnp.float32)[:, None]
        y = (y.astype(jnp.float32) + d * xs.astype(jnp.float32)).astype(
            y.dtype)
    return y


def _pad(a, pad: int):
    return a if not pad else jnp.pad(
        a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))


def chunk_cumsum(dt, A, chunk: int):
    """``cs [Bt, L, H]``: the running sum of ``Delta A`` from each chunk's
    start, inclusive, float32."""
    bt, length, heads = dt.shape
    la = (dt.astype(jnp.float32) * A.astype(jnp.float32)).reshape(
        bt, length // chunk, chunk, heads)
    return jnp.cumsum(la, axis=2).reshape(bt, length, heads)


# ------------------------------------------------------------ jax.numpy


def _scan_chunked(x, dt, B, C, A, chunk: int):
    """The chunked decomposition in ``jax.numpy``, float32 at ``highest``:
    x ``[Bt, L, H, P]``, dt ``[Bt, L, H]``, B, C ``[Bt, L, G, N]``; y
    without the ``D`` skip, float32."""
    bt, length, heads, p = x.shape
    n, nc = B.shape[-1], length // chunk
    f32 = jnp.float32
    ein = functools.partial(jnp.einsum, precision=lax.Precision.HIGHEST,
                            preferred_element_type=f32)

    def chunks(a):
        return a.reshape(bt, nc, chunk, *a.shape[2:])

    rep = heads // B.shape[2]
    Bh, Ch = (jnp.repeat(chunks(t).astype(f32), rep, axis=3) for t in (B, C))
    cs = chunks(chunk_cumsum(dt, A, chunk))                 # b c t h
    xt = chunks(x.astype(f32) * dt.astype(f32)[..., None])  # b c t h p
    csh = jnp.moveaxis(cs, 3, 2)                            # b c h t
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(tri, csh[..., :, None] - csh[..., None, :],
                              NEG_INF))                     # b c h t u
    y = ein("bcthn,bcuhn,bchtu,bcuhp->bcthp", Ch, Bh, decay, xt)
    w = jnp.exp(cs[:, :, -1:] - cs)                         # b c t h
    own = ein("bcuhn,bcuh,bcuhp->bchnp", Bh, w, xt)         # what a chunk adds

    def step(state, inp):
        add, d = inp
        return d[..., None, None] * state + add, state

    _, entering = lax.scan(step, jnp.zeros((bt, heads, n, p), f32),
                           (jnp.moveaxis(own, 1, 0),
                            jnp.moveaxis(jnp.exp(cs[:, :, -1]), 1, 0)))
    y = y + jnp.exp(cs)[..., None] * ein("bcthn,cbhnp->bcthp", Ch, entering)
    return y.reshape(bt, length, heads, p)


# --------------------------------------------------------------- kernels


def _rows(a, heads: int):
    """``[Bt, L, H]`` -> ``[Bt, H / 2, 2, L]``: a pair's two heads a block
    of rows, the tokens along the lanes."""
    bt, length, _ = a.shape
    return a.reshape(bt, length, heads // PAIR, PAIR).transpose(0, 2, 3, 1)


def _kernel_helpers(chunk: int):
    """Masks and the row / column turns a kernel body shares."""
    t = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    u = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri, eye = u <= t, u == t
    first = lax.broadcasted_iota(jnp.int32, (chunk, LANES), 1) < LANES // PAIR
    first_row = lax.broadcasted_iota(jnp.int32, (1, LANES), 1) \
        < LANES // PAIR

    def column(row):            # (1, T) -> (T, 1)
        return jnp.sum(jnp.where(eye, jnp.broadcast_to(row, eye.shape), 0.0),
                       axis=1, keepdims=True)

    def row_of(col):            # (T, 1) -> (1, T)
        return jnp.sum(jnp.where(eye, jnp.broadcast_to(col, eye.shape), 0.0),
                       axis=0, keepdims=True)

    def pick(a, b, mask=first):  # head 0's lanes from a, head 1's from b
        return jnp.where(mask, a, b)

    at_end = lax.broadcasted_iota(jnp.int32, (1, chunk), 1) == chunk - 1

    def last(row):              # (1, T) -> (1, 1): the chunk's last token's
        return jnp.sum(jnp.where(at_end, row, 0.0), axis=1, keepdims=True)

    return tri, first, first_row, column, row_of, pick, at_end, last


def _decay(tri, col, row):
    return jnp.exp(jnp.where(tri, col - row, NEG_INF))


def _dot(a, b, dtype, contract=((1,), (0,))):
    return lax.dot_general(a.astype(dtype), b.astype(dtype),
                           (contract, ((), ())),
                           preferred_element_type=jnp.float32)


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, cs_ref, y_ref, h_ref,
                state_scr, g_scr, *, chunk: int, per_group: int):
    from jax.experimental import pallas as pl

    c, p = pl.program_id(1), pl.program_id(2)
    mm = x_ref.dtype
    tri, first, first_row, column, _, pick, _, last = _kernel_helpers(chunk)

    @pl.when(c == 0)
    def _():
        state_scr[p] = jnp.zeros(state_scr.shape[1:], jnp.float32)

    @pl.when(p % per_group == 0)
    def _():        # G = C B^T, shared by every head of the group
        g_scr[...] = _dot(c_ref[...], b_ref[...], mm, ((1,), (1,)))

    cs, dt = cs_ref[...], dt_ref[...]
    cs_r = [cs[h:h + 1, :] for h in range(PAIR)]
    cs_c = [column(r) for r in cs_r]
    xt = x_ref[...].astype(jnp.float32) * pick(column(dt[0:1, :]),
                                               column(dt[1:2, :]))
    g = g_scr[...]
    y = jnp.zeros(xt.shape, jnp.float32)
    for h in range(PAIR):
        own = first if h == 0 else ~first
        y += _dot(g * _decay(tri, cs_c[h], cs_r[h]),
                  jnp.where(own, xt, 0.0), mm)
    state = state_scr[p]
    h_ref[...] = state
    y += pick(jnp.exp(cs_c[0]), jnp.exp(cs_c[1])) * _dot(c_ref[...], state,
                                                         mm)
    y_ref[...] = y.astype(y_ref.dtype)
    end = [last(r) for r in cs_r]
    w = pick(jnp.exp(end[0] - cs_c[0]), jnp.exp(end[1] - cs_c[1]))
    state_scr[p] = pick(jnp.exp(end[0]), jnp.exp(end[1]), first_row) * state \
        + _dot(b_ref[...].astype(jnp.float32).T, w * xt, mm)


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, cs_ref, h_ref, dy_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, dcs_ref,
                dh_scr, g_scr, dg_scr, *, chunk: int, per_group: int):
    from jax.experimental import pallas as pl

    r, p = pl.program_id(1), pl.program_id(2)
    mm = x_ref.dtype
    tri, first, first_row, column, row_of, pick, at_end, last = \
        _kernel_helpers(chunk)

    @pl.when(r == 0)
    def _():
        dh_scr[p] = jnp.zeros(dh_scr.shape[1:], jnp.float32)

    @pl.when(p % per_group == 0)
    def _():
        g_scr[...] = _dot(c_ref[...], b_ref[...], mm, ((1,), (1,)))
        dg_scr[...] = jnp.zeros(dg_scr.shape, jnp.float32)
        db_ref[...] = jnp.zeros(db_ref.shape, jnp.float32)
        dc_ref[...] = jnp.zeros(dc_ref.shape, jnp.float32)

    cs, dt = cs_ref[...], dt_ref[...]
    cs_r = [cs[h:h + 1, :] for h in range(PAIR)]
    cs_c = [column(row) for row in cs_r]
    dtl = pick(column(dt[0:1, :]), column(dt[1:2, :]))
    x = x_ref[...].astype(jnp.float32)
    xt = x * dtl
    dy = dy_ref[...].astype(jnp.float32)
    state, dh = h_ref[...], dh_scr[p]
    g = g_scr[...]
    own = [first, ~first]
    dxt = jnp.zeros(xt.shape, jnp.float32)
    dg = jnp.zeros(g.shape, jnp.float32)
    dcs_col = [jnp.zeros((chunk, 1), jnp.float32) for _ in range(PAIR)]
    dcs_row = [jnp.zeros((1, chunk), jnp.float32) for _ in range(PAIR)]
    for h in range(PAIR):           # within the chunk
        decay = _decay(tri, cs_c[h], cs_r[h])
        m = g * decay
        dy_h = jnp.where(own[h], dy, 0.0)
        dxt += _dot(m.T, dy_h, mm)
        dm = _dot(dy_h, jnp.where(own[h], xt, 0.0), mm, ((1,), (1,)))
        dg += dm * decay
        q = dm * m
        dcs_col[h] += jnp.sum(q, axis=1, keepdims=True)
        dcs_row[h] -= jnp.sum(q, axis=0, keepdims=True)
    dg_scr[...] += dg
    # the state entering the chunk: y += exp(cs) . (C H)
    e = pick(jnp.exp(cs_c[0]), jnp.exp(cs_c[1]))
    dye = dy * e
    dc_ref[...] += _dot(dye, state, mm, ((1,), (1,)))
    ch_dy = dye * _dot(c_ref[...], state, mm)
    # the state leaving it: H' = exp(cs_T) H + B^T (w . Delta x)
    end = [last(row) for row in cs_r]
    w_c = [jnp.exp(end[h] - cs_c[h]) for h in range(PAIR)]
    w = pick(w_c[0], w_c[1])
    bdh = _dot(b_ref[...], dh, mm)
    dxt += w * bdh
    db_ref[...] += _dot(w * xt, dh, mm, ((1,), (1,)))
    dw = bdh * xt
    hdh = dh * state
    for h in range(PAIR):
        dcs_col[h] += jnp.sum(jnp.where(own[h], ch_dy, 0.0), axis=1,
                              keepdims=True)
        dw_h = jnp.sum(jnp.where(own[h], dw, 0.0), axis=1,
                       keepdims=True) * w_c[h]
        dcs_col[h] -= dw_h
        lanes = first_row if h == 0 else ~first_row
        d_end = jnp.sum(dw_h, axis=0, keepdims=True) + jnp.exp(end[h]) \
            * jnp.sum(jnp.where(lanes, hdh, 0.0), keepdims=True)
        dcs_ref[h:h + 1, :] = dcs_row[h] + row_of(dcs_col[h]) \
            + jnp.where(at_end, d_end, 0.0)
        ddt_ref[h:h + 1, :] = row_of(jnp.sum(
            jnp.where(own[h], dxt * x, 0.0), axis=1, keepdims=True))
    dh_scr[p] = pick(jnp.exp(end[0]), jnp.exp(end[1]), first_row) * dh \
        + _dot(c_ref[...].astype(jnp.float32).T, dye, mm)
    dx_ref[...] = (dxt * dtl).astype(dx_ref.dtype)

    @pl.when(p % per_group == per_group - 1)
    def _():        # G's gradient, summed over the group's pairs, into C's
        # and B's
        dg_all = dg_scr[...]
        dc_ref[...] += _dot(dg_all, b_ref[...], mm)
        db_ref[...] += _dot(dg_all.T, c_ref[...], mm)


def _specs(heads: int, chunk: int, n_chunks: int, reverse: bool,
           groups: int = 1):
    """BlockSpecs of x, B and C (column blocks of the conv's output: a
    pair's group's B and C), of a pair's rows of ``Delta`` / ``cs``, of a
    pair's ``[T, 128]`` tile of ``[Bt, L, H x P]`` and of its group's
    ``[T, 128]`` tile of ``[Bt, L, G x N]`` (B's and C's gradients), on the
    grid (batch, chunk or reversed chunk, pair)."""
    from jax.experimental import pallas as pl

    def at(c):
        return n_chunks - 1 - c if reverse else c

    b_col = heads * LANES // PAIR // LANES
    tile = pl.BlockSpec((None, chunk, LANES), lambda b, c, p: (b, at(c), p))
    x = tile
    per_group = heads // PAIR // groups
    bmat = pl.BlockSpec((None, chunk, LANES),
                        lambda b, c, p: (b, at(c), b_col + p // per_group))
    cmat = pl.BlockSpec(
        (None, chunk, LANES),
        lambda b, c, p: (b, at(c), b_col + groups + p // per_group))
    group_tile = pl.BlockSpec((None, chunk, LANES),
                              lambda b, c, p: (b, at(c), p // per_group))
    rows = pl.BlockSpec((None, None, PAIR, chunk),
                        lambda b, c, p: (b, p, 0, at(c)))
    state = pl.BlockSpec((None, None, None, LANES, LANES),
                         lambda b, c, p: (b, at(c), p, 0, 0))
    return x, bmat, cmat, rows, state, tile, group_tile


def _forward(xbc, dt_rows, cs_rows, heads: int, chunk: int,
             interpret: bool, groups: int = 1):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bt, length, _ = xbc.shape
    nc, pairs = length // chunk, heads // PAIR
    x, bmat, cmat, rows, state, tile, _ = _specs(heads, chunk, nc, False,
                                                 groups)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk,
                          per_group=pairs // groups),
        grid=(bt, nc, pairs),
        in_specs=[x, bmat, cmat, rows, rows],
        out_specs=[tile, state],
        out_shape=[
            jax.ShapeDtypeStruct((bt, length, heads * LANES // PAIR),
                                 xbc.dtype),
            jax.ShapeDtypeStruct((bt, nc, pairs, LANES, LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((pairs, LANES, LANES), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret, name=KERNEL,
    )(xbc, xbc, xbc, dt_rows, cs_rows)


def _backward(xbc, dt_rows, cs_rows, states, dy, heads: int, chunk: int,
              interpret: bool, groups: int = 1):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bt, length, _ = xbc.shape
    nc, pairs = length // chunk, heads // PAIR
    x, bmat, cmat, rows, state, tile, group_tile = _specs(heads, chunk, nc,
                                                          True, groups)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk,
                          per_group=pairs // groups),
        grid=(bt, nc, pairs),
        in_specs=[x, bmat, cmat, rows, rows, state, tile],
        out_specs=[tile, group_tile, group_tile, rows, rows],
        out_shape=[
            jax.ShapeDtypeStruct(dy.shape, xbc.dtype),
            jax.ShapeDtypeStruct((bt, length, groups * LANES), f32),
            jax.ShapeDtypeStruct((bt, length, groups * LANES), f32),
            jax.ShapeDtypeStruct(dt_rows.shape, f32),
            jax.ShapeDtypeStruct(cs_rows.shape, f32)],
        scratch_shapes=[pltpu.VMEM((pairs, LANES, LANES), f32),
                        pltpu.VMEM((chunk, chunk), f32),
                        pltpu.VMEM((chunk, chunk), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret, name=KERNEL,
    )(xbc, xbc, xbc, dt_rows, cs_rows, states, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _kernels(xbc, dt_rows, cs_rows, heads, chunk, interpret, groups):
    return _forward(xbc, dt_rows, cs_rows, heads, chunk, interpret,
                    groups)[0]


def _kernels_fwd(xbc, dt_rows, cs_rows, heads, chunk, interpret, groups):
    y, states = _forward(xbc, dt_rows, cs_rows, heads, chunk, interpret,
                         groups)
    return y, (xbc, dt_rows, cs_rows, states)


def _kernels_bwd(heads, chunk, interpret, groups, res, dy):
    xbc, dt_rows, cs_rows, states = res
    dx, db, dc, ddt, dcs = _backward(xbc, dt_rows, cs_rows, states,
                                     dy.astype(xbc.dtype), heads, chunk,
                                     interpret, groups)
    dxbc = jnp.concatenate([dx, db.astype(dx.dtype), dc.astype(dx.dtype)], -1)
    return dxbc, ddt, dcs


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _scan_pallas(xbc, dt, A, heads: int, chunk: int, interpret: bool,
                 groups: int = 1):
    """y ``[Bt, L, H x P]`` through the kernels; ``Delta`` and its running
    sums go in as a pair's rows, made here where XLA differentiates them.
    Under ``jax.jit``, so that a differentiated program names the kernels
    :data:`KERNEL` and not after the transformations around them (the
    profile's family is that name)."""
    cs = chunk_cumsum(dt, A, chunk)
    return _kernels(xbc, _rows(dt, heads), _rows(cs, heads), heads, chunk,
                    interpret, groups)


def state_bytes(batch: int, length: int, heads: int, state_dim: int,
                head_dim: int, chunk: int = CHUNK) -> int:
    """Bytes of chunk states one forward kernel call writes to HBM: the
    float32 state entering every chunk, every head."""
    return batch * -(-length // chunk) * heads * state_dim * head_dim * 4
