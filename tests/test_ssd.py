"""Mamba-2's chunked scan (``horovod_tpu/ops/ssd.py``) at a small size on
the CPU: both implementations (the Pallas kernels interpreted) against the
recurrence token by token, the definition, in float32, with B and C in one
group or several.

Tolerances and why: the chunked form adds the same terms in another order
(a chunk's products, then the carried state) and its decays are
``exp(cs_t - cs_u)`` where the recurrence multiplies ``exp(Delta A)`` token
by token: read 3e-7 to 6e-7 of the largest element in y and every gradient,
held to 2e-5. bfloat16 operands in the kernels against the float32 path:
their rounding, 2e-2.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import ssd as ssd_op


def recurrence(x, dt, A, B, C, D):
    """``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D
    x_t``, one token at a time."""
    b, _, h, p = x.shape
    rep = h // B.shape[2]
    bh, ch = jnp.repeat(B, rep, 2), jnp.repeat(C, rep, 2)

    def step(s, inputs):
        x_t, dt_t, b_t, c_t = inputs
        s = jnp.exp(dt_t * A)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t, precision="highest")

    _, ys = jax.lax.scan(step, jnp.zeros((b, h, p, B.shape[-1])),
                         tuple(jnp.moveaxis(t, 1, 0)
                               for t in (x, dt, bh, ch)))
    return jnp.moveaxis(ys, 0, 1) + D[:, None] * x


def operands(length, heads=4, p=64, n=128, groups=1, seed=0):
    """Decays drawn as the Granite configuration draws them: A in [1, 16],
    Delta from 0.001 (a state carried over hundreds of tokens) to 0.3."""
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(k[0], (1, length, heads, p))
    dt = jnp.exp(jax.random.uniform(k[1], (1, length, heads),
                                    minval=np.log(1e-3), maxval=np.log(0.3)))
    A = -jnp.exp(jax.random.uniform(k[2], (heads,), maxval=np.log(16.0)))
    B = jax.random.normal(k[3], (1, length, groups, n))
    C = jax.random.normal(k[4], (1, length, groups, n))
    D = 1 + 0.1 * jax.random.normal(k[5], (heads,))
    w = jax.random.normal(k[6], x.shape)
    return (x, dt, A, B, C, D), w


def _close(got, want, tol, name=""):
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale, name


@pytest.mark.parametrize("impl, length", [
    ("chunked", 48), ("chunked", 40), ("pallas", 48), ("pallas", 40)],
    ids=["chunked-3chunks", "chunked-unaligned", "pallas-3chunks",
         "pallas-unaligned"])
def test_the_scan_is_the_recurrence_forward_and_backward(impl, length):
    """Three chunks of 16 (and 40 tokens, the last chunk padded): y and the
    gradients of x, Delta, A, B, C and D."""
    args, w = operands(length)

    def ours(*a):
        return ssd_op.ssd(*a, chunk=16, impl=impl)

    _close(ours(*args), recurrence(*args), 2e-5)
    got = jax.grad(lambda *a: jnp.sum(ours(*a) * w), range(6))(*args)
    want = jax.grad(lambda *a: jnp.sum(recurrence(*a) * w), range(6))(*args)
    for name, g, r in zip(("x", "dt", "A", "B", "C", "D"), got, want):
        _close(g, r, 2e-5, name)


@pytest.mark.parametrize("groups", [1, 8])
@pytest.mark.parametrize("chunk", [128, 256])
def test_the_kernels_take_groups_at_the_cells_chunks(groups, chunk):
    """Heads of 64 reading B and C in one group (4 heads) or in eight (32
    heads: two programs of two heads a group, so a group's gradient of ``G``
    is summed over its pairs, as over Nemotron-3-Nano's four), two chunks of
    128 (Nemotron-3-Nano's) or of 256 (Granite's), the conv's packed output:
    the kernels, interpreted, against the chunked oracle and the
    recurrence, y and every gradient."""
    args, w = operands(2 * chunk, heads=4 if groups == 1 else 32,
                       groups=groups, seed=groups)
    x, dt, A, B, C, D = args
    length = 2 * chunk

    def packed(x, dt, A, B, C, D, impl):
        xbc = jnp.concatenate([x.reshape(1, length, -1),
                               B.reshape(1, length, -1),
                               C.reshape(1, length, -1)], -1)
        y = ssd_op.ssd(xbc, dt, A, D=D, state_dim=128, groups=groups,
                       chunk=chunk, impl=impl)
        return y.reshape(x.shape)

    want = recurrence(*args)
    for impl in ("pallas", "chunked"):
        _close(packed(*args, impl), want, 2e-5, impl)
    got, oracle, truth = (
        jax.grad(lambda *a: jnp.sum(f(*a) * w), range(6))(*args)
        for f in (lambda *a: packed(*a, "pallas"),
                  lambda *a: packed(*a, "chunked"), recurrence))
    for name, g, o, r in zip(("x", "dt", "A", "B", "C", "D"), got, oracle,
                             truth):
        _close(g, o, 2e-5, name)
        _close(g, r, 2e-5, name)


def test_the_chunked_path_takes_several_groups():
    args, _ = operands(32, heads=4, p=8, n=16, groups=2)
    _close(ssd_op.ssd(*args, chunk=8, impl="chunked"), recurrence(*args),
           2e-5)


def test_the_state_is_carried_across_chunks():
    """With slow decays the first token of a chunk still sees the last
    chunk: dropping the carried state changes y past the first chunk."""
    (x, dt, A, B, C, D), _ = operands(48)
    y = ssd_op.ssd(x, dt, A, B, C, D, chunk=16, impl="pallas")
    alone = jnp.concatenate(
        [ssd_op.ssd(*(t[:, i:i + 16] for t in (x, dt)), A,
                    *(t[:, i:i + 16] for t in (B, C)), D, chunk=16,
                    impl="pallas") for i in (0, 16, 32)], 1)
    np.testing.assert_allclose(y[:, :16], alone[:, :16], rtol=1e-5,
                               atol=1e-5)
    assert float(jnp.max(jnp.abs(y[:, 16:] - alone[:, 16:]))) > 1e-2


@pytest.mark.parametrize("impl", ["chunked", "pallas"])
def test_the_conv_output_is_read_where_it_lies(impl):
    """The packed form ``[Bt, L, H x P + 2N]`` (what the mixer's conv
    writes) gives the unpacked form's y, and its gradient is the three
    parts' side by side."""
    (x, dt, A, B, C, D), _ = operands(32)
    packed = jnp.concatenate([x.reshape(1, 32, -1), B[:, :, 0], C[:, :, 0]],
                             -1)
    want = ssd_op.ssd(x, dt, A, B, C, D, chunk=16, impl=impl)
    got = ssd_op.ssd(packed, dt, A, D=D, state_dim=128, chunk=16, impl=impl)
    np.testing.assert_allclose(got, want.reshape(1, 32, -1), rtol=1e-6,
                               atol=1e-6)
    grad = jax.grad(lambda t: jnp.sum(jnp.sin(ssd_op.ssd(
        t, dt, A, D=D, state_dim=128, chunk=16, impl=impl))))(packed)
    parts = jax.grad(lambda x, B, C: jnp.sum(jnp.sin(ssd_op.ssd(
        x, dt, A, B, C, D, chunk=16, impl=impl))), (0, 1, 2))(x, B, C)
    np.testing.assert_allclose(grad, jnp.concatenate(
        [parts[0].reshape(1, 32, -1), parts[1][:, :, 0], parts[2][:, :, 0]],
        -1), rtol=1e-5, atol=1e-5)


def test_the_kernels_in_bfloat16_follow_the_float32_path():
    (x, dt, A, B, C, D), w = operands(48)
    low = [t.astype(jnp.bfloat16) for t in (x, B, C)]
    y = ssd_op.ssd(low[0], dt, A, low[1], low[2], D, chunk=16, impl="pallas")
    assert y.dtype == jnp.bfloat16
    _close(y.astype(jnp.float32), recurrence(x, dt, A, B, C, D), 2e-2)


def test_the_kernels_refuse_what_they_are_not_written_for():
    args, _ = operands(32, p=32)
    with pytest.raises(ValueError, match="heads of 64"):
        ssd_op.ssd(*args, chunk=16, impl="pallas")
    # a group's heads have to make whole pairs
    args, _ = operands(32, heads=4, groups=4)
    with pytest.raises(ValueError, match="an even number of them a group"):
        ssd_op.ssd(*args, chunk=16, impl="pallas")
    with pytest.raises(ValueError, match="impl"):
        ssd_op.ssd(*args, chunk=16, impl="dense")


def test_chunk_sums_restart_at_every_chunk():
    dt = jnp.ones((1, 8, 2))
    cs = ssd_op.chunk_cumsum(dt, jnp.array([-1.0, -2.0]), 4)
    np.testing.assert_array_equal(cs[0, :, 0], [-1, -2, -3, -4] * 2)
    np.testing.assert_array_equal(cs[0, :, 1], [-2, -4, -6, -8] * 2)
    assert ssd_op.state_bytes(1, 16384, 64, 128, 64) == 128 * 2 ** 20
