"""What the plain references share: the precision a product is taken in, the
two optimizers as their papers give them, and norms by leaf.

``precision`` names what the operands of every matrix product and convolution
are rounded to before a float32 product at ``highest``:

* ``float32``: nothing, the reference itself;
* ``bfloat16``: what the configurations state for the program;
* ``fp8``: the control, the step below bfloat16, with one scale a tensor
  taken from its largest magnitude, as an fp8 recipe would.

The rounding is a straight-through one: the backward pass sees the identity, and
its own products round their operands the same way.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
PRECISIONS = ("float32", "bfloat16", "fp8")


def _round_to(x, precision: str):
    """``lax.reduce_precision`` and not a pair of ``astype``: XLA:TPU may keep
    the excess precision of a convert there and back, and did (the control's
    first layer read 6e-8 from the reference; PERF.md)."""
    if precision == "bfloat16":
        return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    if precision == "fp8":
        s = 240.0 / amax        # the largest finite IEEE-style e4m3
        return lax.reduce_precision(x * s, exponent_bits=4,
                                    mantissa_bits=3) / s
    raise ValueError(f"precision {precision!r} is none of {PRECISIONS}")


def rounder(precision: str):
    """``x -> x`` rounded as ``precision`` says, identity to the gradient."""
    if precision == "float32":
        return lambda x: x

    @jax.custom_vjp
    def q(x):
        return _round_to(x, precision)

    q.defvjp(lambda x: (_round_to(x, precision), None),
             lambda _, g: (_round_to(g, precision),))
    return q


def make_einsum(precision: str):
    q = rounder(precision)

    def einsum(spec, a, b):
        return jnp.einsum(spec, q(a), q(b), precision=HIGHEST,
                          preferred_element_type=jnp.float32)

    return einsum


def make_conv(precision: str):
    q = rounder(precision)

    def conv(x, w, stride: int, padding):
        return lax.conv_general_dilated(
            q(x), q(w), window_strides=(stride, stride), padding=padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
            preferred_element_type=jnp.float32)

    return conv


# ---------------------------------------------------------------- optimizers


def sgd_momentum_init(params):
    return {"trace": jax.tree_util.tree_map(jnp.zeros_like, params)}


def sgd_momentum(params, grads, state, *, lr, momentum):
    """Sutskever et al. 2013 as every framework writes it: the trace is the
    gradient plus ``momentum`` times the old trace."""
    trace = jax.tree_util.tree_map(lambda g, t: g + momentum * t, grads,
                                   state["trace"])
    params = jax.tree_util.tree_map(lambda p, t: p - lr * t, params, trace)
    return params, {"trace": trace}


def adam_init(params):
    zeros = functools.partial(jax.tree_util.tree_map, jnp.zeros_like)
    return {"m": zeros(params), "v": zeros(params),
            "t": jnp.zeros((), jnp.float32)}


def adam(params, grads, state, *, lr, b1, b2, eps):
    """Kingma & Ba 2015, Algorithm 1."""
    t = state["t"] + 1.0
    m = jax.tree_util.tree_map(lambda g, m: b1 * m + (1 - b1) * g, grads,
                               state["m"])
    v = jax.tree_util.tree_map(lambda g, v: b2 * v + (1 - b2) * g * g, grads,
                               state["v"])
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        params, m, v)
    return params, {"m": m, "v": v, "t": t}


def optimizer(spec: dict):
    """``(init, update)`` for a configuration's ``optimizer`` entry."""
    spec = dict(spec)
    name = spec.pop("name")
    if name == "sgd_momentum":
        return sgd_momentum_init, functools.partial(sgd_momentum, **spec)
    if name == "adam":
        return adam_init, functools.partial(adam, **spec)
    raise ValueError(f"no reference optimizer {name!r}")


# --------------------------------------------------------------------- norms


def sq_norm_rows(x):
    """Squared norm of each slice along the first axis."""
    return jnp.sum(jnp.square(x.astype(jnp.float32)),
                   axis=tuple(range(1, x.ndim)))


def leaf_sq_norms(tree):
    """``{leaf name: squared norm}`` of a tree of nested dicts."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(k.key for k in path): jnp.sum(jnp.square(leaf))
            for path, leaf in flat}


def readings(losses, grad_sq: dict, delta_sq: dict, **more_sq) -> dict:
    """What every reference's ``train_steps`` returns, from squared norms."""
    def roots(sq):
        return {k: float(v) ** 0.5 for k, v in jax.device_get(sq).items()}

    out = {"losses": losses, "grad_norms": roots(grad_sq),
           "delta_norms": roots(delta_sq)}
    out.update({k: roots(v) for k, v in more_sq.items()})
    return out
