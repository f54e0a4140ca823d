"""A chip's share of a sparse expert layer: route over all the experts, drop
nothing, compute what the experts held here give.

The layer is told which experts it holds (``first`` and the leading axis of
the stacked expert weights). It scores every token against **all** ``E``
experts (sigmoid scores in float32; a selection bias, a buffer and no
parameter, is added to choose and left out to weigh), takes the top ``k``,
and computes ``sum_e w_e MLP_e(x)`` over the chosen experts that are held
here; what the absent experts would add is another chip's part of the sum.
On one chip the layer runs without an exchange: nothing here stands in for
the absent chips.

No token is dropped, whatever the routing: the ``T x k`` (token, choice)
pairs are sorted by expert, pairs of absent experts last, into a buffer whose
provable bound is ``T x k`` rows (every token choosing ``k`` held experts
fills it), and three grouped matrix products (``jax.lax.ragged_dot``, which
XLA:TPU lowers to its own grouped-matmul kernel; the group sizes are data)
run over it: gate, up and down; two where an expert has no gate (``relu(x
W_up)^2 W_down``). The gathers and elementwise passes around those products cost
by the buffer's rows and not by the occupied ones, so the step looks at how
many rows are occupied and runs on the buffer's first ``2 x`` what even
routing fills where that holds them all, and on the whole bound where it
does not (:func:`buffer_sizes`). There is no capacity and nothing to tune. Gathers move the rows both
ways, in the backward pass too: sorting is a permutation, so the transpose of
"take rows by ``order``" is "take rows by the inverse of ``order``", and no
scatter-add runs on the device.

Scopes (docs/timeline.md): ``hvd_moe_route``, ``hvd_moe_dispatch`` (sort
and gather), ``hvd_moe_experts`` (the grouped products), ``hvd_moe_combine``;
gauges ``hvd.moe.*`` of the program being traced, ``hvd.moe.expert_products``
among them: the forward grouped products the step's expert layers issue, a
recomputed layer's twice (the backward pass issues two for each of a
layer's).
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.utils import timeline


def route(x, router_w, bias, top_k: int, route_scale: float = 1.0):
    """``x [T, d]``, ``router_w [d, E]``, ``bias [E]`` or ``None`` ->
    ``(chosen [T, k] int32, weights [T, k] float32, counts [E] float32)``.

    ``s = sigmoid(x W_r)`` in float32 at the highest precision (a bfloat16
    product flips near-ties among the top ``k``, and the product is a
    thousandth of the layer's work); the chosen set is the top ``k`` of
    ``s + bias``; the weights are the chosen experts' **unbiased** scores
    over their sum, times ``route_scale``; ``counts`` is how many tokens
    chose each expert."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    biased = scores if bias is None else scores + lax.stop_gradient(bias)
    _, chosen = lax.top_k(biased, top_k)
    # by a one-hot product and not ``take_along_axis``, whose transpose is a
    # scatter-add of T x k scalars
    onehot = jax.nn.one_hot(chosen, router_w.shape[-1], dtype=jnp.float32)
    picked = jnp.sum(onehot * scores[:, None, :], axis=-1)
    weights = route_scale * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return chosen, weights, jnp.sum(onehot, axis=(0, 1))


def update_selection_bias(bias, counts, coeff: float):
    """The balancing rule that takes the place of an auxiliary loss, once
    after every optimizer step: ``delta = coeff x sign(mean(c) - c)``,
    centred, added to the bias (an expert chosen less than the mean is
    lifted, one chosen more is lowered)."""
    delta = coeff * jnp.sign(jnp.mean(counts) - counts)
    return bias + (delta - jnp.mean(delta))


def _rows_of_pairs(rows, inverse):
    """Every pair's row of the buffer's first ``len(rows)``, zeros for a
    pair whose row lies past them."""
    padded = jnp.concatenate([rows, jnp.zeros_like(rows[:1])])
    return padded[jnp.minimum(inverse, rows.shape[0])]


# Three gathers and their transposes, which are gathers too because sorting
# is a permutation. ``order [n]``: the pair in each of the buffer's first n
# rows; ``inverse [T k]``: each pair's row. No cotangent goes to either.

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _spread(x, order, inverse, top_k: int):
    """Tokens ``x [T, d]`` into the buffer: row ``r`` is the token of pair
    ``order[r]``."""
    return x[order // top_k]


def _spread_fwd(x, order, inverse, top_k):
    return x[order // top_k], (order, inverse)


def _spread_bwd(top_k, res, g):
    order, inverse = res
    return _gather_sum(g, order, inverse, top_k).astype(g.dtype), None, None


_spread.defvjp(_spread_fwd, _spread_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gather_sum(rows, order, inverse, top_k: int):
    """The buffer's rows back to their tokens, a token's pairs added up in
    float32: ``[T, d]``. The transpose of :func:`_spread`."""
    pairs = _rows_of_pairs(rows, inverse).reshape(
        inverse.shape[0] // top_k, top_k, -1)
    return pairs.astype(jnp.float32).sum(1)


def _gather_sum_fwd(rows, order, inverse, top_k):
    return _gather_sum(rows, order, inverse, top_k), (order, inverse,
                                                      rows[:0])


def _gather_sum_bwd(top_k, res, g):
    order, inverse, like = res
    return _spread(g.astype(like.dtype), order, inverse, top_k), None, None


_gather_sum.defvjp(_gather_sum_fwd, _gather_sum_bwd)


@jax.custom_vjp
def _sorted(values, order, inverse):
    """A number a pair, ``values [T k]``, in the buffer's order: ``[n]``."""
    return values[order]


_sorted.defvjp(
    lambda values, order, inverse: (values[order], inverse),
    lambda inverse, g: (_rows_of_pairs(g[:, None], inverse)[:, 0], None,
                        None))


def gated_mlp_grouped(rows, sizes, experts: Dict):
    """``(silu(rows W_gate) * (rows W_up)) W_down`` with every row under its
    own expert's matrices: ``experts`` holds ``gate`` and ``up`` ``[held, d,
    f]`` and ``down`` ``[held, f, d]``, ``rows`` lie sorted by expert and
    ``sizes [held]`` says how many each has. Without ``gate`` the MLP is
    ``relu(rows W_up)^2 W_down``: two products, not three. Rows past their
    sum belong to no expert: they come back as zeros and take no gradient,
    and every product's operands hold zeros there, so whatever a
    grouped-product kernel leaves or reads past the last group can reach no
    result."""
    occupied = (jnp.arange(rows.shape[0]) < jnp.sum(sizes))[:, None]

    def dot(lhs, rhs):
        out = lax.ragged_dot(jnp.where(occupied, lhs, 0),
                             rhs.astype(lhs.dtype), group_sizes=sizes,
                             preferred_element_type=lhs.dtype)
        return jnp.where(occupied, out, 0)

    if "gate" not in experts:
        up = dot(rows, experts["up"]).astype(jnp.float32)
        hidden = jnp.square(jax.nn.relu(up))
        return dot(hidden.astype(rows.dtype), experts["down"])
    gate, up = dot(rows, experts["gate"]), dot(rows, experts["up"])
    hidden = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
    return dot(hidden.astype(rows.dtype), experts["down"])


CUT = 2         # of what even routing fills; the bound is the other size


def buffer_sizes(bound: int, expected: float):
    """The row counts a layer's sorted buffer may be cut to, ascending: the
    layer runs on the first where it holds the step's occupied rows and on
    the last, ``bound`` (every pair's expert held here), where it does not,
    so **no routing is ever cut short**. The first is twice what even routing
    fills, in multiples of 8. Even routing is no steady state: a router that
    scores 8,192 tokens independently fills 8,192 rows give or take a few
    hundred, and training moves it: of a chip's share of the layer only the
    held experts' outputs reach the loss, and the router learns to send them
    half as many rows again within some twenty Adam steps on one batch
    (12,100-12,700 where 8,192 were expected; PERF.md, PR 28). Past the cut
    the layer's passes run over four times the rows. The cut is no capacity:
    it only spares the gathers and elementwise passes over rows that no
    expert reads. Every size is a branch of the layer that XLA compiles,
    forward and backward, which is why there are two and no ladder."""
    return sorted({bound, min(bound, -(-int(expected * CUT) // 8) * 8)})


def _held_part(n: int, top_k: int, x, experts, order, inverse, sizes,
               weights):
    """``sum over a token's pairs of held experts of w MLP_e(x)`` with the
    sorted buffer cut to its first ``n`` rows (all the occupied ones)."""
    order = order[:n]
    with jax.named_scope(timeline.MOE_DISPATCH):
        rows = _spread(x, order, inverse, top_k)               # [n, d]
    with jax.named_scope(timeline.MOE_EXPERTS):
        out = gated_mlp_grouped(rows, sizes, experts)
    with jax.named_scope(timeline.MOE_COMBINE):
        # weighted in the buffer's order, where only n rows are: a row past
        # the occupied ones holds zeros, whatever its pair's weight
        w = _sorted(weights.reshape(-1), order, inverse)
        out = (out.astype(jnp.float32) * w[:, None]).astype(out.dtype)
        return _gather_sum(out, order, inverse, top_k)


def routed_experts(x, router_w, experts: Dict, bias=None, *, first=0,
                   top_k: int, route_scale: float = 1.0, dtype=None,
                   name: str = "", recomputed: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of the layer's result, and the step's counts.

    ``x [T, d]``; ``router_w [d, E]`` scores all ``E`` experts; ``experts``
    are the stacked matrices of the ``held`` experts ``first .. first +
    held - 1`` (:func:`gated_mlp_grouped`: with or without ``gate``);
    ``bias [E]`` or ``None``. The
    router reads ``x`` as it is given (float32 from a norm); the experts'
    products run in ``dtype`` (default: ``x``'s), which ``y`` comes back in.
    ``recomputed`` says that the backward pass runs the layer's forward
    again (the gauge ``hvd.moe.expert_products`` counts its products twice).
    Returns ``(y [T, d], counts [E])``: ``y = sum over chosen experts held
    here of w_e MLP_e(x)``, exact for any routing (module docstring), and
    how many tokens chose each of the ``E``."""
    tokens, _ = x.shape
    dtype = dtype or x.dtype
    held, scored = experts["up"].shape[0], router_w.shape[-1]
    bound = tokens * top_k
    expected = bound * held / scored
    cuts = buffer_sizes(bound, expected)
    products = (3 if "gate" in experts else 2) * (1 + recomputed)
    _record(name, products, experts=scored,
            experts_held=held, top_k=top_k,
            tokens=tokens, row_bound=bound, expected_rows=expected,
            cut_rows=cuts[0])
    with jax.named_scope(timeline.MOE_ROUTE):
        chosen, weights, counts = route(x, router_w, bias, top_k, route_scale)
    with jax.named_scope(timeline.MOE_DISPATCH):
        local = chosen - first                                 # [T, k]
        mine = (local >= 0) & (local < held)
        group = jnp.where(mine, local, held).reshape(-1)       # absent: last
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        inverse = jnp.argsort(order).astype(jnp.int32)
        sizes = jnp.sum(group[:, None] == jnp.arange(held)[None], axis=0,
                        dtype=jnp.int32)
    # Each cut recomputes itself in the backward pass: differentiated as it
    # stands, the switch would hand out every cut's residuals on every step,
    # zeros for all but one (AOT for the v5e: 8.2 GB of temporaries and not
    # 2.9; PERF.md, PR 28).
    branches = [jax.checkpoint(functools.partial(_held_part, n, top_k))
                for n in cuts]
    fits = jnp.sum(jnp.sum(sizes) > jnp.asarray(cuts[:-1], jnp.int32))
    y = lax.switch(fits, branches, x.astype(dtype), experts, order, inverse,
                   sizes, weights)
    return y.astype(dtype), counts


# The layers of the program being traced, as gauges keyed by that program
# (the ``program`` of the ``hvd.spmd.dispatch`` span whose call traces it,
# as the gradient exchange's are): program -> (id of that span, {layer name:
# its grouped products}). Block recomputation traces a layer more than once,
# a re-trace starts anew, so layers are told apart by name.
_traced: dict = {}


def _record(name: str, products: int, **sizes) -> None:
    """``hvd.moe.layers`` (expert layers in the step's program),
    ``hvd.moe.expert_products`` (the forward grouped products a step
    issues, each layer once however often it is traced: 3 a gated layer, 2
    one without a gate, twice that where the backward pass recomputes the
    layer) and, of one layer: ``.experts`` (scored),
    ``.experts_held``, ``.top_k``, ``.tokens`` (a step, on this chip),
    ``.row_bound`` (rows of the sorted buffer: what never dropping is sized
    for), ``.expected_rows`` (rows a step under even routing: ``tokens x
    top_k x held / experts``) and ``.cut_rows`` (rows the layer's passes run
    over while the occupied ones fit its first cut)."""
    program, layers = timeline.program_tally(_traced, dict)
    layers[name] = products
    timeline.gauge("hvd.moe.layers", len(layers), key=program)
    timeline.gauge("hvd.moe.expert_products", sum(layers.values()),
                   key=program)
    for what, value in sizes.items():
        timeline.gauge("hvd.moe." + what, value, key=program)
