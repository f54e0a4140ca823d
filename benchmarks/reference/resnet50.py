"""ResNet-50 (He et al. 2015, arXiv:1512.03385, Table 1, 50-layer) trained as
a classifier: the plain reference.

A 7x7/2 stem, a 3x3/2 max-pool, four stages of 3, 4, 6 and 3 bottleneck blocks
(1x1, 3x3, 1x1, the last four times as wide) with a projection where the shape
changes, batch normalisation after every convolution with the statistics of
the batch, the mean over positions, one dense layer, softmax cross-entropy.
Everything float32 with products at ``highest``. Where the program under test
departs from the paper, the configuration's ``hyper`` entry says so and the
reference follows it: the stride of a down-sampling block sits on its 3x3
("v1.5", as torchvision and Horovod's benchmark run it), and padding is XLA's
``SAME`` (the extra row and column after, not before).

Parameters arrive under the names the benchmark drew them with: ``stem``,
``BottleneckResNetBlock_<i>/{ConvBN_0, ConvBN_1, ConvBN_2, proj}`` each with
``kernel``, ``scale``, ``bias``, and ``head/{kernel, bias}``. Batch statistics
tie the rows of a batch together, so a step takes the batch whole and each
block is recomputed in the backward pass instead. Beside the readings every
reference gives, this one gives ``stat_norms``: the norm of the first step's
batch mean and batch variance of every convolution's output, which the
program keeps (as running averages) in its state.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import common

BLOCK = "BottleneckResNetBlock_"


def _conv_bn(x, p, stride, padding, *, conv, eps):
    """``(normalised output, the batch's statistics)``."""
    y = conv(x, p["kernel"], stride, padding)
    mean = jnp.mean(y, (0, 1, 2))
    var = jnp.mean(jnp.square(y - mean), (0, 1, 2))
    out = (y - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return out, {"mean": lax.stop_gradient(mean),
                 "var": lax.stop_gradient(var)}


def _bottleneck(x, p, stride, *, stride_on_3x3, conv_bn):
    s1, s3 = (1, stride) if stride_on_3x3 else (stride, 1)
    stats = {}
    y, stats["ConvBN_0"] = conv_bn(x, p["ConvBN_0"], s1, "SAME")
    y, stats["ConvBN_1"] = conv_bn(jax.nn.relu(y), p["ConvBN_1"], s3, "SAME")
    y, stats["ConvBN_2"] = conv_bn(jax.nn.relu(y), p["ConvBN_2"], 1, "SAME")
    if "proj" in p:
        x, stats["proj"] = conv_bn(x, p["proj"], stride, "SAME")
    return jax.nn.relu(x + y), stats


def _loss(params, images, labels, *, stages, stride_on_3x3, conv, einsum,
          eps):
    """``(mean loss, (loss of each row, every layer's batch statistics))``."""
    conv_bn = functools.partial(_conv_bn, conv=conv, eps=eps)
    stats = {}
    x, stats["stem"] = conv_bn(images, params["stem"], 2, [(3, 3), (3, 3)])
    x = jax.nn.relu(x)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    i = 0
    for stage, blocks in enumerate(stages):
        for j in range(blocks):
            stride = 2 if stage > 0 and j == 0 else 1
            block = jax.checkpoint(functools.partial(
                _bottleneck, stride=stride, stride_on_3x3=stride_on_3x3,
                conv_bn=conv_bn))
            x, stats[f"{BLOCK}{i}"] = block(x, params[f"{BLOCK}{i}"])
            i += 1
    x = jnp.mean(x, (1, 2))
    logits = einsum("bf,fc->bc", x, params["head"]["kernel"]) \
        + params["head"]["bias"]
    logp = jax.nn.log_softmax(logits, -1)
    rows = -jnp.take_along_axis(logp, labels[:, None], -1)[:, 0]
    return rows.mean(), (rows, stats)


def train_steps(params, batch, hyper: dict, *, steps: int, precision: str,
                loss_rows: int, rows_per_block=None, use_rows=None):
    """Drive ``steps`` optimizer steps on the one batch; see
    ``gpt2.train_steps`` for the arguments and what comes back.
    ``rows_per_block`` has no meaning here (batch statistics need the whole
    batch at once)."""
    images, labels = batch["image"], batch["label"]
    if use_rows is not None:
        images, labels = images[:use_rows], labels[:use_rows]
        loss_rows = min(loss_rows, use_rows)
    loss = functools.partial(
        _loss, stages=hyper["stages"], stride_on_3x3=hyper["stride_on_3x3"],
        conv=common.make_conv(precision),
        einsum=common.make_einsum(precision), eps=hyper["batch_norm_eps"])
    opt_init, opt_update = common.optimizer(hyper["optimizer"])
    grad = jax.jit(jax.value_and_grad(loss, has_aux=True))
    update = jax.jit(opt_update, donate_argnums=(0, 2))
    sq_norms = jax.jit(common.leaf_sq_norms)
    sq_diff = jax.jit(lambda a, b: common.leaf_sq_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b)))

    p = jax.tree_util.tree_map(jnp.copy, params)
    opt_state = opt_init(p)
    losses, grad_sq, stat_sq = [], None, None
    for step in range(steps):
        (_, (rows, stats)), g = grad(p, images, labels)
        losses.append(float(rows[:loss_rows].mean()))
        if step == 0:
            grad_sq = sq_norms(g)
            stat_sq = sq_norms(stats)
        p, opt_state = update(p, g, opt_state)
    return common.readings(losses, grad_sq, sq_diff(p, params),
                           stat_norms=stat_sq)
