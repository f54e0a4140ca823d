"""Weights and batches from ``--seed``, made by the benchmark.

The program under test and the plain reference are both handed what is drawn
here, so neither takes anything the other has made. A tree of shapes (the
program's parameter tree, as ``jax.eval_shape`` gives it) comes back as a tree
of arrays, drawn in one jitted call on the device:

* ``kernel`` (and any other leaf of two or more dimensions): normal, standard
  deviation ``sqrt(gain / fan_in)``, ``fan_in`` the product of all but the
  last dimension; ``gain`` is the configuration's ``kernel_gain``;
* ``embedding``: normal, standard deviation 0.02;
* ``scale``: 1 + 0.1 x normal; every other vector: 0.02 x normal;
* a leaf whose name ends in a key of the configuration's ``draws``: ``mean +
  std x normal``, whatever its kind.

No norm scale starts at zero (flax's ResNet zero-initialises the last scale of
each block, which makes most first gradients exactly zero and the comparison
blind to most of the network); ResNet-50's ``draws`` start those scales small
instead, as near to the program's start as leaves every gradient alive. A
leaf's key is the run's key folded with the leaf's position in the sorted
tree, so the same seed gives the same weights whatever the sharding.
"""

import math

import jax
import jax.numpy as jnp


def run_key(seed: int):
    """A key from any whole number up to 2**32 and beyond."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "name", k)))
                    for k in path)


def draw_leaf(key, name: str, shape, dtype, kernel_gain: float, draws=None):
    last = name.rsplit("/", 1)[-1]
    noise = jax.random.normal(key, shape, jnp.float32)
    own = next((v for k, v in (draws or {}).items() if name.endswith(k)), None)
    if own is not None:
        out = own["mean"] + own["std"] * noise
    elif last == "embedding":
        out = 0.02 * noise
    elif len(shape) >= 2:
        out = noise * math.sqrt(kernel_gain / math.prod(shape[:-1]))
    elif last == "scale":
        out = 1.0 + 0.1 * noise
    else:
        out = 0.02 * noise
    return out.astype(dtype)


def draw_params(key, shapes, kernel_gain: float, draws=None):
    """``shapes``: a pytree whose leaves have ``.shape`` and ``.dtype``;
    ``draws``: ``{end of a leaf's name: {"mean", "std"}}``."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = [draw_leaf(jax.random.fold_in(key, i), leaf_name(path),
                     leaf.shape, leaf.dtype, kernel_gain, draws)
           for i, (path, leaf) in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def draw_batch(key, shapes, ranges):
    """A batch whose rows all differ: floats are normal, integers uniform in
    ``[0, ranges[name])``."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        k = jax.random.fold_in(key, 1_000_003 + i)
        if jnp.issubdtype(leaf.dtype, jnp.integer):
            out.append(jax.random.randint(k, leaf.shape, 0,
                                          ranges[leaf_name(path)],
                                          leaf.dtype))
        else:
            out.append(jax.random.normal(k, leaf.shape, leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def shapes_of(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
