"""The benchmark's own weights: made on the device, in one jitted call,
from ``--seed``.

The tree, the dtypes and the shardings come from the program (``jax.
eval_shape`` of its init, and the shardings the caller passes); the
values are the benchmark's, drawn leaf by leaf from the seed, so the
reference and the program read the same numbers and the program's own
init (an unrolled per-layer program that takes minutes to compile) is
never run.

Per-leaf law, by the leaf's logical axes (the ``layers`` axis aside):

- the token-embedding table ``(vocab, embed)``: N(0, 1);
- norm gains and biases (1-D, or named ``gamma``/``b*``): N(0, 0.02),
  small but not zero, so the check sees them;
- query and key projections: N(0, 2 / sqrt(fan_in)), so scores spread
  over about 4 units and each head attends to a few positions. With
  1 / sqrt(fan_in) a random model averages its whole context, its
  logits follow the current token, and a wrong cache would hardly show;
- every other matrix: N(0, 1 / sqrt(fan_in)), ``fan_in`` being the
  ``embed`` axis where it comes first, else every axis but the last.
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any
SHARP = ("wq", "wk")


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that tells apart every seed up to 2**64."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def leaf_std(name: str, axes, shape) -> float:
    axes = tuple(axes)
    shape = tuple(shape)
    if axes and axes[0] == "layers":
        axes, shape = axes[1:], shape[1:]
    if axes == ("vocab", "embed"):
        return 1.0
    if len(shape) < 2 or name == "gamma" or name.startswith("b"):
        return 0.02
    fan_in = shape[0] if axes[0] == "embed" else int(np.prod(shape[:-1]))
    return (2.0 if name in SHARP else 1.0) / math.sqrt(fan_in)


def make(boxed_shapes: PyTree, raw_shapes: PyTree, seed: int,
         shardings: Optional[PyTree] = None) -> PyTree:
    """Raw params shaped and typed like ``raw_shapes`` (``eval_shape`` of
    the program's init as the path holds its weights), with each leaf's
    law taken from the logical axes of ``boxed_shapes`` (``eval_shape``
    of the program's Boxed init), placed with ``shardings`` (a matching
    tree, or None for the default device)."""
    from repro.models import layers as L
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        boxed_shapes, is_leaf=L.is_boxed)
    raw = jax.tree.leaves(raw_shapes)
    if len(raw) != len(flat):
        raise ValueError("boxed and raw parameter trees differ")
    specs = []
    for (path, b), r in zip(flat, raw):
        if tuple(r.shape) != tuple(b.value.shape):
            raise ValueError(f"{jax.tree_util.keystr(path)}: {r.shape} vs "
                             f"{b.value.shape}")
        name = str(getattr(path[-1], "key", path[-1]))
        specs.append((tuple(r.shape), jnp.dtype(r.dtype),
                      leaf_std(name, b.axes, r.shape)))

    def build(key):
        leaves = []
        for i, (shape, dt, std) in enumerate(specs):
            k = jax.random.fold_in(key, i)
            leaves.append((jax.random.normal(k, shape, jnp.float32)
                           * std).astype(dt))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    fn = jax.jit(build, out_shardings=shardings)
    return fn(seed_key(seed))
