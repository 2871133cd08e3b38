"""Weights made on the device from the seed, in one jitted call, in the
layout the program serves: 2:4 projections already packed as
``{"vals", "idx"}`` (two kept values of each group of four input rows,
and their positions), every other leaf dense in the model's dtype.

The 2:4 pattern is drawn from the seed: which two of four weights a
mask keeps does not change the cost of serving, and drawing it here
keeps the program's pruner out of the serve cells' set-up.  The
reference rebuilds the dense masked weights from the same arrays
(``reference/forward.py``), so it takes nothing that the program made.
"""

from __future__ import annotations

import math
import re
from typing import Any

import jax
import jax.numpy as jnp

# the projections the serve path packs (the program's own default list)
PACKED = (re.compile(r"(mlp|moe/shared)/(wi|wg|wo)$"),
          re.compile(r"attn/(wq|wk|wv|wo)$"))
# the six ways to keep two of four, positions ascending
FIRST = (0, 0, 0, 1, 1, 2)
SECOND = (1, 2, 3, 2, 3, 3)


def _path(keypath) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in keypath)


def _scale(path: str, shape, n_layers: int) -> float:
    """Standard deviation of a leaf, as the model's own initializer sets
    it: 1/sqrt(fan-in), output projections scaled down with depth."""
    fan_in = shape[-2]
    s = 1.0 / math.sqrt(fan_in)
    if path.endswith("/wo"):
        s /= math.sqrt(2 * n_layers)
    return s


def make(model, key, packed: bool) -> Any:
    """The params tree of ``model`` from ``key``, on the device."""
    out = jax.jit(build_fn(model, packed))(key)
    jax.block_until_ready(out)
    return out


def build_fn(model, packed: bool):
    """The function of a key that builds ``model``'s params: 2:4
    projections packed when ``packed`` (each kept value scaled so the
    layer's output has the dense initializer's variance), dense
    otherwise."""
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    n_layers = model.cfg.num_layers
    first = jnp.asarray(FIRST, jnp.int8)
    second = jnp.asarray(SECOND, jnp.int8)

    def build(key):
        leaves = []
        for i, (kp, leaf) in enumerate(flat):
            path = _path(kp)
            k = jax.random.fold_in(key, i)
            dt = leaf.dtype
            if path.endswith("/scale"):                     # norm gains
                leaves.append((1.0 + 0.1 * jax.random.normal(
                    k, leaf.shape, jnp.float32)).astype(dt))
            elif path.endswith("embed/tok"):
                leaves.append((0.02 * jax.random.normal(
                    k, leaf.shape, jnp.float32)).astype(dt))
            elif len(leaf.shape) == 1 or re.search(r"/b[qkv]$", path):
                leaves.append((0.02 * jax.random.normal(
                    k, leaf.shape, jnp.float32)).astype(dt))
            elif packed and any(p.search(path) for p in PACKED):
                *lead, kin, n = leaf.shape
                s = _scale(path, leaf.shape, n_layers) * math.sqrt(2.0)
                kv, ki = jax.random.split(k)
                vals = (s * jax.random.normal(
                    kv, (*lead, kin // 2, n), jnp.float32)).astype(dt)
                c = jax.random.randint(ki, (*lead, kin // 4, n), 0, 6)
                idx = jnp.stack([first[c], second[c]], axis=-2)  # (.., G, 2, N)
                leaves.append({"vals": vals,
                               "idx": idx.reshape(*lead, kin // 2, n)})
            else:
                s = _scale(path, leaf.shape, n_layers)
                leaves.append((s * jax.random.normal(
                    k, leaf.shape, jnp.float32)).astype(dt))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return build
