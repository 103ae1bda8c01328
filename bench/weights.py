"""Random weights from ``--seed``, made on the device in one jitted call, in
the dtype they are served in.

The tree of shapes is a family's (``shapes`` of ``bench/families/<family>.py``,
in its reference's layout); a leaf is drawn by the rule its path names:
a path holding ``norm`` is a norm weight as published (around 1), one
holding ``embed`` an embedding, any other a matrix scaled by its fan-in
(the second-last axis).  ``program.program_params`` hands the same arrays
to the program in its own tree.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative integer seed (wider than 32 bits
    too): its 64 low bits, as two 32-bit words."""
    s = int(seed) % (1 << 64)
    return jax.random.wrap_key_data(np.array([s >> 32, s & 0xFFFFFFFF], np.uint32))


@partial(jax.jit, static_argnames=("treedef", "leaves", "dtype"))
def _make(key, *, treedef, leaves, dtype):
    out = []
    for k, (path, shape) in zip(jax.random.split(key, len(leaves)), leaves):
        if "norm" in path:
            x = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        elif "embed" in path:
            x = 0.02 * jax.random.normal(k, shape, jnp.float32)
        else:  # matrices: fan-in scaled; the fan-in is the second-last axis
            x = jax.random.normal(k, shape, jnp.float32) / np.sqrt(shape[-2])
        out.append(x.astype(dtype))
    return jax.tree.unflatten(treedef, out)


def make_weights(shapes: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Weights for a tree of shapes (tuples), in ``dtype``."""
    is_shape = lambda s: isinstance(s, tuple)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=is_shape)
    leaves = tuple((jax.tree_util.keystr(p), s) for p, s in flat)
    return _make(seed_key(seed), treedef=treedef, leaves=leaves, dtype=dtype)
