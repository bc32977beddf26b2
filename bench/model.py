"""The benchmark's own weights, drawn on the device from the seed.

The weights belong to the benchmark, not to the program: the same arrays
are handed to the engine and, after the window, to the plain reference.
The tree comes from the architecture module the configuration names
(``layout(m)``: leaf -> (shape, init), in the engine's parameter layout);
every leaf is drawn in one jitted call, straight in bf16 (the served type):
normal with the leaf's standard deviation, or ones where init is "ones".
Leaves are drawn in ``jax.tree.flatten`` order, each from its own split of
the seed's key.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def key_of(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also past 32 bits."""
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0x7FFFFFFF)


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


@functools.partial(jax.jit, static_argnums=0)
def _draw(spec: Tuple, key):
    leaves, treedef = spec
    keys = jax.random.split(key, len(leaves))
    out = []
    for (shape, init), k in zip(leaves, keys):
        if init == "ones":
            out.append(jnp.ones(shape, jnp.bfloat16))
        else:
            out.append(jax.random.normal(k, shape, jnp.bfloat16)
                       * jnp.asarray(init, jnp.bfloat16))
    return jax.tree.unflatten(treedef, out)


def draw(layout: Dict, seed: int, device=None) -> Dict:
    """Every leaf of ``layout`` (an architecture module's ``layout(m)``) in
    one jitted program on ``device`` (default: the first device)."""
    leaves, treedef = jax.tree.flatten(layout, is_leaf=_is_leaf)
    key = key_of(seed)
    dev = device if device is not None else jax.devices()[0]
    with jax.default_device(dev):
        return _draw((tuple(leaves), treedef), key)
