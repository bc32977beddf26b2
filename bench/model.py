"""The benchmark's own weights, drawn on the device from the seed.

The weights belong to the benchmark, not to the program: the same arrays
are handed to the engine and, after the window, to the plain reference.
They are drawn in one jitted call, straight in bf16 (the served type), in
the tree layout the engine's parameters take:

    embed (V, D), final_norm (D,), head (D, V),
    layers: ln1 (L, D), ln2 (L, D),
            attn: wq (L, D, Hq, dh), wk/wv (L, D, Hkv, dh), wo (L, Hq, dh, D),
            mlp:  wi (L, D, F), wo (L, F, D)

with V the vocabulary rounded up to 256 (the padded head columns are
masked by both sides).  Projections are normal with standard deviation
fan_in ** -0.5, the two that write into the residual stream (attention's
and the MLP's output) scaled by (2 L) ** -0.5; the embedding has standard
deviation 1, norm scales are 1.  With a small embedding and unscaled
residual writes, ten random layers drive every position to a handful of
tokens, and a check of served tokens then sees little.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench import costs


def key_of(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also past 32 bits."""
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0x7FFFFFFF)


def layout(m: Dict) -> Dict:
    """Leaf -> (shape, init) where init is a std or "ones"."""
    D, F, L = m["d_model"], m["d_ff"], m["n_layers"]
    Hq, Hkv, dh = m["n_heads"], m["n_kv_heads"], costs.head_dim(m)
    V = costs.vocab_padded(m)
    res = (2 * L) ** -0.5
    if m["activation"] not in ("gelu", "squared_relu"):
        raise ValueError(f"dense reference covers gelu and squared_relu "
                         f"MLPs, not {m['activation']!r}")
    return {
        "embed": ((V, D), 1.0),
        "final_norm": ((D,), "ones"),
        "head": ((D, V), D ** -0.5),
        "layers": {
            "ln1": ((L, D), "ones"),
            "ln2": ((L, D), "ones"),
            "attn": {"wq": ((L, D, Hq, dh), D ** -0.5),
                     "wk": ((L, D, Hkv, dh), D ** -0.5),
                     "wv": ((L, D, Hkv, dh), D ** -0.5),
                     "wo": ((L, Hq, dh, D), (Hq * dh) ** -0.5 * res)},
            "mlp": {"wi": ((L, D, F), D ** -0.5),
                    "wo": ((L, F, D), F ** -0.5 * res)},
        },
    }


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


def draw(m: Dict, seed: int, device=None) -> Dict:
    """Every leaf in one jitted program on ``device`` (default: the first
    device)."""
    leaves, treedef = jax.tree.flatten(layout(m), is_leaf=_is_leaf)
    key = key_of(seed)
    dev = device if device is not None else jax.devices()[0]
    with jax.default_device(dev):
        return _draw((tuple(leaves), treedef), key)
