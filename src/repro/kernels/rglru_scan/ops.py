"""Public wrapper for the RG-LRU chunked-scan kernel.

Training gradients flow through a custom VJP that exploits the recurrence
structure: with y_t = a_t y_{t-1} + b_t,
    db_t = g_t + a_{t+1} db_{t+1}   (reverse scan with the same kernel)
    da_t = db_t * y_{t-1}
so both passes reuse ``lru_scan``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.rglru_scan.kernel import lru_scan


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def lru(a, b, block_s=256, block_w=512, interpret=None):
    """h_t = a_t h_{t-1} + b_t over axis 1.  a, b: (B, S, W)."""
    return lru_scan(a, b, block_s=block_s, block_w=block_w,
                    interpret=interpret)


def _fwd(a, b, block_s, block_w, interpret):
    h = lru_scan(a, b, block_s=block_s, block_w=block_w, interpret=interpret)
    return h, (a, h)


def _bwd(block_s, block_w, interpret, res, g):
    a, h = res
    # reverse-time scan: db_t = g_t + a_{t+1} * db_{t+1}
    a_next = jnp.concatenate([a[:, 1:], jnp.zeros_like(a[:, :1])], axis=1)
    db = lru_scan(a_next[:, ::-1], g[:, ::-1].astype(jnp.float32),
                  block_s=block_s, block_w=block_w,
                  interpret=interpret)[:, ::-1]
    h_prev = jnp.concatenate([jnp.zeros_like(h[:, :1]), h[:, :-1]], axis=1)
    da = db * h_prev
    return da.astype(a.dtype), db.astype(a.dtype)


lru.defvjp(_fwd, _bwd)
