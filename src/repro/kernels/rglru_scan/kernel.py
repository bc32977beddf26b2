"""Pallas TPU kernel: chunked linear recurrence h_t = a_t h_{t-1} + b_t.

TPU adaptation of the RG-LRU scan: instead of a log-depth global
associative scan (which makes log2(S) full passes over HBM), the kernel
makes a SINGLE pass: the sequence is cut into VMEM-resident chunks; within
a chunk the recurrence is solved with an in-register Blelloch-style doubling
scan (log2(chunk) vector ops, no HBM traffic); the chunk-to-chunk carry
lives in VMEM scratch across grid steps.

Grid: (B, W // bw, S // bs) — sequence innermost ("arbitrary"), channel
blocks parallel.  One HBM read of (a, b) and one write of h per element:
memory-optimal for this memory-bound op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _chunk_scan(a, b):
    """Doubling scan within a chunk.  a, b: (bs, bw) -> h (bs, bw).

    After k steps, (a, b)[t] composes the affine map of steps t-2^k+1 .. t.
    """
    bs = a.shape[0]
    n = 1
    while n < bs:
        a_shift = jnp.pad(a, ((n, 0), (0, 0)), constant_values=1.0)[:bs]
        b_shift = jnp.pad(b, ((n, 0), (0, 0)))[:bs]
        b = a * b_shift + b
        a = a * a_shift
        n *= 2
    return a, b   # a[t] = prod(a_0..t), b[t] = h_t given h_{-1}=0


def _lru_kernel(a_ref, b_ref, h_ref, carry_scr, *, n_s):
    js = pl.program_id(2)

    @pl.when(js == 0)
    def _init():
        carry_scr[...] = jnp.zeros_like(carry_scr)

    a = a_ref[0].astype(jnp.float32)        # (bs, bw)
    b = b_ref[0].astype(jnp.float32)
    a_cum, h_local = _chunk_scan(a, b)
    h = h_local + a_cum * carry_scr[...]    # inject carry from prior chunks
    h_ref[0] = h.astype(h_ref.dtype)
    carry_scr[...] = h[-1:, :]              # (1, bw) final state of the chunk


def lru_scan(a, b, *, block_s=256, block_w=512, interpret=None):
    """a, b: (B, S, W) -> h: (B, S, W) (f32 out).  Single-pass chunked scan."""
    B, S, W = a.shape
    bs = min(block_s, S)
    bw = min(block_w, W)
    assert S % bs == 0 and W % bw == 0, (S, bs, W, bw)
    n_s = S // bs

    h = pl.pallas_call(
        functools.partial(_lru_kernel, n_s=n_s),
        grid=(B, W // bw, n_s),
        in_specs=[
            pl.BlockSpec((1, bs, bw), lambda ib, iw, js: (ib, js, iw)),
            pl.BlockSpec((1, bs, bw), lambda ib, iw, js: (ib, js, iw)),
        ],
        out_specs=pl.BlockSpec((1, bs, bw), lambda ib, iw, js: (ib, js, iw)),
        out_shape=jax.ShapeDtypeStruct((B, S, W), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(a, b)
    return h
