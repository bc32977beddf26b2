"""Pallas TPU kernel: Mamba2 SSD chunked scan.

TPU adaptation of the SSD algorithm: per (batch*head, chunk) the kernel does
three MXU matmuls — C B^T (Q,Q scores), the masked-decay weighted intra-chunk
product, and the inter-chunk C @ state — plus a rank-Q state update, with the
running (N, P) state held in VMEM scratch across chunk grid steps.  One HBM
pass over x/B/C; states never touch HBM (vs. the XLA scan which spills the
(H, P, N) state every chunk).

Layout: head-major.  x: (BH, S, P); a(=dt*A): (BH, S); B/C: (BG, S, N) with
the head->group mapping folded into the BlockSpec index maps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, hT_ref, state_scr, *,
                nc, Q):
    jc = pl.program_id(1)

    @pl.when(jc == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    xc = x_ref[0].astype(jnp.float32)            # (Q, P) already dt-weighted
    ac = a_ref[0]                                # (Q,) log-decay, f32
    Bc = b_ref[0].astype(jnp.float32)            # (Q, N)
    Cc = c_ref[0].astype(jnp.float32)            # (Q, N)

    a_cum = jnp.cumsum(ac)                       # inclusive (Q,)
    a_tot = a_cum[-1]

    # intra-chunk: y[q] += sum_{k<=q} exp(acum_q - acum_k) (C_q.B_k) xdt_k
    seg = a_cum[:, None] - a_cum[None, :]        # (Q, Q)
    iq = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    ik = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(iq >= ik, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(Cc, Bc, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y = jax.lax.dot_general(scores * L, xc, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: y[q] += exp(acum_q) C_q @ state   (state: (N, P))
    y += jnp.exp(a_cum)[:, None] * jax.lax.dot_general(
        Cc, state_scr[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)

    # state' = exp(a_tot) state + B^T (decay_to_end * xdt)
    decay_end = jnp.exp(a_tot - a_cum)           # (Q,)
    state_scr[...] = (jnp.exp(a_tot) * state_scr[...] +
                      jax.lax.dot_general(
                          Bc, decay_end[:, None] * xc,
                          (((0,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32))

    @pl.when(jc == nc - 1)
    def _write_state():
        hT_ref[0] = state_scr[...]


def ssd_scan(xdt, a, B_, C_, *, chunk=128, hq_per_group=1, interpret=None):
    """xdt: (BH, S, P) dt-weighted inputs; a: (BH, S) log-decays;
    B_/C_: (BG, S, N) with BH = BG * hq_per_group.

    Returns (y (BH, S, P) f32, h_final (BH, N, P) f32).
    """
    BH, S, P = xdt.shape
    N = B_.shape[2]
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q
    G = hq_per_group

    y, hT = pl.pallas_call(
        functools.partial(_ssd_kernel, nc=nc, Q=Q),
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda b, jc: (b, jc, 0)),
            pl.BlockSpec((1, Q), lambda b, jc: (b, jc)),
            pl.BlockSpec((1, Q, N), lambda b, jc: (b // G, jc, 0)),
            pl.BlockSpec((1, Q, N), lambda b, jc: (b // G, jc, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, P), lambda b, jc: (b, jc, 0)),
            pl.BlockSpec((1, N, P), lambda b, jc: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, P), jnp.float32),
            jax.ShapeDtypeStruct((BH, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(xdt, a, B_, C_)
    return y, hT
