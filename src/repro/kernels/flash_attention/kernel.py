"""Pallas TPU flash-attention kernels (forward + backward).

Layout: head-major (BH, S, dh) so the trailing two dims map onto TPU
(sublane, lane) tiles; dh is expected to be a multiple of 128 (MXU lane
width) for the assigned architectures (dh=128 or 256; smoke shapes are
smaller and run in interpret mode).

Grid (forward): (BH_q, n_q_blocks, n_kv_blocks) with the KV dimension
innermost ("arbitrary" semantics) so the online-softmax state lives in VMEM
scratch across KV steps.  GQA is expressed entirely in the BlockSpec index
maps: the q-head grid coordinate selects the matching kv head row, so no
repeated KV tensor is ever materialized in HBM.

Causal / sliding-window blocks that are fully masked are skipped with
``pl.when`` (no MXU work), which is where the kernel beats a dense
attention on TPU for long sequences.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30
LANES = 128
# sentinel "position" for KV entries that must never win a mask test:
# never-written ring slots already carry small negatives, this marks
# masked chunk keys and block padding (far enough below zero that
# ``kp > qp - W`` can never resurrect it)
NEVER = -(2 ** 30)


def _block_visible(iq, jk, bq, bkv, causal, window):
    """Whether (q block iq, kv block jk) contains any unmasked element."""
    q_lo = iq * bq
    q_hi = q_lo + bq - 1
    kv_lo = jk * bkv
    kv_hi = kv_lo + bkv - 1
    vis = jnp.bool_(True)
    if causal:
        vis &= kv_lo <= q_hi
    if window:
        vis &= kv_hi > q_lo - window
    return vis


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, window,
                bq, bkv, n_kv):
    iq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(_block_visible(iq, jk, bq, bkv, causal, window))
    def _compute():
        q = q_ref[0]                                   # (bq, dh)
        k = k_ref[0]                                   # (bkv, dh)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bkv)
        q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        kv_pos = jk * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = jnp.ones((bq, bkv), jnp.bool_)
        if causal:
            mask &= kv_pos <= q_pos
        if window:
            mask &= kv_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, 0]                           # (bq,)
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, 0] * alpha + p.sum(axis=-1)
        acc_scr[...] = (acc_scr[...] * alpha[:, None] +
                        jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    @pl.when(jk == n_kv - 1)
    def _finalize():
        l = l_scr[:, 0]
        denom = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)
        lse_ref[0] = (m_scr[...] + jnp.log(jnp.maximum(l_scr[...], 1e-30)))


def flash_attention_fwd(q, k, v, *, causal=True, window=0,
                        block_q=256, block_kv=256, hq_per_kv=1,
                        interpret=None):
    """q: (BHq, Sq, dh); k/v: (BHkv, Skv, dh) with BHq = BHkv * hq_per_kv.

    Returns (out (BHq, Sq, dh), lse (BHq, Sq, LANES) — lse broadcast on lanes).
    """
    BH, Sq, dh = q.shape
    Skv = k.shape[1]
    bq = min(block_q, Sq)
    bkv = min(block_kv, Skv)
    assert Sq % bq == 0 and Skv % bkv == 0
    n_q, n_kv = Sq // bq, Skv // bkv
    scale = dh ** -0.5
    G = hq_per_kv

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bkv=bkv, n_kv=n_kv)

    out, lse = pl.pallas_call(
        kernel,
        grid=(BH, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, iq, jk: (b, iq, 0)),
            pl.BlockSpec((1, bkv, dh), lambda b, iq, jk: (b // G, jk, 0)),
            pl.BlockSpec((1, bkv, dh), lambda b, iq, jk: (b // G, jk, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, iq, jk: (b, iq, 0)),
            pl.BlockSpec((1, bq, LANES), lambda b, iq, jk: (b, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, dh), q.dtype),
            jax.ShapeDtypeStruct((BH, Sq, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# Ring-chunk forward (serving fused-prefill path; no VJP)
# ---------------------------------------------------------------------------

def _ring_block_visible(iq, jk, bq, bkv, ring):
    """Static skip for the ring-chunk grid: only the chunk segment of the
    concatenated KV axis (indices >= ring) is statically causal — a block
    whose lowest chunk offset exceeds the q block's highest offset can never
    contain a visible entry.  Ring-slot blocks are data-dependent (per-stream
    positions) and are always entered; their masking is per-element.  The
    skip saves MXU work only: the block's keys and values are still
    fetched."""
    q_hi = iq * bq + bq - 1
    kv_lo = jk * bkv
    return (kv_lo < ring) | (kv_lo - ring <= q_hi)


def _ring_kv_positions(pos, n_tokens, jk, *, ring, bkv):
    """(1, bkv) absolute position held by each KV column of block ``jk``
    for one stream whose chunk starts at ``pos`` with ``n_tokens`` live
    tokens.

    Columns [0, ring) are ring slots BEFORE the chunk is written: slot j
    holds the latest position p <= pos-1 with p = j (mod ring), negative
    when never written.  Columns [ring, ring+C) are chunk keys pos+t',
    live while t' < n_tokens.  Everything else (masked chunk keys, block
    padding) carries NEVER."""
    last = pos - 1
    r = jax.lax.rem(last, ring)                        # scalar floor mod
    r = jnp.where(r < 0, r + ring, r)
    j = jk * bkv + jax.lax.broadcasted_iota(jnp.int32, (1, bkv), 1)
    ring_pos = last - r + j - jnp.where(j <= r, 0, ring)
    t = j - ring
    chunk_pos = jnp.where(t < n_tokens, pos + t, NEVER)
    return jnp.where(j < ring, ring_pos, chunk_pos)


def _ring_fwd_kernel(pos_ref, nt_ref, q_ref, k_ref, v_ref, o_ref,
                     m_scr, l_scr, acc_scr, *, scale, ring, window, softcap,
                     bq, bkv, n_kv, kv_heads):
    b = pl.program_id(0) // kv_heads
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    pos = pos_ref[b]
    G, _, dh = q_ref.shape

    @pl.when(jk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(_ring_block_visible(iq, jk, bq, bkv, ring))
    def _compute():
        # the G query heads that share this KV head score the one KV tile
        # as G*bq rows of a single matmul
        q = q_ref[...].reshape(G * bq, dh)
        k = k_ref[0]                                   # (bkv, dh)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (G*bq, bkv)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        s = s.reshape(G, bq, bkv)
        # absolute positions, rebuilt here from the stream's two prefetched
        # scalars: q rows are pos+t, KV columns as in _ring_kv_positions.
        # One band test then expresses all three dense masks: causality
        # (kp <= qp), ring eviction incl. intra-chunk self-eviction for
        # C > W (kp > qp - ring), and never-written slots (kp >= 0).  The
        # (bq, bkv) mask is the same for every head of the group.
        qp = pos + iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        kp = _ring_kv_positions(pos, nt_ref[b], jk, ring=ring, bkv=bkv)
        mask = (kp >= 0) & (kp <= qp) & (kp > qp - ring)
        if window:
            mask &= kp > qp - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :, :1]                       # (G, bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, :, :1] * alpha + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.reshape(G * bq, bkv).astype(v.dtype), v,
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv.reshape(G, bq, dh)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(jk == n_kv - 1)
    def _finalize():
        l = l_scr[:, :, :1]
        denom = jnp.where(l > 0, l, 1.0)
        o_ref[...] = (acc_scr[...] / denom).astype(o_ref.dtype)


def ring_chunk_attention_fwd(q, k, v, pos, n_tokens, *, ring, block_q,
                             block_kv, window=0, softcap=0.0, hq_per_kv=1,
                             interpret=None):
    """Forward-only blocked attention over [prior ring, chunk keys].

    q: (BHq, Cp, dh) chunk queries, head-major, padded to a block_q
    multiple; k/v: (BHkv, Lp, dh) the concatenated [ring, chunk] KV, padded
    to a block_kv multiple; pos: (B,) int32 absolute position of chunk
    token 0; n_tokens: (B,) int32 live chunk tokens.  ``ring`` is the ring
    width W (the implicit eviction window).  Returns (BHq, Cp, dh).

    Grid (BHkv, Cp / block_q, Lp / block_kv): a step scores the
    ``hq_per_kv`` query heads that share a KV head against one KV tile,
    fetched once for all of them.  ``pos``/``n_tokens`` ride in SMEM as
    scalar-prefetch operands and every query/KV position is derived
    in-kernel, so no (B, L) position array needs a lane-aligned VMEM
    block.  The live transient per grid step is one
    (hq_per_kv * block_q, block_kv) f32 score block plus the
    online-softmax state — never the dense (C, W+C) block.
    """
    BH, Cp, dh = q.shape
    BHkv, Lp, _ = k.shape
    B = pos.shape[0]
    G = hq_per_kv
    assert BH == BHkv * G and Cp % block_q == 0 and Lp % block_kv == 0
    n_q, n_kv = Cp // block_q, Lp // block_kv

    kernel = functools.partial(
        _ring_fwd_kernel, scale=dh ** -0.5, ring=ring, window=window,
        softcap=softcap, bq=block_q, bkv=block_kv, n_kv=n_kv,
        kv_heads=BHkv // B)

    out = pl.pallas_call(
        kernel,
        name="ring_chunk_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(BHkv, n_q, n_kv),
            in_specs=[
                pl.BlockSpec((G, block_q, dh),
                             lambda b, iq, jk, *_: (b, iq, 0)),
                pl.BlockSpec((1, block_kv, dh),
                             lambda b, iq, jk, *_: (b, jk, 0)),
                pl.BlockSpec((1, block_kv, dh),
                             lambda b, iq, jk, *_: (b, jk, 0)),
            ],
            out_specs=pl.BlockSpec((G, block_q, dh),
                                   lambda b, iq, jk, *_: (b, iq, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, block_q, LANES), jnp.float32),
                pltpu.VMEM((G, block_q, LANES), jnp.float32),
                pltpu.VMEM((G, block_q, dh), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((BH, Cp, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(pos.astype(jnp.int32), n_tokens.astype(jnp.int32), q, k, v)
    return out


# ---------------------------------------------------------------------------
# Backward: dq kernel (grid over q blocks, scan kv) and dkv kernel
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, scale, causal, window, bq, bkv, n_kv):
    iq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(_block_visible(iq, jk, bq, bkv, causal, window))
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, 0]                          # (bq,)
        delta = delta_ref[0][:, 0]                      # (bq,)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        kv_pos = jk * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = jnp.ones((bq, bkv), jnp.bool_)
        if causal:
            mask &= kv_pos <= q_pos
        if window:
            mask &= kv_pos > q_pos - window
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jk == n_kv - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal, window,
                bq, bkv, n_q, hq_per_kv):
    jk = pl.program_id(1)
    g = pl.program_id(2)
    iq = pl.program_id(3)
    first = (g == 0) & (iq == 0)
    last = (g == hq_per_kv - 1) & (iq == n_q - 1)

    @pl.when(first)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(_block_visible(iq, jk, bq, bkv, causal, window))
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, 0]
        delta = delta_ref[0][:, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        kv_pos = jk * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = jnp.ones((bq, bkv), jnp.bool_)
        if causal:
            mask &= kv_pos <= q_pos
        if window:
            mask &= kv_pos > q_pos - window
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None]) * scale).astype(q.dtype)
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(last)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, out, lse, do, *, causal=True, window=0,
                        block_q=256, block_kv=256, hq_per_kv=1,
                        interpret=None):
    """Returns (dq, dk, dv) with GQA reduction over the q-head group."""
    BH, Sq, dh = q.shape
    BHkv, Skv, _ = k.shape
    G = hq_per_kv
    bq = min(block_q, Sq)
    bkv = min(block_kv, Skv)
    n_q, n_kv = Sq // bq, Skv // bkv
    scale = dh ** -0.5
    delta = (out.astype(jnp.float32) * do.astype(jnp.float32)).sum(-1)
    delta = jnp.broadcast_to(delta[..., None], delta.shape + (LANES,))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bkv=bkv, n_kv=n_kv),
        grid=(BH, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, iq, jk: (b, iq, 0)),
            pl.BlockSpec((1, bkv, dh), lambda b, iq, jk: (b // G, jk, 0)),
            pl.BlockSpec((1, bkv, dh), lambda b, iq, jk: (b // G, jk, 0)),
            pl.BlockSpec((1, bq, dh), lambda b, iq, jk: (b, iq, 0)),
            pl.BlockSpec((1, bq, LANES), lambda b, iq, jk: (b, iq, 0)),
            pl.BlockSpec((1, bq, LANES), lambda b, iq, jk: (b, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, iq, jk: (b, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bkv=bkv, n_q=n_q,
                          hq_per_kv=G),
        grid=(BHkv, n_kv, G, n_q),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, jk, g, iq: (b * G + g, iq, 0)),
            pl.BlockSpec((1, bkv, dh), lambda b, jk, g, iq: (b, jk, 0)),
            pl.BlockSpec((1, bkv, dh), lambda b, jk, g, iq: (b, jk, 0)),
            pl.BlockSpec((1, bq, dh), lambda b, jk, g, iq: (b * G + g, iq, 0)),
            pl.BlockSpec((1, bq, LANES), lambda b, jk, g, iq: (b * G + g, iq, 0)),
            pl.BlockSpec((1, bq, LANES), lambda b, jk, g, iq: (b * G + g, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bkv, dh), lambda b, jk, g, iq: (b, jk, 0)),
            pl.BlockSpec((1, bkv, dh), lambda b, jk, g, iq: (b, jk, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BHkv, Skv, dh), k.dtype),
            jax.ShapeDtypeStruct((BHkv, Skv, dh), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bkv, dh), jnp.float32),
            pltpu.VMEM((bkv, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv
