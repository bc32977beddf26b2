"""Shared neural-net layers: norms, RoPE/M-RoPE, blocked GQA attention, MLPs.

All functions are pure; parameters are plain dict pytrees.  Attention is
implemented as an online-softmax blocked computation (flash-attention
algorithm in pure jnp) so that 32k-token prefills never materialize an
(S, S) score matrix.  ``unroll=True`` statically unrolls the block loops —
used by the dry-run analysis path so ``cost_analysis()`` (which counts a
while-loop body once) sees the true FLOP/byte totals.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def dt(name: str):
    return jnp.dtype(name)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps: float):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, scale, bias, eps: float):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings (RoPE and M-RoPE)
# ---------------------------------------------------------------------------

def rope_tables(positions, head_dim: int, theta: float):
    """positions: (..., S) int -> cos/sin tables (..., S, head_dim//2), f32."""
    half = head_dim // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq  # (..., S, half)
    return jnp.cos(ang), jnp.sin(ang)


def mrope_tables(position_ids, head_dim: int, theta: float, sections):
    """M-RoPE (Qwen2-VL): position_ids (3, ..., S); sections sum to head_dim//2.

    Component c contributes its angle to ``sections[c]`` frequency slots.
    For pure text all three components are equal and this reduces to RoPE.
    """
    half = head_dim // 2
    assert sum(sections) == half, (sections, half)
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang_c = position_ids.astype(jnp.float32)[..., None] * freq  # (3, ..., S, half)
    sel = jnp.repeat(jnp.arange(len(sections)), jnp.array(sections),
                     total_repeat_length=half)                  # (half,) in {0,1,2}
    onehot = jax.nn.one_hot(sel, len(sections), dtype=jnp.float32)  # (half, 3)
    ang = jnp.einsum("c...h,hc->...h", ang_c, onehot)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (B, S, H, dh); cos/sin: (B, S, half) or (S, half)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:
        cos = cos[None]
        sin = sin[None]
    c = cos[:, :, None, :].astype(jnp.float32)
    s = sin[:, :, None, :].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * c - x2f * s, x2f * c + x1f * s], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Blocked GQA attention (flash algorithm, pure jnp)
# ---------------------------------------------------------------------------

def _attn_mask(q_pos, kv_pos, *, causal: bool, window: int, kv_len=None):
    """q_pos: (bq,), kv_pos: (bkv,) -> bool (bq, bkv)."""
    m = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), bool)
    if causal:
        m &= kv_pos[None, :] <= q_pos[:, None]
    if window:
        m &= kv_pos[None, :] > q_pos[:, None] - window
    if kv_len is not None:
        m &= kv_pos[None, :] < kv_len
    return m


def blocked_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                      block_q=512, block_kv=1024, softcap=0.0,
                      unroll=False, kv_offset=0):
    """Online-softmax attention.  q: (B,Sq,Hq,dh), k/v: (B,Skv,Hkv,dh).

    Never materializes (Sq, Skv).  GQA handled natively by grouping query
    heads over KV heads.  Returns (B, Sq, Hq, dh) in q.dtype.

    Gradients flow through a flash-style custom VJP (saves out+lse, replays
    blocks in the backward pass) so the inner online-softmax scan never
    checkpoints its per-block state — without this, vjp-of-scan stores
    every (m, l, acc) carry and activation memory explodes.
    """
    out, _ = _attn_vjp(q, k, v, causal, window, q_offset, block_q, block_kv,
                       softcap, unroll, kv_offset)
    return out


def _pad_blocks(q, k, v, block_q, block_kv):
    B, Sq, Hq, dh = q.shape
    Skv = k.shape[1]
    bq = min(block_q, Sq)
    bkv = min(block_kv, Skv)
    Sq0, Skv0 = Sq, Skv
    if Sq % bq:
        q = jnp.pad(q, ((0, 0), (0, bq - Sq % bq), (0, 0), (0, 0)))
    if Skv % bkv:
        pad = bkv - Skv % bkv
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kv_len = Skv0 if k.shape[1] != Skv0 else None
    return q, k, v, bq, bkv, Sq0, Skv0, kv_len


def _attn_fwd_impl(q, k, v, causal, window, q_offset, block_q, block_kv,
                   softcap, unroll, kv_offset):
    """Returns (out (B,Sq,Hq,dh), lse (B,Sq,Hq) f32)."""
    q, k, v, bq, bkv, Sq0, Skv0, kv_len = _pad_blocks(q, k, v, block_q,
                                                      block_kv)
    B, Sq, Hq, dh = q.shape
    Skv = k.shape[1]
    Hkv = k.shape[2]
    G = Hq // Hkv
    scale = dh ** -0.5
    nq, nkv = Sq // bq, Skv // bkv
    qg = q.reshape(B, Sq, Hkv, G, dh)

    def one_q_block(iq):
        qb = lax.dynamic_slice_in_dim(qg, iq * bq, bq, axis=1)
        q_pos = q_offset + iq * bq + jnp.arange(bq)

        def kv_step(carry, jk):
            m, l, acc = carry
            kb = lax.dynamic_slice_in_dim(k, jk * bkv, bkv, axis=1)
            vb = lax.dynamic_slice_in_dim(v, jk * bkv, bkv, axis=1)
            kv_pos = kv_offset + jk * bkv + jnp.arange(bkv)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            if softcap:
                s = softcap * jnp.tanh(s / softcap)
            mask = _attn_mask(q_pos, kv_pos, causal=causal, window=window,
                              kv_len=kv_len)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, vb, preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, G, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, bq), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, bq, dh), jnp.float32)
        if unroll:
            carry = (m0, l0, a0)
            for jk in range(nkv):
                carry, _ = kv_step(carry, jk)
            m, l, acc = carry
        else:
            (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0), jnp.arange(nkv))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        # (B, Hkv, G, bq, [dh]) -> (B, bq, Hq, [dh])
        out = out.transpose(0, 3, 1, 2, 4).reshape(B, bq, Hq, dh)
        lse = lse.transpose(0, 3, 1, 2).reshape(B, bq, Hq)
        return out.astype(q.dtype), lse

    if unroll:
        blocks = [one_q_block(i) for i in range(nq)]
        out = jnp.concatenate([b[0] for b in blocks], axis=1) \
            if nq > 1 else blocks[0][0]
        lse = jnp.concatenate([b[1] for b in blocks], axis=1) \
            if nq > 1 else blocks[0][1]
    else:
        outs, lses = lax.map(one_q_block, jnp.arange(nq))
        out = outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, Hq, dh)
        lse = lses.transpose(1, 0, 2, 3).reshape(B, Sq, Hq)
    if Sq != Sq0:
        out, lse = out[:, :Sq0], lse[:, :Sq0]
    return out, lse


def _attn_bwd_impl(q, k, v, lse, delta, g, causal, window, q_offset,
                   block_q, block_kv, softcap, unroll, kv_offset):
    """Flash backward: scan q blocks, accumulate dk/dv, emit dq blocks."""
    in_dtype = q.dtype
    q, k, v, bq, bkv, Sq0, Skv0, kv_len = _pad_blocks(q, k, v, block_q,
                                                      block_kv)
    B, Sq, Hq, dh = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = dh ** -0.5
    nq, nkv = Sq // bq, Skv // bkv

    def pad_q(x):
        return jnp.pad(x, ((0, 0), (0, Sq - Sq0)) + ((0, 0),) * (x.ndim - 2)) \
            if Sq != Sq0 else x

    qg = q.reshape(B, Sq, Hkv, G, dh)
    gg = pad_q(g).reshape(B, Sq, Hkv, G, dh)
    lseg = pad_q(lse).reshape(B, Sq, Hkv, G)
    deltag = pad_q(delta).reshape(B, Sq, Hkv, G)

    def q_block(carry, iq):
        dk_acc, dv_acc = carry
        qb = lax.dynamic_slice_in_dim(qg, iq * bq, bq, axis=1)
        gb = lax.dynamic_slice_in_dim(gg, iq * bq, bq, axis=1).astype(jnp.float32)
        lb = lax.dynamic_slice_in_dim(lseg, iq * bq, bq, axis=1)
        db = lax.dynamic_slice_in_dim(deltag, iq * bq, bq, axis=1)
        q_pos = q_offset + iq * bq + jnp.arange(bq)
        # (B,bq,Hkv,G) -> (B,Hkv,G,bq)
        lb = lb.transpose(0, 2, 3, 1)
        db = db.transpose(0, 2, 3, 1)

        def kv_step(inner, jk):
            dk_a, dv_a, dq_blk = inner
            kb = lax.dynamic_slice_in_dim(k, jk * bkv, bkv, axis=1)
            vb = lax.dynamic_slice_in_dim(v, jk * bkv, bkv, axis=1)
            kv_pos = kv_offset + jk * bkv + jnp.arange(bkv)
            s_raw = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb,
                               preferred_element_type=jnp.float32) * scale
            if softcap:
                th = jnp.tanh(s_raw / softcap)
                s = softcap * th
                dsoft = 1.0 - jnp.square(th)
            else:
                s = s_raw
                dsoft = None
            mask = _attn_mask(q_pos, kv_pos, causal=causal, window=window,
                              kv_len=kv_len)
            p = jnp.where(mask[None, None, None],
                          jnp.exp(s - lb[..., None]), 0.0)
            dv_new = jnp.einsum("bhgqk,bqhgd->bkhd", p, gb,
                                preferred_element_type=jnp.float32)
            dp = jnp.einsum("bqhgd,bkhd->bhgqk", gb, vb,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - db[..., None]) * scale
            if dsoft is not None:
                ds = ds * dsoft
            dq_new = jnp.einsum("bhgqk,bkhd->bqhgd", ds.astype(kb.dtype), kb,
                                preferred_element_type=jnp.float32)
            dk_new = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qb,
                                preferred_element_type=jnp.float32)
            dk_a = lax.dynamic_update_slice_in_dim(
                dk_a, lax.dynamic_slice_in_dim(dk_a, jk * bkv, bkv, 1)
                + dk_new, jk * bkv, axis=1)
            dv_a = lax.dynamic_update_slice_in_dim(
                dv_a, lax.dynamic_slice_in_dim(dv_a, jk * bkv, bkv, 1)
                + dv_new, jk * bkv, axis=1)
            return (dk_a, dv_a, dq_blk + dq_new), None

        dq0 = jnp.zeros((B, bq, Hkv, G, dh), jnp.float32)
        if unroll:
            inner = (dk_acc, dv_acc, dq0)
            for jk in range(nkv):
                inner, _ = kv_step(inner, jk)
            dk_acc, dv_acc, dq_blk = inner
        else:
            (dk_acc, dv_acc, dq_blk), _ = lax.scan(
                kv_step, (dk_acc, dv_acc, dq0), jnp.arange(nkv))
        return (dk_acc, dv_acc), dq_blk

    dk0 = jnp.zeros((B, Skv, Hkv, dh), jnp.float32)
    dv0 = jnp.zeros((B, Skv, Hkv, dh), jnp.float32)
    if unroll:
        carry = (dk0, dv0)
        dqs = []
        for iq in range(nq):
            carry, dq_blk = q_block(carry, iq)
            dqs.append(dq_blk)
        dq = jnp.concatenate(dqs, axis=1) if nq > 1 else dqs[0]
        dk, dv = carry
    else:
        (dk, dv), dqs = lax.scan(q_block, (dk0, dv0), jnp.arange(nq))
        dq = dqs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, Hkv, G, dh)
    dq = dq.reshape(B, Sq, Hq, dh)[:, :Sq0]
    dk = dk[:, :Skv0]
    dv = dv[:, :Skv0]
    return dq.astype(in_dtype), dk.astype(in_dtype), dv.astype(in_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _attn_vjp(q, k, v, causal, window, q_offset, block_q, block_kv, softcap,
              unroll, kv_offset):
    return _attn_fwd_impl(q, k, v, causal, window, q_offset, block_q,
                          block_kv, softcap, unroll, kv_offset)


def _attn_vjp_fwd(q, k, v, causal, window, q_offset, block_q, block_kv,
                  softcap, unroll, kv_offset):
    out, lse = _attn_fwd_impl(q, k, v, causal, window, q_offset, block_q,
                              block_kv, softcap, unroll, kv_offset)
    return (out, lse), (q, k, v, out, lse)


def _attn_vjp_bwd(causal, window, q_offset, block_q, block_kv, softcap,
                  unroll, kv_offset, res, cts):
    q, k, v, out, lse = res
    g, _ = cts
    delta = (out.astype(jnp.float32) * g.astype(jnp.float32)).sum(-1)
    dq, dk, dv = _attn_bwd_impl(q, k, v, lse, delta, g, causal, window,
                                q_offset, block_q, block_kv, softcap,
                                unroll, kv_offset)
    return dq, dk, dv


_attn_vjp.defvjp(_attn_vjp_fwd, _attn_vjp_bwd)


def decode_attention(q, k_cache, v_cache, kv_positions, q_pos, *,
                     window=0, softcap=0.0):
    """Single-token attention over a (ring-buffer) cache.

    q: (B, 1, Hq, dh); caches: (B, W, Hkv, dh); kv_positions: (B, W) actual
    absolute positions stored in each slot (negative = empty); q_pos: (B,).
    """
    B, _, Hq, dh = q.shape
    _, W, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    scale = dh ** -0.5
    qg = q.reshape(B, Hkv, G, dh)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    valid = (kv_positions >= 0) & (kv_positions <= q_pos[:, None])
    if window:
        valid &= kv_positions > (q_pos[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, Hq, dh).astype(q.dtype)


def chunk_attention(q, k_new, v_new, k_cache, v_cache, pos, n_tokens, *,
                    window=0, softcap=0.0, kernel="dense", block_q=None,
                    block_kv=None, interpret=None):
    """Multi-token chunk attention over a ring cache: ONE fused score
    computation instead of C sequential decode steps.

    q/k_new/v_new: (B, C, H*, dh) the chunk's projections; k_cache/v_cache:
    (B, W, Hkv, dh) the ring BEFORE the chunk is written; pos: (B,)
    absolute position of chunk token 0; n_tokens: (B,) in [0, C].

    Query t (position pos+t) attends jointly over [prior ring, chunk keys
    t' <= t] under one softmax.  Scoring the prior ring *pre-write* is what
    makes this exact: a per-token scan would let query t read a slot that a
    LATER chunk token t' > t has not yet overwritten, and that slot
    (position pos+t'-W) is inside t's window — so the fused form must score
    the old contents, not the post-write ring.  Masked entries (idle slots,
    short chunks, out-of-window) go to NEG_INF; a fully-masked row (idle
    stream) degrades to a uniform softmax whose output is discarded.

    ``kernel`` selects the score computation: "dense" materializes the
    (B, H, C, W+C) block below (the reference, priced by
    ``costmodel.prefill_chunk_score_bytes``); "blocked" streams KV in
    (block_q, block_kv) tiles, chosen from the shapes unless given, through
    the Pallas online-softmax kernel
    (``kernels.flash_attention.ops.ring_chunk_attention``) so the live
    transient never exceeds one tile.  Both are exact for chunks wider
    than the ring (C > W): intra-chunk self-eviction is the same band
    test ``kv > q - W`` that evicts prior-ring entries.
    """
    if kernel == "blocked":
        from repro.kernels.flash_attention.ops import ring_chunk_attention
        return ring_chunk_attention(
            q, k_new, v_new, k_cache, v_cache, pos, n_tokens,
            window=window, softcap=softcap, block_q=block_q,
            block_kv=block_kv, interpret=interpret)
    if kernel != "dense":
        raise ValueError(f"unknown chunk kernel {kernel!r}: "
                         "expected 'dense' or 'blocked'")
    B, C, Hq, dh = q.shape
    W, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    scale = dh ** -0.5
    qg = q.reshape(B, C, Hkv, G, dh)
    t = jnp.arange(C)
    q_pos = pos[:, None] + t[None, :]                       # (B, C)
    # prior ring: positions held BEFORE the chunk (pos-1 = last written)
    kv_pos = cache_positions(pos - 1, W)                    # (B, W)
    s_prior = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_cache,
                         preferred_element_type=jnp.float32) * scale
    s_chunk = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_new,
                         preferred_element_type=jnp.float32) * scale
    if softcap:
        s_prior = softcap * jnp.tanh(s_prior / softcap)
        s_chunk = softcap * jnp.tanh(s_chunk / softcap)
    # the ring width is an IMPLICIT window: sequential stepping overwrites
    # position p-W when writing p, so query t must not see prior entries
    # at kv_pos <= q_pos - W that its own chunk's earlier tokens would
    # already have evicted (exact match with ring-eviction semantics even
    # for full-attention models whose context exceeds the ring)
    vp = (kv_pos[:, None, :] >= 0) \
        & (kv_pos[:, None, :] <= q_pos[:, :, None]) \
        & (kv_pos[:, None, :] > q_pos[:, :, None] - W)
    vc = (t[None, :] <= t[:, None])[None] \
        & (t[None, None, :] < n_tokens[:, None, None])
    # intra-chunk self-eviction: with C > W, chunk token t' <= t - W has
    # been overwritten (by t'+W <= t) before query t runs sequentially —
    # vacuously true when C <= W, the same band as the ring mask above
    vc &= (t[None, :] > t[:, None] - W)[None]
    if window:
        vp &= kv_pos[:, None, :] > q_pos[:, :, None] - window
        vc &= (t[None, :] > t[:, None] - window)[None]
    s_prior = jnp.where(vp[:, None, None], s_prior, NEG_INF)
    s_chunk = jnp.where(vc[:, None, None], s_chunk, NEG_INF)
    s = jnp.concatenate([s_prior, s_chunk], axis=-1)        # (B,Hkv,G,C,W+C)
    p = jax.nn.softmax(s, axis=-1)
    vcat = jnp.concatenate([v_cache, v_new], axis=1)        # (B, W+C, Hkv, dh)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, vcat,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, C, Hq, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Ring-buffer KV cache helpers
# ---------------------------------------------------------------------------

def cache_update(k_cache, v_cache, k_new, v_new, pos):
    """Write one token at ring slot pos % W, per batch element.

    caches: (B, W, Hkv, dh); k_new/v_new: (B, 1, Hkv, dh); pos: (B,).
    """
    W = k_cache.shape[1]
    slot = pos % W

    def upd(c, x, s):
        return lax.dynamic_update_slice_in_dim(c, x, s, axis=0)

    k_cache = jax.vmap(upd)(k_cache, k_new, slot)
    v_cache = jax.vmap(upd)(v_cache, v_new, slot)
    return k_cache, v_cache


def cache_update_chunk(k_cache, v_cache, k_new, v_new, pos, n_tokens):
    """Write up to C tokens per stream at ring slots (pos+t) % W, masked.

    caches: (B, W, Hkv, dh); k_new/v_new: (B, C, Hkv, dh); pos: (B,)
    position of chunk token 0; n_tokens: (B,) in [0, C] — tokens past a
    stream's count leave their slot untouched, so idle and short-chunk
    streams leave the ring as-is.  Works for ANY chunk width, including
    C > W: sequential stepping writes tokens in order, so when several
    chunk tokens map to one slot the LAST active one (largest t < n with
    t % W == (slot - pos) % W) survives — expressed here as a per-slot
    gather instead of a scatter, which would need ordered duplicate-index
    semantics XLA does not guarantee.
    """
    B, C = k_new.shape[:2]
    W = k_cache.shape[1]
    s_idx = jnp.arange(W)[None, :]                          # (1, W)
    t0 = (s_idx - pos[:, None]) % W                         # (B, W)
    # largest active chunk token landing on each slot (last write wins);
    # candidates are t0, t0+W, t0+2W, ... — none active iff t0 >= n
    kmax = (n_tokens[:, None] - 1 - t0) // W
    t_star = t0 + W * kmax                                  # (B, W)
    written = t0 < n_tokens[:, None]
    src = jnp.clip(t_star, 0, C - 1)

    def upd(c, new, sl, wr):
        g = jnp.take(new, sl, axis=0)                       # (W, Hkv, dh)
        return jnp.where(wr[:, None, None], g, c)

    k_cache = jax.vmap(upd)(k_cache, k_new, src, written)
    v_cache = jax.vmap(upd)(v_cache, v_new, src, written)
    return k_cache, v_cache


def cache_positions(pos, W):
    """Absolute position stored at each ring slot after writing ``pos``.

    pos: (B,) current (just-written) position.  Slot s holds the largest
    p <= pos with p % W == s; slots never written hold negative values.
    """
    slots = jnp.arange(W)
    p = pos[:, None] - ((pos[:, None] - slots[None, :]) % W)
    return p  # negative where never written


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_apply(x, params, activation: str):
    """params: {wi: (D, F) or (D, 2F) for GLU, wo: (F, D)}."""
    if activation in ("swiglu", "gelu_glu", "relu_glu"):
        h = jnp.einsum("bsd,dtf->bstf", x,
                       params["wi"],
                       preferred_element_type=jnp.float32)
        gate, up = h[..., 0, :], h[..., 1, :]
        if activation == "swiglu":
            act = jax.nn.silu(gate)
        elif activation == "gelu_glu":
            act = jax.nn.gelu(gate, approximate=True)
        else:
            act = jax.nn.relu(gate)
        h = (act * up).astype(x.dtype)
    else:
        h = jnp.einsum("bsd,df->bsf", x, params["wi"],
                       preferred_element_type=jnp.float32)
        if activation == "squared_relu":
            h = jnp.square(jax.nn.relu(h))
        elif activation == "gelu":
            h = jax.nn.gelu(h, approximate=True)
        else:
            raise ValueError(activation)
        h = h.astype(x.dtype)
    # bf16 output: the TP all-reduce of this partial sum carries bf16
    return jnp.einsum("bsf,fd->bsd", h, params["wo"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# Causal depthwise conv (SSM front-ends)
# ---------------------------------------------------------------------------

def causal_conv1d(x, w, bias=None):
    """x: (B, S, C); w: (K, C) depthwise causal conv along S."""
    K = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(K):
        out = out + pad[:, i:i + x.shape[1], :].astype(jnp.float32) * w[i].astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def conv1d_step(x_t, conv_state, w, bias=None):
    """One decode step.  x_t: (B, C); conv_state: (B, K-1, C) past inputs."""
    K = w.shape[0]
    window = jnp.concatenate([conv_state, x_t[:, None, :]], axis=1)  # (B,K,C)
    out = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                     w.astype(jnp.float32))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    new_state = window[:, 1:, :]
    return out.astype(x_t.dtype), new_state
