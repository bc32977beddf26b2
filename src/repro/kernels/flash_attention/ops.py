"""Jit'd public wrappers: GQA flash attention with custom VJP, plus the
forward-only ring-chunk attention used by the serving fused-prefill path.

``flash_attention(q, k, v)`` takes model-layout tensors (B, S, H, dh) and
handles head-major reshaping, GQA head mapping, and the Pallas fwd/bwd
kernels.  ``interpret=None`` (the default) resolves per backend through
``repro.kernels.resolve_interpret``: TPU compiles the real kernel,
everything else runs the kernel bodies in interpret mode for validation.
Pass an explicit bool to override.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import kernel as K


def _to_head_major(x):
    B, S, H, dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, dh)


def _from_head_major(x, B, H):
    BH, S, dh = x.shape
    return x.reshape(B, H, S, dh).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=True, window=0, block_q=256,
                    block_kv=256, interpret=None):
    """q: (B,S,Hq,dh); k/v: (B,Skv,Hkv,dh) -> (B,S,Hq,dh)."""
    out, _ = _fwd(q, k, v, causal, window, block_q, block_kv, interpret)
    return out


def _fwd(q, k, v, causal, window, block_q, block_kv, interpret):
    B, Sq, Hq, dh = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qf = _to_head_major(q)
    kf = _to_head_major(k)
    vf = _to_head_major(v)
    out, lse = K.flash_attention_fwd(
        qf, kf, vf, causal=causal, window=window, block_q=block_q,
        block_kv=block_kv, hq_per_kv=G, interpret=interpret)
    return _from_head_major(out, B, Hq), (qf, kf, vf, out, lse, B, Hq, Hkv)


def _fwd_rule(q, k, v, causal, window, block_q, block_kv, interpret):
    out, res = _fwd(q, k, v, causal, window, block_q, block_kv, interpret)
    return out, res


def _bwd_rule(causal, window, block_q, block_kv, interpret, res, g):
    qf, kf, vf, outf, lse, B, Hq, Hkv = res
    G = Hq // Hkv
    gf = _to_head_major(g)
    dq, dk, dv = K.flash_attention_bwd(
        qf, kf, vf, outf, lse, gf, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, hq_per_kv=G, interpret=interpret)
    return (_from_head_major(dq, B, Hq),
            _from_head_major(dk, B, Hkv),
            _from_head_major(dv, B, Hkv))


flash_attention.defvjp(_fwd_rule, _bwd_rule)


def _pad_axis1(x, to):
    n = to - x.shape[1]
    if n <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[1] = (0, n)
    return jnp.pad(x, widths)


def ring_chunk_attention(q, k_new, v_new, k_cache, v_cache, pos, n_tokens, *,
                         window=0, softcap=0.0, block_q=32, block_kv=32,
                         interpret=None):
    """Blocked (online-softmax) drop-in for ``layers.chunk_attention``.

    Same contract as the dense reference — q/k_new/v_new: (B, C, H*, dh),
    k_cache/v_cache: (B, W, Hkv, dh) pre-write ring, pos: (B,) absolute
    position of chunk token 0, n_tokens: (B,) in [0, C] — but the score
    transient is one (block_q, block_kv) tile per grid step instead of the
    dense (C, W+C) block.  All three dense masks collapse into one band
    test on absolute positions the kernel derives from ``pos`` and
    ``n_tokens``: ring keys carry the slot's held position
    (``cache_positions`` on the pre-chunk ring), chunk key t' carries
    pos+t' while t' < n_tokens and a -2^30 sentinel otherwise,
    and ``kp > qp - W`` expresses both ring eviction and intra-chunk
    self-eviction, so chunks wider than the ring (C > W) score exactly.
    Rows with no visible key (idle streams at pos 0, q-block padding)
    return 0 instead of the dense path's discarded uniform-softmax row.
    """
    B, C, Hq, dh = q.shape
    W, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    L = W + C
    bq = max(1, min(block_q, C))
    bkv = max(1, min(block_kv, L))
    Cp = -(-C // bq) * bq
    Lp = -(-L // bkv) * bkv

    kcat = _pad_axis1(jnp.concatenate([k_cache, k_new], axis=1), Lp)
    vcat = _pad_axis1(jnp.concatenate([v_cache, v_new], axis=1), Lp)
    qp = _pad_axis1(q, Cp)

    out = K.ring_chunk_attention_fwd(
        _to_head_major(qp), _to_head_major(kcat), _to_head_major(vcat),
        pos, n_tokens, ring=W, window=window, softcap=softcap,
        block_q=bq, block_kv=bkv, hq_per_kv=G, interpret=interpret)
    return _from_head_major(out, B, Hq)[:, :C]
