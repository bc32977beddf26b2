"""Operations and bytes the serving path needs, computed from shapes.

These are the benchmark's own yardstick, taken from the configuration file's
``model`` block (a dict), never from the program under test.  Dense models
with grouped-query attention only: ``n_layers`` layers of attention + MLP,
then the output head.  Weights are bf16 (2 bytes).

Prefill charges the head once per prompt, on its last token: a chunk step
computes logits for one token per stream and only the last prompt token's
logits are used.
"""
from __future__ import annotations

from typing import Dict, Sequence

BF16 = 2


def _glu(m: Dict) -> bool:
    return m["activation"] in ("swiglu", "gelu_glu", "relu_glu")


def head_dim(m: Dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def vocab_padded(m: Dict) -> int:
    return -(-m["vocab"] // 256) * 256


def layer_params(m: Dict) -> int:
    """Matmul parameters of one layer (norm scales excluded)."""
    D, F = m["d_model"], m["d_ff"]
    Hq, Hkv, dh = m["n_heads"], m["n_kv_heads"], head_dim(m)
    attn = D * (Hq + 2 * Hkv) * dh + Hq * dh * D
    mlp = (3 if _glu(m) else 2) * D * F
    return attn + mlp


def head_params(m: Dict) -> int:
    return m["d_model"] * vocab_padded(m)


def attn_flops(m: Dict, context: float) -> float:
    """Score and value FLOPs of one query token against ``context`` keys,
    over all layers."""
    return m["n_layers"] * 2 * 2 * m["n_heads"] * head_dim(m) * context


def token_flops(m: Dict, context: float, *, head: bool) -> float:
    """Forward FLOPs of one token whose query sees ``context`` keys: the
    layer matmuls, attention, and the head only when ``head``."""
    f = 2.0 * m["n_layers"] * layer_params(m) + attn_flops(m, context)
    if head:
        f += 2.0 * head_params(m)
    return f


def prefill_flops(m: Dict, tokens: float, prompts: float,
                  mean_keys: float) -> float:
    """FLOPs to prefill ``tokens`` prompt tokens that complete ``prompts``
    prompts, each query seeing ``mean_keys`` keys on average: the head runs
    once per prompt, on its last token."""
    return tokens * token_flops(m, mean_keys, head=False) \
        + prompts * 2.0 * head_params(m)


def prefill_mean_keys(requests) -> float:
    """Mean keys a prefilled query sees over ``requests`` (``prompt_len``,
    ``prefix_tokens``): the prompt token at position p attends to p + 1
    keys, and tokens taken from the prefix cache are not prefilled.  0 when
    nothing was prefilled."""
    n = keys = 0.0
    for r in requests:
        a, s = r["prefix_tokens"], r["prompt_len"]
        if s > a:
            n += s - a
            keys += (s - a) * (a + 1 + s) / 2.0
    return keys / n if n else 0.0


def decode_mean_keys(requests) -> float:
    """Mean keys a decode row reads over ``requests`` (``prompt_len``,
    ``n_out``): the token at position p (p >= prompt) attends to p + 1
    keys.  0 when no request decoded."""
    n = keys = 0.0
    for r in requests:
        k = r["n_out"] - 1
        if k > 0:
            s = r["prompt_len"]
            n += k
            keys += k * (s + 1 + s + k) / 2.0
    return keys / n if n else 0.0


def kv_token_bytes(m: Dict) -> int:
    """K and V bytes one token holds over all layers."""
    return m["n_layers"] * 2 * m["n_kv_heads"] * head_dim(m) * BF16


def weight_bytes(m: Dict) -> int:
    """Bytes a step reads once: every layer's weights, the norms and the
    head (the embedding gather is a few rows and is left out)."""
    D = m["d_model"]
    norms = (2 * m["n_layers"] + 1) * D
    return (m["n_layers"] * layer_params(m) + head_params(m) + norms) * BF16


def decode_bytes(m: Dict, steps: float, rows: float,
                 mean_keys: float) -> float:
    """Bytes ``steps`` decode steps of ``rows`` rows in all need: the
    weights once a step, each row's live KV (``mean_keys`` keys on
    average) read, and its new token's K and V written."""
    return steps * weight_bytes(m) + rows * (mean_keys + 1) \
        * kv_token_bytes(m)


def ring_kernel_cost(bhq: int, cp: int, bhkv: int, lp: int, dh: int, *,
                     ring: int, bq: int, bkv: int) -> Dict[str, float]:
    """FLOPs and least bytes of one ring-chunk attention call from its
    shapes: queries (bhq, cp, dh), concatenated [ring, chunk] keys and
    values (bhkv, lp, dh).  FLOPs count every (bq, bkv) tile the kernel's
    grid enters (QK^T and PV, 2 FLOPs a multiply-add each): ring tiles are
    always entered, chunk tiles only where some query can see them.  Bytes
    count Q and O once and each KV head's keys and values once."""
    n_q, n_kv = cp // bq, lp // bkv
    tiles = 0
    for iq in range(n_q):
        q_hi = iq * bq + bq - 1
        for jk in range(n_kv):
            kv_lo = jk * bkv
            if kv_lo < ring or kv_lo - ring <= q_hi:
                tiles += 1
    per_row = tiles / max(n_q, 1)                   # entered tiles per q block
    flops = 4.0 * bhq * cp * per_row * bkv * dh
    byts = (2 * bhq * cp * dh + 2 * bhkv * lp * dh) * BF16
    return {"flops": flops, "bytes": float(byts)}


def roofline_seconds(flops: float, byts: float, peak_flops: float,
                     peak_bw: float) -> float:
    return max(flops / peak_flops, byts / peak_bw)

