"""Operations and bytes the serving path needs, computed from shapes:
the parts that hold for every architecture.

These are the benchmark's own yardstick, taken from the configuration file's
``model`` block (a dict) and the run's requests, never from the program
under test.  What depends on the architecture (a token's FLOPs, the
weights' and a token's KV bytes) is counted by the module the
configuration's ``reference`` names (``bench/references/<name>.py``).
Weights are bf16 (2 bytes).
"""
from __future__ import annotations

from typing import Dict

BF16 = 2


def vocab_padded(m: Dict) -> int:
    return -(-m["vocab"] // 256) * 256


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

