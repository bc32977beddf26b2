"""kernels: the Pallas ring-chunk attention kernel's share of its roofline
(%): the larger of its FLOPs over the bf16 peak and its least bytes over
the HBM bandwidth, from each call's shapes (``costs.ring_kernel_cost``),
summed, over the kernel's summed device time.  At the engine's shapes
(16-token chunks over a 4096-token ring) the bytes bound it.

The kernel is the serving path's only Pallas call: the trace names it
``custom-call ... custom_call_target="tpu_custom_call"``, with its result
(batch x query heads, chunk, head width) and its keys and values
(batch x KV heads, ring + chunk, head width) among the operand shapes.
Moves out_tok_s."""
from bench import costs, trace

KERNEL = "tpu_custom_call"
BLOCK = 32                    # the kernel's (block_q, block_kv) cap


def _cost(text, m, ring):
    shapes = [d for t, d in trace.shapes_of(text) if t == "bf16"
              and len(d) == 3]
    if not shapes:
        return None
    bhq, cp, dh = shapes[0]
    g = m["n_heads"] // m["n_kv_heads"]
    kv = [d for d in shapes[1:] if d[0] * g == bhq]
    if kv:
        bhkv, lp = kv[0][0], kv[0][1]
    else:
        bhkv = bhq // g
        bkv = BLOCK
        lp = -(-(ring + cp) // bkv) * bkv
    return costs.ring_kernel_cost(bhq, cp, bhkv, lp, dh, ring=ring,
                                  bq=min(BLOCK, cp), bkv=min(BLOCK, lp))


def read(rec):
    tr, pk, m = rec["trace"], rec["peaks"], rec["model"]
    if tr is None or pk is None:
        return None
    ring = rec["engine"]["max_len"]
    best = busy = 0.0
    for name, sec, text in tr["ops"]:
        if KERNEL not in name and KERNEL not in text:
            continue
        cost = _cost(name + " " + text, m, ring)
        if cost is None:
            return None
        best += costs.roofline_seconds(cost["flops"], cost["bytes"],
                                       pk["bf16_flops"], pk["hbm_bw"])
        busy += sec
    return 100.0 * best / busy if busy else None
