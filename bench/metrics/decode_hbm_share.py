"""model forward, decode: bytes the decode steps of the traced seconds
need (the weights and head once a step, the live KV of each row, the new
token's KV) over the decode programs' device time times the chip's HBM
bandwidth (%).  Counts from the configuration's architecture module
(``rec["arch"]``).  Moves out_tok_s."""
from bench import costs, trace


def read(rec):
    tr, c, m, arch = rec["trace"], rec["counters"], rec["model"], rec["arch"]
    if tr is None or rec["peaks"] is None:
        return None
    sec, _ = trace.module_seconds(tr, "paged_decode")
    steps, rows = c.get("decode_forwards", 0.0), c.get("decode_row_forwards",
                                                       0.0)
    if not sec or not steps:
        return None
    keys = costs.decode_mean_keys(rec["requests"])
    if not keys:
        return None
    byts = arch.decode_bytes(m, steps, rows, keys)
    return 100.0 * byts / (sec * rec["peaks"]["hbm_bw"])
