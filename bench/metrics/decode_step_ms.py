"""paged step, decode: device time of the decode-step programs over the
decode steps run in the traced seconds (ms).  Moves out_tok_s."""
from bench import trace


def read(rec):
    tr, n = rec["trace"], rec["counters"].get("decode_forwards", 0.0)
    if tr is None or not n:
        return None
    sec, _ = trace.module_seconds(tr, "paged_decode")
    return sec / n * 1e3 if sec else None
