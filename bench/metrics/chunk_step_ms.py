"""paged step, chunk: device time of the chunk-step programs over the
chunk ticks run in the traced seconds (ms).  Moves out_tok_s."""
from bench import trace


def read(rec):
    tr, n = rec["trace"], rec["counters"].get("chunk_ticks", 0.0)
    if tr is None or not n:
        return None
    sec, _ = trace.module_seconds(tr, "paged_chunk")
    return sec / n * 1e3 if sec else None
