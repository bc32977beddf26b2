"""scheduler / tick assembly: decode rows per decode step over the traced
seconds, ``decode_row_forwards / decode_forwards``.  Moves out_tok_s."""


def read(rec):
    c = rec["counters"]
    n = c.get("decode_forwards", 0.0)
    return c.get("decode_row_forwards", 0.0) / n if n else None
