"""scheduler, tick assembly: ``arcas.assemble`` time inside the traced
window over the steps dispatched there (``arcas.dispatch`` with ``step``
chunk, decode or spec), in ms a dispatched step.  Read from the engine's
spans (``bench/spans.py``); a trace without them reads nothing.  Moves
out_tok_s."""

STEPS = ("chunk", "decode", "spec")


def read(rec):
    tr = rec["trace"]
    if tr is None or "spans" not in tr:
        return None
    n = sum(tr["dispatches"].get(s, 0) for s in STEPS)
    sec = tr["spans"].get("arcas.assemble", [0, 0.0])[1]
    return 1e3 * sec / n if n else None
