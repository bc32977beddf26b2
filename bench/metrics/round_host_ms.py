"""engine host: mean host time of the scheduler rounds wholly inside the
traced window, each round's ``arcas.round`` span less the part that
``arcas.sync`` (the host waiting on the device) and the benchmark's own
spans cover (ms).  Read from the engine's spans (``bench/spans.py``); a
trace without them reads nothing.  Moves out_tok_s."""


def read(rec):
    tr = rec["trace"]
    rounds = tr.get("rounds") if tr is not None else None
    if not rounds:
        return None
    return 1e3 * sum(r[2] for r in rounds) / len(rounds)
