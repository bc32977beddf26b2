"""device: share of the traced window in which no operation ran on the
device (%).  Moves out_tok_s."""


def read(rec):
    tr = rec["trace"]
    if tr is None or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
