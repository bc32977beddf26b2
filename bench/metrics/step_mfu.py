"""model forward, whole step: model FLOPs of every token the steps of the
traced seconds computed (the prompt tokens prefilled, with the head once
per prompt, and the decode rows, each with the head) over the traced
window times the chip's bf16 peak (%).  Idle time counts, so this share
bounds what a kernel's roofline gain can move end to end.  Counts from
the configuration's architecture module (``rec["arch"]``).  Moves
out_tok_s."""
from bench import costs


def read(rec):
    tr, c, m, arch = rec["trace"], rec["counters"], rec["model"], rec["arch"]
    if tr is None or rec["peaks"] is None or not tr["window_s"]:
        return None
    pre = c.get("tokens_processed", 0.0) - c.get("decode_committed_tokens",
                                                 0.0)
    rows = c.get("decode_row_forwards", 0.0)
    flops = 0.0
    if pre > 0:
        flops += arch.prefill_flops(m, pre, c.get("prefills", 0.0),
                                    costs.prefill_mean_keys(rec["requests"]))
    if rows > 0:
        flops += rows * arch.token_flops(
            m, costs.decode_mean_keys(rec["requests"]), head=True)
    if not flops:
        return None
    return 100.0 * flops / (tr["window_s"] * rec["peaks"]["bf16_flops"])
