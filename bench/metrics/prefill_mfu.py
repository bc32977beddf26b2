"""model forward, prefill: model FLOPs of the prompt tokens prefilled in
the traced seconds over the chunk-step programs' device time times the
chip's bf16 peak (%).  The whole chunk step, not one kernel.  Layer matmuls
and attention count for every prompt token at the mean context of the
window's prefilled tokens; the head counts once per prompt, on its last
token.  Counts from the configuration's architecture module
(``rec["arch"]``).  Moves ttft_p50_ms."""
from bench import costs, trace


def read(rec):
    tr, c, m, arch = rec["trace"], rec["counters"], rec["model"], rec["arch"]
    if tr is None or rec["peaks"] is None:
        return None
    sec, _ = trace.module_seconds(tr, "paged_chunk")
    toks = c.get("tokens_processed", 0.0) - c.get("decode_committed_tokens",
                                                  0.0)
    if not sec or toks <= 0:
        return None
    keys = costs.prefill_mean_keys(rec["requests"])
    if not keys:
        return None
    flops = arch.prefill_flops(m, toks, c.get("prefills", 0.0), keys)
    return 100.0 * flops / (sec * rec["peaks"]["bf16_flops"])
