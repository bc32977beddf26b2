"""KV pool: prompt tokens served from the prefix cache over all prompt
tokens of the window's requests (%).  Moves ttft_p50_ms."""


def read(rec):
    total = sum(r["prompt_len"] for r in rec["requests"]
                if r["grant"] is not None)
    hit = sum(r["prefix_tokens"] for r in rec["requests"]
              if r["grant"] is not None)
    return 100.0 * hit / total if total else None
