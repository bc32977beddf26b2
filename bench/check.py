"""Is what the timed path served correct?  Served tokens against the plain
reference.

After the window has closed and the engine is freed, a sample of the
finished requests, drawn from the seed, is run through the configuration's
plain reference once each: prompt + served tokens, teacher-forced.  At each
served position the reference's logits give a row maximum and a row
standard deviation; the served token's gap is how far its reference logit
lies below the row maximum, in row standard deviations.  The compared
number is the widest gap over the sample (``gap_max_std``).

The sample always holds the longest finished request (prompt + output) and
the one that took the most prompt tokens from the prefix cache, then
requests drawn from the seed until it holds at least ``MIN_REQUESTS``
requests and ``tokens`` served tokens, or ``requests`` requests (the
configuration's ``check`` block sets both).

The control puts the reference in the program's place, computed with fp8
matmuls (``quant="fp8"``): at each position of the same sequences, the token
the fp8 reference ranks first is scored by the bf16 reference.  Its widest
gap goes through the same ``verdict`` and ``passed`` with the same limit,
and must come out as not correct.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

MIN_REQUESTS = 4


def sample(reqs: List, seed: int, tokens: int = 512, requests: int = 8
           ) -> List:
    """``reqs``: engine requests that finished (``prompt``, ``generated``,
    ``prefix_tokens``).  Deterministic in ``seed``."""
    if not reqs:
        return []
    rng = np.random.default_rng([seed, 0xC4EC])
    longest = max(range(len(reqs)),
                  key=lambda i: (len(reqs[i].prompt) + len(reqs[i].generated),
                                 -i))
    picked = [longest]
    shared = max(range(len(reqs)), key=lambda i: (reqs[i].prefix_tokens, -i))
    if reqs[shared].prefix_tokens > 0 and shared not in picked:
        picked.append(shared)
    for i in rng.permutation(len(reqs)):
        n = sum(len(reqs[j].generated) for j in picked)
        if len(picked) >= requests or (
                len(picked) >= MIN_REQUESTS and n >= tokens):
            break
        if int(i) not in picked:
            picked.append(int(i))
    return [reqs[i] for i in picked]


def _sequence(req):
    toks = np.concatenate([np.asarray(req.prompt, np.int32),
                           np.asarray(req.generated[:-1], np.int32)])
    rows = len(req.prompt) - 1 + np.arange(len(req.generated))
    return toks, rows.astype(np.int32)


def gaps(ref, params, m: Dict, reqs: List, pad_to: int,
         control: bool = False) -> Dict[str, np.ndarray]:
    """Per served token: ``served`` gap, and with ``control`` the gap of the
    fp8 reference's first-ranked token, both in bf16-reference row stds."""
    served, ctrl = [], []
    for q in reqs:
        toks, rows = _sequence(q)
        tok_served = np.asarray(q.generated, np.int32)[:, None]
        if control:
            lo = ref.scores(params, m, toks, rows, tok_served, quant="fp8",
                            pad_to=pad_to)
            score = np.concatenate([tok_served, lo["argmax"][:, None]], 1)
        else:
            score = tok_served
        r = ref.scores(params, m, toks, rows, score, pad_to=pad_to)
        g = (r["max"][:, None] - r["score"]) / r["std"][:, None]
        served.append(g[:, 0])
        if control:
            ctrl.append(g[:, 1])
    out = {"served": np.concatenate(served)}
    if control:
        out["control"] = np.concatenate(ctrl)
    return out


def widest(g: np.ndarray) -> float:
    """The widest gap; a non-finite logit reads as an infinite gap."""
    if g.size == 0:
        return float("inf")
    return float(np.max(np.where(np.isfinite(g), g, np.inf)))


def verdict(gap_max: Optional[float], unfinished: Optional[int],
            limit: float) -> Dict[str, Dict[str, float]]:
    """The numbers compared, each with its limit.  The control serves no
    requests and passes ``unfinished=None``."""
    out = {"gap_max_std": {"value": gap_max, "limit": limit}}
    if unfinished is not None:
        out["unfinished"] = {"value": unfinished, "limit": 0}
    return out


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())
