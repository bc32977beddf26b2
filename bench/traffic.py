"""The one traffic generator: a mix file's parameters + a seed -> requests.

Every seed gets the same work in its own order.  Arrival gaps, prompt and
output lengths are the quantiles of the stated distributions at
``(i + 0.5) / n``, shuffled by the seed, so the offered tokens and the
window's arrivals are the same for every seed.  Each kind of draw has a set
of its own, used whole: the window's outputs, the lead-in's outputs, the
lead-in's contexts, the window's openers' contexts and the continuations'
appends.  The seed gives the order of each set, which session a
continuation extends, and the token ids.

A mix file holds:

* ``rate_per_s``: requests per second over the window (open loop);
* ``prompt``, ``output``: length distributions, each
  ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
  ``{"dist": "uniform", "min", "max"}``;
* optionally ``strata``: the window's gaps, prompts and outputs each
  ordered so that every block of ``strata`` consecutive arrivals holds one
  value of each ``1/strata`` of the sorted set (``order``); 1, the default,
  is a plain shuffle.  It keeps the seed's order from moving how much of
  the work lands early in the window;
* optionally ``sessions``: ``{"p_continue", "max_open", "append",
  "retire_at", "lead_in"}``.  ``round(n * (1 - p_continue))`` of the
  window's ``n`` arrivals open a new session with a ``prompt`` drawn
  context; the others continue a uniformly chosen open session (its prompt
  is the session's last prompt plus ``append`` new tokens).  A session
  retires before its prompt would pass ``retire_at``, and the arrival then
  opens one; opening past ``max_open`` retires the least recently used
  session.  ``lead_in`` sessions are opened before the window, in set-up.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, List, Tuple

import numpy as np


@dataclasses.dataclass
class Req:
    due_s: float                 # seconds after the window opens
    prompt: np.ndarray           # int32 token ids
    max_new: int
    session: int = -1            # -1: no session
    continues: bool = False      # extends its session's previous prompt


def _quantiles(dist: Dict, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        v = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)


def order(vals: np.ndarray, rng: np.random.Generator, strata: int = 1
          ) -> np.ndarray:
    """``vals`` in the seed's order.  With ``strata`` > 1 the sorted values
    are cut into ``strata`` runs of neighbours, and each block of
    ``strata`` consecutive places takes one value of each run, in the
    seed's order: every stretch of the schedule holds a like share of small
    and large values, whatever the seed."""
    if strata <= 1:
        return rng.permutation(vals)
    runs = np.array_split(np.sort(vals), strata)
    blocks: List[list] = [[] for _ in range(len(runs[0]))]
    for run in runs:
        for j, x in enumerate(rng.permutation(run)):
            blocks[j].append(x)
    return np.concatenate([rng.permutation(np.array(b)) for b in blocks])


def lengths(dist: Dict, n: int, rng: np.random.Generator, strata: int = 1
            ) -> np.ndarray:
    return order(_quantiles(dist, n), rng, strata)


def arrivals(rate: float, seconds: float, rng: np.random.Generator,
             strata: int = 1) -> np.ndarray:
    """Due times in [0, seconds): ``round(rate * seconds)`` arrivals whose
    gaps are exponential quantiles (Poisson), in the seed's order, summing
    to the window."""
    n = max(1, int(round(rate * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = order(-np.log1p(-u), rng, strata)
    gaps = gaps / gaps.sum() * seconds
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def generate(mix: Dict, seed: int, seconds: float, vocab: int
             ) -> Tuple[List[Req], List[Req]]:
    """(lead-in requests, window requests) for one run."""
    rng = np.random.default_rng(seed)
    strata = int(mix.get("strata", 1))
    due = arrivals(mix["rate_per_s"], seconds, rng, strata)
    n = len(due)
    outs = lengths(mix["output"], n, rng, strata)

    def toks(k: int) -> np.ndarray:
        return rng.integers(1, vocab, size=int(k), dtype=np.int64) \
            .astype(np.int32)

    sess = mix.get("sessions")
    if not sess:
        plen = lengths(mix["prompt"], n, rng, strata)
        return [], [Req(float(d), toks(p), int(o))
                    for d, p, o in zip(due, plen, outs)]

    n_lead = int(sess.get("lead_in", 0))
    n_open = int(round(n * (1.0 - sess["p_continue"])))
    opens = rng.permutation(np.arange(n) < n_open)
    lead_outs = lengths(mix["output"], n_lead, rng)
    # contexts: the lead-in's, the planned openers', then spares for
    # arrivals that open because their session retired
    contexts = [*lengths(mix["prompt"], n_lead, rng),
                *lengths(mix["prompt"], n_open, rng),
                *lengths(mix["prompt"], n, rng)]
    appends = list(lengths(sess["append"], n - n_open, rng))
    retire_at = sess["retire_at"]
    open_s: Dict[int, np.ndarray] = {}         # session -> last prompt
    lru: List[int] = []                        # least recently used first
    next_id = 0

    def open_new() -> Tuple[int, np.ndarray]:
        nonlocal next_id
        if len(open_s) >= sess["max_open"]:
            old = lru.pop(0)
            del open_s[old]
        sid, next_id = next_id, next_id + 1
        p = toks(contexts.pop(0))
        open_s[sid] = p
        lru.append(sid)
        return sid, p

    lead = []
    for i in range(n_lead):
        sid, p = open_new()
        lead.append(Req(0.0, p, int(lead_outs[i]), sid))
    window = []
    for i in range(n):
        if not opens[i] and open_s:
            ids = sorted(open_s)
            sid = ids[int(rng.integers(len(ids)))]
            grown = np.concatenate([open_s[sid], toks(appends.pop(0))])
            lru.remove(sid)
            if len(grown) <= retire_at:
                open_s[sid] = grown
                lru.append(sid)
                window.append(Req(float(due[i]), grown, int(outs[i]), sid,
                                  True))
                continue
            del open_s[sid]
        sid, p = open_new()
        window.append(Req(float(due[i]), p, int(outs[i]), sid))
    return lead, window


def max_tokens(mix: Dict) -> int:
    """Longest prompt + output a request of this mix can need."""
    sess = mix.get("sessions")
    p = sess["retire_at"] if sess else mix["prompt"]["max"]
    return int(p + mix["output"]["max"])
