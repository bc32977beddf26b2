"""The engine's host spans (``arcas.*``) read beside the device trace.

The engine marks its host work with spans named ``arcas.<stage>``
(``repro.core.counters.span``): one ``arcas.round`` a scheduler round, and
inside it admission, tick assembly, each step's dispatch, the host's wait
for the step's tokens (``arcas.sync``), the commit, the stall watchdog and
the per-round counter feed.  They are written into the same profiler trace
as the device's operations, on the same clock.

``load`` is ``trace.load`` plus those spans under ``spans``, as ``(name,
start_ns, dur_ns, args)``.  ``reduce`` is ``trace.reduce`` with each idle gap
of the device named after

* the ``bench.*`` span covering at least half of it, as ``trace.reduce``
  names it;
* else the innermost (shortest) ``arcas.*`` span covering at least half of
  it; ``arcas.round`` only when no span inside the round covers it, where it
  means engine code that has no span of its own;
* else ``trace.NO_SPAN``;

and adds

* ``idle_by_span``: the device's idle seconds by the span active at each
  instant of every gap (``split``: a ``bench.*`` span, else the innermost
  ``arcas.*`` one, where ``arcas.round`` is the round's own code, else
  ``trace.NO_SPAN``);
* ``spans``: per name, the count and seconds inside the window;
* ``dispatches``: the steps dispatched, by their ``step`` argument;
* ``rounds``: for each ``arcas.round`` wholly inside the window, its start
  after the window's, its length, and its host time, the length less the
  part that ``arcas.sync`` and ``bench.*`` spans cover (seconds).
"""
from __future__ import annotations

import collections
from typing import Dict, List, Optional, Tuple

from bench import trace

PREFIX = "arcas."
ROUND = "arcas.round"
SYNC = "arcas.sync"


def load(path: str) -> Dict:
    from jax.profiler import ProfileData
    loaded = trace.load(path)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append((e.name.split("#")[0], float(e.start_ns),
                                  float(e.duration_ns),
                                  {k: str(v) for k, v in e.stats}))
    loaded["spans"] = spans
    return loaded


def _covered(lo: float, hi: float, intervals) -> float:
    """Length of [lo, hi] that the union of ``intervals`` covers."""
    iv = [(max(s, lo), min(e, hi)) for s, e in intervals]
    return sum(b - a for a, b in trace._union([x for x in iv if x[1] > x[0]]))


def attribute(gap: Tuple[float, float], host, spans) -> str:
    name = trace._attribute(gap, host)
    if name != trace.NO_SPAN:
        return name
    half = 0.5 * (gap[1] - gap[0])
    best, best_len = trace.NO_SPAN, None
    for n, s, d, _ in spans:
        if min(gap[1], s + d) - max(gap[0], s) < half:
            continue
        rank = (n == ROUND, d)          # a round only when nothing inside
        if best_len is None or rank < best_len:
            best, best_len = n, rank
    return best


def split(gap: Tuple[float, float], host, spans) -> Dict[str, float]:
    """Nanoseconds of ``gap`` by the span active at each instant: a
    ``bench.*`` span first, else the innermost ``arcas.*`` span (a round
    only where no span inside it is active, its own code), else
    ``trace.NO_SPAN``."""
    lo, hi = gap
    cover = [(s, s + d, (0, False, d, n)) for n, s, d in host
             if n != trace.WINDOW_SPAN and s < hi and s + d > lo]
    cover += [(s, s + d, (1, n == ROUND, d, n)) for n, s, d, _ in spans
              if s < hi and s + d > lo]
    cuts = sorted({lo, hi} | {min(max(x, lo), hi)
                              for s, e, _ in cover for x in (s, e)})
    out: Dict[str, float] = collections.defaultdict(float)
    for a, b in zip(cuts, cuts[1:]):
        active = [k for s, e, k in cover if s <= a and e >= b]
        out[min(active)[-1] if active else trace.NO_SPAN] += b - a
    return out


def _gaps(loaded: Dict, lo: float, hi: float) -> List[List[Tuple]]:
    """Idle intervals of each device inside [lo, hi], as ``trace.reduce``
    finds them."""
    out = []
    for _, lines in sorted(loaded["devices"].items()):
        ops = lines.get(trace.OPS_LINE)
        if not ops:
            continue
        iv = []
        for _, s, d, _ in ops:
            a, b = trace._clip(s, s + d, lo, hi)
            if b > a:
                iv.append((a, b))
        gaps, prev = [], lo
        for a, b in trace._union(iv):
            if a > prev:
                gaps.append((prev, a))
            prev = b
        if hi > prev:
            gaps.append((prev, hi))
        out.append(gaps)
    return out


def reduce(loaded: Dict) -> Optional[Dict]:
    tr = trace.reduce(loaded)
    if tr is None:
        return None
    lo, hi = trace._window(loaded)
    host, spans = loaded["host"], loaded.get("spans", [])
    per_dev = _gaps(loaded, lo, hi)
    gaps = sorted((g for dev in per_dev for g in dev),
                  key=lambda g: -(g[1] - g[0]))
    tr["idle_gaps"] = [[attribute(g, host, spans), (g[1] - g[0]) * 1e-9]
                       for g in gaps[:trace.TOP]]
    idle: Dict[str, float] = collections.defaultdict(float)
    for g in gaps:
        for name, ns in split(g, host, spans).items():
            idle[name] += ns * 1e-9 / len(per_dev)
    tr["idle_by_span"] = dict(idle)
    summary: Dict[str, List[float]] = {}
    steps: Dict[str, int] = collections.defaultdict(int)
    for n, s, d, args in spans:
        a, b = trace._clip(s, s + d, lo, hi)
        if not lo <= s < hi:
            continue
        c = summary.setdefault(n, [0, 0.0])
        c[0] += 1
        c[1] += max(b - a, 0.0) * 1e-9
        if "step" in args:
            steps[args["step"]] += 1
    tr["spans"] = summary
    tr["dispatches"] = dict(steps)
    waits = [(s, s + d) for n, s, d, _ in spans if n == SYNC] + [
        (s, s + d) for n, s, d in host if n != trace.WINDOW_SPAN]
    tr["rounds"] = [
        [(s - lo) * 1e-9, d * 1e-9, (d - _covered(s, s + d, waits)) * 1e-9]
        for n, s, d, _ in spans if n == ROUND and s >= lo and s + d <= hi]
    return tr


def trim(loaded: Dict, start_ns: float, ms: float = 40.0) -> Dict:
    """``ms`` milliseconds of the traced window from ``start_ns``, as a
    small stand-alone trace that ``reduce`` reads: device events, the
    benchmark's host spans, the engine's spans and a window span over the
    slice."""
    win = trace._window(loaded)
    lo = max(start_ns, win[0])
    hi = min(win[1], lo + ms * 1e6)
    inside = lambda s, d: s < hi and s + d > lo  # noqa: E731
    devices = {p: {ln: [list(e) for e in evs if inside(e[1], e[2])]
                   for ln, evs in lines.items()}
               for p, lines in loaded["devices"].items()}
    host = [list(h) for h in loaded["host"]
            if h[0] != trace.WINDOW_SPAN and inside(h[1], h[2])]
    host.append([trace.WINDOW_SPAN, lo, hi - lo])
    spans = [list(x) for x in loaded.get("spans", []) if inside(x[1], x[2])]
    return {"devices": devices, "host": host, "spans": spans}
