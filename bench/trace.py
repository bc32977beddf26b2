"""Reduce a profiler trace (``.xplane.pb``) to the numbers the readers use.

``load`` flattens the trace into plain events; ``reduce`` works on those
alone, so it is tested on a small recorded event list.

* Device planes are those named ``/device:TPU:<n>``.  On each, the line
  ``XLA Ops`` holds one event per operation run on the device and ``XLA
  Modules`` one per program run.  Busy time is the union of the operation
  intervals inside the traced window, averaged over the devices.
* The traced window is the benchmark's own host span ``bench.traced_window``.
* Each idle gap on the device is named after the benchmark host span
  (``bench.*``) that covers at least half of it, else ``host: no benchmark
  span`` (the engine's own host code, which has no spans yet).
* Operations are named by their HLO id, result type (layouts dropped) and
  opcode, e.g. ``%fusion.10 = bf16[16,16,6144] fusion``.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.traced_window"
NO_SPAN = "host: no benchmark span"
TOP = 10


def _stat_text(stats) -> str:
    return " ".join(str(v) for _, v in stats if isinstance(v, str))


def load(path: str) -> Dict:
    """{"devices": {plane: {line: [(name, start_ns, dur_ns, text)]}},
    "host": [(name, start_ns, dur_ns)]} — ``text`` joins the event's
    string stats (HLO op, module, long name)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, list]] = {}
    other: Dict[str, list] = {}                  # a few events of other lines
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {}
            for line in plane.lines:
                evs = line.events
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        (e.name, float(e.start_ns), float(e.duration_ns),
                         _stat_text(e.stats)) for e in evs]
                else:
                    other[f"{plane.name} / {line.name}"] = [
                        (e.name, float(e.start_ns), float(e.duration_ns),
                         _stat_text(e.stats))
                        for e, _ in zip(evs, range(4))]
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.name, float(e.start_ns),
                                     float(e.duration_ns)))
    return {"devices": devices, "host": host, "other": other}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: float, e: float, lo: float, hi: float) -> Tuple[float, float]:
    return max(s, lo), min(e, hi)


def _window(loaded: Dict) -> Optional[Tuple[float, float]]:
    spans = [(s, s + d) for n, s, d in loaded["host"] if n == WINDOW_SPAN]
    if spans:
        return max(spans, key=lambda x: x[1] - x[0])
    ev = [(s, s + d) for lines in loaded["devices"].values()
          for s, d in ((x[1], x[2]) for x in lines.get(OPS_LINE, []))]
    if not ev:
        return None
    return min(s for s, _ in ev), max(e for _, e in ev)


def _attribute(gap: Tuple[float, float],
               host: List[Tuple[str, float, float]]) -> str:
    best, best_ov = NO_SPAN, 0.5 * (gap[1] - gap[0])
    for name, s, d in host:
        if name == WINDOW_SPAN:
            continue
        ov = min(gap[1], s + d) - max(gap[0], s)
        if ov >= best_ov:
            best, best_ov = name, ov
    return best


_LAYOUT = re.compile(r"\{[^{}]*\}")
_HLO = re.compile(r"^(%[\w.\-]+) = (.*?) ([\w\-]+)\(")


def short_name(name: str) -> str:
    """``%id = type opcode`` of an HLO operation's full text."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    typ = m.group(2)
    while _LAYOUT.search(typ):
        typ = _LAYOUT.sub("", typ)
    if len(typ) > 60:
        typ = typ[:57] + "..."
    out = f"{m.group(1)} = {typ} {m.group(3)}"
    if "tpu_custom_call" in name:
        out += " tpu_custom_call"
    return out


def reduce(loaded: Dict) -> Optional[Dict]:
    """None when the trace holds no device operation."""
    win = _window(loaded)
    devs = {p: l for p, l in loaded["devices"].items() if l.get(OPS_LINE)}
    if win is None or not devs:
        return None
    lo, hi = win
    busy_total = 0.0
    ops: List[Tuple[str, float, str]] = []          # (name, seconds, text)
    modules: Dict[str, float] = collections.defaultdict(float)
    module_calls: Dict[str, int] = collections.defaultdict(int)
    gaps: List[Tuple[float, float]] = []
    for plane, lines in sorted(devs.items()):
        iv = []
        for name, s, d, text in lines[OPS_LINE]:
            a, b = _clip(s, s + d, lo, hi)
            if b > a:
                iv.append((a, b))
                ops.append((name, (b - a) * 1e-9, text))
        u = _union(iv)
        busy_total += sum(b - a for a, b in u) * 1e-9
        prev = lo
        for a, b in u:
            if a > prev:
                gaps.append((prev, a))
            prev = b
        if hi > prev:
            gaps.append((prev, hi))
        for name, s, d, _ in lines.get(MODULES_LINE, []):
            a, b = _clip(s, s + d, lo, hi)
            if b > a:
                key = name.split("(")[0]
                modules[key] += (b - a) * 1e-9
                module_calls[key] += 1
    n_dev = len(devs)
    by_op: Dict[str, float] = collections.defaultdict(float)
    for name, sec, _ in ops:
        by_op[short_name(name)] += sec / n_dev
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    gaps.sort(key=lambda g: -(g[1] - g[0]))
    idle = [[_attribute(g, loaded["host"]), (g[1] - g[0]) * 1e-9]
            for g in gaps[:TOP]]
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy_total / n_dev,
            "devices": n_dev,
            "ops": ops,
            "modules": {k: v / n_dev for k, v in modules.items()},
            "module_calls": dict(module_calls),
            "top_ops": [[k, v] for k, v in top_ops],
            "idle_gaps": idle}


def module_seconds(tr: Dict, pattern: str) -> Tuple[float, int]:
    """Device seconds and calls of the programs whose name holds
    ``pattern``."""
    sec = sum(v for k, v in tr["modules"].items() if pattern in k)
    calls = sum(v for k, v in tr["module_calls"].items() if pattern in k)
    return sec, calls


SHAPE = re.compile(r"(bf16|f32|s32)\[([0-9,]*)\]")


def shapes_of(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    return [(t, tuple(int(x) for x in dims.split(",") if x))
            for t, dims in SHAPE.findall(text)]


def trim(loaded: Dict, ms: float = 40.0, at: str = "tpu_custom_call"
         ) -> Dict:
    """``ms`` milliseconds of the traced window from the first operation
    whose text holds ``at`` (else from the window's start), as a small
    stand-alone trace (device events, the benchmark's host spans and a
    window span over the slice)."""
    win = _window(loaded)
    if win is None:
        return {"devices": {}, "host": []}
    starts = [e[1] for lines in loaded["devices"].values()
              for e in lines.get(OPS_LINE, []) if at in e[0] + e[3]
              and win[0] <= e[1] < win[1]]
    lo = min(starts) - 1e6 if starts else win[0]
    lo = max(lo, win[0])
    hi = min(win[1], lo + ms * 1e6)
    inside = lambda s, d: s < hi and s + d > lo  # noqa: E731
    devices = {p: {ln: [list(e) for e in evs if inside(e[1], e[2])]
                   for ln, evs in lines.items()}
               for p, lines in loaded["devices"].items()}
    host = [list(h) for h in loaded["host"]
            if h[0] != WINDOW_SPAN and inside(h[1], h[2])]
    host.append([WINDOW_SPAN, lo, hi - lo])
    return {"devices": devices, "host": host}


def dump(loaded: Dict, n: int = 8) -> str:
    """A readable summary of a loaded trace, for a first look by hand."""
    out = []
    for plane, lines in loaded["devices"].items():
        for line, evs in lines.items():
            out.append(f"{plane} / {line}: {len(evs)} events")
            for e in evs[:n]:
                out.append(f"    {e[0]} start {e[1]:.0f} dur {e[2]:.0f} "
                           f"| {e[3][:400]}")
    for key, evs in loaded.get("other", {}).items():
        out.append(f"{key} (sample)")
        for e in evs:
            out.append(f"    {e[0]} start {e[1]:.0f} dur {e[2]:.0f} "
                       f"| {e[3][:300]}")
    names = collections.Counter(h[0] for h in loaded["host"])
    out.append(f"host bench spans: {dict(names)}")
    if loaded["host"]:
        out.append(f"    first host span: {loaded['host'][0]}")
    return "\n".join(out)
