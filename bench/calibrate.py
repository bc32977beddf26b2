#!/usr/bin/env python3
"""Readings for the check's limit and for a cell's rate, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 20 \
        [--control] [--rate R]

For each seed, one run of the cell as ``bench/run.py`` makes it (set-up,
window, drain, check), one after another in this process, so that programs
compiled for the first seed serve the rest.  Prints one JSON line per run:
the seed, the offered rate, ``correct``, the widest served gap and, with
``--control``, the fp8 control judged by the same check and limit
(``"control": {"correct": false, ...}`` is the control failing, as it
must), plus the end-to-end metrics, the offered output tokens/s and the
median TTFT of the window's first and last third (a backlog).  ``--rate`` replaces the mix's rate (a sweep for the cell's knee).
The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import run, spec  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rate", type=float, default=None)
    a = ap.parse_args()
    cell = spec.cell(a.workload)
    if a.rate is not None:
        cell = copy.deepcopy(cell)
        cell["mix"]["rate_per_s"] = a.rate
    for seed in (int(s) for s in a.seeds.split(",")):
        try:
            out = run.run_cell(a.workload, seed, a.seconds, False, cell=cell,
                               control=a.control, detail=True)
        except run.NoChip as e:
            run.log(str(e))
            return 2
        line = {"seed": seed, "rate": cell["mix"]["rate_per_s"],
                "correct": out["correct"], "failed": out["failed"],
                "attempted": out["attempted"],
                "gap_max_std": out["checks"]["gap_max_std"]["value"],
                "control": out.get("control"),
                "metrics": {k: v["value"] for k, v in out["metrics"].items()},
                **out["detail"],
                "memory_peak_bytes": out["device"]["memory_peak_bytes"]}
        print(json.dumps(line), flush=True)
        del out
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
