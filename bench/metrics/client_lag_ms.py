"""client: how late the open-loop client submitted, p99 of submit - due
(ms), over the window's requests; those whose submission the profiler held
while it started or stopped are left out.  Moves ttft_p95_ms."""
import numpy as np


def read(rec):
    lag = [r["submit"] - r["due"] for r in rec["requests"]
           if not r["held_by_profiler"]]
    return float(np.percentile(lag, 99)) * 1e3 if lag else None
