"""scheduler / admission: p95 over the window's requests of the first page
grant's wall time minus the due time (ms).  The grant is the engine round
in ``Request.grant_rounds[0]``, mapped to wall time by the round probe;
requests whose submission the profiler held are left out.  Moves
ttft_p95_ms."""
import numpy as np


def read(rec):
    w = [r["grant"] - r["due"] for r in rec["requests"]
         if r["grant"] is not None and not r["held_by_profiler"]]
    return float(np.percentile(w, 95)) * 1e3 if w else None
