"""On-chip serving benchmark, driven by data.

``BENCHMARK.json`` at the repository root names the cells.  Everything that
belongs to one configuration, one traffic mix or one per-layer metric sits
in a file of its own, found by the name the cell or metric gives:

* ``bench/configs/<config>.json``: the model as it is run, its engine sizing,
  its source and what was cut (``reduced``) or assumed (``assumed``); its
  ``reference`` names the architecture module ``bench/references/<name>.py``:
  the weights' layout, the plain forward and the FLOP and byte counts
  (``bench/spec.py``'s ``reference`` lists what it defines);
* ``bench/traffic/<mix>.json``: the parameters of one traffic mix, read by
  the one generator in ``bench/traffic.py``;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric, ``read(rec)``
  over the run's record (``bench/serve.py``), returning a number or None.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell on the chip it is started on.
"""
