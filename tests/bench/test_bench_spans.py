"""The engine's spans read beside the device trace (``bench/spans.py``):
idle gaps named after the innermost span, and the two readers of the
engine's host time."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import spans, spec, trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6                                      # ns


def _sp(name, start_ms, dur_ms, **args):
    return (name, start_ms * MS, dur_ms * MS, args)


def synthetic():
    """One device, a 30 ms window.  Round A (1-11 ms): assembly 1-4, a
    chunk step dispatched 4-4.5 and run on the device 4.5-9 while the host
    waits in sync, commit 9-10.5 (with a commit_prefill dispatch inside),
    the stall watchdog 10.5-11.  Round B (12-24 ms): assembly 12-13, a
    decode step 13-13.5 run 13.6-18, commit 18-22 with the benchmark's
    probe 19-22 inside, then 2 ms of the round's own code.  Round C starts
    at 26 ms and ends after the window."""
    ops = [("fusion.1", 4.5 * MS, 4.5 * MS, "jit_paged_chunk"),
           ("fusion.2", 13.6 * MS, 4.4 * MS, "jit_paged_decode")]
    mods = [("jit_paged_chunk(1)", 4.5 * MS, 4.5 * MS, ""),
            ("jit_paged_decode(2)", 13.6 * MS, 4.4 * MS, "")]
    return {"devices": {"/device:TPU:0": {trace.OPS_LINE: ops,
                                          trace.MODULES_LINE: mods}},
            "host": [(trace.WINDOW_SPAN, 0.0, 30 * MS),
                     ("bench.probe", 19 * MS, 3 * MS)],
            "spans": [_sp("arcas.round", 1, 10),
                      _sp("arcas.assemble", 1, 3),
                      _sp("arcas.dispatch", 4, 0.5, step="chunk"),
                      _sp("arcas.sync", 4.5, 4.5),
                      _sp("arcas.commit", 9, 1.5),
                      _sp("arcas.dispatch", 9.2, 0.1, step="commit_prefill"),
                      _sp("arcas.stall", 10.5, 0.5),
                      _sp("arcas.round", 12, 12),
                      _sp("arcas.assemble", 12, 1),
                      _sp("arcas.dispatch", 13, 0.5, step="decode"),
                      _sp("arcas.sync", 13.5, 4.5),
                      _sp("arcas.commit", 18, 4),
                      _sp("arcas.round", 26, 6),
                      _sp("arcas.assemble", 26, 2)]}


ROUND = _sp("arcas.round", 0, 10)
CASES = {
    # the assembly covers the gap; the round and a short dispatch too
    "innermost": ((1, 4), [], [ROUND, _sp("arcas.assemble", 1, 3),
                               _sp("arcas.dispatch", 3.5, 0.2)],
                  "arcas.assemble"),
    "shortest of two covering": ((3.5, 3.7), [],
                                 [ROUND, _sp("arcas.assemble", 1, 3),
                                  _sp("arcas.dispatch", 3.5, 0.2)],
                                 "arcas.dispatch"),
    "bench span wins": ((5, 8), [("bench.probe", 5 * MS, 3 * MS)],
                        [ROUND, _sp("arcas.commit", 5, 4)], "bench.probe"),
    "round alone": ((8.5, 10), [], [ROUND, _sp("arcas.commit", 5, 4)],
                    "arcas.round"),
    "no span": ((11, 12), [], [ROUND], trace.NO_SPAN),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_attribute(case):
    (a, b), host, sp, want = CASES[case]
    assert spans.attribute((a * MS, b * MS), host, sp) == want


def test_reduce_names_gaps_and_sums_spans():
    tr = spans.reduce(synthetic())
    assert sorted(tr["idle_gaps"], key=lambda g: g[1]) == [
        ["arcas.assemble", pytest.approx(0.0045)],
        [trace.NO_SPAN, pytest.approx(0.0046)],
        ["arcas.round", pytest.approx(0.012)]]
    # instant by instant: 0-1 ms no span, 1-4 assembly, 4-4.5 dispatch;
    # 9-13.6 commit, its dispatch, stall, none, assembly, dispatch, sync;
    # 18-30 commit, the probe inside it, B's own 2 ms, none, C's assembly
    # and C's own 2 ms
    assert tr["idle_by_span"] == pytest.approx(
        {trace.NO_SPAN: 0.004, "arcas.assemble": 0.006,
         "arcas.dispatch": 0.0011, "arcas.commit": 0.0024,
         "arcas.stall": 0.0005, "arcas.sync": 0.0001, "bench.probe": 0.003,
         "arcas.round": 0.004})
    assert sum(tr["idle_by_span"].values()) == pytest.approx(
        tr["window_s"] - tr["busy_s"])
    s = tr["spans"]
    assert s["arcas.round"] == [3, pytest.approx(0.026)]   # C clipped to 4
    assert s["arcas.assemble"] == [3, pytest.approx(0.006)]
    assert s["arcas.sync"] == [2, pytest.approx(0.009)]
    assert s["arcas.dispatch"][0] == 3
    assert tr["dispatches"] == {"chunk": 1, "commit_prefill": 1,
                                "decode": 1}
    # A: 10 ms less 4.5 of sync; B: 12 less 4.5 of sync and 3 of probe
    assert tr["rounds"] == [[pytest.approx(0.001), pytest.approx(0.010),
                             pytest.approx(0.0055)],
                            [pytest.approx(0.012), pytest.approx(0.012),
                             pytest.approx(0.0045)]]


def test_reduce_keeps_the_device_numbers():
    """Every number ``trace.reduce`` gives comes out the same."""
    rec = synthetic()
    base, tr = trace.reduce(rec), spans.reduce(rec)
    for key in ("window_s", "busy_s", "devices", "ops", "modules",
                "module_calls", "top_ops"):
        assert tr[key] == base[key], key
    assert [g[1] for g in tr["idle_gaps"]] == [g[1]
                                               for g in base["idle_gaps"]]


def test_readers_by_hand():
    rec = {"trace": spans.reduce(synthetic()), "counters": {},
           "requests": []}
    read = lambda name: spec.metric_reader(name)(rec)  # noqa: E731
    assert read("round_host_ms") == pytest.approx(5.0)     # (5.5 + 4.5) / 2
    # 6 ms of assembly over the chunk and decode dispatches
    assert read("tick_assembly_ms") == pytest.approx(3.0)


@pytest.mark.parametrize("tr", [None, "no spans"])
def test_readers_read_nothing_without_spans(tr):
    rec = {"trace": trace.reduce(synthetic()) if tr else None,
           "counters": {}, "requests": []}
    for name in ("round_host_ms", "tick_assembly_ms"):
        assert spec.metric_reader(name)(rec) is None


def test_trim_keeps_spans():
    small = spans.trim(synthetic(), start_ns=0.0, ms=12.0)
    tr = spans.reduce(json.loads(json.dumps(small)))
    assert tr["window_s"] == pytest.approx(0.012)
    assert tr["rounds"] == [[pytest.approx(0.001), pytest.approx(0.010),
                             pytest.approx(0.0055)]]
    assert tr["dispatches"] == {"chunk": 1, "commit_prefill": 1}


def test_recorded_chip_slice_with_spans():
    """40 ms of a traced v5e run of the chat cell, with the engine's spans:
    the decode step ends at 10 ms; the host waits 2.5 ms more in sync (the
    greedy pick and its copy), commits, assembles the next tick for 1.5 ms
    and dispatches the chunk step, which starts 5.06 ms after the decode
    step ended."""
    path = DATA / "nem_v5e_spans.trim.json"
    expect = json.loads((DATA / "nem_v5e_spans.expect.json").read_text())
    tr = spans.reduce(json.loads(path.read_text()))
    assert tr["idle_gaps"][0] == [expect["longest_gap"][0],
                                  pytest.approx(expect["longest_gap"][1])]
    assert tr["idle_by_span"] == pytest.approx(expect["idle_by_span"])
    assert sum(tr["idle_by_span"].values()) == pytest.approx(
        tr["window_s"] - tr["busy_s"])
    assert tr["dispatches"] == expect["dispatches"]
    rec = {"trace": tr, "counters": {}, "requests": []}
    for name in ("tick_assembly_ms", "round_host_ms"):
        want = expect[name]
        got = spec.metric_reader(name)(rec)
        assert got == (None if want is None else pytest.approx(want)), name
