"""The trace reduction (trace -> numbers) on small traces."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6                                      # ns


def _ev(name, start_ms, dur_ms, text=""):
    return (name, start_ms * MS, dur_ms * MS, text)


def synthetic():
    """One device, a 10 ms window: ops busy 0-2, 3-5 (two nested ops) and
    8-9 ms; a submit span over 5.5-6 ms and a probe over 6-7.5 ms."""
    ops = [_ev("fusion.1", 0, 2, "jit_paged_chunk"),
           _ev("custom-call.3", 3, 2,
               "ring_fwd_kernel bf16[384,16,128] bf16[384,16,128] "
               "bf16[32,4128,128] bf16[32,4128,128]"),
           _ev("fusion.2", 3.5, 1, ""),
           _ev("fusion.1", 8, 1, ""),
           _ev("fusion.9", 11, 1, "")]        # after the window
    mods = [_ev("jit_paged_chunk(12)", 0, 5), _ev("jit_paged_decode(7)", 8, 1)]
    return {"devices": {"/device:TPU:0": {trace.OPS_LINE: ops,
                                          trace.MODULES_LINE: mods}},
            "host": [("bench.traced_window", 0.0, 10 * MS),
                     ("bench.submit", 5.5 * MS, 0.5 * MS),
                     ("bench.probe", 6 * MS, 1.5 * MS)]}


def test_busy_idle_and_gaps():
    tr = trace.reduce(synthetic())
    assert tr["window_s"] == pytest.approx(0.010)
    assert tr["busy_s"] == pytest.approx(0.005)      # 2 + 2 + 1 ms
    gaps = tr["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([0.003, 0.001, 0.001])
    # the 5-8 ms gap overlaps the probe most; the others no benchmark span
    assert gaps[0][0] == "bench.probe"
    assert {g[0] for g in gaps[1:]} == {trace.NO_SPAN}


def test_programs_and_top_ops():
    tr = trace.reduce(synthetic())
    assert trace.module_seconds(tr, "paged_chunk") == (pytest.approx(0.005),
                                                       1)
    assert trace.module_seconds(tr, "paged_decode") == (pytest.approx(0.001),
                                                        1)
    top = dict(tr["top_ops"])
    assert top["fusion.1"] == pytest.approx(0.003)
    assert "fusion.9" not in top


def test_shapes_from_op_text():
    text = synthetic()["devices"]["/device:TPU:0"][trace.OPS_LINE][1][3]
    assert trace.shapes_of(text)[:3] == [("bf16", (384, 16, 128)),
                                         ("bf16", (384, 16, 128)),
                                         ("bf16", (32, 4128, 128))]


def test_no_device_ops_reads_nothing():
    assert trace.reduce({"devices": {}, "host": []}) is None


def test_trim_keeps_a_reducible_slice():
    small = trace.trim(synthetic(), ms=4.0)
    tr = trace.reduce(small)
    assert tr["window_s"] == pytest.approx(0.004)
    assert tr["busy_s"] == pytest.approx(0.003)


@pytest.mark.parametrize("path", sorted(DATA.glob("*.trim.json")),
                         ids=lambda p: p.name)
def test_recorded_chip_trace(path):
    """A slice of a trace recorded on a v5e: its programs, kernel and idle
    time come out as they were read by hand."""
    loaded = json.loads(path.read_text())
    expect = json.loads(path.with_suffix("").with_suffix(".expect.json")
                        .read_text())
    tr = trace.reduce(loaded)
    assert tr["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-6)
    assert tr["window_s"] == pytest.approx(expect["window_s"], rel=1e-6)
    for pattern, sec in expect["modules"].items():
        assert trace.module_seconds(tr, pattern)[0] == pytest.approx(
            sec, rel=1e-6)
    kern = [o for o in tr["ops"] if expect["kernel"] in o[0] + o[2]]
    assert len(kern) == expect["kernel_calls"]


def test_readers_on_a_synthetic_record():
    """Per-layer readers on the synthetic trace with hand-set counters and
    a model whose costs are easy to count."""
    from bench import costs, spec
    dense = spec.reference("dense_gqa")
    m = {"n_layers": 1, "d_model": 2, "n_heads": 2, "n_kv_heads": 1,
         "head_dim": 1, "d_ff": 4, "vocab": 256, "activation": "gelu"}
    pk = {"bf16_flops": 1e12, "hbm_bw": 1e12}
    rec = {"trace": trace.reduce(synthetic()), "model": m, "arch": dense,
           "peaks": pk,
           "engine": {"max_len": 4096},
           "counters": {"chunk_ticks": 2, "decode_forwards": 1,
                        "decode_row_forwards": 3, "tokens_processed": 35,
                        "decode_committed_tokens": 3, "prefills": 1},
           "requests": [{"prompt_len": 8, "prefix_tokens": 0, "n_out": 3,
                         "due": 0.0, "submit": 0.01, "grant": 0.02,
                         "held_by_profiler": False}]}
    read = lambda name: spec.metric_reader(name)(rec)  # noqa: E731
    assert read("chunk_step_ms") == pytest.approx(2.5)     # 5 ms / 2 ticks
    assert read("decode_step_ms") == pytest.approx(1.0)
    assert read("decode_rows") == 3.0
    assert read("device_idle_share") == pytest.approx(50.0)
    assert read("prefix_hit_share") == 0.0
    # 32 prompt tokens at mean 4.5 keys (positions 0..7) + one head
    flops = dense.prefill_flops(m, 32, 1, 4.5)
    assert read("prefill_mfu") == pytest.approx(100 * flops / (0.005 * 1e12))
    # two decode rows of one request at positions 8, 9: mean 9.5 keys
    byts = dense.decode_bytes(m, 1, 3, 9.5)
    assert read("decode_hbm_share") == pytest.approx(
        100 * byts / (0.001 * 1e12))
    # the whole window: the 32 prompt tokens and 3 decode rows at 9.5 keys
    step = flops + 3 * dense.token_flops(m, 9.5, head=True)
    assert read("step_mfu") == pytest.approx(
        100 * step / (rec["trace"]["window_s"] * 1e12))
    # the synthetic kernel call: q (384,16,128), kv (32,4128,128)
    c = costs.ring_kernel_cost(384, 16, 32, 4128, 128, ring=4096, bq=16,
                               bkv=32)
    assert read("ring_kernel_roofline") is None     # not a tpu_custom_call
    rec["trace"]["ops"] = [(o[0], o[1], o[2] + " tpu_custom_call")
                           for o in rec["trace"]["ops"]
                           if "ring_fwd_kernel" in o[2]]
    m.update(n_heads=48, n_kv_heads=4)
    assert read("ring_kernel_roofline") == pytest.approx(
        100 * costs.roofline_seconds(c["flops"], c["bytes"], 1e12, 1e12)
        / 0.002)
