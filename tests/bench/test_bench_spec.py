"""The harness finds configurations, mixes and metrics by name, and
BENCHMARK.json keeps to the shape the harness reads."""
import json
import re
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
BM = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_benchmark_file_shape():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["command"][0] == "python3" and all(
        (ROOT / p).is_dir() for p in BM["paths"])
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BM[k]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    e2e = {m["name"] for m in BM["end_to_end"]}
    assert "setup_s" in e2e
    for m in BM["per_layer"]:
        assert m["moves"] in e2e
    for c in BM["configs"]:
        f = ROOT / c["file"]
        assert f.is_file() and f.stem == c["name"]
        assert json.loads(f.read_text())["reduced"] == c["reduced"]


@pytest.mark.parametrize("cell", [w["name"] for w in BM["workloads"]])
def test_cells_resolve_from_files(cell):
    c = spec.cell(cell)
    assert c["config"]["name"] == c["workload"]["config"]
    assert c["mix"]["rate_per_s"] > 0
    assert {m["name"] for m in c["end_to_end"]} >= {"setup_s"}
    assert c["per_layer"]
    for name, read in spec.readers(c["per_layer"]).items():
        assert callable(read)
    ref = spec.reference(c["config"]["reference"])
    assert callable(ref.scores)


def test_unknown_names_fail():
    with pytest.raises(KeyError):
        spec.cell("no-such-cell")
    with pytest.raises(FileNotFoundError):
        spec.mix("no-such-mix")
    with pytest.raises(FileNotFoundError):
        spec.metric_reader("no_such_metric")
    with pytest.raises(FileNotFoundError):
        spec.metric_reader("no_such_metric.split")


def test_added_files_are_found_without_editing_any(tmp_path):
    """A later change adds a configuration, a mix, a metric and a cell by
    adding files and entries only."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = tmp_path / "bench"
    conf = json.loads((bench / "configs" / "starcoder2-15b-l10.json")
                      .read_text())
    conf["name"] = "new-model"
    (bench / "configs" / "new-model.json").write_text(json.dumps(conf))
    mix = {"rate_per_s": 1.0,
           "prompt": {"dist": "uniform", "min": 8, "max": 16},
           "output": {"dist": "uniform", "min": 4, "max": 8}}
    (bench / "traffic" / "new-mix.json").write_text(json.dumps(mix))
    (bench / "metrics" / "new_metric.py").write_text(
        "def read(rec):\n    return float(len(rec['requests']))\n")
    bm = json.loads(json.dumps(BM))
    bm["workloads"].append({"name": "new-model.new-mix",
                            "config": "new-model", "traffic": "new-mix",
                            "chips": 1, "why": "added"})
    bm["per_layer"].append({"name": "new_metric", "unit": "requests",
                            "better": "higher", "source": "host_clock",
                            "layer": "client", "moves": "out_tok_s",
                            "workloads": ["new-model.new-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    c = spec.cell("new-model.new-mix", root=tmp_path)
    assert c["config"]["name"] == "new-model"
    assert c["mix"] == mix
    assert [m["name"] for m in c["per_layer"]] == ["new_metric"]
    read = spec.readers(c["per_layer"], bench)["new_metric"]
    assert read({"requests": [1, 2, 3]}) == 3.0
    # the existing cells are untouched by the addition
    old = spec.cell(BM["workloads"][0]["name"], root=tmp_path)
    assert "new_metric" not in {m["name"] for m in old["per_layer"]}


def test_a_split_metric_reads_with_its_base_reader():
    """``<metric>.<split>`` moves another end-to-end metric and reads with
    ``metrics/<metric>.py`` unless it has a reader of its own."""
    rec = {"trace": None, "counters": {}}
    assert spec.metric_reader("decode_step_ms.chat")(rec) is None
    assert spec.metric_reader("decode_step_ms.chat").__module__ == \
        "bench_metric_decode_step_ms_chat"
