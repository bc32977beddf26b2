"""Find a cell's configuration, traffic mix and metric readers by name."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def _load_json(kind: str, name: str, bench: Path = BENCH) -> Dict[str, Any]:
    path = bench / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1] if kind.endswith('s') else kind}"
                                f" file for {name!r}: {path}")
    return json.loads(path.read_text())


def config(name: str, bench: Path = BENCH) -> Dict[str, Any]:
    return _load_json("configs", name, bench)


def mix(name: str, bench: Path = BENCH) -> Dict[str, Any]:
    return _load_json("traffic", name, bench)


def _load_module(path: Path, modname: str):
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(name: str, bench: Path = BENCH):
    """The plain reference module a configuration names."""
    return _load_module(bench / "references" / f"{name}.py",
                        f"bench_reference_{name.replace('-', '_')}")


def metric_reader(name: str, bench: Path = BENCH
                  ) -> Callable[[Dict[str, Any]], Optional[float]]:
    """``metrics/<name>.py``; a metric split by the end-to-end metric it
    moves (``decode_step_ms.chat``) falls back to the reader of its base
    name (``metrics/decode_step_ms.py``)."""
    path = bench / "metrics" / f"{name}.py"
    base = bench / "metrics" / f"{name.split('.')[0]}.py"
    mod = _load_module(path if path.is_file() or not base.is_file() else base,
                       "bench_metric_" + name.replace(".", "_")
                       .replace("-", "_"))
    return mod.read


def cell(name: str, root: Path = ROOT) -> Dict[str, Any]:
    """The workload entry named ``name``, with its configuration, mix and
    the metrics it reports, all resolved from files."""
    bm = benchmark(root)
    found = [w for w in bm["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in bm['workloads']]}")
    w = found[0]
    bench = root / "bench"
    e2e = [m for m in bm["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"]
                 if name in m.get("workloads", [name])
                 and m["moves"] in reported]
    return {"workload": w, "chips": w["chips"],
            "config": config(w["config"], bench),
            "mix": mix(w["traffic"], bench),
            "end_to_end": e2e, "per_layer": per_layer}


def readers(metrics: List[Dict[str, Any]], bench: Path = BENCH
            ) -> Dict[str, Callable]:
    return {m["name"]: metric_reader(m["name"], bench) for m in metrics}
