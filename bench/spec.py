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
    """The architecture module a configuration's ``reference`` names,
    ``references/<name>.py``: everything the harness knows of one
    architecture, so that a new one is added as files only.  ``m`` is the
    configuration's ``model`` block; counts are of bf16 weights.  It
    defines:

    * ``layout(m)``: the benchmark's weights as a tree of leaf ->
      ``(shape, init)``, init a standard deviation or ``"ones"``, in the
      engine's parameter layout (``bench/model.py`` draws it; the engine
      checks it against its own tree);
    * ``scores(params, m, tokens, rows, score, quant=None, pad_to=0)``: the
      plain reference forward over one sequence, returning at positions
      ``rows`` the logits' ``max``, ``std``, ``argmax`` and the logits of
      the token ids ``score``; ``quant="fp8"`` is the control
      (``bench/check.py``);
    * ``token_flops(m, context, *, head)``: forward FLOPs of one token whose
      query sees ``context`` keys, with the output head only when ``head``;
    * ``prefill_flops(m, tokens, prompts, mean_keys)``: FLOPs to prefill
      ``tokens`` prompt tokens at ``mean_keys`` keys on average that
      complete ``prompts`` prompts (the head once per prompt);
    * ``weight_bytes(m)``: bytes one step reads once (weights, norms,
      head);
    * ``kv_token_bytes(m)``: cache bytes one token holds over all layers;
    * ``decode_bytes(m, steps, rows, mean_keys)``: bytes ``steps`` decode
      steps of ``rows`` rows in all need, each row reading ``mean_keys``
      cached tokens on average and writing its own.

    The counts are model FLOPs and least bytes: what the architecture
    needs, not what one implementation of it does."""
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
    """The workload entry named ``name``, with its configuration, its
    architecture module (``arch``), mix and the metrics it reports, all
    resolved from files."""
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
    conf = config(w["config"], bench)
    return {"workload": w, "chips": w["chips"], "config": conf,
            "arch": reference(conf["reference"], bench),
            "mix": mix(w["traffic"], bench),
            "end_to_end": e2e, "per_layer": per_layer}


def readers(metrics: List[Dict[str, Any]], bench: Path = BENCH
            ) -> Dict[str, Callable]:
    return {m["name"]: metric_reader(m["name"], bench) for m in metrics}
