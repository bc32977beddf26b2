#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, in order: the compile cache inside the checkout (every program
cached); the benchmark's weights drawn on the device from the seed; the
engine, warmed at the cell's shapes only; the lead-in the mix asks for (a
filled prefix cache); the measured window, an open loop on the wall clock;
the drain of the window's requests; the peak device memory; the check of
served tokens against the plain reference, with the engine freed.  The last
line of standard output is one JSON object::

    {"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
     "checks"}

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of a few steady seconds in the
middle of the window.  The numbers the check compared, each with its limit,
are the last lines of standard error and the ``checks`` key.

A run that finds no TPU, or fewer chips than the cell asks for, exits 2
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import check, peaks, serve, spec, traffic  # noqa: E402
from bench import model as bmodel  # noqa: E402
from bench import trace as btrace  # noqa: E402


class NoChip(RuntimeError):
    pass


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def devices_for(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"bench: needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX sees "
                     f"{len(devs)}")
    return devs[:chips]


def enable_cache():
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def e2e_metrics(w: serve.Window, reqs, end_t: float) -> dict:
    ttft = [((r["t_first"] if r["t_first"] is not None else end_t)
             - r["due"]) * 1e3 for r in reqs]
    tpot = [(r["t_done"] - r["t_first"]) / (r["n_out"] - 1) * 1e3
            for r in reqs if r["t_done"] is not None and r["n_out"] >= 2]
    span = w.tB - w.tA
    return {"ttft_p50_ms": serve.percentile(ttft, 50),
            "ttft_p95_ms": serve.percentile(ttft, 95),
            "tpot_p95_ms": serve.percentile(tpot, 95),
            "out_tok_s": (w.tok1 - w.tok0) / span if span > 0 else None}


def load_detail(reqs, reqs_out, seconds: float) -> dict:
    """Offered output tokens/s, and the median TTFT of the first and the
    last third of the window's requests: a backlog shows as a last third
    that waits far longer than the first."""
    ttft = [None if r["t_first"] is None else (r["t_first"] - r["due"]) * 1e3
            for r in sorted(reqs_out, key=lambda r: r["due"])]
    k = max(1, len(ttft) // 3)
    return {"offered_tok_s": sum(r.max_new for r in reqs) / seconds,
            "ttft_p50_first_third_ms": serve.percentile(ttft[:k], 50),
            "ttft_p50_last_third_ms": serve.percentile(ttft[-k:], 50)}


class _Served:
    """What the check keeps of a finished request once the engine is
    gone."""

    def __init__(self, q):
        self.prompt = q.prompt
        self.generated = list(q.generated)
        self.prefix_tokens = q.prefix_tokens


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, cell: dict = None,
             keep_trace: str = None, fault=None, cache: bool = True,
             control: bool = False, detail: bool = False) -> dict:
    """One run; returns the result object.  ``cell`` overrides the cell read
    from BENCHMARK.json, ``fault(eng)`` breaks the engine before the window,
    ``cache=False`` leaves JAX's compile cache off (tests), ``control`` also
    judges the fp8 control by the same check (``out["control"]``), and
    ``detail`` adds the load readings a sweep needs (``out["detail"]``);
    both are for ``bench/calibrate.py``."""
    import jax
    c = cell or spec.cell(name)
    conf, mix = c["config"], c["mix"]
    m = conf["model"]
    cache = enable_cache() if cache else "off"
    devs = devices_for(c["chips"], require_tpu)
    dev = devs[0]
    pk = peaks.peaks(dev.device_kind) if require_tpu else None
    log(f"bench: {name} seed {seed} on {dev.device_kind} x {len(devs)}; "
        f"compile cache {cache}")
    compiles = serve.CompileCounter()

    arch = c["arch"]
    weights = bmodel.draw(arch.layout(m), seed, dev)
    jax.block_until_ready(weights)
    cfg, eng = serve.build_engine(conf, weights, devs)
    calls = serve.warm(eng, dev)
    lead, reqs = traffic.generate(mix, seed, seconds, m["vocab"])
    if lead:
        for r in lead:
            eng.submit(r.prompt, r.max_new)
        eng.run_until_done(max_rounds=10 ** 9)
    if fault is not None:
        fault(eng)
    n_setup = compiles.programs
    log(f"bench: set-up {time.monotonic() - T_START:.1f} s: {calls} warm "
        f"calls, {len(lead)} lead-in requests, {n_setup} programs compiled "
        f"or loaded in {compiles.seconds:.1f} s")

    w = serve.Window(eng, reqs, seconds, trace=trace)
    setup_s = time.monotonic() - T_START
    jax.config.update("jax_log_compiles", True)
    w.run()
    jax.config.update("jax_log_compiles", False)
    end_t = time.monotonic()
    n_window = compiles.programs - n_setup
    stats = dev.memory_stats() or {}
    mem_peak = stats.get("peak_bytes_in_use")
    reqs_out = w.requests()
    unfinished = sum(1 for r in reqs_out if r["t_done"] is None)
    log(f"bench: window {seconds} s: {len(reqs)} requests due, "
        f"{len(reqs_out)} submitted, {unfinished} unfinished; "
        f"{n_window} programs compiled inside the window; peak memory "
        f"{mem_peak}")

    metrics = {}
    units = {x["name"]: x["unit"] for x in c["end_to_end"] + c["per_layer"]}
    breakdown, device_extra = None, {}
    if not trace:
        e2e = e2e_metrics(w, reqs_out, end_t)
        e2e["setup_s"] = setup_s
        for x in c["end_to_end"]:
            v = e2e.get(x["name"])
            if v is not None:
                metrics[x["name"]] = {"value": v, "unit": x["unit"]}
    else:
        tr = None
        path = w.xplane()
        if path:
            loaded = btrace.load(path)
            if keep_trace:
                Path(keep_trace).mkdir(parents=True, exist_ok=True)
                (Path(keep_trace) / f"{name}.{seed}.dump.txt").write_text(
                    btrace.dump(loaded))
                (Path(keep_trace) / f"{name}.{seed}.trim.json").write_text(
                    json.dumps(btrace.trim(loaded)))
            tr = btrace.reduce(loaded)
            del loaded
        w.discard_trace()
        rec = {"requests": reqs_out, "trace": tr,
               "counters": w.counter_deltas(), "model": m, "arch": arch,
               "engine": conf["engine"], "peaks": pk,
               "trace_s": (w.trace_t[1] - w.trace_t[0])
               if len(w.trace_t) == 2 else None}
        for x in c["per_layer"]:
            v = spec.metric_reader(x["name"])(rec)
            if v is not None:
                metrics[x["name"]] = {"value": v, "unit": units[x["name"]]}
        if tr is not None:
            device_extra = {"busy_s": tr["busy_s"],
                            "window_s": tr["window_s"]}
            breakdown = {"device_ops": tr["top_ops"],
                         "idle_gaps": tr["idle_gaps"]}
            log(f"bench: trace {tr['window_s']:.3f} s, busy "
                f"{tr['busy_s']:.3f} s; programs {tr['modules']}")

    # the check: the engine and its pool go first, the weights stay
    finished = [_Served(s.req) for s in w.sent if s.req.done]
    del w, eng
    gc.collect()
    lim = conf["check"]
    smp = check.sample(finished, seed, lim["sample_tokens"],
                       lim["sample_requests"])
    t0 = time.monotonic()
    g = check.gaps(arch, weights, m, smp, pad_to=traffic.max_tokens(mix),
                   control=control)
    gap_max = check.widest(g["served"]) if smp else None
    checks = check.verdict(gap_max, unfinished, lim["gap_max_std"])
    log(f"bench: check of {len(smp)} requests, {g['served'].size} served "
        f"tokens in {time.monotonic() - t0:.1f} s; argmax share "
        f"{float((g['served'] == 0).mean()):.4f}")
    if control:
        log(f"control gap_max_std {check.widest(g['control'])} limit "
            f"{lim['gap_max_std']}")
    for k, v in checks.items():
        log(f"check {k} {v['value']} limit {v['limit']}")
    out = {"correct": check.passed(checks), "attempted": len(reqs),
           "failed": unfinished, "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices()),
                      "memory_peak_bytes": mem_peak, **device_extra}}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if detail:
        out["detail"] = load_detail(reqs, reqs_out, seconds)
    if control:
        cv = check.verdict(check.widest(g["control"]), None,
                           lim["gap_max_std"])
        out["control"] = {"correct": check.passed(cv), "checks": cv}
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="directory for a readable dump of the trace")
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), keep_trace=args.keep_trace)
    except NoChip as e:
        log(str(e))
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
