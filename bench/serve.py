"""One run of a cell on the engine: set-up, the window, the drain.

The engine runs as in production, in ``ServeEngine.run_until_done``.  The
benchmark's client is an open loop on the wall clock: a hook on the
engine's task runtime (``profile_hook``, called after every task step) reads
``time.monotonic()`` and calls ``ServeEngine.submit`` for every request
whose due time has passed.  When the engine runs out of work between
arrivals, ``run_until_done`` returns; the loop then waits for the next due
time, submits, and runs the engine again.  Every request is timed from its
due time, so a stall that delays submission is counted.

The hook is also the round probe: it records the wall time of every
runtime step (one step a round on a one-domain engine), which maps the
engine's round numbers (``Request.arrive_round``, ``grant_rounds``) to wall
time, and it reads the output tokens emitted at the window's two ends.
With ``trace``, it starts JAX's profiler for a few steady seconds inside the
window and snapshots the engine's counters at both ends of that span.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil
import tempfile
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from bench import traffic

DRAIN_LIMIT_S = 120.0         # a request may finish this long after the window


class DrainTimeout(RuntimeError):
    pass


class CompileCounter:
    """Counts XLA backend compiles (built or loaded from the persistent
    cache) from JAX's monitoring events."""

    def __init__(self):
        self.programs = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event: str, duration_secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += duration_secs


def model_config(conf: Dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(name=conf["name"], **conf["model"])


@contextlib.contextmanager
def engine_weights(weights):
    """Hand the benchmark's weights to the engine it constructs: the engine
    draws its parameters through ``init_params`` and has no argument for
    them."""
    import repro.serving.engine as engine_mod
    orig = engine_mod.init_params
    engine_mod.init_params = lambda cfg, key: weights
    try:
        yield
    finally:
        engine_mod.init_params = orig


def check_layout(cfg, weights):
    """The weights' tree must be the one the engine's forward reads."""
    from repro.models.params import abstract_params
    want = jax.tree.map(lambda a: (a.shape, str(a.dtype)),
                        abstract_params(cfg))
    have = jax.tree.map(lambda a: (a.shape, str(a.dtype)), weights)
    if want != have:
        raise ValueError(f"weight layout differs from the engine's: "
                         f"{have} vs {want}")


def build_engine(conf: Dict, weights, devices):
    from repro.core.topology import ChipletTopology
    from repro.serving.engine import EngineConfig, ServeEngine
    cfg = model_config(conf)
    check_layout(cfg, weights)
    e = conf["engine"]
    topo = ChipletTopology(n_pods=1, groups_per_pod=e["domains"],
                           chips_per_group=1)
    ecfg = EngineConfig(max_batch=e["max_batch"], max_len=e["max_len"],
                        pool_streams=e["pool_streams"])
    with engine_weights(weights):
        eng = ServeEngine(cfg, topo, ecfg, seed=0, spread_rate=1,
                          devices=devices)
    return cfg, eng


def warm(eng, device) -> int:
    """Compile every program the serve loop dispatches at this engine's
    shapes: ``warm_steps`` at the one chunk width the engine uses, then the
    greedy pick after each step as the loop calls it, on logits that a step
    left on ``device`` (``warm_steps`` warms it on uncommitted zeros, which
    is another program).  Returns the number of calls made."""
    import jax.numpy as jnp
    from repro.models import decode as dec
    calls = eng.warm_steps(chunks=(eng.pool.block_tokens,))
    b = 1
    while b <= eng.ecfg.max_batch:
        logits = jax.device_put(
            np.zeros((b, eng.cfg.vocab_padded), np.float32), device)
        dec.next_token_ids(logits, jnp.asarray(np.zeros((b,), np.int32)))
        calls += 1
        b *= 2
    jax.block_until_ready(eng.pool.storage)
    return calls


def emitted(eng) -> int:
    return sum(len(r.generated) for r in eng.submitted)


@dataclasses.dataclass
class _Sent:
    idx: int
    due: float                  # absolute (monotonic)
    submit: float
    req: Any


class Window:
    """Drives one window of a cell's traffic through ``eng``."""

    def __init__(self, eng, reqs: List[traffic.Req], seconds: float, *,
                 trace: bool = False, trace_s: float = 4.0,
                 clock=time.monotonic):
        self.eng = eng
        self.reqs = reqs
        self.seconds = seconds
        self.clock = clock
        self.trace = trace
        self.trace_s = min(trace_s, seconds / 2)
        self.sent: List[_Sent] = []
        self.round_t: List[float] = []
        self.round_off: Optional[int] = None     # engine round - probe round
        self.i = 0
        self.t0 = self.t_end = 0.0
        self.tok0 = self.tok1 = 0
        self.tA = self.tB = None
        self.trace_dir: Optional[str] = None
        self.trace_state = "off"
        self.trace_t: List[float] = []
        self.trace_counters: List[Dict[str, float]] = []
        self.held: List[tuple] = []     # (from, to): the profiler starting
        self._ann = None                # or stopping held the client

    # -- the hook: once per runtime step ----------------------------------
    def _submit_due(self, now: float, in_round: bool):
        while self.i < len(self.reqs) and self.due[self.i] <= now:
            r = self.reqs[self.i]
            with jax.profiler.TraceAnnotation("bench.submit"):
                req = self.eng.submit(r.prompt, r.max_new)
            t = self.clock()
            if in_round and self.round_off is None:
                # inside a round's step the engine's round counter has not
                # yet advanced: probe step k is engine round k + offset
                self.round_off = req.arrive_round - (len(self.round_t) - 1)
            self.sent.append(_Sent(self.i, self.due[self.i], t, req))
            self.i += 1

    def _trace_step(self, now: float):
        mid = self.t0 + self.seconds / 2
        if self.trace_state == "off" and now >= mid - self.trace_s / 2:
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            t = self.clock()
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self.held.append((t, self.clock()))
            self._ann = jax.profiler.TraceAnnotation("bench.traced_window")
            self._ann.__enter__()
            self.trace_t.append(self.clock())
            self.trace_counters.append(dict(self.eng.counters.totals))
            self.trace_state = "on"
        elif self.trace_state == "on" and now >= self.trace_t[0] + \
                self.trace_s:
            self.trace_t.append(self.clock())
            self.trace_counters.append(dict(self.eng.counters.totals))
            self._ann.__exit__(None, None, None)
            t = self.clock()
            jax.profiler.stop_trace()
            self.held.append((t, self.clock()))
            self.trace_state = "done"

    def _probe(self, now: float):
        if self.tB is None and now >= self.t_end:
            self.tok1, self.tB = emitted(self.eng), now
        if self.trace:
            self._trace_step(now)

    def hook(self, _task=None):
        """Called by the runtime after every task step (one a round)."""
        now = self.clock()
        self.round_t.append(now)
        with jax.profiler.TraceAnnotation("bench.probe"):
            self._submit_due(now, in_round=True)
            self._probe(now)
            if self.i >= len(self.reqs) and now > self.t_end + DRAIN_LIMIT_S \
                    and any(not s.req.done for s in self.sent):
                raise DrainTimeout(
                    f"requests unfinished {DRAIN_LIMIT_S:.0f} s after the "
                    f"window closed")

    # -- the loop -------------------------------------------------------------
    def run(self):
        eng = self.eng
        rt = eng.runtime
        prev_hook = rt.profile_hook
        rt.profile_hook = self.hook
        self.t0 = self.clock()
        self.t_end = self.t0 + self.seconds
        self.due = [self.t0 + r.due_s for r in self.reqs]
        self.tok0, self.tA = emitted(eng), self.t0
        timed_out = False
        try:
            while True:
                now = self.clock()
                self._submit_due(now, in_round=False)
                self._probe(now)
                try:
                    eng.run_until_done(max_rounds=10 ** 9)
                except DrainTimeout:
                    timed_out = True
                    break
                now = self.clock()
                done = all(s.req.done for s in self.sent)
                if self.i >= len(self.reqs) and done and (
                        self.tB is not None or now >= self.t_end) and (
                        not self.trace or self.trace_state == "done"):
                    break
                # idle: nothing runnable until the next arrival or probe
                nxt = [self.t_end] if self.tB is None else []
                if self.i < len(self.reqs):
                    nxt.append(self.due[self.i])
                if self.trace and self.trace_state != "done":
                    nxt.append(self.t0 + self.seconds / 2 - self.trace_s / 2
                               if self.trace_state == "off"
                               else self.trace_t[0] + self.trace_s)
                wait = min(nxt) - now if nxt else 0.0
                if wait > 0:
                    time.sleep(min(wait, 0.5))
        finally:
            rt.profile_hook = prev_hook
            if self.trace_state == "on":
                self._ann.__exit__(None, None, None)
                jax.profiler.stop_trace()
                self.trace_state = "done"
        if self.tB is None:
            self.tok1, self.tB = emitted(eng), self.clock()
        self.timed_out = timed_out
        return self

    # -- results --------------------------------------------------------------
    def round_time(self, engine_round: int) -> Optional[float]:
        if self.round_off is None:
            return None
        k = engine_round - self.round_off
        if 0 <= k < len(self.round_t):
            return self.round_t[k]
        return None

    def requests(self) -> List[Dict[str, Any]]:
        out = []
        for s in self.sent:
            q, r = s.req, self.reqs[s.idx]
            grant = self.round_time(q.grant_rounds[0]) \
                if q.grant_rounds else None
            held = any(s.due <= b and s.submit >= a for a, b in self.held)
            out.append({
                "due": s.due, "submit": s.submit, "grant": grant,
                "held_by_profiler": held,
                "t_first": q.t_first, "t_done": q.t_done,
                "n_out": len(q.generated), "max_new": q.max_new,
                "prompt_len": len(q.prompt), "prefix_tokens": q.prefix_tokens,
                "continues": r.continues})
        return out

    def counter_deltas(self) -> Dict[str, float]:
        if len(self.trace_counters) < 2:
            return {}
        a, b = self.trace_counters
        return {k: b.get(k, 0.0) - a.get(k, 0.0) for k in b
                if isinstance(b.get(k), (int, float))}

    def discard_trace(self):
        if self.trace_dir:
            shutil.rmtree(self.trace_dir, ignore_errors=True)

    def xplane(self) -> Optional[str]:
        if not self.trace_dir:
            return None
        for dirpath, _, files in os.walk(self.trace_dir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return os.path.join(dirpath, f)
        return None


def percentile(xs, q) -> Optional[float]:
    xs = [x for x in xs if x is not None]
    return float(np.percentile(xs, q)) if xs else None
