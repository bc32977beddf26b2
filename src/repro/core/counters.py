"""Performance counters — the paper's §4.5 profiler, libpfm replaced by
compiler-derived traffic classes + wall-clock step timing.

Counter names mirror Tab. 1/2 of the paper:
  local_bytes   — HBM traffic served within the replica's own chiplet groups
  remote_bytes  — collective bytes crossing group boundaries within a pod
                  (the "remote NUMA chiplet" / cache-fill event analogue;
                  this is what Algorithm 1 thresholds on)
  dcn_bytes     — cross-pod traffic (the "main memory" analogue)

Counters are cheap (plain floats) and keep a ring buffer of recent step
samples.  ``span`` marks a stretch of host work (the paper's "profile only
specific code segments") in JAX's profiler trace, on the same clock as the
device's operations; it costs about a microsecond while no profiler runs.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Deque, Dict

from jax.profiler import TraceAnnotation


def span(name: str, **args) -> TraceAnnotation:
    """A host span for JAX's profiler trace: ``with span("arcas.commit"):``.
    Names start with ``arcas.``; ``args`` identify a request or a step."""
    return TraceAnnotation(name, **args)


@dataclasses.dataclass
class StepSample:
    t: float
    step_time: float
    local_bytes: float = 0.0
    remote_bytes: float = 0.0
    dcn_bytes: float = 0.0
    flops: float = 0.0
    # KV block-pool health (serving): fraction of pool blocks in use, parks
    # (allocation failures) since the previous sample, and blocks copied
    # between chiplet-group domains since the previous sample.
    kv_occupancy: float = 0.0
    kv_parks: float = 0.0
    kv_blocks_migrated: float = 0.0
    # Continuous-batching loop health: pages committed lazily as streams
    # crossed a page boundary, streams parked MID-DECODE on domain
    # exhaustion, and prefill chunks processed — all deltas since the
    # previous sample.
    kv_lazy_grows: float = 0.0
    kv_mid_decode_parks: float = 0.0
    prefill_chunks: float = 0.0
    # Swap-tier eviction health: pages spilled to the host tier, spilled
    # streams restored mid-decode, and tokens thrown away by restart
    # evictions (the wasted-recompute metric the swap tier drives to 0) —
    # deltas since the previous sample.
    kv_spilled_pages: float = 0.0
    kv_restores: float = 0.0
    recompute_tokens: float = 0.0
    # Split mixed ticks: masked prefill-query rows decode streams did NOT
    # execute because the tick ran as a compacted chunk step + a single-
    # token step ((C-1) x decode streams per split tick) — delta since the
    # previous sample.
    mixed_tick_decode_rows_saved: float = 0.0
    # Prefix sharing: admissions that attached shared prompt pages and
    # prompt tokens whose prefill chunks were skipped entirely (deltas),
    # plus the pool's current shared-page footprint — pages with refcount
    # > 1 and the HBM bytes deduplication is saving right now (gauges).
    kv_prefix_hits: float = 0.0
    prefill_tokens_skipped: float = 0.0
    kv_shared_pages: float = 0.0
    kv_shared_bytes: float = 0.0
    # Speculative decoding: draft tokens proposed / accepted by greedy
    # verification and partial-accept rollbacks (deltas since the previous
    # sample), plus the engine's running acceptance-rate gauge
    # (accepted / drafted over the whole run so far).
    spec_tokens_drafted: float = 0.0
    spec_tokens_accepted: float = 0.0
    spec_rollbacks: float = 0.0
    spec_accept_rate: float = 0.0
    # SLO-tiered admission: requests granted PAST a blocked line head
    # (size-aware bypass, provably without delaying the head) and rounds
    # the wait line spent non-empty — deltas since the previous sample.
    kv_bypass_grants: float = 0.0
    kv_head_wait_ticks: float = 0.0
    # Async swap tier: pages/bytes with a D2H spill issued but not yet
    # fenced (gauges at sample time), decode ticks that ran with at least
    # one transfer outstanding, and fences that actually had to wait
    # (deltas) — the overlap-efficiency surface of the transfer engine.
    kv_spill_inflight_pages: float = 0.0
    kv_spill_inflight_bytes: float = 0.0
    kv_ticks_while_inflight: float = 0.0
    kv_fence_waits: float = 0.0


class PerfCounters:
    def __init__(self, window: int = 64, clock=time.monotonic):
        self._clock = clock
        self._window = window
        self.reset()

    # -- event API ----------------------------------------------------------
    def reset(self):
        self.totals: Dict[str, float] = collections.defaultdict(float)
        self.samples: Deque[StepSample] = collections.deque(maxlen=self._window)
        self._epoch = self._clock()
        self._last_reset = self._clock()

    def add(self, name: str, value: float):
        self.totals[name] += value

    def set(self, name: str, value: float):
        """Gauge semantics: overwrite instead of accumulate (e.g. pool
        occupancy)."""
        self.totals[name] = value

    def record_step(self, *, step_time: float, **fields: float):
        """Append one ``StepSample``; ``fields`` are its named fields (an
        unknown name raises)."""
        sample = StepSample(self._clock(), step_time, **fields)
        self.add("steps", 1)
        for name in ("local_bytes", "remote_bytes", "dcn_bytes", "flops"):
            self.add(name, getattr(sample, name))
        self.samples.append(sample)

    # -- Algorithm 1 inputs ---------------------------------------------------
    def event_counter(self, name: str = "remote_bytes") -> float:
        """Value accumulated since the last ``reset_events`` (Alg.1 line 5)."""
        return self.totals[name] - self.totals.get(name + "__mark", 0.0)

    def reset_events(self, name: str = "remote_bytes"):
        self.totals[name + "__mark"] = self.totals[name]

    def elapsed(self) -> float:
        return self._clock() - self._last_reset

    def mark_time(self):
        self._last_reset = self._clock()

    def snapshot(self) -> Dict[str, float]:
        return dict(self.totals)
