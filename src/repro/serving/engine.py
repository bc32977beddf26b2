"""Serving engine: a continuous-batching TOKEN loop over chiplet-group
replicas, running on the unified GlobalScheduler substrate with an elastic,
paged, chiplet-aware KV allocator.

ARCAS mapping (the paper's runtime, applied to inference):
  * every request is a COROUTINE: an admission task that reserves KV pages
    from its replica's chiplet-group memory domain — parking via ``yield
    BLOCK`` when the pool is exhausted and woken by the pool's free
    callback (allocation failure IS the back-pressure mechanism);
  * every engine tick builds ONE batched model step whose streams are a mix
    of prefill CHUNKS (page-sized slices of prompts scattered into the pool
    page-by-page, so prefill memory is bounded by one chunk regardless of
    prompt length) and single-token decode streams — there is no separate
    prefill phase, just streams at different positions in one loop.  Chunk
    ticks run on one of TWO COMPILED PATHS
    (``EngineConfig(prefill_mode=)``): "parallel" (default) fuses the
    whole chunk into one model forward — intra-chunk causal attention
    against the gathered ring prefix, chunk scans for rgLRU/SSD state —
    so a C-token chunk costs ONE model step; "scan" keeps the per-token
    reference (C sequential steps, bit-identical to single-token
    stepping).  Pure-decode ticks use the single-token step either way;
  * KV reservations are ELASTIC: admission takes only the pages of the
    first chunk plus the state slot, and the table GROWS lazily as ``pos``
    crosses page boundaries.  When a stream's domain is exhausted MID-
    DECODE it parks — suspend at a defined point, resume wherever capacity
    appears — via the same ``yield BLOCK`` / free-callback path admission
    uses, releasing its decode slot to other streams while it waits;
  * KV cache is PAGED (``serving/kvpool.py``): a block pool partitioned per
    chiplet-group domain; a request holds a block table, not a slot in a
    monolithic per-replica array, so short requests reserve only the pages
    they need and ``max_batch`` becomes a scheduling knob instead of a
    memory allocation;
  * the fleet is partitioned into replica groups by the current Layout
    (spread_rate): compact = many small replicas, spread = few big ones;
    each replica group owns ``spread_rate`` pool domains;
  * waiting requests are WORK-STOLEN between replica queues in §4.4 tier
    order (own queue -> neighborhood -> pod -> fleet) via TieredQueues; a
    steal migrates the request's KV reservation into the thief's domain
    (memory follows work — the NUMA-bind discipline), partially-grown
    tables included;
  * the adaptive controller runs LIVE: on a spread-rate change the engine's
    RelayoutHandler rebuilds replica groups MID-RUN — in-flight streams
    (mid-prefill or mid-decode) keep their pool pages and only re-point
    their block tables at the new owner replica of their domain; streams
    rebalanced onto a non-owner replica copy just their *used* pages
    between domains (never whole cache slices), so adaptive and
    non-adaptive runs generate identical tokens;
  * incremental allocation can deadlock (every stream in a domain holding
    pages and needing one more); a ``round_hook`` on the scheduler watches
    for allocation stalls and resolves them up a memory-pressure LADDER:
    admission headroom (keep ``k`` blocks free past the first chunk) makes
    deadlocks rarer, parking absorbs transient pressure, and when the
    watchdog fires the victim's used pages are SPILLED to a host swap tier
    (``evict_mode="swap"``, the default): its device pages go to the
    longest-parked waiter, the table turns host-resident (migrating by
    re-point, zero device copies), and on re-grant the stream restores its
    pages and resumes mid-decode at its saved cursor — zero recomputed
    tokens.  ``evict_mode="restart"`` keeps the PR-3 last resort (also the
    swap mode's fallback when every parked stream is already spilled):
    free the victim and re-run it from scratch, which under greedy
    decoding regenerates the identical tokens at ``recompute_tokens``
    cost;
  * prompt PREFIX SHARING (``EngineConfig(prefix_share=)``, default on for
    lazy ring models): admission hashes the prompt page-by-page and asks
    each candidate domain for the longest chain of already-resident pages;
    a match attaches those pages REFCOUNTED (copy-on-write at ring-wrap)
    and starts prefill at the first unmatched chunk boundary — skipped
    chunks cost zero model steps AND zero fresh pages, so shared-preamble
    tenants admit more concurrent streams from the same byte budget.  The
    skip is computationally identical to resuming a parked stream at a
    chunk boundary, so tokens are bit-identical to the unshared run;
  * an open-loop client coroutine (``open_loop_client``) shares the same
    TaskRuntime and submits requests over time from a seeded schedule, so
    steady-state adaptation and TTFT/TPOT tails are actually exercised.

``EngineConfig(lazy=False)`` keeps the PR-2 eager allocator (full capped
reservation at admission + whole-prompt prefill); ``paged=False`` keeps the
PR-1 slot monolith.  Both ride the same token loop — their streams simply
never have more than one token per tick — and stay token-identical to the
lazy path.

Replica groups are logical queues over the engine's devices
(``ServeEngine(devices=)``, default every visible device): params are
committed to each device (replicated when there are several) and the
pool's pages are sharded across them, while the scheduling, batching,
stealing, paging, growth, controller and migration logic runs host-side.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.controller import ControllerConfig, Decision
from repro.core.counters import span
from repro.core.layout import Layout
from repro.core.scheduler import GlobalScheduler, TieredQueues
from repro.core.tasks import BLOCK, WaitQueue
from repro.core.topology import ChipletTopology
from repro.models import decode as dec
from repro.models.params import init_params
from repro.core.costmodel import kv_bypass_floor_bytes, \
    kv_transfer_seconds, prefill_chunk_bytes, prefill_chunk_score_bytes, \
    spec_rejected_bytes, spec_rollback_bytes
from repro.launch.steps import make_prefill, make_serve_chunk_step, \
    make_serve_step, make_spec_verify_step
from repro.serving.kvpool import KVBlockPool, KVTable, kv_bytes_exact
from repro.serving.spec import make_drafter


@dataclasses.dataclass(frozen=True)
class ClassSLO:
    """Per-request-class service targets + scheduling privileges.

    ``ttft_target``/``tpot_target`` are reporting targets (seconds to
    first token / seconds per output token after the first) the per-class
    latency stats are judged against; ``bypass`` marks the class eligible
    for the size-aware admission bypass — a grant past a blocked line
    head, allowed only under the provable no-delay bound."""
    ttft_target: float = math.inf
    tpot_target: float = math.inf
    bypass: bool = False


#: The default two-tier mix: latency-sensitive ``interactive`` requests
#: may bypass (their small footprints are exactly what fits the safety
#: bound); throughput ``batch`` requests — the submit() default — never
#: do, so single-class workloads keep the strict-FIFO grant order and
#: every pre-existing counter baseline.
DEFAULT_SLO_CLASSES: Dict[str, ClassSLO] = {
    "interactive": ClassSLO(ttft_target=0.5, tpot_target=0.05, bypass=True),
    "batch": ClassSLO(),
}


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # (S,) int32
    max_new: int
    arrived: float = 0.0
    group: Optional[int] = None
    generated: List[int] = dataclasses.field(default_factory=list)
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    migrations: int = 0                 # relayouts survived while in flight
    table: Optional[KVTable] = None     # paged mode: KV pages + state slot
    prefix_tokens: int = 0              # prompt tokens served from shared
                                        # prefix pages (prefill starts here)
    cls: str = "batch"                  # SLO class (EngineConfig.slo_classes)
    bypassed: bool = False              # granted past a blocked line head
    wq_seq: Optional[int] = None        # wait-line seq drawn at submit; a
                                        # BYPASSED stream that parks later
                                        # re-enters at this arrival position
    grant_rounds: List[int] = dataclasses.field(default_factory=list)
                                        # engine round of every page grant
                                        # (admission, regrow, restore) — the
                                        # no-starvation gates compare these
    arrive_round: int = 0               # engine round at submit: with
                                        # grant_rounds this gives a
                                        # deterministic (round-based)
                                        # admission-delay metric
    page_keys: Optional[List[bytes]] = dataclasses.field(
        default=None, repr=False, compare=False)  # prompt hash chain
    _kv_fn: Optional[Callable[[int], float]] = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def done(self) -> bool:
        return self.t_done is not None

    def kv_bytes(self) -> float:
        """KV footprint moved when this request changes groups.  Exact
        (costmodel-derived per-token bytes) when the engine installed its
        calculator; the seed's rough 2-bytes/token estimate otherwise."""
        tokens = len(self.prompt) + len(self.generated)
        if self._kv_fn is not None:
            return self._kv_fn(tokens)
        return float(tokens * 2)


@dataclasses.dataclass
class EngineConfig:
    max_batch: int = 8                 # decode slots per replica group
    max_len: int = 256
    adaptive: bool = True
    paged: bool = True                 # paged KV block pool (default) vs
                                       # the legacy slot-monolith cache
    lazy: bool = True                  # elastic reservations + chunked
                                       # prefill (False = PR-2 eager mode)
    block_tokens: int = 16             # ring tokens per KV page
    prefill_chunk: Optional[int] = None  # prompt tokens per prefill chunk;
                                         # default: one KV page
    prefill_mode: str = "parallel"     # chunk-tick compiled path: "parallel"
                                       # fuses the whole chunk into ONE
                                       # model forward (intra-chunk causal
                                       # attention + chunk scans for
                                       # rgLRU/SSD state); "scan" keeps the
                                       # PR-3 per-token reference (C
                                       # sequential model steps per chunk,
                                       # bit-identical to single-token
                                       # stepping)
    chunk_kernel: str = "blocked"      # fused-path attention: "blocked"
                                       # streams KV in (block_q, block_kv)
                                       # tiles through the Pallas online-
                                       # softmax ring kernel; "dense" keeps
                                       # the (C, W+C) einsum reference
    split_ticks: bool = True           # mixed ticks run TWO compiled steps
                                       # (a compacted fused chunk forward
                                       # for prefill streams + the single-
                                       # token step for decode streams) so
                                       # decode streams stop paying C-1
                                       # masked query rows; False keeps the
                                       # PR-5 one-step mixed tick
    pool_streams: Optional[int] = None  # per-DOMAIN budget, expressed as
                                        # full-length streams (monolith
                                        # equivalence); default max_batch
    stall_evict_rounds: int = 6        # allocation-stall rounds before the
                                       # deadlock breaker evicts a stream
    evict_mode: str = "swap"           # stall-watchdog policy: "swap" spills
                                       # the victim's used pages to the host
                                       # tier and resumes it mid-decode on
                                       # re-grant (zero recompute); "restart"
                                       # keeps the PR-3 recompute-from-
                                       # scratch eviction
    headroom: int = 0                  # lazy admission guard: grant only
                                       # when the domain keeps this many
                                       # free blocks AFTER the first chunk
                                       # (k=0 = unguarded PR-3 behavior)
    prefix_share: bool = True          # hash-matched prefix caching: new
                                       # requests attach refcounted shared
                                       # KV pages for prompt pages already
                                       # resident in their domain and skip
                                       # the matched prefill chunks; pages
                                       # copy-on-write at ring-wrap.  Only
                                       # active on the lazy paged path for
                                       # models with ring pages
    spec_decode: str = "off"           # speculative decoding: "ngram"
                                       # drafts up to spec_k tokens per
                                       # decode tick from the stream's own
                                       # committed tokens and verifies them
                                       # in ONE fused chunk forward (greedy
                                       # acceptance -> token-identical to
                                       # "off" by construction).  Lazy
                                       # paged path only; deliberately off
                                       # by default so the non-speculative
                                       # counter gates keep their exact
                                       # baselines — flip per run/workload
    spec_k: int = 4                    # max draft tokens per tick
    spec_ngram: int = 3                # longest n-gram the prompt-lookup
                                       # drafter matches on
    cached_retention: str = "access"   # cached prefix-page reclaim order:
                                       # "access" evicts the coldest page
                                       # by last-hit recency, "blind" the
                                       # PR-7 free-list order
    slo_classes: Dict[str, ClassSLO] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_SLO_CLASSES))
                                       # request classes submit() accepts;
                                       # unknown names fail fast
    slo_bypass: bool = True            # size-aware bypass: a bypass-class
                                       # request may be granted past a
                                       # PARKED line head when its charged
                                       # pages fit under the head's provable
                                       # need (never delays the head); off
                                       # = strict FIFO even for bypass
                                       # classes
    slo_aging_rounds: int = 200        # bypass fairness backstop: bypass is
                                       # suspended while ANY waiter ahead of
                                       # the candidate has been blocked
                                       # longer than this many rounds — the
                                       # line drains strictly FIFO until the
                                       # aged waiter is granted
    spill_watermarks: Optional[Tuple[float, float]] = None
                                       # (high, low) per-domain occupancy
                                       # marks for PROACTIVE spill of the
                                       # coldest parked stream BEFORE the
                                       # stall watchdog fires; hysteresis:
                                       # a domain that spilled at high
                                       # re-arms only under low.  None =
                                       # watchdog-only (the PR-4 ladder)
    async_swap: bool = False           # overlap spills behind the token
                                       # loop: the pressure ladder ISSUES
                                       # the D2H copy and keeps ticking,
                                       # landing it (and re-granting the
                                       # victim's pages) at a later poll;
                                       # fences only on shutdown, relayout
                                       # or a genuinely stalled watchdog.
                                       # False = the PR-4 synchronous
                                       # spill (issue + immediate fence,
                                       # byte-identical payload)
    controller: ControllerConfig = dataclasses.field(
        default_factory=lambda: ControllerConfig(
            scheduler_timer=8, threshold=4.0, min_dwell=2))


@dataclasses.dataclass
class _InFlight:
    """A mid-generation stream harvested from a retired replica group (or
    a mid-decode park).  ``cache`` carries the KV slice only in legacy
    (slot-monolith) mode; in paged mode the KV stays in the pool and only
    the table pointer moves.  ``pos`` < len(prompt) means the stream was
    harvested mid-PREFILL: it resumes at the next chunk boundary."""
    req: Request
    cache: Any
    pos: int
    token: int


@dataclasses.dataclass
class _Parked:
    """A stream suspended MID-DECODE because its domain could not grow its
    table.  It holds its pages (and its place in the engine's FIFO wait
    line) but not a decode slot; ``_regrow_task`` resumes it."""
    req: Request
    pos: int
    token: int
    seq: int                            # park order (eviction prefers max)
    cell: Dict[str, Any] = dataclasses.field(default_factory=dict)
    evicted: bool = False


class _Group:
    """One replica group: decode slots (+ its own cache pool in legacy
    mode; in paged mode KV lives in the engine's KVBlockPool).

    ``queue`` is the group's deque inside the engine's TieredQueues;
    ``resume`` holds migrated in-flight streams awaiting a free slot;
    ``retired`` marks groups dissolved by a relayout (their coroutine exits
    at its next yield point).  ``pos_h``/``tok_h`` are the host-side view
    of every slot's stream cursor: absolute position of the next token to
    process and the last emitted token.
    """

    def __init__(self, gid: int, pod: int, cfg: ModelConfig, params,
                 ecfg: EngineConfig, queue, domains: List[int]):
        self.gid = gid
        self.pod = pod
        self.cfg = cfg
        self.params = params
        self.ecfg = ecfg
        self.queue = queue
        self.domains = domains          # chiplet-group pool domains owned
        self.resume: List[_InFlight] = []
        self.retired = False
        self.slots: List[Optional[Request]] = [None] * ecfg.max_batch
        self.cache = (None if ecfg.paged
                      else dec.init_cache(cfg, ecfg.max_batch, ecfg.max_len))
        self.pos_h = np.zeros((ecfg.max_batch,), np.int32)
        self.tok_h = np.zeros((ecfg.max_batch,), np.int32)
        self.steps = 0

    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def busy(self) -> bool:
        return (bool(self.queue) or bool(self.resume)
                or any(s is not None for s in self.slots))

    def kv_pressure(self) -> float:
        used = sum(1 for s in self.slots if s is not None)
        return used / max(1, len(self.slots))


class ServeEngine:
    def __init__(self, cfg: ModelConfig, topology: ChipletTopology,
                 ecfg: EngineConfig = EngineConfig(), *, seed: int = 0,
                 spread_rate: int = 1, devices=None):
        self.cfg = cfg
        self.topology = topology
        self.ecfg = ecfg
        self.sched = GlobalScheduler(
            topology, ecfg.controller, spread_rate=spread_rate,
            control_enabled=ecfg.adaptive)
        # compat aliases: the scheduler owns these now
        self.counters = self.sched.counters
        self.controller = self.sched.controller
        self.runtime = self.sched.tasks
        self.devices = list(devices if devices is not None
                            else jax.devices())
        self.params = dec.place_params(
            init_params(cfg, jax.random.PRNGKey(seed)), self.devices)
        self._prefill = jax.jit(make_prefill(cfg, max_len=ecfg.max_len))
        self._decode = jax.jit(make_serve_step(cfg))
        self._rid = itertools.count()
        self._clock = time.monotonic
        self._running = False
        self._inflight = 0              # submitted, not yet done
        self._clients = 0               # active open-loop client coroutines
        self.submitted: List[Request] = []
        self.relayouts: List[Dict] = []
        self.pool: Optional[KVBlockPool] = None
        self._lazy = ecfg.paged and ecfg.lazy
        self._async = bool(ecfg.paged and ecfg.async_swap)
        if ecfg.evict_mode not in ("swap", "restart"):
            raise ValueError(f"unknown evict_mode {ecfg.evict_mode!r}")
        if ecfg.prefill_mode not in ("parallel", "scan"):
            raise ValueError(f"unknown prefill_mode {ecfg.prefill_mode!r}")
        if ecfg.chunk_kernel not in ("blocked", "dense"):
            raise ValueError(f"unknown chunk_kernel {ecfg.chunk_kernel!r}")
        if ecfg.spec_decode not in ("off", "ngram"):
            raise ValueError(f"unknown spec_decode {ecfg.spec_decode!r}")
        self._prefill_mode = ecfg.prefill_mode if self._lazy else "scan"
        self._chunk_kernel = (ecfg.chunk_kernel
                              if self._prefill_mode == "parallel" else "dense")
        self._parked: Dict[int, _Parked] = {}
        self._park_seq = itertools.count()
        self._progress_mark = -1.0
        self._stall_rounds = 0
        self._round = 0                 # scheduler rounds seen (_stall_hook)
        self._head_id: Optional[int] = None   # current line-head task id and
        self._head_wait = 0                   # rounds it has sat blocked there
        if not ecfg.slo_classes:
            raise ValueError("slo_classes must name at least one class")
        # size-aware bypass bookkeeping: round each waiter joined the line
        # (the aging backstop's clock) and the waiting admission cells of
        # bypass-eligible classes (targeted wakes — non-head waiters only
        # retry when a bypass could actually have opened)
        # _bypass_wake: bypass-class waiters are WOKEN on frees/grants (so a
        # ``slo_bypass=False`` twin steps task-for-task with the bypass
        # engine until the first actual bypass grant — the no-starvation
        # comparison is exact, not cadence-polluted); _bypass_on gates the
        # GRANTS themselves
        self._bypass_wake = bool(ecfg.paged
                                 and any(c.bypass
                                         for c in ecfg.slo_classes.values()))
        self._bypass_on = bool(self._bypass_wake and ecfg.slo_bypass)
        self._wait_round: Dict[int, int] = {}
        self._bypass_cells: Dict[int, Dict[str, Any]] = {}
        # every bypass grant as (round, granted rid, jumped head rid)
        self.bypass_log: List[Tuple[int, int, int]] = []
        if ecfg.paged:
            streams = ecfg.pool_streams or ecfg.max_batch
            budget = KVBlockPool.blocks_for_streams(
                cfg, ecfg.max_len, streams, ecfg.block_tokens)
            self.pool = KVBlockPool(
                cfg, n_domains=topology.total_groups, max_len=ecfg.max_len,
                block_tokens=ecfg.block_tokens, counters=self.counters,
                retention=ecfg.cached_retention, topology=topology,
                devices=self.devices, **budget)
            self.waiters = WaitQueue(self.runtime)
            # wake ONE waiter per free: grants stay FIFO (a successful
            # admission cascades the wake to the next waiter itself).
            # Bypass-eligible waiters are additionally woken — they are
            # allowed to attempt a grant without being the head
            self.pool.on_free(self._on_pool_free)
            if ecfg.spill_watermarks is not None:
                self.pool.set_watermarks(*ecfg.spill_watermarks)
            # donate the pool storage: the scatter-back updates in place
            # instead of copying the whole fleet's blocks every tick
            self._paged_decode = jax.jit(
                dec.make_paged_decode(cfg, self.pool.spec),
                donate_argnums=(1,))
            self._decode_in_place = dec.decode_writes_in_place(
                cfg, self.pool.spec)
            self._commit_prefill = jax.jit(self._make_commit_prefill(),
                                           donate_argnums=(0,))
            ml = ecfg.max_len
            self._kv_fn = lambda n: kv_bytes_exact(cfg, n, ml)
            # prefix sharing needs elastic tables (the skip resumes at a
            # chunk boundary exactly like a restored park) and ring pages
            # to share; eager and pure-state models run unshared
            self._share = (self._lazy and ecfg.prefix_share
                           and self.pool.pages_per_stream > 0)
            # prefill chunk: one KV page by default (ring models), the
            # configured page size for pure-state models (no ring pages)
            self._chunk = ecfg.prefill_chunk or (
                self.pool.block_tokens if self.pool.pages_per_stream
                else ecfg.block_tokens)
            # no C <= W clamp: the fused forward handles chunks wider than
            # the ring (attention masks each query to its surviving span,
            # the cache write keeps the last W active tokens)
            if self._lazy:
                self._paged_chunk = jax.jit(
                    self._make_paged_chunk(self._prefill_mode),
                    donate_argnums=(1,))
            # speculative decoding rides the lazy chunk path: drafted
            # decode streams become small-chunk rows verified through an
            # all-position-logits variant of the same fused forward
            self._spec = self._lazy and ecfg.spec_decode != "off" \
                and ecfg.spec_k > 0 and self._chunk > 1
            if self._spec:
                self.drafter = make_drafter(ecfg.spec_decode,
                                            ngram=ecfg.spec_ngram)
                # pure-spec ticks run at this narrow width; ticks that
                # also carry a prefill chunk reuse the full chunk width
                self._spec_w = min(ecfg.spec_k + 1, self._chunk)
                self._paged_spec = jax.jit(
                    self._make_paged_spec(self._prefill_mode),
                    donate_argnums=(1,))
            else:
                self.drafter = None
        else:
            self._kv_fn = None
            self._decode_in_place = False
            self._chunk = 1
            self._share = False
            self._spec = False
            self.drafter = None
        self._build_groups()
        self.sched.register_relayout(self._relayout)

    # ------------------------------------------------------------------
    def _domains_of(self, gid: int, lay: Layout) -> List[int]:
        """Chiplet-group pool domains a replica group spans (Algorithm 2's
        contiguous-group affinity)."""
        rpp = lay.replicas_per_pod
        pod, local = divmod(gid, rpp)
        s = lay.spread_rate
        base = pod * self.topology.groups_per_pod + local * s
        return list(range(base, base + s))

    def _build_groups(self):
        lay = self.sched.layout()
        rpp = lay.replicas_per_pod
        pods = [g // rpp for g in range(lay.replicas)]
        # neighborhood tier: adjacent replica pairs inside a pod share
        # 1-hop ICI spans; only meaningful when a pod holds >1 replica
        hoods = ([(p, (g % rpp) // 2) for g, p in enumerate(pods)]
                 if rpp > 1 else None)
        self.queues = TieredQueues(pods, neighborhoods=hoods,
                                   counters=self.counters,
                                   bytes_fn=Request.kv_bytes)
        self.groups = [_Group(g, pods[g], self.cfg, self.params, self.ecfg,
                              self.queues.queue(g), self._domains_of(g, lay))
                       for g in range(lay.replicas)]

    def _owner_group(self, domain: int) -> "_Group":
        for g in self.groups:
            if domain in g.domains:
                return g
        raise KeyError(domain)

    def _domain_order(self, g: _Group) -> List[int]:
        """A group's domains, most-capacity first (blocks are the scarce
        resource when the model has ring pages; state slots otherwise)."""
        assert self.pool is not None
        return sorted(g.domains,
                      key=lambda d: (-self.pool.free_blocks(d),
                                     -self.pool.free_states(d), d))

    def _try_admit(self, total_tokens: int, first_tokens: Optional[int],
                   keys: Optional[List[bytes]] = None, prompt_len: int = 0
                   ) -> Tuple[Optional["_Group"], Optional[KVTable]]:
        """Sweep every group (least-pressured first) and every domain it
        owns; one logical alloc failure only when the whole pool is dry.
        Lazy admissions keep ``headroom`` blocks free in the granting
        domain so growth of in-flight streams is less likely to close the
        incremental-allocation deadlock.

        With ``keys`` (the prompt's page hash chain), candidate domains are
        re-ranked by matched prefix length FIRST: a domain already holding
        the prompt's pages admits the request onto shared refcounted pages
        and charges only the unshared tail — both fewer pages AND fewer
        prefill chunks.  Ties fall back to the pressure order."""
        headroom = self.ecfg.headroom if self._lazy else 0
        cands = [(g, d)
                 for g in sorted(self.groups,
                                 key=lambda gr: (gr.kv_pressure(),
                                                 len(gr.queue), gr.gid))
                 for d in self._domain_order(g)]
        matches: Dict[int, Tuple[List[int], int]] = {}
        if keys:
            matches = {d: self.pool.match_prefix(d, keys,
                                                 prompt_len=prompt_len)
                       for _, d in cands}
            # stable sort: longest match first, pressure order inside ties
            cands.sort(key=lambda gd: -len(matches[gd[1]][0]))
        for g, d in cands:
            shared, ckpt = matches.get(d, ((), 0))
            first = first_tokens
            if shared:
                # the skip moves the first chunk past the shared pages
                skip = len(shared) * self.pool.block_tokens
                first = skip + min(self._chunk, max(1, prompt_len - skip))
            table = self.pool.reserve(d, total_tokens,
                                      first_tokens=first,
                                      headroom=headroom,
                                      count_failure=False,
                                      prefix_blocks=shared,
                                      prefix_state=ckpt)
            if table is not None:
                return g, table
        self.counters.add("kv_alloc_failures", 1)
        return None, None

    def _migrate_into(self, table: KVTable, g: _Group) -> bool:
        """Move a reservation into any of the group's domains."""
        if table.domain in g.domains:
            return True
        return any(self.pool.migrate(table, d) for d in self._domain_order(g))

    # -- size-aware bypass (PR 9): grant past a blocked head, provably free --
    def _head_rec(self) -> Optional[_Parked]:
        """The line head's park record — None when the head is an
        ADMISSION task.  Bypass only ever jumps a PARKED head: a blocked
        admission can be served from any domain, so every page in the
        pool is a page it might need and no provable slack exists; a
        parked stream's need is pinned to specific domains, leaving the
        rest of the pool provably useless to it."""
        head = self.waiters.oldest()
        if head is None:
            return None
        for rec in self._parked.values():
            if rec.cell.get("task") is head:
                return rec
        return None

    def _head_need_in(self, rec: _Parked, d: int
                      ) -> Optional[Tuple[int, bool]]:
        """``(pages, needs_state)``: the blocked head's PROVABLE need from
        domain ``d`` — the free-block floor a bypass grant in ``d`` must
        leave behind so the head's time-to-grant cannot be delayed.

        A spilled head restores anywhere: its floor is its host pages
        plus next-chunk growth (and a state slot) in EVERY domain.  A
        parked grower is pinned: its own domain owes the next-chunk
        pages, its replica group's other domains owe a whole-table
        migrate, and domains OUTSIDE its group owe NOTHING — growth and
        migration never leave the group, so those domains' pages are
        provably useless to the head.  That last case is the bypass
        window this whole mechanism exists for."""
        t = rec.req.table
        if t.spill is not None:
            n, _ = self._next_chunk_need(rec.req, rec.pos)
            grow = max(0, self.pool.pages_needed(rec.pos + n)
                       - t.spill.pages)
            return t.spill.pages + grow, t.spill.had_state
        n, need = self._next_chunk_need(rec.req, rec.pos)
        need = max(need, 0)
        if d == t.domain:
            return need, False
        g = self._owner_group(t.domain)
        if d in g.domains:
            return len(t.blocks) + need, False
        return 0, False

    def _aging_clear(self, task) -> bool:
        """The bypass fairness backstop: True when no waiter AHEAD of
        ``task`` has been blocked longer than ``slo_aging_rounds`` —
        otherwise bypass is suspended and the line drains strictly FIFO
        until the aged waiter is granted.  (The head itself is protected
        by the safety bound; this bounds how long anyone else can be
        repeatedly jumped.)"""
        limit = self.ecfg.slo_aging_rounds
        my = self.waiters.seq_of(task)
        if my is None:
            return False
        for t in self.waiters.tasks():
            if self.waiters.seq_of(t) >= my:
                return True             # reached ourselves: all clear
            if self._round - self._wait_round.get(t.id, self._round) > limit:
                return False
        return True

    def _try_bypass(self, req: Request, total_tokens: int
                    ) -> Tuple[Optional["_Group"], Optional[KVTable]]:
        """Attempt a size-aware bypass grant for a non-head waiter.

        The reservation is EAGER (full cap pages up front, minus
        prefix-match credit) even on the lazy path: a bypassed stream
        never grows, so its footprint can never later eat into frees the
        head is waiting for — the no-delay bound is checked once, at
        grant time, and stays true.  Per candidate domain the grant must
        keep ``head_need`` free blocks (reserve's unclamped ``min_free``
        floor) and, for a spilled hybrid head, a second state slot."""
        rec = self._head_rec()
        if rec is None or rec.req.table is None:
            return None, None
        cands = [(g, d)
                 for g in sorted(self.groups,
                                 key=lambda gr: (gr.kv_pressure(),
                                                 len(gr.queue), gr.gid))
                 for d in self._domain_order(g)]
        matches: Dict[int, Tuple[List[int], int]] = {}
        if req.page_keys:
            matches = {d: self.pool.match_prefix(d, req.page_keys,
                                                 prompt_len=len(req.prompt))
                       for _, d in cands}
            cands.sort(key=lambda gd: -len(matches[gd[1]][0]))
        headroom = self.ecfg.headroom if self._lazy else 0
        for g, d in cands:
            bound = self._head_need_in(rec, d)
            if bound is None:
                continue
            hn, head_state = bound
            if (head_state and self.pool.has_state
                    and self.pool.free_states(d) < 2):
                continue                # the head's restore slot is not ours
            shared, ckpt = matches.get(d, ((), 0))
            table = self.pool.reserve(d, total_tokens,
                                      first_tokens=None,  # eager: no growth
                                      headroom=headroom,
                                      min_free=hn,
                                      count_failure=False,
                                      prefix_blocks=shared,
                                      prefix_state=ckpt)
            if table is not None:
                self.counters.add("kv_bypass_floor_pages", hn)
                # (round, granted rid, jumped head rid): the no-starvation
                # gates compare the FIRST entry's head across bypass-on/off
                # twins — dynamics are identical up to that round
                self.bypass_log.append((self._round, req.rid, rec.req.rid))
                return g, table
        return None, None

    # -- submission ---------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new: int,
               cls: str = "batch") -> Request:
        if cls not in self.ecfg.slo_classes:
            raise ValueError(
                f"unknown SLO class {cls!r}: configured classes are "
                f"{sorted(self.ecfg.slo_classes)}")
        req = Request(next(self._rid), np.asarray(prompt, np.int32), max_new,
                      arrived=self._clock(), cls=cls,
                      arrive_round=self._round)
        req._kv_fn = self._kv_fn
        self._inflight += 1
        self.submitted.append(req)
        self.counters.add(f"kv_class_submits/{cls}", 1)
        if not self.ecfg.paged:
            # legacy: route straight to the least-pressured group's queue
            g = min(self.groups,
                    key=lambda gr: (gr.kv_pressure(), len(gr.queue)))
            req.group = g.gid
            self.queues.push(g.gid, req)
            return req
        cell: Dict[str, Any] = {"req": req}
        cell["task"] = self.sched.spawn(
            self._admission_task(req, cell), name=f"admit{req.rid}",
            priority=1)
        # join the FIFO wait line AT SUBMIT TIME: grant order is submission
        # order, not coroutine execution order (workers pop LIFO, so a
        # burst of arrivals would otherwise be admitted newest-first — and
        # could starve a stream parked mid-decode before they arrived)
        req.wq_seq = self._join_line(cell["task"])
        if self._bypass_wake and self.ecfg.slo_classes[cls].bypass:
            self._bypass_cells[cell["task"].id] = cell
        return req

    # -- wait-line bookkeeping (size-aware bypass, PR 9) --------------------
    def _join_line(self, task, seq: Optional[int] = None) -> int:
        s = self.waiters.park(task, seq=seq)
        self._wait_round.setdefault(task.id, self._round)
        return s

    def _leave_line(self, task):
        """Grant-time cleanup + wake cascade: the next head retries, and
        bypass-eligible waiters get a shot too (a grant may have changed
        the head — and with it the safety bound)."""
        self.waiters.remove(task)
        self._wait_round.pop(task.id, None)
        self._bypass_cells.pop(task.id, None)
        self.waiters.wake(1)            # maybe the next waiter fits too
        self._wake_bypassers()

    def _on_pool_free(self):
        self.waiters.wake(1)
        self._wake_bypassers()

    def _wake_bypassers(self):
        for cell in self._bypass_cells.values():
            self.runtime.unblock(cell["task"])

    def _admission_task(self, req: Request, cell: Dict[str, Any]):
        """Per-request coroutine: reserve KV pages, sweeping groups by
        pressure; park on pool exhaustion until a free wakes us.

        Grants are FIFO across admissions AND mid-decode growers: every
        admission is in the wait line from submit time and only the line
        HEAD attempts a reservation, waiters stay in the line until their
        reservation is GRANTED, and a successful admission cascades the
        wake to the next waiter (frees wake exactly one task).

        ONE exception (PR 9, size-aware bypass): a bypass-class request
        may be granted while NOT the head — but only past a PARKED head,
        only in a domain where the grant provably leaves the head's whole
        restore/grow need free (``_try_bypass``), and only while no
        waiter ahead of it has aged past the fairness backstop.  The
        head's time-to-grant is untouched by construction: strict FIFO
        order is relaxed exactly where relaxing it is free."""
        total = len(req.prompt) + req.max_new
        # lazy: only the first chunk's pages are committed at admission
        first = (min(self._chunk, max(1, len(req.prompt)))
                 if self._lazy else None)
        while True:
            with span("arcas.admit", rid=req.rid):
                if self._share and req.page_keys is None:
                    req.page_keys = self.pool.prefix_keys(req.prompt)
                table = None
                if self.waiters.oldest() is cell["task"]:
                    g, table = self._try_admit(total, first, req.page_keys,
                                               len(req.prompt))
                elif (self._bypass_on
                        and cell["task"].id in self._bypass_cells
                        and self._aging_clear(cell["task"])):
                    g, table = self._try_bypass(req, total)
                    if table is not None:
                        req.bypassed = True
                        self.counters.add("kv_bypass_grants", 1)
                        self.counters.add(f"kv_class_bypass/{req.cls}", 1)
                if table is not None:
                    self._leave_line(cell["task"])
                    req.grant_rounds.append(self._round)
                    self.counters.add(f"kv_class_admits/{req.cls}", 1)
                    req.table = table
                    # shared prefix pages are already filled: prefill
                    # resumes at the first unmatched chunk boundary
                    # (identical to a restored park)
                    req.prefix_tokens = (table.used_pages
                                         * self.pool.block_tokens)
                    req.group = g.gid
                    self.queues.push(g.gid, req)
                    return
            yield BLOCK                 # woken by KVBlockPool.free (heads
                                        # + bypass candidates) or a grant

    def open_loop_client(self, schedule: Iterable[Tuple[int, np.ndarray, int]]
                         ) -> Any:
        """Spawn an open-loop client on the shared TaskRuntime.

        ``schedule`` yields ``(gap_rounds, prompt, max_new)`` or
        ``(gap_rounds, prompt, max_new, cls)``: the client sleeps
        ``gap_rounds`` engine rounds (cooperative yields), then submits —
        arrivals over time instead of an up-front queue, so the controller
        sees steady-state load and tail latencies are real.  The optional
        4th element tags the arrival's SLO class (default ``"batch"``).
        """
        self._clients += 1

        def client():
            try:
                for item in schedule:
                    gap, prompt, max_new = item[0], item[1], item[2]
                    cls = item[3] if len(item) > 3 else "batch"
                    for _ in range(int(gap)):
                        yield
                    self.submit(prompt, max_new, cls=cls)
            finally:
                self._clients -= 1

        return self.sched.spawn(client(), name="client", priority=2)

    # -- live relayout: merge/split replica groups mid-run -------------------
    def _relayout(self, new_layout: Layout, decision: Decision):
        old_groups = self.groups
        if new_layout.replicas == len(old_groups):
            return
        if self.pool is not None:
            # quiesce the transfer engine: tables must not be harvested or
            # re-pointed with a D2H copy still on the wire
            self.pool.drain()
        # harvest in-flight streams and queued requests from the dissolving
        # groups; in paged mode KV stays in the pool (tables move, data
        # does not — except used pages of rebalanced streams).  Streams
        # harvested mid-prefill carry just their position: their next chunk
        # resumes on the new owner.  Mid-decode PARKED streams need no
        # harvesting at all — their regrow task re-resolves the owner group
        # of their domain when it wakes.
        inflight: List[_InFlight] = []
        queued: List[Request] = []
        mig0 = self.counters.totals.get("kv_blocks_migrated", 0.0)
        for g in old_groups:
            g.retired = True
            for slot, req in enumerate(g.slots):
                if req is None:
                    continue
                if self.ecfg.paged:
                    one = None
                else:
                    one = jax.tree.map(lambda p: p[:, slot], g.cache)
                inflight.append(_InFlight(req, one, int(g.pos_h[slot]),
                                          int(g.tok_h[slot])))
                g.slots[slot] = None
                # counted per slot-harvest so each migration pairs with
                # exactly one restore; resume-backlog streams below were
                # already counted on their first hop
                self.counters.add("kv_slots_migrated", 1)
                self.counters.add("migration_bytes", req.kv_bytes())
            inflight.extend(g.resume)
            g.resume = []
            while g.queue:
                queued.append(g.queue.popleft())
        self._build_groups()
        n = len(self.groups)
        if self.ecfg.paged:
            # tables follow their domain's new owner; only streams
            # rebalanced off the owner copy their used pages cross-domain
            cap = max(1, math.ceil(len(inflight) / n))
            load = {g.gid: 0 for g in self.groups}
            for fl in inflight:
                tgt = self._owner_group(fl.req.table.domain)
                if load[tgt.gid] >= cap:
                    alt = min(self.groups,
                              key=lambda gr: (load[gr.gid], gr.gid))
                    if alt is not tgt and self._migrate_into(fl.req.table,
                                                            alt):
                        tgt = alt
                fl.req.group = tgt.gid
                fl.req.migrations += 1
                load[tgt.gid] += 1
                tgt.resume.append(fl)
            for req in queued:
                tgt = self._owner_group(req.table.domain)
                req.group = tgt.gid
                self.queues.push(tgt.gid, req)
        else:
            for i, fl in enumerate(inflight):
                tgt = self.groups[i % n]
                fl.req.group = tgt.gid
                fl.req.migrations += 1
                tgt.resume.append(fl)
            for i, req in enumerate(queued):
                tgt = self.groups[i % n]
                req.group = tgt.gid
                self.queues.push(tgt.gid, req)
        self.relayouts.append({
            "step": decision.step, "old_groups": len(old_groups),
            "new_groups": n, "moved_slots": len(inflight),
            "requeued": len(queued), "reason": decision.reason,
            "blocks_migrated": self.counters.totals.get(
                "kv_blocks_migrated", 0.0) - mig0})
        if self._running:
            for g in self.groups:
                self._spawn_group(g)

    # -- paged device-side step builders -------------------------------------
    def _make_paged_chunk(self, mode: str = "scan"):
        """The continuous-batching mixed step: prefill chunks and decode
        streams share one gather -> chunked-masked step -> scatter.
        ``mode="parallel"`` compiles the fused multi-token forward (one
        model pass per tick); "scan" the per-token reference."""
        spec = self.pool.spec
        step = dec.replicate_over(
            make_serve_chunk_step(self.cfg, spec, mode=mode,
                                  chunk_kernel=self._chunk_kernel),
            self.devices)

        def paged_chunk(params, storage, tables, state_slots, tokens, pos,
                        n_tokens):
            view = dec.gather_cache_view(storage, spec, tables, state_slots)
            logits, view = step(params, view, tokens, pos, n_tokens)
            storage = dec.scatter_cache_view(storage, spec, tables,
                                             state_slots, view)
            return logits, storage

        return paged_chunk

    def _make_paged_spec(self, mode: str = "scan"):
        """The speculative VERIFY step: same gather -> masked chunk forward
        -> scatter as ``_make_paged_chunk`` but returning the logits after
        EVERY fed token (B, C, V), so greedy acceptance can compare each
        draft against the argmax one position earlier.  The cache commits
        optimistically; the host rolls back rejected suffixes from the
        pool's page checkpoints."""
        spec = self.pool.spec
        step = dec.replicate_over(
            make_spec_verify_step(self.cfg, spec, mode=mode,
                                  chunk_kernel=self._chunk_kernel),
            self.devices)

        def paged_spec(params, storage, tables, state_slots, tokens, pos,
                       n_tokens):
            view = dec.gather_cache_view(storage, spec, tables, state_slots)
            logits, view = step(params, view, tokens, pos, n_tokens)
            storage = dec.scatter_cache_view(storage, spec, tables,
                                             state_slots, view)
            return logits, storage

        return paged_spec

    def _make_commit_prefill(self):
        spec = self.pool.spec

        def commit(storage, tables, state_slots, cache1):
            return dec.scatter_cache_view(storage, spec, tables,
                                          state_slots, cache1)

        return commit

    def _table_row(self, req: Optional[Request]) -> Tuple[List[int], int]:
        """Null-padded (pages, state_slot) row for the gather indices.
        Partially-grown tables pad their unallocated tail with the null
        block — those ring positions are past ``pos`` and never read."""
        P = self.pool.pages_per_stream
        if req is None or req.table is None:
            return [0] * P, 0
        t = req.table
        return t.blocks + [0] * (P - len(t.blocks)), t.state_slot

    def _group_indices(self, g: _Group) -> Tuple[jnp.ndarray, jnp.ndarray]:
        rows, slots = zip(*(self._table_row(r) for r in g.slots))
        P = self.pool.pages_per_stream
        tables = jnp.asarray(
            np.asarray(rows, np.int32).reshape(len(g.slots), P))
        return tables, jnp.asarray(np.asarray(slots, np.int32))

    # -- elastic growth / mid-decode parking ---------------------------------
    def _next_chunk_need(self, req: Request, pos: int) -> Tuple[int, int]:
        """(tokens the stream consumes next tick, pages its table is short
        by) — the single definition both the tick's growth phase and a
        parked stream's regrow retry must agree on."""
        S = len(req.prompt)
        n = min(self._chunk, S - pos) if pos < S else 1
        need = self.pool.pages_needed(pos + n) - len(req.table.blocks)
        return n, need

    def _grow_stream(self, req: Request, g: _Group, need: int,
                     forks: Tuple[int, ...] = ()) -> bool:
        """Commit ``need`` more pages for a stream — and privatize (CoW)
        any shared pages its next write touches — its own domain first,
        then any domain its replica group owns (migrating the used pages —
        memory follows the stream's placement, never the reverse; a
        migration COPIES every page, so the moved table is private and the
        pending forks dissolve)."""
        t = req.table
        if (all(self.pool.cow_fork(t, p) for p in forks)
                and self.pool.grow(t, need)):
            return True
        for d in self._domain_order(g):
            if d == t.domain:
                continue
            if self.pool.free_blocks(d) < len(t.blocks) + need:
                continue
            if self.pool.migrate(t, d) and self.pool.grow(t, need):
                return True
        return False

    def _park_stream(self, g: _Group, slot: int):
        """Suspend a stream MID-DECODE: it keeps its pages but releases its
        decode slot, joins the engine's FIFO wait line (ahead of any
        later-arriving admission) and resumes via the pool free callback."""
        req = g.slots[slot]
        g.slots[slot] = None
        rec = _Parked(req, int(g.pos_h[slot]), int(g.tok_h[slot]),
                      next(self._park_seq))
        self._parked[req.rid] = rec
        self.counters.add("kv_mid_decode_parks", 1)
        rec.cell["task"] = self.sched.spawn(
            self._regrow_task(rec), name=f"regrow{req.rid}", priority=1)
        # join the line NOW (synchronously): a request admitted after this
        # park must queue behind it — mid-decode streams cannot be starved
        # by newcomers (grants are FIFO by park order).  A BYPASSED stream
        # re-enters at its original ARRIVAL seq instead: it jumped the line
        # once under the no-delay bound, but parking must not also demote
        # it behind arrivals it legitimately preceded (to_back stays
        # reserved for spill victims, who consumed their turn)
        req.wq_seq = self._join_line(
            rec.cell["task"], seq=req.wq_seq if req.bypassed else None)

    def _regrow_task(self, rec: _Parked):
        """Waiter coroutine for a mid-decode parked stream: retry growth
        when it reaches the head of the line (same discipline as
        admission, so grants stay FIFO across admissions AND growers); on
        grant, hand the stream back to the owner group of its (possibly
        migrated) domain.

        If the stall watchdog SPILLED the stream while it waited, the
        retry becomes a restore: re-grant device pages (any domain —
        host-resident tables re-point for free), scatter the host payload
        back, and resume at the saved cursor — zero recomputed tokens."""
        req = rec.req
        while True:
            if rec.evicted:
                return
            if req.table is not None and req.table.inflight:
                # our own spill is still on the wire: the fence-before-
                # regrant invariant freezes the table until it lands (the
                # landing's free callback wakes the line head)
                yield BLOCK
                continue
            if self.waiters.oldest() is not rec.cell["task"]:
                if self._async and req.table.spill is not None:
                    # not our turn yet: stage the H2D upload behind the
                    # ticks ahead of us so the eventual re-grant scatters
                    # device-resident arrays instead of waiting on PCIe
                    self.pool.restore_prefetch(req.table)
                yield BLOCK             # not our turn: the grant cascade
                continue                # (or a free) will wake the head
            if req.table.spill is not None:
                g = self._restore_stream(rec)
                if g is not None:
                    break
            else:
                g = self._owner_group(req.table.domain)
                n, need = self._next_chunk_need(req, rec.pos)
                forks = (self.pool.fork_pages(req.table, rec.pos, n)
                         if self._share else [])
                if self._grow_stream(req, g, max(need, 0), tuple(forks)):
                    break
            yield BLOCK                 # woken by KVBlockPool.free
        self._leave_line(rec.cell["task"])
        req.grant_rounds.append(self._round)
        self._parked.pop(req.rid, None)
        req.group = g.gid
        g.resume.append(_InFlight(req, None, rec.pos, rec.token))
        return

    def _restore_stream(self, rec: _Parked) -> Optional["_Group"]:
        """Re-grant a SPILLED stream: find a domain with room for its host
        pages PLUS the growth its next chunk needs (its own domain first —
        re-pointing a host-resident table to any other is free) and land
        it there in ONE atomic ``restore_into`` leg; None when no domain
        can take it yet.  The old sweep re-pointed, restored and grew in
        separate steps — a leg whose grow failed after the restore left
        the stream half-granted in the wrong domain with its state
        checkpoint consumed.  ``restore_into`` reserves pages + grow +
        state slot all-or-nothing, so a failed leg has zero side effects
        and the sweep just tries the next domain."""
        req = rec.req
        t = req.table
        n, _ = self._next_chunk_need(req, rec.pos)
        grow_by = max(0, self.pool.pages_needed(rec.pos + n) - t.spill.pages)
        order = [t.domain] + [
            d for g in sorted(self.groups,
                              key=lambda gr: (gr.kv_pressure(), gr.gid))
            for d in self._domain_order(g) if d != t.domain]
        for d in order:
            if self.pool.restore_into(t, d, grow_by=grow_by):
                return self._owner_group(t.domain)
        return None

    # -- allocation-stall watchdog (the incremental-allocation deadlock) -----
    def _progress_signature(self) -> float:
        t = self.counters.totals
        return (t.get("tokens_processed", 0.0)
                + t.get("kv_reservations", 0.0)
                + t.get("kv_lazy_grows", 0.0)
                + t.get("kv_blocks_freed", 0.0)
                # an ISSUED spill is progress-in-motion: its frees are on
                # the wire, so the watchdog must not fire again before the
                # landing re-grants them
                + t.get("kv_spill_issues", 0.0))

    def _stall_hook(self):
        """Called by the scheduler after every round.  If nothing has made
        progress for ``stall_evict_rounds`` rounds while streams sit parked
        holding pages, the classic incremental-allocation deadlock has
        closed: break it by evicting the MOST-RECENTLY-parked stream (it
        loses the least work and nobody behind it in the line exists)."""
        if self.pool is None:
            return
        with span("arcas.stall"):
            self._relieve_stall()

    def _relieve_stall(self):
        """One round of the pressure ladder: poll transfers, spill past a
        watermark, and break an allocation stall."""
        self._round += 1
        if self._async:
            # poll phase of the ladder: land every transfer whose device
            # arrays report ready — landings fire the free callback, so
            # re-grants happen here, not at issue
            self.pool.spill_poll()
        if len(self.waiters):
            # rounds the wait line spent non-empty: the head-blocking
            # exposure the size-aware bypass converts into admissions
            self.counters.add("kv_head_wait_ticks", 1)
        head = self.waiters.oldest()
        hid = head.id if head is not None else None
        if hid != self._head_id:
            self._head_id, self._head_wait = hid, 0
        elif hid is not None:
            self._head_wait += 1
        # proactive-spill rung of the pressure ladder: a domain crossing
        # its HIGH occupancy watermark sheds ONE cold parked stream NOW,
        # before the allocation stall can close into a watchdog-grade
        # deadlock (hysteresis: it re-arms only under the LOW mark)
        if self._parked:
            infl = self.pool.inflight_domains() if self._async else set()
            for d in self.pool.watermark_domains():
                if d in infl:
                    continue            # its frees are already in the pipe:
                                        # never double-spill a domain
                if self._spill_parked(domain=d):
                    self.pool.watermark_arm(d)
                    self.counters.add("kv_proactive_spills", 1)
        sig = self._progress_signature()
        if sig != self._progress_mark:
            self._progress_mark = sig
            self._stall_rounds = 0
        else:
            self._stall_rounds += 1
        stalled = self._stall_rounds >= self.ecfg.stall_evict_rounds
        # Bypassed streams tick the GLOBAL progress clock (their tokens and
        # frees are progress) without ever feeding the head's need domains —
        # left alone they would postpone the very spill that unblocks the
        # head, re-introducing the delay the bypass-safety bound rules out.
        # Once any bypass grant exists, the head's OWN wait drives the
        # watchdog too: the head is unblocked at the same round or earlier
        # than a no-bypass run, never later.
        head_stalled = (not stalled
                        and self.counters.totals.get("kv_bypass_grants",
                                                     0.0) > 0
                        and self._head_wait >= self.ecfg.stall_evict_rounds)
        if stalled and self._parked:
            if self._async and self.pool.inflight_tables():
                # a spill is already on the wire: fence it instead of
                # issuing another — the landing re-grants the victim's
                # pages, which is exactly the progress the watchdog wants
                self.pool.spill_fence()
            elif self.ecfg.evict_mode == "swap" and self._spill_youngest():
                self.counters.add("kv_watchdog_spills", 1)
            else:
                self._evict_youngest()
            self._stall_rounds = 0
            self._head_wait = 0
        elif head_stalled and self._parked:
            # the head-wait rung frees pages the head can actually USE: a
            # parked grower regrows only in its own domain, so the victim
            # must hold pages there (a spilled or admission head restores
            # anywhere — any domain's coldest park will do).  Never spill
            # the head itself: that would demote it to the back of the
            # line, manufacturing the starvation this rung prevents.
            hr = self._head_rec()
            dom = None
            if hr is not None and hr.req.table is not None \
                    and hr.req.table.spill is None:
                dom = hr.req.table.domain
            ex = hr.req.rid if hr is not None else None
            if self._async and self.pool.inflight_tables():
                self.pool.spill_fence()     # land the pipe before adding
                self._head_wait = 0         # to it (same as the stalled
                return                      # rung)
            if self.ecfg.evict_mode == "swap" and (
                    self._spill_parked(domain=dom, exclude_rid=ex)
                    or (dom is not None
                        and self._spill_parked(domain=None, exclude_rid=ex))):
                self.counters.add("kv_watchdog_spills", 1)
            self._head_wait = 0

    def _spill_youngest(self) -> bool:
        """Swap-tier deadlock breaker: move the most-recently-parked
        stream's used pages to the host spill store — its device pages go
        to the LONGEST-parked waiter via the free callback, but nothing is
        recomputed: the stream keeps its saved cursor and restores
        mid-decode when it is re-granted pages.  The victim re-queues at
        the BACK of the wait line (it had its turn), exactly where
        restart-eviction would have sent its re-admission.  False when
        every parked stream is already host-resident (nothing left to
        spill — the caller falls back to restart eviction)."""
        return self._spill_parked(domain=None)

    def _spill_parked(self, domain: Optional[int],
                      exclude_rid: Optional[int] = None) -> bool:
        """Spill the most-recently-parked spillable stream — pool-wide for
        the stall watchdog, or restricted to ``domain`` for the proactive
        watermark rung and the head-wait rung (which also excludes the
        line head itself via ``exclude_rid``).  The victim rule is shared:
        the youngest park re-queues at the back of the line either way, so
        of all parked streams its pages are the COLDEST — the last the
        line will ask for.  False when nothing in scope is left to
        spill."""
        cands = [r for r in self._parked.values()
                 if r.req.table is not None and r.req.table.spill is None
                 and not r.req.table.inflight
                 and r.req.table.blocks
                 and r.req.rid != exclude_rid
                 and (domain is None or r.req.table.domain == domain)]
        if not cands:
            return False
        if self._async and domain is not None:
            # async ladder, domain-scoped rungs: the §4.5 access counters
            # pick the victim — min ``last_touch`` is the parked stream
            # whose pages have gone longest without a decode tick, so its
            # bytes are the cheapest to push behind the token loop
            rec = min(cands, key=lambda r: (r.req.table.last_touch, r.seq))
        else:
            rec = max(cands, key=lambda r: r.seq)
        task = rec.cell.get("task")
        if task is not None:
            # demote BEFORE spilling: the spill's free callback wakes the
            # line head, which must be the next waiter — not the victim.
            # The fresh seq retires any arrival-position claim a bypassed
            # victim held: it consumed its turn
            ns = self.waiters.to_back(task)
            if ns is not None:
                rec.req.wq_seq = ns
                self._wait_round[task.id] = self._round
        if self._async:
            # issue-only: the D2H copy drains behind the token loop and
            # the victim's pages re-grant at the poll that lands it
            # (fence-before-regrant) — the wake fires there, not here
            self.pool.spill_issue(rec.req.table)
        else:
            self.pool.spill(rec.req.table)  # frees pages -> wakes the head
        rec.seq = next(self._park_seq)  # its park is "fresh" again
        return True

    def _evict_youngest(self):
        """Restart-eviction deadlock breaker (``evict_mode="restart"``, and
        the swap mode's last resort): free the most-recently-parked
        stream's pages (granting them to the LONGEST-parked waiter via the
        free callback) and restart it from scratch — greedy decoding
        regenerates the identical tokens, so eviction is invisible in the
        output, but every token processed so far is recomputed
        (``recompute_tokens``)."""
        rec = max(self._parked.values(), key=lambda r: r.seq)
        rec.evicted = True
        self._parked.pop(rec.req.rid, None)
        task = rec.cell.get("task")
        if task is not None:
            self.waiters.remove(task)
            self._wait_round.pop(task.id, None)
            self.runtime.unblock(task)  # let the generator observe .evicted
        req = rec.req
        self.pool.free(req.table)       # wakes the longest-parked waiter
        req.table = None
        req.generated = []
        req.t_first = None
        req.bypassed = False            # the restart is a fresh admission
        self.counters.add("kv_evictions", 1)
        self.counters.add("recompute_tokens", rec.pos)
        cell: Dict[str, Any] = {"req": req}
        cell["task"] = self.sched.spawn(
            self._admission_task(req, cell), name=f"readmit{req.rid}",
            priority=1)
        # back of the line: it had its turn (and that demotion replaces
        # any arrival-position claim for future parks)
        req.wq_seq = self._join_line(cell["task"])
        if self._bypass_wake and self.ecfg.slo_classes[req.cls].bypass:
            self._bypass_cells[cell["task"].id] = cell

    # -- one engine tick: admit + mixed chunk/decode token step ---------------
    def _install(self, g: _Group, slot: int, fl: _InFlight):
        """Re-slot a migrated stream.  Paged mode is pure bookkeeping (the
        KV never left the pool); legacy mode writes the carried slice."""
        if not self.ecfg.paged:
            g.cache = jax.tree.map(
                lambda pool, one: pool.at[:, slot].set(one),
                g.cache, fl.cache)
        g.slots[slot] = fl.req
        g.pos_h[slot] = fl.pos
        g.tok_h[slot] = fl.token
        self.counters.add("kv_slots_restored", 1)

    def _accept_steal(self, g: _Group):
        """TieredQueues accept hook: a stolen request's KV reservation must
        move into the thief's memory domain (memory follows work).
        Partially-grown tables move only their reserved pages."""
        def accept(req: Request, _tier: str) -> bool:
            if not self.ecfg.paged or req.table is None:
                return True
            return self._migrate_into(req.table, g)
        return accept

    def _admit(self, g: _Group):
        for slot in g.free_slots():
            if g.resume:                       # migrated streams first
                fl = g.resume.pop(0)
                with span("arcas.admit", rid=fl.req.rid):
                    self._install(g, slot, fl)
                continue
            req, tier = self.queues.pop(g.gid, accept=self._accept_steal(g))
            if req is None:
                break
            with span("arcas.admit", rid=req.rid):
                if tier != "local":
                    req.group = g.gid
                if self._lazy:
                    # the token loop prefills this stream chunk-by-chunk;
                    # admission points a slot at the first unmatched prompt
                    # position (0 when no prefix pages were shared)
                    g.slots[slot] = req
                    g.pos_h[slot] = req.prefix_tokens
                    g.tok_h[slot] = 0
                    continue
                prompt = req.prompt[None, :]
                logits, cache1 = self._prefill(self.params, {"tokens": prompt})
                nxt = int(jnp.argmax(logits[0]))
                req.generated.append(nxt)
                req.t_first = self._clock()
                self.counters.add("prefills", 1)
                self.counters.add("tokens_processed", len(req.prompt))
                if len(req.generated) >= req.max_new:
                    # prefill's token already met the budget (max_new=1):
                    # finish without ever taking a decode slot or pool pages
                    req.t_done = req.t_first
                    self._inflight -= 1
                    if self.ecfg.paged:
                        self.pool.free(req.table)
                    continue
                if self.ecfg.paged:
                    tables, slots1 = self._table_row(req)
                    with span("arcas.dispatch", step="commit_prefill"):
                        self.pool.storage = self._commit_prefill(
                            self.pool.storage,
                            jnp.asarray(np.asarray([tables], np.int32)),
                            jnp.asarray(np.asarray([slots1], np.int32)),
                            cache1)
                    req.table.used_pages = self.pool.pages_needed(
                        len(req.prompt))
                else:
                    # copy the single-stream cache into the group slot
                    g.cache = jax.tree.map(
                        lambda pool, one: pool.at[:, slot].set(one[:, 0]),
                        g.cache, cache1)
                g.slots[slot] = req
                g.pos_h[slot] = len(req.prompt)
                g.tok_h[slot] = nxt

    def _split_tick(self, g: _Group, n_h, toks, C: int,
                    deco_rows: List[int]) -> np.ndarray:
        """A mixed tick as TWO compiled steps instead of one C-wide step.

        BOTH halves run over COMPACTED batches padded to a power-of-two
        bucket (so the number of distinct compiled shapes stays
        O(log max_batch) per half): the fused chunk forward holds only the
        multi-token prefill streams, the single-token step only the decode
        streams.  Bucket padding rows point at the null table/state slot
        (reserved id 0 — written but never read, the same convention empty
        slots use).  The two steps touch disjoint real pages, so running
        them back to back over the donated storage is exact.  Decode
        streams thus pay 1 query row instead of C — the (C-1)·n_decode
        rows saved land in ``mixed_tick_decode_rows_saved`` — and the
        decode gather/scatter moves bucket-of-n_decode rows instead of
        max_batch (``decode_gather_rows_saved``).
        """
        B = self.ecfg.max_batch
        P = self.pool.pages_per_stream
        # -- chunk half: compacted fused forward over prefill streams only
        with span("arcas.assemble"):
            chunk_rows = [i for i in range(B) if n_h[i] > 1]
            Bc = 1
            while Bc < len(chunk_rows):
                Bc *= 2
            Bc = min(Bc, B)
            rows = chunk_rows + [None] * (Bc - len(chunk_rows))
            trows, srows = zip(*(self._table_row(g.slots[i])
                                 if i is not None else self._table_row(None)
                                 for i in rows))
            toks_c = np.zeros((Bc, C), np.int32)
            pos_c = np.zeros((Bc,), np.int32)
            n_c = np.zeros((Bc,), np.int32)
            for j, i in enumerate(chunk_rows):
                toks_c[j] = toks[i]
                pos_c[j] = g.pos_h[i]
                n_c[j] = n_h[i]
        with span("arcas.dispatch", step="chunk"):
            logits_c, self.pool.storage = self._paged_chunk(
                self.params, self.pool.storage,
                jnp.asarray(np.asarray(trows, np.int32).reshape(Bc, P)),
                jnp.asarray(np.asarray(srows, np.int32)),
                jnp.asarray(toks_c), jnp.asarray(pos_c), jnp.asarray(n_c))
        with span("arcas.sync"):
            nxt_c = np.asarray(dec.next_token_ids(logits_c,
                                                  jnp.asarray(n_c)))
        # -- decode half: the single-token step, compacted to its own bucket
        with span("arcas.assemble"):
            Bd = 1
            while Bd < len(deco_rows):
                Bd *= 2
            Bd = min(Bd, B)
            rows_d = deco_rows + [None] * (Bd - len(deco_rows))
            trows, srows = zip(*(self._table_row(g.slots[i])
                                 if i is not None else self._table_row(None)
                                 for i in rows_d))
            toks_d = np.zeros((Bd, 1), np.int32)
            pos_d = np.zeros((Bd,), np.int32)
            n_d = np.zeros((Bd,), np.int32)
            for j, i in enumerate(deco_rows):
                toks_d[j, 0] = toks[i, 0]
                pos_d[j] = g.pos_h[i]
                n_d[j] = 1
        with span("arcas.dispatch", step="decode"):
            logits_d, self.pool.storage = self._paged_decode(
                self.params, self.pool.storage,
                jnp.asarray(np.asarray(trows, np.int32).reshape(Bd, P)),
                jnp.asarray(np.asarray(srows, np.int32)),
                jnp.asarray(toks_d), jnp.asarray(pos_d))
        with span("arcas.sync"):
            nxt_d = np.asarray(dec.next_token_ids(logits_d,
                                                  jnp.asarray(n_d)))
        nxt = np.full((B,), -1, np.int32)   # idle rows keep the sentinel
        for j, i in enumerate(deco_rows):
            nxt[i] = nxt_d[j]
        for j, i in enumerate(chunk_rows):
            nxt[i] = nxt_c[j]
        self.counters.add("split_ticks", 1)
        self.counters.add("mixed_tick_decode_rows_saved",
                          (C - 1) * len(deco_rows))
        self.counters.add("decode_gather_rows_saved", B - Bd)
        self.counters.add("decode_gather_null_rows", Bd - len(deco_rows))
        return nxt

    def _draft_for(self, req: Request, pos: int) -> List[int]:
        """Up to spec_k draft tokens for a DECODE stream — empty during
        prefill, near max_new (the verify chunk's free boundary token must
        never overrun the budget), or when the drafter has nothing.
        Proposals are sanitized (in-vocab prefix) but never trusted: the
        verify forward is the only thing that commits tokens."""
        S = len(req.prompt)
        if pos < S:
            return []
        k = min(self.ecfg.spec_k, self._spec_w - 1,
                req.max_new - len(req.generated) - 1)
        if k <= 0:
            return []
        out: List[int] = []
        for t in self.drafter.draft(req, k)[:k]:
            t = int(t)
            if not 0 <= t < self.cfg.vocab:
                break
            out.append(t)
        return out

    def _spec_verify(self, g: _Group, toks, n_h,
                     drafts: Dict[int, List[int]]) -> Dict[int, np.ndarray]:
        """The verify half: ONE all-position-logits fused chunk forward
        over the drafted rows, compacted into their own pow-2 bucket at
        the narrow spec width (drafted rows never share a compiled program
        with prefill chunks or plain decode rows, so those paths stay
        bit-identical to the spec-off engine).  The cache commits
        optimistically; rejected suffixes roll back from the page
        checkpoints.  Returns row -> (n_i, V) logits."""
        rows = sorted(drafts)
        W = self._spec_w
        P = self.pool.pages_per_stream
        with span("arcas.assemble"):
            Bs = 1
            while Bs < len(rows):
                Bs *= 2
            Bs = min(Bs, self.ecfg.max_batch)
            rs = rows + [None] * (Bs - len(rows))
            trows, srows = zip(*(self._table_row(g.slots[i])
                                 if i is not None else self._table_row(None)
                                 for i in rs))
            toks_s = np.zeros((Bs, W), np.int32)
            pos_s = np.zeros((Bs,), np.int32)
            n_s = np.zeros((Bs,), np.int32)
            for j, i in enumerate(rows):
                n = int(n_h[i])
                toks_s[j, :n] = toks[i, :n]
                pos_s[j] = g.pos_h[i]
                n_s[j] = n
        with span("arcas.dispatch", step="spec"):
            lg, self.pool.storage = self._paged_spec(
                self.params, self.pool.storage,
                jnp.asarray(np.asarray(trows, np.int32).reshape(Bs, P)),
                jnp.asarray(np.asarray(srows, np.int32)),
                jnp.asarray(toks_s), jnp.asarray(pos_s), jnp.asarray(n_s))
        with span("arcas.sync"):
            lg = np.asarray(lg)
        self.counters.add("spec_verify_forwards", 1)
        self.counters.add("spec_row_forwards", len(rows))
        return {i: lg[j, :int(n_h[i])] for j, i in enumerate(rows)}

    def _spec_reapply(self, g: _Group, toks,
                      rows: List[Tuple[int, int]]):
        """Re-apply the ACCEPTED prefix of each rolled-back draft row with
        one masked chunk forward from the restored pre-verify state
        (logits discarded — the verify pass already fixed the committed
        tokens).  Causal masking makes this bit-equivalent to having fed
        only those tokens in the first place."""
        W = self._spec_w
        P = self.pool.pages_per_stream
        with span("arcas.assemble"):
            Br = 1
            while Br < len(rows):
                Br *= 2
            Br = min(Br, self.ecfg.max_batch)
            rs = rows + [(None, 0)] * (Br - len(rows))
            trows, srows = zip(*(self._table_row(g.slots[i])
                                 if i is not None else self._table_row(None)
                                 for i, _ in rs))
            toks_r = np.zeros((Br, W), np.int32)
            pos_r = np.zeros((Br,), np.int32)
            n_r = np.zeros((Br,), np.int32)
            for j, (i, nc) in enumerate(rows):
                toks_r[j, :nc] = toks[i, :nc]
                pos_r[j] = g.pos_h[i]
                n_r[j] = nc
        with span("arcas.dispatch", step="chunk"):
            _, self.pool.storage = self._paged_chunk(
                self.params, self.pool.storage,
                jnp.asarray(np.asarray(trows, np.int32).reshape(Br, P)),
                jnp.asarray(np.asarray(srows, np.int32)),
                jnp.asarray(toks_r), jnp.asarray(pos_r), jnp.asarray(n_r))
        self.counters.add("spec_reapply_forwards", 1)
        self.counters.add("spec_row_reapplies", len(rows))

    def _decode_tick(self, g: _Group):
        """ONE batched model step for the group: every occupied slot
        consumes its next tokens — a page-sized prompt chunk for streams
        still in prefill, the last generated token (plus up to spec_k
        drafted tokens when speculative decoding is on) for decode
        streams.  Lazy tables grow (or park their stream) before the step
        commits any bytes."""
        with span("arcas.assemble"):
            B = self.ecfg.max_batch
            n_h = np.zeros((B,), np.int32)
            chunked = False
            drafts: Dict[int, List[int]] = {}
            for i in range(B):
                req = g.slots[i]
                if req is None:
                    continue
                pos = int(g.pos_h[i])
                if req.table is not None and self.ecfg.paged:
                    self.pool.touch_table(req.table)
                    n, need = self._next_chunk_need(req, pos)
                    d = self._draft_for(req, pos) if self._spec else []
                    if d:
                        # a drafted decode stream writes 1 + k positions this
                        # tick: growth and CoW must cover the full draft width
                        # BEFORE the optimistic verify forward touches pages
                        n = 1 + len(d)
                        need = (self.pool.pages_needed(pos + n)
                                - len(req.table.blocks))
                    forks = (self.pool.fork_pages(req.table, pos, n)
                             if self._share else [])
                    grown = not (self._lazy and self.pool.pages_per_stream
                                 and (need > 0 or forks)) \
                        or self._grow_stream(req, g, max(need, 0),
                                             tuple(forks))
                    if not grown and d:
                        # speculation is opportunistic: under memory pressure
                        # drop the draft and retry as a plain decode, so spec
                        # never parks a stream the non-speculative engine
                        # would have run this tick
                        d = []
                        n, need = self._next_chunk_need(req, pos)
                        forks = (self.pool.fork_pages(req.table, pos, n)
                                 if self._share else [])
                        grown = not (need > 0 or forks) or self._grow_stream(
                            req, g, max(need, 0), tuple(forks))
                    if not grown:
                        self._park_stream(g, i)
                        continue
                    if self._share:
                        # writing into a published page forks the page's index
                        # entry off it (the OLD block keeps its entry)
                        self.pool.note_writes(req.table, pos, n)
                    if d:
                        drafts[i] = d
                else:
                    S = len(req.prompt)
                    n = min(self._chunk, S - pos) if pos < S else 1
                n_h[i] = n
                # drafted rows run their OWN verify half; "chunked" tracks
                # only real prefill chunks so the spec-off paths (and their
                # counters) stay byte-for-byte unchanged
                chunked = chunked or (n > 1 and i not in drafts)
            if not n_h.any():
                return
            if self.ecfg.paged and self.pool.inflight_tables():
                # the overlap clock: a real model tick ran with at least one
                # D2H transfer on the wire — decode time the spill hid behind
                self.counters.add("kv_ticks_while_inflight", 1)
            if self.ecfg.paged:
                tables, slots1 = self._group_indices(g)
            pos_j = jnp.asarray(g.pos_h)
            # per-stream token feed: the next prompt slice for streams still in
            # prefill (a final chunk may hold a single token), the last emitted
            # token — plus its draft continuation — for decode streams
            C = self._chunk if chunked else (self._spec_w if drafts else 1)
            toks = np.zeros((B, C), np.int32)
            for i in range(B):
                req = g.slots[i]
                if req is None or not n_h[i]:
                    continue
                pos = int(g.pos_h[i])
                if pos < len(req.prompt):
                    toks[i, :n_h[i]] = req.prompt[pos:pos + n_h[i]]
                else:
                    toks[i, 0] = g.tok_h[i]
                    d = drafts.get(i)
                    if d:
                        toks[i, 1:1 + len(d)] = d
            # drafted rows are carved out of the regular paths (n_eff = 0:
            # gathered but never computed or written) — they run through the
            # dedicated verify half below, so prefill chunks and plain decode
            # rows execute the EXACT compiled programs the spec-off engine runs
            n_eff = n_h.copy()
            for i in drafts:
                n_eff[i] = 0
            deco_rows = [i for i in range(B) if n_eff[i] == 1]
        if chunked:
            # model-step accounting, STRUCTURAL (by construction of the
            # compiled path, not measured at runtime): the fused path is
            # one forward per tick, the scan path a length-C lax.scan of
            # decode_step.  The benchmark's parallel-vs-scan token
            # identity is the behavioral gate; this feeds the C× metric.
            self.counters.add("chunk_ticks", 1)
            self.counters.add(
                "prefill_model_steps",
                1 if self._prefill_mode == "parallel" else C)
            if self.ecfg.split_ticks and deco_rows:
                nxt = self._split_tick(g, n_eff, toks, C, deco_rows)
            else:
                if deco_rows:
                    # single-token streams ride the C-wide step: C-1 of
                    # their query rows are pure masked-FLOP waste
                    self.counters.add("decode_masked_query_rows",
                                      (C - 1) * len(deco_rows))
                with span("arcas.dispatch", step="chunk"):
                    logits, self.pool.storage = self._paged_chunk(
                        self.params, self.pool.storage, tables, slots1,
                        jnp.asarray(toks), pos_j, jnp.asarray(n_eff))
                with span("arcas.sync"):
                    nxt = np.asarray(dec.next_token_ids(logits,
                                                        jnp.asarray(n_eff)))
        elif deco_rows:
            with span("arcas.assemble"):
                tokens = jnp.asarray(toks[:, :1])
                if self.ecfg.paged and drafts:
                    # the single-token step has NO per-row length mask, so
                    # a drafted row riding it would write its ring page
                    # AND advance its recurrent state a second time before
                    # the verify half runs.  Point drafted rows at the
                    # null table/state row instead (reserved id 0 —
                    # written but never read, the same convention idle
                    # slots and bucket padding use); their logits are
                    # already masked to the -1 sentinel via n_eff.
                    P = self.pool.pages_per_stream
                    rowlist, slotlist = zip(
                        *(self._table_row(None) if i in drafts
                          else self._table_row(g.slots[i])
                          for i in range(B)))
                    tables = jnp.asarray(
                        np.asarray(rowlist, np.int32).reshape(B, P))
                    slots1 = jnp.asarray(np.asarray(slotlist, np.int32))
            with span("arcas.dispatch", step="decode"):
                if self.ecfg.paged:
                    logits, self.pool.storage = self._paged_decode(
                        self.params, self.pool.storage, tables, slots1,
                        tokens, pos_j)
                else:
                    logits, g.cache = self._decode(self.params, g.cache,
                                                   tokens, pos_j)
            # idle-slot hardening: slots with n == 0 get the -1 sentinel,
            # never an argmax over a constant (all-zero / all-NEG_INF) row
            with span("arcas.sync"):
                nxt = np.asarray(dec.next_token_ids(logits,
                                                    jnp.asarray(n_eff)))
        else:
            nxt = np.full((B,), -1, np.int32)   # pure-spec tick
        if deco_rows:
            self.counters.add("decode_row_forwards", sum(
                1 for i in deco_rows
                if int(g.pos_h[i]) >= len(g.slots[i].prompt)))
            if not chunked or self.ecfg.split_ticks:
                self.counters.add("decode_forwards", 1)
                if self._decode_in_place:
                    self.counters.add("decode_inplace_forwards", 1)
        # -- speculative verify half: one all-logits fused forward over the
        # drafted rows, then greedy acceptance with checkpoint rollback
        commits: Dict[int, List[int]] = {}
        if drafts:
            self.counters.add("spec_ticks", 1)
            # Rollback needs, per row.  While the write window stays below
            # the ring width, rejected-suffix KV PAGE writes are dead
            # weight, never wrong: position -> ring slot is injective
            # there, the suffix sits at or past the committed cursor, and
            # every read (attention gather, prefix match, spill) is
            # cursor-masked, so the stale bytes are overwritten before any
            # read can see them.  Once ``pos + n`` crosses the ring width
            # (local-attention models whose window is narrower than
            # max_len) a rejected write at position p lands on slot
            # p % W and DESTROYS the still-live position p - W, so the
            # touched pages must be snapshotted.  Recurrent STATE always
            # needs its snapshot: the slot holds the reduction over ALL n
            # fed tokens and cannot be recomputed from pages.  A partial
            # accept restores the snapshot and re-applies the accepted
            # prefix to advance it.
            ring_w = self.pool.spec.width if self.pool.pages_per_stream \
                else 0
            snap_rows: List[Tuple[KVTable, int, int, bool]] = []
            snap_idx: List[int] = []
            for i in sorted(drafts):
                p0, nn = int(g.pos_h[i]), int(n_h[i])
                wraps = bool(ring_w) and p0 + nn > ring_w
                if self.pool.has_state or wraps:
                    snap_rows.append((g.slots[i].table, p0, nn, wraps))
                    snap_idx.append(i)
            # ONE device gather snapshots every drafted row (PR-8
            # leftover): the checkpoints stay device-resident — a full
            # accept drops them without any host copy ever happening
            snaps = dict(zip(snap_idx,
                             self.pool.checkpoint_rows(snap_rows))) \
                if snap_rows else {}
            spec_lg = self._spec_verify(g, toks, n_h, drafts)
            with span("arcas.commit"):
                reapply: List[Tuple[int, int]] = []
                rolled: List[dict] = []
                for i in sorted(drafts):
                    n = int(n_h[i])
                    am = np.argmax(spec_lg[i], axis=-1)
                    # accept the longest prefix where each draft token matches
                    # the verified argmax one position earlier; the token at
                    # the accept boundary comes free (full accept: k+1 tokens)
                    m = 0
                    while m < n - 1 and int(toks[i, m + 1]) == int(am[m]):
                        m += 1
                    commits[i] = [int(x) for x in am[:m + 1]]
                    self.counters.add("spec_tokens_drafted", n - 1)
                    self.counters.add("spec_tokens_accepted", m)
                    if m + 1 < n:
                        self.counters.add("spec_rollbacks", 1)
                        if m == 0:
                            self.counters.add("spec_full_rejects", 1)
                        if i in snaps:
                            rolled.append(snaps[i])
                            reapply.append((i, m + 1))
                if rolled:
                    # one batched scatter restores every rejected row
                    self.pool.rollback_rows(rolled)
            if reapply:
                self._spec_reapply(g, toks, reapply)
            drafted = self.counters.totals.get("spec_tokens_drafted", 0.0)
            if drafted:
                self.counters.set(
                    "spec_accept_rate",
                    self.counters.totals.get("spec_tokens_accepted", 0.0)
                    / drafted)
        with span("arcas.commit"):
            g.steps += 1
            now = self._clock()
            for i in range(B):
                req = g.slots[i]
                if req is None or not n_h[i]:
                    continue
                S = len(req.prompt)
                pos0 = int(g.pos_h[i])
                if i in commits:
                    # a drafted decode row commits its verified tokens: the
                    # accepted draft prefix plus the free boundary token.  The
                    # cursor lands on the last ACCEPTED position — a park or
                    # spill next tick saves exactly this state
                    out = commits[i]
                    g.pos_h[i] = pos0 + len(out)
                    self.counters.add("tokens_processed", len(out))
                    self.counters.add("decode_committed_tokens", len(out))
                    for tok in out:
                        assert tok >= 0, f"spec slot {i} emitted a sentinel"
                        req.generated.append(tok)
                    g.tok_h[i] = out[-1]
                    req.table.used_pages = min(
                        len(req.table.blocks),
                        self.pool.pages_needed(pos0 + len(out)))
                    if len(req.generated) >= req.max_new:
                        req.t_done = now
                        g.slots[i] = None
                        self._inflight -= 1
                        self.pool.free(req.table)  # wakes parked streams
                    continue
                new_pos = pos0 + int(n_h[i])
                g.pos_h[i] = new_pos
                self.counters.add("tokens_processed", int(n_h[i]))
                if pos0 >= S:
                    self.counters.add("decode_committed_tokens", 1)
                if pos0 < S:
                    self.counters.add("prefill_chunks", 1)
                    if self.ecfg.paged:
                        req.table.used_pages = min(
                            len(req.table.blocks),
                            self.pool.pages_needed(new_pos))
                    if self._share and req.page_keys:
                        # publish the prompt pages this chunk completed so
                        # later requests with the same prefix can attach
                        self.pool.register_prefix(req.table, req.page_keys,
                                                  pos0, new_pos, S)
                    if new_pos < S:
                        continue            # mid-prompt: no token emitted yet
                    req.t_first = now
                    self.counters.add("prefills", 1)
                tok = int(nxt[i])
                assert tok >= 0, f"idle slot {i} emitted a token"
                req.generated.append(tok)
                g.tok_h[i] = tok
                if self.ecfg.paged:
                    req.table.used_pages = min(len(req.table.blocks),
                                               self.pool.pages_needed(new_pos))
                if len(req.generated) >= req.max_new:
                    req.t_done = now
                    g.slots[i] = None
                    self._inflight -= 1
                    if self.ecfg.paged:
                        self.pool.free(req.table)  # wakes parked streams
            self.counters.add("decode_steps", 1)
            self.counters.add("decode_tokens",
                              sum(1 for s in g.slots if s is not None))

    # -- engine task (coroutine per group, scheduled by the task runtime) ----
    def _group_task(self, g: _Group):
        while not g.retired:
            outstanding = self._inflight > 0 or self._clients > 0
            if not g.busy() and not outstanding:
                return
            self._admit(g)
            self._decode_tick(g)
            yield   # yield point: profiler + Algorithm 1 + possible relayout

    def _spawn_group(self, g: _Group):
        self.sched.spawn(self._group_task(g), group=g.gid,
                         name=f"group{g.gid}")

    def _round_metrics(self) -> Optional[Callable[[], Dict[str, float]]]:
        """Per-round profiler feed: KV-pool gauges + deltas since the
        previous round (None in legacy slot-monolith mode)."""
        if self.pool is None:
            return None
        names = ("kv_alloc_failures", "kv_blocks_migrated", "kv_lazy_grows",
                 "kv_mid_decode_parks", "prefill_chunks",
                 "kv_spilled_pages", "kv_restores", "recompute_tokens",
                 "mixed_tick_decode_rows_saved",
                 "kv_prefix_hits", "prefill_tokens_skipped",
                 "spec_tokens_drafted", "spec_tokens_accepted",
                 "spec_rollbacks", "kv_bypass_grants", "kv_head_wait_ticks",
                 "kv_ticks_while_inflight", "kv_fence_waits")
        state = {"t": self._clock()}
        state.update({n: self.counters.totals.get(n, 0.0) for n in names})

        def metrics() -> Dict[str, float]:
            t1 = self._clock()
            cur = {n: self.counters.totals.get(n, 0.0) for n in names}
            out = {"step_time": t1 - state["t"],
                   "kv_occupancy": self.pool.occupancy(),
                   "kv_parks": cur["kv_alloc_failures"]
                   - state["kv_alloc_failures"],
                   "kv_shared_pages": float(self.pool.shared_pages()),
                   "kv_shared_bytes": self.pool.shared_bytes(),
                   "spec_accept_rate": self.counters.totals.get(
                       "spec_accept_rate", 0.0),
                   # transfer-engine gauges at sample time, not deltas
                   "kv_spill_inflight_pages": float(
                       self.pool.inflight_pages()),
                   "kv_spill_inflight_bytes": float(
                       self.pool.inflight_bytes())}
            for n in names[1:]:
                out[n] = cur[n] - state[n]
            state.update(t=t1, **cur)
            return out

        return metrics

    def run_until_done(self, *, max_rounds: int = 100000) -> Dict:
        trace: List[int] = []
        self._running = True
        try:
            for g in self.groups:
                self._spawn_group(g)
            self.sched.run_until_done(max_rounds=max_rounds,
                                      concurrency_trace=trace,
                                      metrics_fn=self._round_metrics(),
                                      round_hook=self._stall_hook)
        finally:
            self._running = False
            if self.pool is not None:
                self.pool.drain()       # no transfer outlives the run
        out = {"concurrency": trace, "counters": self.counters.snapshot(),
               "relayouts": list(self.relayouts),
               "decisions": [dataclasses.asdict(x)
                             for x in self.controller.decisions]}
        if self.pool is not None:
            out["kv"] = self.kv_stats()
        return out

    # -- measured model steps (compiled HLO, not structural) -----------------
    def _layer_trips(self) -> Tuple[int, ...]:
        """Trip counts a per-layer scan can compile to for this model —
        the probe ``hlo_analysis.model_steps_per_call`` matches while
        loops against."""
        if self.cfg.block_pattern:
            from repro.models.params import hybrid_structure
            _, n_groups, _ = hybrid_structure(self.cfg)
            return (n_groups,)
        if self.cfg.family == "encdec":
            return (self.cfg.dec_layers,)
        return (self.cfg.n_layers,)

    def measured_model_steps(self, kind: str = "chunk", *,
                             C: Optional[int] = None, B: int = 1) -> float:
        """Sequential model steps ONE call of a compiled paged step runs,
        counted from its optimized HLO (while-loop trip counts) instead of
        assumed from the path's construction — the PR-5 leftover that
        makes accepted-tokens-per-model-step a measured number.  ``kind``
        is "decode" (single-token step), "chunk" (the mixed chunk step) or
        "spec" (the all-logits verify step); ``C`` the chunk width to
        compile at (defaults to the engine's own width for the kind)."""
        from repro.launch.hlo_analysis import model_steps_per_call
        if self.pool is None:
            raise ValueError("measured_model_steps needs the paged path")
        P = self.pool.pages_per_stream
        sd = jax.ShapeDtypeStruct
        storage = jax.tree.map(lambda a: sd(a.shape, a.dtype),
                               self.pool.storage)
        tables = sd((B, P), jnp.int32)
        slots = sd((B,), jnp.int32)
        pos = sd((B,), jnp.int32)
        if kind == "decode":
            fn = self._paged_decode
            args = (self.params, storage, tables, slots,
                    sd((B, 1), jnp.int32), pos)
        elif kind in ("chunk", "spec"):
            if kind == "spec" and not self._spec:
                raise ValueError("spec step not built: spec_decode is off")
            fn = self._paged_chunk if kind == "chunk" else self._paged_spec
            W = C or (self._chunk if kind == "chunk" else self._spec_w)
            args = (self.params, storage, tables, slots,
                    sd((B, W), jnp.int32), pos, sd((B,), jnp.int32))
        else:
            raise ValueError(f"unknown step kind {kind!r}")
        hlo = fn.lower(*args).compile().as_text()
        return model_steps_per_call(hlo, self._layer_trips())

    def warm_steps(self, chunks: Tuple[int, ...] = (4, 8, 16)) -> int:
        """Trace + compile every paged step the serve loop can dispatch —
        decode, the chunk widths in ``chunks`` (clamped to the engine's
        chunk size) and, when speculative decoding is on, the verify and
        reapply widths — at every pow-2 batch bucket up to ``max_batch``.

        Each warm call drives the REAL dispatch partials (the AOT
        ``lower().compile()`` path keeps its own cache, so it cannot
        pre-pay dispatch-side compiles) with all-null rows: tables point
        at reserved block 0 and state slot 0, whose contents are written
        but never read, and chunk rows carry n_tokens=0 so live caches
        pass through bit-unchanged.  Serving after a warm-up therefore
        never stalls a request on an XLA backend compile.  Returns the
        number of step calls made."""
        if self.pool is None:
            return 0
        P = self.pool.pages_per_stream
        calls = 0
        widths = sorted({min(c, self._chunk) for c in chunks}
                        | ({self._spec_w} if self._spec else set()))
        B = 1
        while B <= self.ecfg.max_batch:
            tables = jnp.asarray(np.zeros((B, P), np.int32))
            slots = jnp.asarray(np.zeros((B,), np.int32))
            pos = jnp.asarray(np.zeros((B,), np.int32))
            _, self.pool.storage = self._paged_decode(
                self.params, self.pool.storage, tables, slots,
                jnp.asarray(np.zeros((B, 1), np.int32)), pos)
            calls += 1
            for W in widths:
                toks = jnp.asarray(np.zeros((B, W), np.int32))
                n = jnp.asarray(np.zeros((B,), np.int32))
                _, self.pool.storage = self._paged_chunk(
                    self.params, self.pool.storage, tables, slots,
                    toks, pos, n)
                calls += 1
                if self._spec and W == self._spec_w:
                    _, self.pool.storage = self._paged_spec(
                        self.params, self.pool.storage, tables, slots,
                        toks, pos, n)
                    calls += 1
            # the host-side argmax/mask group that follows every step
            dec.next_token_ids(jnp.zeros((B, self.cfg.vocab)),
                               jnp.asarray(np.zeros((B,), np.int32)))
            B *= 2
        # the pow-2 page-copy buckets behind migrations and prefix forks:
        # null-block self-copies are bit-exact no-ops
        b = 1
        while b <= P:
            self.pool.storage = dec.copy_pool_entries(
                self.pool.storage, self.pool.spec, [0] * b, [0] * b)
            calls += 1
            b *= 2
        return calls

    # -- latency / pool stats --------------------------------------------------
    def kv_stats(self) -> Dict[str, float]:
        """KV-pool health: occupancy, park (alloc-failure) rate, lazy
        growth / mid-decode park / eviction counts, blocks migrated per
        relayout."""
        if self.pool is None:
            return {}
        s = self.pool.stats()
        # the pool defaults this to one page; the engine knows the real
        # configured chunk size (prefill_chunk may span several pages) and
        # the compiled path (parallel adds the fused score transient)
        s["prefill_chunk_bytes"] = prefill_chunk_bytes(
            self.cfg, self._chunk, self.ecfg.max_len,
            mode=self._prefill_mode, kernel=self._chunk_kernel)
        s["prefill_score_bytes"] = (
            prefill_chunk_score_bytes(self.cfg, self._chunk,
                                      self.ecfg.max_len,
                                      kernel=self._chunk_kernel)
            if self._prefill_mode == "parallel" else 0.0)
        s["chunk_kernel"] = self._chunk_kernel
        s["mixed_tick_decode_rows_saved"] = self.counters.totals.get(
            "mixed_tick_decode_rows_saved", 0.0)
        s["decode_gather_rows_saved"] = self.counters.totals.get(
            "decode_gather_rows_saved", 0.0)
        s["decode_masked_query_rows"] = self.counters.totals.get(
            "decode_masked_query_rows", 0.0)
        s["prefill_model_steps"] = self.counters.totals.get(
            "prefill_model_steps", 0.0)
        s["chunk_ticks"] = self.counters.totals.get("chunk_ticks", 0.0)
        s["evictions"] = self.counters.totals.get("kv_evictions", 0.0)
        s["recompute_tokens"] = self.counters.totals.get(
            "recompute_tokens", 0.0)
        s["blocks_per_relayout"] = [r.get("blocks_migrated", 0.0)
                                    for r in self.relayouts]
        # speculative decoding: acceptance totals, forward participations
        # (the denominators of accepted-tokens-per-model-step) and the
        # costmodel-priced bytes optimism wasted
        s["spec_decode"] = self.ecfg.spec_decode if self._spec else "off"
        tot = self.counters.totals
        for k in ("spec_ticks", "spec_verify_forwards",
                  "spec_reapply_forwards", "spec_row_forwards",
                  "spec_row_reapplies", "spec_tokens_drafted",
                  "spec_tokens_accepted", "spec_rollbacks",
                  "spec_full_rejects", "spec_accept_rate",
                  "decode_forwards", "decode_inplace_forwards",
                  "decode_row_forwards", "decode_committed_tokens"):
            s[k] = tot.get(k, 0.0)
        rejected = s["spec_tokens_drafted"] - s["spec_tokens_accepted"]
        s["spec_rejected_bytes"] = spec_rejected_bytes(self.cfg,
                                                       int(rejected))
        s["spec_rollback_bytes"] = spec_rollback_bytes(
            self.cfg, int(tot.get("kv_spec_ckpt_pages", 0.0)),
            int(tot.get("kv_spec_rollback_pages", 0.0)),
            self.pool.block_tokens,
            ckpts=int(tot.get("kv_spec_ckpts", 0.0)),
            rollbacks=int(s["spec_rollbacks"]))
        # SLO-tiered admission: bypass volume, the priced safety floors
        # those grants preserved for the blocked heads they jumped, the
        # head-blocking exposure, the proactive-vs-watchdog spill split,
        # and per-class admission counts + latency percentiles (computed
        # from the very samples ``stats``/the benchmark report)
        s["bypass_grants"] = tot.get("kv_bypass_grants", 0.0)
        s["bypass_floor_pages"] = tot.get("kv_bypass_floor_pages", 0.0)
        s["bypass_floor_bytes"] = kv_bypass_floor_bytes(
            self.cfg, int(s["bypass_floor_pages"]), self.pool.block_tokens)
        s["head_wait_ticks"] = tot.get("kv_head_wait_ticks", 0.0)
        s["proactive_spills"] = tot.get("kv_proactive_spills", 0.0)
        s["watchdog_spills"] = tot.get("kv_watchdog_spills", 0.0)
        # async swap tier: overlap efficiency (decode ticks that ran with
        # a transfer on the wire, rounds each landed spill hid behind,
        # fences that actually waited) + the costmodel-priced time the
        # host link spent moving spill payloads
        s["async_swap"] = bool(self._async)
        s["ticks_while_inflight"] = tot.get("kv_ticks_while_inflight", 0.0)
        spills = max(1.0, s.get("spills", 0.0))
        s["overlap_rounds_per_spill"] = (
            tot.get("kv_spill_overlap_rounds", 0.0) / spills)
        s["d2h_seconds"] = kv_transfer_seconds(
            tot.get("kv_d2h_bytes", 0.0), self.topology.hw.d2h_bw)
        s["h2d_seconds"] = kv_transfer_seconds(
            tot.get("kv_h2d_bytes", 0.0), self.topology.hw.h2d_bw)
        s["class_submits"] = {c: tot.get(f"kv_class_submits/{c}", 0.0)
                              for c in self.ecfg.slo_classes}
        s["class_admits"] = {c: tot.get(f"kv_class_admits/{c}", 0.0)
                             for c in self.ecfg.slo_classes}
        s["class_bypass_grants"] = {c: tot.get(f"kv_class_bypass/{c}", 0.0)
                                    for c in self.ecfg.slo_classes}
        s["per_class"] = self.class_stats(self.submitted,
                                          self.ecfg.slo_classes)
        return s

    @staticmethod
    def class_stats(reqs: List[Request],
                    slo_classes: Optional[Dict[str, ClassSLO]] = None
                    ) -> Dict[str, Dict[str, float]]:
        """Per-SLO-class latency stats over the SAME samples :meth:`stats`
        reports — one ``stats`` dict per class, annotated with the class's
        TTFT/TPOT targets and whether the p99s met them.  Classes with no
        finished requests report ``{"n": 0}`` plus their targets."""
        classes = sorted({r.cls for r in reqs} | set(slo_classes or ()))
        out: Dict[str, Dict[str, float]] = {}
        for c in classes:
            sub = ServeEngine.stats([r for r in reqs if r.cls == c])
            if not sub:
                sub = {"n": 0}
            if slo_classes and c in slo_classes:
                slo = slo_classes[c]
                sub["ttft_target"] = slo.ttft_target
                sub["tpot_target"] = slo.tpot_target
                if sub["n"]:
                    sub["ttft_slo_met"] = bool(
                        sub["ttft_p99"] <= slo.ttft_target)
                    sub["tpot_slo_met"] = bool(
                        sub["tpot_p99"] <= slo.tpot_target)
            out[c] = sub
        return out

    @staticmethod
    def stats(reqs: List[Request]) -> Dict[str, float]:
        done = [r for r in reqs if r.done]
        if not done:
            return {}
        ttft = np.array([r.t_first - r.arrived for r in done])
        total = np.array([r.t_done - r.arrived for r in done])
        tpot = np.array([(r.t_done - r.t_first)
                         / max(1, len(r.generated) - 1) for r in done])
        return {
            "n": len(done),
            "ttft_mean": float(ttft.mean()),
            "ttft_p50": float(np.percentile(ttft, 50)),
            "ttft_p99": float(np.percentile(ttft, 99)),
            "tpot_p50": float(np.percentile(tpot, 50)),
            "tpot_p99": float(np.percentile(tpot, 99)),
            "latency_mean": float(total.mean()),
            "latency_p95": float(np.percentile(total, 95)),
            "tokens": sum(len(r.generated) for r in done),
        }
