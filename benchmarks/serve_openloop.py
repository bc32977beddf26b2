"""Open-loop serving benchmark: Poisson-ish arrivals against the paged
chiplet-aware KV allocator, comparing LAZY (chunked prefill + elastic page
growth) against EAGER (full capped reservation at admission) for the same
byte budget — and, within lazy mode, SWAP-tier eviction (spill parked
pages to host, resume mid-decode) against RESTART eviction (recompute from
scratch, the PR-3 policy).

A client coroutine on the engine's shared TaskRuntime submits requests over
time from a seeded schedule (exponential inter-arrival gaps measured in
engine rounds) with a LONG-TAIL ``max_new`` mix — most requests are short,
a minority run to a large token budget.  That is exactly the workload where
eager reservation wastes memory: every long-tail request pins its worst-
case page count at admission, while the lazy allocator commits one chunk's
pages and grows as ``pos`` crosses page boundaries, parking mid-decode on
exhaustion.  The benchmark reports the *admitted concurrency* (peak
simultaneously-reserved streams) both ways, plus TTFT/TPOT tails, park /
lazy-growth counts, spill/restore/eviction counts with the WASTED-
RECOMPUTE metric (``recompute_tokens`` — the tokens restart eviction
throws away, driven to 0 by the swap tier), and the per-chunk prefill
footprint from ``costmodel.prefill_chunk_bytes``.

The default run compares all three (lazy-swap / lazy-restart / eager) on
one schedule and asserts token identity across them, ``recompute_tokens
== 0`` in swap mode, and that every restart-mode eviction became a
spill/restore cycle instead of recompute.

Chunk ticks run on one of TWO COMPILED PATHS (``--prefill-mode``):
"parallel" (default) fuses a whole C-token chunk into ONE model forward —
intra-chunk causal attention over the gathered ring prefix plus chunk
scans for rgLRU/SSD state — while "scan" keeps the per-token reference (C
sequential model steps per chunk tick).  Whenever the lazy run uses the
parallel path, a scan twin runs on the same schedule and the benchmark
asserts token identity plus the model-step claim (1 step per chunk tick
vs C).  The parallel path's attention runs on one of two kernels
(``--chunk-kernel``): "blocked" (default) streams the ring + chunk KV
through a Pallas online-softmax kernel in (block_q, block_kv) tiles,
"dense" materializes the full (C, W + C) einsum score block.  Mixed ticks
(prefill chunks and decoders in one batch) split into two compiled steps
by default (``--no-split-ticks`` pads decoders into the chunk forward
instead, paying C-1 masked query rows each).  The default parallel run
adds a kernel twin and a split twin on the same schedule and asserts
token identity, the blocked < dense transient claim, and zero masked
decode rows under splitting.  ``--chunk-sweep`` sweeps chunk sizes x
{path, kernel, split} at equal byte budget (``--prefill-chunk`` pins a
single size).

``--spec-decode ngram`` runs the SPECULATIVE DECODING comparison
instead: the prompt-lookup (n-gram) drafter proposes up to ``--spec-k``
tokens per decode tick from the stream's own committed history, one
all-position-logits fused forward verifies them, and greedy acceptance
keeps the longest matching prefix — token-identical to the spec-off
engine by construction, asserted on every run.  The schedule is
lookup-friendly (short prompts, long generations, params doctored so
greedy decode is self-repetitive — see ``lookup_friendly``); the run
asserts measured acceptance > 0, accepted-tokens-per-model-step > 1.0
with the per-path step costs counted from optimized HLO (spec-off pins
this metric at exactly 1.0), and a tpot_p50 strictly below the spec-off
twin on the same schedule.

``--prefix-share`` runs the SHARED-PREFIX TENANT workload instead: T
tenants, each with a fixed multi-page preamble (per-tenant lengths), one
warm request per tenant publishing the preamble pages into the prefix
index, then a burst of identical-prompt requests per tenant that must
ATTACH those pages.  Two cells per mode: page-sized chunks (the prefill-
skip measurement — every cache-hit request may run only its 1-chunk
unshared tail, >=80% of prefill chunks skipped) and a whole-prompt first
chunk (admission charges the full prompt, so the peak admitted
concurrency at the same per-domain byte budget is the gate — sharing
must admit STRICTLY more streams).  Token identity sharing-on vs
sharing-off is asserted across both cells; ``--no-prefix-share`` reports
the unshared baseline only.

    PYTHONPATH=src python benchmarks/serve_openloop.py                  # all 3
    PYTHONPATH=src python benchmarks/serve_openloop.py --prefill-chunked
    PYTHONPATH=src python benchmarks/serve_openloop.py --eager
    PYTHONPATH=src python benchmarks/serve_openloop.py --chunk-sweep
    PYTHONPATH=src python benchmarks/serve_openloop.py --prefix-share --smoke
    PYTHONPATH=src python benchmarks/serve_openloop.py --smoke          # CI
    PYTHONPATH=src python benchmarks/serve_openloop.py --prefill-chunked \
        --evict-mode swap --smoke                                       # CI
    PYTHONPATH=src python benchmarks/serve_openloop.py --prefill-chunked \
        --prefill-mode parallel --smoke                                 # CI
    PYTHONPATH=src python benchmarks/serve_openloop.py --prefill-chunked \
        --chunk-kernel dense --no-split-ticks --smoke
    PYTHONPATH=src python benchmarks/serve_openloop.py --spec-decode \
        ngram --smoke                                                   # CI
    PYTHONPATH=src python benchmarks/serve_openloop.py --async-swap \
        --smoke                                                         # CI

``--async-swap`` runs the ASYNC TWO-TIER MEMORY comparison instead: the
transfer engine issues each victim's D2H spill and keeps decoding —
pages re-grant only when the per-round poll (or a fence) lands the copy
— against the synchronous swap twin on the same oversubscription
schedule.  Gates, all asserted in-run: token identity, zero recomputed
tokens, spill cycles actually happened, ``pool.audit()`` exact while
transfers were in flight, and no added tpot_p50 vs the sync twin.  The
report adds the overlap-efficiency surface: decode ticks run with bytes
on the wire, overlap rounds per spill, fence-wait count, peak in-flight
footprint and the costmodel-priced host-link seconds.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).parent))

from common import emit, row

from repro.configs import REGISTRY, reduced_config
from repro.core.controller import ControllerConfig
from repro.core.costmodel import (fwd_flops_per_token, kv_cache_bytes,
                                  prefill_chunk_bytes)
from repro.configs.base import ShapeConfig
from repro.core.topology import ChipletTopology
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.engine import EngineConfig, ServeEngine


def longtail_schedule(seed: int, n: int, mean_gap: float,
                      vocab: int, max_len: int):
    """Seeded (gap_rounds, prompt, max_new) arrivals; exponential gaps and
    a long-tail ``max_new`` mix: ~3/4 short generations, ~1/4 that run
    close to the ring width (the requests whose eager reservations pin
    whole domains)."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        gap = int(rng.exponential(mean_gap))
        # prompts up to half the ring: long ones span several prefill chunks
        plen = int(rng.integers(4, max(5, max_len // 2)))
        tail_lo = min(max_len // 2, max_len - plen - 1)
        if tail_lo > 4 and rng.random() < 0.25:
            max_new = int(rng.integers(tail_lo, max_len - plen))
        else:
            max_new = int(rng.integers(4, max(5, max_len // 8)))
        out.append((gap, rng.integers(2, vocab, size=plen), max_new))
    return out


SLO_MAX_LEN = 48                        # page geometry the schedule lengths
SLO_GROUPS = 4                          # below are tuned against


def slo_schedule(seed: int, n_batch: int, n_interactive: int, vocab: int):
    """Mixed-tenant arrivals for the SLO-class cells, as TWO waves.

    The ``batch`` wave arrives at tight gaps: 2-page prompts whose
    chunked prefill parks mid-prefill fast under oversubscription, so
    the wait line grows a PARKED head holding pages.  The
    ``interactive`` wave is 1-page requests released only once that
    congestion exists (``run_slo_mode``'s trigger client): arrivals
    whose charged pages fit the bypass-safety bound while FIFO would
    hold them behind the parked head."""
    rng = np.random.default_rng(seed)
    bigs, inter = [], []
    for i in range(n_batch):
        gap = 0 if i == 0 else int(rng.integers(0, 2))
        plen = int(rng.integers(17, 21))
        max_new = int(rng.integers(10, 13))
        bigs.append((gap, rng.integers(2, vocab, size=plen), max_new,
                     "batch"))
    for _ in range(n_interactive):
        gap = int(rng.integers(0, 3))
        plen = int(rng.integers(4, 8))
        max_new = int(rng.integers(2, 5))
        inter.append((gap, rng.integers(2, vocab, size=plen), max_new,
                      "interactive"))
    return bigs, inter


def spec_schedule(seed: int, n: int, mean_gap: float,
                  vocab: int, max_len: int):
    """Seeded arrivals for the speculative-decoding cells: SHORT prompts,
    LONG generations — tpot-dominated streams where the drafter gets a
    history to look up and the verify width amortizes.

    Arrivals are SERIALIZED (gap = max_len rounds, so each stream decodes
    alone): the gate metric is tpot_p50, a per-stream latency, and under
    oversubscription the park/queue share of tpot swamps the per-token
    signal with admission noise that has nothing to do with speculation.
    The admission-pressure cells (default mode, --prefix-share) measure
    contention; these cells measure the decode loop."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        gap = 0 if i == 0 else max_len
        plen = int(rng.integers(4, 9))
        prompt = rng.integers(2, vocab, size=plen)
        # near-full-ring generations: the lookup drafter only starts once
        # the stream's token orbit closes (~sqrt(V) tokens for a random
        # map), so the drafted fraction — and the measured win — scales
        # with how far past that onset each stream decodes
        max_new = int(rng.integers(2 * max_len // 3, max_len - plen))
        out.append((gap, prompt, max_new))
    return out


def lookup_friendly(params):
    """Make the reduced model PREDICTABLE: zero every residual-branch
    output projection ('wo'), so each block passes the residual through
    and the logits become a fixed function of the LAST token alone.
    Greedy decode then walks a deterministic token map, which enters a
    short cycle — the self-repetitive regime prompt-lookup drafting
    exploits on real models (grounded / repetitive text).  Random-weight
    reduced models are incompressible token sources (their greedy output
    never repeats), so without this the n-gram drafter measures only the
    reject path.  Both spec cells share the SAME doctored params, so the
    token-identity gate is unweakened."""
    import jax

    def z(path, leaf):
        if "'wo'" in jax.tree_util.keystr(path):
            return leaf * 0
        return leaf
    return jax.tree_util.tree_map_with_path(z, params)


def run_mode(args, cfg, *, lazy: bool, evict_mode: str = "swap",
             prefill_mode: str = None, prefill_chunk: int = None,
             chunk_kernel: str = None, split_ticks: bool = None,
             spec_decode: str = "off", spec_k: int = None,
             schedule=None, params_fn=None, warm: bool = False):
    topo = ChipletTopology(n_pods=1, groups_per_pod=4, chips_per_group=1)
    # max_batch is 2x the memory budget's stream count: the paged pool
    # admits by pages actually reserved, not worst-case slots
    max_batch = 2 * args.pool_streams
    ecfg = EngineConfig(
        max_batch=max_batch, max_len=args.max_len, adaptive=True, lazy=lazy,
        pool_streams=args.pool_streams, evict_mode=evict_mode,
        headroom=args.headroom,
        prefill_mode=prefill_mode or args.prefill_mode,
        prefill_chunk=(prefill_chunk if prefill_chunk is not None
                       else args.prefill_chunk),
        chunk_kernel=chunk_kernel or args.chunk_kernel,
        split_ticks=(args.split_ticks if split_ticks is None
                     else split_ticks),
        spec_decode=spec_decode,
        spec_k=(spec_k if spec_k is not None else args.spec_k),
        spec_ngram=args.spec_ngram,
        slo_bypass=args.slo_bypass,
        controller=ControllerConfig(scheduler_timer=8, threshold=64.0,
                                    min_dwell=2))
    eng = ServeEngine(cfg, topo, ecfg, spread_rate=1, seed=args.seed)
    if params_fn is not None:
        eng.params = params_fn(eng.params)
    n_warm = 0
    if warm:
        # compile every (path, pow-2 bucket) combo the timed run can
        # touch, then zero the counters so the cells measure steady-state
        # serving, not XLA backend compiles mid-request.  warm_steps
        # drives the engine's REAL dispatch partials over the full
        # (kind, width, batch-bucket) grid with null rows; the traffic
        # phases then warm the host-side tails (commit bookkeeping,
        # eager jnp ops) the step grid can't reach: one solo request,
        # then a staggered pair for the mixed (split chunk+decode) tick.
        eng.warm_steps()
        eng.submit(np.arange(2, 6), 24)
        eng.run_until_done()
        eng.open_loop_client([(0, np.arange(2, 10), 20),
                              (3, np.arange(3, 8), 16)])
        eng.run_until_done()
        eng.counters.reset()
        n_warm = 3
    sched = (schedule if schedule is not None
             else longtail_schedule(args.seed, args.requests, args.mean_gap,
                                    cfg.vocab, args.max_len))
    eng.open_loop_client(sched)
    res = eng.run_until_done()
    reqs = eng.submitted[n_warm:]
    assert len(reqs) == args.requests
    assert all(r.done for r in reqs), \
        f"{sum(not r.done for r in reqs)} requests unfinished"
    return eng, res


def report(mode: str, args, eng, res):
    st = ServeEngine.stats(eng.submitted)
    kv = eng.kv_stats()
    c = res["counters"]
    emit([
        row(f"openloop_ttft_p50[{mode}]", st["ttft_p50"] * 1e6,
            f"p99={st['ttft_p99']*1e6:.0f}us n={st['n']}"),
        row(f"openloop_tpot_p50[{mode}]", st["tpot_p50"] * 1e6,
            f"p99={st['tpot_p99']*1e6:.0f}us tokens={st['tokens']}"),
        row(f"openloop_admitted[{mode}]", kv["peak_active_tables"],
            f"peak concurrent reservations (budget="
            f"{args.pool_streams} streams/domain), peak_blocks="
            f"{kv['peak_used_blocks']:.0f}/{kv['total_blocks']:.0f}"),
        row(f"openloop_backpressure[{mode}]", kv["alloc_failures"],
            f"park_rate={kv['park_rate']:.2f} "
            f"mid_decode_parks={kv['mid_decode_parks']:.0f} "
            f"lazy_grows={kv['lazy_grows']:.0f} "
            f"evictions={kv['evictions']:.0f} "
            f"unblocked={c.get('tasks_unblocked', 0):.0f}"),
        row(f"openloop_recompute[{mode}]", kv["recompute_tokens"],
            f"tokens thrown away by restart evictions; spills="
            f"{kv['spills']:.0f} spilled_pages={kv['spilled_pages']:.0f} "
            f"restores={kv['restores']:.0f} "
            f"peak_spilled_bytes={kv['peak_spilled_bytes']:.0f}"),
        row(f"openloop_migration[{mode}]", kv["blocks_migrated"],
            f"tables_migrated={kv['tables_migrated']:.0f} "
            f"spill_repoints={kv['spill_repoints']:.0f} "
            f"relayouts={len(res['relayouts'])}"),
    ])
    if mode == "lazy":
        max_prompt = max(len(r.prompt) for r in eng.submitted)
        whole = kv_cache_bytes(
            eng.cfg, ShapeConfig("kv", "decode", max_prompt, 1), 1)
        emit([row("openloop_prefill_chunk_bytes",
                  kv["prefill_chunk_bytes"],
                  f"chunks={kv['prefill_chunks']:.0f} "
                  f"score_transient={kv['prefill_score_bytes']:.0f}B "
                  f"vs whole-prompt buffer {whole:.0f}B at S={max_prompt}")])
    if eng._lazy:
        emit([row(f"openloop_prefill_model_steps[{mode}]",
                  kv["prefill_model_steps"],
                  f"chunk_ticks={kv['chunk_ticks']:.0f} "
                  f"({eng._prefill_mode}: "
                  f"{kv['prefill_model_steps'] / max(1, kv['chunk_ticks']):.1f}"
                  f" model steps per chunk tick, chunk={eng._chunk}, "
                  f"kernel={kv['chunk_kernel']})")])
    if eng._lazy and eng._prefill_mode == "parallel":
        # masked decode-query rows a mixed tick would have paid in the
        # fused chunk forward, priced as forward FLOPs at ring depth
        saved_rows = kv["mixed_tick_decode_rows_saved"]
        n_split = res["counters"].get("split_ticks", 0)
        flops_per_row = fwd_flops_per_token(eng.cfg, args.max_len,
                                            decode=True)
        emit([row(f"openloop_split_ticks[{mode}]", n_split,
                  f"decode_rows_saved={saved_rows:.0f} "
                  f"(~{saved_rows * flops_per_row / 1e6:.1f} MFLOP, "
                  f"{saved_rows * flops_per_row / max(1, n_split) / 1e6:.1f}"
                  f" MFLOP/split-tick); residual masked rows="
                  f"{kv['decode_masked_query_rows']:.0f}")])
    moves = [(r["old_groups"], r["new_groups"], r["blocks_migrated"])
             for r in res["relayouts"]]
    print(f"[{mode}] relayouts (old_groups, new_groups, blocks_migrated): "
          f"{moves}")


def prefix_tenant_prompts(seed: int, tenant_pages, bt: int, vocab: int):
    """One FIXED prompt per tenant: a preamble spanning ``tenant_pages[i]``
    full KV pages plus one trailing token — the fully-shared-prefix case
    (the final prompt token always recomputes to seed generation, so the
    shareable prefix is exactly the full pages)."""
    rng = np.random.default_rng(seed)
    return [rng.integers(2, vocab, size=p * bt + 1) for p in tenant_pages]


def run_prefix_mode(args, cfg, *, share: bool, prefill_chunk,
                    max_len: int, pool_streams: int, per_tenant: int,
                    tenant_pages, max_new: int):
    """Warmed tenant workload on ONE chiplet-group domain: a warm wave
    (one request per tenant) publishes the preamble pages, then a burst
    of ``per_tenant`` identical-prompt requests per tenant measures
    cache-hit prefill and admission.  Returns the engine, its kv stats,
    the burst wave's prefill-chunk count, the peak shared-page gauge and
    all generated tokens."""
    topo = ChipletTopology(n_pods=1, groups_per_pod=1, chips_per_group=1)
    ecfg = EngineConfig(
        max_batch=2 * per_tenant * len(tenant_pages), max_len=max_len,
        adaptive=False, lazy=True, pool_streams=pool_streams,
        evict_mode="swap", prefill_chunk=prefill_chunk,
        prefill_mode=args.prefill_mode, chunk_kernel=args.chunk_kernel,
        split_ticks=args.split_ticks, prefix_share=share,
        cached_retention=args.cached_retention,
        slo_bypass=args.slo_bypass)
    eng = ServeEngine(cfg, topo, ecfg, spread_rate=1, seed=args.seed)
    prompts = prefix_tenant_prompts(args.seed, tenant_pages,
                                    eng.pool.block_tokens, cfg.vocab)
    # prompts must stay inside the ring: a wrap would (correctly)
    # invalidate the published pages and the bench would measure nothing
    assert all(len(p) + max_new <= eng.pool.pages_per_stream
               * eng.pool.block_tokens for p in prompts)
    for p in prompts:                    # warm wave: publish the pages
        eng.submit(p, max_new)
    eng.run_until_done()
    warm_chunks = eng.counters.totals.get("prefill_chunks", 0.0)
    for p in prompts:                    # measurement burst: cache hits
        for _ in range(per_tenant):
            eng.submit(p, max_new)
    eng.run_until_done()
    assert all(r.done for r in eng.submitted), "prefix bench deadlock"
    eng.pool.audit([])
    assert eng.pool.occupancy() == 0.0
    burst_chunks = (eng.counters.totals.get("prefill_chunks", 0.0)
                    - warm_chunks)
    peak_shared = (max((s.kv_shared_pages for s in eng.counters.samples),
                       default=0.0),
                   max((s.kv_shared_bytes for s in eng.counters.samples),
                       default=0.0))
    return (eng, eng.kv_stats(), burst_chunks, peak_shared,
            [r.generated for r in eng.submitted])


def run_prefix_bench(args, cfg, *, compare: bool):
    """The shared-prefix tenant workload (``--prefix-share`` /
    ``--no-prefix-share``).  With ``compare`` (sharing requested) runs
    every cell sharing-on AND sharing-off and asserts the ISSUE-7 gates:
    token identity, >=80% of prefill chunks skipped for a fully-shared
    prefix, and strictly more admitted concurrency at the same
    per-domain byte budget."""
    per_tenant = 2 if args.smoke else 4
    tenant_pages = (5, 4)          # per-tenant preamble lengths, in pages
    common = dict(max_len=96, pool_streams=3, per_tenant=per_tenant,
                  tenant_pages=tenant_pages, max_new=8)
    n_burst = per_tenant * len(tenant_pages)
    cells = {}
    for share in ((True, False) if compare else (False,)):
        tag = "share" if share else "no-share"
        # cell A — page-sized chunks: the prefill-skip measurement
        eng_a, kv_a, chunks_a, shared_a, toks_a = run_prefix_mode(
            args, cfg, share=share, prefill_chunk=None, **common)
        # cell B — whole-prompt first chunk: admission charges the full
        # prompt up front, so concurrency is admission-limited and the
        # cached-prefix discount (charge only the unshared tail) is
        # exactly what admits more streams
        eng_b, kv_b, chunks_b, shared_b, toks_b = run_prefix_mode(
            args, cfg, share=share, prefill_chunk=common["max_len"],
            **common)
        burst_a = eng_a.submitted[len(tenant_pages):]
        emit([
            row(f"prefix_burst_chunks[{tag}]", chunks_a,
                f"{n_burst} cache-burst requests x tenants "
                f"pages={tenant_pages}; hits={kv_a['prefix_hits']:.0f} "
                f"tokens_skipped={kv_a['prefill_tokens_skipped']:.0f} "
                f"pages_attached={kv_a['prefix_pages']:.0f}"),
            row(f"prefix_burst_ttft_p50[{tag}]",
                ServeEngine.stats(burst_a)["ttft_p50"] * 1e6,
                f"burst wave only; cow_forks={kv_a['cow_forks']:.0f} "
                f"peak_shared_pages={shared_a[0]:.0f} "
                f"peak_dedup_bytes_saved={shared_a[1]:.0f}"),
            row(f"prefix_admitted[{tag}]", kv_b["peak_active_tables"],
                f"whole-prompt admission cell (budget="
                f"{common['pool_streams']} streams/domain), peak_blocks="
                f"{kv_b['peak_used_blocks']:.0f}/"
                f"{kv_b['total_blocks']:.0f} "
                f"alloc_failures={kv_b['alloc_failures']:.0f}"),
            row(f"prefix_cached_pages[{tag}]", kv_a["cached_page_hits"],
                f"free-but-cached pages re-attached without any copy "
                f"({kv_a['retention']} retention: reclaims="
                f"{kv_a['cached_reclaims']:.0f} of the coldest-touched "
                f"free pages first)"),
        ])
        cells[share] = (kv_a, chunks_a, toks_a, kv_b, toks_b)
    if not compare:
        return
    kv_a, on_a, toks_a, kv_b, toks_b = cells[True]
    kv_a0, off_a, toks_a0, kv_b0, toks_b0 = cells[False]
    # gate 1: token identity, sharing on vs off, both cells (and across
    # cells — the chunking policy must not change tokens either)
    assert toks_a == toks_a0, "prefix sharing changed tokens (chunk cell)"
    assert toks_b == toks_b0, \
        "prefix sharing changed tokens (admission cell)"
    assert toks_a == toks_b, "chunk-size cells diverged"
    assert kv_a0["prefix_hits"] == 0 and kv_b0["prefix_hits"] == 0
    # gate 2: every cache-burst request ran ONLY its 1-chunk unshared
    # tail — >=80% of the prefill chunks the unshared run pays are
    # skipped outright
    skip = 1.0 - on_a / max(1.0, off_a)
    assert on_a == n_burst, \
        f"cache-hit burst ran {on_a:.0f} chunks, wanted {n_burst} tails"
    assert skip >= 0.80, \
        f"prefill-chunk skip {skip:.1%} below the 80% gate " \
        f"({on_a:.0f} vs {off_a:.0f} chunks)"
    # gate 3: strictly more admitted concurrency at the same byte budget
    assert kv_b["peak_active_tables"] > kv_b0["peak_active_tables"], \
        f"sharing admitted {kv_b['peak_active_tables']:.0f} streams, " \
        f"unshared {kv_b0['peak_active_tables']:.0f} — not strictly more"
    assert kv_a["prefix_hits"] >= n_burst
    print(f"prefix sharing token-identical: True "
          f"(chunk skip={skip:.1%}, admitted "
          f"{kv_b['peak_active_tables']:.0f} vs "
          f"{kv_b0['peak_active_tables']:.0f} streams at "
          f"{common['pool_streams']} streams/domain)")


def run_slo_mode(args, cfg, sched, *, bypass: bool):
    """One SLO-class cell.  The regime is PINNED (not taken from the
    generic args): four single-chip chiplet-group domains each sized for
    ONE max-length stream (``pool_streams=1`` — two batch tables
    oversubscribe a domain), swap-tier eviction, chunked-lazy growth,
    and the size-aware bypass toggled by ``bypass``.  adaptive=False
    keeps the twin runs deterministic (no controller relayouts).

    The interactive wave is submitted by a TRIGGER client that waits for
    the first mid-flight park: the wave lands exactly when the wait line
    has a parked head.  Twin dynamics are identical up to the first
    bypass grant (the no-bypass engine still WAKES bypass-class waiters,
    it just never grants them), so the trigger fires at the same round
    in both cells and the head-starvation gate compares like for like."""
    bigs, inter = sched
    topo = ChipletTopology(n_pods=1, groups_per_pod=SLO_GROUPS,
                           chips_per_group=1)
    ecfg = EngineConfig(
        max_batch=4, max_len=SLO_MAX_LEN, adaptive=False, lazy=True,
        pool_streams=1, evict_mode="swap", slo_bypass=bypass)
    eng = ServeEngine(cfg, topo, ecfg, spread_rate=1, seed=args.slo_seed)
    eng.open_loop_client(bigs)
    eng._clients += 1

    def iclient():
        try:
            while not eng._parked:
                yield
            for gap, prompt, max_new, cls in inter:
                for _ in range(int(gap)):
                    yield
                eng.submit(prompt, max_new, cls=cls)
        finally:
            eng._clients -= 1

    eng.sched.spawn(iclient(), name="slo-interactive", priority=2)
    eng.run_until_done()
    assert all(r.done for r in eng.submitted), "slo bench deadlock"
    return eng


def admission_delay_rounds(eng, cls: str):
    """Deterministic TTFT proxy: engine rounds from submit to the first
    page grant, per request of ``cls`` — round-counted, so the bypass-on
    vs bypass-off comparison is seed-exact (no wall-clock noise)."""
    return [r.grant_rounds[0] - r.arrive_round
            for r in eng.submitted if r.cls == cls and r.grant_rounds]


def run_slo_bench(args, cfg):
    """The mixed-tenant SLO-class workload (``--slo-classes``): the SAME
    seeded schedule through the size-aware bypass engine and a FIFO-only
    twin.  Gates, all asserted in-run:

      1. token identity per rid (the bypass must be invisible in output);
      2. the bypass actually fired (and the twin never did);
      3. strictly more peak concurrent reservations with bypass;
      4. ZERO head starvation — the head the FIRST bypass jumped is
         re-granted at the same round or EARLIER than in the FIFO twin
         (dynamics are twin-identical up to that round, so the comparison
         is exact);
      5. interactive admission delay (round-counted TTFT proxy) p99
         strictly improves, with per-class wall-clock TTFT/TPOT p50/p99
         reported from ``kv_stats()['per_class']``.
    """
    sched = slo_schedule(args.slo_seed, 8, 8, cfg.vocab)
    cells = {}
    for bypass in (True, False):
        tag = "bypass" if bypass else "fifo"
        eng = run_slo_mode(args, cfg, sched, bypass=bypass)
        kv = eng.kv_stats()
        for c, st in sorted(kv["per_class"].items()):
            if not st.get("n"):
                continue
            emit([row(f"slo_ttft_p50[{tag},{c}]", st["ttft_p50"] * 1e6,
                      f"p99={st['ttft_p99']*1e6:.0f}us n={st['n']:.0f} "
                      f"admit_delay_p99="
                      f"{np.percentile(admission_delay_rounds(eng, c), 99):.0f}"
                      f" rounds"),
                  row(f"slo_tpot_p50[{tag},{c}]", st["tpot_p50"] * 1e6,
                      f"p99={st['tpot_p99']*1e6:.0f}us "
                      f"tokens={st['tokens']:.0f}")])
        emit([row(f"slo_admitted[{tag}]", kv["peak_active_tables"],
                  f"peak concurrent reservations; bypass_grants="
                  f"{kv['bypass_grants']:.0f} "
                  f"floor_pages={kv['bypass_floor_pages']:.0f} "
                  f"head_wait_ticks={kv['head_wait_ticks']:.0f} "
                  f"spills={kv['spills']:.0f} "
                  f"(watchdog={kv['watchdog_spills']:.0f})")])
        cells[bypass] = (eng, kv)
    on, kv_on = cells[True]
    off, kv_off = cells[False]
    toks = {b: [r.generated for r in sorted(cells[b][0].submitted,
                                            key=lambda r: r.rid)]
            for b in cells}
    # gate 1 — the CI divergence gate
    assert toks[True] == toks[False], "slo bypass changed tokens"
    # gate 2 — the mechanism fired, and only when enabled
    assert kv_on["bypass_grants"] > 0, \
        "bypass never fired — the schedule stopped congesting the line"
    assert kv_off["bypass_grants"] == 0, "FIFO twin granted a bypass"
    # gate 3 — strictly more admitted concurrency on the same schedule
    assert kv_on["peak_active_tables"] > kv_off["peak_active_tables"], \
        f"bypass admitted {kv_on['peak_active_tables']:.0f} concurrent " \
        f"streams, FIFO {kv_off['peak_active_tables']:.0f} — not " \
        f"strictly more"
    # gate 4 — zero head starvation: the first jumped head's re-grant
    r0, _, head_rid = on.bypass_log[0]
    grant_on = next((t for t in on.submitted[head_rid].grant_rounds
                     if t >= r0), None)
    grant_off = next((t for t in off.submitted[head_rid].grant_rounds
                      if t >= r0), None)
    assert grant_on is not None and grant_off is not None, \
        f"jumped head rid={head_rid} has no re-grant after round {r0}"
    delay = grant_on - grant_off
    assert delay <= 0, \
        f"bypass delayed the jumped head rid={head_rid}: granted at " \
        f"round {grant_on} vs {grant_off} in the FIFO twin"
    # gate 5 — the interactive win, round-counted (seed-exact)
    d_on = admission_delay_rounds(on, "interactive")
    d_off = admission_delay_rounds(off, "interactive")
    p99_on, p99_off = np.percentile(d_on, 99), np.percentile(d_off, 99)
    assert p99_on < p99_off, \
        f"interactive admission-delay p99 {p99_on:.0f} rounds not below " \
        f"FIFO's {p99_off:.0f}"
    print(f"slo bypass token-identical: True "
          f"(bypass_grants={kv_on['bypass_grants']:.0f}, admitted "
          f"{kv_on['peak_active_tables']:.0f} vs "
          f"{kv_off['peak_active_tables']:.0f} streams, head delay="
          f"{delay} rounds, interactive admit-delay p99 "
          f"{p99_on:.0f} vs {p99_off:.0f} rounds)")


def accepted_per_model_step(eng, kv) -> float:
    """Committed decode tokens per sequential MODEL STEP, with the steps
    each compiled path costs counted from its optimized HLO
    (``ServeEngine.measured_model_steps``), not assumed: plain decode
    rows pay steps(decode) each, drafted rows steps(spec) per verify and
    steps(chunk) per rollback re-apply.  A spec-off engine scores exactly
    1.0 on this metric (every committed token is one decode-row forward),
    so > 1.0 is the speculation win."""
    den = kv["decode_row_forwards"] * eng.measured_model_steps("decode")
    if kv["spec_row_forwards"]:         # spec-off engines build no verify
        den += kv["spec_row_forwards"] * eng.measured_model_steps("spec")
    if kv["spec_row_reapplies"]:
        den += (kv["spec_row_reapplies"]
                * eng.measured_model_steps("chunk"))
    return kv["decode_committed_tokens"] / max(1.0, den)


def run_spec_bench(args, cfg):
    """The speculative-decoding headline (``--spec-decode ngram``): the
    n-gram drafter + verify path against the spec-off engine on the same
    lookup-friendly schedule and SAME (predictable) params.  Gates, all
    asserted in-run: token identity, measured acceptance > 0,
    HLO-counted accepted-tokens-per-model-step > 1.0 (spec-off pins the
    metric at exactly 1.0), and tpot_p50 strictly below spec-off.

    Both cells run the DENSE chunk kernel: the interpret-mode Pallas
    kernel prices each extra query row at a full kernel pass, which is a
    CPU-emulation artifact the kernel twin gate already covers — kernel
    choice is orthogonal to (and identity-asserted against) the
    speculation machinery."""
    # Speculation amortizes over DECODE length: the drafter needs one
    # cycle lap of history before it starts proposing, so short smoke
    # generations spend most tokens in the undrafted warmup.  Give the
    # spec cells a longer ring than the admission-pressure cells
    # (--max-len above the floor is honored).
    args = argparse.Namespace(**{**vars(args),
                                 "max_len": max(args.max_len, 144)})
    sched = spec_schedule(args.seed, args.requests, args.mean_gap,
                          cfg.vocab, args.max_len)
    cells = {}
    for spec in (args.spec_decode, "off"):
        tag = f"spec-{spec}"
        eng, res = run_mode(args, cfg, lazy=True,
                            evict_mode=args.evict_mode,
                            chunk_kernel="dense", spec_decode=spec,
                            schedule=sched, params_fn=lookup_friendly,
                            warm=True)
        reqs = eng.submitted[3:]                   # drop the warm requests
        st = ServeEngine.stats(reqs)
        kv = eng.kv_stats()
        toks = [r.generated for r in sorted(reqs, key=lambda r: r.rid)]
        ratio = accepted_per_model_step(eng, kv)
        emit([
            row(f"openloop_tpot_p50[{tag}]", st["tpot_p50"] * 1e6,
                f"p99={st['tpot_p99']*1e6:.0f}us tokens={st['tokens']}"),
            row(f"spec_accepted_per_model_step[{tag}]", ratio,
                f"committed={kv['decode_committed_tokens']:.0f} over "
                f"decode_rows={kv['decode_row_forwards']:.0f} "
                f"verify_rows={kv['spec_row_forwards']:.0f} "
                f"reapply_rows={kv['spec_row_reapplies']:.0f} "
                f"(HLO steps: decode="
                f"{eng.measured_model_steps('decode'):.0f}"
                + (f" chunk={eng.measured_model_steps('chunk'):.0f}"
                   f" spec={eng.measured_model_steps('spec'):.0f})"
                   if spec != "off" else ")")),
        ])
        if spec != "off":
            emit([
                row(f"spec_accept_rate[{tag}]", kv["spec_accept_rate"],
                    f"drafted={kv['spec_tokens_drafted']:.0f} "
                    f"accepted={kv['spec_tokens_accepted']:.0f} "
                    f"rollbacks={kv['spec_rollbacks']:.0f} "
                    f"full_rejects={kv['spec_full_rejects']:.0f} "
                    f"k={args.spec_k}"),
                row(f"spec_wasted_bytes[{tag}]", kv["spec_rejected_bytes"],
                    f"rejected-draft compute+KV bytes; rollback traffic="
                    f"{kv['spec_rollback_bytes']:.0f}B "
                    f"(ckpts={kv['spec_ckpts']:.0f} "
                    f"ckpt_pages={kv['spec_ckpt_pages']:.0f} "
                    f"restored={kv['spec_rollback_pages']:.0f})"),
            ])
        cells[spec] = (st, kv, toks, ratio)
    st_on, kv_on, toks_on, ratio_on = cells[args.spec_decode]
    st_off, kv_off, toks_off, ratio_off = cells["off"]
    # gate 1 — the CI divergence gate: greedy acceptance must make the
    # speculative engine TOKEN-IDENTICAL to the plain one
    assert toks_on == toks_off, "speculative decode changed tokens"
    # gate 2: the drafter must actually land accepts on this schedule (a
    # 0-acceptance run measures only the reject path)
    assert kv_on["spec_tokens_accepted"] > 0, \
        "acceptance rate is exactly 0 — the lookup-friendly schedule " \
        "stopped being lookup-friendly"
    # gate 3: the measured win — strictly more than one committed token
    # per HLO-counted model step, against the off-cell's exact 1.0
    assert ratio_off == 1.0, \
        f"spec-off accepted/model-step {ratio_off:.3f} != 1.0 — the " \
        f"denominator accounting drifted"
    assert ratio_on > 1.0, \
        f"accepted tokens per model step {ratio_on:.3f} not > 1.0"
    # gate 4: the wall-clock win, same schedule, both cells steady-state
    assert st_on["tpot_p50"] < st_off["tpot_p50"], \
        f"spec tpot_p50 {st_on['tpot_p50']*1e6:.0f}us not below " \
        f"spec-off {st_off['tpot_p50']*1e6:.0f}us"
    print(f"speculative decode token-identical: True "
          f"(accept_rate={kv_on['spec_accept_rate']:.2f}, "
          f"{ratio_on:.2f} accepted tokens/model step vs 1.00 off, "
          f"tpot_p50 {st_on['tpot_p50']*1e6:.0f}us vs "
          f"{st_off['tpot_p50']*1e6:.0f}us off)")


def oversub_schedule(seed: int, n: int, vocab: int, max_len: int):
    """Dense arrivals at short gaps with generations sized to thrash a
    1-stream/domain budget: the schedule that deterministically forces
    spill/restore cycles (the PR-4 acceptance workload)."""
    rng = np.random.default_rng(seed)
    return [(int(rng.integers(0, 2)),
             rng.integers(2, vocab, size=4),
             max(8, int(max_len * 0.55))) for _ in range(n)]


def run_async_mode(args, cfg, *, async_swap: bool):
    """One async-swap bench cell: a single replica group at a
    1-stream/domain budget on the oversubscription schedule, with the
    pool audited at EVERY transfer transition (issue / poll / fence) —
    including while bytes are in flight."""
    topo = ChipletTopology(n_pods=1, groups_per_pod=1, chips_per_group=1)
    ecfg = EngineConfig(
        max_batch=4, max_len=args.max_len, adaptive=False, lazy=True,
        pool_streams=args.pool_streams, evict_mode="swap",
        headroom=args.headroom, async_swap=async_swap,
        spill_watermarks=(0.5, 0.25),
        controller=ControllerConfig(scheduler_timer=8, threshold=64.0,
                                    min_dwell=2))
    eng = ServeEngine(cfg, topo, ecfg, spread_rate=1, seed=args.seed)
    pool = eng.pool
    audits = {"calls": 0, "inflight": 0}

    def live():
        return [r.table for r in eng.submitted if r.table is not None]

    for name in ("spill_issue", "spill_poll", "spill_fence", "spill"):
        orig = getattr(pool, name)

        def wrapped(*a, _orig=orig, **kw):
            out = _orig(*a, **kw)
            if pool.inflight_tables():
                audits["inflight"] += 1
            pool.audit(live())
            audits["calls"] += 1
            return out

        setattr(pool, name, wrapped)
    sched = oversub_schedule(args.seed, max(6, args.requests // 2),
                             cfg.vocab, args.max_len)
    eng.open_loop_client(sched)
    res = eng.run_until_done()
    assert all(r.done for r in eng.submitted), "async bench deadlock"
    assert eng.pool.inflight_tables() == 0, "transfer outlived the run"
    return eng, res, audits


def run_async_bench(args, cfg):
    """The async two-tier memory headline (``--async-swap``): overlap the
    swap tier's D2H/H2D transfers behind the token loop and charge them
    nothing.  Gates, all asserted in-run: token identity vs the
    synchronous twin on the same schedule, ``recompute_tokens == 0``,
    spill cycles actually happened, ``pool.audit()`` exact WHILE
    transfers were in flight, and a tpot_p50 no worse than the sync twin
    (generous 1.5x factor — interpret-mode CPU timings are noisy)."""
    cells = {}
    for is_async in (True, False):
        tag = "async" if is_async else "sync"
        eng, res, audits = run_async_mode(args, cfg, async_swap=is_async)
        st = ServeEngine.stats(eng.submitted)
        kv = eng.kv_stats()
        toks = [r.generated for r in
                sorted(eng.submitted, key=lambda r: r.rid)]
        cells[tag] = (st, kv, toks, audits, eng)
        emit([row(f"openloop_tpot_p50[{tag}-swap]", st["tpot_p50"] * 1e6,
                  f"p99={st['tpot_p99']*1e6:.0f}us spills={kv['spills']:.0f}"
                  f" restores={kv['restores']:.0f} "
                  f"recompute={kv['recompute_tokens']:.0f}")])
    st_a, kv_a, toks_a, audits_a, eng_a = cells["async"]
    st_s, kv_s, toks_s, _, _ = cells["sync"]
    # overlap efficiency: decode ticks that ran with bytes on the wire,
    # rounds each landed spill hid behind, fences that actually waited,
    # peak in-flight footprint, and the priced host-link time
    peak_pages = max((s.kv_spill_inflight_pages
                      for s in eng_a.counters.samples), default=0.0)
    peak_bytes = max((s.kv_spill_inflight_bytes
                      for s in eng_a.counters.samples), default=0.0)
    emit([
        # NB on CPU CI the D2H gather is ready instantly, so every issue
        # lands at the NEXT round's poll: the engine advances exactly one
        # full round per spill without blocking (overlap_rounds/spill =
        # 1.0) and decode ticks rarely land inside that one-round window.
        # On hardware where the copy takes many rounds, ticks_while_
        # inflight counts the decode work the transfer actually hid behind.
        row("async_swap_overlap_ticks", kv_a["ticks_while_inflight"],
            f"decode ticks with a transfer in flight; "
            f"overlap_rounds/spill={kv_a['overlap_rounds_per_spill']:.1f} "
            f"fence_waits={kv_a['fence_waits']:.0f} "
            f"issues={kv_a['spill_issues']:.0f}"),
        row("async_swap_inflight_peak_bytes", peak_bytes,
            f"peak_pages={peak_pages:.0f} "
            f"prefetches={kv_a['restore_prefetches']:.0f} "
            f"pinned_host={kv_a['swap_tier']['pinned_host']} "
            f"tier_overflows={kv_a['swap_tier']['overflow_allocs']:.0f}"),
        row("async_swap_link_us", (kv_a["d2h_seconds"]
                                   + kv_a["h2d_seconds"]) * 1e6,
            f"d2h={kv_a['d2h_bytes']:.0f}B h2d={kv_a['h2d_bytes']:.0f}B "
            f"priced at the host-link bw (overlapped behind the loop)"),
    ])
    # gate 1: token identity against the synchronous twin
    assert toks_a == toks_s, "async/sync swap token divergence"
    # gate 2: the swap tier still never recomputes
    assert kv_a["recompute_tokens"] == 0 and kv_s["recompute_tokens"] == 0
    # gate 3: the schedule actually exercised spill cycles, and every
    # issue landed exactly once
    assert kv_a["spills"] >= 1, "oversubscription never spilled"
    assert kv_a["spill_issues"] == kv_a["spills"], \
        "issued transfers did not all land"
    # gate 4: accounting stayed exact WITH transfers in flight (the
    # audit wrapper runs at every issue/poll/fence)
    assert audits_a["calls"] > 0 and audits_a["inflight"] > 0, \
        "audit never observed an in-flight transfer"
    # gate 5: overlap must not add decode latency vs the sync twin
    assert st_a["tpot_p50"] <= st_s["tpot_p50"] * 1.5, \
        f"async tpot_p50 {st_a['tpot_p50']*1e6:.0f}us regressed vs " \
        f"sync {st_s['tpot_p50']*1e6:.0f}us"
    print(f"async swap token-identical: True (spills={kv_a['spills']:.0f} "
          f"overlapped ticks={kv_a['ticks_while_inflight']:.0f}, "
          f"fence_waits={kv_a['fence_waits']:.0f}, tpot_p50 "
          f"async={st_a['tpot_p50']*1e6:.0f}us "
          f"sync={st_s['tpot_p50']*1e6:.0f}us)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--mean-gap", type=float, default=1.0,
                    help="mean inter-arrival gap in engine rounds")
    ap.add_argument("--pool-streams", type=int, default=1,
                    help="KV budget per domain, in full-length streams "
                         "(the old slot-monolith limit)")
    ap.add_argument("--max-len", type=int, default=48)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefill-chunked", action="store_true",
                    help="run ONLY the lazy mode (chunked prefill + "
                         "elastic page growth)")
    ap.add_argument("--eager", action="store_true",
                    help="run ONLY the eager-reservation mode")
    ap.add_argument("--evict-mode", choices=("swap", "restart"),
                    default="swap",
                    help="stall-watchdog policy for the lazy run: spill "
                         "parked pages to the host tier (swap) or "
                         "recompute from scratch (restart)")
    ap.add_argument("--prefill-mode", choices=("parallel", "scan"),
                    default="parallel",
                    help="chunk-tick compiled path: fuse the whole chunk "
                         "into ONE model forward (parallel) or scan "
                         "decode_step per token (scan, the reference)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="prompt tokens per prefill chunk (default: one "
                         "KV page)")
    ap.add_argument("--chunk-kernel", choices=("blocked", "dense"),
                    default="blocked",
                    help="fused-path attention kernel: the Pallas "
                         "online-softmax ring kernel (blocked, one "
                         "(block_q, block_kv) tile live) or the einsum "
                         "reference (dense, a full (C, W+C) score block)")
    ap.add_argument("--split-ticks", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="run mixed ticks as TWO compiled steps — a fused "
                         "chunk step for prefilling streams plus a "
                         "single-token step for decoders — instead of one "
                         "padded chunk forward where every decode stream "
                         "pays C-1 masked query rows")
    ap.add_argument("--prefix-share", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="run ONLY the shared-prefix tenant workload: a "
                         "warm wave publishes per-tenant preamble pages, "
                         "then an identical-prompt burst must attach them. "
                         "--prefix-share compares sharing on vs off and "
                         "asserts token identity, >=80%% prefill-chunk "
                         "skip and strictly higher admitted concurrency; "
                         "--no-prefix-share reports the unshared baseline")
    ap.add_argument("--chunk-sweep", action="store_true",
                    help="sweep chunk sizes x {parallel, scan}: TTFT + "
                         "model steps per chunk tick + honest per-chunk "
                         "bytes, token identity asserted across every "
                         "cell")
    ap.add_argument("--spec-decode", choices=("off", "ngram"),
                    default="off",
                    help="run ONLY the speculative-decoding comparison: "
                         "the n-gram/prompt-lookup drafter + fused verify "
                         "path vs the spec-off engine on one lookup-"
                         "friendly schedule.  Asserts token identity, "
                         "acceptance > 0, HLO-measured accepted-tokens-"
                         "per-model-step > 1.0 and a strictly lower "
                         "tpot_p50")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max draft tokens verified per decode tick")
    ap.add_argument("--spec-ngram", type=int, default=3,
                    help="longest n-gram the prompt-lookup drafter "
                         "matches against the stream's own history")
    ap.add_argument("--slo-classes", action="store_true",
                    help="run ONLY the mixed-tenant SLO-class workload: "
                         "long batch requests congest the wait line while "
                         "1-page interactive requests arrive behind the "
                         "parked head, bypass-on vs the FIFO-only twin on "
                         "the same seed.  Asserts token identity, strictly "
                         "higher admitted concurrency, ZERO head delay and "
                         "a strictly better interactive admission-delay "
                         "p99")
    ap.add_argument("--slo-seed", type=int, default=10,
                    help="seed for the mixed-tenant SLO schedule (pinned "
                         "separately from --seed: the SLO cells run their "
                         "own tuned regime)")
    ap.add_argument("--slo-bypass", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="size-aware SLO bypass in the engine under test; "
                         "--no-slo-bypass pins the strict-FIFO grant rule "
                         "(the baseline the spec/prefix smoke cells run "
                         "against)")
    ap.add_argument("--cached-retention", choices=("access", "blind"),
                    default="access",
                    help="free-but-cached page reclaim order for the "
                         "prefix workload: coldest-access-first (access) "
                         "or FIFO (blind)")
    ap.add_argument("--headroom", type=int, default=0,
                    help="admission headroom k: grant only when the "
                         "domain keeps k free blocks past the first chunk")
    ap.add_argument("--async-swap", action="store_true",
                    help="async two-tier memory comparison: spill/restore "
                         "issued behind the token loop (issue/poll/fence) "
                         "vs the synchronous swap twin on the same "
                         "oversubscription schedule")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI run: few requests, fast")
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        args.requests = 8
        args.mean_gap = 1.0

    cfg = reduced_config(REGISTRY["llama3-8b"])
    if args.async_swap:
        run_async_bench(args, cfg)
        return
    if args.slo_classes:
        run_slo_bench(args, cfg)
        return
    if args.spec_decode != "off":
        run_spec_bench(args, cfg)
        return
    if args.prefix_share is not None:
        run_prefix_bench(args, cfg, compare=args.prefix_share)
        return
    if args.chunk_sweep:
        # chunk-size sweep at equal byte budget: every
        # (C, path, kernel, split) cell must generate identical tokens; the
        # fused path must hold 1 model step per chunk tick (scan pays C);
        # the blocked kernel must price a strictly smaller score transient
        # than dense once the (C, W+C) block outgrows one tile
        cells = (("parallel", "blocked", True),
                 ("parallel", "blocked", False),
                 ("parallel", "dense", True),
                 ("scan", "dense", True))
        base = None
        for C in (4, 8, 16, 24):
            score = {}
            for pm, kern, split in cells:
                eng, res = run_mode(args, cfg, lazy=True,
                                    evict_mode=args.evict_mode,
                                    prefill_mode=pm, prefill_chunk=C,
                                    chunk_kernel=kern, split_ticks=split)
                st = ServeEngine.stats(eng.submitted)
                kv = eng.kv_stats()
                toks = [r.generated for r in
                        sorted(eng.submitted, key=lambda r: r.rid)]
                if base is None:
                    base = toks
                assert toks == base, \
                    f"chunk-sweep divergence at C={C} {pm}/{kern}/{split}"
                per_tick = (kv["prefill_model_steps"]
                            / max(1, kv["chunk_ticks"]))
                assert per_tick == (1 if pm == "parallel" else eng._chunk)
                if pm == "parallel" and split:
                    score[kern] = kv["prefill_score_bytes"]
                emit([row(f"sweep_ttft_p50[{pm},{kern},"
                          f"{'split' if split else 'unsplit'},"
                          f"C={eng._chunk}]",
                          st["ttft_p50"] * 1e6,
                          f"model_steps/chunk_tick={per_tick:.0f} "
                          f"chunk_bytes={kv['prefill_chunk_bytes']:.0f} "
                          f"(score={kv['prefill_score_bytes']:.0f}B)")])
            if C >= 16:
                # at C=16 the dense (C, W+C) block exceeds one (32, 32)
                # tile, so blocked must be strictly cheaper
                assert score["blocked"] < score["dense"], \
                    f"C={C}: blocked transient {score['blocked']:.0f}B " \
                    f"not below dense {score['dense']:.0f}B"
        print("chunk sweep token-identical across sizes, paths, kernels "
              "and tick splitting: True")
        return
    # (label, lazy, evict_mode): the default run compares swap-evict lazy
    # against restart-evict lazy AND eager on the same schedule/budget
    modes = []
    if args.prefill_chunked or not args.eager:
        modes.append(("lazy", True, args.evict_mode))
    if not (args.prefill_chunked or args.eager):
        other = "restart" if args.evict_mode == "swap" else "swap"
        modes.append((f"{other}-evict", True, other))
    if args.eager or not args.prefill_chunked:
        modes.append(("eager", False, "swap"))
    runs = {}
    kvs = {}
    for mode, lazy, evict in modes:
        eng, res = run_mode(args, cfg, lazy=lazy, evict_mode=evict)
        report(mode, args, eng, res)
        runs[mode] = eng
        kvs[mode] = eng.kv_stats()
        if evict == "swap" and lazy:
            # the CI gate: the swap tier must NEVER recompute a token
            assert kvs[mode]["recompute_tokens"] == 0, \
                f"[{mode}] swap mode recomputed " \
                f"{kvs[mode]['recompute_tokens']:.0f} tokens"
    toks = {m: [e.generated for e in sorted(runs[m].submitted,
                                            key=lambda r: r.rid)]
            for m in runs}
    if "lazy" in runs and args.prefill_mode == "parallel":
        # parallel-vs-scan divergence gate: the fused one-forward-per-tick
        # path must generate the per-token reference's exact tokens, and a
        # C-token chunk must cost 1 model step (vs C in scan mode)
        eng_s, res_s = run_mode(args, cfg, lazy=True,
                                evict_mode=args.evict_mode,
                                prefill_mode="scan")
        report("scan-prefill", args, eng_s, res_s)
        toks_s = [r.generated for r in
                  sorted(eng_s.submitted, key=lambda r: r.rid)]
        assert toks["lazy"] == toks_s, \
            "parallel/scan prefill token divergence"
        # (the steps metric is structural — derived from which compiled
        # path ran — so the token-identity assert above is the real gate)
        kp, ks = kvs["lazy"], eng_s.kv_stats()
        C = runs["lazy"]._chunk
        assert kp["prefill_model_steps"] == kp["chunk_ticks"], \
            "parallel chunk tick took more than one model step"
        assert ks["prefill_model_steps"] == C * ks["chunk_ticks"], \
            "scan chunk tick did not pay C model steps"
        print(f"prefill model steps per chunk tick: parallel=1 scan={C} "
              f"(chunk={C}); token-identical: True")
        # kernel gate: the other fused kernel on the same schedule must be
        # token-identical, and blocked must price the smaller transient
        other_k = "dense" if args.chunk_kernel == "blocked" else "blocked"
        eng_k, res_k = run_mode(args, cfg, lazy=True,
                                evict_mode=args.evict_mode,
                                chunk_kernel=other_k)
        toks_k = [r.generated for r in
                  sorted(eng_k.submitted, key=lambda r: r.rid)]
        assert toks["lazy"] == toks_k, \
            f"{args.chunk_kernel}/{other_k} kernel token divergence"
        score = {args.chunk_kernel: kp["prefill_score_bytes"],
                 other_k: eng_k.kv_stats()["prefill_score_bytes"]}
        if C >= 16:
            assert score["blocked"] < score["dense"], \
                f"blocked transient {score['blocked']:.0f}B not below " \
                f"dense {score['dense']:.0f}B at C={C}"
        print(f"chunk kernels token-identical: True (score transient: "
              f"blocked={score['blocked']:.0f}B "
              f"dense={score['dense']:.0f}B at C={C})")
        # split gate: the other tick-splitting mode must be
        # token-identical; the split run must leave decode streams with
        # ZERO masked prefill-query rows and a tpot tail no worse than
        # the padded mixed ticks (generous factor — interpret-mode CPU
        # timings are noisy)
        eng_u, res_u = run_mode(args, cfg, lazy=True,
                                evict_mode=args.evict_mode,
                                split_ticks=not args.split_ticks)
        report("unsplit" if args.split_ticks else "split", args,
               eng_u, res_u)
        toks_u = [r.generated for r in
                  sorted(eng_u.submitted, key=lambda r: r.rid)]
        assert toks["lazy"] == toks_u, "split/unsplit token divergence"
        e_split = runs["lazy"] if args.split_ticks else eng_u
        e_pad = eng_u if args.split_ticks else runs["lazy"]
        kv_s, kv_p = e_split.kv_stats(), e_pad.kv_stats()
        assert kv_s["decode_masked_query_rows"] == 0, \
            "split mode still paid masked decode-query rows"
        if kv_p["decode_masked_query_rows"]:
            assert kv_s["mixed_tick_decode_rows_saved"] > 0, \
                "mixed ticks occurred but split saved no rows"
        tp_s = ServeEngine.stats(e_split.submitted)["tpot_p50"]
        tp_p = ServeEngine.stats(e_pad.submitted)["tpot_p50"]
        assert tp_s <= tp_p * 1.5, \
            f"split tpot_p50 {tp_s*1e6:.0f}us regressed vs " \
            f"unsplit {tp_p*1e6:.0f}us"
        print(f"tick splitting token-identical: True (decode rows saved="
              f"{kv_s['mixed_tick_decode_rows_saved']:.0f}, unsplit "
              f"masked rows={kv_p['decode_masked_query_rows']:.0f}, "
              f"tpot_p50 split={tp_s*1e6:.0f}us "
              f"unsplit={tp_p*1e6:.0f}us)")
    swap_mode = "lazy" if args.evict_mode == "swap" else "swap-evict"
    restart_mode = "restart-evict" if args.evict_mode == "swap" else "lazy"
    if swap_mode in runs and restart_mode in runs:
        # same schedule, same budget: every restart eviction must become a
        # spill/restore cycle — identical tokens, zero recompute
        assert toks[swap_mode] == toks[restart_mode], \
            "swap/restart token divergence"
        sw, rs = kvs[swap_mode], kvs[restart_mode]
        print(f"eviction thrash: restart={rs['evictions']:.0f} evictions "
              f"({rs['recompute_tokens']:.0f} recomputed tokens) vs "
              f"swap={sw['spills']:.0f} spills / {sw['restores']:.0f} "
              f"restores ({sw['recompute_tokens']:.0f} recomputed); "
              f"token-identical: True")
        assert sw["evictions"] == 0, "swap mode fell back to restart"
        if rs["evictions"]:
            assert sw["spills"] > 0, \
                "restart thrashed but swap mode never spilled"
    if "lazy" in runs and "eager" in runs:
        # lazy must admit at least as much concurrency as eager and
        # generate identical tokens
        assert toks["lazy"] == toks["eager"], \
            "lazy/eager token divergence"
        lz = runs["lazy"].pool.peak_active_tables
        eg = runs["eager"].pool.peak_active_tables
        print(f"admitted concurrency: lazy={lz} eager={eg} "
              f"(same {args.pool_streams} streams/domain budget); "
              f"token-identical: True")
        assert lz >= eg, "lazy admitted less concurrency than eager"


if __name__ == "__main__":
    main()
