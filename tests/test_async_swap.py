"""Async two-tier KV memory (ISSUE 10): spill/restore overlapped behind
the token loop.

The PR-4 swap tier spilled synchronously: the pressure ladder gathered
the victim's pages, waited for the copy, then re-granted.  The transfer
engine splits that into ISSUE / POLL / FENCE phases — ``spill_issue``
dispatches the D2H gather and returns, decode ticks keep running, and
the victim's pages are re-granted only when the poll (or a fence) lands
the transfer.  Everything here asserts the invariants that make the
overlap safe:

  * token identity: async mode, its synchronous twin and an uncontended
    baseline generate IDENTICAL tokens on randomized oversubscription
    schedules — and async still never recomputes;
  * fence-before-regrant: an in-flight victim KEEPS its device pages and
    state slot until the transfer lands; the pool free callback fires at
    landing, never at issue;
  * ``pool.audit()`` stays exact WHILE transfers are outstanding;
  * migration (the relayout path) and shutdown drain the pipe first;
  * ``restore_into`` reserves pages + growth + state slot atomically —
    a failed sweep leg has ZERO side effects (the PR-10 regression: the
    old sweep could leak a state checkpoint on a failed grow).
"""
import collections

import jax
import jax.numpy as jnp
import numpy as np

from conftest import hypothesis_tools
from repro.configs import REGISTRY, reduced_config
from repro.core.topology import ChipletTopology
from repro.serving.engine import EngineConfig, ServeEngine
from repro.serving.kvpool import KVBlockPool

given, settings, st = hypothesis_tools()

CFG = reduced_config(REGISTRY["llama3-8b"])


def _engine(*, groups=1, max_batch=2, max_len=32, pool_streams=1,
            evict_mode="swap", headroom=0, adaptive=False, **ecfg_kw):
    topo = ChipletTopology(n_pods=1, groups_per_pod=groups,
                           chips_per_group=1)
    ecfg = EngineConfig(max_batch=max_batch, max_len=max_len, paged=True,
                        lazy=True, pool_streams=pool_streams,
                        adaptive=adaptive, evict_mode=evict_mode,
                        headroom=headroom, **ecfg_kw)
    return ServeEngine(CFG, topo, ecfg, spread_rate=1, seed=0)


def _instrument_async(eng):
    """Audit the pool after EVERY transfer-engine transition — issue,
    poll, fence, restore and free — so accounting is checked with
    transfers at every stage of flight, not just at rest."""
    pool = eng.pool

    def live_tables():
        return [r.table for r in eng.submitted if r.table is not None]

    audits = {"n": 0}
    for name in ("spill_issue", "spill_poll", "spill_fence",
                 "restore_into", "restore", "free"):
        orig = getattr(pool, name)

        def wrapped(*a, _orig=orig, **kw):
            out = _orig(*a, **kw)
            pool.audit(live_tables())
            audits["n"] += 1
            return out

        setattr(pool, name, wrapped)
    return audits


def _drain(eng):
    res = eng.run_until_done()
    assert all(r.done for r in eng.submitted), "allocation deadlock"
    return res


def _longtail(rng, n, max_len):
    out = []
    for _ in range(n):
        gap = int(rng.integers(0, 4))
        plen = int(rng.integers(3, max_len // 2))
        if rng.random() < 0.5:
            max_new = int(rng.integers(max_len // 2, max_len - plen))
        else:
            max_new = int(rng.integers(1, max(2, max_len // 8)))
        out.append((gap, rng.integers(2, CFG.vocab, size=plen), max_new))
    return out


def _tokens(eng):
    return [r.generated for r in sorted(eng.submitted, key=lambda r: r.rid)]


# ---------------------------------------------------------------------------
# the acceptance property: async == sync == baseline, token for token
# ---------------------------------------------------------------------------

@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 10**6))
def test_async_token_identity_randomized(seed):
    """For every randomized oversubscription schedule: the async engine,
    its synchronous twin and an uncontended baseline generate IDENTICAL
    tokens; async never recomputes, never restart-evicts, audits exactly
    at every transfer transition, and drains its pipe at shutdown."""
    rng = np.random.default_rng(seed)
    sched = _longtail(rng, int(rng.integers(3, 7)), 32)
    groups = int(rng.integers(1, 3))
    outs, counters = {}, {}
    for mode, (streams, is_async) in {"async": (1, True),
                                      "sync": (1, False),
                                      "baseline": (8, False)}.items():
        eng = _engine(groups=groups, max_batch=4, pool_streams=streams,
                      async_swap=is_async)
        if is_async:
            audits = _instrument_async(eng)
        eng.open_loop_client(list(sched))
        res = _drain(eng)
        outs[mode] = _tokens(eng)
        counters[mode] = res["counters"]
        assert eng.pool.inflight_tables() == 0, "transfer outlived the run"
        assert eng.pool.occupancy() == 0.0
        assert eng.pool.spilled_tables == 0 and eng.pool.spilled_bytes == 0
        eng.pool.audit([])
    assert outs["async"] == outs["sync"] == outs["baseline"]
    assert counters["async"].get("recompute_tokens", 0) == 0
    assert counters["async"].get("kv_evictions", 0) == 0
    assert counters["baseline"].get("kv_spills", 0) == 0
    # every issue landed exactly once
    assert counters["async"].get("kv_spill_issues", 0) == \
        counters["async"].get("kv_spills", 0)
    if counters["async"].get("kv_spills", 0):
        assert audits["n"] > 0


def test_async_oversubscription_overlap_counters():
    """The dense 1-stream/domain schedule that forces spill cycles: the
    async twin must spill (issue == land), stay token-identical to the
    sync twin, and surface the overlap accounting the benchmark reports
    (ticks-while-in-flight, overlap rounds, priced D2H seconds)."""
    rng = np.random.default_rng(0)
    sched = [(int(rng.integers(0, 2)),
              rng.integers(2, CFG.vocab, size=4), 26) for _ in range(6)]
    runs = {}
    for is_async in (True, False):
        eng = _engine(groups=1, max_batch=4, pool_streams=1,
                      async_swap=is_async)
        eng.open_loop_client(list(sched))
        res = _drain(eng)
        runs[is_async] = (_tokens(eng), res["counters"], eng.kv_stats())
    toks_a, ctr_a, kv_a = runs[True]
    toks_s, ctr_s, kv_s = runs[False]
    assert toks_a == toks_s
    assert ctr_a.get("kv_spills", 0) >= 1
    assert ctr_s.get("kv_spills", 0) >= 1
    assert ctr_a.get("recompute_tokens", 0) == 0
    assert kv_a["async_swap"] and not kv_s["async_swap"]
    assert kv_a["spill_issues"] == kv_a["spills"]
    assert kv_s["spill_issues"] == kv_s["spills"]  # sync = issue + fence
    # gauges are zero at rest; the overlap surface exists either way
    assert kv_a["spill_inflight_pages"] == 0
    assert kv_a["spill_inflight_bytes"] == 0
    for key in ("ticks_while_inflight", "overlap_rounds_per_spill",
                "fence_waits", "d2h_seconds", "h2d_seconds"):
        assert key in kv_a
    assert kv_a["d2h_seconds"] > 0          # priced spill traffic
    # the sync twin never counts a fence wait: its fences are immediate
    # by construction, not stalls
    assert kv_s["fence_waits"] == 0
    assert kv_s["ticks_while_inflight"] == 0


def test_inflight_spill_reaches_the_step_samples():
    """The per-round profiler feed carries the transfer engine's gauges:
    a round sampled while a spill is on the wire shows its pages and
    bytes in the scheduler's ``StepSample`` stream."""
    rng = np.random.default_rng(0)
    sched = [(int(rng.integers(0, 2)),
              rng.integers(2, CFG.vocab, size=4), 26) for _ in range(6)]
    eng = _engine(groups=1, max_batch=4, pool_streams=1, async_swap=True)
    eng.counters.samples = collections.deque(maxlen=100000)
    eng.open_loop_client(list(sched))
    res = _drain(eng)
    assert res["counters"].get("kv_spill_issues", 0) >= 1
    samples = eng.counters.samples
    assert max(s.kv_spill_inflight_pages for s in samples) > 0
    assert max(s.kv_spill_inflight_bytes for s in samples) > 0
    assert sum(s.kv_ticks_while_inflight for s in samples) == \
        res["counters"].get("kv_ticks_while_inflight", 0)


# ---------------------------------------------------------------------------
# fence-before-regrant (pool unit)
# ---------------------------------------------------------------------------

def test_fence_before_regrant_pool_unit():
    """An issued spill keeps the victim's pages until it lands: free
    counts are unchanged at issue, the free callback fires at landing,
    double-issue is refused, and audit passes at every stage."""
    pool = KVBlockPool(CFG, n_domains=1, max_len=32, blocks_per_domain=4,
                       states_per_domain=2)
    t = pool.reserve(0, 40, first_tokens=8)
    pool.grow(t, 1)
    t.used_pages = 2
    frees = []
    pool.on_free(lambda: frees.append(pool.free_blocks(0)))
    free0 = pool.free_blocks(0)
    assert pool.spill_issue(t) == 2
    # in flight: pages retained, nothing re-granted, no callback yet
    assert t.inflight and t.spill is None
    assert len(t.blocks) == 2
    assert pool.free_blocks(0) == free0
    assert pool.inflight_tables() == 1 and pool.inflight_pages() == 2
    assert pool.inflight_bytes() > 0
    assert pool.inflight_domains() == {0}
    assert frees == []
    pool.audit([t])                         # exact WHILE in flight
    assert pool.spill_issue(t) == 0         # never double-issue
    assert pool.spill_issue(t) == 0
    pool.audit([t])
    # the fence lands it: pages re-granted, callback fired exactly now
    pool.spill_fence(t)
    assert not t.inflight and t.spill is not None
    assert t.blocks == [] and pool.free_blocks(0) == free0 + 2
    assert pool.inflight_tables() == 0
    assert len(frees) == 1
    pool.audit([t])
    snap = pool.counters.totals
    assert snap.get("kv_spill_issues", 0) == 1
    assert snap.get("kv_spills", 0) == 1
    assert pool.restore(t)
    pool.audit([t])
    pool.free(t)
    pool.audit([])


def test_poll_lands_ready_transfers():
    """``spill_poll`` (the per-round poll phase) lands a completed
    transfer without a blocking fence, and the overlap clock counts the
    rounds between issue and landing."""
    pool = KVBlockPool(CFG, n_domains=2, max_len=32, blocks_per_domain=2,
                       states_per_domain=2)
    t = pool.reserve(0, 40, first_tokens=8)
    pool.grow(t, 1)
    t.used_pages = 2
    assert pool.spill_issue(t) == 2
    for leaf in pool._inflight[0].leaves:   # CPU: force completion so the
        if leaf is not None:                # poll observes ready arrays
            leaf.block_until_ready()
    landed = pool.spill_poll()
    assert landed == 1
    assert not t.inflight and t.spill is not None
    assert pool.counters.totals.get("kv_fence_waits", 0) == 0
    pool.audit([t])
    pool.free(t)
    pool.audit([])


def test_migrate_and_free_fence_inflight_first():
    """The relayout/steal path (``migrate``) and the release path
    (``free``) must drain a table's transfer before acting — a re-point
    or a free with bytes on the wire would corrupt the payload."""
    pool = KVBlockPool(CFG, n_domains=2, max_len=32, blocks_per_domain=2,
                       states_per_domain=2)
    t = pool.reserve(0, 40, first_tokens=8)
    pool.grow(t, 1)
    t.used_pages = 2
    assert pool.spill_issue(t) == 2
    assert pool.migrate(t, 1)               # fences, lands, then re-points
    assert not t.inflight and t.spill is not None and t.domain == 1
    assert pool.inflight_tables() == 0
    assert pool.restore(t)
    assert t.domain == 1 and len(t.blocks) == 2
    pool.audit([t])
    # free() with a transfer outstanding: fence first, then release
    t2 = pool.reserve(0, 40, first_tokens=8)
    pool.grow(t2, 1)
    t2.used_pages = 2
    assert pool.spill_issue(t2) == 2
    pool.free(t2)
    assert pool.inflight_tables() == 0
    pool.free(t)
    pool.audit([])


def test_grow_refused_while_inflight():
    """An in-flight victim is FROZEN: grow is refused (the stream parks
    and retries after the landing) instead of mutating pages whose bytes
    are mid-copy."""
    pool = KVBlockPool(CFG, n_domains=1, max_len=32, blocks_per_domain=4,
                       states_per_domain=2)
    t = pool.reserve(0, 40, first_tokens=8)
    t.used_pages = 1
    assert pool.spill_issue(t) == 1
    gf0 = pool.counters.totals.get("kv_grow_failures", 0)
    assert not pool.grow(t, 1)
    assert pool.counters.totals.get("kv_grow_failures", 0) == gf0 + 1
    pool.spill_fence(t)
    pool.audit([t])
    pool.free(t)
    pool.audit([])


# ---------------------------------------------------------------------------
# atomic restore_into (the PR-10 sweep-leg regression)
# ---------------------------------------------------------------------------

def test_restore_into_failed_leg_has_zero_side_effects():
    """A sweep leg that cannot fit pages + growth must leave the table
    EXACTLY as it found it: domain un-repointed, spill intact, free lists
    untouched — the old sweep re-pointed, restored, then grew in separate
    steps and a failed grow stranded the stream."""
    pool = KVBlockPool(CFG, n_domains=2, max_len=32, blocks_per_domain=4,
                       states_per_domain=2)
    t = pool.reserve(0, 40, first_tokens=8)
    pool.grow(t, 1)
    t.used_pages = 2
    assert pool.spill(t) == 2
    # starve domain 1: leave only 1 free block (< the 2 pages needed)
    eat1 = pool.reserve(1, 40, first_tokens=32)
    eat2 = pool.reserve(1, 8, first_tokens=8)
    assert pool.free_blocks(1) == 1
    free0, free1 = pool.free_blocks(0), pool.free_blocks(1)
    assert not pool.restore_into(t, 1)
    # ZERO side effects on the failed leg
    assert t.domain == 0 and t.spill is not None and t.blocks == []
    assert pool.free_blocks(0) == free0 and pool.free_blocks(1) == free1
    pool.audit([t, eat1, eat2])
    # the next leg (home domain) succeeds atomically, growth clamped to
    # the table's page cap
    assert pool.restore_into(t, 0, grow_by=1)
    assert t.domain == 0 and t.spill is None
    assert len(t.blocks) == 2 and t.used_pages == 2   # cap_pages == 2
    pool.audit([t, eat1, eat2])
    for x in (t, eat1, eat2):
        pool.free(x)
    pool.audit([])


def test_restore_into_state_slot_not_leaked_on_failed_leg():
    """Hybrid models: a failed sweep leg must not consume the spilled
    STATE checkpoint or a destination state slot (the leak the audit
    regression guards)."""
    cfg = reduced_config(REGISTRY["recurrentgemma-9b"])
    pool = KVBlockPool(cfg, n_domains=2, max_len=32, blocks_per_domain=4,
                       states_per_domain=1)
    assert pool.has_state
    t = pool.reserve(0, 40, first_tokens=8)
    if pool.pages_per_stream:
        t.used_pages = len(t.blocks)
    assert pool.spill(t) >= 0
    assert t.spill is not None and t.spill.had_state
    # exhaust domain 1's single state slot
    eater = pool.reserve(1, 8, first_tokens=8)
    assert not pool.state_available(1)
    assert not pool.restore_into(t, 1)
    assert t.domain == 0 and t.spill is not None
    assert t.spill.had_state, "state checkpoint consumed by failed leg"
    pool.audit([t, eater])
    assert pool.restore_into(t, 0)
    assert t.state_slot and t.domain == 0
    pool.audit([t, eater])
    pool.free(t)
    pool.free(eater)
    pool.audit([])


def test_restore_prefetch_stages_h2d_and_preserves_bytes():
    """``restore_prefetch`` stages the spilled payload device-side while
    the stream waits in line; the eventual restore reads the staged
    arrays and the bytes survive bit-exact."""
    pool = KVBlockPool(CFG, n_domains=1, max_len=32, blocks_per_domain=4,
                       states_per_domain=2)
    t = pool.reserve(0, 40, first_tokens=8)
    pool.grow(t, 1)
    t.used_pages = 2
    new = []
    for leaf, s in zip(jax.tree.leaves(pool.storage), pool.spec.leaves):
        ax = s.batch_axis
        idx = (slice(None),) * ax
        if s.token_axis is not None and t.blocks:
            leaf = leaf.at[idx + (jnp.asarray(t.blocks),)].set(3.25)
        new.append(leaf)
    pool.storage = jax.tree.unflatten(pool.spec.treedef, new)
    assert pool.spill(t) == 2
    assert pool.restore_prefetch(t)
    assert t.spill.staged is not None
    assert not pool.restore_prefetch(t)     # idempotent
    assert pool.counters.totals.get("kv_restore_prefetches", 0) == 1
    assert pool.restore(t)
    for leaf, s in zip(jax.tree.leaves(pool.storage), pool.spec.leaves):
        if s.token_axis is not None and t.blocks:
            vals = jnp.take(leaf, jnp.asarray(t.blocks), axis=s.batch_axis)
            assert jnp.all(vals == 3.25), "staged restore lost bytes"
    pool.audit([t])
    pool.free(t)
    pool.audit([])


# ---------------------------------------------------------------------------
# engine-level: park + drain with a transfer outstanding
# ---------------------------------------------------------------------------

def test_engine_park_while_transfer_outstanding_drains():
    """Drive the 2-stream deadlock by hand on an async engine: the ladder
    ISSUES the victim's spill (pages retained, line head still parked),
    and the run then drains token-identically — landings, not issues,
    re-grant the pages."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, CFG.vocab, size=4) for _ in range(2)]
    eng = _engine(groups=1, max_batch=2, pool_streams=1, async_swap=True)
    reqs = [eng.submit(p, max_new=26) for p in prompts]
    eng._running = True
    for g in eng.groups:
        eng._spawn_group(g)
    rounds = 0
    while len(eng._parked) < 2 and rounds < 500:
        eng.sched.tick()
        rounds += 1
    assert len(eng._parked) == 2, "deadlock scenario failed to form"
    free0 = sum(eng.pool.free_blocks(d)
                for d in range(eng.pool.n_domains))
    assert eng._spill_parked(domain=None)
    # issued, not landed: fence-before-regrant at the engine level
    assert eng.pool.inflight_tables() == 1
    victim = [r for r in eng._parked.values() if r.req.table.inflight]
    assert len(victim) == 1 and victim[0].req.table.spill is None
    assert sum(eng.pool.free_blocks(d)
               for d in range(eng.pool.n_domains)) == free0
    eng.pool.audit([r.table for r in eng.submitted if r.table is not None])
    # a second ladder fire with the pipe busy must not double-spill the
    # same table (its candidate filter excludes in-flight victims)
    assert victim[0].req.table.spill is None
    eng.sched.run_until_done(max_rounds=100000,
                             round_hook=eng._stall_hook)
    eng._running = False
    eng.pool.drain()
    assert all(r.done for r in eng.submitted)
    assert eng.pool.inflight_tables() == 0
    assert eng.pool.occupancy() == 0.0 and eng.pool.spilled_tables == 0
    base = _engine(groups=1, max_batch=2, pool_streams=8)
    base_reqs = [base.submit(p, max_new=26) for p in prompts]
    _drain(base)
    assert [r.generated for r in reqs] == \
        [r.generated for r in base_reqs]


def test_sync_spill_unchanged_by_default():
    """``async_swap`` defaults OFF and the default engine's spill path is
    the PR-4 synchronous one: ``pool.spill`` still fires (spy-visible),
    with no issue left unfenced at any point."""
    eng = _engine(groups=1, max_batch=2, pool_streams=1)
    assert not eng._async and not eng.ecfg.async_swap
    calls = []
    orig = eng.pool.spill

    def spy(table, _o=orig):
        out = _o(table)
        calls.append(out)
        assert eng.pool.inflight_tables() == 0
        return out

    eng.pool.spill = spy
    rng = np.random.default_rng(5)
    for p in [rng.integers(2, CFG.vocab, size=4) for _ in range(2)]:
        eng.submit(p, max_new=26)
    _drain(eng)
    assert calls, "the deadlock schedule never spilled"
    assert eng.counters.totals.get("kv_fence_waits", 0) == 0
