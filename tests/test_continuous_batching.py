"""Continuous-batching token loop: chunked paged prefill + lazy page
growth with mid-decode parking (ISSUE 3).

The engine's three allocator modes must be interchangeable at the token
level: LAZY (chunked prefill, elastic page growth, mid-decode parks and —
under the incremental-allocation deadlock — evictions) vs EAGER (PR-2 full
capped reservation + whole-prompt prefill) vs the PR-1 slot monolith
(``paged=False``).  Everything here asserts that equivalence plus the
mechanics that make lazy mode safe: FIFO fairness of the wait line,
page-by-page commitment, and the stall watchdog."""
import numpy as np
import pytest

from conftest import hypothesis_tools
from repro.configs import REGISTRY, reduced_config
from repro.core.topology import ChipletTopology
from repro.serving.engine import EngineConfig, ServeEngine

given, settings, st = hypothesis_tools()

CFG = reduced_config(REGISTRY["llama3-8b"])


def _run(prompts, max_new, *, lazy=True, paged=True, pool_streams=1,
         max_batch=2, max_len=32, groups=2, client_sched=None,
         adaptive=False, **ecfg_kw):
    topo = ChipletTopology(n_pods=1, groups_per_pod=groups,
                           chips_per_group=1)
    ecfg = EngineConfig(max_batch=max_batch, max_len=max_len, paged=paged,
                        lazy=lazy, pool_streams=pool_streams,
                        adaptive=adaptive, **ecfg_kw)
    eng = ServeEngine(CFG, topo, ecfg, spread_rate=1, seed=0)
    reqs = [eng.submit(p, max_new=m) for p, m in zip(prompts, max_new)]
    if client_sched is not None:
        eng.open_loop_client(client_sched)
    res = eng.run_until_done()
    assert all(r.done for r in eng.submitted)
    return eng, reqs, res


# ---------------------------------------------------------------------------
# token identity across allocator modes (property, conftest-fallback safe)
# ---------------------------------------------------------------------------

@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 10**6))
def test_lazy_eager_legacy_token_identity(seed):
    """Random prompt/max_new mixes generate IDENTICAL tokens under lazy
    paging (chunked prefill + growth + parks), eager paging and the legacy
    monolith.  pool_streams=1 keeps the pool tight so long examples
    really do park mid-decode and wrap the ring."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 6))
    prompts = [rng.integers(2, CFG.vocab, size=int(rng.integers(3, 28)))
               for _ in range(n)]
    max_new = [int(rng.integers(1, 20)) for _ in range(n)]
    outs = {}
    for mode, (lazy, paged) in {"lazy": (True, True),
                                "eager": (False, True),
                                "legacy": (False, False)}.items():
        _, reqs, _ = _run(prompts, max_new, lazy=lazy, paged=paged)
        outs[mode] = [r.generated for r in reqs]
        assert all(len(g) == m for g, m in zip(outs[mode], max_new))
    assert outs["lazy"] == outs["eager"] == outs["legacy"]


def test_forced_mid_decode_park_token_identity():
    """A stream that PARKS mid-decode (domain exhausted at a page
    boundary) resumes via the pool free callback and still generates
    exactly the eager run's tokens."""
    rng = np.random.default_rng(0)
    # one domain, 2 pages (max_len=32, bt=16).  The long request A (cap 2
    # pages, admitted with 1 — admission grants are FIFO by submit order)
    # shares the domain with a stream of one-page requests that keep the
    # second page continuously occupied (each finish grants the next
    # parked admission).  When A's pos crosses the page boundary the
    # domain is exhausted and A parks mid-decode until a page frees.
    prompts = [rng.integers(2, CFG.vocab, size=4) for _ in range(4)]
    max_new = [24, 8, 8, 8]
    eng, reqs, res = _run(prompts, max_new, lazy=True, groups=1)
    c = res["counters"]
    assert c.get("kv_mid_decode_parks", 0) >= 1      # A really parked
    assert c.get("kv_lazy_grows", 0) >= 1            # and grew on resume
    assert c.get("kv_evictions", 0) == 0             # B's finish unblocked A
    assert eng.pool.occupancy() == 0.0
    _, reqs_e, _ = _run(prompts, max_new, lazy=False, groups=1)
    assert [r.generated for r in reqs] == [r.generated for r in reqs_e]


def test_mid_decode_park_fairness_over_new_admissions():
    """Admission-order fairness (ISSUE 3 satellite): a stream parked
    mid-decode joins the FIFO wait line at park time, so requests arriving
    AFTER it queue behind it — the next free goes to the parked stream,
    not a newcomer."""
    rng = np.random.default_rng(1)
    # A's prompt nearly fills its first page, so it parks a few decode
    # ticks in (pos 16) while one-page B (alive for 12 generated tokens)
    # holds the domain's second page.  C and D are submitted THE MOMENT A
    # parks (tick spy) and must wait behind A in the line.
    prompts = [rng.integers(2, CFG.vocab, size=s) for s in (14, 4, 4, 4)]
    topo = ChipletTopology(n_pods=1, groups_per_pod=1, chips_per_group=1)
    eng = ServeEngine(CFG, topo,
                      EngineConfig(max_batch=2, max_len=32, pool_streams=1,
                                   adaptive=False),
                      spread_rate=1, seed=0)
    a = eng.submit(prompts[0], max_new=10)
    b = eng.submit(prompts[1], max_new=12)
    orig_tick = eng._decode_tick

    def spy(g):
        if a.rid in eng._parked and len(eng.submitted) == 2:
            eng.submit(prompts[2], max_new=4)
            eng.submit(prompts[3], max_new=4)
        orig_tick(g)

    eng._decode_tick = spy
    res = eng.run_until_done()
    assert all(r.done for r in eng.submitted) and len(eng.submitted) == 4
    c_req, d_req = eng.submitted[2], eng.submitted[3]
    assert res["counters"].get("kv_mid_decode_parks", 0) >= 1
    assert res["counters"].get("kv_evictions", 0) == 0
    # C arrived while A sat parked...
    assert c_req.arrived > a.t_first
    assert c_req.arrived < a.t_done
    # ...yet A finished before C or D were even granted pages (prefill
    # implies a table): longest-parked-first granting
    assert c_req.t_first >= a.t_done
    assert d_req.t_first >= a.t_done


def test_eviction_breaks_incremental_allocation_deadlock():
    """Two streams each holding one page and each needing one more is the
    classic incremental-allocation deadlock: in ``evict_mode="restart"``
    (the PR-3 policy, now behind a flag) the stall watchdog evicts the
    most-recently-parked stream, its pages unblock the other, and the
    evicted request restarts — with greedy decoding the final tokens are
    identical to the eager (serialized) run.  The swap-tier default is
    exercised by tests/test_memory_pressure.py."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(2, CFG.vocab, size=4) for _ in range(2)]
    max_new = [26, 26]
    eng, reqs, res = _run(prompts, max_new, lazy=True, groups=1,
                          evict_mode="restart")
    c = res["counters"]
    assert c.get("kv_mid_decode_parks", 0) >= 2      # both parked
    assert c.get("kv_evictions", 0) >= 1             # watchdog fired
    assert c.get("kv_spills", 0) == 0                # swap tier never used
    assert c.get("recompute_tokens", 0) > 0          # the wasted work
    assert eng.pool.occupancy() == 0.0
    _, reqs_e, _ = _run(prompts, max_new, lazy=False, groups=1)
    assert [r.generated for r in reqs] == [r.generated for r in reqs_e]


# ---------------------------------------------------------------------------
# chunked prefill mechanics
# ---------------------------------------------------------------------------

def test_chunked_prefill_commits_page_by_page():
    """A long prompt prefills in page-sized chunks THROUGH the pool: the
    whole-prompt prefill path is never invoked, one chunk is processed per
    tick, and pages are committed lazily as the prompt crosses page
    boundaries — admission holds a single page."""
    rng = np.random.default_rng(3)
    prompt = rng.integers(2, CFG.vocab, size=30)          # 2 pages of 16

    def boom(*a, **k):
        raise AssertionError("lazy engine must never whole-prompt prefill")

    topo = ChipletTopology(n_pods=1, groups_per_pod=1, chips_per_group=1)
    eng = ServeEngine(CFG, topo,
                      EngineConfig(max_batch=1, max_len=32, pool_streams=1,
                                   adaptive=False),
                      spread_rate=1, seed=0)
    eng._prefill = boom
    admitted_pages = []
    orig_tick = eng._decode_tick

    def spy(g):
        if g.slots[0] is not None and g.pos_h[0] == 0:
            admitted_pages.append(len(g.slots[0].table.blocks))
        orig_tick(g)

    eng._decode_tick = spy
    req = eng.submit(prompt, max_new=2)
    res = eng.run_until_done()
    assert req.done and len(req.generated) == 2
    c = res["counters"]
    assert c["prefill_chunks"] == 2                  # ceil(30 / 16)
    assert c.get("kv_lazy_grows", 0) >= 1            # page 2 grown mid-prompt
    assert admitted_pages == [1]                     # admission took 1 page
    assert eng.pool.occupancy() == 0.0


def test_max_new_one_in_lazy_mode():
    """max_new=1 is satisfied by the last prefill chunk's logits — no
    decode tick, pool drained at the end."""
    rng = np.random.default_rng(4)
    eng, reqs, _ = _run([rng.integers(2, CFG.vocab, size=20)], [1],
                        lazy=True, groups=1)
    assert len(reqs[0].generated) == 1
    assert eng.pool.occupancy() == 0.0


def test_single_token_final_chunk_token_identity():
    """A prompt of chunk+1 tokens leaves a FINAL prefill chunk of exactly
    one token, which rides the plain (non-chunked) step — it must feed the
    prompt token, not the stale last-emitted token (regression: plen=17
    diverged at the first generated token)."""
    rng = np.random.default_rng(8)
    for plen in (17, 33):
        prompts = [rng.integers(2, CFG.vocab, size=plen)]
        out = {}
        for lazy in (True, False):
            _, reqs, _ = _run(prompts, [4], lazy=lazy, groups=1,
                              max_len=48)
            out[lazy] = reqs[0].generated
        assert out[True] == out[False], plen


def test_lazy_relayout_migrates_partial_tables():
    """Live relayout with streams mid-prefill and partially-grown tables:
    adaptive and non-adaptive lazy runs stay token-identical (harvested
    streams carry their chunk cursor; tables re-point or copy only used
    pages)."""
    from repro.core.controller import ControllerConfig
    rng = np.random.default_rng(7)
    prompts = [rng.integers(2, CFG.vocab, size=6) for _ in range(12)]
    max_new = [2 if i % 4 == 0 else 10 for i in range(12)]

    def run(adaptive):
        return _run(prompts, max_new, lazy=True, groups=4, max_batch=1,
                    pool_streams=4, adaptive=adaptive,
                    controller=ControllerConfig(scheduler_timer=3,
                                                threshold=1.0, min_dwell=1))

    eng_a, reqs_a, res_a = run(True)
    assert len(res_a["relayouts"]) >= 1
    eng_b, reqs_b, res_b = run(False)
    assert res_b["relayouts"] == []
    assert [r.generated for r in reqs_a] == [r.generated for r in reqs_b]


# ---------------------------------------------------------------------------
# parallel (fused) vs scan chunk path (ISSUE 5)
# ---------------------------------------------------------------------------

FAMILY_ARCHS = ("llama3-8b", "mixtral-8x22b", "mamba2-780m",
                "recurrentgemma-9b", "seamless-m4t-large-v2")


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10**6), arch=st.sampled_from(FAMILY_ARCHS),
       kernel=st.sampled_from(("dense", "blocked")),
       wide=st.booleans())
def test_parallel_scan_chunk_identity_property(seed, arch, kernel, wide):
    """The fused multi-token forward (``prefill_chunk_step``) matches the
    per-token scan reference (``chunk_decode_step``) within tolerance on
    logits AND every cache leaf, for random chunks over a randomly warmed
    ring — across dense / MoE / SSM / hybrid / enc-dec families, with
    mixed per-stream lengths including a decode stream (n=1) and an idle
    slot (n=0), and with positions deep enough to wrap the ring.  Both
    chunk kernels (dense einsum and the blocked Pallas ring kernel) must
    pass, including chunks WIDER than the ring (``wide`` shrinks the ring
    below the chunk: the C≤W clamp is lifted)."""
    import jax
    import jax.numpy as jnp
    from repro.models import decode as dec
    from repro.models.params import init_params
    cfg = reduced_config(REGISTRY[arch])
    rng = np.random.default_rng(seed)
    B, C = 3, 6
    max_len = 4 if wide else 16          # wide: ring W=4 < C=6
    src = 6 if cfg.family == "encdec" else 0
    params = init_params(cfg, jax.random.PRNGKey(seed % 7))
    spec = dec.cache_view_specs(cfg, max_len, src)
    cache = dec.init_cache(cfg, B, max_len, src)
    if cfg.family == "encdec":
        key = jax.random.PRNGKey(seed % 11)
        for leaf in ("cross_k", "cross_v"):
            cache[leaf] = 0.1 * jax.random.normal(
                key, cache[leaf].shape, cache[leaf].dtype)
    # warm each stream to a random depth (possibly past the ring width)
    # with the trusted scan path, then compare ONE chunk step
    warm = int(rng.integers(0, max_len + 4))
    pos = jnp.zeros((B,), jnp.int32)
    if warm:
        wt = jnp.asarray(rng.integers(2, cfg.vocab, size=(B, warm)),
                         jnp.int32)
        nw = jnp.asarray([warm, max(1, warm // 2), warm], jnp.int32)
        _, cache = dec.chunk_decode_step(params, cfg, spec, cache, wt, pos,
                                         nw)
        pos = nw
    toks = jnp.asarray(rng.integers(2, cfg.vocab, size=(B, C)), jnp.int32)
    nt = jnp.asarray([C, 1, 0], jnp.int32)   # prefill chunk, decode, idle
    lg_s, c_s = dec.chunk_decode_step(params, cfg, spec, cache, toks, pos,
                                      nt)
    lg_p, c_p = dec.prefill_chunk_step(params, cfg, spec, cache, toks, pos,
                                       nt, chunk_kernel=kernel)
    act = np.asarray(nt) > 0
    np.testing.assert_allclose(np.asarray(lg_p)[act], np.asarray(lg_s)[act],
                               rtol=2e-2, atol=2e-3)
    assert np.asarray(lg_p)[~act].max() <= -1e29      # idle rows poisoned
    for a, b in zip(jax.tree.leaves(c_p), jax.tree.leaves(c_s)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-2, atol=2e-3)


def test_parallel_prefill_one_model_step_per_chunk_tick():
    """The acceptance claim at test scale: a C-token prompt chunk costs
    ONE model forward on the parallel path and C sequential steps on the
    scan reference — token-identically."""
    rng = np.random.default_rng(9)
    prompts = [rng.integers(2, CFG.vocab, size=s) for s in (30, 20, 5)]
    max_new = [4, 6, 3]
    outs = {}
    for pm in ("parallel", "scan"):
        eng, reqs, _ = _run(prompts, max_new, lazy=True, groups=2,
                            prefill_mode=pm)
        outs[pm] = [r.generated for r in reqs]
        kv = eng.kv_stats()
        assert kv["chunk_ticks"] > 0
        expect = 1 if pm == "parallel" else eng._chunk
        assert kv["prefill_model_steps"] == expect * kv["chunk_ticks"], pm
    assert outs["parallel"] == outs["scan"]


def test_parallel_mid_chunk_park_token_identity():
    """A stream that PARKS while still mid-prompt (growth fails at a chunk
    boundary inside the prefill) under the FUSED path resumes at its chunk
    cursor and stays token-identical to the scan path and to the eager
    whole-prompt run — the spill/park machinery is path-agnostic."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(2, CFG.vocab, size=30) for _ in range(2)]
    max_new = [4, 4]
    outs = {}
    for pm in ("parallel", "scan"):
        eng, reqs, res = _run(prompts, max_new, lazy=True, groups=1,
                              max_batch=2, prefill_mode=pm)
        c = res["counters"]
        assert c.get("kv_mid_decode_parks", 0) >= 1, pm
        assert eng.pool.occupancy() == 0.0
        outs[pm] = [r.generated for r in reqs]
    _, reqs_e, _ = _run(prompts, max_new, lazy=False, groups=1)
    assert outs["parallel"] == outs["scan"] == \
        [r.generated for r in reqs_e]


def test_parallel_chunk_spanning_pages_token_identity():
    """``prefill_chunk`` above the page size (a chunk whose growth commits
    2 pages mid-chunk) and below it both stay token-identical across the
    two compiled paths — the chunk-size sweep's correctness core."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(2, CFG.vocab, size=s) for s in (28, 9)]
    max_new = [3, 5]
    base = None
    for chunk in (6, 24):
        for pm in ("parallel", "scan"):
            _, reqs, _ = _run(prompts, max_new, lazy=True, groups=1,
                              max_len=32, prefill_mode=pm,
                              prefill_chunk=chunk)
            toks = [r.generated for r in reqs]
            base = base or toks
            assert toks == base, (chunk, pm)


def test_chunk_kernel_and_split_ticks_token_identity():
    """Every cell of the kernel x split matrix generates the scan
    reference's exact tokens, and the split cells actually split: decode
    streams execute ZERO masked prefill-query rows (counter-verified)
    while unsplit mixed ticks pay (C-1) rows per decode stream."""
    rng = np.random.default_rng(13)
    # long prompts prefill while earlier streams decode -> mixed ticks
    prompts = [rng.integers(2, CFG.vocab, size=s) for s in (4, 30, 28, 5)]
    max_new = [14, 4, 4, 10]
    base = None
    for kern in ("blocked", "dense"):
        for split in (True, False):
            eng, reqs, res = _run(prompts, max_new, lazy=True, groups=1,
                                  max_batch=4, pool_streams=4,
                                  chunk_kernel=kern, split_ticks=split)
            toks = [r.generated for r in reqs]
            base = base or toks
            assert toks == base, (kern, split)
            c = res["counters"]
            if split:
                assert c.get("split_ticks", 0) >= 1, (kern, split)
                assert c.get("mixed_tick_decode_rows_saved", 0) > 0
                assert c.get("decode_masked_query_rows", 0) == 0
            else:
                assert c.get("split_ticks", 0) == 0
                assert c.get("decode_masked_query_rows", 0) > 0
            kv = eng.kv_stats()
            assert kv["chunk_kernel"] == kern
    _, reqs_s, _ = _run(prompts, max_new, lazy=True, groups=1, max_batch=4,
                        pool_streams=4, prefill_mode="scan")
    assert [r.generated for r in reqs_s] == base
    # scan mode prices no fused transient regardless of requested kernel
    eng, _, _ = _run(prompts[:1], max_new[:1], lazy=True, groups=1,
                     prefill_mode="scan", chunk_kernel="blocked")
    assert eng.kv_stats()["chunk_kernel"] == "dense"


def test_chunk_wider_than_ring_engine_token_identity():
    """The C<=W clamp is LIFTED: a hybrid model (ring W=32 < max_len=48)
    runs 40-token prefill chunks — wider than its ring — through both
    fused kernels and stays token-identical to the scan path (which steps
    token-by-token and never saw a clamp)."""
    hyb = reduced_config(REGISTRY["recurrentgemma-9b"])
    rng = np.random.default_rng(17)
    prompts = [rng.integers(2, hyb.vocab, size=44) for _ in range(2)]
    topo = ChipletTopology(n_pods=1, groups_per_pod=1, chips_per_group=1)
    outs = {}
    for key, (pm, kern) in {"blocked": ("parallel", "blocked"),
                            "dense": ("parallel", "dense"),
                            "scan": ("scan", "dense")}.items():
        ecfg = EngineConfig(max_batch=2, max_len=48, pool_streams=2,
                            prefill_chunk=40, prefill_mode=pm,
                            chunk_kernel=kern, adaptive=False)
        eng = ServeEngine(hyb, topo, ecfg, spread_rate=1, seed=0)
        assert eng._chunk == 40 > eng.pool.spec.width == 32
        reqs = [eng.submit(p, max_new=3) for p in prompts]
        eng.run_until_done()
        assert all(r.done for r in reqs)
        outs[key] = [r.generated for r in reqs]
    assert outs["blocked"] == outs["dense"] == outs["scan"]


def test_idle_slot_logits_are_poisoned_not_argmaxable():
    """ISSUE 5 bugfix regression: pre-fix, ``chunk_decode_step``
    initialized idle-slot logits to ZEROS, whose argmax is token 0 — a
    perfectly plausible token id at the engine's append site.  Both chunk
    paths must poison idle rows to NEG_INF and ``next_token_ids`` must map
    them to the -1 sentinel, so an idle slot can never append a token in
    any mode (the engine additionally asserts ``tok >= 0`` on append)."""
    import jax
    import jax.numpy as jnp
    from repro.models import decode as dec
    from repro.models.params import init_params
    max_len = 16
    params = init_params(CFG, jax.random.PRNGKey(0))
    spec = dec.cache_view_specs(CFG, max_len)
    cache = dec.init_cache(CFG, 2, max_len)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        2, CFG.vocab, size=(2, 4)), jnp.int32)
    nt = jnp.asarray([4, 0], jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    for step in (dec.chunk_decode_step, dec.prefill_chunk_step):
        lg, _ = step(params, CFG, spec, cache, toks, pos, nt)
        lg = np.asarray(lg)
        assert lg[1].max() <= -1e29, step.__name__    # no argmax-able row
        ids = np.asarray(dec.next_token_ids(jnp.asarray(lg), nt))
        assert ids[1] == -1 and ids[0] >= 0, step.__name__


# ---------------------------------------------------------------------------
# counters / stats surface + cost model
# ---------------------------------------------------------------------------

def test_new_counters_surface_in_kv_stats_and_samples():
    """kv_lazy_grows / kv_mid_decode_parks / prefill_chunks reach the
    engine's kv_stats AND the profiler's StepSample stream."""
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, CFG.vocab, size=20) for _ in range(2)]
    eng, reqs, res = _run(prompts, [12, 12], lazy=True, groups=1,
                          max_batch=2, pool_streams=2)
    kv = eng.kv_stats()
    for key in ("lazy_grows", "mid_decode_parks", "prefill_chunks",
                "evictions", "peak_active_tables", "peak_used_per_domain",
                "prefill_chunk_bytes"):
        assert key in kv, key
    assert kv["prefill_chunks"] >= 2
    assert kv["lazy_grows"] >= 1
    assert kv["prefill_chunk_bytes"] > 0
    samples = eng.counters.samples
    assert sum(s.prefill_chunks for s in samples) >= 2
    assert sum(s.kv_lazy_grows for s in samples) >= 1
    # per-domain watermark actually watched the one busy domain
    assert max(kv["peak_used_per_domain"]) == kv["peak_used_blocks"]


def test_prefill_chunk_bytes_costmodel():
    """prefill_chunk_bytes = chunk * slope(kv_cache_bytes) + state bytes —
    byte-accurate against the cost model for ring and pure-state models."""
    from repro.configs.base import ShapeConfig
    from repro.core.costmodel import (kv_cache_bytes, kv_state_bytes,
                                      kv_token_bytes, prefill_chunk_bytes)
    cfg = CFG
    per_tok = kv_token_bytes(cfg)
    assert per_tok > 0
    s8 = kv_cache_bytes(cfg, ShapeConfig("kv", "decode", 8, 1), 1)
    s16 = kv_cache_bytes(cfg, ShapeConfig("kv", "decode", 16, 1), 1)
    assert s16 - s8 == pytest.approx(8 * per_tok)
    assert prefill_chunk_bytes(cfg, 16) == \
        pytest.approx(16 * per_tok + kv_state_bytes(cfg))
    # a chunk never exceeds the ring
    assert prefill_chunk_bytes(cfg, 64, max_len=16) == \
        pytest.approx(16 * per_tok + kv_state_bytes(cfg))
    ssm = reduced_config(REGISTRY["mamba2-780m"])
    assert kv_token_bytes(ssm) == 0
    assert prefill_chunk_bytes(ssm, 16) == pytest.approx(kv_state_bytes(ssm))


def test_prefill_chunk_score_bytes_costmodel():
    """The parallel path's (C, W + C) f32 score transient, hand-computed
    for one dense and one hybrid config (ISSUE 5 satellite) — and
    ``prefill_chunk_bytes(mode="parallel")`` must price it on top of the
    scan footprint so chunk sweeps compare honest bytes."""
    from repro.core.costmodel import (prefill_chunk_bytes,
                                      prefill_chunk_score_bytes)
    # dense (llama smoke): full attention -> ring width W = max_len = 32;
    # 4 query heads, C=8 queries x (32 prior + 8 chunk) f32 scores, two
    # live buffers (joint scores + softmax probabilities)
    assert prefill_chunk_score_bytes(CFG, 8, max_len=32) == \
        pytest.approx(2 * 4 * 8 * (32 + 8) * 4.0)
    # hybrid (recurrentgemma smoke): attn layers use local_window=32,
    # ring W = min(max_len=16, 32) = 16; recurrent layers add no scores
    hyb = reduced_config(REGISTRY["recurrentgemma-9b"])
    assert hyb.local_window == 32 and hyb.n_heads == 4
    assert prefill_chunk_score_bytes(hyb, 8, max_len=16) == \
        pytest.approx(2 * 4 * 8 * (16 + 8) * 4.0)
    # pure-state model: no attention scores at all
    ssm = reduced_config(REGISTRY["mamba2-780m"])
    assert prefill_chunk_score_bytes(ssm, 8, max_len=16) == 0.0
    # parallel footprint = scan footprint + score transient; a chunk never
    # exceeds the ring in either term
    for cfg, ml in ((CFG, 32), (hyb, 16)):
        assert prefill_chunk_bytes(cfg, 8, ml, mode="parallel") == \
            pytest.approx(prefill_chunk_bytes(cfg, 8, ml)
                          + prefill_chunk_score_bytes(cfg, 8, ml))
    assert prefill_chunk_score_bytes(CFG, 64, max_len=16) == \
        pytest.approx(prefill_chunk_score_bytes(CFG, 16, max_len=16))


def test_prefill_chunk_score_bytes_blocked_kernel():
    """The blocked (Pallas online-softmax) kernel's transient is ONE
    (block_q, block_kv) tile pair, hand-computed for dense and hybrid
    configs, and — the acceptance bound — NEVER exceeds
    2*n_heads*block_q*block_kv*4 no matter how wide the ring or the chunk
    grows (the dense transient scales as C*(W+C))."""
    from repro.core.costmodel import (prefill_chunk_bytes,
                                      prefill_chunk_score_bytes)
    # llama smoke (4 query heads, window=0 -> ring W = max_len):
    # C=8 clips block_q, W+C=40 saturates block_kv=32
    assert prefill_chunk_score_bytes(CFG, 8, max_len=32, kernel="blocked") \
        == pytest.approx(2 * 4 * min(32, 8) * min(32, 32 + 8) * 4.0)
    # hybrid: W = min(max_len=16, local_window=32) = 16, so W+C=24 < 32
    # clips block_kv too
    hyb = reduced_config(REGISTRY["recurrentgemma-9b"])
    assert prefill_chunk_score_bytes(hyb, 8, max_len=16, kernel="blocked") \
        == pytest.approx(2 * 4 * 8 * 24 * 4.0)
    # W- and C-independence: once C and W+C exceed the block sizes the
    # transient is exactly one tile, for ANY chunk/ring width
    bound = 2 * CFG.n_heads * 32 * 32 * 4.0
    for c_tokens, ml in ((32, 64), (256, 1024), (512, 4096), (4096, 65536)):
        got = prefill_chunk_score_bytes(CFG, c_tokens, max_len=ml,
                                        kernel="blocked")
        assert got == pytest.approx(bound)
    for c_tokens, ml in ((1, 8), (8, 32), (64, 4096)):
        assert prefill_chunk_score_bytes(CFG, c_tokens, max_len=ml,
                                         kernel="blocked") <= bound
    # blocked strictly undercuts dense whenever the dense transient
    # outgrows one tile
    assert prefill_chunk_score_bytes(CFG, 16, max_len=512,
                                     kernel="blocked") < \
        prefill_chunk_score_bytes(CFG, 16, max_len=512)
    # pure-state model: still zero
    ssm = reduced_config(REGISTRY["mamba2-780m"])
    assert prefill_chunk_score_bytes(ssm, 8, max_len=16,
                                     kernel="blocked") == 0.0
    # footprint composition threads the kernel through
    for cfg, ml in ((CFG, 32), (hyb, 16)):
        assert prefill_chunk_bytes(cfg, 8, ml, mode="parallel",
                                   kernel="blocked") == \
            pytest.approx(prefill_chunk_bytes(cfg, 8, ml)
                          + prefill_chunk_score_bytes(cfg, 8, ml,
                                                      kernel="blocked"))
    with pytest.raises(ValueError):
        prefill_chunk_score_bytes(CFG, 8, max_len=32, kernel="banded")


def test_waitqueue_order_accessors():
    """WaitQueue keeps first-park order across wake/re-park cycles and
    exposes oldest/youngest + parked_since (used by the fairness path and
    the eviction watchdog)."""
    from repro.core.tasks import TaskRuntime, WaitQueue

    def gen():
        yield

    rt = TaskRuntime(n_pods=1, groups_per_pod=1)
    t = [0.0]
    wq = WaitQueue(rt, clock=lambda: t[0])
    a = rt.spawn(gen(), name="a")
    b = rt.spawn(gen(), name="b")
    t[0] = 1.0
    wq.park(a)
    t[0] = 2.0
    wq.park(b)
    assert a in wq and b in wq and len(wq) == 2
    assert wq.oldest() is a and wq.youngest() is b
    assert wq.parked_since(a) == 1.0
    t[0] = 3.0
    wq.park(a)                       # re-park: keeps position AND timestamp
    assert wq.oldest() is a and wq.parked_since(a) == 1.0
    wq.remove(a)
    assert a not in wq and wq.oldest() is b
    assert wq.parked_since(a) is None


def test_lazy_admits_more_concurrency_than_eager_same_budget():
    """The acceptance property at test scale: under a long-tail max_new
    mix and one full-length stream of budget per domain, lazy admission
    sustains strictly more concurrent reservations than eager."""
    rng = np.random.default_rng(6)
    prompts = [rng.integers(2, CFG.vocab, size=int(rng.integers(4, 14)))
               for _ in range(8)]
    max_new = [20 if i % 4 == 0 else 4 for i in range(8)]
    peaks = {}
    toks = {}
    for mode in ("lazy", "eager"):
        eng, reqs, _ = _run(prompts, max_new, lazy=(mode == "lazy"),
                            groups=2, max_batch=4, pool_streams=1)
        peaks[mode] = eng.pool.peak_active_tables
        toks[mode] = [r.generated for r in reqs]
    assert toks["lazy"] == toks["eager"]
    assert peaks["lazy"] > peaks["eager"]


@pytest.mark.parametrize("arch,in_place", [
    ("llama3-8b", True),            # ring-only cache: new token written back
    ("recurrentgemma-9b", False),   # state leaves: gather, step, scatter
    ("mamba2-780m", False),
])
def test_decode_inplace_forwards_counts_the_paged_decode_path(arch, in_place):
    """Every decode forward of a ring-only model takes the write-back of
    the new token alone (``decode_inplace_forwards`` == ``decode_forwards``);
    hybrid and SSM models keep the whole-view path (0).  Either way the
    paged engine serves the slot monolith's tokens."""
    cfg = reduced_config(REGISTRY[arch])
    rng = np.random.default_rng(23)
    prompts = [rng.integers(2, cfg.vocab, size=s) for s in (5, 27, 12)]
    max_new = [18, 6, 11]
    topo = ChipletTopology(n_pods=1, groups_per_pod=1, chips_per_group=1)
    outs, kv = {}, None
    for paged in (True, False):
        ecfg = EngineConfig(max_batch=4, max_len=32, paged=paged,
                            lazy=paged, pool_streams=4, adaptive=False)
        eng = ServeEngine(cfg, topo, ecfg, spread_rate=1, seed=0)
        reqs = [eng.submit(p, max_new=m) for p, m in zip(prompts, max_new)]
        eng.run_until_done()
        assert all(r.done for r in reqs)
        outs[paged] = [r.generated for r in reqs]
        kv = kv or eng.kv_stats()
    assert outs[True] == outs[False]
    assert kv["decode_forwards"] > 0
    assert kv["decode_inplace_forwards"] == (kv["decode_forwards"]
                                             if in_place else 0)
