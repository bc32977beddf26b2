"""Speculative decoding (ISSUE 8): n-gram drafting + fused verify.

The acceptance property: a spec-enabled engine is TOKEN-IDENTICAL to the
spec-off engine — greedy acceptance keeps exactly the prefix a plain
decode would have produced — no matter how good or hostile the drafter
is, across model families, page-boundary and ring-wrap rollbacks,
copy-on-write shared pages, and park/spill mid-draft.  Identity is the
gate everywhere; counters then pin which machinery (accepts, rollbacks,
checkpoints) actually ran, so a vacuous pass cannot hide.

Injected drafters make the edge cases deterministic: an ORACLE replays
the spec-off baseline (full accepts), an ANTI-ORACLE proposes baseline+1
(guaranteed full rejects), a PARTIAL drafter prepends a correct prefix to
garbage (guaranteed mid-window rollback).
"""
from typing import Dict, List, Tuple

import numpy as np
import pytest

from repro.configs import REGISTRY, reduced_config
from repro.core.topology import ChipletTopology
from repro.serving.engine import EngineConfig, ServeEngine
from repro.serving.kvpool import KVBlockPool
from repro.serving.spec import NGramDrafter, make_drafter

CFG = reduced_config(REGISTRY["llama3-8b"])
HYB = reduced_config(REGISTRY["recurrentgemma-9b"])


def _engine(cfg=CFG, *, spec="ngram", spec_k=3, groups=1, max_batch=2,
            max_len=48, pool_streams=2, share=False, evict_mode="swap",
            **ecfg_kw):
    topo = ChipletTopology(n_pods=1, groups_per_pod=groups,
                           chips_per_group=1)
    ecfg = EngineConfig(max_batch=max_batch, max_len=max_len, paged=True,
                        lazy=True, pool_streams=pool_streams,
                        adaptive=False, evict_mode=evict_mode,
                        prefix_share=share, spec_decode=spec,
                        spec_k=spec_k, **ecfg_kw)
    return ServeEngine(cfg, topo, ecfg, spread_rate=1, seed=0)


def _serve(eng, prompts, max_new) -> List[List[int]]:
    for p, m in zip(prompts, max_new):
        eng.submit(p, m)
    eng.run_until_done()
    assert all(r.done for r in eng.submitted), "deadlock"
    return [r.generated for r in eng.submitted]


def _baseline(cfg, prompts, max_new, **kw) -> List[List[int]]:
    return _serve(_engine(cfg, spec="off", **kw), prompts, max_new)


class OracleDrafter:
    """Replays the spec-off baseline: every draft token is exactly what
    greedy decode will produce, so every verify is a FULL accept."""

    def __init__(self, prompts, baselines):
        self._by_prompt = {tuple(int(t) for t in p): list(b)
                           for p, b in zip(prompts, baselines)}

    def draft(self, req, k: int) -> List[int]:
        base = self._by_prompt[tuple(int(t) for t in req.prompt)]
        done = len(req.generated)
        return base[done:done + k]


class AntiOracleDrafter(OracleDrafter):
    """Baseline+1 mod vocab: every draft token is provably WRONG, so
    every verify is a FULL reject (m=0) and only the bonus token
    commits — the k=0-accept edge, every tick."""

    def __init__(self, prompts, baselines, vocab):
        super().__init__(prompts, baselines)
        self._vocab = vocab

    def draft(self, req, k: int) -> List[int]:
        return [(t + 1) % self._vocab
                for t in super().draft(req, k)]


class PartialDrafter(OracleDrafter):
    """``good`` correct tokens followed by provably-wrong ones: every
    full-width verify accepts a strict prefix and rolls back the rest."""

    def __init__(self, prompts, baselines, vocab, good=1):
        super().__init__(prompts, baselines)
        self._vocab = vocab
        self._good = good

    def draft(self, req, k: int) -> List[int]:
        toks = super().draft(req, k)
        return (toks[:self._good]
                + [(t + 1) % self._vocab for t in toks[self._good:]])


def _prompts(rng, n, lens, vocab=None):
    v = vocab or CFG.vocab
    return [rng.integers(2, v, size=int(s)) for s, _ in zip(lens, range(n))]


# ---------------------------------------------------------------------------
# identity across families (the tentpole gate)
# ---------------------------------------------------------------------------

ENGINE_FAMILIES = ("llama3-8b", "mixtral-8x22b", "mamba2-780m",
                   "recurrentgemma-9b")


@pytest.mark.parametrize("arch", ENGINE_FAMILIES)
def test_spec_identity_across_families(arch):
    """Speculative decode is token-identical to plain decode for dense /
    MoE / SSM / hybrid engines.  The injected partial drafter (one right
    token, then garbage) guarantees every family exercises drafting,
    acceptance AND rollback — the n-gram drafter can go quiet when the
    generated tokens never recur, which would let the gate pass vacuously."""
    cfg = reduced_config(REGISTRY[arch])
    rng = np.random.default_rng(3)
    prompts = [rng.integers(2, cfg.vocab, size=s) for s in (7, 5)]
    max_new = [14, 11]
    base = _baseline(cfg, prompts, max_new)
    eng = _engine(cfg, spec="ngram")
    eng.drafter = PartialDrafter(prompts, base, cfg.vocab, good=1)
    toks = _serve(eng, prompts, max_new)
    assert toks == base
    kv = eng.kv_stats()
    assert kv["spec_tokens_drafted"] > 0
    assert kv["spec_tokens_accepted"] > 0
    assert kv["spec_rollbacks"] > 0
    assert kv["spec_verify_forwards"] > 0


def test_ngram_drafting_end_to_end():
    """The real prompt-lookup drafter on a prompt that holds every token
    id: the engine drafts from its own committed history (no injection)
    and stays token-identical with a non-trivial amount actually drafted.
    Whatever token the random weights emit already occurs in the prompt,
    so the drafter's 1-gram fallback finds a match on every decode tick —
    independent of the PRNG stream that drew the weights."""
    rng = np.random.default_rng(3)
    prompts = [rng.permutation(CFG.vocab).astype(np.int32)
               for _ in range(2)]
    max_new = [14, 11]
    max_len = CFG.vocab + 32
    base = _baseline(CFG, prompts, max_new, max_len=max_len)
    eng = _engine(CFG, spec="ngram", max_len=max_len)
    assert _serve(eng, prompts, max_new) == base
    kv = eng.kv_stats()
    assert kv["spec_tokens_drafted"] > 0
    assert kv["spec_verify_forwards"] > 0


def test_spec_verify_matches_sequential_decode_encdec():
    """The enc-dec family has no engine serving path (model-level only,
    as in test_continuous_batching): the all-logits verify forward must
    agree with per-token sequential decode on every position's argmax —
    the model-level statement of greedy-acceptance identity."""
    import jax
    import jax.numpy as jnp
    from repro.models import decode as dec
    from repro.models.params import init_params
    cfg = reduced_config(REGISTRY["seamless-m4t-large-v2"])
    max_len, src, B, W = 16, 6, 1, 4
    params = init_params(cfg, jax.random.PRNGKey(0))
    spec = dec.cache_view_specs(cfg, max_len, src)
    key = jax.random.PRNGKey(1)
    rng = np.random.default_rng(2)

    def fresh_cache():
        cache = dec.init_cache(cfg, B, max_len, src)
        for leaf in ("cross_k", "cross_v"):
            cache[leaf] = 0.1 * jax.random.normal(
                key, cache[leaf].shape, cache[leaf].dtype)
        return cache

    toks = jnp.asarray(rng.integers(2, cfg.vocab, size=(B, W)), jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    n = jnp.full((B,), W, jnp.int32)
    lg_v, _ = dec.chunk_decode_step(params, cfg, spec, fresh_cache(), toks,
                                    pos, n, all_logits=True)
    cache = fresh_cache()
    seq = []
    for i in range(W):
        lg, cache = dec.chunk_decode_step(
            params, cfg, spec, cache, toks[:, i:i + 1],
            jnp.full((B,), i, jnp.int32), jnp.ones((B,), jnp.int32))
        seq.append(np.asarray(lg))
    verify = np.asarray(lg_v)
    for i in range(W):
        assert np.argmax(verify[0, i]) == np.argmax(seq[i][0]), i


# ---------------------------------------------------------------------------
# accept / rollback edges, pinned with injected drafters
# ---------------------------------------------------------------------------

def test_full_reject_anti_oracle():
    """Every draft token wrong: m=0 full rejects every spec tick, only
    the bonus token commits — yet output is identical, and (the refined
    rollback design) a pure-attention unwrapped ring takes NO page
    checkpoints: the rejected writes are dead bytes behind the cursor
    mask, overwritten before any read."""
    rng = np.random.default_rng(5)
    prompts = _prompts(rng, 2, (6, 9))
    max_new = [16, 12]
    base = _baseline(CFG, prompts, max_new)
    eng = _engine(CFG, spec="ngram")
    eng.drafter = AntiOracleDrafter(prompts, base, CFG.vocab)
    assert _serve(eng, prompts, max_new) == base
    kv = eng.kv_stats()
    assert kv["spec_tokens_accepted"] == 0
    assert kv["spec_full_rejects"] > 0
    assert kv["spec_rollbacks"] > 0
    assert kv["spec_ckpts"] == 0            # no state, no wrap: no snapshot
    assert kv["spec_rollback_pages"] == 0
    assert kv["spec_rejected_bytes"] > 0


def test_full_accept_oracle():
    """Every draft token right: acceptance is total, no rollback runs,
    and decode finishes in strictly fewer model forwards than tokens."""
    rng = np.random.default_rng(6)
    prompts = _prompts(rng, 2, (5, 8))
    max_new = [18, 15]
    base = _baseline(CFG, prompts, max_new)
    eng = _engine(CFG, spec="ngram")
    eng.drafter = OracleDrafter(prompts, base)
    assert _serve(eng, prompts, max_new) == base
    kv = eng.kv_stats()
    assert kv["spec_tokens_drafted"] > 0
    assert kv["spec_tokens_accepted"] == kv["spec_tokens_drafted"]
    assert kv["spec_rollbacks"] == 0
    assert kv["spec_accept_rate"] == 1.0
    forwards = (kv["decode_row_forwards"] + kv["spec_row_forwards"]
                + kv["spec_row_reapplies"])
    assert forwards < kv["decode_committed_tokens"]


def test_page_boundary_rollback():
    """A verify window that straddles a page boundary rolls back its
    rejected suffix without corrupting either page: prompt length 14 with
    k=3 puts the first window at positions 14..17 across the 16-token
    page seam."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(2, CFG.vocab, size=14)]
    max_new = [15]
    base = _baseline(CFG, prompts, max_new)
    eng = _engine(CFG, spec="ngram")
    assert eng.pool.block_tokens == 16
    eng.drafter = PartialDrafter(prompts, base, CFG.vocab, good=1)
    assert _serve(eng, prompts, max_new) == base
    kv = eng.kv_stats()
    assert kv["spec_rollbacks"] > 0
    assert kv["spec_tokens_accepted"] > 0      # partial, not full, rejects


def test_cow_shared_page_bits_unchanged_across_rollbacks():
    """Prefix-shared pages under speculative rollback: a published page
    attached by a drafting stream keeps its exact bytes through full
    rejects — speculation must never write (or roll back) through a
    refcount>1 page.  The published blocks are byte-compared before and
    after the speculative burst."""
    from repro.models import decode as dec
    rng = np.random.default_rng(8)
    pre = rng.integers(2, CFG.vocab, size=32)       # two full pages
    prompts = [np.concatenate([pre, rng.integers(2, CFG.vocab, size=3)])
               for _ in range(2)]
    max_new = [10, 10]

    base = _baseline(CFG, prompts, max_new, share=True, max_len=64,
                     pool_streams=3)
    warm = _engine(CFG, spec="ngram", share=True, max_len=64,
                   pool_streams=3)
    warm.drafter = AntiOracleDrafter(prompts, base, CFG.vocab)
    # warm request publishes the preamble pages into the prefix index
    assert _serve(warm, prompts[:1], max_new[:1]) == base[:1]
    shared = [b for b in warm.pool._entry_of_block]
    assert len(shared) >= 2
    before = [x for x in dec.extract_pool_entries(
        warm.pool.storage, warm.pool.spec, shared) if x is not None]
    # burst: the second stream attaches the published pages, then drafts
    # hostile tokens every tick
    warm.submit(prompts[1], max_new[1])
    warm.run_until_done()
    assert [r.generated for r in warm.submitted] == base
    kv = warm.kv_stats()
    assert kv["spec_full_rejects"] > 0
    assert kv["prefix_hits"] > 0 or kv["cached_page_hits"] > 0
    after = [x for x in dec.extract_pool_entries(
        warm.pool.storage, warm.pool.spec, shared) if x is not None]
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a, b)
    warm.pool.audit([r.table for r in warm.submitted
                     if r.table is not None])


def test_hybrid_state_rollback_past_ring_wrap():
    """recurrentgemma: rgLRU state slots must snapshot on EVERY spec tick
    (the reduction over fed tokens is not recomputable from pages) and
    ring-WRAPPING windows must also snapshot pages (a rejected write at p
    past the ring width destroys live position p-W).  Identity through
    both, with the wrap checkpoints observed."""
    rng = np.random.default_rng(9)
    prompts = _prompts(rng, 1, (5,), HYB.vocab)
    max_new = [52]                  # ring is 32 < 5 + 52: wraps for sure
    base = _baseline(HYB, prompts, max_new, max_len=64)
    eng = _engine(HYB, spec="ngram", max_len=64)
    assert eng.pool.spec.width < 5 + 52
    eng.drafter = PartialDrafter(prompts, base, HYB.vocab, good=1)
    assert _serve(eng, prompts, max_new) == base
    kv = eng.kv_stats()
    assert kv["spec_rollbacks"] > 0
    assert kv["spec_ckpts"] > 0                 # state slots every tick
    assert kv["spec_ckpt_pages"] > 0            # wrapped windows: pages too
    assert kv["spec_rollback_pages"] > 0
    assert kv["spec_rollback_bytes"] > 0


def test_park_spill_mid_draft():
    """Oversubscription parks a stream BETWEEN spec ticks: the saved
    cursor is the last accepted position, so the restored stream resumes
    token-identically with zero recomputation (swap tier, not restart)."""
    rng = np.random.default_rng(10)
    prompts = [np.tile(rng.integers(2, CFG.vocab, size=4), 5)
               for _ in range(3)]
    max_new = [20, 18, 16]
    kw = dict(pool_streams=1, max_batch=3, max_len=32, evict_mode="swap")
    base = _baseline(CFG, prompts, max_new, **kw)
    eng = _engine(CFG, spec="ngram", **kw)
    assert _serve(eng, prompts, max_new) == base
    kv = eng.kv_stats()
    assert kv["spec_tokens_drafted"] > 0
    assert kv["recompute_tokens"] == 0


# ---------------------------------------------------------------------------
# cached-page retention order (satellite)
# ---------------------------------------------------------------------------

def _retention_pool(retention):
    pool = KVBlockPool(CFG, n_domains=1, max_len=32, blocks_per_domain=4,
                       states_per_domain=4, block_tokens=16,
                       retention=retention)
    bt = pool.block_tokens
    rng = np.random.default_rng(11)
    tables = []
    for i in range(2):
        prompt = rng.integers(2, CFG.vocab, size=bt + 3)
        keys = pool.prefix_keys(prompt)
        t = pool.reserve(0, len(prompt) + 4, first_tokens=len(prompt))
        pool.register_prefix(t, keys, 0, bt, len(prompt))
        tables.append((t, keys, prompt))
    return pool, tables


@pytest.mark.parametrize("retention", ("access", "blind"))
def test_cached_page_retention_order(retention):
    """With every free block caching a published page, "access" reclaims
    the COLDEST page (the one never re-matched) and keeps the re-touched
    one resident; "blind" reclaims in plain free order regardless of the
    touch.  Both count the reclaim."""
    pool, tables = _retention_pool(retention)
    (t1, keys1, p1), (t2, keys2, p2) = tables
    b1, b2 = t1.blocks[0], t2.blocks[0]
    pool.free(t1)
    pool.free(t2)
    # re-touch the FIRST published page only
    hit, _ = pool.match_prefix(0, keys1, prompt_len=len(p1))
    assert hit == [b1]
    # drain every uncached free block, then force one cached reclaim
    grabbed = []
    while True:
        t = pool.reserve(0, 8)
        grabbed.append(t)
        if pool.counters.totals.get("kv_cached_reclaims", 0.0):
            break
    reclaimed_b1 = any(b1 in t.blocks for t in grabbed)
    reclaimed_b2 = any(b2 in t.blocks for t in grabbed)
    if retention == "access":
        # the touched page survives; the cold one was reclaimed
        assert reclaimed_b2 and not reclaimed_b1
        assert pool.match_prefix(0, keys1, prompt_len=len(p1))[0] == [b1]
    else:
        assert reclaimed_b1 or reclaimed_b2
    assert pool.counters.totals["kv_cached_reclaims"] >= 1


# ---------------------------------------------------------------------------
# measured steps-per-token + costmodel (satellites)
# ---------------------------------------------------------------------------

def test_measured_model_steps_parallel_and_scan():
    """HLO-counted sequential model steps per compiled call: the parallel
    path runs ONE fused step for decode, chunk and verify alike; the scan
    reference pays one step per fed token (C for a chunk, spec_w for the
    verify window) — measured from the optimized while loops, not assumed."""
    eng = _engine(CFG, spec="ngram", spec_k=3)
    assert eng.measured_model_steps("decode") == 1.0
    assert eng.measured_model_steps("chunk") == 1.0
    assert eng.measured_model_steps("spec") == 1.0
    scan = _engine(CFG, spec="ngram", spec_k=3, prefill_mode="scan")
    assert scan.measured_model_steps("chunk", C=8) == 8.0
    assert scan.measured_model_steps("spec") == scan._spec_w
    off = _engine(CFG, spec="off")
    with pytest.raises(ValueError):
        off.measured_model_steps("spec")


def test_warm_steps_compiles_and_stays_identical():
    """warm_steps pre-compiles the dispatch grid by writing only null
    rows: serving after a warm-up produces the same tokens as a cold
    engine."""
    rng = np.random.default_rng(12)
    prompts = _prompts(rng, 2, (5, 7))
    max_new = [8, 6]
    base = _baseline(CFG, prompts, max_new)
    eng = _engine(CFG, spec="ngram")
    assert eng.warm_steps() > 0
    assert _serve(eng, prompts, max_new) == base


def test_costmodel_spec_bytes_hand_computed():
    from repro.core.costmodel import (kv_spill_bytes, kv_state_bytes,
                                      kv_token_bytes, spec_rejected_bytes,
                                      spec_rollback_bytes)
    act = 2.0 * CFG.d_model * len(CFG.layer_types()) * 2.0
    assert spec_rejected_bytes(CFG, 0) == 0.0
    assert spec_rejected_bytes(CFG, 3) == pytest.approx(
        3 * (act + kv_token_bytes(CFG)))
    got = spec_rollback_bytes(CFG, 2, 1, 16, ckpts=2, rollbacks=1)
    want = (kv_spill_bytes(CFG, 2, 16, with_state=False)
            + 2 * kv_state_bytes(CFG)
            + kv_spill_bytes(CFG, 1, 16, with_state=False)
            + 1 * kv_state_bytes(CFG))
    assert got == pytest.approx(want)


def test_ngram_drafter_lookup():
    """The prompt-lookup rule itself: most recent prior occurrence of the
    trailing n-gram wins, longest n-gram first, no match -> no draft."""
    d = NGramDrafter(max_ngram=3)

    class R:
        prompt = [1, 2, 3, 9, 1, 2, 3]
        generated = []

    assert d.draft(R(), 2) == [9, 1]          # trigram 1,2,3 matched
    r2 = R()
    r2.prompt = [4, 5, 6, 7]
    assert d.draft(r2, 2) == []               # nothing recurs
    r3 = R()
    r3.prompt = [4, 5, 8, 5]                  # only the 1-gram recurs
    assert d.draft(r3, 3) == [8, 5]
    with pytest.raises(ValueError):
        make_drafter("model")
