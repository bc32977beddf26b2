"""The engine's host spans in JAX's profiler trace, and the program names
the benchmark's trace readers match.

A tiny engine runs a shared-prefix workload under ``jax.profiler.trace``;
the trace is read back with the benchmark's own loader (``bench/spans.py``),
so these tests pin what a traced run on the chip can attribute its idle
time to.
"""
import collections
import glob
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY, reduced_config
from repro.core.topology import ChipletTopology
from repro.serving.engine import EngineConfig, ServeEngine

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import spans as bspans  # noqa: E402

CFG = reduced_config(REGISTRY["llama3-8b"])

NAMES = ("arcas.round", "arcas.admit", "arcas.pool.match", "arcas.assemble",
         "arcas.pool.cow", "arcas.dispatch", "arcas.sync", "arcas.commit",
         "arcas.pool.publish", "arcas.stall", "arcas.round_metrics")


def _engine(**kw):
    topo = ChipletTopology(n_pods=1, groups_per_pod=1, chips_per_group=1)
    ecfg = EngineConfig(max_batch=2, max_len=64, pool_streams=4,
                        adaptive=False, **kw)
    return ServeEngine(CFG, topo, ecfg, spread_rate=1, seed=0)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Three prompts sharing a one-page preamble; the last two decode past
    the ring width, so their wrap writes copy the shared page (CoW).  The
    first runs alone before the trace, to publish the preamble."""
    eng = _engine()
    bt = eng.pool.block_tokens
    W = eng.pool.pages_per_stream * bt
    rng = np.random.default_rng(6)
    pre = rng.integers(2, CFG.vocab, size=bt)
    prompts = [np.concatenate([pre, rng.integers(2, CFG.vocab, size=3)])
               for _ in range(3)]
    eng.submit(prompts[0], 4)
    eng.run_until_done()
    for p in prompts[1:]:
        eng.submit(p, W - len(p) + bt)
    before = dict(eng.counters.totals)
    d = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(d)):
        eng.run_until_done()
    after = eng.counters.totals
    delta = {k: after.get(k, 0.0) - before.get(k, 0.0) for k in after}
    path = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
    return bspans.load(path)["spans"], delta


def test_every_span_appears(traced):
    spans, _ = traced
    names = collections.Counter(s[0] for s in spans)
    assert set(NAMES) <= set(names), set(NAMES) - set(names)
    assert all(n.startswith("arcas.") for n in names)


def test_spans_lie_inside_rounds(traced):
    spans, _ = traced
    rounds = sorted((s, s + d) for n, s, d, _ in spans if n == "arcas.round")
    starts = [r[0] for r in rounds]
    for n, s, d, _ in spans:
        if n == "arcas.round":
            continue
        i = np.searchsorted(starts, s, side="right") - 1
        assert i >= 0 and rounds[i][0] <= s and s + d <= rounds[i][1], n


def test_one_sync_per_device_step(traced):
    spans, delta = traced
    syncs = sum(1 for s in spans if s[0] == "arcas.sync")
    steps = (delta.get("chunk_ticks", 0) + delta.get("decode_forwards", 0)
             + delta.get("spec_verify_forwards", 0))
    assert steps > 0 and syncs == steps
    dispatched = collections.Counter(s[3].get("step") for s in spans
                                     if s[0] == "arcas.dispatch")
    assert dispatched["chunk"] == delta.get("chunk_ticks", 0)
    assert dispatched["decode"] == delta.get("decode_forwards", 0)


def test_shared_prefix_run_publishes_and_forks(traced):
    spans, delta = traced
    names = collections.Counter(s[0] for s in spans)
    assert names["arcas.pool.publish"] >= 1
    assert names["arcas.pool.cow"] == delta.get("kv_cow_forks", 0) >= 1
    rids = {s[3].get("rid") for s in spans if s[0] == "arcas.admit"}
    assert rids and None not in rids


def _lowered_text(eng, kind):
    """The StableHLO of a paged step at batch 1, as jit lowers it."""
    P = eng.pool.pages_per_stream
    sd = jax.ShapeDtypeStruct
    storage = jax.tree.map(lambda a: sd(a.shape, a.dtype), eng.pool.storage)
    i32 = lambda *s: sd(s, jnp.int32)  # noqa: E731
    if kind == "decode":
        fn, args = eng._paged_decode, (i32(1, 1), i32(1))
    else:
        fn, args = eng._paged_chunk, (i32(1, eng._chunk), i32(1), i32(1))
    return fn.lower(eng.params, storage, i32(1, P), i32(1), *args).as_text()


@pytest.mark.parametrize("kind,module", [("chunk", "jit_paged_chunk"),
                                         ("decode", "jit_paged_decode")])
def test_step_module_names(kind, module):
    """``chunk_step_ms`` and ``decode_step_ms`` find the steps' device time
    by these program names (``paged_chunk`` / ``paged_decode``)."""
    assert f"module @{module} " in _lowered_text(_engine(), kind)
