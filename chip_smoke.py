#!/usr/bin/env python3
"""Chip smoke: the serving main path on a TPU at published widths.

One chip (the default):

* builds a ``ServeEngine`` for llama3.2-3b at its published widths (28
  layers, d_model 3072, 24 query heads over 8 KV heads, d_ff 8192, vocab
  128256, bf16; random weights from ``--seed``) on its default path:
  paged pool, lazy chunked prefill, the fused "parallel" chunk forward
  through the blocked Pallas ring kernel, split ticks, prefix sharing.
  The chip is split into two logical chiplet-group domains of four
  1024-token streams each;
* serves 8 requests with prompts of a few hundred tokens through the
  open-loop client.  The last one arrives later and shares a 16-page
  prefix with the first, so it attaches that request's published pages;
* re-runs every request through the plain non-paged full forward on the
  same chip, teacher-forced over prompt + served tokens.  Each served
  token's reference logit must lie within ``TOL_STD`` standard
  deviations (of that position's logit row) of the row's maximum, and
  at least ``MIN_AGREE`` of the served tokens must be the reference
  argmax (see the tolerances below).

``--chips 4`` runs only what exists across chips, each against one chip
of the same host:

* (a) the same requests served with the KV pool sharded (and the params
  replicated) over all four chips, after the one-chip placement; both
  pass the same teacher-forced check;
* (b) a few trainer steps on a 2x2 data x model mesh at llama3.2-3b
  widths, cut to ``TRAIN_LAYERS`` whole layers so that one chip holds
  the reference run; losses must agree within ``LOSS_RTOL``.

Usage (from the repository root):

    python chip_smoke.py [--seed N]
    python chip_smoke.py --chips 4

Any failed phase exits non-zero; so does a run where JAX finds no TPU.
Only then is the last stdout line printed:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Times are wall-clock smoke readings taken after the device finished,
not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import REGISTRY  # noqa: E402
from repro.core.topology import ChipletTopology  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.models.params import param_bytes  # noqa: E402
from repro.serving.engine import EngineConfig, ServeEngine  # noqa: E402

MODEL = "llama3.2-3b"
MAX_LEN = 1024
DOMAINS = 2                 # logical chiplet-group domains on the chip
STREAMS_PER_DOMAIN = 4      # 8 streams in all
N_REQUESTS = 8
MAX_NEW = 32
SHARED_PREFIX = 256         # tokens (16 pages of 16) the last request shares
# Teacher-forced tolerances, in units of a reference logit row's standard
# deviation.  A wrong token sits about 4.5 below the row maximum (the
# maximum of 128256 logits), while the top two logits of a row are
# typically only 0.2 apart.  bf16 rounding moves this 28-layer
# random-weight forward's logits by about 0.28 rms (against a float32
# forward on a v5e), so two correct bf16 implementations that round
# differently often pick different near-tied tokens: the served path
# has been seen up to 0.59 below the plain bf16 forward's maximum.  A
# served token passes within TOL_STD of the maximum, and at least
# MIN_AGREE of the served tokens must be the reference argmax (a path
# that scores the wrong positions agrees on almost none).
TOL_STD = 1.5
MIN_AGREE = 0.5
TRAIN_LAYERS = 3            # 4 layers of training state do not fit one chip
TRAIN_STEPS = 4
TRAIN_BATCH, TRAIN_SEQ = 4, 256
LOSS_RTOL = 1e-2


def log(msg: str):
    print(msg, flush=True)


class CompileStats:
    """Counts XLA compiles (programs built or loaded from the persistent
    cache) and their seconds, from JAX's own monitoring events."""

    def __init__(self):
        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration_secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += duration_secs

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def line(self) -> str:
        return (f"{self.programs} programs compiled in "
                f"{self.seconds:.1f} s ({self.cache_hits} loaded from the "
                f"persistent cache)")


def peak_gb(device) -> str:
    stats = device.memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        return "not reported"
    return f"{stats['peak_bytes_in_use'] / 1e9:.2f} GB"


def make_requests(cfg, seed: int):
    """Prompts of a few hundred tokens; the last request repeats the
    first one's opening SHARED_PREFIX tokens and arrives after that
    prefill has published its pages."""
    rng = np.random.default_rng(seed)
    lens = [320] + [int(n) for n in rng.integers(200, 400, N_REQUESTS - 2)]
    prompts = [rng.integers(2, cfg.vocab, n).astype(np.int32) for n in lens]
    tail = rng.integers(2, cfg.vocab, 64).astype(np.int32)
    prompts.append(np.concatenate([prompts[0][:SHARED_PREFIX], tail]))
    gaps = [0] * (N_REQUESTS - 1) + [40]
    return [(g, p, MAX_NEW) for g, p in zip(gaps, prompts)]


def serve(cfg, devices, seed: int, compiles: CompileStats, tag: str):
    """Serve the smoke requests through ServeEngine's normal entry points
    on ``devices``; returns (engine, served requests)."""
    topo = ChipletTopology(n_pods=1, groups_per_pod=DOMAINS,
                           chips_per_group=1)
    ecfg = EngineConfig(max_batch=STREAMS_PER_DOMAIN, max_len=MAX_LEN,
                        pool_streams=STREAMS_PER_DOMAIN)
    t0 = time.monotonic()
    eng = ServeEngine(cfg, topo, ecfg, seed=seed, spread_rate=1,
                      devices=devices)
    jax.block_until_ready(eng.params)
    spread = len(jax.tree.leaves(eng.pool.storage)[0].sharding.device_set)
    log(f"[{tag}] engine built in {time.monotonic() - t0:.1f} s on "
        f"{len(devices)} device(s): params "
        f"{param_bytes(cfg) / 1e9:.2f} GB, pool {eng.pool.total_blocks()} "
        f"pages of {eng.pool.block_tokens} tokens over "
        f"{eng.pool.n_domains} domains, stored on {spread} device(s); "
        f"peak after init {peak_gb(devices[0])}")
    t0 = time.monotonic()
    calls = eng.warm_steps(chunks=(eng.pool.block_tokens,))
    jax.block_until_ready(eng.pool.storage)
    log(f"[{tag}] warm-up: {calls} step calls in "
        f"{time.monotonic() - t0:.1f} s; compiles so far: "
        f"{compiles.line()}")
    schedule = make_requests(cfg, seed)
    t0 = time.monotonic()
    eng.open_loop_client(schedule)
    eng.run_until_done()
    wall = time.monotonic() - t0
    reqs = list(eng.submitted)
    kv = eng.kv_stats()
    tot = eng.counters.totals
    st = ServeEngine.stats(reqs)
    log(f"[{tag}] served {sum(r.done for r in reqs)}/{len(reqs)} requests, "
        f"{st.get('tokens', 0)} tokens in {wall:.1f} s; prefix hits "
        f"{tot.get('kv_prefix_hits', 0):.0f}, prompt tokens served from "
        f"shared pages {tot.get('prefill_tokens_skipped', 0):.0f}; "
        f"chunk ticks {kv['chunk_ticks']:.0f}; relayouts "
        f"{len(eng.relayouts)}")
    if st:
        log(f"[{tag}] smoke readings (host clock, not a benchmark): "
            f"TTFT p50 {st['ttft_p50'] * 1e3:.1f} ms, TPOT p50 "
            f"{st['tpot_p50'] * 1e3:.1f} ms; peak after serving "
            f"{peak_gb(devices[0])}")
    bad = [r.rid for r in reqs
           if not r.done or len(r.generated) != r.max_new]
    if len(reqs) != N_REQUESTS or bad:
        raise SystemExit(f"[{tag}] FAIL: requests unfinished or short: {bad}")
    if tot.get("kv_prefix_hits", 0) < 1:
        raise SystemExit(f"[{tag}] FAIL: the shared-prefix request did not "
                         "hit the prefix cache")
    return eng, reqs


def reference_logits(cfg, params, reqs) -> np.ndarray:
    """Teacher-forced logits (R, n, V) of the plain full forward over
    prompt + served tokens at every served position.  Sequences are
    padded to one length; the forward is causal, so padding never reaches
    a checked position."""
    n = len(reqs[0].generated)
    length = max(len(r.prompt) + n for r in reqs)
    length = -(-length // 128) * 128
    toks = np.zeros((len(reqs), length), np.int32)
    idx = np.zeros((len(reqs), n), np.int32)
    for i, r in enumerate(reqs):
        seq = np.concatenate([r.prompt, np.asarray(r.generated[:-1],
                                                   np.int32)])
        toks[i, :len(seq)] = seq
        idx[i] = len(r.prompt) - 1 + np.arange(n)

    @jax.jit
    def logits(params, tokens, idx):
        x, _ = T.forward(params, cfg, tokens)
        x = jnp.take_along_axis(x, idx[:, :, None], axis=1)
        return T.head_logits(params, cfg, x)[..., :cfg.vocab]

    return np.asarray(logits(params, jnp.asarray(toks), jnp.asarray(idx)))


def teacher_forced_check(cfg, params, reqs, tag: str):
    """Score every served token against the plain forward — the same
    bf16 weights and compute dtype, sharing no code with paging, chunked
    prefill or the ring kernel.  A token's gap is how far its reference
    logit sits below the row maximum, in row standard deviations.  The
    served path passes when every gap is within TOL_STD and at least
    MIN_AGREE of its tokens are the reference argmax."""
    ref = reference_logits(cfg, params, reqs)
    mx, sd = ref.max(-1), ref.std(-1)

    def gaps(toks):                                       # (R, n) -> (R, n)
        return (mx - np.take_along_axis(ref, toks[..., None], -1)[..., 0]) / sd

    served = np.asarray([r.generated for r in reqs])        # (R, n)
    g = gaps(served)
    # control: the NEXT served token scored at this position — what a
    # one-position slip would look like to the check
    g_ctrl = gaps(np.concatenate([served[:, 1:], served[:, :1]], 1))[:, :-1]
    agree = float((g == 0).mean())
    q = "/".join(f"{v:.4f}" for v in np.quantile(g, [.5, .9, 1]))
    log(f"[{tag}] teacher-forced check against the plain forward, "
        f"{served.size} tokens: {agree:.4f} are its argmax (minimum "
        f"{MIN_AGREE}), gap p50/p90/max {q} std (tolerance {TOL_STD}); "
        f"control (next token here) median gap {np.median(g_ctrl):.4f} std; "
        f"worst gap per request {' '.join(f'{v:.4f}' for v in g.max(1))}")
    if not np.all(np.isfinite(ref)) or g.max() > TOL_STD \
            or agree < MIN_AGREE:
        worst = np.unravel_index(np.argmax(g), g.shape)
        raise SystemExit(f"[{tag}] FAIL: served tokens outside tolerance "
                         f"(worst at request {worst[0]} token {worst[1]})")


def smoke_one_chip(cfg, seed: int, compiles: CompileStats):
    dev = jax.devices()[:1]
    eng, reqs = serve(cfg, dev, seed, compiles, "1 chip")
    teacher_forced_check(cfg, eng.params, reqs, "1 chip")
    log(f"[1 chip] compiles in all: {compiles.line()}; peak "
        f"{peak_gb(dev[0])}")


class _Blocks:
    """Seeded (batch, seq+1) token blocks: the loader interface the
    trainer reads, built in memory from the synthetic corpus."""

    def __init__(self, vocab: int, seed: int):
        from repro.data.pipeline import SyntheticCorpus
        self._toks = SyntheticCorpus(vocab, seed=seed).shard_tokens(
            0, TRAIN_STEPS * TRAIN_BATCH * (TRAIN_SEQ + 1))
        self._step = 0

    def next(self) -> np.ndarray:
        n = TRAIN_BATCH * (TRAIN_SEQ + 1)
        block = self._toks[self._step * n:(self._step + 1) * n]
        self._step += 1
        return block.reshape(TRAIN_BATCH, TRAIN_SEQ + 1)


def train_losses(cfg, devices, shape, seed: int):
    from jax.sharding import Mesh
    from repro.optim.adamw import AdamWConfig
    from repro.runtime.trainer import Trainer, TrainerConfig
    mesh = Mesh(np.array(devices).reshape(shape), ("data", "model"))
    tcfg = TrainerConfig(steps=TRAIN_STEPS, ckpt_every=0, seed=seed,
                         log_every=TRAIN_STEPS,
                         opt=AdamWConfig(warmup_steps=1, peak_lr=1e-3,
                                         total_steps=TRAIN_STEPS))
    tr = Trainer(cfg, mesh, _Blocks(cfg.vocab, seed), tcfg, log=log)
    out = tr.run()
    del tr
    gc.collect()
    return out["losses"]


def smoke_four_chips(cfg, seed: int, compiles: CompileStats):
    devs = jax.devices()
    if len(devs) != 4:
        raise SystemExit(f"--chips 4 needs 4 devices, JAX sees {len(devs)}")
    # (a) pool over one chip, then sharded over all four
    tokens = {}
    for tag, use in (("pool on 1 chip", devs[:1]),
                     ("pool on 4 chips", devs)):
        eng, reqs = serve(cfg, use, seed, compiles, tag)
        # the reference runs on chip 0, from its copy of the params
        ref_params = jax.tree.map(
            lambda a: a.addressable_shards[0].data, eng.params)
        teacher_forced_check(cfg, ref_params, reqs, tag)
        tokens[tag] = [list(r.generated) for r in reqs]
        del eng, reqs, ref_params
        gc.collect()
    a, b = tokens.values()
    same = sum(x == y for ra, rb in zip(a, b) for x, y in zip(ra, rb))
    log(f"[pool] {same}/{sum(map(len, a))} served tokens identical "
        f"between the 1-chip and 4-chip placements")
    # (b) trainer: 2x2 data x model mesh against one chip
    tcfg = dataclasses.replace(cfg, n_layers=TRAIN_LAYERS)
    ref = train_losses(tcfg, devs[:1], (1, 1), seed)
    mesh = train_losses(tcfg, devs, (2, 2), seed)
    diff = max(abs(x - y) / abs(x) for x, y in zip(ref, mesh))
    log(f"[train] {TRAIN_LAYERS}-layer llama3.2-3b, batch {TRAIN_BATCH}x"
        f"{TRAIN_SEQ}: 1-chip losses {[round(x, 5) for x in ref]}, 2x2 "
        f"losses {[round(x, 5) for x in mesh]}, max relative gap "
        f"{diff:.2e} (tolerance {LOSS_RTOL})")
    if not (np.all(np.isfinite(ref)) and np.all(np.isfinite(mesh))) \
            or diff > LOSS_RTOL:
        raise SystemExit("[train] FAIL: 2x2 losses disagree with one chip")
    log(f"[4 chips] compiles in all: {compiles.line()}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        sys.exit(2)
    compiles = CompileStats()
    cfg = REGISTRY[MODEL]
    log(f"model {cfg.name}: {cfg.n_layers} layers x d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads / {cfg.n_kv_heads} KV heads x {cfg.head_dim}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab}, {cfg.param_dtype}; "
        f"{cfg.param_count() / 1e9:.3f}e9 params; device {dev.device_kind} "
        f"x {len(jax.devices())}; compile cache {cache_dir}")
    if args.chips == 4:
        smoke_four_chips(cfg, args.seed, compiles)
    else:
        smoke_one_chip(cfg, args.seed, compiles)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
