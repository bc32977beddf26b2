"""Quickstart: train a tiny llama-family model for 20 steps with the ARCAS
runtime (counters + Algorithm-1 controller) and generate a few tokens.

    PYTHONPATH=src python examples/quickstart.py
"""
import shutil

import jax
import numpy as np
from jax.sharding import Mesh

from repro.configs import REGISTRY, reduced_config
from repro.core.topology import ChipletTopology
from repro.launch.compile_cache import enable_compile_cache
from repro.data.pipeline import (ShardedLoader, SyntheticCorpus,
                                 write_corpus_shards)
from repro.launch.steps import make_generate, make_prefill
from repro.models.params import init_params
from repro.runtime.trainer import Trainer, TrainerConfig


def main():
    enable_compile_cache()
    cfg = reduced_config(REGISTRY["llama3-8b"])
    print(f"model: {cfg.name} ({cfg.n_layers}L d={cfg.d_model})")

    # --- data + trainer ---------------------------------------------------
    shutil.rmtree("/tmp/repro_quickstart", ignore_errors=True)
    corpus = SyntheticCorpus(cfg.vocab, seed=0)
    files = write_corpus_shards("/tmp/repro_quickstart/data", corpus,
                                n_shards=2, tokens_per_shard=50_000)
    loader = ShardedLoader(files, seq_len=64, batch=4)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    topo = ChipletTopology(n_pods=1, groups_per_pod=4, chips_per_group=4)
    trainer = Trainer(cfg, mesh, loader,
                      TrainerConfig(steps=20, ckpt_every=10, log_every=5,
                                    ckpt_dir="/tmp/repro_quickstart/ckpt"),
                      topology=topo)
    out = trainer.run()
    print(f"trained {out['steps']} steps; "
          f"loss {out['losses'][0]:.3f} -> {out['losses'][-1]:.3f}")

    # --- generate ----------------------------------------------------------
    prompt = np.array([[5, 17, 42, 99]], np.int32)
    prefill = jax.jit(make_prefill(cfg, max_len=64))
    logits, cache = prefill(trainer.params, {"tokens": prompt})
    gen = jax.jit(make_generate(cfg, steps=12))
    first = np.argmax(np.asarray(logits), -1)[:, None].astype(np.int32)
    pos = np.full((1,), prompt.shape[1], np.int32)
    toks, _, _ = gen(trainer.params, cache, first, pos, jax.random.PRNGKey(0))
    print("generated tokens:", np.asarray(toks)[0].tolist())
    print("ARCAS counters:", {k: round(v, 1) for k, v in
                              trainer.counters.snapshot().items()})


if __name__ == "__main__":
    main()
