"""Serving demo: continuous batching with ARCAS adaptive replica layout and
the paged chiplet-aware KV allocator.

Two phases of load hit the engine:
  1. many small requests  -> compact layout (many replicas) serves best;
  2. long-context requests -> KV pressure + steals push the controller
     toward spread (fewer, larger replica groups).

KV lives in a block pool partitioned per chiplet-group domain: requests
hold block tables, relayouts move tables (not cache slices), and admission
parks on pool exhaustion instead of queueing blindly.

    PYTHONPATH=src python examples/serve_adaptive.py
"""
import numpy as np

from repro.configs import REGISTRY, reduced_config
from repro.core.topology import ChipletTopology
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.engine import EngineConfig, ServeEngine


def main():
    enable_compile_cache()
    cfg = reduced_config(REGISTRY["mixtral-8x22b"])
    topo = ChipletTopology(n_pods=1, groups_per_pod=4, chips_per_group=2)
    eng = ServeEngine(cfg, topo, EngineConfig(max_batch=2, max_len=96,
                                              pool_streams=2),
                      spread_rate=1)
    rng = np.random.default_rng(0)

    print(f"groups={len(eng.groups)} (spread_rate="
          f"{eng.controller.spread_rate}), KV pool: "
          f"{eng.pool.total_blocks()} blocks of "
          f"{eng.pool.block_tokens} tokens over "
          f"{eng.pool.n_domains} chiplet-group domains")
    # phase 1: short interactive requests
    short = [eng.submit(rng.integers(2, cfg.vocab, size=6), max_new=4)
             for _ in range(10)]
    eng.run_until_done()
    print("phase1 (short):", ServeEngine.stats(short))

    # phase 2: long-context analytical requests, arriving over time
    # (open-loop client on the shared task runtime)
    sched = [(2, rng.integers(2, cfg.vocab, size=48), 8) for _ in range(6)]
    eng.open_loop_client(sched)
    eng.run_until_done()
    long = eng.submitted[len(short):]
    print("phase2 (long, open-loop):", ServeEngine.stats(long))
    print("controller decisions:",
          [(d.step, d.old_spread, "->", d.new_spread, d.reason)
           for d in eng.controller.decisions])
    print("live relayouts (mid-run group rebuilds):")
    for r in eng.relayouts:
        print(f"  step {r['step']}: {r['old_groups']} -> {r['new_groups']} "
              f"groups, {r['moved_slots']} streams re-pointed, "
              f"{r['blocks_migrated']:.0f} KV blocks copied, "
              f"{r['requeued']} requests requeued")
    print("kv pool:", {k: round(v, 3) if isinstance(v, (int, float)) else v
                       for k, v in eng.kv_stats().items()
                       if not isinstance(v, list)})
    print("counters:", {k: round(v, 1) for k, v in
                        eng.counters.snapshot().items()
                        if "steal" in k or k in ("prefills", "decode_steps",
                                                 "kv_alloc_failures",
                                                 "tasks_unblocked")})


if __name__ == "__main__":
    main()
