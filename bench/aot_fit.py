#!/usr/bin/env python3
"""Size a configuration's engine for one v5e without the chip.

Compiles the engine's largest chunk step and decode step (batch
``max_batch``, chunk one KV page) for a described v5e, with the Pallas
kernels compiled for the chip (``interpret=False``), and prints each
program's ``memory_analysis()`` beside the weights and the KV pool:

    JAX_PLATFORMS=cpu python3 bench/aot_fit.py starcoder2-15b-l10 [--batch 16]

Nothing is allocated: parameters, pool and inputs are shapes only.  The
steps are the engine's own programs, built by its own builders at its
default prefill mode and chunk kernel: ``ServeEngine._make_paged_chunk``
(gather the batch's rings from the pool, the fused chunk forward, scatter
back) and ``decode.make_paged_decode`` (the decode program the engine
jits).  The KV bytes a token holds come from the configuration's
architecture module.
"""
from __future__ import annotations

import argparse
import os
import sys
import types
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench import serve, spec  # noqa: E402

GB = 1e9


def _force_compiled_kernels():
    """Compile the Pallas kernels for the chip, not the CPU interpreter."""
    import importlib
    for mod in ("repro.kernels.flash_attention.kernel",):
        k = importlib.import_module(mod)
        k.resolve_interpret = lambda interpret=None: False


def steps(cfg, sp, device):
    """The engine's (chunk, decode) programs for one device, before jit."""
    from repro.models import decode as dec
    from repro.serving.engine import EngineConfig, ServeEngine
    e = EngineConfig()
    # the chunk builder reads only these of its engine
    host = types.SimpleNamespace(cfg=cfg, devices=[device],
                                 pool=types.SimpleNamespace(spec=sp),
                                 _chunk_kernel=e.chunk_kernel)
    return (ServeEngine._make_paged_chunk(host, e.prefill_mode),
            dec.make_paged_decode(cfg, sp))


def fit(name: str, batch: int = 0, streams: int = 0) -> dict:
    from jax.experimental import topologies
    from repro.models import decode as dec
    from repro.models.params import abstract_params
    from repro.serving.kvpool import KVBlockPool
    _force_compiled_kernels()
    conf = spec.config(name)
    arch = spec.reference(conf["reference"])
    e = conf["engine"]
    B = batch or e["max_batch"]
    streams = streams or e["pool_streams"]
    cfg = serve.model_config(conf)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    sd = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)  # noqa: E731
    params = jax.tree.map(lambda a: sd(a.shape, a.dtype),
                          abstract_params(cfg))
    sp = dec.cache_view_specs(cfg, e["max_len"])
    paged_chunk, paged_decode = steps(cfg, sp, topo.devices[0])
    budget = KVBlockPool.blocks_for_streams(cfg, e["max_len"], streams, 16)
    pages = sp.width // 16
    storage = jax.eval_shape(lambda: dec.init_block_pool(
        cfg, sp, n_blocks=1 + budget["blocks_per_domain"],
        n_states=1 + budget["states_per_domain"], block_tokens=16,
        max_len=e["max_len"]))
    storage = jax.tree.map(lambda a: sd(a.shape, a.dtype), storage)
    i32 = jnp.int32
    out = {"params_gb": sum(a.size * a.dtype.itemsize
                            for a in jax.tree.leaves(params)) / GB,
           "pool_gb": sum(a.size * a.dtype.itemsize
                          for a in jax.tree.leaves(storage)) / GB,
           "kv_token_bytes": arch.kv_token_bytes(conf["model"])}
    for kind, fn, args in (
            ("chunk", paged_chunk,
             (params, storage, sd((B, pages), i32), sd((B,), i32),
              sd((B, 16), i32), sd((B,), i32), sd((B,), i32))),
            ("decode", paged_decode,
             (params, storage, sd((B, pages), i32), sd((B,), i32),
              sd((B, 1), i32), sd((B,), i32)))):
        c = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
        ma = c.memory_analysis()
        out[kind] = {"args_gb": ma.argument_size_in_bytes / GB,
                     "out_gb": ma.output_size_in_bytes / GB,
                     "temp_gb": ma.temp_size_in_bytes / GB,
                     "alias_gb": ma.alias_size_in_bytes / GB,
                     "kernel": "tpu_custom_call" in c.as_text()}
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("config")
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--streams", type=int, default=0)
    a = ap.parse_args()
    r = fit(a.config, a.batch, a.streams)
    print(f"{a.config}: params {r['params_gb']:.3f} GB, pool "
          f"{r['pool_gb']:.3f} GB, KV {r['kv_token_bytes']} B/token")
    for kind in ("chunk", "decode"):
        x = r[kind]
        print(f"  {kind} step: arguments {x['args_gb']:.3f} GB, outputs "
              f"{x['out_gb']:.3f} GB (aliased {x['alias_gb']:.3f}), "
              f"temporaries {x['temp_gb']:.3f} GB; Pallas kernel "
              f"{'in' if x['kernel'] else 'NOT in'} the program; peak "
              f"{x['args_gb'] + x['out_gb'] - x['alias_gb'] + x['temp_gb']:.3f}"
              f" GB")


if __name__ == "__main__":
    main()
