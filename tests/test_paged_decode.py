"""The paged decode step that writes back only the new token
(``decode.paged_decode_step``) against the step it replaces for ring-only
models: gather the batch's whole rings out of the block pool, run
``decode_step`` over them, scatter every ring back.

One step over a pool of random pages must give the same logits, bit for
bit, and leave every page the same, byte for byte.  The batch holds a
stream below the ring width, one that has wrapped it, one that shares
another stream's first page (a prefix-shared page that neither writes),
and a bucket-padding row on the null table.  Block 0 is the null block:
padding rows write into it and nothing reads it, so it is left out of the
page comparison.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY, reduced_config
from repro.models import decode as dec
from repro.models.params import init_params
from repro.serving.kvpool import KVBlockPool


def _gather_step_scatter(cfg, spec):
    def step(params, storage, tables, slots, tokens, pos):
        view = dec.gather_cache_view(storage, spec, tables, slots)
        logits, view = dec.decode_step(params, cfg, view, tokens, pos)
        return logits, dec.scatter_cache_view(storage, spec, tables, slots,
                                              view)
    return step


@pytest.mark.parametrize("arch,max_len,dtype", [
    ("llama3-8b", 64, "float32"),       # dense, ring 64 in 4 pages of 16
    ("llama3-8b", 64, "bfloat16"),      # the served precision
    ("llama3-8b", 40, "float32"),       # unaligned ring: pages of 10
    ("mixtral-8x22b", 64, "float32"),   # MoE, sliding window over the ring
    ("qwen2-vl-2b", 64, "float32"),     # VLM, M-RoPE
])
def test_paged_decode_matches_gather_step_scatter(arch, max_len, dtype):
    cfg = dataclasses.replace(reduced_config(REGISTRY[arch]),
                              param_dtype=dtype, compute_dtype=dtype)
    budget = KVBlockPool.blocks_for_streams(cfg, max_len, 4, 16)
    pool = KVBlockPool(cfg, n_domains=1, max_len=max_len, block_tokens=16,
                       **budget)
    spec, bt, P = pool.spec, pool.block_tokens, pool.pages_per_stream
    W = P * bt
    assert dec.decode_writes_in_place(cfg, spec) and P >= 3
    params = init_params(cfg, jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(pool.storage)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    storage = jax.tree.unflatten(spec.treedef, [
        jax.random.normal(k, a.shape, jnp.float32).astype(a.dtype)
        for k, a in zip(keys, leaves)])
    n_blocks = leaves[0].shape[1]
    assert n_blocks > 3 * P

    shared = 1
    tables = np.zeros((4, P), np.int32)
    tables[0] = np.arange(1, 1 + P)                 # owns blocks 1..P
    tables[1] = np.arange(1 + P, 1 + 2 * P)
    tables[2] = np.arange(1 + 2 * P, 1 + 3 * P)
    tables[2, 0] = shared                           # stream 0's first page
    pos = np.asarray([
        bt + 3,                 # below W: writes page 1
        W + bt + 5,             # wrapped: slot bt + 5, page 1
        W - 1,                  # below W: writes the last page
        0,                      # bucket padding on the null table
    ], np.int32)
    written = {int(tables[b, (p % W) // bt]) for b, p in enumerate(pos[:3])}
    assert shared not in written
    tokens = np.random.default_rng(2).integers(2, cfg.vocab, (4, 1))
    args = (params, storage, jnp.asarray(tables),
            jnp.zeros((4,), jnp.int32), jnp.asarray(tokens, jnp.int32),
            jnp.asarray(pos))

    lg_ref, pool_ref = jax.jit(_gather_step_scatter(cfg, spec))(*args)
    lg, pool_new = jax.jit(dec.make_paged_decode(cfg, spec))(*args)

    np.testing.assert_array_equal(np.asarray(lg), np.asarray(lg_ref))
    for before, ref, new in zip(jax.tree.leaves(storage),
                                jax.tree.leaves(pool_ref),
                                jax.tree.leaves(pool_new)):
        before, ref, new = (np.asarray(a)[:, 1:] for a in (before, ref, new))
        np.testing.assert_array_equal(new, ref)
        np.testing.assert_array_equal(new[:, shared - 1],
                                      before[:, shared - 1])
        # the step wrote the new token of each real stream, and only there
        changed = np.argwhere((new != before).any(axis=(0, 3, 4)))
        assert {(int(b) + 1, int(o)) for b, o in changed} == {
            (int(tables[b, (p % W) // bt]), int(p % bt))
            for b, p in enumerate(pos[:3])}
