"""Autoregressive serving path: cache init, prefill, single-token decode.

Caches use ring buffers of width W = min(max_len, attention window), so
sliding-window / recurrent / SSM architectures serve 500k+ contexts with a
bounded working set — the property that makes their ``long_500k`` cells
runnable (and the ARCAS "compact" policy attractive for them).

Cache pytrees mirror the parameter stacking so layer loops are
``lax.scan``s over (stacked params, stacked cache).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssd as ssd_mod
from repro.models.params import hybrid_structure
from repro.models.transformer import (
    _attn_out, _attn_proj, _ffn, cdt, embed_tokens, forward, head_logits,
    _rope_for)


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------

def _attn_cache_width(cfg: ModelConfig, max_len: int, layer_type="attn",
                      hybrid=False) -> int:
    w = cfg.local_window if hybrid else cfg.window
    return min(max_len, w) if w else max_len


def _attn_cache(cfg: ModelConfig, B: int, W: int):
    dtype = cdt(cfg)
    shape = (B, W, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _layer_cache(cfg: ModelConfig, lt: str, B: int, max_len: int,
                 hybrid=False):
    if lt == "attn":
        return _attn_cache(cfg, B, _attn_cache_width(cfg, max_len, hybrid=hybrid))
    if lt == "rec":
        return rglru_mod.rglru_init_state(cfg, B, cdt(cfg))
    if lt == "ssd":
        return ssd_mod.ssd_init_state(cfg, B, cdt(cfg))
    raise ValueError(lt)


def _stack_cache(c, n):
    return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), c)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               src_len: int = 0) -> Dict:
    """Zero cache for ``batch`` streams with context capacity ``max_len``."""
    if cfg.family == "encdec":
        self_c = _stack_cache(_attn_cache(cfg, batch, max_len), cfg.dec_layers)
        dt = cdt(cfg)
        cshape = (cfg.dec_layers, batch, src_len, cfg.n_kv_heads, cfg.head_dim)
        return {"self": self_c,
                "cross_k": jnp.zeros(cshape, dt),
                "cross_v": jnp.zeros(cshape, dt)}
    if cfg.block_pattern:
        pattern, n_groups, tail = hybrid_structure(cfg)
        group = {f"b{i}_{t}": _layer_cache(cfg, t, batch, max_len, hybrid=True)
                 for i, t in enumerate(pattern)}
        return {"groups": _stack_cache(group, n_groups),
                "tail": {f"t{i}_{t}": _layer_cache(cfg, t, batch, max_len,
                                                   hybrid=True)
                         for i, t in enumerate(tail)}}
    lt = cfg.layer_types()[0]
    return {"layers": _stack_cache(_layer_cache(cfg, lt, batch, max_len),
                                   cfg.n_layers)}


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int,
                   src_len: int = 0):
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_len, src_len))


# ---------------------------------------------------------------------------
# Paged cache views (the KV block pool's device-side layout)
# ---------------------------------------------------------------------------
#
# A *block pool* stores the same pytree structure as ``init_cache`` but with
# the stream axis replaced by a physical-block axis:
#
#   token leaves  (.., B, W, rest)  ->  (.., n_blocks, block_tokens, rest)
#   state leaves  (.., B, rest)     ->  (.., n_states, rest)
#
# A stream is then a *block table* — ``W / block_tokens`` physical block ids
# (its ring-buffer pages, in ring order) plus one state slot — and the
# batched cache the decode step consumes is materialized by gathering the
# active streams' tables into a (.., B, W, rest) view and scattered back
# after the step.  Leaf classification is structural: a leaf whose shape
# changes with ``max_len`` has a token (ring) axis; one whose shape only
# changes with ``batch`` is per-stream state (recurrent/SSD states, enc-dec
# cross-attention KV).

@dataclasses.dataclass(frozen=True)
class CacheLeafSpec:
    batch_axis: int
    token_axis: Optional[int]       # None = per-stream state leaf
    width: int                      # ring width at the probed max_len (tokens)


@dataclasses.dataclass(frozen=True)
class CacheViewSpec:
    """Per-leaf layout of the serving cache, in ``jax.tree`` leaf order."""
    leaves: Tuple[CacheLeafSpec, ...]
    treedef: Any
    width: int                      # shared ring width of all token leaves

    @property
    def has_token_leaves(self) -> bool:
        return any(s.token_axis is not None for s in self.leaves)


def cache_view_specs(cfg: ModelConfig, max_len: int,
                     src_len: int = 0) -> CacheViewSpec:
    """Classify every cache leaf by probing ``init_cache`` shapes.

    Probes with batch 1 vs 2 locate the stream axis; probes with max_len 1
    vs 2 locate the token (ring) axis.  Token axes are required to sit
    immediately after the stream axis (true for every family) so a gathered
    (block, token) pair can be reshaped into the contiguous (B, W) view.
    """
    b1 = jax.tree.leaves(abstract_cache(cfg, 1, max_len, src_len))
    b2 = jax.tree.leaves(abstract_cache(cfg, 2, max_len, src_len))
    t1, tdef = jax.tree.flatten(abstract_cache(cfg, 1, 1, src_len))
    t2 = jax.tree.leaves(abstract_cache(cfg, 1, 2, src_len))
    specs = []
    for lb1, lb2, lt1, lt2 in zip(b1, b2, t1, t2):
        baxes = [i for i, (a, b) in enumerate(zip(lb1.shape, lb2.shape))
                 if a != b]
        assert len(baxes) == 1, f"ambiguous stream axis: {lb1.shape}"
        taxes = [i for i, (a, b) in enumerate(zip(lt1.shape, lt2.shape))
                 if a != b]
        assert len(taxes) <= 1, f"ambiguous token axis: {lt1.shape}"
        tax = taxes[0] if taxes else None
        if tax is not None:
            assert tax == baxes[0] + 1, \
                f"token axis must follow stream axis: {lb1.shape}"
        width = lb1.shape[tax] if tax is not None else 0
        specs.append(CacheLeafSpec(baxes[0], tax, width))
    widths = {s.width for s in specs if s.token_axis is not None}
    assert len(widths) <= 1, f"token leaves disagree on ring width: {widths}"
    return CacheViewSpec(tuple(specs), tdef,
                         widths.pop() if widths else 0)


def init_block_pool(cfg: ModelConfig, spec: CacheViewSpec, n_blocks: int,
                    n_states: int, block_tokens: int, max_len: int,
                    src_len: int = 0):
    """Zeroed physical storage for ``n_blocks`` KV pages + ``n_states``
    per-stream state slots (index 0 of each is the engine's null slot)."""
    base = jax.tree.leaves(abstract_cache(cfg, 1, max_len, src_len))
    out = []
    for leaf, s in zip(base, spec.leaves):
        if s.token_axis is not None:
            shape = (leaf.shape[:s.batch_axis] + (n_blocks, block_tokens)
                     + leaf.shape[s.token_axis + 1:])
        else:
            shape = (leaf.shape[:s.batch_axis] + (n_states,)
                     + leaf.shape[s.batch_axis + 1:])
        out.append(jnp.zeros(shape, leaf.dtype))
    return jax.tree.unflatten(spec.treedef, out)


def gather_cache_view(pool, spec: CacheViewSpec, tables, state_slots):
    """Materialize the batched cache for ``decode_step``.

    tables: (B, P) int32 physical block ids (ring order, null-padded);
    state_slots: (B,) int32 state slot ids.  Returns a cache pytree shaped
    exactly like ``init_cache(cfg, B, max_len)``.
    """
    B, P = tables.shape
    flat = tables.reshape(-1)
    out = []
    for leaf, s in zip(jax.tree.leaves(pool), spec.leaves):
        ax = s.batch_axis
        if s.token_axis is None:
            out.append(jnp.take(leaf, state_slots, axis=ax))
            continue
        bt = leaf.shape[ax + 1]
        g = jnp.take(leaf, flat, axis=ax)            # (.., B*P, bt, rest)
        shape = leaf.shape[:ax] + (B, P * bt) + leaf.shape[ax + 2:]
        out.append(g.reshape(shape))
    return jax.tree.unflatten(spec.treedef, out)


def scatter_cache_view(pool, spec: CacheViewSpec, tables, state_slots, view):
    """Write a (possibly updated) batched cache view back into the pool.

    Inverse of ``gather_cache_view``: each stream's W-token ring is split
    back into P pages and written to its table's physical blocks.  Streams
    MAY share real blocks (prefix-shared pages, refcount > 1) only under
    the pool's copy-on-write invariant — a shared page is never written by
    the model step (the engine forks it first), so the duplicate scatter
    indices all carry the page's unchanged gathered bytes and last-write-
    wins is exact.  Null-padded table entries all point at the engine's
    null block, whose contents are never read.
    """
    B, P = tables.shape
    flat = tables.reshape(-1)
    out = []
    for leaf, vleaf, s in zip(jax.tree.leaves(pool), jax.tree.leaves(view),
                              spec.leaves):
        ax = s.batch_axis
        idx = (slice(None),) * ax
        if s.token_axis is None:
            out.append(leaf.at[idx + (state_slots,)].set(vleaf))
            continue
        bt = leaf.shape[ax + 1]
        shape = vleaf.shape[:ax] + (B * P, bt) + vleaf.shape[ax + 2:]
        out.append(leaf.at[idx + (flat,)].set(vleaf.reshape(shape)))
    return jax.tree.unflatten(spec.treedef, out)


def copy_pool_entries(pool, spec: CacheViewSpec, src_blocks, dst_blocks,
                      src_state=None, dst_state=None):
    """Copy physical pages (and optionally a state slot) inside the pool —
    the device-side half of a cross-domain block migration.

    The block lists are padded to a pow-2 bucket with null-block
    self-copies (block 0 -> block 0, bit-identical values, so duplicate
    scatter indices are exact regardless of write order): migrations and
    prefix forks copy arbitrary page counts, and an unbucketed gather/
    scatter dispatches a fresh XLA module per distinct count."""
    src_blocks, dst_blocks = list(src_blocks), list(dst_blocks)
    if src_blocks:
        bucket = 1 << (len(src_blocks) - 1).bit_length()
        pad = bucket - len(src_blocks)
        src_blocks = src_blocks + [0] * pad
        dst_blocks = dst_blocks + [0] * pad
    src_b = jnp.asarray(src_blocks, jnp.int32)
    dst_b = jnp.asarray(dst_blocks, jnp.int32)
    out = []
    for leaf, s in zip(jax.tree.leaves(pool), spec.leaves):
        ax = s.batch_axis
        idx = (slice(None),) * ax
        if s.token_axis is not None:
            if src_b.size:
                vals = jnp.take(leaf, src_b, axis=ax)
                leaf = leaf.at[idx + (dst_b,)].set(vals)
        elif src_state is not None:
            vals = jnp.take(leaf, jnp.asarray([src_state]), axis=ax)
            leaf = leaf.at[idx + (jnp.asarray([dst_state]),)].set(vals)
        out.append(leaf)
    return jax.tree.unflatten(spec.treedef, out)


def fork_state_slot(pool, spec: CacheViewSpec, src_state, dst_state):
    """Copy ONE stream's carried-state leaves (rgLRU / SSD states) from
    ``src_state`` into ``dst_state``, token pages untouched.

    This is the state half of a prefix-cache hit: ring pages can be
    attached by reference, but the per-stream state slot is POSITION-
    dependent — the new stream needs the donor's state exactly at the
    match boundary, forked into its own slot so the two streams diverge
    freely afterwards.  Registration uses the same copy in the other
    direction to snapshot a checkpoint at a page boundary."""
    return copy_pool_entries(pool, spec, [], [],
                             src_state=src_state, dst_state=dst_state)


def zero_state_slot(pool, spec: CacheViewSpec, state_slot: int):
    """Clear ONE state slot's carried-state leaves to the init (zero)
    state.  A freed slot still holds its dead stream's FINAL rgLRU/SSD
    state; the recurrence reads the slot at the new stream's first token,
    so reusing a slot without clearing it corrupts the new stream's
    tokens.  (Ring pages need no such scrub: attention masks them past
    ``pos``.)"""
    idx = jnp.asarray([state_slot])
    out = []
    for leaf, s in zip(jax.tree.leaves(pool), spec.leaves):
        if s.token_axis is None:
            ax = s.batch_axis
            leaf = leaf.at[(slice(None),) * ax + (idx,)].set(0)
        out.append(leaf)
    return jax.tree.unflatten(spec.treedef, out)


def extract_pool_entries(pool, spec: CacheViewSpec, blocks,
                         state_slot: Optional[int] = None):
    """Gather physical pages (and optionally a state slot) out of the pool
    into HOST (numpy) arrays — the device->host half of a swap-tier spill.

    Returns a flat leaf list in ``jax.tree`` order; entries are None where
    a leaf contributes nothing (token leaves when ``blocks`` is empty,
    state leaves when ``state_slot`` is None).  On a real fleet this is the
    D2H DMA of exactly the stream's used pages; ``insert_pool_entries`` is
    its inverse."""
    blk = jnp.asarray(list(blocks), jnp.int32)
    out = []
    for leaf, s in zip(jax.tree.leaves(pool), spec.leaves):
        ax = s.batch_axis
        if s.token_axis is not None:
            out.append(np.asarray(jnp.take(leaf, blk, axis=ax))
                       if blk.size else None)
        else:
            out.append(np.asarray(jnp.take(leaf, jnp.asarray([state_slot]),
                                           axis=ax))
                       if state_slot is not None else None)
    return out


def insert_pool_entries(pool, spec: CacheViewSpec, blocks, host_leaves,
                        state_slot: Optional[int] = None):
    """Scatter host arrays from ``extract_pool_entries`` back into the pool
    at (freshly reserved) ``blocks`` / ``state_slot`` — the host->device
    half of a swap-tier restore.  Page COUNT must match the extract; the
    physical ids may differ (the restore's reservation is new)."""
    blk = jnp.asarray(list(blocks), jnp.int32)
    out = []
    for leaf, host, s in zip(jax.tree.leaves(pool), host_leaves, spec.leaves):
        ax = s.batch_axis
        idx = (slice(None),) * ax
        if s.token_axis is not None:
            if blk.size and host is not None:
                assert host.shape[ax] == blk.size, \
                    f"spill holds {host.shape[ax]} pages, restoring {blk.size}"
                leaf = leaf.at[idx + (blk,)].set(jnp.asarray(host))
        elif state_slot is not None and host is not None:
            leaf = leaf.at[idx + (jnp.asarray([state_slot]),)].set(
                jnp.asarray(host))
        out.append(leaf)
    return jax.tree.unflatten(spec.treedef, out)


def extract_pool_entries_async(pool, spec: CacheViewSpec, blocks,
                               state_slot: Optional[int] = None):
    """Gather physical pages (and optionally a state slot) out of the pool
    as DEVICE arrays — the issue half of an asynchronous swap-tier spill.

    Same leaf-list contract as ``extract_pool_entries`` but without the
    blocking ``np.asarray``: the gather dispatches and returns immediately
    (JAX async dispatch), so decode ticks keep running while the copy
    drains.  The gather snapshots the pool's CURRENT leaf values — the
    functional storage update means later pool writes land in NEW arrays,
    so the payload stays exactly the issue-time bytes.  Poll completion
    with ``.is_ready()`` per leaf; ``np.asarray`` after that is the cheap
    landed-copy read (on TPU, stage through a pinned-host buffer)."""
    blk = jnp.asarray(list(blocks), jnp.int32)
    out = []
    for leaf, s in zip(jax.tree.leaves(pool), spec.leaves):
        ax = s.batch_axis
        if s.token_axis is not None:
            out.append(jnp.take(leaf, blk, axis=ax) if blk.size else None)
        else:
            out.append(jnp.take(leaf, jnp.asarray([state_slot]), axis=ax)
                       if state_slot is not None else None)
    return out


def gather_pool_rows(pool, spec: CacheViewSpec, blocks, state_slots=()):
    """ONE batched device gather of many streams' pages + state slots —
    the spec-decode checkpoint path (every drafted row snapshots its
    write-touched pages per tick; per-row gathers cost a host round-trip
    each).  ``blocks`` is the concatenation of all rows' page ids,
    ``state_slots`` one slot per hybrid row.  Returns device arrays (no
    host copy — rollback scatters them straight back; most checkpoints
    are dropped untouched when the draft fully accepts).  Blocks are
    padded to a pow-2 bucket with null-block gathers so the compiled-
    shape count stays bounded; callers slice rows by offset and never
    read the pad."""
    blocks = list(blocks)
    n_real = len(blocks)
    if blocks:
        bucket = 1 << (n_real - 1).bit_length()
        blocks = blocks + [0] * (bucket - n_real)
    blk = jnp.asarray(blocks, jnp.int32)
    slots = jnp.asarray(list(state_slots), jnp.int32)
    out = []
    for leaf, s in zip(jax.tree.leaves(pool), spec.leaves):
        ax = s.batch_axis
        if s.token_axis is not None:
            out.append(jnp.take(leaf, blk, axis=ax) if blk.size else None)
        else:
            out.append(jnp.take(leaf, slots, axis=ax) if slots.size
                       else None)
    return out


def scatter_pool_rows(pool, spec: CacheViewSpec, blocks, leaves,
                      state_slots=()):
    """Inverse of ``gather_pool_rows`` for the rows that ROLL BACK: one
    batched scatter of the rejected rows' pages (``leaves`` token entries
    sized exactly ``len(blocks)`` at the block axis — the caller slices
    real rows out of the bucketed gather) and their state slots."""
    blk = jnp.asarray(list(blocks), jnp.int32)
    slots = jnp.asarray(list(state_slots), jnp.int32)
    out = []
    for leaf, vals, s in zip(jax.tree.leaves(pool), leaves, spec.leaves):
        ax = s.batch_axis
        idx = (slice(None),) * ax
        if s.token_axis is not None:
            if blk.size and vals is not None:
                leaf = leaf.at[idx + (blk,)].set(jnp.asarray(vals))
        elif slots.size and vals is not None:
            leaf = leaf.at[idx + (slots,)].set(jnp.asarray(vals))
        out.append(leaf)
    return jax.tree.unflatten(spec.treedef, out)


def place_block_pool(pool, spec: CacheViewSpec, devices):
    """Commit pool storage onto physical devices — the placement half of
    the two-tier hierarchy.

    Single device (CPU CI, one chip): a committed ``device_put`` —
    placement is explicit rather than inherited from whatever the first
    jit happened to choose.  Multiple devices: shard every leaf's
    block/slot axis across them when it divides evenly (the pool pads
    both axes so it does; domain block-id ranges are contiguous, so each
    group's pages land on few devices), replicating leaves that don't."""
    if len(devices) <= 1:
        return jax.device_put(pool, devices[0])
    mesh = Mesh(np.array(devices), ("groups",))
    out = []
    for leaf, s in zip(jax.tree.leaves(pool), spec.leaves):
        ax = s.batch_axis
        if leaf.shape[ax] % len(devices) == 0:
            ps = PartitionSpec(*((None,) * ax + ("groups",)))
        else:
            ps = PartitionSpec()
        out.append(jax.device_put(leaf, NamedSharding(mesh, ps)))
    return jax.tree.unflatten(spec.treedef, out)


def place_params(params, devices):
    """Commit params onto the serving devices: a committed ``device_put``
    on one device, replicated over several (each device's share of the
    pool is then read by a step running against its own copy)."""
    if len(devices) <= 1:
        return jax.device_put(params, devices[0])
    mesh = Mesh(np.array(devices), ("groups",))
    return jax.device_put(params, NamedSharding(mesh, PartitionSpec()))


def replicate_over(fn, devices):
    """``fn`` inside a program that spans ``devices``, run whole on every
    device (``shard_map`` with every operand and result replicated): XLA
    cannot partition a Mosaic (Pallas TPU) kernel by itself, so a step
    that calls one over several devices must be placed by hand.  One
    device: ``fn`` unchanged."""
    if len(devices) <= 1:
        return fn
    mesh = Mesh(np.array(devices), ("groups",))
    return jax.shard_map(fn, mesh=mesh, in_specs=PartitionSpec(),
                         out_specs=PartitionSpec(), check_vma=False)


def select_streams(spec: CacheViewSpec, mask, new_cache, old_cache):
    """Per-stream cache select: leaves of ``new_cache`` where ``mask`` (B,)
    is True, ``old_cache`` elsewhere — broadcast along each leaf's stream
    axis from ``spec``.  This is what makes a masked multi-token step exact:
    an inactive stream's cache (and ring write pointer) passes through
    bit-unchanged, so a decode stream inside a mixed prefill/decode chunk
    computes exactly what a plain single-token step would."""
    out = []
    for ln, lo, s in zip(jax.tree.leaves(new_cache),
                         jax.tree.leaves(old_cache), spec.leaves):
        shape = [1] * ln.ndim
        shape[s.batch_axis] = mask.shape[0]
        out.append(jnp.where(mask.reshape(shape), ln, lo))
    return jax.tree.unflatten(spec.treedef, out)


def next_token_ids(logits, n_tokens):
    """Greedy next token per stream, HARDENED against idle slots: a slot
    that consumed no tokens this tick (``n_tokens == 0``) yields the -1
    sentinel — never an argmax-able token id.  Both chunk steps also
    poison idle rows to NEG_INF, but the engine must not trust a bare
    ``argmax`` over them (argmax of a constant row is token 0)."""
    return jnp.where(jnp.asarray(n_tokens) > 0,
                     jnp.argmax(logits, axis=-1).astype(jnp.int32),
                     jnp.int32(-1))


def chunk_decode_step(params, cfg: ModelConfig, spec: CacheViewSpec, cache,
                      tokens, pos, n_tokens, extras=None, all_logits=False):
    """One continuous-batching tick: every stream consumes UP TO C tokens.

    tokens: (B, C) int32 — stream i's next ``n_tokens[i]`` tokens (prefill
    chunks put a prompt slice here, decode streams put [last_token, ...]);
    pos: (B,) absolute position of tokens[:, 0]; n_tokens: (B,) in [0, C]
    (0 = idle slot: nothing is computed into its cache and its logits row
    stays poisoned at NEG_INF — see ``next_token_ids``).

    Scans ``decode_step`` over the chunk axis with per-stream masking, so a
    stream's math is bit-identical to feeding its tokens one per tick —
    mixing prefill chunks with single-token decode streams in ONE batched
    model step is then purely a scheduling decision.  This is the
    REFERENCE path: C sequential model steps per tick.  The fused
    ``prefill_chunk_step`` computes the same chunk in one forward.
    Returns (logits (B, V) after each stream's LAST active token, new
    cache).  With ``all_logits=True`` (speculative verification) returns
    the PER-POSITION logits (B, C, V) instead — row [i, t] is the
    distribution after stream i consumed tokens[i, t], positions at or
    past ``n_tokens[i]`` poisoned to NEG_INF.
    """
    B, C = tokens.shape
    logits0 = jnp.full((B, cfg.vocab), L.NEG_INF, jnp.float32)

    def body(carry, t):
        cache, pos_c, logits = carry
        active = t < n_tokens
        tok = lax.dynamic_slice_in_dim(tokens, t, 1, axis=1)   # (B, 1)
        lg, new_cache = decode_step(params, cfg, cache, tok, pos_c, extras)
        cache = select_streams(spec, active, new_cache, cache)
        logits = jnp.where(active[:, None], lg, logits)
        pos_c = pos_c + active.astype(pos_c.dtype)
        return (cache, pos_c, logits), (lg if all_logits else None)

    (cache, _, logits), ys = lax.scan(
        body, (cache, pos, logits0), jnp.arange(C))
    if all_logits:
        la = jnp.transpose(ys, (1, 0, 2))                      # (B, C, V)
        active = jnp.arange(C)[None, :] < jnp.asarray(n_tokens)[:, None]
        return jnp.where(active[:, :, None], la, L.NEG_INF), cache
    return logits, cache


# ---------------------------------------------------------------------------
# Single-token decode layers
# ---------------------------------------------------------------------------

def _decode_attn_layer(x, lp, lc, cfg: ModelConfig, rope1, pos, *, window):
    q, k, v = _attn_proj(L.rms_norm(x, lp["ln1"], cfg.norm_eps), lp["attn"],
                         rope1, cfg=cfg)
    kc, vc = L.cache_update(lc["k"], lc["v"], k, v, pos)
    return _decode_attn_rest(x, lp, cfg, q, kc, vc, pos, window=window), \
        {"k": kc, "v": vc}


def _decode_attn_rest(x, lp, cfg: ModelConfig, q, kc, vc, pos, *, window):
    """The new token's attention over its post-write ring (B, W, Hkv, dh),
    the output projection and the FFN."""
    kv_pos = L.cache_positions(pos, kc.shape[1])
    o = L.decode_attention(q, kc, vc, kv_pos, pos, window=window)
    h = x + _attn_out(o, lp["attn"], x.dtype)
    f, _ = _ffn(L.rms_norm(h, lp["ln2"], cfg.norm_eps), lp, cfg,
                dropless=True)
    return h + f


def _decode_layer(x, lp, lc, cfg: ModelConfig, lt: str, rope1, pos, *,
                  hybrid=False):
    if lt == "attn":
        w = cfg.local_window if hybrid else cfg.window
        return _decode_attn_layer(x, lp, lc, cfg, rope1, pos, window=w)
    if lt == "rec":
        r, st = rglru_mod.rglru_decode_step(
            L.rms_norm(x, lp["ln1"], cfg.norm_eps), lp["rec"], cfg, lc)
        h = x + r
        f, _ = _ffn(L.rms_norm(h, lp["ln2"], cfg.norm_eps), lp, cfg,
                    dropless=True)
        return h + f, st
    if lt == "ssd":
        s, st = ssd_mod.ssd_decode_step(
            L.rms_norm(x, lp["ln"], cfg.norm_eps), lp["ssd"], cfg, lc)
        return x + s, st
    raise ValueError(lt)


def decode_step(params, cfg: ModelConfig, cache, tokens, pos, extras=None,
                gather_specs=None):
    """One token for every stream.  tokens: (B,1); pos: (B,) absolute.

    Returns (logits (B, V) f32, new cache).
    """
    from repro.models.transformer import _wsc_tree
    x = embed_tokens(params, cfg, tokens)
    rope1 = _decode_rope(cfg, tokens, pos, extras)

    if cfg.family == "encdec":
        def body(x, inp):
            lp, lc = inp
            lp = _wsc_tree(lp, gather_specs and gather_specs.get("dec_layers"))
            # 1. self-attention (ln1) with ring cache
            xin = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
            q, k, v = _attn_proj(xin, lp["attn"], rope1, cfg=cfg)
            kc, vc = L.cache_update(lc["self_c"]["k"], lc["self_c"]["v"],
                                    k, v, pos)
            W = kc.shape[1]
            kv_pos = L.cache_positions(pos, W)
            o = L.decode_attention(q, kc, vc, kv_pos, pos)
            h = x + _attn_out(o, lp["attn"], x.dtype)
            # 2. cross-attention (ln2) over static encoder KV
            xin = L.rms_norm(h, lp["ln2"], cfg.norm_eps)
            cq = jnp.einsum("bsd,dhk->bshk", xin, lp["cross"]["wq"],
                            preferred_element_type=jnp.float32).astype(x.dtype)
            S_src = lc["ck"].shape[1]
            src_pos = jnp.broadcast_to(jnp.arange(S_src)[None],
                                       (x.shape[0], S_src))
            co = L.decode_attention(cq, lc["ck"], lc["cv"], src_pos,
                                    jnp.full((x.shape[0],), 2**30, jnp.int32))
            h = h + _attn_out(co, lp["cross"], x.dtype)
            # 3. FFN (ln3)
            f, _ = _ffn(L.rms_norm(h, lp["ln3"], cfg.norm_eps), lp, cfg,
                        dropless=True)
            return h + f, {"k": kc, "v": vc}

        xs = (params["dec_layers"],
              {"self_c": cache["self"], "ck": cache["cross_k"],
               "cv": cache["cross_v"]})
        x, new_self = lax.scan(body, x, xs)
        new_cache = dict(cache, self=new_self)
    elif cfg.block_pattern:
        pattern, n_groups, tail = hybrid_structure(cfg)

        def gbody(x, inp):
            gp, gc = inp
            gp = _wsc_tree(gp, gather_specs and gather_specs.get("groups"))
            new_gc = {}
            for i, t in enumerate(pattern):
                nm = f"b{i}_{t}"
                x, st = _decode_layer(x, gp[nm], gc[nm], cfg, t, rope1, pos,
                                      hybrid=True)
                new_gc[nm] = st
            return x, new_gc

        x, new_groups = lax.scan(gbody, x, (params["groups"], cache["groups"]))
        new_tail = {}
        for nm, lp in params["tail"].items():
            t = nm.split("_", 1)[1]
            x, st = _decode_layer(x, lp, cache["tail"][nm], cfg, t, rope1, pos,
                                  hybrid=True)
            new_tail[nm] = st
        new_cache = {"groups": new_groups, "tail": new_tail}
    else:
        lt = cfg.layer_types()[0]

        def body(x, inp):
            lp, lc = inp
            lp = _wsc_tree(lp, gather_specs and gather_specs.get("layers"))
            x, st = _decode_layer(x, lp, lc, cfg, lt, rope1, pos)
            return x, st

        x, new_layers = lax.scan(body, x, (params["layers"], cache["layers"]))
        new_cache = {"layers": new_layers}

    return _decode_head(params, cfg, x), new_cache


def _decode_rope(cfg: ModelConfig, tokens, pos, extras=None):
    """RoPE tables of one token per stream at ``pos`` (None without RoPE);
    M-RoPE reads ``extras["position_ids"]`` when given."""
    if cfg.rope_type == "mrope":
        pid = (extras or {}).get(
            "position_ids",
            jnp.broadcast_to(pos[None, :, None], (3,) + tokens.shape))
        return L.mrope_tables(pid, cfg.head_dim, cfg.rope_theta,
                              cfg.mrope_sections)
    if cfg.rope_type == "none":
        return None
    return L.rope_tables(pos[:, None], cfg.head_dim, cfg.rope_theta)


def _decode_head(params, cfg: ModelConfig, x):
    """(B, 1, D) last hidden state -> (B, V) f32 logits."""
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return head_logits(params, cfg, x[:, 0])


# ---------------------------------------------------------------------------
# Paged single-token decode (the serving engine's decode program)
# ---------------------------------------------------------------------------

def decode_writes_in_place(cfg: ModelConfig, spec: CacheViewSpec) -> bool:
    """Whether the paged decode step can read the pool's pages per layer
    and write back only the new token: the serving cache is the plain
    ``layers`` stack of attention rings (dense, MoE and VLM families).
    Hybrid and SSM models carry per-stream state leaves and enc-dec models
    cross-attention caches; they gather, step and scatter whole views."""
    return (cfg.family != "encdec" and not cfg.block_pattern
            and all(s.token_axis is not None for s in spec.leaves))


def paged_decode_step(params, cfg: ModelConfig, pool, tables, tokens, pos):
    """``decode_step`` read from and written to a block pool in place.

    pool: the ``init_block_pool`` storage of a ``decode_writes_in_place``
    model, leaves (L, n_blocks, bt, Hkv, dh); tables: (B, P) block ids in
    ring order, so the ring width is W = P * bt; tokens (B, 1); pos (B,).

    Each layer gathers its own pages of the batch's tables into a
    (B, W, Hkv, dh) ring and selects the new token in at slot pos % W: the
    ring ``L.cache_update`` writes, so attention reads exactly the values
    ``decode_step`` over a gathered view reads and the logits are the
    same.  The layer scan carries only the hidden state and returns the
    new token's K/V, (L, B, Hkv, dh); one scatter after it writes them to
    ``tables[b, (pos % W) // bt]`` at offset ``pos % bt``.  That page is
    the stream's own: the engine forks a shared page before a step writes
    into it.  Rows on the null table write into block 0, which is never
    read.  Returns (logits (B, V) f32, new pool)."""
    kp, vp = pool["layers"]["k"], pool["layers"]["v"]
    B, P = tables.shape
    bt = kp.shape[2]
    W = P * bt
    slot = pos % W
    new_slot = (jnp.arange(W)[None, :] == slot[:, None])[:, :, None, None]

    def ring(leaf, layer, new):
        pages = leaf[layer, tables]                     # (B, P, bt, Hkv, dh)
        return jnp.where(new_slot, new, pages.reshape((B, W) + leaf.shape[3:]))

    rope1 = _decode_rope(cfg, tokens, pos)

    def body(x, inp):
        lp, layer = inp
        q, k, v = _attn_proj(L.rms_norm(x, lp["ln1"], cfg.norm_eps),
                             lp["attn"], rope1, cfg=cfg)
        x = _decode_attn_rest(x, lp, cfg, q, ring(kp, layer, k),
                              ring(vp, layer, v), pos, window=cfg.window)
        return x, (k[:, 0], v[:, 0])

    x, (k_new, v_new) = lax.scan(
        body, embed_tokens(params, cfg, tokens),
        (params["layers"], jnp.arange(kp.shape[0])))
    blk = jnp.take_along_axis(tables, (slot // bt)[:, None], axis=1)[:, 0]
    off = slot % bt
    pool = {"layers": {"k": kp.at[:, blk, off].set(k_new),
                       "v": vp.at[:, blk, off].set(v_new)}}
    return _decode_head(params, cfg, x), pool


def make_paged_decode(cfg: ModelConfig, spec: CacheViewSpec):
    """The engine's decode program, (params, storage, tables, state_slots,
    tokens, pos) -> (logits, storage): ``paged_decode_step`` where
    ``decode_writes_in_place`` holds, else gather the batch's views,
    ``decode_step`` and scatter them back."""
    if decode_writes_in_place(cfg, spec):
        def paged_decode(params, storage, tables, state_slots, tokens, pos):
            return paged_decode_step(params, cfg, storage, tables, tokens,
                                     pos)
    else:
        def paged_decode(params, storage, tables, state_slots, tokens, pos):
            view = gather_cache_view(storage, spec, tables, state_slots)
            logits, view = decode_step(params, cfg, view, tokens, pos)
            return logits, scatter_cache_view(storage, spec, tables,
                                              state_slots, view)
    return paged_decode


# ---------------------------------------------------------------------------
# Fused multi-token chunk forward (the PARALLEL prefill path)
# ---------------------------------------------------------------------------
#
# ``chunk_decode_step`` above is exact but SEQUENTIAL: a C-token prompt
# chunk costs C batched model steps inside one tick.  The functions below
# process the whole chunk in ONE forward — queries (B, C) attend jointly
# against the pre-chunk ring cache plus the chunk's own keys under an
# intra-chunk causal mask, and rgLRU/SSD layers run their existing chunk
# scans over the C axis inside one layer pass.  Per-stream ``n_tokens``
# masking keeps mixed ticks exact: a decode stream is just a chunk of 1, an
# idle slot a chunk of 0 (no cache leaf moves, logits poisoned to NEG_INF).

def _chunk_attn_layer(x, lp, lc, cfg: ModelConfig, rope1, pos, n_tokens, *,
                      window, chunk_kernel="dense"):
    xin = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
    q, k, v = _attn_proj(xin, lp["attn"], rope1, cfg=cfg)
    o = L.chunk_attention(q, k, v, lc["k"], lc["v"], pos, n_tokens,
                          window=window, kernel=chunk_kernel)
    kc, vc = L.cache_update_chunk(lc["k"], lc["v"], k, v, pos, n_tokens)
    h = x + _attn_out(o, lp["attn"], x.dtype)
    f, _ = _ffn(L.rms_norm(h, lp["ln2"], cfg.norm_eps), lp, cfg,
                dropless=True)
    return h + f, {"k": kc, "v": vc}


def _chunk_layer(x, lp, lc, cfg: ModelConfig, lt: str, rope1, pos, n_tokens,
                 *, hybrid=False, chunk_kernel="dense"):
    if lt == "attn":
        w = cfg.local_window if hybrid else cfg.window
        return _chunk_attn_layer(x, lp, lc, cfg, rope1, pos, n_tokens,
                                 window=w, chunk_kernel=chunk_kernel)
    if lt == "rec":
        r, st = rglru_mod.rglru_chunk_step(
            L.rms_norm(x, lp["ln1"], cfg.norm_eps), lp["rec"], cfg, lc,
            n_tokens)
        h = x + r
        f, _ = _ffn(L.rms_norm(h, lp["ln2"], cfg.norm_eps), lp, cfg,
                    dropless=True)
        return h + f, st
    if lt == "ssd":
        s, st = ssd_mod.ssd_chunk_step(
            L.rms_norm(x, lp["ln"], cfg.norm_eps), lp["ssd"], cfg, lc,
            n_tokens)
        return x + s, st
    raise ValueError(lt)


def prefill_chunk_step(params, cfg: ModelConfig, spec: CacheViewSpec, cache,
                       tokens, pos, n_tokens, extras=None, gather_specs=None,
                       chunk_kernel="dense", all_logits=False):
    """One continuous-batching tick as ONE fused multi-token forward.

    Same contract as ``chunk_decode_step`` (tokens (B, C), pos (B,),
    n_tokens (B,) in [0, C]; returns (last-active-token logits, new
    cache)) but every stream's chunk is processed in a single model pass:
    attention scores the whole chunk against [prior ring, intra-chunk
    causal] jointly (``layers.chunk_attention``), recurrent and SSD layers
    run their chunk-parallel scans from the carried state.  ~C× fewer
    sequential model steps per prefill tick, at the cost of a (B, C, W+C)
    score transient (``costmodel.prefill_chunk_score_bytes``) and numerics
    that match the scan path to tolerance rather than bit-exactly — the
    scan stays available as the reference (``prefill_mode="scan"``).
    ``chunk_kernel="blocked"`` swaps the dense score block for the Pallas
    online-softmax ring kernel, shrinking the attention transient to one
    (block_q, block_kv) tile; "dense" keeps the einsum reference.

    Masking invariants: active tokens are a per-stream PREFIX of the
    chunk; an inactive token updates no cache leaf (ring writes are
    masked, recurrent/SSD steps degrade to identity), and an idle slot
    (n_tokens == 0) passes its cache through bit-unchanged and gets a
    NEG_INF-poisoned logits row — ``next_token_ids`` maps it to -1, so an
    idle slot can never emit a token.  Chunks wider than the ring are
    supported: attention masks each query to its surviving span and the
    ring write keeps the last W active tokens (last-write-wins).

    With ``all_logits=True`` (speculative verification) returns the
    PER-POSITION logits (B, C, V): row [i, t] is the distribution after
    stream i's token t — the intra-chunk causal mask makes it independent
    of every later token in the chunk, which is what lets greedy
    acceptance keep a verified prefix and discard the rest.  Positions at
    or past ``n_tokens[i]`` are poisoned to NEG_INF.
    """
    from repro.models.transformer import _wsc_tree
    extras = extras or {}
    B, C = tokens.shape
    x = embed_tokens(params, cfg, tokens)
    q_pos = pos[:, None] + jnp.arange(C)[None, :]
    if cfg.rope_type == "mrope":
        pid = extras.get("position_ids",
                         jnp.broadcast_to(q_pos[None], (3, B, C)))
        rope1 = L.mrope_tables(pid, cfg.head_dim, cfg.rope_theta,
                               cfg.mrope_sections)
    elif cfg.rope_type == "none":
        rope1 = None
    else:
        rope1 = L.rope_tables(q_pos, cfg.head_dim, cfg.rope_theta)

    if cfg.family == "encdec":
        def body(x, inp):
            lp, lc = inp
            lp = _wsc_tree(lp, gather_specs and gather_specs.get("dec_layers"))
            # 1. self-attention (ln1): fused chunk over the ring cache
            xin = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
            q, k, v = _attn_proj(xin, lp["attn"], rope1, cfg=cfg)
            o = L.chunk_attention(q, k, v, lc["self_c"]["k"],
                                  lc["self_c"]["v"], pos, n_tokens,
                                  kernel=chunk_kernel)
            kc, vc = L.cache_update_chunk(lc["self_c"]["k"],
                                          lc["self_c"]["v"], k, v, pos,
                                          n_tokens)
            h = x + _attn_out(o, lp["attn"], x.dtype)
            # 2. cross-attention (ln2): all C queries over static encoder KV
            xin = L.rms_norm(h, lp["ln2"], cfg.norm_eps)
            cq = jnp.einsum("bsd,dhk->bshk", xin, lp["cross"]["wq"],
                            preferred_element_type=jnp.float32).astype(x.dtype)
            co = L.blocked_attention(cq, lc["ck"], lc["cv"], causal=False,
                                     block_q=cfg.attn_block_q,
                                     block_kv=cfg.attn_block_kv)
            h = h + _attn_out(co, lp["cross"], x.dtype)
            # 3. FFN (ln3)
            f, _ = _ffn(L.rms_norm(h, lp["ln3"], cfg.norm_eps), lp, cfg,
                        dropless=True)
            return h + f, {"k": kc, "v": vc}

        xs = (params["dec_layers"],
              {"self_c": cache["self"], "ck": cache["cross_k"],
               "cv": cache["cross_v"]})
        x, new_self = lax.scan(body, x, xs)
        new_cache = dict(cache, self=new_self)
    elif cfg.block_pattern:
        pattern, n_groups, tail = hybrid_structure(cfg)

        def gbody(x, inp):
            gp, gc = inp
            gp = _wsc_tree(gp, gather_specs and gather_specs.get("groups"))
            new_gc = {}
            for i, t in enumerate(pattern):
                nm = f"b{i}_{t}"
                x, st = _chunk_layer(x, gp[nm], gc[nm], cfg, t, rope1, pos,
                                     n_tokens, hybrid=True,
                                     chunk_kernel=chunk_kernel)
                new_gc[nm] = st
            return x, new_gc

        x, new_groups = lax.scan(gbody, x, (params["groups"], cache["groups"]))
        new_tail = {}
        for nm, lp in params["tail"].items():
            t = nm.split("_", 1)[1]
            x, st = _chunk_layer(x, lp, cache["tail"][nm], cfg, t, rope1, pos,
                                 n_tokens, hybrid=True,
                                 chunk_kernel=chunk_kernel)
            new_tail[nm] = st
        new_cache = {"groups": new_groups, "tail": new_tail}
    else:
        lt = cfg.layer_types()[0]

        def body(x, inp):
            lp, lc = inp
            lp = _wsc_tree(lp, gather_specs and gather_specs.get("layers"))
            x, st = _chunk_layer(x, lp, lc, cfg, lt, rope1, pos, n_tokens,
                                 chunk_kernel=chunk_kernel)
            return x, st

        x, new_layers = lax.scan(body, x, (params["layers"], cache["layers"]))
        new_cache = {"layers": new_layers}

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if all_logits:
        la = head_logits(params, cfg, x.reshape(B * C, x.shape[-1]))
        la = la.reshape(B, C, -1)
        active = jnp.arange(C)[None, :] < n_tokens[:, None]
        return jnp.where(active[:, :, None], la, L.NEG_INF), new_cache
    last = jnp.clip(n_tokens - 1, 0, C - 1)
    xl = jnp.take_along_axis(
        x, jnp.broadcast_to(last[:, None, None], (B, 1, x.shape[-1])),
        axis=1)[:, 0]
    logits = head_logits(params, cfg, xl)
    logits = jnp.where((n_tokens > 0)[:, None], logits, L.NEG_INF)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Prefill: full-sequence forward that also materializes the decode cache
# ---------------------------------------------------------------------------

def _ring_arrange(kv, W):
    """kv: (B, S, H, dh) full-seq keys/values -> ring cache (B, W, H, dh)."""
    S = kv.shape[1]
    if S <= W:
        pad = [(0, 0), (0, W - S), (0, 0), (0, 0)]
        return jnp.pad(kv, pad)
    last = kv[:, -W:]
    return jnp.roll(last, shift=(S - W) % W, axis=1)


def _state_to_cache(cfg, st, lt, max_len, hybrid=False):
    if lt in ("attn", "enc"):
        W = _attn_cache_width(cfg, max_len, hybrid=hybrid)
        return {"k": _ring_arrange(st["k"], W), "v": _ring_arrange(st["v"], W)}
    return st  # rec/ssd states already in decode form


def prefill(params, cfg: ModelConfig, tokens, extras=None, *, max_len: int,
            gather_specs=None):
    """Process the prompt; return (last-token logits (B,V), cache).

    Ring-arranging happens INSIDE the layer scan (state_fn), so a
    sliding-window cache never stacks (L, B, S_full, ...) — only
    (L, B, W, ...)."""
    extras = extras or {}
    if cfg.family == "encdec":
        return encdec_prefill(params, cfg, extras["frame_embeds"], tokens,
                              max_len=max_len)
    hybrid = bool(cfg.block_pattern)

    def sfn(s, t):
        return _state_to_cache(cfg, s, t, max_len, hybrid=hybrid)

    x, states, _ = forward(params, cfg, tokens, extras, return_states=True,
                           state_fn=sfn, gather_specs=gather_specs)
    if cfg.block_pattern:
        cache = {"groups": states["groups"], "tail": states["tail"]}
    else:
        cache = {"layers": states["layers"]}
    logits = head_logits(params, cfg, x[:, -1])
    return logits, cache


def encdec_prefill(params, cfg: ModelConfig, frame_embeds, tokens, *,
                   max_len: int):
    """Encode source; prefill decoder on target prefix; build caches."""
    from repro.models.transformer import decoder_forward, encode

    enc_out = encode(params, cfg, frame_embeds)
    x, states = decoder_forward(params, cfg, tokens, enc_out,
                                return_states=True)
    self_c = jax.vmap(lambda s: {
        "k": _ring_arrange(s["k"], max_len),
        "v": _ring_arrange(s["v"], max_len)})(
            {"k": states["k"], "v": states["v"]})
    logits = head_logits(params, cfg, x[:, -1])
    cache = {"self": self_c, "cross_k": states["ck"], "cross_v": states["cv"]}
    return logits, cache
