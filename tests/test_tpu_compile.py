"""Ahead-of-time compiles of the serving path's Pallas kernels for a
described TPU v5e, at the published widths of the models the engine
serves.  Nothing runs: the TPU compiler, which is installed with JAX,
compiles for a chip that is described and not attached, and refuses
what the chip would refuse (tiling, VMEM, unsupported primitives) —
refusals that interpret-mode tests cannot see.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, and every
test worker imports this file.
"""
import dataclasses
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.configs import REGISTRY, reduced_config
from repro.kernels.flash_attention.ops import (flash_attention,
                                               ring_blocks,
                                               ring_chunk_attention)
from repro.kernels.rglru_scan.kernel import lru_scan
from repro.models import decode as dec
from repro.models.decode import replicate_over
from repro.models.params import abstract_params

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench.metrics import ring_kernel_roofline  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# llama3.2-3b attention widths: 24 query heads over 8 KV heads, dh 128
B, C, HQ, HKV, DH = 8, 16, 24, 8, 128


@pytest.mark.parametrize("W", [1024, 4096])
def test_ring_chunk_kernel_compiles_for_v5e(one_chip, W):
    sd = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    bf = jnp.bfloat16
    compiled = _compile(
        lambda q, kn, vn, kc, vc, p, n: ring_chunk_attention(
            q, kn, vn, kc, vc, p, n, interpret=False),
        sd((B, C, HQ, DH), bf), sd((B, C, HKV, DH), bf),
        sd((B, C, HKV, DH), bf), sd((B, W, HKV, DH), bf),
        sd((B, W, HKV, DH), bf), sd((B,), jnp.int32), sd((B,), jnp.int32))
    # the kernel carries its name into the program (and so into a device
    # trace), and stays the tpu_custom_call that ring_kernel_roofline reads
    calls = [ln for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls and all("%ring_chunk_attention" in ln for ln in calls)


@pytest.mark.parametrize("B,Hq,Hkv,dh,W,window", [
    (4, 48, 8, 128, 4096, 0), (1, 48, 8, 128, 4096, 0),
    (2, 16, 1, 256, 2048, 2048)],
    ids=["nemotron_4_rows", "nemotron_1_row", "group16_dh256_window"])
def test_ring_chunk_kernel_at_engine_shapes(one_chip, monkeypatch, B, Hq,
                                            Hkv, dh, W, window):
    """Nemotron-4-15B's chunk step (48 query heads over 8 KV heads of 128,
    a 16-token chunk over a 4096-token ring) and a 16-head group of width
    256 over a 2048-token window compile with the blocks chosen from the
    shapes: one KV tile serves a whole query-head group, the keys take a
    few large tiles, and the custom call keeps the operands the roofline
    metric reads (result (B*Hq, Cp, dh), keys and values (B*Hkv, Lp, dh))."""
    C, bf = 16, jnp.bfloat16
    sd = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    compiled = _compile(
        lambda q, kn, vn, kc, vc, p, n: ring_chunk_attention(
            q, kn, vn, kc, vc, p, n, window=window, interpret=False),
        sd((B, C, Hq, dh), bf), sd((B, C, Hkv, dh), bf),
        sd((B, C, Hkv, dh), bf), sd((B, W, Hkv, dh), bf),
        sd((B, W, Hkv, dh), bf), sd((B,), jnp.int32), sd((B,), jnp.int32))
    calls = [ln for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1 and "%ring_chunk_attention" in calls[0]

    bq, bkv = ring_blocks(C, W, Hq // Hkv, dh, bf)
    Cp, Lp = -(-C // bq) * bq, -(-(W + C) // bkv) * bkv
    assert Lp // bkv <= 4
    if W == 4096:
        assert Lp <= 1.03 * (W + C)
    seen = {}
    monkeypatch.setattr(
        ring_kernel_roofline.costs, "ring_kernel_cost",
        lambda bhq, cp, bhkv, lp, dh, **_: seen.update(
            bhq=bhq, cp=cp, bhkv=bhkv, lp=lp, dh=dh) or {})
    ring_kernel_roofline._cost(calls[0], {"n_heads": Hq, "n_kv_heads": Hkv},
                               W)
    assert seen == dict(bhq=B * Hq, cp=Cp, bhkv=B * Hkv, lp=Lp, dh=dh)


def test_ring_chunk_kernel_compiles_over_four_chips(topo):
    """A serving step over several chips (pool pages sharded across them)
    runs the kernel through ``replicate_over``: XLA refuses to partition
    a Mosaic kernel by itself."""
    mesh = Mesh(np.array(topo.devices), ("groups",))
    rep = NamedSharding(mesh, PartitionSpec())
    by_stream = NamedSharding(mesh, PartitionSpec("groups"))
    sd = lambda s, d, sh: jax.ShapeDtypeStruct(s, d, sharding=sh)
    bf, W = jnp.bfloat16, 1024
    _compile(
        replicate_over(lambda *a: ring_chunk_attention(*a, interpret=False),
                       topo.devices),
        sd((B, C, HQ, DH), bf, rep), sd((B, C, HKV, DH), bf, rep),
        sd((B, C, HKV, DH), bf, rep), sd((B, W, HKV, DH), bf, by_stream),
        sd((B, W, HKV, DH), bf, by_stream), sd((B,), jnp.int32, rep),
        sd((B,), jnp.int32, rep))


def test_flash_attention_fwd_compiles_for_v5e(one_chip):
    sd = lambda s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
    S = 2048
    _compile(lambda q, k, v: flash_attention(q, k, v, True, 0, 512, 1024,
                                             False),
             sd((1, S, HQ, DH)), sd((1, S, HKV, DH)), sd((1, S, HKV, DH)))


def test_lru_scan_compiles_for_v5e(one_chip):
    # recurrentgemma-9b's RG-LRU width
    sd = lambda s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    shape = (2, 2048, 4096)
    _compile(lambda a, b: lru_scan(a, b, interpret=False), sd(shape),
             sd(shape))


def _hlo_instructions(hlo):
    """(computation, name, type, opcode, operands, line) of every
    instruction of an optimized HLO text, TPU layouts and tuples included."""
    out, comp = [], None
    for line in hlo.splitlines():
        s = line.strip()
        m = re.match(r"(?:ROOT )?%([\w.\-]+) = ", s)
        if not m:
            if s.endswith("{") and "->" in s:
                comp = re.match(r"(?:ENTRY )?%?([\w.\-]+)", s).group(1)
            continue
        rest, depth = s[m.end():], 0
        for i, ch in enumerate(rest):       # the type: a shape or a tuple
            depth += (ch == "(") - (ch == ")")
            if ch == " " and depth == 0:
                break
        typ = rest[:i]
        op = re.match(r"\s*([\w\-]+)\(([^)]*)\)", rest[i:])
        out.append((comp, m.group(1), typ, op.group(1),
                    re.findall(r"%([\w.\-]+)", op.group(2)), s))
    return out


def _shapes(typ):
    return [tuple(int(d) for d in dims.split(",") if d)
            for dims in re.findall(r"\w+\[([\d,]*)\]", typ)]


def _whole_ring_traffic(hlo, *, L, B, W, token_elems, pool_shape):
    """What of a compiled paged decode still moves the batch's whole rings:
    scatters whose updates hold B x W tokens or more, selects over as many
    outside the layer loop (inside it, the post-write ring that attention
    reads is one layer's (B, W) view), and arrays of L x B x W tokens in
    the layer loop's carry, the pool itself aside."""
    ins = _hlo_instructions(hlo)
    types = {name: typ for _, name, typ, _, _, _ in ins}
    elems = lambda typ: max((int(np.prod(s)) for s in _shapes(typ)),
                            default=0)
    trip = {}                       # a loop condition's bound
    for comp, _, _, op, _, line in ins:
        m = re.search(r"constant\((\d+)\)", line) if op == "constant" else 0
        if m:
            trip[comp] = max(trip.get(comp, 0), int(m.group(1)))
    layer = [(name, typ, re.search(r"body=%([\w.\-]+)", line).group(1))
             for _, name, typ, op, _, line in ins if op == "while"
             and trip.get(re.search(r"condition=%([\w.\-]+)",
                                    line).group(1)) == L]
    assert len(layer) == 1, "one layer loop"
    _, carry, body = layer[0]
    calls = {}
    for comp, _, _, _, _, line in ins:
        calls.setdefault(comp, set()).update(re.findall(
            r"(?:calls|to_apply|body|condition)=%([\w.\-]+)", line))
    inside, todo = set(), [body]
    while todo:
        c = todo.pop()
        if c not in inside:
            inside.add(c)
            todo += calls.get(c, ())
    ring = B * W * token_elems
    found = [f"scatter {name}" for _, name, _, op, args, _ in ins
             if op == "scatter" and elems(types[args[2]]) >= ring]
    found += [f"select {name} {typ}" for comp, name, typ, op, _, _ in ins
              if op == "select" and comp not in inside and elems(typ) >= ring]
    found += [f"carry {s}" for s in _shapes(carry)
              if sorted(s) != sorted(pool_shape)
              and int(np.prod(s)) >= L * ring]
    return found


@pytest.mark.parametrize("program", ["engine", "gather_step_scatter"])
def test_paged_decode_writes_back_only_new_tokens(one_chip, program):
    """The engine's decode program for a dense model, compiled for a v5e
    at reduced widths, moves no ring of the batch as a whole: it scatters
    only the new tokens into the pool, selects no whole ring outside the
    layer loop, and carries no (L, B, W, ...) cache through it.  The
    gather -> ``decode_step`` -> scatter program it replaced shows all
    three, which is what the guard is there to see."""
    L, B, max_len, bt = 3, 8, 512, 16
    cfg = dataclasses.replace(
        reduced_config(REGISTRY["nemotron-4-15b"], layers=L), d_model=256,
        n_heads=4, n_kv_heads=2, head_dim=128, d_ff=768, vocab=1024,
        param_dtype="bfloat16", compute_dtype="bfloat16")
    spec = dec.cache_view_specs(cfg, max_len)
    W = spec.width
    P = W // bt
    assert dec.decode_writes_in_place(cfg, spec)
    sd = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    storage = jax.tree.map(sd, jax.eval_shape(lambda: dec.init_block_pool(
        cfg, spec, 1 + B * P + 3, 1, bt, max_len)))
    if program == "engine":
        fn = dec.make_paged_decode(cfg, spec)
    else:
        def fn(params, storage, tables, slots, tokens, pos):
            view = dec.gather_cache_view(storage, spec, tables, slots)
            logits, view = dec.decode_step(params, cfg, view, tokens, pos)
            return logits, dec.scatter_cache_view(storage, spec, tables,
                                                  slots, view)
    hlo = jax.jit(fn, donate_argnums=(1,)).lower(
        jax.tree.map(sd, abstract_params(cfg)), storage, i32(B, P), i32(B),
        i32(B, 1), i32(B)).compile().as_text()
    found = _whole_ring_traffic(
        hlo, L=L, B=B, W=W, token_elems=cfg.n_kv_heads * cfg.head_dim,
        pool_shape=jax.tree.leaves(storage)[0].shape)
    if program == "engine":
        assert found == []
    else:
        assert {f.split()[0] for f in found} == {"scatter", "select",
                                                 "carry"}, found
