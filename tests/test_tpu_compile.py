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
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.kernels.flash_attention.ops import (flash_attention,
                                               ring_chunk_attention)
from repro.kernels.rglru_scan.kernel import lru_scan
from repro.models.decode import replicate_over


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
