"""Pallas kernel sweeps: shapes x dtypes vs the pure-jnp oracles
(interpret mode), plus hypothesis property tests on the math."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import hypothesis_tools

given, settings, st = hypothesis_tools()

from repro.kernels.flash_attention.ops import (RING_MAX_ROWS,
                                               flash_attention,
                                               ring_blocks,
                                               ring_chunk_attention)
from repro.kernels.flash_attention.ref import gqa_attention_ref
from repro.kernels.rglru_scan.ops import lru
from repro.kernels.rglru_scan.ref import lru_scan_ref
from repro.kernels.ssd_scan.ops import ssd, ssd_with_state
from repro.kernels.ssd_scan.ref import ssd_scan_ref
from repro.models.layers import blocked_attention

KEY = jax.random.PRNGKey(11)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FA_SWEEP = [
    # B, S, Hq, Hkv, dh, causal, window, dtype
    (2, 128, 4, 2, 64, True, 0, jnp.float32),
    (1, 256, 4, 4, 32, True, 64, jnp.float32),
    (2, 128, 8, 2, 64, False, 0, jnp.float32),
    (1, 128, 2, 1, 128, True, 32, jnp.float32),
    (2, 64, 4, 1, 64, True, 0, jnp.bfloat16),
    (1, 192, 6, 3, 32, True, 48, jnp.float32),
]


@pytest.mark.parametrize("B,S,Hq,Hkv,dh,causal,window,dtype", FA_SWEEP)
def test_flash_attention_fwd(B, S, Hq, Hkv, dh, causal, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, dh), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, dh), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, dh), dtype)
    out = flash_attention(q, k, v, causal, window, 64, 64, True)
    ref = gqa_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("B,S,Hq,Hkv,dh,causal,window,dtype", FA_SWEEP[:4])
def test_flash_attention_grads(B, S, Hq, Hkv, dh, causal, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, dh), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, dh), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, dh), dtype)
    f = lambda q, k, v: (flash_attention(q, k, v, causal, window, 64, 64,
                                         True) ** 2).sum()
    fr = lambda q, k, v: (gqa_attention_ref(q, k, v, causal=causal,
                                            window=window) ** 2).sum()
    g1 = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_attention_causality():
    """Changing a future token never changes past outputs."""
    ks = jax.random.split(KEY, 3)
    B, S, H, dh = 1, 64, 2, 32
    q = jax.random.normal(ks[0], (B, S, H, dh))
    k = jax.random.normal(ks[1], (B, S, H, dh))
    v = jax.random.normal(ks[2], (B, S, H, dh))
    out1 = flash_attention(q, k, v, True, 0, 32, 32, True)
    k2 = k.at[:, -1].add(100.0)
    v2 = v.at[:, -1].add(100.0)
    out2 = flash_attention(q, k2, v2, True, 0, 32, 32, True)
    np.testing.assert_allclose(np.asarray(out1[:, :-1]),
                               np.asarray(out2[:, :-1]), rtol=1e-6, atol=1e-6)


def test_window_equals_masked_dense():
    """SWA kernel == dense attention with an explicit band mask."""
    ks = jax.random.split(KEY, 3)
    B, S, H, dh, W = 1, 96, 2, 16, 24
    q = jax.random.normal(ks[0], (B, S, H, dh))
    k = jax.random.normal(ks[1], (B, S, H, dh))
    v = jax.random.normal(ks[2], (B, S, H, dh))
    out = flash_attention(q, k, v, True, W, 32, 32, True)
    ref = gqa_attention_ref(q, k, v, causal=True, window=W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 3), st.integers(1, 4), st.integers(0, 1))
def test_blocked_attention_property(b, heads_pow, causal):
    """jnp blocked attention == dense oracle for random GQA configs."""
    Hq = 2 ** heads_pow
    Hkv = max(1, Hq // 2)
    S, dh = 48, 16
    ks = jax.random.split(jax.random.PRNGKey(b * 7 + heads_pow), 3)
    q = jax.random.normal(ks[0], (b, S, Hq, dh))
    k = jax.random.normal(ks[1], (b, S, Hkv, dh))
    v = jax.random.normal(ks[2], (b, S, Hkv, dh))
    out = blocked_attention(q, k, v, causal=bool(causal), block_q=16,
                            block_kv=16)
    ref = gqa_attention_ref(q, k, v, causal=bool(causal))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# ring-chunk attention (serving fused-prefill kernel)
# ---------------------------------------------------------------------------

def _ring_case(B, C, W, Hq, Hkv, dh, pos, nt, window, softcap, bq, bkv,
               dtype, seed=0):
    """Blocked Pallas kernel (interpret) vs the dense chunk_attention
    reference, row-by-row: active rows (t < n_tokens) must match; inactive
    rows are discarded by the engine but must at least stay finite (the
    kernel returns 0 where the dense path degrades to a uniform softmax)."""
    from repro.models.layers import chunk_attention
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, C, Hq, dh), dtype)
    kn = jax.random.normal(ks[1], (B, C, Hkv, dh), dtype)
    vn = jax.random.normal(ks[2], (B, C, Hkv, dh), dtype)
    kc = jax.random.normal(ks[3], (B, W, Hkv, dh), dtype)
    vc = jax.random.normal(ks[4], (B, W, Hkv, dh), dtype)
    pos = jnp.asarray(pos, jnp.int32)
    nt = jnp.asarray(nt, jnp.int32)
    ref = np.asarray(chunk_attention(q, kn, vn, kc, vc, pos, nt,
                                     window=window, softcap=softcap),
                     np.float32)
    out = np.asarray(ring_chunk_attention(q, kn, vn, kc, vc, pos, nt,
                                          window=window, softcap=softcap,
                                          block_q=bq, block_kv=bkv,
                                          interpret=True), np.float32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 5e-5
    for b in range(B):
        n = int(nt[b])
        np.testing.assert_allclose(out[b, :n], ref[b, :n], rtol=tol,
                                   atol=tol, err_msg=f"stream {b}")
        assert np.all(np.isfinite(out[b, n:])), f"stream {b} inactive rows"


RING_SWEEP = [
    # B, C, W, Hq, Hkv, dh, pos, nt, window, softcap, bq, bkv, dtype
    (2, 4, 16, 4, 2, 32, (0, 3), (4, 2), 0, 0.0, 32, 32, jnp.float32),
    (2, 6, 8, 4, 4, 16, (13, 27), (6, 6), 0, 0.0, 4, 4, jnp.float32),
    (2, 10, 8, 4, 2, 16, (5, 21), (10, 7), 0, 0.0, 32, 32, jnp.float32),
    (3, 4, 8, 2, 1, 16, (0, 5, 9), (0, 0, 4), 0, 0.0, 32, 32, jnp.float32),
    (2, 8, 16, 4, 2, 32, (20, 3), (8, 5), 7, 30.0, 4, 8, jnp.float32),
    (1, 4, 8, 8, 1, 32, (11,), (4,), 0, 0.0, 32, 32, jnp.float32),
    (1, 5, 13, 2, 2, 16, (29,), (5,), 0, 0.0, 4, 8, jnp.float32),
    (2, 6, 12, 4, 2, 32, (9, 15), (6, 3), 0, 0.0, 8, 8, jnp.bfloat16),
]


@pytest.mark.parametrize(
    "B,C,W,Hq,Hkv,dh,pos,nt,window,softcap,bq,bkv,dtype", RING_SWEEP,
    ids=["basic", "ring_wrap_tails", "chunk_wider_than_ring", "idle_rows",
         "window_softcap", "gqa_group8", "nondivisible_bkv", "bf16"])
def test_ring_chunk_attention_vs_dense(B, C, W, Hq, Hkv, dh, pos, nt,
                                       window, softcap, bq, bkv, dtype):
    _ring_case(B, C, W, Hq, Hkv, dh, pos, nt, window, softcap, bq, bkv,
               dtype, seed=B * 31 + C)


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 10**6))
def test_ring_chunk_attention_property(seed):
    """Randomized equivalence: random chunk/ring widths (incl. C > W),
    positions (incl. ring wrap and pos=0), per-stream n_tokens (incl. 0),
    GQA group sizes and block shapes that leave partial tail blocks."""
    rng = np.random.default_rng(seed)
    B = int(rng.integers(1, 4))
    C = int(rng.integers(1, 12))
    W = int(rng.integers(2, 20))
    G = int(rng.choice([1, 2, 4]))
    Hkv = int(rng.choice([1, 2]))
    dh = int(rng.choice([8, 16]))
    pos = rng.integers(0, 3 * W, size=B)
    nt = rng.integers(0, C + 1, size=B)
    window = int(rng.choice([0, 0, max(1, W // 2)]))
    softcap = float(rng.choice([0.0, 25.0]))
    bq = int(rng.choice([3, 4, 8, 32]))
    bkv = int(rng.choice([5, 8, 16, 32]))
    _ring_case(B, C, W, G * Hkv, Hkv, dh, pos, nt, window, softcap, bq,
               bkv, jnp.float32, seed=seed % 1009)


# blocks chosen from the shapes (``ring_blocks``), as the engine runs them
RING_SHAPE_SWEEP = [
    # B, C, W, Hq, Hkv, dh, pos, nt, window, softcap, dtype
    (2, 16, 4096, 12, 2, 128, (4100, 37), (16, 9), 0, 0.0, jnp.bfloat16),
    (1, 16, 512, 12, 1, 64, (700,), (16,), 0, 0.0, jnp.float32),
    (2, 16, 2048, 16, 1, 256, (3000, 1500), (16, 11), 1000, 0.0,
     jnp.float32),
    (3, 5, 64, 8, 2, 32, (0, 70, 200), (5, 3, 5), 0, 0.0, jnp.float32),
    (2, 24, 16, 4, 2, 32, (5, 40), (24, 17), 0, 0.0, jnp.float32),
    (3, 16, 32, 6, 1, 32, (0, 0, 50), (0, 16, 16), 0, 0.0, jnp.float32),
    (2, 16, 256, 8, 1, 64, (300, 10), (16, 12), 100, 30.0, jnp.bfloat16),
]


@pytest.mark.parametrize(
    "B,C,W,Hq,Hkv,dh,pos,nt,window,softcap,dtype", RING_SHAPE_SWEEP,
    ids=["group6_c16_bf16", "group12", "group16_window", "spec_verify_c5",
         "chunk_wider_than_ring", "idle_rows_at_pos0",
         "group8_window_softcap_bf16"])
def test_ring_chunk_attention_shape_blocks_vs_dense(B, C, W, Hq, Hkv, dh,
                                                    pos, nt, window,
                                                    softcap, dtype):
    _ring_case(B, C, W, Hq, Hkv, dh, pos, nt, window, softcap, None, None,
               dtype, seed=B * 37 + C)


@pytest.mark.parametrize("C,W,G,dh,dtype", [
    (16, 4096, 6, 128, jnp.bfloat16), (16, 4096, 12, 128, jnp.bfloat16),
    (16, 4096, 3, 128, jnp.bfloat16), (16, 2048, 16, 256, jnp.bfloat16),
    (5, 4096, 6, 128, jnp.bfloat16), (512, 4096, 1, 128, jnp.bfloat16),
    (7, 20, 2, 16, jnp.float32)])
def test_ring_blocks_tile_the_chip(C, W, G, dh, dtype):
    """Blocks chosen from the shapes: query rows on whole sublane tiles
    covering the chunk (or capped), keys on whole 128-lane tiles, and the
    padding of the keys past W + C under 3% wherever the ring is long
    enough for 128-lane tiles to allow it."""
    bq, bkv = ring_blocks(C, W, G, dh, dtype)
    sub = 32 // jnp.dtype(dtype).itemsize
    assert bq % sub == 0 and G * bq <= max(RING_MAX_ROWS, G * sub)
    assert bq >= C or G * (bq + sub) > RING_MAX_ROWS
    assert bkv % 128 == 0
    L = W + C
    Lp = -(-L // bkv) * bkv
    if L >= 4096:
        assert Lp <= 1.03 * L and Lp // bkv <= 4


def test_ring_chunk_attention_idle_stream_at_pos0_returns_zeros():
    """A fully-masked row (idle stream with an empty ring) must come out
    exactly 0 from the kernel — the online-softmax finalize guards its
    zero normalizer instead of emitting NaN (the dense path's discarded
    uniform-softmax row is the reference's equivalent hazard)."""
    ks = jax.random.split(KEY, 5)
    B, C, W, H, dh = 1, 4, 8, 2, 16
    out = ring_chunk_attention(
        jax.random.normal(ks[0], (B, C, H, dh)),
        jax.random.normal(ks[1], (B, C, H, dh)),
        jax.random.normal(ks[2], (B, C, H, dh)),
        jax.random.normal(ks[3], (B, W, H, dh)),
        jax.random.normal(ks[4], (B, W, H, dh)),
        jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
        interpret=True)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


# ---------------------------------------------------------------------------
# RG-LRU scan
# ---------------------------------------------------------------------------

LRU_SWEEP = [(2, 128, 64, 32, 32), (1, 64, 128, 16, 128), (3, 32, 16, 32, 16),
             (1, 256, 32, 64, 32)]


@pytest.mark.parametrize("B,S,W,bs,bw", LRU_SWEEP)
def test_lru_scan(B, S, W, bs, bw):
    k1, k2 = jax.random.split(KEY)
    a = jax.nn.sigmoid(jax.random.normal(k1, (B, S, W)))
    b = jax.random.normal(k2, (B, S, W))
    h = lru(a, b, bs, bw, True)
    href, _ = lru_scan_ref(a, b)
    np.testing.assert_allclose(np.asarray(h), np.asarray(href),
                               rtol=1e-5, atol=1e-5)


def test_lru_grads():
    B, S, W = 2, 64, 32
    k1, k2 = jax.random.split(KEY)
    a = jax.nn.sigmoid(jax.random.normal(k1, (B, S, W)))
    b = jax.random.normal(k2, (B, S, W))
    g1 = jax.grad(lambda a, b: (lru(a, b, 16, 32, True) ** 2).sum(),
                  argnums=(0, 1))(a, b)
    g2 = jax.grad(lambda a, b: (lru_scan_ref(a, b)[0] ** 2).sum(),
                  argnums=(0, 1))(a, b)
    for x, y in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 1000))
def test_lru_decay_bound_property(seed):
    """|h_t| <= max|b| / (1 - max a) for decays in (0, 1) (BIBO bound)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (1, 64, 8))) * 0.95
    b = jax.random.normal(ks[1], (1, 64, 8))
    h = lru(a, b, 16, 8, True)
    bound = float(jnp.max(jnp.abs(b))) / (1 - 0.95) + 1e-3
    assert float(jnp.max(jnp.abs(h))) <= bound


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

SSD_SWEEP = [(2, 64, 4, 16, 1, 32, 16), (1, 32, 4, 8, 2, 16, 32),
             (2, 128, 2, 32, 1, 8, 64)]


@pytest.mark.parametrize("B,S,H,Pd,G,N,chunk", SSD_SWEEP)
def test_ssd_scan(B, S, H, Pd, G, N, chunk):
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (B, S, H, Pd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)) - 1.0)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    B_ = jax.random.normal(ks[3], (B, S, G, N)) * 0.5
    C_ = jax.random.normal(ks[0], (B, S, G, N)) * 0.5
    y, hT = ssd_with_state(x, dt, A, B_, C_, chunk=chunk, interpret=True)
    yref, href = ssd_scan_ref(x, dt, A, B_, C_)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(href),
                               rtol=3e-4, atol=3e-4)


def test_ssd_grads():
    B, S, H, Pd, G, N = 1, 32, 2, 8, 1, 16
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (B, S, H, Pd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)) - 1.0)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    B_ = jax.random.normal(ks[3], (B, S, G, N)) * 0.5
    C_ = jax.random.normal(ks[0], (B, S, G, N)) * 0.5
    g1 = jax.grad(lambda x, dt: (ssd(x, dt, A, B_, C_, 16, True) ** 2).sum(),
                  argnums=(0, 1))(x, dt)
    g2 = jax.grad(lambda x, dt: (ssd_scan_ref(x, dt, A, B_, C_)[0] ** 2).sum(),
                  argnums=(0, 1))(x, dt)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 500))
def test_ssd_state_linearity_property(seed):
    """SSD is linear in x: y(ax) = a*y(x) for fixed gates."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    B, S, H, Pd, G, N = 1, 32, 2, 8, 1, 8
    x = jax.random.normal(ks[0], (B, S, H, Pd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.2)
    B_ = jax.random.normal(ks[3], (B, S, G, N)) * 0.5
    C_ = jax.random.normal(ks[0], (B, S, G, N)) * 0.5
    y1 = ssd(x, dt, A, B_, C_, 16, True)
    y2 = ssd(2.5 * x, dt, A, B_, C_, 16, True)
    np.testing.assert_allclose(np.asarray(y2), 2.5 * np.asarray(y1),
                               rtol=1e-4, atol=1e-4)
