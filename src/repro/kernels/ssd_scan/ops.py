"""Public wrapper for the SSD kernel, model layout in/out.

Forward runs the Pallas kernel; backward recomputes through the equivalent
differentiable jnp chunked algorithm (``repro.models.ssd.ssd_chunked``) —
the standard fused-forward / XLA-backward trade for scan kernels.  Both the
sequence output and the final state are differentiable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.kernel import ssd_scan


def _to_head_major(x):
    # (B, S, H, P) -> (B*H, S, P)
    B, S, H, P = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, P)


def _ssd_pallas(x, dt, A, B_, C_, chunk, interpret):
    Bb, S, H, P = x.shape
    G, N = B_.shape[2], B_.shape[3]
    a = (dt.astype(jnp.float32) * A.astype(jnp.float32)[None, None, :])
    xdt = x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]
    xf = _to_head_major(xdt)
    af = a.transpose(0, 2, 1).reshape(Bb * H, S)
    Bf = _to_head_major(B_)
    Cf = _to_head_major(C_)
    y, hT = ssd_scan(xf, af, Bf, Cf, chunk=chunk, hq_per_group=H // G,
                     interpret=interpret)
    y = y.reshape(Bb, H, S, P).transpose(0, 2, 1, 3).astype(x.dtype)
    # hT: (BH, N, P) -> (B, H, P, N) to match the model/ref state layout
    hT = hT.reshape(Bb, H, N, P).transpose(0, 1, 3, 2)
    return y, hT


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def ssd_with_state(x, dt, A, B_, C_, chunk=128, interpret=None):
    """x: (B,S,H,P); dt: (B,S,H); A: (H,); B_/C_: (B,S,G,N).

    Returns (y (B,S,H,P), h_final (B,H,P,N) f32)."""
    return _ssd_pallas(x, dt, A, B_, C_, chunk, interpret)


def _fwd(x, dt, A, B_, C_, chunk, interpret):
    out = _ssd_pallas(x, dt, A, B_, C_, chunk, interpret)
    return out, (x, dt, A, B_, C_)


def _bwd(chunk, interpret, res, cts):
    from repro.models.ssd import ssd_chunked
    x, dt, A, B_, C_ = res

    def recompute(x, dt, A, B_, C_):
        return ssd_chunked(x.astype(jnp.float32), dt.astype(jnp.float32),
                           A.astype(jnp.float32), B_, C_, chunk=chunk)

    _, vjp = jax.vjp(recompute, x, dt, A, B_, C_)
    g_y, g_h = cts
    return vjp((g_y.astype(jnp.float32), g_h.astype(jnp.float32)))


ssd_with_state.defvjp(_fwd, _bwd)


def ssd(x, dt, A, B_, C_, chunk=128, interpret=None):
    """Sequence output only."""
    return ssd_with_state(x, dt, A, B_, C_, chunk, interpret)[0]
