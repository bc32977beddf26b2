"""Pallas TPU kernels for the perf-critical compute hot spots.

Each kernel subpackage has: kernel.py (pl.pallas_call + BlockSpec),
ops.py (jit'd public wrapper, custom_vjp where trained through), and
ref.py (pure-jnp oracle used by the allclose test sweeps).
"""
import jax


def resolve_interpret(interpret):
    """The one interpret-mode rule every kernel's ``pallas_call`` uses.

    ``None`` (every wrapper's default) compiles the real kernel on a TPU
    and runs the kernel body in the Pallas interpreter on any other
    backend; an explicit bool overrides (tests force ``True``)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
