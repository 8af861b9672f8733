"""Shared kernel plumbing.

All kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling) and the
``ops.py`` wrappers compile them natively by default.  Interpret mode —
the Pallas interpreter, which runs the kernel body as ordinary XLA ops on
any backend for bit-level validation against the ref oracles — is an
explicit opt-in: ``REPRO_PALLAS_INTERPRET=1``.  The wrappers never choose
it by looking at the backend, so a kernel called on a CPU without the
opt-in fails to lower instead of silently running interpreted.
"""
from __future__ import annotations

import os


def use_interpret() -> bool:
    """True only when ``REPRO_PALLAS_INTERPRET=1`` asks for the Pallas
    interpreter (read at trace time, so tests can set it per module)."""
    return os.environ.get("REPRO_PALLAS_INTERPRET", "") == "1"


def pad_to(x, axis: int, multiple: int, value=0.0):
    """Pad `axis` up to a multiple; returns (padded, original_size)."""
    import jax.numpy as jnp
    n = x.shape[axis]
    rem = (-n) % multiple
    if rem == 0:
        return x, n
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads, constant_values=value), n
