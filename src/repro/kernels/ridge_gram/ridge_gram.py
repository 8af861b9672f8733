"""Pallas TPU kernel: blocked Gram accumulation  G = XᵀY.

The compute hot-spot of SplitMe's analytic layer-wise inversion (paper
eq. 9): each rApp computes A0 = Σ OᵀO and A1 = Σ OᵀZ over its local shard
before the cross-rApp all-reduce.  n (samples) is the contraction dim and is
by far the largest, so the kernel tiles it as the innermost sequential grid
axis and accumulates partial MXU products into a VMEM-resident output block.

BlockSpec layout (MXU-aligned, fp32 accumulation):
    X block (bk, bm) @ grid (i, j, k) -> (k, i)
    Y block (bk, bn) @ grid (i, j, k) -> (k, j)
    G block (bm, bn) @ grid (i, j, k) -> (i, j)   (k sequential, accumulate)
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _gram_kernel(x_ref, y_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jax.lax.dot_general(
        x_ref[...], y_ref[...],
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def gram_pallas(x: jax.Array, y: jax.Array, *, bm: int = 128, bn: int = 128,
                bk: int = 512, interpret: bool = False) -> jax.Array:
    """x: (n, d1), y: (n, d2) -> (d1, d2) in float32.  Dims must be multiples
    of the block sizes (ops.py pads)."""
    n, d1 = x.shape
    _, d2 = y.shape
    grid = (d1 // bm, d2 // bn, n // bk)
    return pl.pallas_call(
        _gram_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bm), lambda i, j, k: (k, i)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((d1, d2), jnp.float32),
        interpret=interpret,
    )(x.astype(jnp.float32), y.astype(jnp.float32))
