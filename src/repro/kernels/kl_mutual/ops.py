"""jit'd public wrapper for the fused mutual-KL loss kernel.

Forward runs the Pallas kernel; the backward pass uses the closed-form
gradient  ∂/∂x mean KL(x‖y) = (softmax(x/T) − softmax(y/T)) / (T·n)
via custom_vjp (cheaper than autodiff through the online-softmax kernel,
and the target side y is stop-gradient by the paper's construction).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import pad_to, use_interpret
from repro.kernels.kl_mutual.kl_mutual import kl_rows_pallas


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _kl_mean(x_logits, y_logits, temperature, bq):
    n = x_logits.shape[0]
    x_p, _ = pad_to(x_logits, 0, bq)
    y_p, _ = pad_to(y_logits, 0, bq)
    rows = kl_rows_pallas(x_p, y_p, temperature=temperature, bq=bq,
                          interpret=use_interpret())
    return jnp.sum(rows[:n]) / n


def _kl_fwd(x_logits, y_logits, temperature, bq):
    return _kl_mean(x_logits, y_logits, temperature, bq), (x_logits, y_logits)


def _kl_bwd(temperature, bq, res, g):
    x_logits, y_logits = res
    n = x_logits.shape[0]
    p_x = jax.nn.softmax(x_logits.astype(jnp.float32) / temperature, -1)
    p_y = jax.nn.softmax(y_logits.astype(jnp.float32) / temperature, -1)
    gx = (g * (p_x - p_y) / (temperature * n)).astype(x_logits.dtype)
    return gx, jnp.zeros_like(y_logits)     # y is the stop-grad target


_kl_mean.defvjp(_kl_fwd, _kl_bwd)


@functools.partial(jax.jit, static_argnames=("temperature", "bq"))
def kl_loss(x_logits: jax.Array, y_logits: jax.Array, *,
            temperature: float = 1.0, bq: int = 256) -> jax.Array:
    """Mean over rows of D_KL(x ‖ y) (y = stop-grad target, paper order).
    Rows pad to a multiple of 16 (the bf16 sublane tile); zero-padded rows
    have KL exactly 0 and are sliced off before the mean anyway."""
    n = x_logits.shape[0]
    bq = min(bq, -(-n // 16) * 16)
    y_logits = jax.lax.stop_gradient(y_logits)
    return _kl_mean(x_logits, y_logits, temperature, bq)
