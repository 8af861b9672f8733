"""Analytic layer-wise inversion of the inverse server-side model
(paper §III-B Step 4, eq. 8-9) — the "zeroth-order" final-model acquisition.

For each layer l of the server-side model s(·):

    W_l = ( Σ_m O_l^(m)ᵀ O_l^(m) + γI )⁻¹ ( Σ_m O_l^(m)ᵀ Z_l^(m) )

where O_l is the input of layer l (starting from the smashed data c(X_m)) and
Z_l is the matching-depth activation of the trained inverse model s⁻¹ fed
with the labels.  Both Gram sums are all-reduce ops across the selected
rApps; on the mesh that is ``jax.lax.psum`` over the client axis.  Each layer
trains in one shot — a single communication round recovers all of s(·).

The Gram products are the compute hot-spot; they route through the kernel
dispatch layer (``repro.kernels.dispatch.gram``), which picks the Pallas
ridge_gram kernel or the reference f32 matmul per the ``KernelPolicy``
(default: auto by backend — kernel on TPU, reference on CPU).  The legacy
``use_kernel`` flag force-overrides the policy's gram bit.
"""
from __future__ import annotations

from dataclasses import replace
from typing import List, Optional

import jax
import jax.numpy as jnp

from repro.configs.splitme_dnn import DNNConfig
from repro.core import dnn
from repro.kernels import dispatch
from repro.kernels.dispatch import KernelPolicy
from repro.models.common import activation_fn


def _gram(o: jax.Array, z: jax.Array,
          policy: Optional[KernelPolicy] = None):
    """Returns (OᵀO, OᵀZ) in float32 via the kernel dispatch layer.  The
    policy is resolved by the caller (or auto-resolved here for direct
    use); selection is a trace-time Python branch on a frozen dataclass,
    so flag flips never retrace a shared closure."""
    pol = dispatch.get_policy(policy)
    return dispatch.gram(o, o, policy=pol), dispatch.gram(o, z, policy=pol)


def _augment(o: jax.Array) -> jax.Array:
    """Append a ones column so the ridge solve also recovers the bias."""
    return jnp.concatenate([o, jnp.ones((*o.shape[:-1], 1), o.dtype)], -1)


def invert_inverse_model(inverse_params: List[dict],
                         smashed: jax.Array,
                         labels_onehot: jax.Array,
                         cfg: DNNConfig,
                         gamma: float = 1e-3,
                         axis_name: Optional[str] = None,
                         use_kernel: Optional[bool] = None,
                         policy: Optional[KernelPolicy] = None
                         ) -> List[dict]:
    """Recover the server-side model s(·) from the trained s⁻¹(·).

    smashed: c(X_m) for this client's shard, (n, d_split).
    labels_onehot: (n, n_classes).
    axis_name: mesh axis of the selected rApps; the Gram sums are psum'd over
      it (the paper's GLOO all-reduce → TPU ICI all-reduce).
    policy: kernel dispatch policy for the Gram products (None → auto by
      backend); ``use_kernel`` (legacy) force-overrides its gram bit.
    The ridge solve itself always runs f32 — the Grams accumulate f32 even
    when the smashed activations arrive in the policy's compute dtype, and
    both the reference Gram and the layer-to-layer ``o @ w`` run at HIGHEST
    precision (a TPU's default f32 dot is one bf16 pass, which the
    ill-conditioned solve would amplify).
    """
    pol = dispatch.get_policy(policy)
    if use_kernel is not None:
        pol = replace(pol, ridge_gram=use_kernel)
    act = activation_fn(cfg.activation)
    # supervised targets: activations of s⁻¹ on the labels, deepest first.
    # s⁻¹ activations [a_1 … a_L]; target for s's layer l (1-based) is
    # a_{L-l}, and for the last layer the labels themselves.
    inv_acts = dnn.mlp_activations(inverse_params, labels_onehot,
                                   cfg.activation)
    L = len(inverse_params)
    targets = [inv_acts[L - 1 - l] for l in range(1, L)] + [labels_onehot]

    server_params: List[dict] = []
    o = smashed
    for l, z in enumerate(targets):
        o_aug = _augment(o)
        a0, a1 = _gram(o_aug, z, pol)
        if axis_name is not None:
            # one fused all-reduce per layer: both Gram sums cross the mesh
            # in a single concatenated payload (exact — elementwise sums)
            both = jax.lax.psum(jnp.concatenate([a0, a1], axis=1), axis_name)
            a0, a1 = both[:, :a0.shape[1]], both[:, a0.shape[1]:]
        d = a0.shape[0]
        w_aug = jnp.linalg.solve(a0 + gamma * jnp.eye(d, dtype=a0.dtype), a1)
        w, b = w_aug[:-1], w_aug[-1]
        server_params.append({"w": w, "b": b})
        o = jnp.matmul(o, w, precision=jax.lax.Precision.HIGHEST) + b
        if l < len(targets) - 1:
            o = act(o)
    return server_params
