"""Kernel dispatch + mixed-precision policy — the switchboard between the
reference ``jnp`` implementations and the Pallas kernels.

Every hot-path op that has both a reference and a kernel implementation is
called THROUGH this module (``kl_loss``, ``gram``), selected by a
``KernelPolicy``:

* per-op on/off bits (``kl_mutual`` / ``ridge_gram``) — ``None`` means
  *auto*: on TPU the Pallas kernels compile natively, so auto enables
  them; elsewhere auto picks the reference path UNLESS
  ``REPRO_PALLAS_INTERPRET=1`` is set.  That variable is also the ONLY
  thing that runs a kernel through the Pallas interpreter
  (``repro.kernels.common.use_interpret``): a kernel the policy turns on
  compiles natively, or runs interpreted because the variable asked for
  it — never because the backend is not a TPU.  The parity suite uses it
  to check the kernel bodies without a TPU (``scripts/ci.sh``
  kernel-parity stage, ``pytest -m kernels``),
* block sizes forwarded to the kernels' BlockSpecs (``kl_block_rows``,
  ``gram_block_{m,n,k}``),
* a ``Precision`` policy: ``compute`` dtype for activations / matmul
  inputs (bf16 on the mixed preset) with ``accum`` (f32) accumulators —
  master parameters always stay f32 and loss/metric reductions are pinned
  to f32 by the callers (``repro.core.dnn`` forwards, the engine's masked
  E_max-scan).

Named presets (accepted anywhere a policy is: ``make_spec(policy=...)``,
``run_campaign(policy=...)``, the trainers):

* ``"reference"``   — pure-jnp f32 everywhere (force kernels OFF),
* ``"kernel"``      — auto per-op dispatch (kernels on TPU / under
  ``REPRO_PALLAS_INTERPRET=1``), f32,
* ``"kernel_bf16"`` — auto dispatch + a bf16-activation REQUEST: applied
  on backends with native low-precision matmul units (TPU/GPU),
  downgraded to f32 elsewhere (on CPU the casts are pure overhead).
  Construct ``KernelPolicy(precision=BF16)`` to force bf16 anywhere.

``None`` resolves to the ``"kernel"`` preset, so the default behavior on
CPU is numerically identical to the pre-dispatch reference code while TPU
runs pick up the kernels with no caller changes.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.kernels.common import use_interpret
from repro.kernels.kl_mutual import ops as _kl_ops
from repro.kernels.kl_mutual import ref as _kl_ref
from repro.kernels.ridge_gram import ops as _rg_ops
from repro.kernels.ridge_gram import ref as _rg_ref


# ---------------------------------------------------------------------------
# Precision policy
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Precision:
    """Mixed-precision rule: activations / matmul inputs in ``compute``,
    matmul accumulation and loss/metric reductions in ``accum``.  Master
    parameters are ALWAYS stored f32 — the compute cast happens inside the
    forward, so autodiff returns f32 gradients and SGD updates f32 weights
    (no precision loss accumulates across rounds)."""
    compute: str = "float32"
    accum: str = "float32"

    @property
    def compute_dtype(self):
        return jnp.dtype(self.compute)

    @property
    def accum_dtype(self):
        return jnp.dtype(self.accum)

    @property
    def is_mixed(self) -> bool:
        return self.compute != self.accum


F32 = Precision()
BF16 = Precision(compute="bfloat16", accum="float32")


# ---------------------------------------------------------------------------
# Kernel policy
# ---------------------------------------------------------------------------

def kernels_supported() -> bool:
    """Auto-dispatch default for the per-op bits: native on TPU; on every
    other backend only when ``REPRO_PALLAS_INTERPRET=1`` opts into the
    Pallas interpreter (parity testing, not speed).  Read dynamically so
    tests can flip the env var without re-importing."""
    return jax.default_backend() == "tpu" or use_interpret()


def mixed_precision_supported() -> bool:
    """Auto-precision default: bf16 compute pays only where the hardware
    has native low-precision matmul units (TPU MXU / GPU tensor cores);
    on CPU XLA upcasts every bf16 dot, so the casts are pure overhead."""
    return jax.default_backend() in ("tpu", "gpu")


@dataclass(frozen=True)
class KernelPolicy:
    """Per-op kernel dispatch + block sizes + precision.  ``None`` op bits
    mean "auto by backend" (see ``kernels_supported``); ``auto_precision``
    marks the precision as a *request* that resolution may downgrade to
    f32 on backends without native low-precision units.  ``resolved()``
    pins everything so a policy captured in a jitted closure never
    re-reads the environment."""
    kl_mutual: Optional[bool] = None
    ridge_gram: Optional[bool] = None
    precision: Precision = F32
    auto_precision: bool = False
    kl_block_rows: int = 256
    gram_block_m: int = 128
    gram_block_n: int = 128
    gram_block_k: int = 512

    def resolved(self) -> "KernelPolicy":
        auto = kernels_supported()
        prec = self.precision
        if self.auto_precision and not mixed_precision_supported():
            prec = F32
        return replace(
            self,
            kl_mutual=auto if self.kl_mutual is None else self.kl_mutual,
            ridge_gram=auto if self.ridge_gram is None else self.ridge_gram,
            precision=prec, auto_precision=False)


REFERENCE = KernelPolicy(kl_mutual=False, ridge_gram=False)
KERNEL = KernelPolicy()
# the PRESET requests bf16 (auto): applied on TPU/GPU, downgraded to f32
# elsewhere.  Construct KernelPolicy(precision=BF16) directly to FORCE
# bf16 compute on any backend (the parity tests do).
KERNEL_BF16 = KernelPolicy(precision=BF16, auto_precision=True)

_NAMED = {
    "reference": REFERENCE,
    "kernel": KERNEL,
    "kernel_bf16": KERNEL_BF16,
}

PolicyLike = Union[None, str, KernelPolicy]


def policy_names() -> tuple:
    return tuple(_NAMED)


def get_policy(policy: PolicyLike = None) -> KernelPolicy:
    """Normalize ``None`` / preset name / ``KernelPolicy`` to a RESOLVED
    policy (no ``None`` op bits left)."""
    if policy is None:
        policy = KERNEL
    if isinstance(policy, str):
        try:
            policy = _NAMED[policy]
        except KeyError:
            raise KeyError(f"unknown kernel policy {policy!r}; "
                           f"have {policy_names()}") from None
    return policy.resolved()


# ---------------------------------------------------------------------------
# Dispatched ops
# ---------------------------------------------------------------------------

def kl_loss(x_feat: jax.Array, y_feat: jax.Array, *,
            temperature: float = 1.0,
            policy: PolicyLike = None) -> jax.Array:
    """Mean over rows of D_KL(x ‖ y), y = stop-gradient target (the paper's
    eq. 5 order).  Kernel path: fused online-softmax Pallas kernel with
    closed-form custom_vjp; reference path: the same graph as
    ``repro.core.mutual.kl_paper``.  Both compute in f32 regardless of the
    input dtype (loss reductions are pinned)."""
    pol = get_policy(policy)
    if pol.kl_mutual:
        return _kl_ops.kl_loss(x_feat, y_feat, temperature=temperature,
                               bq=pol.kl_block_rows)
    y = jax.lax.stop_gradient(y_feat)
    return jnp.mean(_kl_ref.kl_rows(x_feat, y, temperature))


def gram(x: jax.Array, y: jax.Array, *,
         policy: PolicyLike = None) -> jax.Array:
    """G = XᵀY with f32 accumulation (x: (n, d1), y: (n, d2)).  Kernel
    path: MXU-blocked Pallas accumulation; reference path: one f32
    matmul.  Safe under vmap and inside ``shard_map`` (the Step-4
    per-layer Gram psum crosses the mesh AFTER this local product)."""
    pol = get_policy(policy)
    if pol.ridge_gram:
        return _rg_ops.gram(x, y, bm=pol.gram_block_m, bn=pol.gram_block_n,
                            bk=pol.gram_block_k)
    return _rg_ref.gram(x, y)
