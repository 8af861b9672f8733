"""Pallas kernels for the paper's compute hot-spots, plus the dispatch
layer that makes them the production fast path.

Kernel packages (each: ``<name>.py`` kernel + ``ops.py`` jit'd wrapper +
``ref.py`` pure-jnp oracle):

* ``kl_mutual``   — fused online-softmax mutual-KL loss (paper eq. 5) with
  a closed-form ``custom_vjp``; every SplitMe local step runs it,
* ``ridge_gram``  — MXU-blocked Gram accumulation G = XᵀY for the Step-4
  analytic inversion (paper eq. 9),
* ``flash_attention`` / ``mamba2_scan`` / ``rwkv6_wkv`` — substrate
  kernels for the model-zoo configs.

Kernel & precision policy
=========================

The training stack never imports kernel ``ops`` directly — hot-path ops go
through ``repro.kernels.dispatch``:

* ``KernelPolicy`` holds per-op on/off bits (``None`` = auto), kernel
  block sizes, and a ``Precision`` (compute/accum dtypes).
* Auto dispatch rule: Pallas kernels on TPU; reference jnp on every other
  backend.  Interpret mode is never chosen by backend: only
  ``REPRO_PALLAS_INTERPRET=1`` runs the kernel bodies through the Pallas
  interpreter (and turns the auto bits on off-TPU) — that is how the
  parity suite (``pytest -m kernels``, the ``scripts/ci.sh`` kernel stage)
  validates them bit-for-bit without a TPU.
* Presets: ``"reference"`` (force kernels off, f32 — the escape hatch
  that reproduces pre-kernel numerics exactly), ``"kernel"`` (auto, f32),
  ``"kernel_bf16"`` (auto + bf16 activations / f32 accumulators and
  master params where the backend has native low-precision units —
  TPU/GPU — downgraded to f32 elsewhere; loss and metric reductions stay
  f32 always.  ``KernelPolicy(precision=BF16)`` forces bf16 anywhere).
* Threading: ``engine.make_spec(policy=...)`` binds a resolved policy
  into the framework spec; the round builders, ``build_eval_fn``, the
  Step-4 inversion, the serial trainers (``kernel_policy=``) and the
  campaign runner (``run_campaign(policy=...)``) all honor it, so one
  flag kernelizes a whole scanned campaign end-to-end.

Parity: the f32 kernel policy matches the reference path at 1e-5 over a
full campaign; the bf16 policy at 1e-3 (tests/test_kernel_dispatch.py).
"""
