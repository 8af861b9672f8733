"""Mesh adapters over the unified engine (paper communication pattern as
real collectives).

This module used to hand-write the shard_map SplitMe round; that hot path
now lives in ``repro.core.engine.build_sharded_round_fn`` (clients sharded
over the mesh ``data``/``pod`` axes, masked-FedAvg psum as the round's only
cross-device collective).  What remains here:

* ``make_splitme_round`` — the old (w_c, w_s⁻¹, x, y1, key) signature as a
  thin adapter over the engine's "splitme" spec, kept for the fl_dryrun
  lowering and external callers,
* ``make_distributed_inversion`` — Step 4 on the mesh: per-shard Gram
  partials + psum (eq. 9 exactly), a thin adapter over
  ``repro.core.inversion``.

The hand-written vanilla-SFL round (per-step boundary ``ppermute`` — the
traffic SplitMe deletes) is dry-run collective accounting, not a production
path, and moved to ``repro.launch.fl_dryrun``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.splitme_dnn import DNNConfig
from repro.core import engine
from repro.core.engine import client_axes as _client_axes  # re-export


def make_splitme_round(cfg: DNNConfig, mesh: Mesh, *, n_clients: int,
                       samples_per_client: int, E: int, batch: int = 32,
                       lr_c: float = 0.05, lr_s: float = 0.02,
                       temperature: float = 2.0, unroll_steps: bool = False,
                       quant=None):
    """One SplitMe global round under shard_map, clients sharded over the
    mesh data axes — engine-backed.

    Returns ``round_fn(w_c, w_s_inv, x, y1, key) -> (w_c', w_s_inv')``
    training ALL clients (the dry-run cohort).  E local steps on both sides
    run with ZERO cross-client traffic; the only collective is the per-round
    FedAvg ``psum`` — the paper's "one communication per round".

    ``quant`` selects the ``CommQuant`` wire format of that psum (the
    fl_dryrun lowering counts the quantized payload).  This adapter keeps
    the old 5-argument signature, so the int8 error-feedback accumulator
    is re-zeroed per call — fine for single-round lowering/dry-runs; use
    the engine builder directly to carry it across rounds.
    """
    del samples_per_client  # shapes come from the data argument
    spec = engine.make_spec("splitme", cfg, lr_c=lr_c, lr_s=lr_s,
                            temperature=temperature, batch_size=batch,
                            masked_loss_metric=True, quant=quant)
    rf = engine.build_sharded_round_fn(spec, cfg, mesh, n_clients=n_clients,
                                       e_max=E, jit=False,
                                       unroll_steps=unroll_steps)
    n_shards = engine.n_client_shards(mesh)

    def round_fn(w_c, w_s_inv, x, y1, key):
        y = jnp.argmax(y1, -1).astype(jnp.int32)
        a_mask = jnp.ones((n_clients,), jnp.float32)
        qstate = engine.init_quant_state(spec, (w_c, w_s_inv),
                                         n_shards=n_shards)
        (w_c2, w_s2), _, _ = rf((w_c, w_s_inv), x, y, a_mask,
                                jnp.asarray(E, jnp.int32), key, qstate)
        return w_c2, w_s2

    return round_fn


def make_distributed_inversion(cfg: DNNConfig, mesh: Mesh,
                               gamma: float = 1e-3):
    """Step 4 on the mesh: per-shard Gram partials + psum (eq. 9 exactly)."""
    axes = _client_axes(mesh)
    from repro.core.inversion import invert_inverse_model

    def local(w_s_inv, smashed, y1):
        flat_s = smashed.reshape(-1, smashed.shape[-1])
        flat_y = y1.reshape(-1, y1.shape[-1])
        return invert_inverse_model(w_s_inv, flat_s, flat_y, cfg,
                                    gamma=gamma, axis_name=axes)

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(), P(axes), P(axes)),
                         out_specs=P(), check_vma=False)
