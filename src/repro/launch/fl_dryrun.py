"""Dry-run of the paper's OWN technique on the production mesh.

Lowers one global round of (a) SplitMe — the engine's shard_map round via
``repro.core.distributed.make_splitme_round`` — and (b) vanilla SFL — the
hand-written per-step boundary-exchange round kept HERE as dry-run
collective accounting — with M clients sharded over the mesh data axes, for
E ∈ {1, 10}, and compares collective traffic.  The paper's claim ("reduce
the multiple-communication-per-round level of SFL to one-communication-per-
round") becomes a structural property of the lowered HLO:

    SplitMe  : collective bytes CONSTANT in E (one fused all-reduce per
               round + Step-4 Gram psum)
    vanilla  : collective bytes ∝ E (two boundary permutes per local step)

    PYTHONPATH=src python -m repro.launch.fl_dryrun [--multipod]

(The XLA host-device flag is set only when run as a script, so importing
this module — e.g. for the SFL dry-run round — never touches jax state.)
"""
import argparse
import json
import os
import time
from pathlib import Path

if __name__ == "__main__":
    # append, don't replace: the forced device count must survive a
    # user-supplied XLA_FLAGS (the 16x16 mesh needs 256 devices)
    _flag = "--xla_force_host_platform_device_count=512"
    if _flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = \
            f"{os.environ.get('XLA_FLAGS', '')} {_flag}".strip()

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.splitme_dnn import DNN10, DNNConfig
from repro.core import dnn, quantcomm
from repro.core.distributed import (_client_axes, make_distributed_inversion,
                                    make_splitme_round)
from repro.launch.mesh import make_production_mesh
from repro.roofline.analysis import parse_collectives

RESULTS = Path(__file__).resolve().parents[3] / "benchmarks" / "results"


# ---------------------------------------------------------------------------
# Vanilla-SFL round with the per-batch boundary exchange made explicit.
# This is DRY-RUN COLLECTIVE ACCOUNTING for the paper's baseline, not a
# production path (the engine's "sfl" spec trains the same joint gradients
# locally and only *counts* the boundary bits in its comm_model) — which is
# why it lives here and not in repro.core.
# ---------------------------------------------------------------------------

def _steps_scan(step, carry, keys, unroll_steps: bool):
    """lax.scan over local updates, or python-unrolled (the dry-run needs
    unrolled bodies so per-step collectives are counted E times)."""
    if not unroll_steps:
        carry, losses = jax.lax.scan(step, carry, keys)
        return carry, losses
    losses = []
    for i in range(keys.shape[0]):
        carry, l = step(carry, keys[i])
        losses.append(l)
    return carry, jnp.stack(losses)


def make_sfl_round(cfg: DNNConfig, mesh, *, n_clients: int,
                   samples_per_client: int, E: int, batch: int = 32,
                   lr: float = 0.05, unroll_steps: bool = False):
    """Vanilla SFL (SplitFed) round with the per-batch boundary exchange
    made explicit: each local step all-gathers the smashed batch to the
    server tier and scatter-reduces the boundary gradient back — E times
    per round per client (the traffic SplitMe eliminates)."""
    axes = _client_axes(mesh)

    def local_round(w_c, w_s, x, y, key):
        def per_client(x_m, y_m, key_m):
            def step(carry, k):
                wc, ws = carry
                idx = jax.random.randint(k, (batch,), 0, x_m.shape[0])
                xb, yb = x_m[idx], y_m[idx]

                def client_half(wc):
                    return dnn.client_forward(wc, xb, cfg)

                smashed, vjp_c = jax.vjp(client_half, wc)
                # --- boundary exchange #1: smashed data -> server tier ----
                # point-to-point xApp -> rApp transfer = collective-permute
                size = mesh.shape["model"]
                up = [(i, (i + 1) % size) for i in range(size)]
                down = [(i, (i - 1) % size) for i in range(size)]
                smashed_srv = jax.lax.ppermute(smashed, "model", up)

                def server_loss(ws, h):
                    logits = dnn.server_forward(ws, h, cfg)
                    logp = jax.nn.log_softmax(logits, -1)
                    return -jnp.mean(jnp.take_along_axis(
                        logp, yb[:, None], axis=1))

                loss, (g_ws, g_h) = jax.value_and_grad(
                    server_loss, argnums=(0, 1))(ws, smashed_srv)
                # --- boundary exchange #2: gradient -> client tier --------
                g_h_back = jax.lax.ppermute(g_h, "model", down)
                (g_wc,) = vjp_c(g_h_back)
                wc = jax.tree.map(lambda p, g: p - lr * g, wc, g_wc)
                ws = jax.tree.map(lambda p, g: p - lr * g, ws, g_ws)
                return (wc, ws), loss

            (wc, ws), _ = _steps_scan(step, (w_c, w_s),
                                      jax.random.split(key_m, E),
                                      unroll_steps)
            return wc, ws

        keys = jax.random.split(key, x.shape[0])
        wc_new, ws_new = jax.vmap(per_client)(x, y, keys)
        mean_local = lambda t: jax.tree.map(lambda a: jnp.mean(a, 0), t)
        wc_new, ws_new = mean_local(wc_new), mean_local(ws_new)
        scale = 1.0 / jax.lax.psum(1.0, axes)
        wc_agg = jax.tree.map(lambda a: jax.lax.psum(a * scale, axes), wc_new)
        ws_agg = jax.tree.map(lambda a: jax.lax.psum(a * scale, axes), ws_new)
        return wc_agg, ws_agg

    spec_clients = P(axes)
    spec_rep = P()
    return jax.shard_map(local_round, mesh=mesh,
                         in_specs=(spec_rep, spec_rep, spec_clients,
                                   spec_clients, spec_rep),
                         out_specs=(spec_rep, spec_rep), check_vma=False)


# ---------------------------------------------------------------------------
# Lowering + collective accounting
# ---------------------------------------------------------------------------

def collective_comm_bits(colls, quant=None) -> float:
    """Wire bits of the lowered collectives under the ``CommQuant``
    accounting: payload ELEMENT count × the policy's wire width.

    This used to be ``result_bytes * 8`` — hardcoding whatever dtype the
    HLO printed, which is f32 even for quantized rounds: XLA's CPU passes
    hoist the bf16 converts out of the all-reduce, and int8 is a simulated
    wire format carried as f32 in the HLO by design (an int8 all-reduce
    sum would overflow).  Counting elements × ``wire_bits`` reports the
    quantized payload width on every backend
    (tests/test_quantcomm.py pins bf16 → exactly half the f32 bits)."""
    q = quantcomm.get_quant(quant)
    return float(sum(c.result_elems for c in colls)) * q.wire_bits


def lower_round(kind: str, mesh, M: int, n: int, E: int, quant=None):
    cfg = DNN10
    SDS = jax.ShapeDtypeStruct
    f32, i32 = jnp.float32, jnp.int32
    w_c = jax.eval_shape(lambda: dnn.init_client(jax.random.PRNGKey(0), cfg))
    key = SDS((2,), jnp.uint32)
    if kind == "splitme":
        fn = make_splitme_round(cfg, mesh, n_clients=M, samples_per_client=n,
                                E=E, unroll_steps=True, quant=quant)
        w_i = jax.eval_shape(
            lambda: dnn.init_inverse_server(jax.random.PRNGKey(0), cfg))
        args = (w_c, w_i, SDS((M, n, cfg.n_features), f32),
                SDS((M, n, cfg.n_classes), f32), key)
    elif kind == "sfl":
        fn = make_sfl_round(cfg, mesh, n_clients=M, samples_per_client=n,
                            E=E, unroll_steps=True)
        w_s = jax.eval_shape(lambda: dnn.init_server(jax.random.PRNGKey(0),
                                                     cfg))
        args = (w_c, w_s, SDS((M, n, cfg.n_features), f32),
                SDS((M, n), i32), key)
    else:  # inversion (Step 4)
        fn = make_distributed_inversion(cfg, mesh)
        w_i = jax.eval_shape(
            lambda: dnn.init_inverse_server(jax.random.PRNGKey(0), cfg))
        d_split = dnn.client_dims(cfg)[-1]
        args = (w_i, SDS((M, n, d_split), f32),
                SDS((M, n, cfg.n_classes), f32))
    with mesh:
        compiled = jax.jit(fn).lower(*args).compile()
    colls = parse_collectives(compiled.as_text())
    cost = compiled.cost_analysis()
    if isinstance(cost, list):          # older jaxlib: one dict per device
        cost = cost[0] if cost else {}
    return {
        "collective_bytes": float(sum(c.result_bytes for c in colls)),
        "comm_bits": collective_comm_bits(colls, quant),
        "quant": quantcomm.get_quant(quant).mode,
        "collective_s": float(sum(c.wire_seconds for c in colls)),
        "counts": {k: sum(1 for c in colls if c.kind == k)
                   for k in {c.kind for c in colls}},
        "flops": float(cost.get("flops", 0.0)),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--clients", type=int, default=512)
    ap.add_argument("--samples", type=int, default=64)
    args = ap.parse_args()
    mesh = make_production_mesh(multi_pod=args.multipod)
    mesh_name = "2x16x16" if args.multipod else "16x16"
    out = {"mesh": mesh_name, "clients": args.clients,
           "samples_per_client": args.samples}
    for kind in ("splitme", "sfl"):
        for E in (1, 10):
            t0 = time.time()
            r = lower_round(kind, mesh, args.clients, args.samples, E)
            out[f"{kind}_E{E}"] = r
            print(f"{kind} E={E}: collective_bytes="
                  f"{r['collective_bytes']:.3e} "
                  f"({r['counts']}) [{time.time() - t0:.1f}s]", flush=True)
    # quantized wire formats: same one-all-reduce structure, narrower bits
    for qm in ("bf16", "int8"):
        r = lower_round("splitme", mesh, args.clients, args.samples, 1,
                        quant=qm)
        out[f"splitme_E1_{qm}"] = r
        print(f"splitme E=1 quant={qm}: comm_bits={r['comm_bits']:.3e} "
              f"({r['counts']})", flush=True)
    # comm_bits is elems × wire_bits by construction, so the halving alone
    # would be tautological — the flag also demands the quantized lowering
    # kept the one-fused-all-reduce structure with a real payload
    out["quant_bf16_halves_comm_bits"] = bool(
        out["splitme_E1_bf16"]["counts"] == {"all-reduce": 1}
        and out["splitme_E1_int8"]["counts"] == {"all-reduce": 1}
        and out["splitme_E1_bf16"]["comm_bits"] > 0
        and abs(out["splitme_E1_bf16"]["comm_bits"]
                - 0.5 * out["splitme_E1"]["comm_bits"]) < 1e-6)
    out["inversion"] = lower_round("inversion", mesh, args.clients,
                                   args.samples, 1)
    print(f"step4 inversion: collective_bytes="
          f"{out['inversion']['collective_bytes']:.3e} "
          f"({out['inversion']['counts']})")
    # the paper's claim, as a structural assertion on the lowered HLO:
    s1 = out["splitme_E1"]["collective_bytes"]
    s10 = out["splitme_E10"]["collective_bytes"]
    v1 = out["sfl_E1"]["collective_bytes"]
    v10 = out["sfl_E10"]["collective_bytes"]
    out["splitme_bytes_constant_in_E"] = bool(abs(s10 - s1) < 0.01 * s1 + 1e3)
    out["sfl_bytes_scale_with_E"] = bool(v10 > 5 * v1 / 2)
    print(f"SplitMe bytes E1->E10: {s1:.3e} -> {s10:.3e} (constant: "
          f"{out['splitme_bytes_constant_in_E']})")
    print(f"SFL bytes     E1->E10: {v1:.3e} -> {v10:.3e} (scales: "
          f"{out['sfl_bytes_scale_with_E']})")
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / f"fl_dryrun_{mesh_name}.json").write_text(
        json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
