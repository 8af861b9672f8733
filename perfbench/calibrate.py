"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 perfbench/calibrate.py --workload <cell> --seeds 11 12 13 ...

For each seed: the cell's data and one campaign of its mix, as a run with
that ``--seed`` makes them, then the comparison's numbers (``compare.py``)
of

* ``program``: the campaign through the cell's entry and kernel policy
  (the sound readings, the lower end of each limit);
* ``program_highest``: the same campaign under the program's reference
  policy with every matmul at HIGHEST precision: the witness that the
  program and the reference compute the same thing;
* ``reference_fp8``: the reference put in the program's place with its
  matmul inputs in float8_e4m3fn;
* ``half_cohort``: the reference put in the program's place with a
  planted fault: each round trains and averages the first half of its
  selected set only;
* ``state_unchanged``, ``answer_altered`` and, on a cell with a mesh,
  ``exchange_left_out``: the program with that fault of ``faults.py``
  planted.

One JSON line per seed and variant.  The benchmark's own runs do not run
this; it needs a TPU like they do.  ``--cpu`` reads the same on the host's
CPU instead, with the program's matmuls computed as a TPU computes them
at default precision (``tpu_default_precision``); its Pallas kernels are
off there, so these are not the chip's readings.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

import numpy as np

import run as harness

VARIANTS = ("program", "program_highest", "reference_fp8", "half_cohort",
            "state_unchanged", "answer_altered")
MESH_VARIANTS = VARIANTS + ("exchange_left_out",)


def control_dtype():
    """The control's matmul input type: a TPU runs the configurations'
    default-precision float32 matmuls as one bf16 pass, so the nearest
    precision below what the program computes in is fp8."""
    import jax.numpy as jnp
    return jnp.float8_e4m3fn


@contextlib.contextmanager
def tpu_default_precision():
    """Inside, on the CPU, a float32 matmul left at default precision (no
    ``precision`` argument and no ``jax.default_matmul_precision`` above
    default) takes its inputs rounded to bfloat16 and accumulates in
    float32, as one pass of a TPU's matrix unit does; its transposes in the
    backward pass too.  Matmuls at HIGH or HIGHEST stay float32, and so
    does a product with a vector, which the TPU's compiler turns into a
    float32 multiply and sum (the masked average over clients)."""
    import jax
    import jax.numpy as jnp
    from jax._src.lax import lax as lax_impl
    orig = lax_impl.dot_general

    def default(precision) -> bool:
        ps = precision if isinstance(precision, tuple) else (precision,)
        set_ = jax.config.jax_default_matmul_precision
        return (all(p in (None, jax.lax.Precision.DEFAULT, "default")
                    for p in ps)
                and set_ in (None, "default", "bfloat16", "fastest"))

    def dot_general(lhs, rhs, dimension_numbers, precision=None,
                    preferred_element_type=None, **kw):
        if (default(precision) and min(lhs.ndim, rhs.ndim) > 1
                and jnp.float32 in (lhs.dtype, rhs.dtype)):
            lhs, rhs = (x.astype(jnp.bfloat16) if x.dtype == jnp.float32
                        else x for x in (lhs, rhs))
            preferred_element_type = preferred_element_type or jnp.float32
        return orig(lhs, rhs, dimension_numbers, precision=precision,
                    preferred_element_type=preferred_element_type, **kw)

    lax_impl.dot_general = dot_general
    try:
        yield
    finally:
        lax_impl.dot_general = orig


def first_half(a):
    """Each round's selected set cut to its first half (at least one)."""
    out = np.zeros_like(a)
    for t, row in enumerate(a):
        sel = np.flatnonzero(row)
        out[t, sel[:max(len(sel) // 2, 1)]] = 1.0
    return out


def readings(system, seed: int, variants=VARIANTS, emulate=False):
    """The comparison's numbers of each variant on one campaign; with
    ``emulate`` the program's variants run under
    ``tpu_default_precision``."""
    import jax
    import compare
    import faults
    import reference
    mix = system.mix
    seeds = system.next_seeds()
    kw = dict(rounds=mix["rounds"], seeds=seeds)
    ref = reference.run_campaign(system.kind, system.config, system.clients,
                                 system.test, **kw)
    chip = tpu_default_precision if emulate else contextlib.nullcontext
    out = {}
    for v in variants:
        if v == "program":
            with chip():
                got = system.host_view(system.run(seeds))
        elif v == "program_highest":
            with jax.default_matmul_precision("highest"):
                got = system.host_view(system.run(seeds, policy="reference"))
        elif v in ("state_unchanged", "answer_altered", "exchange_left_out"):
            with faults.planted(v), chip():
                got = system.host_view(system.run(seeds))
        elif v == "half_cohort":
            got = reference.run_campaign(
                system.kind, system.config, system.clients, system.test,
                **kw, train_mask=first_half)
        else:
            got = reference.run_campaign(
                system.kind, system.config, system.clients, system.test,
                **kw, compute_dtype=control_dtype())
        out[v] = compare.readings(got, ref, reference.accuracy(
            system.kind, system.config, system.clients, system.test,
            got["params"]))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+", default=None,
                    help="default: every variant the cell can have")
    ap.add_argument("--cpu", action="store_true",
                    help="read on the CPU, the program's matmuls as a "
                         "TPU's default precision computes them")
    args = ap.parse_args(argv)
    harness.configure_jax()
    c = harness.load_cell(harness.ROOT, args.workload)
    variants = args.variants or (MESH_VARIANTS if c["config"].get("mesh")
                                 else VARIANTS)
    if args.cpu:
        import jax
        devices = jax.devices("cpu")
    else:
        try:
            devices = harness.chips_for(c["cell"])
        except harness.NoChip as e:
            print(f"calibrate: {e}", file=sys.stderr)
            return 1
    for seed in args.seeds:
        system = harness.System(harness.ROOT, c, seed, devices)
        for v, r in readings(system, seed, variants,
                             emulate=args.cpu).items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "variant": v, "device": devices[0].platform,
                              **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    sys.exit(main())
