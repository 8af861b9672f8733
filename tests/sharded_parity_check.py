"""Sharded-engine parity checker: the shard_map round must reproduce the
single-device engine round at 1e-5 for all four frameworks.

Used two ways by tests/test_engine_parity.py:
  * imported and run on a 1-device host mesh in-process;
  * executed as a script in a subprocess with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` for a real
    multi-device mesh (cross-shard psum reassociation included).
"""
import jax
import jax.numpy as jnp
import numpy as np

ATOL = 1e-5


def run_check(data_shards: int) -> None:
    from repro.configs.splitme_dnn import DNN10
    from repro.core import engine
    from repro.launch.mesh import make_client_mesh

    if jax.device_count() < data_shards:
        raise RuntimeError(f"need {data_shards} devices, "
                           f"have {jax.device_count()}")
    mesh = make_client_mesh(data_shards)
    rng = np.random.default_rng(0)
    M, n, e_max, e_steps = 8, 16, 4, 3
    x = jnp.asarray(rng.normal(size=(M, n, DNN10.n_features)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 3, (M, n)), jnp.int32)
    a = jnp.asarray(rng.integers(0, 2, M).astype(np.float32))
    a = a.at[0].set(1.0)                      # non-empty selection
    key = jax.random.PRNGKey(7)

    for name in engine.framework_names():
        spec = engine.make_spec(name, DNN10)
        params = spec.init_fn(jax.random.PRNGKey(3))
        qs = engine.init_quant_state(spec, params)     # () for quant=none
        single = engine.build_round_fn(spec, DNN10, e_max=e_max,
                                       donate=False)
        p1, l1, _ = single(params, x, y, a, jnp.asarray(e_steps), key, qs)
        sharded = engine.build_round_fn(spec, DNN10, e_max=e_max, mesh=mesh,
                                        donate=False)
        p2, l2, _ = sharded(params, x, y, a, jnp.asarray(e_steps), key, qs)
        for g, h in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(h),
                                       atol=ATOL, rtol=0,
                                       err_msg=f"{name}: params diverge")
        for g, h in zip(l1, l2):
            assert abs(float(g) - float(h)) < ATOL, \
                f"{name}: losses diverge ({float(g)} vs {float(h)})"
        print(f"{name}: sharded round matches single-device at {ATOL}")


if __name__ == "__main__":
    import sys
    shards = int(sys.argv[1]) if len(sys.argv) > 1 else jax.device_count()
    run_check(shards)
    print("PARITY_OK")
