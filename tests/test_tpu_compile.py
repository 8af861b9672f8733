"""Ahead-of-time compiles of the main path's Pallas kernels for a TPU v5e
that is described, not attached: the TPU compiler refuses here what the
Pallas interpreter accepts (block shapes off the tiling, layouts Mosaic
cannot match, kernels outside a shard_map on a multi-chip program), so
these guard the chip path at no chip time.

Shapes are the engine's real ones for the paper's DNN10 at full width:
the SplitMe phase losses vmap the KL kernel over a 50-client cohort of
32-row batches of the 256-wide split layer, and the fused Step-4
inversion builds its Grams over 50 x 96 rows, padded by the wrapper to
(5120, 384) x (5120, 384 | 128), once per seed.

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library, and every test worker
imports this module.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels.kl_mutual.kl_mutual import kl_rows_pallas
from repro.kernels.ridge_gram.ridge_gram import gram_pallas


@pytest.fixture(scope="module")
def v5e_2x2():
    """The four devices of a described v5e:2x2 host."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without the chip
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topo.devices
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n", [32, 512])
def test_kl_rows_compiles_vmapped_over_clients(v5e_2x2, n, dtype):
    one_chip = SingleDeviceSharding(v5e_2x2[0])
    bq = min(256, -(-n // 16) * 16)           # the ops.kl_loss block rule
    s = jax.ShapeDtypeStruct((50, n, 256), dtype, sharding=one_chip)
    text = _compiled_text(
        jax.vmap(lambda x, y: kl_rows_pallas(x, y, bq=bq)), s, s)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("d2", [384, 128])
@pytest.mark.parametrize("seeds", [None, 2])
def test_gram_compiles_at_inversion_shapes(v5e_2x2, seeds, d2):
    one_chip = SingleDeviceSharding(v5e_2x2[0])
    lead = () if seeds is None else (seeds,)
    x = jax.ShapeDtypeStruct(lead + (5120, 384), jnp.float32,
                             sharding=one_chip)
    y = jax.ShapeDtypeStruct(lead + (5120, d2), jnp.float32,
                             sharding=one_chip)
    fn = gram_pallas if seeds is None else jax.vmap(gram_pallas)
    assert "tpu_custom_call" in _compiled_text(fn, x, y)


def test_mesh_splitme_eval_compiles_with_gram_kernel(v5e_2x2):
    """The fused SplitMe eval of a campaign whose 48 clients shard over a
    (data=4, model=1) mesh, vmapped over 2 seeds: its Step-4 Grams run the
    Pallas kernel per shard inside a shard_map and psum across chips."""
    from repro.configs.splitme_dnn import DNN10
    from repro.core import engine
    from repro.kernels.dispatch import KernelPolicy
    from repro.launch import campaign

    mesh = Mesh(np.array(v5e_2x2).reshape(4, 1), ("data", "model"))
    clients, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    spec = engine.make_spec("splitme", DNN10, masked_loss_metric=True,
                            policy=KernelPolicy(kl_mutual=True,
                                                ridge_gram=True))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
        jax.eval_shape(jax.vmap(spec.init_fn),
                       jax.ShapeDtypeStruct((2, 2), jnp.uint32)))
    data = {"x": jax.ShapeDtypeStruct((48, 96, 30), jnp.float32,
                                      sharding=clients),
            "y": jax.ShapeDtypeStruct((48, 96), jnp.int32, sharding=clients),
            "x_test": jax.ShapeDtypeStruct((1200, 30), jnp.float32,
                                           sharding=rep),
            "y_test": jax.ShapeDtypeStruct((1200,), jnp.int32, sharding=rep)}
    text = _compiled_text(
        lambda p, d: jax.vmap(campaign._fused_eval(spec, DNN10, d, 1e-3,
                                                   mesh))(p),
        params, data)
    assert "tpu_custom_call" in text and "all-reduce" in text
