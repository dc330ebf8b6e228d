"""Compile-only checks for a described TPU v5e (no chip attached).

The TPU compiler refuses what interpret mode accepts: block shapes off the
(8, 128) tiling, rank-1 blocks, a lane block wider than the array.  These
tests compile the main path's kernels and the batched client step for one
chip of a described ``v5e:2x2``, checking the Pallas kernels are in the
program (``tpu_custom_call``), and the client step sharded over its four
chips.  Nothing runs; a compile that passes is not
a chip run.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ensemble_kl import (ensemble_kl, ensemble_kl_bank,
                                       ensemble_kl_pre)

B = 128          # distillation batch of the main path
POOL = 4000      # logit-bank rows
K = 8            # stacked teachers (8 active clients)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


# C=4: the token task's classes; C=64: the class count of the distill
# benches; C=512: a vocabulary-wide row at the paper-scale width
@pytest.mark.parametrize("c", [4, 64, 512])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
def test_ensemble_kl_bank_fwd_bwd_compiles(one_chip, c, dtype):
    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    step = jax.value_and_grad(
        lambda s, rows, sc, idx: ensemble_kl_bank(s, rows, sc, idx, 2.0,
                                                  False))
    text = _compile_text(step, sds((B, c)), sds((POOL, c), dtype),
                         sds((B,)), sds((B,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("c", [64, 512])
@pytest.mark.parametrize("kernel", ["ensemble_kl", "ensemble_kl_pre"])
def test_ensemble_kl_teacher_kernels_fwd_bwd_compile(one_chip, kernel, c):
    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    if kernel == "ensemble_kl":
        fn, teachers = ensemble_kl, sds((K, B, c))
    else:
        fn, teachers = ensemble_kl_pre, sds((B, c))
    step = jax.value_and_grad(lambda s, t: fn(s, t, 1.0, 8, False))
    assert "tpu_custom_call" in _compile_text(step, sds((B, c)), teachers)


def _client_step_args(net, replicated, per_client):
    """Shapes of the batched client update's arguments for K clients of
    ``net``, placed by the two shardings."""
    seq, n_steps, bsz = 128, 8, 32

    def place(sharding):
        return lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                              sharding=sharding)

    params = jax.tree.map(place(replicated),
                          jax.eval_shape(net.init, jax.random.PRNGKey(0)))
    stacked = [jax.ShapeDtypeStruct(shape, dt) for shape, dt in (
        ((K, n_steps, bsz, seq), jnp.int32), ((K, n_steps, bsz), jnp.int32),
        ((K, n_steps), jnp.bool_), ((K, 2), jnp.uint32))]
    xb, yb, mask, keys = map(place(per_client), stacked)
    return params, xb, yb, params, mask, keys


def _smoke_net():
    """chip_smoke.py's model: the paper-scale transformer width."""
    from repro.configs.feddf_paper import CONFIG as paper
    from repro.core.nets import tiny_transformer
    return tiny_transformer(paper.vocab_size, 4, 128, d_model=paper.d_model,
                            n_layers=paper.n_layers, n_heads=paper.n_heads)


def test_batched_client_step_of_smoke_model_compiles(one_chip):
    """The vmapped local update of the smoke model for 8 clients, Adam, as
    the round engine builds it, on one chip."""
    from repro.core.client import make_batched_local_update
    from repro.optim.optimizers import adam

    net = _smoke_net()
    update = make_batched_local_update(net, adam(1e-3))
    compiled = update.lower(*_client_step_args(net, one_chip,
                                               one_chip)).compile()
    assert compiled.memory_analysis() is not None


def test_sharded_client_step_compiles_on_4_chip_mesh(topo):
    """The same update with the client axis sharded over the four chips
    (``ShardingSpec(shard_clients=True)``): 2 clients per chip."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.core.client import make_batched_local_update
    from repro.optim.optimizers import adam

    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",),
                axis_types=(AxisType.Auto,))
    net = _smoke_net()
    update = make_batched_local_update(net, adam(1e-3), mesh=mesh)
    compiled = update.lower(*_client_step_args(
        net, NamedSharding(mesh, P()), NamedSharding(mesh, P("data")))
    ).compile()
    # every stacked client parameter comes back split over the 4 chips
    for sharding in jax.tree.leaves(compiled.output_shardings):
        assert len(sharding.device_set) == 4
        assert not sharding.is_fully_replicated


def test_fused_distill_chunk_compiles_on_4_chip_mesh(topo, monkeypatch):
    """A client-sharded round hands fusion inputs that span the four chips;
    the distillation chunk with the compiled bank kernel must still
    compile (Mosaic kernels cannot be partitioned automatically)."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.core.feddf import FusionConfig, _build_chunk, _make_distill_opt
    from repro.data.distill_sources import UnlabeledDataset
    from repro.kernels import ops

    # the described chip is not the process's backend: steer the ops
    # wrappers to the compiled kernels, as on a TPU
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",),
                axis_types=(AxisType.Auto,))
    net = _smoke_net()
    fusion = FusionConfig(max_steps=100, eval_every=100, batch_size=B,
                          logit_bank="on")
    source = UnlabeledDataset(np.zeros((POOL, 128), np.int32))
    chunk = _build_chunk(net, source, fusion, True, False, mode="bank",
                         mesh=mesh)

    rep = NamedSharding(mesh, P())

    def place(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep)

    params = jax.eval_shape(net.init, jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(_make_distill_opt(fusion).init, params)
    args = jax.tree.map(place, (
        params, opt_state, jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((POOL, 128), jnp.int32),
        jax.ShapeDtypeStruct((POOL, 4), jnp.int8),
        jax.ShapeDtypeStruct((POOL,), jnp.float32)))
    text = chunk.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
