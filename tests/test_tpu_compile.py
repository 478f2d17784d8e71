"""The main path compiled for a described TPU v5e, without the chip.

Full ``configs/mosso_stream.py`` capacities, the layout the chip smoke runs
(four shards stacked on one chip, vmapped replicas, donated state): the
TPU compiler must accept each program, and each must fit one chip's
16 GB.  The topology is described inside a fixture (never at import), so
every test worker collects the same tests and only the one that runs this
file loads the TPU compiler.
"""
import pytest

import jax
import jax.numpy as jnp
import numpy as np

HBM_BYTES = 16e9        # one v5e chip
N_SHARDS = 4            # shards stacked on one chip, as chip_smoke.py runs


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip_mesh(topo):
    from jax.sharding import Mesh
    return Mesh(np.asarray(topo.devices[:1]), ("shard",))


@pytest.fixture(scope="module")
def cfg():
    from repro.configs.mosso_stream import full_config
    return full_config()


def _fits(compiled) -> int:
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert total <= HBM_BYTES, f"{total} bytes > one chip"
    return total


def _stacked(tree, mesh, n):
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = NamedSharding(mesh, P(mesh.axis_names[0]))
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((n,) + l.shape, l.dtype, sharding=sh),
        tree)


def _states(cfg, mesh):
    from repro.core.engine.state import new_state
    from repro.dist.router import intern_new
    return (_stacked(jax.eval_shape(lambda: new_state(cfg)), mesh, N_SHARDS),
            _stacked(jax.eval_shape(lambda: intern_new(cfg)), mesh, N_SHARDS))


def test_single_engine_step_compiles(topo, cfg):
    from jax.sharding import SingleDeviceSharding

    from repro.core.engine.state import new_state
    from repro.core.engine.trial import make_step

    one = SingleDeviceSharding(topo.devices[0])
    st = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one),
        jax.eval_shape(lambda: new_state(cfg)))
    b = cfg.batch
    i32 = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one)
    ins = jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=one)
    step = make_step(cfg, dense=True, trial_backend="xla")
    assert _fits(step.lower(st, i32, i32, ins).compile()) > 1 << 30


def test_route_and_engine_stages_compile(one_chip_mesh, cfg):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.dist import router as R

    mesh, chunk = one_chip_mesh, 1024
    route, geom = R.make_route_step(
        mesh, N_SHARDS, chunk,
        R.default_lane_cap(chunk, 1, N_SHARDS, cfg.batch))
    sh = NamedSharding(mesh, P("shard"))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sh)

    _fits(route.lower(*(i32(chunk),) * 5).compile())
    # the replica layout the router picks on a TPU (the test process
    # itself runs on the CPU backend, whose default is "map")
    engine = R.make_engine_step(cfg, mesh, N_SHARDS, geom.acc_cap,
                                replica_exec="vmap", trial_backend="xla")
    est, ist = _states(cfg, mesh)
    # telemetry: one row per device, (drain rounds, engine rounds)
    args = ((est, ist, i32(1, 2)) + (i32(N_SHARDS, geom.acc_cap),) * 5
            + (i32(N_SHARDS), i32(1)))
    compiled = engine.lower(*args).compile()
    _fits(compiled)
    # the stacked states are donated: updated in place, not doubled
    assert compiled.memory_analysis().alias_size_in_bytes >= 5 * (1 << 30)


def test_sharded_query_kernels_compile(one_chip_mesh, cfg):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.serve.query import make_sharded_query_kernels

    mesh = one_chip_mesh
    k = make_sharded_query_kernels(cfg, mesh, trial_backend="xla")
    est, ist = _states(cfg, mesh)
    q = jax.ShapeDtypeStruct((64,), jnp.int32,
                             sharding=NamedSharding(mesh, P()))
    for fn, n_q in ((k.neighbors, 2), (k.degree, 2), (k.has_edge, 4)):
        _fits(fn.lower(est, ist, *(q,) * n_q).compile())
