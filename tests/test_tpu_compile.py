"""Compile the main path for a described TPU v5e — no chip needed.

The TPU compiler is installed beside the CPU backend, so it can compile for a
chip that is described rather than attached.  That catches what interpret
mode cannot: block shapes Mosaic refuses, and programs that do not fit the
chip's 16 GB.  Nothing runs here; results and times come from the chip.

The topology is described inside a module-scoped fixture (never at import):
only one process at a time may load the TPU library.
"""
import numpy as np
import pytest

V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    # a compile for a described chip is written to the cache but cannot be
    # read back without one; keep the cache out of these tests entirely.
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _fits(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < used < V5E_HBM_BYTES, used


# n objects, S nodes, C event slots: PHOLD Table II widths, and a narrow one.
@pytest.mark.parametrize("n,S,C", [(1024, 4000, 256), (64, 256, 128)])
def test_event_apply_compiles_for_v5e(one_chip, n, S, C):
    import jax
    import jax.numpy as jnp

    from repro.kernels.event_apply import build_event_apply

    LANES = 6
    K, KR = max(1, S // 32), max(1, int(np.ceil(0.001 * S)))
    call = build_event_apply(S=S, LANES=LANES, C=C, K=K, KR=KR,
                             n_objects=1024, lookahead=0.5, dist="dyadic",
                             mean=1.0, interpret=False, hot_objects=32,
                             hot_prob=96)
    arg = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = jax.jit(call).lower(
        arg((n, LANES, S), jnp.float32), arg((n, S), jnp.int32),
        arg((n,), jnp.int32), arg((n, C), jnp.float32),
        arg((n, C), jnp.uint32), arg((n,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()   # Mosaic, not interpret
    _fits(compiled)


def test_phold_table2_drain_compiles_for_v5e(topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.engine import AXIS, EngineConfig, ParsirEngine
    from repro.workloads.registry import get_workload

    model = get_workload("phold")          # PholdParams defaults: Table II
    cfg = EngineConfig(lookahead=model.params.lookahead, n_buckets=16,
                       bucket_cap=256, route_cap=8192, fallback_cap=8192)
    shapes = jax.eval_shape(ParsirEngine(model, cfg).init)
    mesh = Mesh(np.array(topo.devices[:1]), (AXIS,))
    eng = ParsirEngine(model, cfg, mesh=mesh)
    state = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                       sharding=eng._sharding), shapes)
    bound = jax.ShapeDtypeStruct((), jnp.int32,
                                 sharding=NamedSharding(mesh, P()))
    _fits(eng._drain_sm.lower(state, bound).compile())
