"""Compile the main path for a described TPU v5e — no chip needed.

The TPU compiler is installed beside the CPU backend, so it can compile for a
chip that is described rather than attached.  That catches what interpret
mode cannot: block shapes Mosaic refuses, and programs that do not fit the
chip's 16 GB.  Nothing runs here; results and times come from the chip.

The topology is described inside a module-scoped fixture (never at import):
only one process at a time may load the TPU library.
"""
import math
import re

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


_DEF = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = \w+\[([\d,]*)\]", re.M)
_INDEXED = re.compile(
    r"\b(scatter|gather)\((?:\w+\[([\d,]*)\]\S*\s+)?%?([\w.\-]+)")


def _elems(dims: str) -> int:
    return math.prod(int(d) for d in dims.split(",") if d)


def payload_index_ops(hlo: str, n_elems: int) -> list[str]:
    """The scatters and gathers in HLO text whose indexed operand holds
    ``n_elems`` elements, under any layout or relayout (``[n,S,LANES]``,
    ``[LANES,n*S]`` …).  Operand shapes are read inline where the text
    prints them, else from the operand's definition."""
    shape = {m.group(1): m.group(2) for m in _DEF.finditer(hlo)}
    found = []
    for m in _INDEXED.finditer(hlo):
        dims = m.group(2) if m.group(2) is not None else shape.get(m.group(3))
        if dims is not None and _elems(dims) == n_elems:
            found.append(m.group(0))
    return found


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


@pytest.fixture(scope="module")
def table2_run(topo):
    """The benchmark's program: the compiled Table II `run` on one chip,
    with its model and engine config."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.engine import AXIS, EngineConfig, ParsirEngine
    from repro.workloads.registry import get_workload

    model = get_workload("phold")          # PholdParams defaults: Table II
    cfg = EngineConfig(lookahead=model.params.lookahead, n_buckets=16,
                       bucket_cap=256, route_cap=8192, fallback_cap=8192,
                       route="allgather", scheduler="batch",
                       batch_impl="rounds")
    shapes = jax.eval_shape(ParsirEngine(model, cfg).init)
    mesh = Mesh(np.array(topo.devices[:1]), (AXIS,))
    eng = ParsirEngine(model, cfg, mesh=mesh)
    state = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                       sharding=eng._sharding), shapes)
    n = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P()))
    return eng._run_sm.lower(state, n).compile().as_text(), model, cfg


def test_phold_table2_run_indexes_no_payload_row(table2_run):
    # The rounds loop's handler writes PHOLD's touch window and reallocated
    # nodes as masked dense updates: the compiled Table II `run` (the
    # benchmark's program) holds no scatter or gather over the payload.
    hlo, model, _ = table2_run
    p = model.params
    nodes = p.n_objects * p.state_nodes
    assert payload_index_ops(hlo, nodes * p.lanes) == []
    # the parser does see indexed ops: the allocator's stack keeps its own.
    assert payload_index_ops(hlo, nodes)


_YIELDS = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = \(?\w+\[([\d,]*)\]"
                     r"[^=\n]*?\s(sort|gather)\(", re.M)


def slot_wide_ops(hlo: str, n_elems: int) -> list[str]:
    """The sorts and gathers in HLO text that yield ``n_elems`` elements
    (a sort's first result, under any layout)."""
    return [m.group(0).strip() for m in _YIELDS.finditer(hlo)
            if _elems(m.group(1)) == n_elems]


def test_phold_table2_route_sorts_and_gathers_no_slot_batch(table2_run):
    # Route picks the route buffer and the fallback by a prefix sum and a
    # search, so the compiled Table II `run` neither sorts the emitted
    # slots plus the fallback (270,336 at Table II) nor gathers a field at
    # every one of them; its gathers yield route_cap or fallback_cap rows.
    hlo, model, cfg = table2_run
    slots = model.params.n_objects * cfg.bucket_cap + cfg.fallback_cap
    assert slot_wide_ops(hlo, slots) == []
    # the parser does see sorts: extract's one bucket sort keeps its own.
    assert slot_wide_ops(hlo, model.params.n_objects * cfg.bucket_cap)


def test_phold_table2_extract_sorts_once_and_gathers_no_slot(table2_run):
    # Extract orders each drained bucket with one stable sort keyed
    # (ts, seed), the payload carried as an operand: the compiled Table II
    # `run` holds exactly one sort over the n_objects x bucket_cap slots
    # (262,144 at Table II) and no gather that yields one element per slot.
    hlo, model, cfg = table2_run
    ops = slot_wide_ops(hlo, model.params.n_objects * cfg.bucket_cap)
    assert [op for op in ops if op.endswith("gather(")] == []
    assert len([op for op in ops if op.endswith("sort(")]) == 1
