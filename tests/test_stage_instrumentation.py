"""The epoch step's instrumentation: stage scopes and schedule counters.

* every stage of the conservative and the speculative step runs under its
  ``parsir.*`` name scope (:mod:`repro.core.pipeline.names`), which reaches
  the lowered program's op metadata — what a profiler trace names ops by;
* the scopes change no op of the program, and the round counter is carried
  only under ``count_rounds``: off, the state holds no counter at all;
* each scheduler counts the ``rounds`` it ran, and the ``lanes`` they ran
  follow from its static round width, checked against a count by hand from
  the bucket counts of every epoch;
* the counter is an activity meter: not a clean-run counter, and bounded by
  the engine's fail-fast overflow check.
"""
import math
import os
import re
import subprocess
import sys
import textwrap

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EngineConfig, ParsirEngine, Stats
from repro.core.pipeline import names
from repro.core.pipeline.base import stats_dtype
from repro.testing import CLEAN_COUNTERS
from repro.workloads.registry import get_workload

KW = dict(lookahead=0.5, n_buckets=8, bucket_cap=64, route_cap=512,
          fallback_cap=512)
SCOPE = re.compile(r"parsir\.[a-z]+")


def _tiny_phold():
    return get_workload("phold", n_objects=16, initial_events=4,
                        state_nodes=64, realloc_fraction=0.02,
                        lookahead=0.5, dist="dyadic")


def _scopes(eng) -> set[str]:
    low = eng._run_sm.lower(eng.init(), jnp.int32(1))
    return set(SCOPE.findall(low.as_text(debug_info=True)))


# ---------------------------------------------------------------------------
# stage scopes
# ---------------------------------------------------------------------------

# at D=1 the exchange is the identity (no op to carry a scope); the
# two-device test below covers it.
LOCAL = {names.EXTRACT, names.PROCESS, names.ROUTE, names.DELIVER}


@pytest.mark.parametrize("extra,want", [
    ({}, LOCAL),
    ({"placement": "adaptive", "rebalance_every": 4},
     LOCAL | {names.REBALANCE}),
    ({"opt_window": 2},
     LOCAL | {names.SHADOW, names.VERDICT, names.COMMIT, names.RESTORE}),
], ids=["conservative", "adaptive", "speculative"])
def test_step_stages_reach_op_metadata(extra, want):
    eng = ParsirEngine(_tiny_phold(), EngineConfig(**KW, **extra))
    got = _scopes(eng)
    assert want <= got, sorted(want - got)
    assert got <= set(names.STAGE_SCOPES), sorted(got)


_TWO_DEVICES = textwrap.dedent("""
    import re
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core.engine import AXIS, EngineConfig, ParsirEngine
    from repro.workloads.registry import get_workload

    assert len(jax.devices()) == 2, jax.devices()
    mesh = Mesh(np.array(jax.devices()), (AXIS,))
    model = get_workload("phold", n_objects=16, initial_events=4,
                         state_nodes=64, realloc_fraction=0.02,
                         lookahead=0.5, dist="dyadic")
    kw = dict(lookahead=0.5, n_buckets=8, bucket_cap=64, route_cap=512,
              fallback_cap=512)
    for extra in ({"route": "a2a"},
                  {"placement": "adaptive", "rebalance_every": 4},
                  {"opt_window": 2, "steal": True, "opt_commit": "global"}):
        eng = ParsirEngine(model, EngineConfig(**kw, **extra), mesh=mesh)
        low = eng._run_sm.lower(eng.init(), jnp.int32(1))
        got = sorted(set(re.findall(r"parsir\\.[a-z]+",
                                    low.as_text(debug_info=True))))
        print(sorted(extra), " ".join(got))
""")


def test_every_stage_scope_lowers_across_two_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", _TWO_DEVICES], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    lines = r.stdout.strip().splitlines()
    assert len(lines) == 3, r.stdout
    seen = [set(SCOPE.findall(ln)) for ln in lines]
    # the collective itself is scoped, in the conservative and the
    # speculative step alike, and every name is one the steps declare.
    assert all(names.EXCHANGE in s for s in seen), lines
    assert set().union(*seen) == set(names.STAGE_SCOPES), lines


class _NoScope(contextlib.ContextDecorator):
    """A scope that opens nothing, as a ``with`` block or a decorator."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("extra", [{}, {"opt_window": 2}],
                         ids=["conservative", "speculative"])
def test_scopes_change_no_op(extra, monkeypatch):
    # the same program with every named scope a no-op: identical ops, the
    # scopes live only in the locations (op metadata) the text leaves out.
    model, cfg = _tiny_phold(), EngineConfig(**KW, **extra)

    def lowered():
        eng = ParsirEngine(model, cfg)
        return eng._run_sm.lower(eng.init(), jnp.int32(1)).as_text()

    scoped = lowered()
    monkeypatch.setattr(jax, "named_scope", lambda name: _NoScope())
    assert lowered() == scoped


def test_engine_keys_the_compile_cache_on_its_scopes():
    # two builds that differ only in their scopes compile to one program;
    # a persistent cache must still keep them apart, or a trace read from
    # a cached executable names another build's stages.
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    try:
        jax.config.update(flag, False)
        ParsirEngine(_tiny_phold(), EngineConfig(**KW))
        assert getattr(jax.config, flag) is True
    finally:
        jax.config.update(flag, before)


# ---------------------------------------------------------------------------
# schedule counters
# ---------------------------------------------------------------------------

def test_round_counter_is_carried_only_when_asked():
    model = _tiny_phold()
    off = ParsirEngine(model, EngineConfig(**KW))
    on = ParsirEngine(model, EngineConfig(count_rounds=True, **KW))
    st_off, st_on = off.run(off.init(), 4), on.run(on.init(), 4)
    # off, the epoch loop carries one leaf fewer: no counter, no add
    assert st_off.stats.rounds is None
    assert len(jax.tree.leaves(st_off.stats)) == len(Stats._fields) - 1
    assert "rounds" not in off.totals(st_off)
    assert "lanes" not in off.totals(st_off)
    # counting observes the run, it never changes it
    t_on = on.totals(st_on)
    assert t_on["rounds"] > 0
    assert {k: v for k, v in t_on.items()
            if k not in ("rounds", "lanes")} == off.totals(st_off)
    for a, b in zip(jax.tree.leaves(st_off.obj), jax.tree.leaves(st_on.obj)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _by_hand(impl: str, cnt: np.ndarray, tile: int) -> tuple[int, int]:
    """(rounds, lanes) of one epoch from its per-row bucket counts."""
    rows, depth = len(cnt), int(cnt.max(initial=0))
    if impl == "rounds":
        return depth, depth * rows
    if impl == "packed":
        t = min(tile, rows)
        tiles = sum(math.ceil(int((cnt > r).sum()) / t) for r in range(depth))
        return tiles, tiles * t
    if impl == "ltf":
        return int(cnt.sum()), int(cnt.sum())
    # the event-apply kernel: one grid step per 8 objects; its rounds have
    # no fixed width, so it reports no lanes.
    return math.ceil(rows / 8), None


@pytest.mark.parametrize("impl", ["rounds", "packed", "ltf", "model"])
def test_scheduler_counts_rounds_and_lanes_by_hand(impl):
    tile = 4
    kw = dict(KW, count_rounds=True)
    cfg = (EngineConfig(scheduler="ltf", **kw) if impl == "ltf" else
           EngineConfig(batch_impl=impl, pack_tile=tile, **kw))
    eng = ParsirEngine(_tiny_phold(), cfg)
    st = eng.init()
    want_r, want_l = 0, 0
    depth_sum = 0
    for e in range(6):
        cnt = np.asarray(st.cal.cnt)[:, e % KW["n_buckets"]]
        r, l = _by_hand(impl, cnt, tile)
        want_r += r
        want_l = None if l is None else want_l + l
        depth_sum += int(cnt.max(initial=0))
        st = eng.run(st, 1)
    tot = eng.totals(st)
    assert (tot["rounds"], tot.get("lanes")) == (want_r, want_l)
    processed = tot["processed"]
    assert processed > 0
    if impl == "rounds":
        assert tot["lanes"] == tot["rounds"] * eng.placement.n_local_max
        assert tot["lanes"] >= processed
    elif impl == "packed":
        # within one tile per round of the events present
        assert processed <= tot["lanes"] < processed + depth_sum * tile
    elif impl == "ltf":
        assert tot["lanes"] == processed


@pytest.mark.parametrize("inject", [0, 2], ids=["committed", "rolled-back"])
def test_speculation_counts_the_work_it_executed(inject):
    # a committed window runs each epoch once, as the conservative step
    # does; a rolled-back window's work was executed too and is counted
    # again when the epochs re-run.
    model = _tiny_phold()
    a = ParsirEngine(model, EngineConfig(count_rounds=True, **KW))
    b = ParsirEngine(model, EngineConfig(opt_window=2, count_rounds=True,
                                         inject_straggler_every=inject, **KW))
    ta, tb = a.totals(a.run(a.init(), 12)), b.totals(b.run(b.init(), 12))
    assert ta["processed"] == tb["processed"] > 0
    if inject:
        assert tb["rollbacks"] > 0
        assert tb["rounds"] > ta["rounds"] and tb["lanes"] > ta["lanes"]
    else:
        assert tb["rollbacks"] == 0
        assert (tb["rounds"], tb["lanes"]) == (ta["rounds"], ta["lanes"])


def test_round_counter_is_a_bounded_activity_meter():
    assert "rounds" not in CLEAN_COUNTERS
    model = _tiny_phold()
    # a speculative window's re-execution can run more rounds than the
    # bucket holds: the fail-fast bound counts them when they are counted,
    # so a horizon safe for the conservative step can be refused here.
    dense = ParsirEngine(model, EngineConfig(count_rounds=True, **KW))
    spec = ParsirEngine(model, EngineConfig(opt_window=3, **KW))
    spec_counted = ParsirEngine(model, EngineConfig(opt_window=3,
                                                    count_rounds=True, **KW))
    cap = int(jnp.iinfo(stats_dtype()).max)
    n = cap // (dense.placement.n_local_max * KW["bucket_cap"])
    dense.check_stats_bound(n)
    spec.check_stats_bound(n)
    with pytest.raises(ValueError, match="overflow"):
        spec_counted.check_stats_bound(n)


def test_simulate_profile_writes_a_trace(tmp_path):
    # the operator's view of the stage scopes: a profiler trace of the run,
    # with the scheduler's rounds and lanes counted
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.simulate", "--workload", "phold",
         "--objects", "16", "--epochs", "4", "--dist", "dyadic",
         "--profile", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "'rounds'" in r.stdout and "'lanes'" in r.stdout
    assert list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
