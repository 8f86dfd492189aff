"""Whole benchmark runs at a tiny size on the CPU: the harness's look for a
chip is skipped and everything else runs as on the chip.  A sound program
comes out ``correct``; with the timed path broken underneath, ``correct``
comes out false, once for each fault a one-chip cell can have:

- a step that returns its state unchanged;
- half of the events left out (those with an odd seed do nothing);
- an answer altered where it is produced (one object-state element).

The exchange between chips cannot be left out of a one-chip cell.
"""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.harness import Bench, plan  # noqa: E402

TINY = dict(n_objects=16, initial_events=4, state_nodes=64,
            realloc_fraction=0.02)
CELL = "phold-t2.uniform"
SECONDS = 0.3


def tiny_plan() -> dict:
    p = plan(CELL)
    p["config"]["model_kw"].update(TINY)
    p["config"]["engine_kw"].update(n_buckets=8, bucket_cap=64,
                                    route_cap=512, fallback_cap=512)
    p["traffic"].update(horizon_epochs=8)
    return p


_benches: list = []


def bench() -> Bench:
    if not _benches:
        b = Bench(CELL, require_chip=False, plan_=tiny_plan())
        b.setup()
        _benches.append(b)
    return _benches[0]


def unchanged(eng, monkeypatch):
    monkeypatch.setattr(eng, "run", lambda st, n: st)


def altered(eng, monkeypatch):
    orig = eng.run

    def run(st, n):
        st = orig(st, n)
        k = next(k for k, v in st.obj.items() if v.dtype.kind == "f")
        leaf = st.obj[k]
        return st._replace(obj={**st.obj,
                                k: leaf.at[(0,) * leaf.ndim].add(1.0)})
    monkeypatch.setattr(eng, "run", run)


def test_sound_run_is_correct():
    line = bench().measure(11, SECONDS, trace=False)
    assert line["correct"], line
    assert line["compared"] == 1 and line["attempted"] >= 1
    assert list(line)[-1] == "checks"
    assert all(v["value"] == 0 for v in line["checks"].values())


def test_each_run_simulates_a_seed_drawn_from_its_own():
    """The window's first simulation is drawn from ``--seed``: the same seed
    gives the same simulation, another seed another."""
    from bench.drivers.steady import Driver
    from bench.harness import simulation_seeds

    b = bench()
    seen = []
    for seed in (21, 22, 21):
        drv = Driver(b.eng, b.p["traffic"], simulation_seeds(seed))
        drv.warm()
        seen.append(drv.seed)
    assert seen[0] == seen[2] != seen[1]
    assert seen[0] == next(simulation_seeds(21))


@pytest.mark.parametrize("fault", [unchanged, altered])
def test_broken_dispatch_is_not_correct(fault, monkeypatch):
    b = bench()
    fault(b.eng, monkeypatch)
    line = b.measure(12, SECONDS, trace=False)
    assert not line["correct"], line
    assert any(v["value"] > v["limit"] for v in line["checks"].values())


def test_half_the_events_left_out_is_not_correct(monkeypatch):
    import jax
    import jax.numpy as jnp

    from repro.phold.model import Phold

    orig = Phold.process_event

    def process_event(self, state, ts, seed, payload):
        new, out = orig(self, state, ts, seed, payload)
        keep = (seed.astype(jnp.uint32) & jnp.uint32(1)) == 0
        new = jax.tree.map(lambda a, b: jnp.where(keep, a, b), new, state)
        return new, out._replace(valid=out.valid & keep)
    monkeypatch.setattr(Phold, "process_event", process_event)

    b = Bench(CELL, require_chip=False, plan_=tiny_plan())
    b.setup()
    line = b.measure(13, SECONDS, trace=False)
    assert not line["correct"], line
    assert line["checks"]["committed_gap"]["value"] > 0
