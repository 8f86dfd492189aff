"""The comparison that decides ``correct``.

Each simulation the driver hands over is run again by the plain reference
(``bench/oracle.py`` with the handler in ``bench/reference/<workload>.py``)
to the same horizon, and compared exactly:

- ``committed_gap``: events the program committed against the reference;
- ``in_flight_gap``: where a simulation stops at a horizon with events in
  flight, the in-flight ``(dst, seed)`` multiset there, counted as the size
  of the symmetric difference;
- ``state_gap``: object-state elements whose bits differ, over every leaf;
- ``failed``: simulations that ended with a nonzero clean-run counter.

Under the dyadic draw the program is bit-exact by contract, so every limit
is 0.
"""
from __future__ import annotations

import importlib
from collections import Counter
from dataclasses import dataclass

import numpy as np

from bench import oracle

LIMITS = {"failed": 0, "committed_gap": 0, "in_flight_gap": 0,
          "state_gap": 0}

#: Stats counters that must stay zero in a sound run (the program's
#: clean-run contract, restated here so that the check cannot move with it).
CLEAN_COUNTERS = ("cal_overflow", "fb_overflow", "route_overflow",
                  "late_events", "lookahead_violations", "oob_events")


@dataclass
class Sim:
    """One simulation as the program left it."""

    seed: int
    epochs: int                   # the horizon, in epochs, it was run to
    committed: int
    state: dict                   # object state, leading dim n_objects
    pending: np.ndarray | None = None   # (dst, seed) multiset, if it stops


def unclean(totals: dict) -> bool:
    return any(int(totals[k]) for k in CLEAN_COUNTERS)


def reference_model(workload: str, model_kw: dict):
    return importlib.import_module(f"bench.reference.{workload}").Model(
        **model_kw)


def multiset_gap(a: np.ndarray, b: np.ndarray) -> int:
    if a.shape == b.shape and np.array_equal(a, b):
        return 0
    ca, cb = Counter(map(tuple, a.tolist())), Counter(map(tuple, b.tolist()))
    return sum(((ca - cb) + (cb - ca)).values())


def state_gap(got: dict, want: dict) -> int:
    gap = 0
    for k in set(got) | set(want):
        if k not in got or k not in want:
            gap += np.size(got.get(k, want.get(k)))
            continue
        a, b = np.asarray(got[k]), np.asarray(want[k])
        if a.shape != b.shape or a.dtype != b.dtype:
            gap += max(a.size, b.size)
            continue
        if a.dtype.kind == "f":                 # bits: -0.0 and NaN count
            a, b = a.view(f"u{a.itemsize}"), b.view(f"u{b.itemsize}")
        gap += int(np.count_nonzero(a != b))
    return gap


def compare_one(sim: Sim, ref: oracle.Result) -> dict:
    out = {"committed_gap": abs(sim.committed - ref.committed),
           "state_gap": state_gap(sim.state, ref.state)}
    if sim.pending is not None:
        out["in_flight_gap"] = multiset_gap(sim.pending, ref.pending)
    return out


def compare(sims: list[Sim], model, epoch_len: float, rnd=None,
            stand_in=None) -> dict:
    """Sum the gaps of ``sims`` against the reference.  ``stand_in`` (the
    control) replaces each program simulation by another reference run, at
    the precision ``rnd`` gives it."""
    tot: dict[str, int] = {}
    for sim in sims:
        horizon = np.float32(sim.epochs) * np.float32(epoch_len)
        ref = oracle.run(model, horizon, sim.seed)
        if stand_in is not None:
            alt = oracle.run(stand_in, horizon, sim.seed, rnd=rnd)
            sim = Sim(sim.seed, sim.epochs, alt.committed, alt.state,
                      None if sim.pending is None else alt.pending)
        for k, v in compare_one(sim, ref).items():
            tot[k] = tot.get(k, 0) + v
    return tot
