"""Window drivers, one module per kind, found by the ``driver`` of a traffic
file.  Each module defines ``Driver(eng, traffic, seeds)`` with:

- ``warm()``: set-up.  Builds the first state and runs every program the
  window dispatches once, at a zero bound, so that nothing compiles later;
- ``window(seconds) -> dict``: the measured window.  Returns ``window_s``
  (host clock, from the first dispatch to the end of the last), ``attempted``
  and ``failed`` simulations, and the counts the metrics read;
- ``sims(rec) -> list[check.Sim]``: after the window, the simulations to
  compare, read back; any simulation it runs for the comparison is counted
  into ``rec``'s ``attempted`` and ``failed``.

The helpers below read the program's state the way a user of
``ParsirEngine`` would.
"""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from bench.oracle import sorted_records


def object_state(eng, obj: dict, bounds) -> dict:
    """Object state in global id order, from a (single-simulation) ``obj``
    pytree and its placement ``bounds``."""
    return eng.global_object_state(SimpleNamespace(obj=obj, bounds=bounds))


def pending(eng, state) -> np.ndarray:
    """The sorted (dst, seed) multiset parked in calendar and fallback."""
    cnt = np.asarray(state.cal.cnt)                       # [rows, buckets]
    seed = np.asarray(state.cal.seed)                     # [rows, buckets, cap]
    gid, _ = eng.global_row_of(state)
    live = np.arange(seed.shape[2])[None, None, :] < cnt[:, :, None]
    dst = np.broadcast_to(gid[:, None, None], live.shape)[live]
    fbv = np.asarray(state.fb.events.valid)
    return sorted_records(
        np.concatenate([dst, np.asarray(state.fb.events.dst)[fbv]]),
        np.concatenate([seed[live], np.asarray(state.fb.events.seed)[fbv]]))
