"""The "clean run" contract, in one place (DESP-C++-style validation).

A conservative engine must never silently drop or reorder an event; every
such condition is *counted* in ``Stats``.  The flip side of that contract is
the driver's duty to actually look: a run with nonzero ``fb_overflow`` has
*dropped events* (the fallback spill is truncated after being counted), a
nonzero ``oob_events`` means emissions vanished outside the object space,
and a wall-clock or events/s number from such a run is meaningless.  Both
shipped drivers historically checked only a subset of the counters —
``launch/simulate.py`` ignored ``fb_overflow``/``oob_events`` and
``benchmarks/pdes_perf`` ignored ``fb_overflow``/``route_overflow`` — which
is exactly the bug this module retires: one checker, used by the drivers,
the conformance harness and the tests alike.

Deliberately dependency-free (works on any mapping of counter name → int,
e.g. ``ParsirEngine.totals()`` output or a decoded bench JSON), so the
stdlib-only contexts (CI docs job imports :mod:`repro.testing`; the bench
parent process has no ``src`` on its path) stay importable.
"""
from __future__ import annotations

from typing import Mapping

#: every Stats counter that must be zero after a healthy run.  ``processed``
#: / ``stolen`` / ``rebalances`` / ``migrated`` / ``rounds`` are activity
#: meters, not error counters, and are deliberately absent.
CLEAN_COUNTERS: tuple[str, ...] = (
    "cal_overflow",          # calendar bucket capacity exceeded
    "fb_overflow",           # fallback spill — events counted then DROPPED
    "route_overflow",        # route buffer misses (events recirculate)
    "late_events",           # causality violations (already-closed epoch)
    "lookahead_violations",  # model emitted ts < ts_in + L
    "oob_events",            # dst outside [0, n_objects) — events dropped
)


def unclean_counters(totals: Mapping[str, int]) -> dict[str, int]:
    """The nonzero must-be-zero counters of ``totals`` (empty == clean)."""
    return {k: int(totals[k]) for k in CLEAN_COUNTERS if int(totals[k]) != 0}


def assert_clean(totals: Mapping[str, int], context: str = "") -> None:
    """Raise AssertionError naming every dirty counter; no-op when clean.

    ``context`` (e.g. ``"simulate"`` or a conformance axis string) prefixes
    the message so sweep failures name their point.
    """
    bad = unclean_counters(totals)
    if bad:
        prefix = f"{context} " if context else ""
        raise AssertionError(
            f"{prefix}UNCLEAN RUN — events were dropped or misordered: "
            f"{bad} (every overflow/causality counter must be 0; resize "
            f"bucket/route/fallback caps or fix the model)")
