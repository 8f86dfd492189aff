"""From a profiler trace to the numbers the benchmark reports.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps two
things: the operations that ran on each device (the ``XLA Ops`` line of every
``/device:`` plane; ``XLA Modules`` where a plane has no op line) and the
benchmark's own host spans (``bench.*`` TraceAnnotations).  Both are on the
profiler's one clock.  ``reduce`` turns them into:

- ``window_s``: the length of the ``bench.window`` span;
- ``busy_s``: the union of the device's operation intervals inside the
  window, averaged over the devices that ran anything;
- ``device_ops``: the operations with the most device time in the window,
  each counted by its self time (an enclosing ``while`` or ``call`` less the
  operations that ran inside it), under its short HLO name (``fusion.12``);
- ``idle_gaps``: the longest stretches of the window in which the device ran
  nothing, each named by the host span that covers most of it.

A trace is kept as plain lists (``Trace``), so a small recorded one can be
stored as JSON and checked without a chip.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
OP_LINES = ("XLA Ops", "XLA Modules")


@dataclass
class Trace:
    # device plane name -> [[start_ns, end_ns, op name], ...]
    device: dict[str, list] = field(default_factory=dict)
    # [[start_ns, end_ns, span name], ...] of the bench.* host spans
    spans: list = field(default_factory=list)


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:"):
            line = next((lines[n] for n in OP_LINES if n in lines), None)
            if line is not None:
                tr.device[plane.name] = [
                    [ev.start_ns, ev.start_ns + ev.duration_ns,
                     short_name(ev.name)] for ev in line.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                tr.spans += [[ev.start_ns, ev.start_ns + ev.duration_ns,
                              ev.name] for ev in ln.events
                             if ev.name.startswith(SPAN_PREFIX)]
    return tr


def short_name(name: str) -> str:
    """``%fusion.12 = f32[8]{0} fusion(...)`` -> ``fusion.12``: the TPU's op
    line names each event by its whole HLO instruction."""
    return name.split(" = ", 1)[0].lstrip("%")


def self_times(evs, lo: float, hi: float) -> dict[str, float]:
    """Each op name's time inside [lo, hi) less the time of the ops nested
    inside it on the same line."""
    out: dict[str, float] = {}
    stack: list[tuple[float, str]] = []          # (end, name) of open ops
    for s, e, name in sorted(evs, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        out[name] = out.get(name, 0.0) + max(0.0, min(e, hi) - max(s, lo))
        if stack:
            p_end, parent = stack[-1]
            out[parent] -= max(0.0, min(e, p_end, hi) - max(s, lo))
        stack.append((e, name))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted, disjoint intervals."""
    merged: list[list[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def window_of(tr: Trace) -> tuple[float, float]:
    wins = [(s, e) for s, e, name in tr.spans if name == WINDOW]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(wins)}")
    return wins[0]


def gap_owner(tr: Trace, s: float, e: float) -> str:
    """The host span, other than the window itself, that covers most of
    [s, e); the window's name where none does."""
    best, name = 0.0, WINDOW
    for hs, he, n in tr.spans:
        if n == WINDOW:
            continue
        ov = min(e, he) - max(s, hs)
        if ov > best:
            best, name = ov, n
    return name


def reduce(tr: Trace, top: int = 10) -> dict:
    """Window, busy time, top device operations and longest idle gaps."""
    w0, w1 = window_of(tr)
    busy, ops, gaps = [], {}, []
    for evs in tr.device.values():
        ivs = union(clip([(s, e) for s, e, _ in evs], w0, w1))
        if not ivs:
            continue
        busy.append(sum(e - s for s, e in ivs))
        for name, t in self_times(evs, w0, w1).items():
            ops[name] = ops.get(name, 0.0) + t
        edges = [w0] + [t for iv in ivs for t in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    if not busy:
        raise ValueError("no device operation ran inside the window")
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    top_ops = sorted(((n, t) for n, t in ops.items() if t > 0),
                     key=lambda kv: kv[1], reverse=True)[:top]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "device_ops": [[name, t * 1e-9] for name, t in top_ops],
        "idle_gaps": [[gap_owner(tr, s, e), (e - s) * 1e-9]
                      for s, e in gaps[:top]],
    }
