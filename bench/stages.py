"""Device time of the epoch step's stages, from the benchmark's profiler
trace.

The program opens a ``jax.named_scope`` for each stage of its epoch step
(``parsir.extract``, ``parsir.process``, ...).  A scope is op metadata, so
each device op carries its name-scope path: a TPU v5e trace keeps it as the
``tf_op`` stat of the op's metadata.  ``load`` reads it from the same
``.xplane.pb`` that ``trace.load`` reads.  ``reduce`` gives each op's
self time in the window (as ``trace.reduce`` counts it) to the innermost
segment of its path that is one of ``STAGES``, and everything else to
``other``.  A fused op carries its root op's path, so a fusion that spans
two stages counts under its root's stage.

The stage names are kept here, not imported from the program, so a program
change cannot move the yardstick; a test checks that both lists agree, and
a ``parsir.*`` scope missing from the list is reported on stderr.  A program
without the scopes reads all ``other``, and a stage's reader then reports
nothing.

The scopes are read from the executable that ran.  Where it came from a
persistent compile cache whose key leaves op metadata out (JAX's default),
they are those of whichever build first compiled the same computation:
stale, not only missing.
"""
from __future__ import annotations

import functools
import os
import sys

from bench import trace

PREFIX = "parsir."
OTHER = "other"
STAGES = tuple(PREFIX + s for s in (
    "extract", "process", "rebalance", "route", "exchange", "deliver",
    "shadow", "verdict", "commit", "restore"))
#: the stat of a device op that holds its name-scope path
PATH_STAT = "tf_op"


def stage_of(path: str) -> str:
    """The innermost segment of a name-scope path that is one of
    ``STAGES`` (``tf_op`` ends the path with ``:`` and the op's type, here
    empty).  Another ``parsir.*`` segment is passed over and reported."""
    for seg in reversed(path.split(":", 1)[0].split("/")):
        if seg in STAGES:
            return seg
        if seg.startswith(PREFIX) and seg not in _UNKNOWN:
            _UNKNOWN.add(seg)
            print(f"bench: stage scope {seg!r} is not in bench/stages.py; "
                  f"its ops count under the enclosing stage or {OTHER!r}",
                  file=sys.stderr)
    return OTHER


#: ``parsir.*`` scopes met that are not ``STAGES`` (each reported once)
_UNKNOWN: set[str] = set()


def load(path: str) -> trace.Trace:
    """The trace's device ops, each named by its name-scope path (``""``
    where it has none), and the benchmark's host spans."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    tr = trace.Trace(spans=trace.load(path).spans)
    for field, plane in _fields(data):
        if field != 1:
            continue
        name, lines, paths = _plane(plane)
        line = next((lines[n] for n in trace.OP_LINES if n in lines), None)
        if name.startswith("/device:") and line is not None:
            tr.device[name] = _events(line, paths)
    return tr


# -- a reader of the xplane's protobuf wire format --------------------------
# ``jax.profiler.ProfileData`` gives each op's own stats but not those of its
# metadata, where a TPU v5e keeps ``tf_op``.  The fields read here:
# XSpace.planes 1; XPlane: name 2, lines 3, event_metadata 4 (map entry:
# key 1, value 2), stat_metadata 5 (likewise); XLine: name 2,
# timestamp_ns 3, events 4; XEvent: metadata_id 1, offset_ps 2,
# duration_ps 3; XEventMetadata: id 1, stats 5; XStatMetadata: id 1,
# name 2; XStat: metadata_id 1, str_value 5, ref_value 7 (the id of a stat
# metadata whose name is the string).

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of a message: varints as ints,
    every other wire type as its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _plane(buf):
    """A plane's name, its lines by name, and the ``tf_op`` path of each
    event metadata id."""
    name, lines, metadata, stat_names = "", {}, [], {}
    for f, v in _fields(buf):
        if f == 2:
            name = _str(v)
        elif f == 3:
            line_name = next((_str(x) for g, x in _fields(v) if g == 2), "")
            lines[line_name] = v
        elif f in (4, 5):
            value = next((x for g, x in _fields(v) if g == 2), b"")
            if f == 4:
                metadata.append(value)
            else:
                md = dict(_fields(value))
                stat_names[md.get(1, 0)] = _str(md.get(2, b""))
    paths = {}
    for md in metadata:
        md_id, path = 0, ""
        for f, v in _fields(md):
            if f == 1:
                md_id = v
            elif f == 5:
                stat = dict(_fields(v))
                if stat_names.get(stat.get(1)) == PATH_STAT:
                    path = (_str(stat[5]) if 5 in stat
                            else stat_names.get(stat.get(7), ""))
        paths[md_id] = path
    return name, lines, paths


def _events(line, paths) -> list:
    """``[start_ns, end_ns, path]`` of a line's events, on the clock and in
    the whole nanoseconds that ``ProfileData`` gives them."""
    t0, raw = 0, []
    for f, v in _fields(line):
        if f == 3:
            t0 = v
        elif f == 4:
            raw.append(v)
    out = []
    for ev in raw:
        md_id = off = dur = 0
        for f, v in _fields(ev):
            if f == 1:
                md_id = v
            elif f == 2:
                off = v
            elif f == 3:
                dur = v
        s = t0 + off // 1000
        out.append([s, s + dur // 1000, paths.get(md_id, "")])
    return out


def reduce(tr: trace.Trace) -> dict[str, float]:
    """Seconds of device self time in the window by stage, averaged over
    the devices that ran anything in it."""
    w0, w1 = trace.window_of(tr)
    out: dict[str, float] = {}
    n = 0
    for evs in tr.device.values():
        staged = [[s, e, stage_of(p)] for s, e, p in evs]
        if not trace.clip([(s, e) for s, e, _ in staged], w0, w1):
            continue
        n += 1
        for stage, t in trace.self_times(staged, w0, w1).items():
            out[stage] = out.get(stage, 0.0) + t
    if not n:
        raise ValueError("no device operation ran inside the window")
    return {k: t / n * 1e-9 for k, t in out.items() if t > 0}


@functools.lru_cache(maxsize=2)
def _seconds(path: str, mtime_ns: int) -> dict[str, float]:
    return reduce(load(path))


def window_seconds() -> dict[str, float]:
    """Seconds by stage of the traced window the harness left in its trace
    directory."""
    from bench.harness import TRACE_DIR

    path = trace.find_xplane(str(TRACE_DIR))
    return _seconds(path, os.stat(path).st_mtime_ns)


def epoch_ms(rec: dict, *names: str) -> float | None:
    """Device milliseconds per epoch of the stages ``names`` together:
    ``None`` without a trace, or where no op ran under any of them."""
    if "busy_s" not in rec or not rec.get("epochs"):
        return None
    secs = window_seconds()
    if not any(n in secs for n in names):
        return None
    return sum(secs.get(n, 0.0) for n in names) * 1e3 / rec["epochs"]
