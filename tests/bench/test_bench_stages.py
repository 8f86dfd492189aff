"""Device time by epoch-step stage, from traces small enough to count by
hand: the stage of an op's name-scope path, nesting, window clipping, the
ops under no stage, and the stage readers."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, stages, trace  # noqa: E402

MS = 1_000_000   # ns
RUN = "jit(run)/jit(shard_map)/while/body"

# window 0..100 ms.  A while loop 10-60 ms (no stage) holds an extract sort
# 10-20, a process loop 20-50 holding its fusion 25-45, and a route fusion
# 50-55; an exchange runs 60-62, a deliver scatter 62-70, a copy 70-72 with
# no scope; an extract op starts before the window and a deliver op ends
# after it.
HAND = trace.Trace(
    device={"/device:TPU:0": [
        [-5 * MS, 4 * MS, f"{RUN}/parsir.extract/sort"],
        [10 * MS, 60 * MS, "jit(run)/while"],
        [10 * MS, 20 * MS, f"{RUN}/parsir.extract/sort"],
        [20 * MS, 50 * MS, f"{RUN}/parsir.process/while"],
        [25 * MS, 45 * MS, f"{RUN}/parsir.process/while/body/mul"],
        [50 * MS, 55 * MS, f"{RUN}/parsir.route/select_n"],
        [60 * MS, 62 * MS, f"{RUN}/parsir.exchange/all_gather"],
        [62 * MS, 70 * MS, f"{RUN}/parsir.deliver/scatter"],
        [70 * MS, 72 * MS, ""],
        [95 * MS, 110 * MS, f"{RUN}/parsir.deliver/scatter"]]},
    spans=[[0, 100 * MS, "bench.window"]])


def test_stage_is_the_innermost_parsir_segment():
    assert stages.stage_of(f"{RUN}/parsir.process/while/body/mul") == \
        "parsir.process"
    # the speculative step's restore branch runs inside its commit scope
    assert stages.stage_of("jit(run)/parsir.commit/cond/branch_0_fun/"
                           "parsir.restore/dynamic_update_slice") == \
        "parsir.restore"
    # a TPU's tf_op ends in ":" and the op type
    assert stages.stage_of(f"{RUN}/parsir.deliver:") == "parsir.deliver"
    for path in ("", "jit(run)/while", "jit(_token)/reduce_sum",
                 "jit(run)/parsirx.route/add"):
        assert stages.stage_of(path) == stages.OTHER


def test_unknown_stage_scope_is_reported_not_counted(capsys, monkeypatch):
    monkeypatch.setattr(stages, "_UNKNOWN", set())
    # an unlisted scope inside a listed one counts under the listed one,
    # alone it counts as other; either way it is named once on stderr.
    assert stages.stage_of(f"{RUN}/parsir.process/parsir.apply/mul") == \
        "parsir.process"
    assert stages.stage_of(f"{RUN}/parsir.apply/add") == stages.OTHER
    err = capsys.readouterr().err
    assert err.count("'parsir.apply'") == 1, err


def test_hand_counted_stages():
    got = stages.reduce(HAND)
    want = {"parsir.extract": 4 + 10, "parsir.process": 30,
            "parsir.route": 5, "parsir.exchange": 2,
            "parsir.deliver": 8 + 5, "other": 5 + 2}
    assert got == pytest.approx({k: v * 1e-3 for k, v in want.items()})
    # every op's self time lands in exactly one stage: the stages add up
    # to the busy time the trace's own reduction finds.
    assert sum(got.values()) == pytest.approx(trace.reduce(HAND)["busy_s"])


def test_nesting_counts_self_time_only():
    # a process loop 0-10 holding a fusion 2-9 of the same stage and a
    # route op 3-4 nested in that fusion: process keeps 9 ms, route 1 ms.
    tr = trace.Trace(
        device={"/device:TPU:0": [
            [0, 10 * MS, f"{RUN}/parsir.process/while"],
            [2 * MS, 9 * MS, f"{RUN}/parsir.process/fusion"],
            [3 * MS, 4 * MS, f"{RUN}/parsir.route/add"]]},
        spans=[[0, 20 * MS, "bench.window"]])
    assert stages.reduce(tr) == pytest.approx(
        {"parsir.process": 9e-3, "parsir.route": 1e-3})


def test_a_program_without_scopes_is_all_other():
    tr = trace.Trace(device={"/device:TPU:0": [[0, 7 * MS, "fusion.1"],
                                               [7 * MS, 9 * MS, ""]]},
                     spans=[[0, 10 * MS, "bench.window"]])
    assert stages.reduce(tr) == pytest.approx({"other": 9e-3})


def test_stage_names_are_the_programs():
    """The benchmark keeps its own copy of the stage names, so a program
    change cannot move the yardstick; the two lists must agree."""
    from repro.core.pipeline import names

    assert stages.STAGES == names.STAGE_SCOPES
    assert all(s.startswith(stages.PREFIX) for s in names.STAGE_SCOPES)


READERS = {"extract_ms.steady": ("parsir.extract",),
           "process_ms.steady": ("parsir.process",),
           "route_ms.steady": ("parsir.route", "parsir.exchange"),
           "deliver_ms.steady": ("parsir.deliver",)}


@pytest.mark.parametrize("name", sorted(READERS))
def test_stage_readers_are_silent_without_a_trace(name):
    reader = harness.metric(name).compute
    assert reader({"committed": 10, "epochs": 4, "peaks": None}) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_stage_readers_per_epoch(name, monkeypatch):
    secs = stages.reduce(HAND)
    monkeypatch.setattr(stages, "window_seconds", lambda: secs)
    rec = {"committed": 10, "epochs": 4, "busy_s": 0.08,
           "trace_window_s": 0.1}
    want = sum(secs[s] for s in READERS[name]) * 1e3 / 4
    assert harness.metric(name).compute(rec) == pytest.approx(want)
    # a trace of a program without the scopes: nothing to report
    monkeypatch.setattr(stages, "window_seconds", lambda: {"other": 0.08})
    assert harness.metric(name).compute(rec) is None


# -- a small xplane, written in the protobuf wire format ---------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """(field number, int | str | bytes) pairs as one message."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def _stat_md(i, name):                       # XPlane.stat_metadata entry
    return (5, _msg((1, i), (2, _msg((1, i), (2, name)))))


def _event_md(i, name, *stats):              # XPlane.event_metadata entry
    return (4, _msg((1, i), (2, _msg((1, i), (2, name),
                                     *((5, s) for s in stats)))))


def _event(md, start_ns, end_ns, *stats):    # XLine.events
    return (4, _msg((1, md), (2, start_ns * 1000),
                    (3, (end_ns - start_ns) * 1000),
                    *((4, s) for s in stats)))


def _xplane() -> bytes:
    # as a TPU v5e writes it: each op's metadata holds its ``tf_op`` path,
    # as a string (the process fusion) or interned as a ref to a stat
    # metadata holding the string (the extract sort); a copy has none.
    # Times are in picoseconds, off whole nanoseconds.
    device = _msg(
        (2, "/device:TPU:0"),
        _event_md(10, "%sort.2 = f32[8]{0} sort(f32[8] %p)",
                  _msg((1, 1), (7, 3))),
        _event_md(11, "%fusion.7 = f32[8]{0} fusion(f32[8] %q)",
                  _msg((1, 1), (5, f"{RUN}/parsir.process/while/mul:"))),
        _event_md(12, "copy.3", _msg((1, 2), (5, "u32[8]"))),
        _stat_md(1, "tf_op"), _stat_md(2, "shape_with_layout"),
        _stat_md(3, f"{RUN}/parsir.extract/sort:"),
        (3, _msg((2, "XLA Modules"), (3, 0), _event(10, 0, 90 * MS))),
        (3, _msg((2, "XLA Ops"), (3, 0),
                 _event(10, 0, 10 * MS), _event(11, 10 * MS, 80 * MS),
                 (4, _msg((1, 12), (2, 80 * MS * 1000 + 400),
                          (3, 10 * MS * 1000 + 900))))))
    host = _msg((2, "/host:CPU"), _event_md(1, "bench.window"),
                (3, _msg((2, "python"), (3, 0), _event(1, 0, 100 * MS))))
    return _msg((1, device), (1, host))


def test_load_reads_paths_from_op_metadata(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xplane())
    tr = stages.load(str(path))
    assert tr.spans == [[0, 100 * MS, "bench.window"]]
    assert tr.device == {"/device:TPU:0": [
        [0, 10 * MS, f"{RUN}/parsir.extract/sort:"],
        [10 * MS, 80 * MS, f"{RUN}/parsir.process/while/mul:"],
        [80 * MS, 90 * MS, ""]]}
    assert stages.reduce(tr) == pytest.approx(
        {"parsir.extract": 0.010, "parsir.process": 0.070, "other": 0.010})
    # the same ops at the same whole nanoseconds as the trace's own reader
    plain = trace.load(str(path))
    assert [ev[:2] for ev in plain.device["/device:TPU:0"]] == \
        [ev[:2] for ev in tr.device["/device:TPU:0"]]
