"""The reduction from a trace to numbers, and the metric arithmetic, on
traces small enough to count by hand and on one recorded on a TPU v5e."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, trace  # noqa: E402

RECORDED = Path(__file__).parent / "data" / "trace_v5e_drain.json"
MS = 1_000_000   # ns

# window 0..100 ms; device busy 10-40 (a while loop running fusion.1 then
# sort.2) and 60-70 ms; host: dispatch 5-12, readback 40-58, wait 70-100.
HAND = trace.Trace(
    device={"/device:TPU:0": [[10 * MS, 40 * MS, "while.3"],
                              [10 * MS, 30 * MS, "fusion.1"],
                              [30 * MS, 40 * MS, "sort.2"],
                              [60 * MS, 70 * MS, "fusion.1"]]},
    spans=[[0, 100 * MS, "bench.window"], [5 * MS, 12 * MS, "bench.dispatch"],
           [40 * MS, 58 * MS, "bench.readback"],
           [70 * MS, 100 * MS, "bench.wait"]])


def test_hand_counted_trace():
    red = trace.reduce(HAND)
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.040)          # 10-40 and 60-70
    # self times: fusion.1 20 + 10 ms, sort.2 10 ms; the while has none.
    assert red["device_ops"] == [["fusion.1", pytest.approx(0.030)],
                                 ["sort.2", pytest.approx(0.010)]]
    assert red["idle_gaps"] == [["bench.wait", pytest.approx(0.030)],
                                ["bench.readback", pytest.approx(0.020)],
                                ["bench.dispatch", pytest.approx(0.010)]]


def test_short_names_and_nested_self_time():
    assert trace.short_name("%fusion.12 = f32[8]{0} fusion(f32[8] %p)") == \
        "fusion.12"
    assert trace.short_name("copy-start.3") == "copy-start.3"
    # a while 0-10 holding a call 1-9 holding a fusion 2-5; window 3-100
    got = trace.self_times([[0, 10, "while.1"], [1, 9, "call.2"],
                            [2, 5, "fusion.3"]], 3, 100)
    assert got == {"while.1": 1, "call.2": 4, "fusion.3": 2}


def test_events_outside_the_window_do_not_count():
    tr = trace.Trace(device={"/device:TPU:0": [[-5 * MS, 5 * MS, "a"],
                                               [95 * MS, 120 * MS, "b"]]},
                     spans=[[0, 100 * MS, "bench.window"]])
    red = trace.reduce(tr)
    assert red["busy_s"] == pytest.approx(0.010)
    assert red["idle_gaps"][0] == ["bench.window", pytest.approx(0.090)]


def test_no_device_work_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce(trace.Trace(device={}, spans=HAND.spans))
    with pytest.raises(ValueError):
        trace.reduce(trace.Trace(device=HAND.device, spans=[]))


def test_recorded_v5e_trace():
    """A slice of a traced drain window from the chip: its busy time is the
    union of its operations and lies inside the window."""
    tr = trace.Trace(**json.loads(RECORDED.read_text()))
    red = trace.reduce(tr)
    (evs,) = tr.device.values()
    w0, w1 = trace.window_of(tr)
    want = sum(e - s for s, e in trace.union(trace.clip(
        [(s, e) for s, e, _ in evs], w0, w1)))
    assert red["busy_s"] == pytest.approx(want * 1e-9)
    assert 0 < red["busy_s"] < red["window_s"]
    assert len(red["device_ops"]) == 10 and len(red["idle_gaps"]) == 10
    assert all(name.startswith("bench.") for name, _ in red["idle_gaps"])
    gaps = [s for _, s in red["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert red["busy_s"] + sum(gaps) <= red["window_s"] * (1 + 1e-9)


def metric(name):
    return harness.metric(name).compute


def test_rates_are_all_work_over_all_time():
    rec = {"committed": 123_456, "window_s": 20.5, "attempted": 2,
           "setup_s": 9.25}
    assert metric("events_per_s")(rec) == 123_456 / 20.5
    assert metric("setup_s")(rec) == 9.25


def test_trace_metrics():
    rec = {"committed": 100_000, "epochs": 80,
           "busy_s": 19.0, "trace_window_s": 20.0,
           "model_kw": json.loads((ROOT / "bench/configs/phold-t2.json")
                                  .read_text())["model_kw"],
           "peaks": {"hbm_bytes_per_s": 819e9}}
    assert metric("device_idle_share.steady")(rec) == pytest.approx(0.05)
    assert metric("epoch_device_ms.steady")(rec) == pytest.approx(237.5)
    share = 100 * 100_000 * 6176 / 819e9 / 19.0
    assert metric("event_hbm_share.steady")(rec) == pytest.approx(share)


def test_event_bytes_by_hand_at_table_ii():
    kw = json.loads((ROOT / "bench/configs/phold-t2.json").read_text())[
        "model_kw"]
    mod = harness.metric("event_hbm_share.steady")
    # touch window: 125 nodes x 6 lanes x 4 B, read and written
    touch = 125 * 6 * 4 * 2
    # ceil(0.001 * 4000) = 4 nodes: payload 24 B, address freed and
    # allocated 8 B each; the stack top read and written
    realloc = 4 * (24 + 4 + 4) + 8
    # the event record: read (ts, seed, payload), emitted (+ dst), inserted
    record = 12 + 16 + 12
    assert (touch, realloc, record) == (6000, 136, 40)
    assert mod.event_bytes(kw) == touch + realloc + record == 6176


@pytest.mark.parametrize("name", ["device_idle_share.steady",
                                  "epoch_device_ms.steady",
                                  "event_hbm_share.steady"])
def test_trace_metrics_are_silent_without_a_trace(name):
    assert metric(name)({"committed": 10, "epochs": 4, "peaks": None}) is None
