"""``BENCHMARK.json`` against the benchmark's contract, and the harness
finding every cell's files by name, including cells and metrics added as new
files only."""
import json
import re
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
CELLS = [c["name"] for c in SPEC["workloads"]]


def one_line(s: str) -> bool:
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    cmd = SPEC["command"]
    assert len(cmd) <= 32 and all(one_line(w) for w in cmd)
    for w in cmd:
        if (ROOT / w).is_file():
            assert any(w.startswith(p + "/") for p in SPEC["paths"]), w
    rs = SPEC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells fits its 12 hours
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs_and_cells():
    configs = {c["name"]: c for c in SPEC["configs"]}
    assert 1 <= len(configs) <= 24
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"])
        assert one_line(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
    assert len({c["file"] for c in SPEC["configs"]}) == len(configs)
    pairs = set()
    assert 1 <= len(CELLS) == len(set(CELLS)) <= 24
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert one_line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == set(configs)
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(CELLS) // 2)


def test_metrics():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    layer = SPEC["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    names = list(e2e) + [m["name"] for m in layer]
    assert len(names) == len(set(names))
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e[
        "setup_s"]
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in layer:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert one_line(m["layer"]) and m["moves"] in e2e
        moved = e2e[m["moves"]].get("workloads", CELLS)
        assert set(m.get("workloads", moved)) <= set(moved), m["name"]
    for m in SPEC["end_to_end"] + layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for cell in CELLS:
        p = harness.plan(cell)
        got = {m["name"] for m in p["end_to_end"]}
        assert "setup_s" in got and len(got) >= 2, cell
        assert p["per_layer"], cell


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    import importlib

    p = harness.plan(cell)
    assert p["cell"]["name"] == cell
    importlib.import_module(f"bench.drivers.{p['traffic']['driver']}")
    importlib.import_module(f"bench.reference.{p['config']['workload']}")
    for m in p["end_to_end"] + p["per_layer"]:
        assert callable(harness.metric(m["name"]).compute)


def test_new_cell_and_metric_from_new_files_only(tmp_path):
    """A cell of an existing driver kind is added as data files, and a
    per-layer metric as one reader file; the harness finds both by name."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    traffic = json.loads((ROOT / "bench/traffic/uniform.json").read_text())
    traffic["epochs_per_dispatch"] = 8
    (tmp_path / "bench/traffic/uniform8.json").write_text(json.dumps(traffic))
    (tmp_path / "bench/metrics/committed_per_epoch.steady.py").write_text(
        "def compute(rec):\n    return rec['committed'] / rec['epochs']\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "phold-t2.uniform8",
                              "config": "phold-t2", "traffic": "uniform8",
                              "chips": 1, "why": "eight epochs a dispatch"})
    for m in spec["end_to_end"]:
        if m["name"] == "events_per_s":
            m["workloads"].append("phold-t2.uniform8")
    spec["per_layer"].append({
        "name": "committed_per_epoch.steady", "unit": "events",
        "better": "higher", "source": "program_counter", "layer": "Epoch step",
        "moves": "events_per_s", "workloads": ["phold-t2.uniform8"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    p = harness.plan("phold-t2.uniform8", root=tmp_path)
    assert p["traffic"]["epochs_per_dispatch"] == 8
    assert p["config"]["name"] == "phold-t2"
    assert [m["name"] for m in p["end_to_end"]] == ["events_per_s", "setup_s"]
    assert [m["name"] for m in p["per_layer"]] == [
        "committed_per_epoch.steady"]
    reader = harness.metric("committed_per_epoch.steady", root=tmp_path)
    assert reader.compute({"committed": 80, "epochs": 8}) == 10
    with pytest.raises(FileNotFoundError):
        harness.metric("no_such_metric", root=tmp_path)


def test_split_metric_shares_its_quantity_reader(tmp_path):
    """A metric split by part reads its quantity's shared reader unless the
    part has a reader of its own, so a new part needs no new file."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    metrics = tmp_path / "bench" / "metrics"
    rec = {"busy_s": 3.0, "trace_window_s": 4.0, "epochs": 6}
    assert harness.metric("device_idle_share.drain",
                          root=tmp_path).compute(rec) == 0.25
    (metrics / "device_idle_share.drain.py").write_text(
        "def compute(rec):\n    return 7.0\n")
    assert harness.metric("device_idle_share.drain",
                          root=tmp_path).compute(rec) == 7.0
    assert harness.metric("device_idle_share.steady",
                          root=tmp_path).compute(rec) == 0.25
    with pytest.raises(FileNotFoundError):
        harness.metric("no_such_metric.steady", root=tmp_path)
