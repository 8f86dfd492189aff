"""The benchmark harness: one cell of ``BENCHMARK.json``, one seed, one run.

Everything a cell needs is found by name:

- ``bench/configs/<config>.json``: the workload id, ``model_kw`` and
  ``engine_kw`` (with ``source``, ``reduced`` and ``assumed``);
- ``bench/traffic/<traffic>.json``: the driver kind, its parameters and any
  ``model_kw`` the mix sets (routing skew);
- ``bench/drivers/<driver>.py``: the window driver;
- ``bench/metrics/<metric>.py``: ``compute(record)`` of each metric, ``None``
  where the run has nothing to read it from (a split metric
  ``<quantity>.<part>`` may share its quantity's reader);
- ``bench/reference/<workload>.py``: the plain reference's handler.

A run: set-up (device check, compile cache, engine, warm-up), the window of
``seconds`` (traced with ``trace``), the peak memory, the comparison with the
reference, and one result line.  ``Bench`` keeps the engine, so one process
can measure several seeds (``bench/control.py`` does).
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from bench import check

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
#: fixed places inside the checkout (listed in .gitignore).  The cache path
#: is part of what a cached program is found by, so it never moves.
TRACE_DIR = ROOT / ".bench_trace"
CACHE_DIR = ROOT / ".jax_cache"
#: monitoring events that mean a program was traced or compiled.
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell asks."""


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def plan(name: str, root: Path = ROOT) -> dict:
    """The cell ``name`` with its configuration, traffic and metrics."""
    spec = read_json(root / "BENCHMARK.json")
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[name]

    def applies(m):
        return name in m.get("workloads", [name])

    return {"cell": cell,
            "config": read_json(root / "bench" / "configs"
                                / f"{cell['config']}.json"),
            "traffic": read_json(root / "bench" / "traffic"
                                 / f"{cell['traffic']}.json"),
            "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
            "per_layer": [m for m in spec["per_layer"] if applies(m)]}


def metric(name: str, root: Path = ROOT):
    """The reader module of metric ``name``: ``bench/metrics/<name>.py``, or
    for a split metric ``<quantity>.<part>`` without a file of its own, the
    quantity's shared ``bench/metrics/<quantity>.py``."""
    tried = [root / "bench" / "metrics" / f"{name}.py"]
    if "." in name:
        tried.append(tried[0].with_name(f"{name.rsplit('.', 1)[0]}.py"))
    path = next((p for p in tried if p.is_file()), None)
    if path is None:
        raise FileNotFoundError(f"no reader for metric {name!r} at "
                                f"{' or '.join(map(str, tried))}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def simulation_seeds(seed: int):
    """The seeds of the simulations a run draws, from ``--seed``: the
    program's bootstrap streams take 32-bit seeds."""
    rng = np.random.default_rng([seed % (1 << 64), 0])
    while True:
        yield int(rng.integers(0, 1 << 32))


def device_info(devs, used) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


class Bench:
    """One cell, set up once; ``measure`` makes a run of it."""

    def __init__(self, name: str, root: Path = ROOT,
                 require_chip: bool = True, plan_: dict | None = None):
        self.name, self.root = name, root
        self.p = plan_ or plan(name, root)
        self.require_chip = require_chip

    def setup(self) -> None:
        import jax
        from jax.sharding import Mesh

        devs = jax.devices()
        self.t_devices = time.perf_counter()
        chips = int(self.p["cell"]["chips"])
        if self.require_chip:
            if devs[0].platform != "tpu":
                raise NoChip(f"no TPU: JAX sees {devs[0].platform!r}")
            if len(devs) < chips:
                raise NoChip(f"{len(devs)} chips visible, the cell asks for "
                             f"{chips}")
        peaks = read_json(BENCH / "peaks.json")
        kind = devs[0].device_kind
        if self.require_chip and kind not in peaks:
            raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
        self.peaks = peaks.get(kind)

        from repro.core.engine import AXIS, EngineConfig, ParsirEngine
        from repro.workloads.registry import get_workload

        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._count_compile)

        cfg, traffic = self.p["config"], self.p["traffic"]
        self.model_kw = {**cfg["model_kw"], **traffic.get("model_kw", {})}
        self.devs, self.used = devs, devs[:chips]
        self.eng = ParsirEngine(
            get_workload(cfg["workload"], **self.model_kw),
            EngineConfig(lookahead=self.model_kw["lookahead"],
                         **cfg["engine_kw"]),
            mesh=Mesh(np.array(self.used), (AXIS,)))
        self.epoch_len = float(self.eng.cfg.epoch_len)
        self.t_engine = time.perf_counter()
        self.driver_mod = importlib.import_module(
            f"bench.drivers.{traffic['driver']}")

    def _count_compile(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.compiles += 1

    def reference(self):
        return check.reference_model(self.p["config"]["workload"],
                                     self.model_kw)

    def measure(self, seed: int, seconds: float, trace: bool,
                t_start: float | None = None) -> dict:
        """One run: warm-up, window, memory, comparison; the result line."""
        import jax

        phases = t_start is not None
        t_start = time.perf_counter() if t_start is None else t_start
        traffic = self.p["traffic"]
        drv = self.driver_mod.Driver(self.eng, traffic,
                                     simulation_seeds(seed))
        t_warm = time.perf_counter()
        drv.warm()
        setup_s = time.perf_counter() - t_start
        if phases:
            print(f"bench: set-up {setup_s:.3f} s: JAX and devices by "
                  f"{self.t_devices - t_start:.3f}, engine by "
                  f"{self.t_engine - t_start:.3f}, first state and warm-up "
                  f"{t_start + setup_s - t_warm:.3f}", file=sys.stderr)

        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
        compiles = self.compiles
        rec = drv.window(seconds)
        compiles = self.compiles - compiles
        if trace:
            jax.profiler.stop_trace()
        device = device_info(self.devs, self.used)
        print(f"bench: {compiles} programs traced or compiled in the window",
              file=sys.stderr)

        rec.update(setup_s=setup_s, model_kw=self.model_kw, peaks=self.peaks)
        breakdown = None
        if trace:
            from bench import trace as tr

            red = tr.reduce(tr.load(tr.find_xplane(str(TRACE_DIR))))
            rec.update(busy_s=red["busy_s"], trace_window_s=red["window_s"])
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}

        sims = drv.sims(rec)
        del drv                         # the program's state is freed here
        self.sims = sims
        t_ref = time.perf_counter()
        gaps = check.compare(sims, self.reference(), self.epoch_len)
        print(f"bench: the reference compared {len(sims)} simulations in "
              f"{time.perf_counter() - t_ref:.3f} s", file=sys.stderr)
        numbers = {"failed": rec["failed"], **gaps}

        metrics = {}
        for m in self.p["per_layer" if trace else "end_to_end"]:
            v = metric(m["name"], self.root).compute(rec)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        line = {"correct": bool(sims) and all(
                    v <= check.LIMITS[k] for k, v in numbers.items()),
                "attempted": int(rec["attempted"]),
                "failed": int(rec["failed"]),
                "metrics": metrics, "device": device}
        if breakdown is not None:
            line["breakdown"] = breakdown
        line["compared"] = len(sims)
        line["checks"] = {k: {"value": int(v), "limit": check.LIMITS[k]}
                          for k, v in numbers.items()}
        return line


def report(line: dict) -> None:
    """The compared numbers as the last lines of stderr, then the result as
    the last line of stdout."""
    for k, v in line["checks"].items():
        print(f"check {k} = {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
