"""Bring-up smoke of the PARSIR engine on a TPU: does the main path run on
the chip, through the entry points a user calls, and give the right bits?

  python chip_smoke.py              # one chip (the default)
  python chip_smoke.py --chips 4    # the cross-device paths on four chips
  JAX_PLATFORMS=cpu python chip_smoke.py --tiny   # CPU rehearsal

One process, no JAX subprocess.  Phases, in order; any failure raises and
the script exits nonzero without a result line:

  1. device check — the platform must be ``tpu`` (``--tiny`` alone lets a
     CPU rehearsal run the phases, and still exits nonzero with no result);
  2. conformance — every registered workload under ``batch-allgather``,
     ``phold`` through the Pallas event-apply kernel (``batch-model``) and
     ``wireless`` packed and speculative, each bit-exact against the numpy
     oracle through the fused drain (the tier-1 harness);
  3. PHOLD at the paper's Table II settings (``PholdParams`` defaults):
     warm-up, then a 64-epoch ``run``; every clean-run counter zero, exactly
     O x M events still in flight, events processed;
  4. the ``wireless`` drain at the ``pdes_perf`` bench size until nothing is
     in flight, then a small campaign through ``repro.campaign``.

``--chips 4`` runs only what exists across chips: the wireless drain at
D=4 under ``a2a`` and ``allgather``, each bit-identical to D=1, and an
8-seed replication-sharded (``rep_shards=4``) drain leaf-equal to the same
seeds vmapped on one device.

The last line of a passing run is the only JSON line:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Speeds printed here are smoke figures, not benchmarks.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
#: the campaign's results store (listed in .gitignore; emptied every run).
CAMPAIGN_STORE = ROOT / "artifacts" / "chip_smoke_campaign"
#: PHOLD epochs in the steady window.
PHOLD_EPOCHS = 64
#: drain bound for the wireless drains.
DRAIN_EPOCHS = 256


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def timed(fn, *args):
    """``fn(*args)`` with its result on the device; returns (result, s)."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


# -- one chip ------------------------------------------------------------------

def conformance_phase() -> None:
    from repro.testing.conformance import check_workload
    from repro.workloads.registry import all_workloads

    cases = ([(w, "batch-allgather") for w in all_workloads()]
             + [("phold", "batch-model"), ("wireless", "batch-packed"),
                ("wireless", "spec-w2")])
    ref_cache: dict = {}
    for name, config in cases:
        t0 = time.perf_counter()
        rep = check_workload(name, config, ref_cache=ref_cache, drain=True)
        say(f"conformance {name}/{config}: bit-exact vs oracle, processed="
            f"{rep['totals']['processed']} pending={rep['pending']} "
            f"({time.perf_counter() - t0:.1f} s)")


def phold_phase(tiny: bool) -> None:
    import jax

    from repro.core.engine import EngineConfig, ParsirEngine
    from repro.testing import assert_clean
    from repro.workloads.registry import get_workload

    model = get_workload("phold", **(dict(n_objects=64, state_nodes=256)
                                     if tiny else {}))
    p = model.params
    # launch/simulate.py's engine defaults.
    eng = ParsirEngine(model, EngineConfig(
        lookahead=p.lookahead, n_buckets=16, bucket_cap=256, route_cap=8192,
        fallback_cap=8192))
    st, init_s = timed(eng.init)
    st, warm_s = timed(eng.run, st, 0)
    base = eng.totals(st)["processed"]
    st, run_s = timed(eng.run, st, PHOLD_EPOCHS)

    tot = eng.totals(st)
    assert_clean(tot, context="phold Table II")
    in_flight, want = eng.in_flight(st), p.n_objects * p.initial_events
    assert in_flight == want, f"in flight {in_flight} != O*M = {want}"
    done = tot["processed"] - base
    assert done > 0, "no event processed"
    mem = jax.devices()[0].memory_stats() or {}
    say(f"phold O={p.n_objects} M={p.initial_events} S={p.state_nodes} "
        f"P={p.realloc_fraction} L={p.lookahead} lanes={p.lanes}: set-up "
        f"(compile) init {init_s:.2f} s + run {warm_s:.2f} s")
    say(f"phold {PHOLD_EPOCHS} epochs: {done} events in {run_s:.4f} s = "
        f"{done / run_s:.0f} events/s (smoke figure, not a benchmark); "
        f"in flight {in_flight}; clean")
    say(f"peak_bytes_in_use {mem.get('peak_bytes_in_use', 'not reported')}")


def wireless_model(tiny: bool):
    """``wireless`` at the ``benchmarks.pdes_perf`` bench size, with the
    ``max_calls=4`` budget of its draining rungs."""
    from benchmarks.pdes_perf import BASE, BENCH_MODEL_KW
    from repro.workloads.registry import get_workload

    return get_workload(
        "wireless", n_objects=64 if tiny else BASE["o"], lookahead=BASE["la"],
        dist=BASE["dist"], **dict(BENCH_MODEL_KW["wireless"], max_calls=4))


def wireless_drain(tiny: bool, mesh=None, route: str = "a2a"):
    """The it4_drain_budget rung of ``benchmarks.pdes_perf``: returns
    (engine, drained state, seconds including compile)."""
    from benchmarks.pdes_perf import BASE
    from repro.core.engine import EngineConfig, ParsirEngine
    from repro.testing import assert_clean

    eng = ParsirEngine(wireless_model(tiny), EngineConfig(
        lookahead=BASE["la"], n_buckets=32, bucket_cap=256,
        route_cap=BASE["route_cap"], fallback_cap=16384, route=route),
        mesh=mesh)
    st, dt = timed(lambda: eng.run_until_drained(eng.init(), DRAIN_EPOCHS))
    tot = eng.totals(st)
    assert_clean(tot, context=f"wireless drain D={eng.D} {route}")
    left = eng.in_flight(st)
    assert left == 0, f"wireless drain D={eng.D} {route}: {left} in flight"
    return eng, st, dt


def drain_phase(tiny: bool) -> None:
    eng, st, dt = wireless_drain(tiny)
    say(f"wireless drain o={eng.model.n_objects}: drained at epoch "
        f"{int(st.epoch[0])}, {eng.totals(st)['processed']} events, "
        f"{dt:.2f} s with compile")


def campaign_phase() -> None:
    from repro.campaign import CampaignSpec, ResultsStore, run_campaign

    # the CI campaign smoke grid (.github/workflows/ci.yml), one device.
    spec = CampaignSpec(
        workload="wireless", seeds=(0, 1, 2),
        base_model_kw=dict(lookahead=0.5, n_cells=6, n_channels=2,
                           handoff_p=0),
        grid={"max_calls": [2, 3]},
        engine_kw=dict(lookahead=0.5, n_buckets=8, bucket_cap=64,
                       route_cap=512, fallback_cap=512),
        devices=1, max_epochs=200)
    shutil.rmtree(CAMPAIGN_STORE, ignore_errors=True)
    t0 = time.perf_counter()
    summary = run_campaign(spec, store=ResultsStore(str(CAMPAIGN_STORE)))
    assert summary["ran"] == len(spec.points()), summary["ran"]
    for key in ("unclean", "undrained", "missing"):
        assert not summary[key], f"campaign {key}: {summary[key]}"
    say(f"campaign {summary['n_points']} points x {len(spec.seeds)} seeds: "
        f"clean, drained ({time.perf_counter() - t0:.1f} s)")


# -- four chips ------------------------------------------------------------------

def assert_spans(state, n: int, what: str) -> None:
    import jax

    got = {d for leaf in jax.tree.leaves(state)
           for d in leaf.sharding.device_set}
    assert len(got) == n, f"{what} spans {len(got)} devices, want {n}"


def object_sharded_phase(tiny: bool, devs) -> None:
    import numpy as np
    from jax.sharding import Mesh

    from repro.core.engine import AXIS

    one, st1, _ = wireless_drain(tiny, Mesh(np.array(devs[:1]), (AXIS,)))
    want_obj, want_tot = one.global_object_state(st1), one.totals(st1)
    for route in ("a2a", "allgather"):
        eng, st, dt = wireless_drain(tiny, Mesh(np.array(devs[:4]), (AXIS,)),
                                     route)
        assert_spans(st, 4, f"D=4 {route} state")
        assert eng.totals(st) == want_tot, (route, eng.totals(st), want_tot)
        got = eng.global_object_state(st)
        for k in want_obj:
            np.testing.assert_array_equal(
                got[k], want_obj[k], err_msg=f"D=4 {route} object state {k}")
        say(f"wireless drain D=4 {route}: bit-identical to D=1 "
            f"({want_tot['processed']} events, {dt:.2f} s with compile)")


def rep_sharded_phase(tiny: bool) -> None:
    import jax
    import numpy as np

    from benchmarks.pdes_perf import BASE
    from repro.core.engine import EngineConfig, ParsirEngine
    from repro.testing import assert_clean

    # the it5_campaign rung of benchmarks.pdes_perf, at 8 seeds.
    model = wireless_model(tiny)
    cfg = EngineConfig(lookahead=BASE["la"], n_buckets=32, bucket_cap=64,
                       route_cap=2048, fallback_cap=4096)
    seeds = list(range(8))
    states = {}
    for shards in (None, 4):
        eng = ParsirEngine(model, cfg, rep_shards=shards)
        st, dt = timed(lambda: eng.run_replicated_drained(
            eng.init_replicated(seeds), DRAIN_EPOCHS))
        for r, tot in enumerate(eng.totals_replicated(st)):
            assert_clean(tot, context=f"rep_shards={shards} seed {r}")
        assert not eng.in_flight_replicated(st).any(), "undrained replication"
        states[shards] = st
        say(f"8-seed drain rep_shards={shards}: {dt:.2f} s with compile")
    assert_spans(states[4], 4, "rep_shards=4 state")
    for r in range(len(seeds)):
        for a, b in zip(jax.tree.leaves(jax.tree.map(lambda l: l[r],
                                                     states[None])),
                        jax.tree.leaves(jax.tree.map(lambda l: l[r],
                                                     states[4]))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"replication {r}")
    say("8-seed rep_shards=4: every replication leaf-equal to vmapped D=1")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--tiny", action="store_true",
                    help="small sizes; on a non-TPU platform run the phases "
                         "as a rehearsal (still exits nonzero, no result)")
    args = ap.parse_args(argv)
    if not __debug__:
        say("FAIL: the checks are assert statements; run without -O")
        return 1

    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    say(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    on_tpu = dev["platform"] == "tpu"
    if not on_tpu and not args.tiny:
        say(f"FAIL: no TPU (platform {dev['platform']!r})")
        return 1
    if len(devs) < args.chips:
        say(f"FAIL: {len(devs)} devices, --chips {args.chips}")
        return 1

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.runtime import enable_compile_cache

    say(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        object_sharded_phase(args.tiny, devs)
        rep_sharded_phase(args.tiny)
    else:
        conformance_phase()
        phold_phase(args.tiny)
        drain_phase(args.tiny)
        campaign_phase()
    say(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    if not on_tpu:
        say("rehearsal only: not a TPU, no result")
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
