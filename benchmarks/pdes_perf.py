"""§Perf for the paper's own engine: hypothesis→change→measure iterations.

Runs the PARSIR engine hillclimb ladder on CPU (wall-clock events/s) and
reports, for each routing strategy, the *structural* per-epoch exchange bytes
(what the ICI would carry on a pod) — the measurable CPU proxy plus the
analytic collective term.

``--workload`` selects any registered zoo workload (repro/workloads), so the
perf trajectory covers skewed traffic (phold-hotspot), FIFO-coupled traffic
(queueing) and deterministic ring traffic (cluster), not just uniform PHOLD.

The ``it4_fused_drain`` rung measures *dispatches-per-simulation* — the same
window driven one-host-dispatch-per-epoch, in fixed fused chunks, and as one
``lax.while_loop`` dispatch (``run_until_drained``; must report exactly 1).
The ``it5_campaign`` rung (wireless) measures *dispatches-per-campaign*:
32 replication seeds of the draining simulation run one-fused-drain-per-seed
vs all 32 stacked through the replication-vmapped while_loop (must report
exactly 1 dispatch for the whole sweep).  When the seed count divides the
device count the stacked drive runs replication-*sharded* (``rep_shards``:
each replication collective-free on its own device, capacities right-sized
to one replication's traffic via ``rep_engine_kw``) — the layout that wins
at campaign scale.
The ``it6_speculation`` rung (wireless + epidemic, the draining loads)
sweeps ``opt_window`` over {0, 1, 2, 4} and measures *epochs-to-drain* —
fused while-loop iterations, ``(spec_commits + rollbacks) / D`` when
speculating (the meters count once per device per window) — which must
fall strictly below the conservative drain at every W while the drained
bits stay identical; rollbacks are reported alongside.
The ``it7_per_device_commit`` rung (same draining loads) drives a fixed
window once under the PR 9 global all-or-nothing vote and once under
per-device commit, and measures *rolled-back device-windows* — the
``rollbacks`` counter, one per device per aborted window — which the
per-device verdict must strictly reduce (or drain in strictly fewer
iterations) while reaching bit-identical drained state.
Any rung whose run is unclean (nonzero overflow/causality counter, the full
:mod:`repro.testing.clean` set) fails the driver with a nonzero exit —
a perf number from a run that dropped events is not a result.  Draining
rungs (``expect_drained``) additionally fail if they hit their epoch bound
with events still in flight: ev/s from a simulation that never finished is
not a result either.

  PYTHONPATH=src python -m benchmarks.pdes_perf [--devices 8]
  PYTHONPATH=src python -m benchmarks.pdes_perf --workload phold-hotspot
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap

_CHILD = textwrap.dedent("""
    import json, sys, time
    import numpy as np, jax
    from jax.sharding import Mesh
    from repro.core.engine import AXIS, EngineConfig, ParsirEngine
    from repro.launch.runtime import enable_compile_cache
    from repro.workloads.registry import get_workload

    enable_compile_cache()
    spec = json.loads(sys.argv[1])
    D = spec["devices"]
    mesh = Mesh(np.array(jax.devices()[:D]), (AXIS,))
    wname = spec.get("workload", "phold")
    model_kw = dict(n_objects=spec["o"], lookahead=spec["la"],
                    dist=spec["dist"], **spec.get("model_kw", {}))
    if wname in ("phold", "phold-hotspot"):
        model_kw.update(initial_events=spec["m"], state_nodes=spec["s"],
                        realloc_fraction=0.004)
        # hot_o/hot_p ladder overrides apply to BOTH phold workloads (the
        # hotspot ladder used to silently run with default hot params).
        if "hot_o" in spec:
            model_kw["hot_objects"] = spec["hot_o"]
        if "hot_p" in spec:
            model_kw["hot_prob"] = spec["hot_p"]
    try:
        model = get_workload(wname, **model_kw)
    except TypeError as e:
        # unknown model_kw keys must fail fast and loudly, never be dropped.
        # Anything other than a bad-kwarg TypeError is a real bug: keep its
        # traceback instead of mislabeling it as a spec problem.
        if "unexpected keyword argument" not in str(e):
            raise
        raise SystemExit(f"bad model_kw for workload {wname!r}: {e} "
                         f"(keys: {sorted(model_kw)})")
    ckw = dict(lookahead=spec["la"],
               epoch_len=spec.get("epoch_len"),
               n_buckets=32, bucket_cap=spec.get("bucket_cap", 256),
               route_cap=spec["route_cap"], fallback_cap=16384,
               route=spec["route"], scheduler=spec.get("sched", "batch"),
               steal=spec.get("steal", False), steal_cap=8,
               claim_cap=16,
               batch_impl=spec.get("batch_impl", "rounds"),
               pack_tile=spec.get("pack_tile", 64),
               placement=spec.get("placement", "equal"),
               rebalance_every=spec.get("rebalance_every", 0),
               migrate_cap=spec.get("migrate_cap", 16),
               placement_slack=spec.get("placement_slack", 2.0),
               opt_window=spec.get("opt_window", 0),
               opt_stage_cap=spec.get("opt_stage_cap", 0),
               opt_commit=spec.get("opt_commit", "device"))
    cfg = EngineConfig(**ckw)
    eng = ParsirEngine(model, cfg, mesh=mesh)
    from repro.testing import unclean_counters

    if spec.get("speculation"):
        # speculation rung (PR 9): the SAME draining simulation driven by the
        # fused while_loop at every opt_window W in spec["windows"].  The
        # honest metric is *epochs-to-drain* — while-loop iterations, i.e.
        # spec_commits + rollbacks when speculating, epochs_run at W=0 —
        # because each iteration is one barrier'd dispatch round: the window
        # must cut iterations strictly below the conservative drain while
        # reaching bit-identical drained state (asserted below, every W
        # against the W=0 bits).  Rollbacks are *expected* at D>1 (every
        # cross-device event into an open window is a straggler) and the
        # rung surfaces them next to the win they price.
        E = spec["epochs"]
        windows, base, failures = {}, None, []
        for W in spec["windows"]:
            eng_w = ParsirEngine(model, EngineConfig(**dict(
                ckw, opt_window=W)), mesh=mesh)
            jax.block_until_ready(eng_w.run_until_drained(eng_w.init(), E))
            st = eng_w.init()                       # measured pass
            t0 = time.perf_counter()
            st = eng_w.run_until_drained(st, E)
            jax.block_until_ready(st)
            dt = time.perf_counter() - t0
            tot = eng_w.totals(st)
            epochs_run = int(np.asarray(st.epoch)[0])
            # the commit/rollback meters tick once per device per window
            # (so their per-device sums equal the fused-loop iteration
            # count on every device) — normalize totals back to windows.
            iters = ((tot["spec_commits"] + tot["rollbacks"]) // D if W
                     else epochs_run)
            obj = {k: np.asarray(v) for k, v in
                   eng_w.global_object_state(st).items()}
            if base is None:
                base = dict(iters=iters, n=tot["processed"], obj=obj)
            else:
                assert tot["processed"] == base["n"], \
                    f"W={W} diverged: {tot['processed']} != {base['n']}"
                for k in obj:
                    assert np.array_equal(obj[k], base["obj"][k]), \
                        f"W={W} object state {k!r} diverges from W=0"
                if iters >= base["iters"]:
                    failures.append(f"W={W}: {iters} iterations >= "
                                    f"conservative {base['iters']}")
            windows[f"w{W}"] = {
                "opt_window": W, "epochs_to_drain": iters,
                "epochs_run": epochs_run, "dt": dt,
                "ev_s": tot["processed"] / dt,
                "rollbacks": tot["rollbacks"],
                "spec_commits": tot["spec_commits"],
                "speculated": tot["speculated"],
                "drained": eng_w.in_flight(st) == 0,
                "unclean": unclean_counters(tot)}
        assert not failures, f"speculation never won: {failures}"
        bad = {}
        for wrec in windows.values():
            for k, v in wrec["unclean"].items():
                bad[k] = bad.get(k, 0) + v
        drained = all(wrec["drained"] for wrec in windows.values())
        best = max(windows.values(), key=lambda wrec: wrec["ev_s"])
        print(json.dumps({"ev_s": best["ev_s"], "n": base["n"],
                          "windows": windows, "unclean": bad,
                          "drained": drained, "bound_hit": not drained,
                          "epochs_run": max(wrec["epochs_run"]
                                            for wrec in windows.values())}))
        raise SystemExit(0)

    if spec.get("commit_compare"):
        # per-device-commit rung (PR 10): the SAME draining simulation at a
        # fixed opt_window, driven once under the PR 9 global all-or-nothing
        # vote and once under per-device commit.  The honest waste metric is
        # *rolled-back device-windows* — the rollbacks counter ticks once per
        # device per aborted window, so under the global vote one straggler
        # anywhere prices D device-windows of discarded work while the
        # per-device verdict aborts only the devices a straggler actually
        # reached.  The verdict must strictly reduce that waste (or, because
        # committed-early emissions shift later arrival timing, drain in
        # strictly fewer fused-loop iterations) while the drained object
        # state stays bit-identical between the two commit modes.
        E, W = spec["epochs"], spec["opt_window"]
        recs, base = {}, None
        for mode in ("global", "device"):
            eng_m = ParsirEngine(model, EngineConfig(**dict(
                ckw, opt_window=W, opt_commit=mode)), mesh=mesh)
            jax.block_until_ready(eng_m.run_until_drained(eng_m.init(), E))
            st = eng_m.init()                       # measured pass
            t0 = time.perf_counter()
            st = eng_m.run_until_drained(st, E)
            jax.block_until_ready(st)
            dt = time.perf_counter() - t0
            tot = eng_m.totals(st)
            iters = (tot["spec_commits"] + tot["rollbacks"]) // D
            obj = {k: np.asarray(v) for k, v in
                   eng_m.global_object_state(st).items()}
            if base is None:
                base = dict(n=tot["processed"], obj=obj)
            else:
                assert tot["processed"] == base["n"], \
                    f"{mode} diverged: {tot['processed']} != {base['n']}"
                for k in obj:
                    assert np.array_equal(obj[k], base["obj"][k]), \
                        f"{mode} object state {k!r} diverges from global vote"
            recs[mode] = {
                "opt_commit": mode, "opt_window": W,
                "epochs_to_drain": iters,
                "epochs_run": int(np.asarray(st.epoch)[0]), "dt": dt,
                "ev_s": tot["processed"] / dt,
                "rolled_back_device_windows": tot["rollbacks"],
                "committed_device_windows": tot["spec_commits"],
                "speculated": tot["speculated"],
                "drained": eng_m.in_flight(st) == 0,
                "unclean": unclean_counters(tot)}
        g, d = recs["global"], recs["device"]
        # the strict win is only claimable when the global vote actually
        # rolled work back (a straggler-free smoke drain has no waste for
        # the per-device verdict to reduce).
        if g["rolled_back_device_windows"]:
            assert (d["rolled_back_device_windows"]
                    < g["rolled_back_device_windows"]) or \
                   (d["epochs_to_drain"] < g["epochs_to_drain"]), \
                (f"per-device commit never won: rolled back "
                 f"{d['rolled_back_device_windows']} device-windows vs "
                 f"global {g['rolled_back_device_windows']}, drained in "
                 f"{d['epochs_to_drain']} iters vs {g['epochs_to_drain']}")
        bad = {}
        for rec in recs.values():
            for k, v in rec["unclean"].items():
                bad[k] = bad.get(k, 0) + v
        drained = all(rec["drained"] for rec in recs.values())
        print(json.dumps({"ev_s": d["ev_s"], "n": base["n"],
                          "modes": recs, "unclean": bad,
                          "rollback_reduction":
                              g["rolled_back_device_windows"]
                              - d["rolled_back_device_windows"],
                          "drained": drained, "bound_hit": not drained,
                          "epochs_run": max(rec["epochs_run"]
                                            for rec in recs.values())}))
        raise SystemExit(0)

    if spec.get("campaign"):
        # campaign rung: R replication seeds of the SAME draining simulation,
        # driven (a) one fused drain per seed (the PR6 state of the art) and
        # (b) all R stacked through ONE replication-vmapped while_loop
        # (run_replicated_drained).  dispatches-per-campaign is the honest
        # metric — the vmapped drive must hit exactly 1 — and per-seed
        # processed totals must agree across drives (each replication is
        # leaf-exact vs its own independent drain by construction).
        # Execution layout for the stacked drive: when the campaign has more
        # replications than devices, shard the REPLICATION axis instead of
        # the object axis (rep_shards=D on a single-device engine mesh) —
        # each replication runs collective-free on its own device, which
        # beats D-way object sharding whenever one replication fits a
        # device (the a2a/allgather sync per epoch costs more than the
        # whole single-device step at these object counts).
        # The rep-sharded engine also right-sizes its static capacities to
        # ONE replication's traffic (spec key rep_engine_kw; the ladder's
        # caps are sized for 4-way object-sharded device traffic and their
        # slack is pure per-epoch fixed cost — the extract sort alone walks
        # bucket_cap slots per object per epoch).  Any under-sizing trips
        # the overflow counters and fails the rung, and the per-seed
        # processed-equality assert below holds both drives to identical
        # event flow.
        E, R = spec["epochs"], spec["reps"]
        seeds = list(range(R))
        rep_kw = spec.get("rep_engine_kw", {})
        if D > 1 and R % D == 0:
            eng_v = ParsirEngine(model, EngineConfig(**dict(ckw, **rep_kw)),
                                 mesh=Mesh(np.array(jax.devices()[:1]),
                                           (AXIS,)),
                                 rep_shards=D)
        else:
            eng_v = eng

        def drive(mode):
            if mode == "host_loop":
                per, infl, disp, dt, bad, epochs = [], 0, 0, 0.0, {}, 0
                for s in seeds:
                    st = eng.init(seed=s)
                    d0 = eng.dispatches
                    t0 = time.perf_counter()
                    st = eng.run_until_drained(st, E)
                    jax.block_until_ready(st)
                    dt += time.perf_counter() - t0
                    disp += eng.dispatches - d0
                    tot = eng.totals(st)
                    per.append(tot["processed"])
                    infl += eng.in_flight(st)
                    epochs = max(epochs, int(np.asarray(st.epoch)[0]))
                    for k, v in unclean_counters(tot).items():
                        bad[k] = bad.get(k, 0) + v
                return per, infl, disp, dt, bad, epochs
            st = eng_v.init_replicated(seeds)
            d0 = eng_v.dispatches
            t0 = time.perf_counter()
            st = eng_v.run_replicated_drained(st, E)
            jax.block_until_ready(st)
            dt = time.perf_counter() - t0
            disp = eng_v.dispatches - d0
            totr = eng_v.totals_replicated(st)
            per = [t["processed"] for t in totr]
            infl = int(eng_v.in_flight_replicated(st).sum())
            bad = {}
            for t in totr:
                for k, v in unclean_counters(t).items():
                    bad[k] = bad.get(k, 0) + v
            epochs = int(np.asarray(st.epoch)[:, 0].max())
            return per, infl, disp, dt, bad, epochs

        modes, per_seed, unclean, infl_total = {}, {}, {}, 0
        epochs_run = 0
        for mode in ("host_loop", "vmapped"):
            drive(mode)                                   # compile pass
            per, infl, disp, dt, bad, epochs = drive(mode)
            per_seed[mode] = per
            unclean.update(bad)
            infl_total += infl
            epochs_run = max(epochs_run, epochs)
            modes[mode] = {"dispatches_per_campaign": disp, "dt": dt,
                           "ev_s": sum(per) / dt}
        assert per_seed["host_loop"] == per_seed["vmapped"], \
            f"drives diverged per seed: {per_seed}"
        assert modes["vmapped"]["dispatches_per_campaign"] == 1, modes
        drained = infl_total == 0
        print(json.dumps({"ev_s": modes["vmapped"]["ev_s"],
                          "n": sum(per_seed["vmapped"]),
                          "replications": R,
                          "rep_shards": eng_v.rep_shards,
                          "rep_engine_kw": rep_kw,
                          "per_seed": per_seed["vmapped"],
                          "speedup_vs_host_loop":
                              modes["vmapped"]["ev_s"]
                              / modes["host_loop"]["ev_s"],
                          "modes": modes, "unclean": unclean,
                          "drained": drained, "bound_hit": not drained,
                          "epochs_run": epochs_run}))
        raise SystemExit(0)

    if spec.get("fused_drain"):
        # dispatch-ladder rung: the same simulation window driven three ways
        # — one host dispatch per epoch, fixed-size fused chunks, and the
        # whole window as ONE lax.while_loop dispatch (run_until_drained).
        # dispatches-per-simulation is the honest metric on CPU, where host
        # dispatch overhead swamps compute; processed totals must agree
        # across all three (drained state is a step fixpoint).
        E, C = spec["epochs"], spec.get("chunk", 6)

        def drive(mode):
            st = eng.init()
            d0 = eng.dispatches
            t0 = time.perf_counter()
            if mode == "host_stepped":
                for _ in range(E):
                    st = eng.step(st)
            elif mode == "fixed_chunks":
                for lo in range(0, E, C):
                    st = eng.run(st, min(C, E - lo))
            else:
                st = eng.run_until_drained(st, E)
            jax.block_until_ready(st)
            return st, eng.dispatches - d0, time.perf_counter() - t0

        modes, processed = {}, {}
        for mode in ("host_stepped", "fixed_chunks", "fused_drain"):
            drive(mode)                       # compile pass
            st, disp, dt = drive(mode)        # measured pass
            tot = eng.totals(st)
            processed[mode] = tot["processed"]
            modes[mode] = {"dispatches_per_simulation": disp, "dt": dt,
                           "ev_s": tot["processed"] / dt}
        assert len(set(processed.values())) == 1, \
            f"drive modes diverged: {processed}"
        assert modes["fused_drain"]["dispatches_per_simulation"] == 1, modes
        tot["rebalances"] //= D
        drained = eng.in_flight(st) == 0
        print(json.dumps({"ev_s": modes["fused_drain"]["ev_s"],
                          "n": processed["fused_drain"], "stats": tot,
                          "unclean": unclean_counters(tot), "modes": modes,
                          "drained": drained, "bound_hit": not drained,
                          "epochs_run": int(np.asarray(st.epoch)[0])}))
        raise SystemExit(0)

    st = eng.run(eng.init(), spec.get("warm", 6))
    base = eng.totals(st)["processed"]
    # structural schedule cost of the warmed-up epoch, summed over devices:
    # the dense rounds grid executes max_depth x n_local_max lanes per device
    # whether occupied or not; packing executes ~the events present.  This is
    # the padded-row-tax proxy a wide-SIMD accelerator would feel directly —
    # CPU wall-clock mostly measures loop dispatch instead.
    occ = eng.occupancy(st)
    lanes = {"padded_lanes_epoch": int(occ["padded_lanes"].sum()),
             "packed_lanes_epoch": int(occ["packed_lanes"].sum()),
             "n_local_max": int(occ["n_local_max"])}
    t0 = time.perf_counter()
    st = eng.run(st, spec["epochs"])
    st.stats.processed.block_until_ready()
    dt = time.perf_counter() - t0
    tot = eng.totals(st)
    n = tot["processed"] - base
    # structural exchange bytes per epoch: record bytes are 17B/event
    # (dst4 ts4 seed4 payload4 valid1)
    rec_b = 17
    if spec["route"] == "allgather":
        ex = D * D * spec["route_cap"] * rec_b          # D bufs to D devices
    else:
        ex = D * spec["route_cap"] * rec_b              # pairwise a2a
    def state_bytes():
        # per-object state bytes, generic over workloads: one object's pytree.
        st0 = model.init_object_state(np.arange(1))
        return sum(np.asarray(l).nbytes for l in jax.tree.leaves(st0)) + 8
    if spec.get("steal"):
        loan_b = 8 * (cfg.bucket_cap * 12 + state_bytes())
        ex += 2 * D * D * loan_b                        # publish + return
    if spec.get("rebalance_every"):
        # migration all_gather: up to K whole rows (calendar + state) per
        # device, broadcast D-wide, amortized over the rebalance period.
        K = 2 * (cfg.migrate_cap // 2)
        row_b = (cfg.n_buckets * cfg.bucket_cap * 12 + cfg.n_buckets * 4
                 + state_bytes() + 4)
        ex += D * D * K * row_b // spec["rebalance_every"]
    # rebalances: every device reports each firing — normalize to firings so
    # the recorded counter partitions like processed/stolen/migrated do.
    tot["rebalances"] //= D
    print(json.dumps({"ev_s": n / dt, "n": n, "dt": dt, "stats": tot,
                      "unclean": unclean_counters(tot),
                      "exchange_bytes_per_epoch": ex, "lanes": lanes}))
""")

BASE = dict(o=512, m=40, s=256, la=0.5, dist="exponential", route_cap=8192,
            epochs=30)

# workload-specific bench-scale extras forwarded to make().
BENCH_MODEL_KW = {
    # at bench scale, spread the hot set so per-object batches fit bucket_cap
    # (same skew point as the uniform-phold skew ladder rows).
    "phold-hotspot": dict(hot_objects=32, hot_prob=96, hot_boost=1),
    "queueing": dict(n_jobs=2048),
    "cluster": dict(n_rings=64),
    # open network: n_objects is split ~evenly across the five roles by
    # make(); unbounded sources keep the arrival stream going all run.
    "open-queueing": dict(),
    # enough seeds/susceptibles that the epidemic is still growing (not
    # burned out) across the measured window.
    "epidemic": dict(pop=64, n_seeds=32, trans_p=128),
    # the natively hotspot-prone load (PR 5): a hot head with extra
    # generator streams on a finer arrival grid — what the placement
    # ladder below rebalances.
    "wireless": dict(n_channels=8, hot_cells=32, hot_shift=3,
                     hot_streams=2, handoff_p=112),
}


def run_child(devices: int, workload: str, **spec):
    model_kw = dict(BENCH_MODEL_KW.get(workload, {}),
                    **spec.pop("model_kw", {}))
    merged = dict(BASE, devices=devices, workload=workload,
                  model_kw=model_kw, **spec)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                        f"platform_device_count={devices}").strip()
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", _CHILD, json.dumps(merged)],
                       env=env, capture_output=True, text=True, timeout=2400)
    if r.returncode != 0:
        return {"error": r.stderr[-300:]}
    return json.loads(r.stdout.strip().splitlines()[-1])


def build_ladder(workload: str):
    ladder = [
        ("baseline_paper_faithful", dict(route="allgather")),
        ("it1_route_a2a", dict(route="a2a")),
        ("it2_epoch_half_L", dict(route="a2a", epoch_len=0.25)),
        # the width-packed scheduler (PR 4): process only the occupied event
        # slots — the anti-padded-row-tax rung, same bits by construction.
        ("it3_width_packed", dict(route="a2a", batch_impl="packed")),
        # the fused on-device loop (PR 6): the same window driven host-stepped
        # / fixed-chunked / as ONE while_loop dispatch — the rung reports
        # dispatches-per-simulation per mode (the fused mode must hit 1).
        ("it4_fused_drain", dict(route="a2a", fused_drain=True)),
    ]
    if workload == "phold":
        # uniform PHOLD needs explicit hot params to produce skew.
        ladder += [
            ("skew_baseline_nosteal", dict(route="a2a", hot_o=32, hot_p=96,
                                           bucket_cap=512)),
            ("skew_it3_steal", dict(route="a2a", hot_o=32, hot_p=96,
                                    bucket_cap=512, steal=True)),
        ]
    else:
        # phold-hotspot is skewed by construction; queueing/cluster measure
        # the stealing overhead on their native (im)balance.
        ladder += [
            ("steal_off", dict(route="a2a", bucket_cap=512)),
            ("steal_on", dict(route="a2a", bucket_cap=512, steal=True)),
        ]
    if workload in ("phold-hotspot", "wireless"):
        # the placement ladder: static knapsack from the model's weight hint,
        # runtime rebalancing, and rebalancing composed with loans — measured
        # against the equal-placement `steal_off` rung above.  Each placement
        # is measured under both batch impls: the `_packed` twins quantify
        # how much of the uneven-placement loss is the padded-row tax the
        # width-packer removes (BENCH_pr3 showed weighted/adaptive losing to
        # equal exactly by that tax).  wireless (PR 5) runs the same ladder
        # on a model-native hotspot — skew from the workload's own physics
        # rather than a synthetic routing knob.
        pl = dict(route="a2a", bucket_cap=512, placement_slack=1.5)
        ladder += [
            ("packed_equal", dict(route="a2a", bucket_cap=512,
                                  batch_impl="packed")),
            ("placement_weighted", dict(pl, placement="weighted")),
            ("placement_weighted_packed",
             dict(pl, placement="weighted", batch_impl="packed")),
            ("placement_adaptive", dict(pl, placement="adaptive",
                                        rebalance_every=4, migrate_cap=64)),
            ("placement_adaptive_packed",
             dict(pl, placement="adaptive", rebalance_every=4,
                  migrate_cap=64, batch_impl="packed")),
            ("placement_adaptive_steal",
             dict(pl, placement="adaptive", rebalance_every=4,
                  migrate_cap=64, steal=True)),
        ]
    if workload == "wireless":
        # a *draining* simulation (per-cell arrival budgets exhaust, calls
        # complete, the network empties): the fused loop completes the whole
        # thing — init to empty — in exactly one dispatch, while the host-
        # stepped drive pays one dispatch per epoch of the same window.
        ladder.append(("it4_drain_budget",
                       dict(route="a2a", fused_drain=True, epochs=256,
                            expect_drained=True,
                            model_kw=dict(max_calls=4))))
        # the campaign rung (PR 7): 32 replication seeds of the draining
        # simulation above, run (a) one fused drain per seed and (b) all 32
        # stacked through ONE replication-vmapped while_loop — the whole
        # sweep in a single XLA dispatch.  `epochs` is the drain *bound*, not
        # a window: every replication must actually drain (expect_drained).
        ladder.append(("it5_campaign",
                       dict(route="a2a", campaign=True, reps=32, epochs=256,
                            expect_drained=True,
                            model_kw=dict(max_calls=4),
                            rep_engine_kw=dict(bucket_cap=64, route_cap=2048,
                                               fallback_cap=4096))))
        # the speculation rung (PR 9): the draining simulation above driven
        # at opt_window 0/1/2/4 — epochs-to-drain (while-loop iterations)
        # must fall strictly below the conservative drain at every W, bits
        # identical, rollbacks reported next to the win they price.
        ladder.append(("it6_speculation",
                       dict(route="a2a", speculation=True,
                            windows=[0, 1, 2, 4], epochs=256,
                            expect_drained=True,
                            model_kw=dict(max_calls=4))))
        # the per-device-commit rung (PR 10): the draining simulation at a
        # fixed window, global all-or-nothing vote vs per-device verdict —
        # rolled-back device-windows must strictly shrink, bits identical.
        ladder.append(("it7_per_device_commit",
                       dict(route="a2a", commit_compare=True, opt_window=2,
                            epochs=256, expect_drained=True,
                            model_kw=dict(max_calls=4))))
    if workload == "epidemic":
        # epidemic burns out (finite susceptible pool, absorbing recovered
        # patches) once pop/trans_p stop sustaining the chain — the second,
        # structurally different draining load for the speculation rung:
        # state-dependent arity and ring-local traffic instead of the
        # wireless hotspot.
        ladder.append(("it6_speculation",
                       dict(route="a2a", speculation=True,
                            windows=[0, 1, 2, 4], o=128, epochs=512,
                            expect_drained=True,
                            model_kw=dict(pop=8, n_seeds=16, trans_p=96))))
        # ring-local traffic is the adversarial case for the global vote:
        # stragglers only cross at patch boundaries, so most windows have a
        # straggler-free majority the per-device verdict keeps committed.
        ladder.append(("it7_per_device_commit",
                       dict(route="a2a", commit_compare=True, opt_window=2,
                            o=128, epochs=512, expect_drained=True,
                            model_kw=dict(pop=8, n_seeds=16, trans_p=96))))
    ladder.append(("ltf_reference_scheduler",
                   dict(route="a2a", sched="ltf", epochs=10, warm=2)))
    return ladder


#: tiny CI-smoke scale: every ladder rung must *run* (drivers rot silently
#: otherwise), wall time a few seconds per rung.
SMOKE = dict(o=64, m=8, s=64, epochs=6, warm=2, route_cap=4096)


def build_smoke_ladder(workload: str):
    out = []
    for n, s in build_ladder(workload):
        merged = dict(s, **SMOKE)
        if s.get("expect_drained"):
            # `epochs` on a draining rung is the drain *bound*, not the
            # measured window — clamping it to the smoke window would turn
            # the rung into a guaranteed bound-hit failure.
            merged["epochs"] = s["epochs"]
        if "reps" in s:
            merged["reps"] = min(s["reps"], 8)
        if "windows" in s:
            # one compile per window width — smoke keeps the conservative
            # baseline plus a single speculative width.
            merged["windows"] = [0, 2]
        out.append((n, merged))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--workload", default="phold",
                    help="registered zoo workload (repro/workloads)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config, exit nonzero on any rung error "
                         "(CI guard against benchmark-driver rot)")
    ap.add_argument("--rungs", default=None,
                    help="comma-separated rung names to run (default: the "
                         "full ladder); unknown names fail fast")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    D = args.devices
    out = args.out or ("artifacts/pdes_perf.json" if args.workload == "phold"
                       else f"artifacts/pdes_perf_{args.workload}.json")

    failed = []
    results = {}
    ladder = (build_smoke_ladder if args.smoke else build_ladder)(args.workload)
    if args.rungs:
        want = set(args.rungs.split(","))
        if (unknown := want - {n for n, _ in ladder}):
            raise SystemExit(f"[pdes_perf] unknown rungs {sorted(unknown)} — "
                             f"ladder has {[n for n, _ in ladder]}")
        ladder = [(n, s) for n, s in ladder if n in want]
    for name, spec in ladder:
        print(f"[pdes_perf:{args.workload}] {name}...", flush=True)
        results[name] = run_child(D, args.workload, **spec)
        r = results[name]
        if "error" in r:
            print(f"  ERROR {r['error']}")
            failed.append(name)
        else:
            # the full clean-run contract (repro.testing.clean): the child
            # reports every nonzero must-be-zero counter — this parent used
            # to check only 3 of the 6 (fb_overflow/route_overflow dropped
            # events without failing the rung).
            clean = not r.get("unclean")
            if spec.get("campaign"):
                disp = {m: v["dispatches_per_campaign"]
                        for m, v in r["modes"].items()}
                print(f"  {r['ev_s']:,.0f} ev/s aggregate over "
                      f"{r['replications']} replications  "
                      f"dispatches/campaign {disp}  "
                      f"speedup={r['speedup_vs_host_loop']:.2f}x "
                      f"drained={r['drained']} clean={clean}")
            elif spec.get("commit_compare"):
                line = "  ".join(
                    f"{m['opt_commit']}: rb={m['rolled_back_device_windows']}"
                    f" cm={m['committed_device_windows']}"
                    f" iters={m['epochs_to_drain']}"
                    for m in r["modes"].values())
                print(f"  {r['ev_s']:,.0f} ev/s  {line}  "
                      f"(-{r['rollback_reduction']} rolled-back "
                      f"device-windows)  drained={r['drained']} "
                      f"clean={clean}")
            elif spec.get("speculation"):
                line = "  ".join(
                    f"W={w['opt_window']}: {w['epochs_to_drain']} iters "
                    f"(rb={w['rollbacks']})" for w in r["windows"].values())
                print(f"  {r['ev_s']:,.0f} ev/s best  {line}  "
                      f"drained={r['drained']} clean={clean}")
            elif "modes" in r:
                disp = {m: v["dispatches_per_simulation"]
                        for m, v in r["modes"].items()}
                print(f"  {r['ev_s']:,.0f} ev/s  dispatches/simulation "
                      f"{disp}  epochs={r['epochs_run']} "
                      f"drained={r['drained']} clean={clean}")
            else:
                print(f"  {r['ev_s']:,.0f} ev/s  exchange "
                      f"{r['exchange_bytes_per_epoch']/1e6:.2f} MB/epoch "
                      f"stolen={r['stats']['stolen']} "
                      f"rebalances={r['stats']['rebalances']} clean={clean}")
            if not clean:
                print(f"  UNCLEAN {r['unclean']} — run is invalid")
                failed.append(name)
            if spec.get("expect_drained") and r.get("bound_hit"):
                # a draining rung that hit its epoch bound reported ev/s for
                # a simulation that never finished — not a result.
                print(f"  BOUND HIT at epochs={spec['epochs']} with events "
                      f"still in flight — expected a full drain")
                failed.append(name)
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    print(f"[pdes_perf] wrote {out}")
    if failed:
        raise SystemExit(f"[pdes_perf] FAILED rungs: {failed}")


if __name__ == "__main__":
    main()
