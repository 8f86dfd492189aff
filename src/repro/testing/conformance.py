"""Differential conformance harness: any workload × any engine config.

The engine's correctness contract (engine.py module docstring) is checked
against the sequential numpy oracle (:mod:`repro.core.ref_engine`) in four
parts:

  1. **clean counters** — every overflow/causality/lookahead counter in
     ``Stats`` is zero (a conservative engine never silently drops/reorders;
     the checker is the shared :func:`repro.testing.assert_clean`, the same
     contract the CLI drivers enforce);
  2. **processed count** — equals the oracle's;
  3. **pending multiset** — the (dst, seed) multiset still parked in the
     calendar + fallback equals the oracle's final event heap.  Because all
     model randomness is counter-based, the full event tree is a pure
     function of the initial seeds, so (2) + (3) pin down the processed
     record multiset without the engine keeping a processed log;
  4. **bit-exact state** — for dyadic workloads the final per-object state
     pytree matches the oracle bit-for-bit.

``SWEEP`` names the engine-config axes of the zoo: scheduler (batch | ltf),
routing (allgather | a2a), stealing on/off, per-object batch implementation
(vmap rounds | width-packed tiles | Pallas model kernel — ``packed`` is the
"same bits, different schedule" axis and must stay bit-exact for every
workload, composition and tile width), fractional epoch length, placement
(equal | weighted | adaptive — the oracle knows nothing of devices, so every
packing, including runtime rebalancing with object migration, must reach the
identical drained state), and speculation (``opt_window`` > 0 — windows past
the safe horizon must commit or roll back to exactly the conservative bits,
so the oracle contract is unchanged whether a run speculated or not).  The
checks are emission-arity-agnostic: workloads with fan-out (``max_out > 1``)
and absorption (events that emit nothing — the pending multiset *shrinks*)
run through the identical assertions, since the generalized oracle
(:func:`repro.core.ref_engine.run_sequential`) iterates emitted-event lists.

The module doubles as the multi-device driver (device count is locked at
first JAX init, so multi-device sweeps run in a subprocess)::

  XLA_FLAGS=--xla_force_host_platform_device_count=4 PYTHONPATH=src \\
    python -m repro.testing.conformance --workload queueing --devices 4 \\
        --configs batch-a2a,steal-allgather,steal-a2a [--drain]

``--drain`` swaps the host-chunked ``run`` for the fused on-device drain
loop (:meth:`ParsirEngine.run_until_drained`) under identical assertions —
the equivalence face of the fused loop, sweepable across every config axis.
"""
from __future__ import annotations

import argparse
from typing import Any

import numpy as np

from ..core.engine import EngineConfig, ParsirEngine
from ..core.ref_engine import SequentialResult, run_sequential
from ..workloads.registry import all_workloads, conformance_spec, get_workload
from .clean import assert_clean

#: named engine-config points of the conformance sweep.  Values are
#: EngineConfig overrides; the two pseudo-keys are handled by the harness:
#: ``epoch_len_frac`` scales epoch_len off the model lookahead (the epoch
#: count is rescaled so the simulated horizon is unchanged), ``batch_impl``
#: = "model" requires the workload's ``supports_batch_impl``.
SWEEP: dict[str, dict] = {
    "batch-allgather": dict(),
    "batch-a2a": dict(route="a2a"),
    "ltf": dict(scheduler="ltf"),
    "steal-allgather": dict(steal=True, steal_cap=2, claim_cap=4),
    "steal-a2a": dict(route="a2a", steal=True, steal_cap=2, claim_cap=4),
    "epoch-fraction": dict(epoch_len_frac=0.5),
    "batch-model": dict(batch_impl="model"),
    # batch_impl axis: the width-packed scheduler must be "same bits,
    # different schedule" — a tiny tile forces many tiles per round (and
    # round-boundary padding) at conformance scale, where the default tile
    # would collapse to one tile per round.
    "batch-packed": dict(batch_impl="packed", pack_tile=4),
    "packed-a2a": dict(route="a2a", batch_impl="packed"),
    "steal-packed": dict(route="a2a", batch_impl="packed", pack_tile=4,
                         steal=True, steal_cap=2, claim_cap=4),
    "packed-adaptive": dict(batch_impl="packed", pack_tile=4,
                            placement="adaptive", rebalance_every=8,
                            migrate_cap=8),
    # placement axis: the same drained state must fall out of every packing
    # of objects onto devices (weighted knapsack, runtime rebalancing, and
    # rebalancing composed with loans) — the oracle knows nothing of devices.
    "weighted": dict(placement="weighted"),
    "adaptive": dict(placement="adaptive", rebalance_every=8, migrate_cap=8),
    "adaptive-a2a": dict(route="a2a", placement="adaptive",
                         rebalance_every=8, migrate_cap=8),
    "steal-adaptive": dict(route="a2a", placement="adaptive",
                           rebalance_every=8, migrate_cap=8,
                           steal=True, steal_cap=2, claim_cap=4),
    # speculation axis (bounded optimism, pipeline/speculate.py): windows up
    # to opt_window epochs past the safe horizon must either commit or roll
    # back to exactly the conservative bits — the oracle knows nothing of
    # speculation, so every assertion below is unchanged.  W=4 needs
    # n_buckets >= 6 (every conformance engine_kw has >= 8).  The default
    # commit locality is per-device (only devices that received a straggler
    # restore their shadow); spec-global pins the PR 9 atomic vote so both
    # verdict modes stay under oracle proof.  Stealing composes under the
    # global vote only (loans execute on the borrower — EngineConfig rejects
    # steal × device-commit fail-fast); adaptive placement composes under
    # either (windows stop short of rebalance firing epochs).  spec-inject
    # drives the deterministic straggler-injection harness: every 2nd window
    # is forced down the rollback path — at ANY device count, D=1 included —
    # and the drained bits must still match the oracle exactly.
    "spec-w1": dict(opt_window=1),
    "spec-w2": dict(opt_window=2),
    "spec-w4": dict(opt_window=4),
    "spec-a2a": dict(route="a2a", opt_window=2),
    "spec-packed-a2a": dict(route="a2a", batch_impl="packed", pack_tile=4,
                            opt_window=2),
    "spec-weighted": dict(placement="weighted", opt_window=2),
    "spec-global": dict(opt_window=2, opt_commit="global"),
    "spec-steal": dict(route="a2a", steal=True, steal_cap=2, claim_cap=4,
                       opt_window=2, opt_commit="global"),
    "spec-adaptive": dict(placement="adaptive", rebalance_every=8,
                          migrate_cap=8, opt_window=2),
    "spec-inject": dict(opt_window=2, inject_straggler_every=2),
}


def engine_pending(eng: ParsirEngine, state) -> np.ndarray:
    """(dst, seed) multiset of events in flight (calendar + fallback), sorted.

    Calendar leading dims concatenate per-device padded rows; the engine maps
    each row to its backing global id (pad rows hold no events by invariant —
    asserted here, since a counted event on a dead row would otherwise be
    silently re-labeled).
    """
    cnt = np.asarray(state.cal.cnt)                  # [D*M, N]
    seed = np.asarray(state.cal.seed)                # [D*M, N, C]
    R, N, C = seed.shape
    gid, live_row = eng.global_row_of(state)
    assert not np.any(cnt[~live_row]), "events parked on a pad row"
    live = np.arange(C)[None, None, :] < cnt[:, :, None]
    obj = np.broadcast_to(gid[:, None, None], live.shape)
    dsts = [obj[live].astype(np.uint64)]
    seeds = [seed[live].astype(np.uint64)]

    fbv = np.asarray(state.fb.events.valid)
    dsts.append(np.asarray(state.fb.events.dst)[fbv].astype(np.uint64))
    seeds.append(np.asarray(state.fb.events.seed)[fbv].astype(np.uint64))

    rec = np.stack([np.concatenate(dsts), np.concatenate(seeds)], axis=1)
    return rec[np.lexsort((rec[:, 1], rec[:, 0]))] if rec.size \
        else rec.reshape(0, 2)


def stack_oracle_state(obj_state: list[dict]) -> dict[str, np.ndarray]:
    """List-of-per-object-dicts (oracle) → dict-of-arrays (engine layout)."""
    keys = obj_state[0].keys()
    return {k: np.stack([np.asarray(s[k]) for s in obj_state])
            for k in keys}


def axes_of(cfg: EngineConfig, n_devices: int) -> str:
    """The sweep coordinates of an engine config, for failure messages.

    Every divergence report must say *which axis point* diverged — a bare
    assert in a workloads × configs × devices sweep is otherwise
    unattributable from the failure line alone.
    """
    impl = cfg.batch_impl
    if impl == "packed":
        impl += f"(tile={cfg.pack_tile})"
    opt = f"opt_window={cfg.opt_window}"
    if cfg.opt_window:
        opt += f"(commit={cfg.opt_commit})"
    return (f"scheduler={cfg.scheduler} batch_impl={impl} "
            f"route={cfg.route} steal={cfg.steal} "
            f"placement={cfg.placement} epoch_len={cfg.epoch_len:g} "
            f"{opt} D={n_devices}")


def _assert_vs_oracle(eng: ParsirEngine, st, tot: dict,
                      ref: SequentialResult, dyadic: bool,
                      ctx: str) -> np.ndarray:
    """The oracle-agreement assertions shared by the scalar and replicated
    conformance faces: processed count, pending (dst, seed) multiset, and —
    for dyadic workloads — bit-exact object state.  Returns the pending
    records."""
    assert tot["processed"] == ref.total_processed, \
        f"{ctx} processed {tot['processed']} != oracle {ref.total_processed}"

    pend = engine_pending(eng, st)
    ref_pend = ref.pending_sorted()
    assert pend.shape == ref_pend.shape, \
        f"{ctx} pending count {pend.shape[0]} != oracle {ref_pend.shape[0]}"
    np.testing.assert_array_equal(
        pend, ref_pend, err_msg=f"{ctx} pending (dst, seed) multiset")

    if dyadic:
        want = stack_oracle_state(ref.obj_state)
        obj = eng.global_object_state(st)
        assert set(want) == set(obj), (ctx, set(want), set(obj))
        for k in want:
            np.testing.assert_array_equal(
                obj[k], want[k], err_msg=f"{ctx} object state [{k}]")
    return pend


def run_conformance(model: Any, overrides: dict, *, n_epochs: int,
                    engine_kw: dict | None = None, mesh=None,
                    dyadic: bool = True,
                    ref: SequentialResult | None = None,
                    label: str = "", drain: bool = False) -> dict:
    """Run ``model`` through the engine under ``overrides`` and assert full
    agreement with the sequential oracle.  Returns a report dict (totals,
    pending count, the oracle result for reuse).  ``label`` (e.g.
    ``"phold/batch-packed"``) prefixes every failure message alongside the
    resolved config axes, so a sweep failure names its diverging point.

    ``drain=True`` runs the horizon through the fused on-device drain loop
    (:meth:`ParsirEngine.run_until_drained` bounded by ``n_epochs``) instead
    of the host-chunked ``run`` — every assertion is unchanged, because a
    drained state is a fixpoint of the step: stopping early at the drain
    epoch leaves exactly the state (and stats) the full horizon would, and
    a non-draining workload runs the identical ``n_epochs`` epochs."""
    overrides = dict(overrides)
    lookahead = model.params.lookahead
    frac = overrides.pop("epoch_len_frac", None)
    kw = dict(lookahead=lookahead)
    kw.update(engine_kw or {})
    kw.update(overrides)
    if frac is not None:
        kw["epoch_len"] = lookahead * frac
        n_epochs = int(round(n_epochs / frac))
    cfg = EngineConfig(**kw)

    eng = ParsirEngine(model, cfg, mesh=mesh)
    ctx = f"[{label + ': ' if label else ''}{axes_of(cfg, eng.D)}]"
    st = (eng.run_until_drained(eng.init(), n_epochs) if drain
          else eng.run(eng.init(), n_epochs))
    tot = eng.totals(st)

    assert_clean(tot, context=ctx)
    if cfg.placement == "adaptive":
        # per-device counters: every device reports each firing, so the sum
        # is (firings × D) — nonzero iff the stage actually ran.
        assert tot["rebalances"] > 0, \
            f"{ctx} adaptive placement never rebalanced: {tot}"

    if ref is None:
        ref = run_sequential(model, n_epochs, cfg.epoch_len)
    pend = _assert_vs_oracle(eng, st, tot, ref, dyadic, ctx)

    return {"totals": tot, "pending": int(pend.shape[0]), "ref": ref,
            "config": kw, "n_epochs": n_epochs}


def check_workload(name: str, config: str, *, mesh=None,
                   ref_cache: dict | None = None,
                   model_overrides: dict | None = None,
                   engine_overrides: dict | None = None,
                   drain: bool = False) -> dict:
    """Conformance-check a registered workload under a named SWEEP config."""
    spec = conformance_spec(name)
    overrides = dict(SWEEP[config])
    if overrides.get("batch_impl") == "model" \
            and not spec["supports_batch_impl"]:
        raise ValueError(f"workload {name} has no process_batch")
    model = get_workload(name, **dict(spec["model_kw"],
                                      **(model_overrides or {})))
    engine_kw = dict(spec["engine_kw"], **(engine_overrides or {}))

    ref = None
    if ref_cache is not None:
        # the oracle run depends on (workload, overrides, horizon), not the
        # engine routing/scheduling config — amortize it across the sweep.
        frac = overrides.get("epoch_len_frac")
        key = (name, spec["n_epochs"], frac,
               tuple(sorted((model_overrides or {}).items())),
               tuple(sorted((engine_overrides or {}).items())))
        ref = ref_cache.get(key)
    report = run_conformance(model, overrides, n_epochs=spec["n_epochs"],
                             engine_kw=engine_kw, mesh=mesh,
                             dyadic=spec["dyadic"], ref=ref,
                             label=f"{name}/{config}", drain=drain)
    if ref_cache is not None:
        ref_cache[key] = report["ref"]
    return report


def check_workload_replicated(name: str, config: str, *, replications: int,
                              mesh=None, rep_shards=None) -> dict:
    """Conformance-check the replication-vmapped fused drain.

    Runs ``replications`` seeds of the workload stacked through ONE
    ``run_replicated_drained`` dispatch (bounded by the workload's
    conformance horizon), then holds **every** replication slice to the full
    scalar contract against its *own* seeded sequential oracle: clean
    counters, processed count, pending multiset, bit-exact dyadic state.
    This is the strongest correctness face of the campaign engine — each
    replication must be indistinguishable from having run alone.

    ``rep_shards=W`` checks the replication-sharded layout instead (mesh
    must be single-device): the R axis is split across W devices and each
    replication steps collective-free inside its shard — same contract,
    same oracles.
    """
    spec = conformance_spec(name)
    overrides = dict(SWEEP[config])
    if overrides.get("batch_impl") == "model" \
            and not spec["supports_batch_impl"]:
        raise ValueError(f"workload {name} has no process_batch")
    model = get_workload(name, **spec["model_kw"])
    n_epochs = spec["n_epochs"]
    lookahead = model.params.lookahead
    frac = overrides.pop("epoch_len_frac", None)
    kw = dict(lookahead=lookahead)
    kw.update(spec["engine_kw"])
    kw.update(overrides)
    if frac is not None:
        kw["epoch_len"] = lookahead * frac
        n_epochs = int(round(n_epochs / frac))
    cfg = EngineConfig(**kw)

    eng = ParsirEngine(model, cfg, mesh=mesh, rep_shards=rep_shards)
    seeds = list(range(replications))
    st = eng.run_replicated_drained(eng.init_replicated(seeds), n_epochs)
    totals = eng.totals_replicated(st)

    processed = []
    for r, seed in enumerate(seeds):
        ctx = (f"[{name}/{config} R={replications} rep={r} seed={seed}: "
               f"{axes_of(cfg, eng.D)}]")
        tot = totals[r]
        assert_clean(tot, context=ctx)
        rep_st = eng.replication(st, r)
        ref = run_sequential(model, n_epochs, cfg.epoch_len, seed=seed)
        _assert_vs_oracle(eng, rep_st, tot, ref, spec["dyadic"], ctx)
        processed.append(tot["processed"])
    return {"processed": processed, "totals": totals, "config": kw,
            "n_epochs": n_epochs}


# ---------------------------------------------------------------------------
# subprocess driver (multi-device sweeps)
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True, choices=all_workloads())
    ap.add_argument("--configs", default="batch-allgather",
                    help="comma-separated SWEEP names, or 'all'")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--expect-stolen", action="store_true",
                    help="assert stats.stolen > 0 summed over steal configs")
    ap.add_argument("--expect-rebalances", type=int, default=0, metavar="N",
                    help="assert every adaptive config fired the rebalance "
                         "stage at least N times")
    ap.add_argument("--expect-rollbacks", action="store_true",
                    help="assert stats.rollbacks > 0 summed over speculation "
                         "(opt_window > 0) configs — the negative path: "
                         "stragglers actually hit the window and the engine "
                         "rolled back, yet every assertion above still held")
    ap.add_argument("--drain", action="store_true",
                    help="run each config through the fused on-device drain "
                         "loop (run_until_drained bounded by the workload's "
                         "n_epochs) instead of host-chunked run — same "
                         "assertions, one XLA dispatch")
    ap.add_argument("--replications", type=int, default=0, metavar="R",
                    help="run R seeds stacked through ONE replication-vmapped"
                         " fused drain (run_replicated_drained) and hold "
                         "every replication to the full scalar contract "
                         "against its own seeded oracle")
    ap.add_argument("--rep-shards", type=int, default=0, metavar="W",
                    help="with --replications: shard the R axis across W "
                         "devices (each replication collective-free on its "
                         "own device) instead of object-sharding — the "
                         "campaign throughput layout, same oracle contract")
    args = ap.parse_args(argv)
    if args.rep_shards and not args.replications:
        ap.error("--rep-shards requires --replications")

    import jax
    from jax.sharding import Mesh
    from ..core.engine import AXIS
    from ..launch.runtime import enable_compile_cache, too_few_devices

    enable_compile_cache()
    devs = jax.devices()
    if len(devs) < args.devices:
        ap.error(too_few_devices(len(devs), args.devices))
    mesh = Mesh(np.array(devs[:args.devices]), (AXIS,))

    names = list(SWEEP) if args.configs == "all" \
        else args.configs.split(",")
    unknown = [c for c in names if c not in SWEEP]
    if unknown:
        ap.error(f"unknown config(s) {unknown}; choose from {list(SWEEP)}")
    spec = conformance_spec(args.workload)
    ref_cache: dict = {}
    stolen = 0
    rollbacks = 0
    for config in names:
        if SWEEP[config].get("batch_impl") == "model" \
                and not spec["supports_batch_impl"]:
            print(f"SKIP {args.workload} {config} (no process_batch)")
            continue
        if args.replications:
            # rep-sharding runs each replication whole on one device: the
            # engine's own (object) mesh is single-device, W devices carry
            # the replication axis.
            rmesh = (Mesh(np.array(devs[:1]), (AXIS,)) if args.rep_shards
                     else mesh)
            rep = check_workload_replicated(
                args.workload, config, mesh=rmesh,
                replications=args.replications,
                rep_shards=args.rep_shards or None)
            layout = (f"rep_shards={args.rep_shards}" if args.rep_shards
                      else f"D={args.devices}")
            print(f"OK {args.workload} {config} {layout} "
                  f"R={args.replications} processed={rep['processed']}")
            continue
        report = check_workload(args.workload, config, mesh=mesh,
                                ref_cache=ref_cache, drain=args.drain)
        tot = report["totals"]
        if SWEEP[config].get("steal"):
            stolen += tot["stolen"]
        if SWEEP[config].get("opt_window"):
            rollbacks += tot["rollbacks"]
        if SWEEP[config].get("placement") == "adaptive" \
                and args.expect_rebalances:
            # `rebalances` sums the per-device counters: firings × D.
            fired = tot["rebalances"] // args.devices
            assert fired >= args.expect_rebalances, \
                (f"{config}: rebalance fired {fired} < "
                 f"{args.expect_rebalances} times")
        print(f"OK {args.workload} {config} D={args.devices} "
              f"processed={tot['processed']} pending={report['pending']} "
              f"stolen={tot['stolen']} rebalances={tot['rebalances']} "
              f"migrated={tot['migrated']} rollbacks={tot['rollbacks']} "
              f"speculated={tot['speculated']}")
    if args.expect_stolen:
        assert stolen > 0, "stealing never engaged across steal configs"
    if args.expect_rollbacks:
        assert rollbacks > 0, \
            "no speculation window ever rolled back across opt_window configs"
    print("CONFORMANCE PASS")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
