"""CLI simulation driver (the paper-kind end-to-end entry point).

  PYTHONPATH=src python -m repro.launch.simulate --workload phold \\
      --epochs 100 [--devices 2] [--scheduler ltf] [--route a2a] \\
      [--batch-impl packed] [--placement adaptive --rebalance-every 4] \\
      [--steal] [--drain] [--model-kw n_channels=2] [--verify]

Every choice-typed flag is driven by the live registries — the workload zoo
(:mod:`repro.workloads.registry`) and the pipeline stage names
(:mod:`repro.core.pipeline.names`) — so a newly registered workload, batch
implementation or placement shows up here without touching this file
(:mod:`repro.testing.docs_check` cross-checks that this stays true).

Exit contract: any nonzero overflow/causality counter is a **failed run**
(events were dropped or misordered; the perf line printed above it is
meaningless) and the process exits nonzero via the shared
:func:`repro.testing.assert_clean` checker.

``--drain`` completes the whole simulation as one fused on-device dispatch
(:meth:`ParsirEngine.run_until_drained` bounded by ``--epochs``) instead of
a fixed horizon; ``--verify`` cross-checks the final object state bit-exactly
against the sequential oracle for any workload under ``--dist dyadic``.
"""
from __future__ import annotations

import argparse
import time


def parse_kv(pairs: list[str]) -> dict:
    """``k=v`` strings → kwargs dict (python-literal values, else str)."""
    import ast
    out = {}
    for pair in pairs:
        if "=" not in pair:
            raise SystemExit(f"--model-kw expects k=v, got {pair!r}")
        k, v = pair.split("=", 1)
        try:
            out[k] = ast.literal_eval(v)
        except (SyntaxError, ValueError):
            out[k] = v
    return out


def main():
    from ..core.pipeline.names import (BATCH_IMPLS, PLACEMENTS, ROUTES,
                                       SELECTABLE_SCHEDULERS)
    from ..workloads.registry import all_workloads

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="phold", choices=all_workloads())
    ap.add_argument("--objects", type=int, default=512)
    ap.add_argument("--lookahead", type=float, default=0.5)
    ap.add_argument("--epoch-len", type=float, default=None)
    ap.add_argument("--dist", default="exponential",
                    choices=["exponential", "uniform24", "dyadic"])
    ap.add_argument("--model-kw", action="append", default=[],
                    metavar="K=V", help="extra workload make() override "
                    "(repeatable), e.g. --model-kw max_calls=8")
    ap.add_argument("--epochs", type=int, default=100,
                    help="epochs to run (--drain: the drain bound)")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--scheduler", default="batch",
                    choices=list(SELECTABLE_SCHEDULERS))
    ap.add_argument("--route", default="allgather", choices=list(ROUTES))
    ap.add_argument("--batch-impl", default="rounds",
                    choices=list(BATCH_IMPLS))
    ap.add_argument("--pack-tile", type=int, default=64)
    ap.add_argument("--steal", action="store_true")
    ap.add_argument("--placement", default="equal", choices=list(PLACEMENTS))
    ap.add_argument("--rebalance-every", type=int, default=0,
                    help="adaptive placement: epochs between rebalances")
    ap.add_argument("--migrate-cap", type=int, default=16)
    ap.add_argument("--placement-slack", type=float, default=2.0)
    ap.add_argument("--opt-window", type=int, default=0,
                    help="speculate up to W epochs past the safe horizon "
                         "(Time Warp lite; 0 = strictly conservative). "
                         "Same bits either way — stragglers roll the window "
                         "back; see stats rollbacks/speculated/spec_commits")
    ap.add_argument("--opt-stage-cap", type=int, default=0,
                    help="staging buffer for speculative emissions "
                         "(0 = route_cap); overflow aborts the window, "
                         "never drops")
    ap.add_argument("--opt-commit", default="device",
                    choices=["device", "global"],
                    help="speculation commit locality: 'device' rolls back "
                         "only devices that received a straggler, 'global' "
                         "is the atomic all-or-nothing vote (same bits)")
    ap.add_argument("--opt-adaptive", action="store_true",
                    help="retune the live speculation window between drain "
                         "dispatches from the observed rollback rate "
                         "(--opt-window becomes the cap; --drain only)")
    ap.add_argument("--n-buckets", type=int, default=16)
    ap.add_argument("--bucket-cap", type=int, default=256)
    ap.add_argument("--route-cap", type=int, default=8192)
    ap.add_argument("--fallback-cap", type=int, default=8192)
    ap.add_argument("--drain", action="store_true",
                    help="run to empty as ONE fused on-device dispatch "
                         "(run_until_drained, bounded by --epochs)")
    ap.add_argument("--verify", action="store_true",
                    help="cross-check final object state against the "
                         "sequential oracle (dyadic dist only)")
    args = ap.parse_args()

    import jax
    import numpy as np
    from jax.sharding import Mesh

    from ..core.engine import AXIS, EngineConfig, ParsirEngine
    from ..testing import assert_clean
    from ..workloads.registry import get_workload
    from .runtime import enable_compile_cache, too_few_devices

    enable_compile_cache()
    devs = jax.devices()
    if len(devs) < args.devices:
        raise SystemExit(too_few_devices(len(devs), args.devices))
    mesh = Mesh(np.array(devs[:args.devices]), (AXIS,))

    model = get_workload(args.workload, n_objects=args.objects,
                         lookahead=args.lookahead, dist=args.dist,
                         **parse_kv(args.model_kw))
    cfg = EngineConfig(
        lookahead=args.lookahead, epoch_len=args.epoch_len,
        n_buckets=args.n_buckets, bucket_cap=args.bucket_cap,
        route_cap=args.route_cap, fallback_cap=args.fallback_cap,
        scheduler=args.scheduler, route=args.route,
        batch_impl=args.batch_impl, pack_tile=args.pack_tile,
        steal=args.steal, steal_cap=4, claim_cap=8,
        placement=args.placement, rebalance_every=args.rebalance_every,
        migrate_cap=args.migrate_cap, placement_slack=args.placement_slack,
        opt_window=args.opt_window, opt_stage_cap=args.opt_stage_cap,
        opt_commit=args.opt_commit, opt_adaptive=args.opt_adaptive)
    eng = ParsirEngine(model, cfg, mesh=mesh)

    st = eng.init()
    # warm/compile the exact program the timed section dispatches, without
    # advancing the simulation: both loops no-op at a zero bound.
    st = (eng.run_until_drained(st, 0) if args.drain else eng.run(st, 0))
    base = eng.totals(st)["processed"]

    t0 = time.perf_counter()
    st = (eng.run_until_drained(st, args.epochs) if args.drain
          else eng.run(st, args.epochs))
    st.stats.processed.block_until_ready()
    dt = time.perf_counter() - t0

    tot = eng.totals(st)
    epochs_run = int(np.asarray(st.epoch)[0])
    done = tot["processed"] - base
    print(f"[simulate] {args.workload} D={args.devices}: {done} events over "
          f"{epochs_run} epochs in {dt:.2f}s ({done / max(dt, 1e-9):,.0f} "
          f"ev/s) — {eng.dispatches} host dispatches")
    if args.drain:
        left = eng.in_flight(st)
        print(f"[simulate] drain: {'complete' if left == 0 else 'BOUND HIT'} "
              f"at epoch {epochs_run} (in-flight {left})")
    print(f"[simulate] stats: {tot}")
    try:
        assert_clean(tot, context="simulate")
    except AssertionError as e:
        raise SystemExit(f"[simulate] {e}") from None

    if args.verify:
        if args.dist != "dyadic":
            raise SystemExit("--verify needs --dist dyadic (bit-exact mode)")
        from ..core.ref_engine import run_sequential
        ref = run_sequential(model, epochs_run, cfg.epoch_len)
        assert tot["processed"] == ref.total_processed, \
            (tot["processed"], ref.total_processed)
        gobj = eng.global_object_state(st)
        for key, leaf in gobj.items():
            ref_leaf = np.stack([s[key] for s in ref.obj_state])
            assert np.array_equal(leaf, ref_leaf), \
                f"object state {key!r} diverges from the oracle"
        print("[simulate] verified bit-exact vs sequential oracle ✓")


if __name__ == "__main__":
    main()
