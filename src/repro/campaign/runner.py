"""The campaign loop: one vmapped fused-drain dispatch per grid point.

Grid-point parameters are trace-time constants (they change shapes, branch
structure, compiled code), so points run sequentially — but *within* a point
every replication seed is pure data, and all of them advance together through
:meth:`ParsirEngine.run_replicated_drained`: two host dispatches per point
(the ingest and the fused drain), independent of the seed count.

Every replication's counters are checked against the clean-run contract
(:mod:`repro.testing.clean`) and its drain status recorded; the point result
lands in the :class:`ResultsStore` before the next point compiles, so an
interrupted campaign resumes where it stopped.
"""
from __future__ import annotations

from typing import Any, Callable

from .spec import CampaignSpec
from .store import ResultsStore


def _run_point(spec: CampaignSpec, index: int, mesh,
               rep_shards: int | None = None) -> dict[str, Any]:
    import numpy as np

    from ..core.engine import EngineConfig, ParsirEngine
    from ..testing.clean import unclean_counters
    from ..workloads.registry import get_workload

    point = spec.points()[index]
    model = get_workload(spec.workload, **point)
    eng = ParsirEngine(model, EngineConfig(**spec.engine_kw), mesh=mesh,
                       rep_shards=rep_shards)

    base = eng.dispatches
    st = eng.init_replicated(spec.seeds)
    st = eng.run_replicated_drained(st, spec.max_epochs)

    totals = eng.totals_replicated(st)
    in_flight = eng.in_flight_replicated(st)
    epochs = np.asarray(st.epoch)[:, 0]
    reps = []
    for r, seed in enumerate(spec.seeds):
        reps.append({
            "seed": int(seed),
            "processed": totals[r]["processed"],
            "epochs": int(epochs[r]),
            "in_flight": int(in_flight[r]),
            "unclean": unclean_counters(totals[r]),
            "stats": totals[r],
        })
    return {
        "index": index,
        "label": spec.point_label(index),
        "model_kw": point,
        "seeds": [int(s) for s in spec.seeds],
        "max_epochs": spec.max_epochs,
        "dispatches": eng.dispatches - base,
        "drained": bool(int(in_flight.sum()) == 0),
        "replications": reps,
    }


def run_campaign(spec: CampaignSpec, store: ResultsStore | None = None,
                 mesh=None, log: Callable[[str], None] | None = None
                 ) -> dict[str, Any]:
    """Run (or resume) a campaign; return the summary dict.

    With a ``store``, completed grid points are skipped (their stored result
    is reused in the summary) and fresh results are written as they finish.
    ``mesh`` defaults to the first ``spec.devices`` visible JAX devices;
    with ``spec.devices > 1`` and a divisible seed count the replication
    axis is sharded across them (``rep_shards`` — each replication runs
    collective-free on its own device) rather than the object axis.

    The summary reports, per the clean-run contract, every replication with
    nonzero overflow/causality counters (``unclean``) and every grid point
    whose drain hit ``max_epochs`` with events still in flight
    (``undrained``) — drivers decide which of those are fatal.
    """
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from ..core.engine import AXIS
    from ..launch.runtime import too_few_devices

    say = log or (lambda msg: None)
    rep_shards = None
    if mesh is None:
        devs = jax.devices()
        if len(devs) < spec.devices:
            raise ValueError(too_few_devices(len(devs), spec.devices))
        if spec.devices > 1 and len(spec.seeds) % spec.devices == 0:
            # the campaign throughput layout: shard the REPLICATION axis —
            # each replication runs whole (collective-free) on its own
            # device, which beats object-sharding whenever one replication
            # fits a device.  Falls back to object-sharding when the seed
            # count doesn't divide (or a caller supplied its own mesh).
            rep_shards = spec.devices
            mesh = Mesh(np.array(devs[:1]), (AXIS,))
        else:
            mesh = Mesh(np.array(devs[:spec.devices]), (AXIS,))

    if store is not None:
        store.write_manifest(spec)

    points = spec.points()
    results, ran, resumed = [], 0, 0
    for i in range(len(points)):
        if store is not None and store.has(spec, i):
            results.append(store.get(spec, i))
            resumed += 1
            say(f"[campaign] point {i} ({spec.point_label(i)}): resumed")
            continue
        res = _run_point(spec, i, mesh, rep_shards)
        if store is not None:
            store.put(spec, i, res)
        results.append(res)
        ran += 1
        done = sum(r["processed"] for r in res["replications"])
        say(f"[campaign] point {i} ({res['label']}): {done} events over "
            f"{len(spec.seeds)} seeds, {res['dispatches']} dispatches, "
            f"drained={res['drained']}")

    unclean = [(res["index"], rep["seed"], rep["unclean"])
               for res in results for rep in res["replications"]
               if rep["unclean"]]
    undrained = [res["index"] for res in results if not res["drained"]]
    return {
        "digest": spec.digest(),
        "n_points": len(points),
        "ran": ran,
        "resumed": resumed,
        "missing": store.missing(spec) if store is not None else [],
        "unclean": unclean,
        "undrained": undrained,
        "results": results,
    }
