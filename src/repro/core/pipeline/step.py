"""The per-device epoch step: pure wiring of the pipeline stages.

    extract → steal → process → rebalance → route → deliver  (+ stats)

Each stage runs under its ``jax.named_scope`` from
:mod:`repro.core.pipeline.names` (``parsir.extract``, ``parsir.process`` —
steal policy and scheduler —, ``parsir.rebalance``, ``parsir.route``,
``parsir.exchange``, ``parsir.deliver``): op metadata only, so the compiled
computation is unchanged while a profiler trace names each stage's device
time.

Stage behavior lives behind the :mod:`repro.core.pipeline.base` interfaces;
:func:`make_step` resolves the configured Scheduler / Router / StealPolicy /
RebalancePolicy once, runs their fail-fast validation, and returns the
jittable step closure the engine shard_maps over the mesh.  The process
stage receives the live :class:`EngineConfig` (schedulers read their knobs —
``lookahead``, the width-packer's ``pack_tile`` — off it), so the wiring
here stays knob-free.

Placement boundaries are *state*, not trace constants: every step rebuilds a
runtime :class:`~repro.core.placement.Placement` from ``state.bounds`` so the
adaptive rebalance stage can move the cuts at epoch boundaries.  The
rebalance runs between process and route — the epoch's fresh emissions (and
every fallback re-offer) are routed against the new boundaries immediately.

Out-of-range destinations (``dst`` outside ``[0, n_objects)``) are triaged at
the producer: counted in ``stats.oob_events`` (a hard error at the driver,
like overflow) and excluded from routing/fallback, where the owner
searchsorted + local-index clip would otherwise deliver them into the wrong
object's calendar.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from ..api import SimModel
from ..calendar import Fallback, extract_sorted
from ..events import concat_batches, take_first
from ..placement import Placement
from . import names
from . import rebalance, routers, schedulers, steal  # noqa: F401  (registration imports)
from .base import (AXIS, EngineState, epoch_of, resolve_rebalance,
                   resolve_router, resolve_scheduler, resolve_steal, tally)
from .config import EngineConfig
from .deliver import deliver


def make_step(model: SimModel, cfg: EngineConfig, placement: Placement
              ) -> Callable[[EngineState], EngineState]:
    D = placement.n_devices
    N = cfg.n_buckets
    O = placement.n_objects

    scheduler = resolve_scheduler(cfg)
    router = resolve_router(cfg.route)
    policy = resolve_steal(cfg, D)
    rebalancer = resolve_rebalance(cfg)
    adaptive = cfg.placement == "adaptive"
    scheduler.validate(model, cfg)
    router.validate(cfg, placement)

    def step(state: EngineState) -> EngineState:
        dev = jax.lax.axis_index(AXIS)
        cur = state.epoch[0]
        pl = placement.with_boundaries(state.bounds[0])

        # 1. extract — drain the calendar bucket of the current epoch.
        with jax.named_scope(names.EXTRACT):
            cal, ts_s, seed_s, pay_s, cnt_b = extract_sorted(state.cal, cur)

        # 2.+3. steal + process — the policy runs the scheduler (possibly on
        # loan-augmented batches) and reports emitted events + counts.
        with jax.named_scope(names.PROCESS):
            obj, out_flat, lv, stolen, proc_count, rounds = \
                policy.process(model, scheduler, cfg, pl, dev, state.obj,
                               ts_s, seed_s, pay_s, cnt_b)

        # 3b. rebalance — adaptive placement moves the boundaries and
        # migrates object rows at epoch boundaries; everything downstream
        # (routing, delivery) sees the new cuts.
        if adaptive:
            with jax.named_scope(names.REBALANCE):
                load = state.load + cnt_b
                bounds, load, cal, obj, migrated, fired = \
                    rebalancer.rebalance(cfg, placement, dev, cur,
                                         state.bounds[0], load, cal, obj)
            pl = placement.with_boundaries(bounds)
        else:
            bounds, load = state.bounds[0], state.load
            migrated = fired = jnp.int32(0)

        # 4. route — producer-side triage (fresh events + fallback entries),
        # selection against the route capacity, then the exchange collective.
        with jax.named_scope(names.ROUTE):
            prod = concat_batches(out_flat, state.fb.events)
            epochs = epoch_of(prod.ts, cfg.epoch_len)
            oob = prod.valid & ((prod.dst < 0) | (prod.dst >= O))
            n_oob = jnp.sum(oob.astype(jnp.int32))
            eligible = (prod.valid & ~oob & (epochs >= cur + 1)
                        & (epochs <= cur + N))
            late_prod = prod.valid & ~oob & (epochs <= cur)
            n_late_prod = jnp.sum(late_prod.astype(jnp.int32))

            route_buf, send, route_ovf = router.select_send(prod, eligible,
                                                            pl, cfg)

            keep = prod.valid & ~send & ~late_prod & ~oob
            kept, fb_ovf = take_first(prod, keep, cfg.fallback_cap)
            fb = Fallback(kept)

        with jax.named_scope(names.EXCHANGE):
            routed = router.exchange(route_buf, pl, cfg)

        # 5. deliver — owners insert into calendar buckets / fallback.  The
        # router declares its output topology: a broadcast batch is counted
        # once globally, a per-device a2a slice is counted where it lands.
        with jax.named_scope(names.DELIVER):
            cal, fb, cal_ovf, fb_ovf2, late2, oob2 = deliver(
                cal, fb, routed, cur, dev, pl, cfg, init=False,
                replicated=router.replicated)

        st = state.stats
        # the conservative step never speculates: rollbacks / speculated /
        # spec_commits ride through untouched (zero unless opt_window > 0,
        # which routes to pipeline.speculate's step instead of this one).
        stats = st._replace(
            processed=st.processed + proc_count,
            cal_overflow=st.cal_overflow + cal_ovf,
            fb_overflow=st.fb_overflow + fb_ovf + fb_ovf2,
            route_overflow=st.route_overflow + route_ovf,
            late_events=st.late_events + n_late_prod + late2,
            lookahead_violations=st.lookahead_violations + lv,
            stolen=st.stolen + stolen,
            oob_events=st.oob_events + n_oob + oob2,
            rebalances=st.rebalances + fired,
            migrated=st.migrated + migrated,
            rounds=tally(st.rounds, rounds),
        )
        return EngineState(cal, fb, obj, state.epoch + 1, stats,
                           bounds[None, :], load)

    return step
