"""The PARSIR epoch-synchronous conservative engine (paper §II), in JAX.

One SPMD program instance per mesh device plays the role of a PARSIR worker
thread pinned to a CPU; a device's HBM plays the NUMA node.  An engine step
processes exactly one epoch through the stage pipeline of
:mod:`repro.core.pipeline`:

  1. **extract** — drain the calendar bucket of the current epoch for all local
     objects, sorted per object by (ts, seed) (lock-free: exclusive ownership,
     see calendar.py);
  2. **steal (optional)** — epoch-granular loans of hot objects from overloaded
     to underloaded devices (``StealPolicy``), decided from the globally
     gathered load vector (possible because the lookahead closes the epoch's
     workload);
  3. **process** — the per-object *batch* execution at the heart of the paper
     (``Scheduler``): round r applies the r-th in-order event of every object
     in parallel (vmap), so each object's state stays register/VMEM-hot across
     its whole batch while objects are processed in parallel;
  3b. **rebalance (optional)** — with ``placement="adaptive"``, every
     ``rebalance_every`` epochs the placement boundaries are recomputed from
     measured per-object load and moved objects (state + calendar rows)
     migrate to their new owners (``RebalancePolicy``, paper §II-C);
  4. **route** — emitted events plus drained fallback entries are exchanged
     (``Router``: `allgather` mirrors the shared-memory "any thread enqueues
     anywhere" semantics; `a2a` is the optimized pairwise exchange);
  5. **deliver** — owners insert routed events into calendar buckets (conflict-
     free scatter) or park beyond-horizon events in the fallback buffer;
  6. **barrier** — implicit in the collectives; epoch advances everywhere.

Object → device placement is contiguous-by-id (the paper's NUMA knapsack):
``EngineConfig.placement`` selects ``equal`` ranges, ``weighted`` ranges
balancing the model's :meth:`~repro.core.api.SimModel.object_weights` hint,
or ``adaptive`` runtime rebalancing.  Because placements may be uneven while
SPMD sharding must be even, every device materializes ``n_local_max`` object
rows (the *pad*); rows beyond a device's live range are inert — zero calendar
counts, never receiving events.  With the default equal placement on a
divisible object count the pad is exact and the layout is identical to the
classic one.  The live boundaries vector rides in ``EngineState`` so the
rebalance stage can move it without retracing.

Event flow is variable-arity end to end: each processed event emits
0..``model.max_out`` successors (``EmittedEvents`` rows with ``valid`` masks
honored at every stage), so open networks — sources fanning out, sinks
absorbing — run through the same pipeline as the classic one-in/one-out
workloads.

All capacities are static; every overflow/causality condition is *counted* in
``Stats`` and surfaced — a conservative engine must never silently drop or
reorder, so drivers (and tests) assert these counters stay zero.

The host loop itself is on-device: :meth:`ParsirEngine.run` advances a fixed
epoch count as one compiled chunked program (the count is a traced operand —
no per-length retrace), and :meth:`ParsirEngine.run_until_drained` fuses the
whole drain-to-empty simulation into a single ``lax.while_loop`` dispatch
with donated buffers (see docs/architecture.md, "The fused on-device drain
loop").

This module is the user-facing wrapper (:class:`ParsirEngine`: mesh setup,
sharding, lifecycle) and re-exports the pipeline's stable names
(``EngineConfig``, ``EngineState``, ``Stats``, ``AXIS``, ``make_step``) so
historical ``repro.core.engine`` imports keep working.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .api import SimModel
from .calendar import bucket_occupancy, make_calendar, make_fallback
from .events import EventBatch
from .pipeline import (AXIS, EngineConfig, EngineState, Stats, deliver,
                       make_spec_step, make_step, zero_stats)
from .pipeline.base import stats_dtype
from .placement import Placement, equal_placement, weighted_placement

__all__ = ["AXIS", "REP_AXIS", "EngineConfig", "EngineState", "ParsirEngine",
           "Stats", "make_spec_step", "make_step", "zero_stats"]

#: mesh axis name for replication-sharded campaigns (``rep_shards``): the
#: device grid is ``(REP_AXIS=W, AXIS=1)``, so the step's collectives over
#: ``AXIS`` are single-member no-ops and each replication stays local.
REP_AXIS = "replications"


def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def build_placement(model: SimModel, cfg: EngineConfig, D: int) -> Placement:
    """Resolve ``cfg.placement`` into the engine's initial Placement.

    ``weighted``/``adaptive`` consult the model's optional ``object_weights``
    hint (falling back to the equal split when the model declares none);
    ``adaptive`` additionally widens the per-device row pad by
    ``placement_slack`` so the boundaries have static headroom to skew.
    """
    O = model.n_objects
    if cfg.placement == "equal":
        return equal_placement(O, D)
    w = model.object_weights()
    pl = equal_placement(O, D) if w is None else weighted_placement(w, D)
    if cfg.placement == "adaptive":
        pad = min(O, int(math.ceil(O / D * cfg.placement_slack)))
        pl = pl.padded(max(pl.n_local_max, pad))
    return pl


class ParsirEngine:
    """Build, initialize and run a PARSIR simulation on a device mesh."""

    def __init__(self, model: SimModel, cfg: EngineConfig,
                 mesh: Mesh | None = None, rep_shards: int | None = None):
        """``mesh`` shards the *object* axis (the classic PARSIR layout:
        D workers share one simulation).  ``rep_shards=W`` instead shards the
        *replication* axis of :meth:`init_replicated` stacks across W devices
        — each replication runs whole (collective-free) on its device, which
        is the throughput layout for campaigns whose single replication fits
        one device.  ``rep_shards`` requires the engine's own mesh to be
        single-device and ``len(seeds) % W == 0``."""
        if mesh is None:
            mesh = Mesh(np.array(jax.devices()[:1]), (AXIS,))
        self.model, self.cfg, self.mesh = model, cfg, mesh
        D = int(np.prod(mesh.devices.shape))
        cfg.validate(D)
        self.placement = build_placement(model, cfg, D)
        self.D = D

        self._step = make_step(model, cfg, self.placement)
        #: the bounded-optimism (Time Warp lite) step — built only when the
        #: config asks for it.  With opt_window == 0 nothing speculative is
        #: even constructed and every compiled path below is byte-identical
        #: to a pre-speculation build (no shadow copies, no second exchange).
        self._spec_step = (make_spec_step(model, cfg, self.placement)
                           if cfg.opt_window > 0 else None)
        spec = P(AXIS)
        rep_spec = P(None, AXIS)   # stacked leaves: [R, ...] sharded on dim 1
        self._sharding = NamedSharding(mesh, spec)
        self._rep_sharding = NamedSharding(mesh, rep_spec)
        self._step_sm = jax.jit(_shard_map(self._step, mesh, (spec,), spec),
                                donate_argnums=0)
        #: host-side XLA program launches (init ingest, step, run chunks,
        #: fused drains) — the honest dispatches-per-simulation number the
        #: benchmarks report.
        self.dispatches = 0
        #: lazily compiled drain programs per live window width, used by the
        #: adaptive-W controller (cfg.opt_adaptive): EngineState layout is
        #: W-independent, so the same state flows through any variant.
        self._drain_variants: dict[int, object] = {}

        def in_flight_device(s: EngineState) -> jax.Array:
            # the drain predicate's operand: global events still parked in
            # calendars + fallback lists (device-local sum, psum over AXIS).
            local = (jnp.sum(s.cal.cnt)
                     + jnp.sum(s.fb.events.valid.astype(jnp.int32)))
            return jax.lax.psum(local, AXIS)

        def run_n(state: EngineState, n: jax.Array) -> EngineState:
            # n is a *traced* operand: one compiled program serves every
            # epoch count (the old per-n_epochs scan retraced per length).
            return jax.lax.fori_loop(0, n, lambda i, s: self._step(s), state)

        if self._spec_step is not None:
            def run_n(state: EngineState, n: jax.Array) -> EngineState:
                # A speculative step advances a *variable* epoch count
                # (W_eff + 1 on commit, 1 on abort), so the fixed-trip
                # fori_loop becomes a while_loop on the replicated epoch
                # counter.  The bound rides into the step, which clamps its
                # last window to land on exactly epoch start + n — run(n)
                # stays horizon-exact vs the oracle.
                bound = state.epoch[0] + n
                return jax.lax.while_loop(
                    lambda s: s.epoch[0] < bound,
                    lambda s: self._spec_step(s, bound), state)

        self._run_sm = jax.jit(
            _shard_map(run_n, mesh, (spec, P()), spec), donate_argnums=0)

        def drain(state: EngineState, max_epochs: jax.Array) -> EngineState:
            # Fused on-device drain loop: a single lax.while_loop whose body
            # is the epoch step.  The carry is (state, epochs_run, in_flight);
            # in_flight is computed (with its psum) at the END of the body so
            # the cond stays collective-free — every device computes the same
            # replicated predicate and the loop exits in lockstep.
            def cond(carry):
                s, n, pending = carry
                return (pending > 0) & (n < max_epochs)

            def body(carry):
                s, n, _ = carry
                s = self._step(s)
                return s, n + jnp.int32(1), in_flight_device(s)

            s, _, _ = jax.lax.while_loop(
                cond, body, (state, jnp.int32(0), in_flight_device(state)))
            return s

        if self._spec_step is not None:
            def drain(state: EngineState, max_epochs: jax.Array) -> EngineState:
                # Speculative fused drain: one while iteration is one
                # committed-or-aborted window (epochs-to-drain, the number
                # the it6 bench reports, is spec_commits + rollbacks), so
                # the cap moves off the iteration count onto the replicated
                # epoch counter — a window advances up to opt_window + 1
                # epochs at once.  The shadow copies live entirely inside
                # the step body; the loop carry is unchanged.
                bound = state.epoch[0] + max_epochs

                def cond(carry):
                    s, pending = carry
                    return (pending > 0) & (s.epoch[0] < bound)

                def body(carry):
                    s, _ = carry
                    s = self._spec_step(s, bound)
                    return s, in_flight_device(s)

                s, _ = jax.lax.while_loop(
                    cond, body, (state, in_flight_device(state)))
                return s

        self._drain_sm = jax.jit(
            _shard_map(drain, mesh, (spec, P()), spec), donate_argnums=0)
        if self._spec_step is not None:
            # the full-width drain doubles as the adaptive controller's
            # starting variant — no duplicate compile for w == opt_window.
            self._drain_variants[cfg.opt_window] = self._drain_sm

        def drain_replicated(state: EngineState,
                             max_epochs: jax.Array) -> EngineState:
            # The replication-vmapped fused drain: R independent simulations
            # advance inside ONE lax.while_loop dispatch.  Every leaf of the
            # carry is the [R, ...]-stacked per-device state; the body vmaps
            # the epoch step over the replication axis (the collectives
            # inside the step batch over R via their vmap rules, so one
            # psum/all_gather/all_to_all serves all replications at once).
            #
            # Exit + freezing: the predicate is ANY replication still having
            # in-flight events; a replication whose own pending count hit
            # zero is *frozen* — the body computes its step but jnp.where
            # keeps the old leaves — so its epoch counter and Stats stop at
            # exactly its own drain epoch and its final state is leaf-exact
            # vs an independent run_until_drained of that seed.  As in the
            # scalar drain, pending is computed at the body END so the cond
            # stays collective-free.
            vstep = jax.vmap(self._step)
            freeze = self._freeze_replications

            def pending_of(s: EngineState) -> jax.Array:
                per_rep = jax.vmap(
                    lambda t: jnp.sum(t.cal.cnt)
                    + jnp.sum(t.fb.events.valid.astype(jnp.int32)))(s)
                return jax.lax.psum(per_rep, AXIS)          # i32 [R]

            def cond(carry):
                s, n, pending = carry
                return jnp.any(pending > 0) & (n < max_epochs)

            def body(carry):
                s, n, pending = carry
                active = pending > 0                        # bool [R]
                s = freeze(active, vstep(s), s)
                return s, n + jnp.int32(1), pending_of(s)

            s, _, _ = jax.lax.while_loop(
                cond, body, (state, jnp.int32(0), pending_of(state)))
            return s

        if self._spec_step is not None:
            def drain_replicated(state: EngineState,
                                 max_epochs: jax.Array) -> EngineState:
                # Replications commit/abort independently, so their epoch
                # counters diverge — each gets its own bound and freezes
                # when it drains or reaches it.  The freeze contract holds
                # unchanged: a drained replication's speculative step is a
                # bit-exact no-op (empty buckets speculate nothing, V == 0,
                # commit delivers nothing) and its advancing leaves (epoch,
                # Stats incl. spec_commits) take the mask.
                bounds_r = state.epoch[:, 0] + max_epochs       # i32 [R]
                vstep = jax.vmap(self._spec_step)
                freeze = self._freeze_replications

                def pending_of(s: EngineState) -> jax.Array:
                    per_rep = jax.vmap(
                        lambda t: jnp.sum(t.cal.cnt)
                        + jnp.sum(t.fb.events.valid.astype(jnp.int32)))(s)
                    return jax.lax.psum(per_rep, AXIS)          # i32 [R]

                def cond(carry):
                    s, pending = carry
                    return jnp.any((pending > 0)
                                   & (s.epoch[:, 0] < bounds_r))

                def body(carry):
                    s, pending = carry
                    active = (pending > 0) & (s.epoch[:, 0] < bounds_r)
                    s = freeze(active, vstep(s, bounds_r), s)
                    return s, pending_of(s)

                s, _ = jax.lax.while_loop(
                    cond, body, (state, pending_of(state)))
                return s

        self._drain_rep_sm = jax.jit(
            _shard_map(drain_replicated, mesh, (rep_spec, P()), rep_spec),
            donate_argnums=0)

        def ingest(state: EngineState, batch: EventBatch) -> EngineState:
            dev = jax.lax.axis_index(AXIS)
            cur = state.epoch[0]
            pl = self.placement.with_boundaries(state.bounds[0])
            cal, fb, cal_ovf, fb_ovf, late, oob = deliver(
                state.cal, state.fb, batch, cur, dev, pl, cfg, init=True,
                replicated=True)
            st = state.stats
            stats = st._replace(cal_overflow=st.cal_overflow + cal_ovf,
                                fb_overflow=st.fb_overflow + fb_ovf,
                                late_events=st.late_events + late,
                                oob_events=st.oob_events + oob)
            return state._replace(cal=cal, fb=fb, stats=stats)

        self._ingest = jax.jit(_shard_map(ingest, mesh, (spec, P()), spec))
        self._ingest_rep = jax.jit(
            _shard_map(jax.vmap(ingest), mesh, (rep_spec, P()), rep_spec))

        self.rep_shards = None if rep_shards is None else int(rep_shards)
        if self.rep_shards is not None:
            W = self.rep_shards
            if D != 1:
                raise ValueError(
                    f"rep_shards requires a single-device engine mesh (got "
                    f"D={D}): each replication runs whole on one device")
            devs = jax.devices()
            if W < 1 or len(devs) < W:
                raise ValueError(
                    f"rep_shards={W} needs {W} devices, have {len(devs)}")
            # 2D device grid (REP_AXIS=W, AXIS=1): inside a shard the step's
            # AXIS collectives act over a single member (identity), so every
            # replication advances collective-free on its own device and the
            # drain needs no cross-device traffic at all (each device's
            # while_loop exits at its own local drain epoch).
            mesh2 = Mesh(np.array(devs[:W]).reshape(W, 1), (REP_AXIS, AXIS))
            rspec = P(REP_AXIS)   # stacked leaves sharded on the leading R
            self._rep_mesh = mesh2
            self._rep_sharding = NamedSharding(mesh2, rspec)

            def drain_rep_sharded(state: EngineState,
                                  max_epochs: jax.Array) -> EngineState:
                # Same freeze contract as drain_replicated, but pending is
                # the LOCAL [R/W] slice and — because the whole body is
                # collective-free across devices (the AXIS collectives are
                # single-member) — the cond can be local too: each device's
                # while_loop exits as soon as ITS replications drain, with
                # no cross-device sync at any point in the drain.
                vstep = jax.vmap(self._step)
                freeze = self._freeze_replications

                def pending_of(s: EngineState) -> jax.Array:
                    per_rep = jax.vmap(
                        lambda t: jnp.sum(t.cal.cnt)
                        + jnp.sum(t.fb.events.valid.astype(jnp.int32)))(s)
                    return jax.lax.psum(per_rep, AXIS)      # i32 [R/W]

                def cond(carry):
                    s, n, p_loc = carry
                    return jnp.any(p_loc > 0) & (n < max_epochs)

                def body(carry):
                    s, n, p_loc = carry
                    active = p_loc > 0                      # bool [R/W]
                    s = freeze(active, vstep(s), s)
                    return s, n + jnp.int32(1), pending_of(s)

                s, _, _ = jax.lax.while_loop(
                    cond, body, (state, jnp.int32(0), pending_of(state)))
                return s

            if self._spec_step is not None:
                def drain_rep_sharded(state: EngineState,
                                      max_epochs: jax.Array) -> EngineState:
                    # Per-rep epoch bounds as in the vmapped drain; the cond
                    # stays local (the AXIS collectives inside the spec step
                    # — the verdict all_gather included — are single-member
                    # no-ops, so the [D, 2] verdict table collapses to this
                    # replication's own [m_local, v_local] and each device's
                    # loop still exits at its own local drain epoch).
                    bounds_r = state.epoch[:, 0] + max_epochs   # i32 [R/W]
                    vstep = jax.vmap(self._spec_step)
                    freeze = self._freeze_replications

                    def pending_of(s: EngineState) -> jax.Array:
                        per_rep = jax.vmap(
                            lambda t: jnp.sum(t.cal.cnt)
                            + jnp.sum(t.fb.events.valid.astype(jnp.int32)))(s)
                        return jax.lax.psum(per_rep, AXIS)      # i32 [R/W]

                    def cond(carry):
                        s, p_loc = carry
                        return jnp.any((p_loc > 0)
                                       & (s.epoch[:, 0] < bounds_r))

                    def body(carry):
                        s, p_loc = carry
                        active = (p_loc > 0) & (s.epoch[:, 0] < bounds_r)
                        s = freeze(active, vstep(s, bounds_r), s)
                        return s, pending_of(s)

                    s, _ = jax.lax.while_loop(
                        cond, body, (state, pending_of(state)))
                    return s

            self._drain_rep_sm = jax.jit(
                _shard_map(drain_rep_sharded, mesh2, (rspec, P()), rspec),
                donate_argnums=0)
            self._ingest_rep = jax.jit(
                _shard_map(jax.vmap(ingest), mesh2, (rspec, rspec), rspec))

    def _freeze_replications(self, active, stepped: EngineState,
                             old: EngineState) -> EngineState:
        """Per-replication freeze for the stacked drains: keep ``old`` leaves
        wherever ``active`` (the PRE-step pending mask, bool [R]) is False,
        so a drained replication stops at exactly its own drain epoch.

        The select is *light* where the drained-state fixpoint already
        guarantees bit-equality: an empty calendar extracts, processes,
        routes and delivers nothing, so the per-slot calendar buffers — by
        far the largest state in the system — leave the step bit-identical
        for frozen replications and ride through unmasked.  Selecting them
        too forces a full-array copy every epoch, which measured *slower*
        than the sequential host loop at campaign scale.  Only the leaves
        the step advances unconditionally (epoch counter, decaying load,
        Stats) plus the cheap small buffers take the mask.  Adaptive
        placement is the exception: a post-drain rebalance may still
        migrate rows, so it keeps the full-tree select.
        """
        def sel(new, olds):
            return jnp.where(
                active.reshape((-1,) + (1,) * (new.ndim - 1)), new, olds)
        if self.cfg.placement == "adaptive":
            return jax.tree.map(sel, stepped, old)
        return stepped._replace(
            cal=stepped.cal._replace(cnt=sel(stepped.cal.cnt, old.cal.cnt)),
            fb=jax.tree.map(sel, stepped.fb, old.fb),
            obj=jax.tree.map(sel, stepped.obj, old.obj),
            epoch=sel(stepped.epoch, old.epoch),
            stats=jax.tree.map(sel, stepped.stats, old.stats),
            bounds=sel(stepped.bounds, old.bounds),
            load=sel(stepped.load, old.load))

    # -- lifecycle -------------------------------------------------------------

    def _fresh_state(self, R: int | None) -> EngineState:
        """The zeroed pre-ingest EngineState; ``R`` stacks every leaf with a
        leading replication axis (sharded ``P(None, AXIS)``), ``None`` builds
        the classic single-simulation layout."""
        D, M = self.D, self.placement.n_local_max
        cfg = self.cfg
        sharding = self._sharding if R is None else self._rep_sharding
        rep = ((lambda l: l) if R is None
               else (lambda l: jnp.broadcast_to(l[None], (R,) + l.shape)))
        put = lambda l: jax.device_put(rep(jnp.asarray(l)), sharding)
        obj = jax.tree.map(
            put, self.model.init_object_state(self.placement.padded_gids()))
        cal = jax.tree.map(put,
                           make_calendar(D * M, cfg.n_buckets, cfg.bucket_cap))
        fb = jax.tree.map(put, make_fallback(D * cfg.fallback_cap))
        epoch = put(jnp.zeros((D,), jnp.int32))
        stats = jax.tree.map(lambda l: put(jnp.tile(l, D)), zero_stats())
        b = jnp.asarray(np.asarray(self.placement.boundaries, np.int32))
        bounds = put(jnp.tile(b[None, :], (D, 1)))
        load = put(jnp.zeros((D * M,), jnp.int32))
        return EngineState(cal, fb, obj, epoch, stats, bounds, load)

    def _initial_batch(self, seed: int | None) -> EventBatch:
        init_ev = (self.model.initial_events() if seed is None
                   else self.model.initial_events(seed))
        return EventBatch(
            dst=jnp.asarray(init_ev["dst"], jnp.int32),
            ts=jnp.asarray(init_ev["ts"], jnp.float32),
            seed=jnp.asarray(init_ev["seed"], jnp.uint32),
            payload=jnp.asarray(init_ev["payload"], jnp.float32),
            valid=jnp.ones((len(init_ev["dst"]),), bool),
        )

    def init(self, seed: int | None = None) -> EngineState:
        """Build the initial state and ingest the bootstrap events.

        ``seed`` selects the replication stream (forwarded to the model's
        ``initial_events``); ``None`` keeps the model's own default."""
        state = self._fresh_state(None)
        self.dispatches += 1
        return self._ingest(state, self._initial_batch(seed))

    def init_replicated(self, seeds) -> EngineState:
        """Build an R-replication stacked state, one bootstrap stream per
        seed.  Every leaf leads with the replication axis ``R = len(seeds)``
        (initial object state is identical across replications — trajectories
        diverge through the seed-salted bootstrap events alone); run it with
        :meth:`run_replicated_drained`."""
        seeds = [int(s) for s in seeds]
        if not seeds:
            raise ValueError("init_replicated needs at least one seed")
        if self.rep_shards and len(seeds) % self.rep_shards:
            raise ValueError(
                f"rep_shards={self.rep_shards} needs len(seeds) divisible by"
                f" it (got {len(seeds)})")
        state = self._fresh_state(len(seeds))
        batches = [self._initial_batch(s) for s in seeds]
        batch = EventBatch(*(jnp.stack(ls) for ls in zip(*batches)))
        self.dispatches += 1
        return self._ingest_rep(state, batch)

    def check_stats_bound(self, n_epochs: int) -> None:
        """Fail fast if ``n_epochs`` epochs could overflow a Stats counter.

        The in-carry ledger accumulates in :func:`stats_dtype` — int32 unless
        ``JAX_ENABLE_X64=1`` widens it to int64 — and int32 overflow would
        wrap *silently* inside the fused loop.  The worst-case per-device
        per-epoch increment of any counter is bounded by the largest static
        buffer a stage can fill: the epoch bucket (``n_local_max *
        bucket_cap``, plus claimed loans under stealing), the route buffer,
        or the fallback list.  Every run entry point checks this bound
        before dispatching.
        """
        cap = int(jnp.iinfo(stats_dtype()).max)
        per_epoch = self.placement.n_local_max * self.cfg.bucket_cap
        if self.cfg.steal:
            per_epoch += self.cfg.claim_cap * self.cfg.bucket_cap
        per_epoch = max(per_epoch, self.cfg.route_cap, self.cfg.fallback_cap)
        if int(n_epochs) * per_epoch > cap:
            raise ValueError(
                f"{n_epochs} epochs could overflow the {stats_dtype().__name__}"
                f" Stats counters (worst-case {per_epoch} events/epoch/device,"
                f" bound {int(n_epochs) * per_epoch:,} > {cap:,}); set"
                f" JAX_ENABLE_X64=1 to widen the ledger to int64, or split"
                f" the horizon")

    def step(self, state: EngineState) -> EngineState:
        """Advance exactly one epoch (always the conservative step — the
        single-epoch contract leaves no room to speculate; ``opt_window``
        engages inside :meth:`run` and the fused drains)."""
        self.dispatches += 1
        return self._step_sm(state)

    def run(self, state: EngineState, n_epochs: int) -> EngineState:
        """Advance exactly ``n_epochs`` epochs in one XLA dispatch.

        The epoch count is a traced operand of one compiled chunked program
        (an on-device ``fori_loop``), so calling with a new ``n_epochs``
        never retraces — the historical per-length ``scan`` cache is retired.
        ``state`` is donated: rebind the result, the input handle dies.
        """
        self.check_stats_bound(n_epochs)
        self.dispatches += 1
        return self._run_sm(state, jnp.int32(n_epochs))

    def run_until_drained(self, state: EngineState,
                          max_epochs: int) -> EngineState:
        """Run to empty — an entire simulation as ONE XLA dispatch.

        A single ``lax.while_loop`` whose body is the epoch step and whose
        carry holds the drain predicate: the loop exits when no event is
        parked anywhere (``sum(cal.cnt) + sum(fb.valid) == 0``, the same
        quantity :meth:`in_flight` reads) or after ``max_epochs`` epochs,
        whichever first.  Stats accumulate in-carry exactly as under
        :meth:`run`; buffers are donated, so the input handle dies.

        Bit-exactness: a drained simulation's state is a fixpoint of the
        step (empty calendars process, route and deliver nothing), so
        stopping at the drain epoch k <= max_epochs yields the same
        calendars/state/stats as running the full bound — the sequential
        oracle at any horizon >= k compares bit-for-bit.  Non-draining
        workloads run exactly ``max_epochs`` epochs, identical to
        ``run(state, max_epochs)`` including the epoch counter.

        Use :meth:`run` to advance a fixed horizon (conformance sweeps,
        chunked inspection loops); use this to complete a simulation whose
        event population dies out (absorbing networks, exhausted budgets)
        without guessing an epoch count — and without paying per-chunk
        host dispatch.

        With ``cfg.opt_adaptive`` the drain runs in chunks through the
        adaptive-W controller instead of one fused dispatch: between chunks
        the host reads the observed ``rollbacks / spec_commits`` ratio and
        retunes the live window (``cfg.opt_window`` is the cap) — see
        :meth:`_run_drain_adaptive`.
        """
        self.check_stats_bound(max_epochs)
        if self.cfg.opt_adaptive and self.cfg.opt_window > 0:
            return self._run_drain_adaptive(state, max_epochs)
        self.dispatches += 1
        return self._drain_sm(state, jnp.int32(max_epochs))

    def _drain_variant(self, w: int):
        """The compiled fused-drain program for a live window width ``w``.

        Built (and cached) lazily: ``EngineState`` carries nothing W-shaped
        — the shadow copies live inside the step body — so the identical
        state flows through any variant and switching widths between chunks
        costs one compile per distinct width, ever.
        """
        if w not in self._drain_variants:
            cfg_w = dataclasses.replace(self.cfg, opt_window=w,
                                        opt_adaptive=False)
            step_w = make_spec_step(self.model, cfg_w, self.placement)

            def drain(state: EngineState, max_epochs: jax.Array) -> EngineState:
                bound = state.epoch[0] + max_epochs

                def in_flight_device(s: EngineState) -> jax.Array:
                    local = (jnp.sum(s.cal.cnt)
                             + jnp.sum(s.fb.events.valid.astype(jnp.int32)))
                    return jax.lax.psum(local, AXIS)

                def cond(carry):
                    s, pending = carry
                    return (pending > 0) & (s.epoch[0] < bound)

                def body(carry):
                    s, _ = carry
                    s = step_w(s, bound)
                    return s, in_flight_device(s)

                s, _ = jax.lax.while_loop(
                    cond, body, (state, in_flight_device(state)))
                return s

            spec = P(AXIS)
            self._drain_variants[w] = jax.jit(
                _shard_map(drain, self.mesh, (spec, P()), spec),
                donate_argnums=0)
        return self._drain_variants[w]

    def _run_drain_adaptive(self, state: EngineState,
                            max_epochs: int) -> EngineState:
        """Host-side adaptive-W drain: chunked dispatches, retuned between.

        Policy: after each chunk, read the chunk's rollback ratio
        ``rollbacks / (rollbacks + spec_commits)`` from the in-carry meters.
        Above 1/2 the window is mostly wasted work — shrink it (floor 1);
        below 1/10 stragglers are rare — grow it (cap ``cfg.opt_window``).
        Purely schedule-level control: any W sequence drains to the same
        bits (each chunk is itself a bit-exact fused drain), so the
        controller needs no correctness reasoning, only taste.  Each chunk
        is one honest host dispatch (``self.dispatches`` counts them).
        """
        W0 = self.cfg.opt_window
        w = W0
        # a chunk must be long enough to observe several windows at the
        # widest width, short enough to react — a few windows' worth.
        chunk = max(8, 4 * (W0 + 1))
        tot = self.totals(state)
        prev_cm, prev_rb = tot["spec_commits"], tot["rollbacks"]
        start_epoch = int(np.asarray(state.epoch)[0])
        while True:
            epochs_run = int(np.asarray(state.epoch)[0]) - start_epoch
            n = min(chunk, int(max_epochs) - epochs_run)
            self.dispatches += 1
            state = self._drain_variant(w)(state, jnp.int32(max(n, 0)))
            epochs_run = int(np.asarray(state.epoch)[0]) - start_epoch
            if (epochs_run >= int(max_epochs) or n <= 0
                    or self.in_flight(state) == 0):
                return state
            tot = self.totals(state)
            d_cm = tot["spec_commits"] - prev_cm
            d_rb = tot["rollbacks"] - prev_rb
            prev_cm, prev_rb = tot["spec_commits"], tot["rollbacks"]
            if d_cm + d_rb:
                ratio = d_rb / (d_rb + d_cm)
                if ratio > 0.5 and w > 1:
                    w -= 1
                elif ratio < 0.1 and w < W0:
                    w += 1

    def run_replicated_drained(self, state: EngineState,
                               max_epochs: int) -> EngineState:
        """Drain R independent replications as ONE XLA dispatch.

        ``state`` is the stacked carry of :meth:`init_replicated`; the fused
        ``lax.while_loop`` vmaps the epoch step over the replication axis and
        exits when *every* replication's in-flight count is zero (or at
        ``max_epochs``).  A replication that drains early is frozen in-carry
        — its epoch counter, Stats and object state stop at its own drain
        epoch — so each slice of the result is leaf-exact vs an independent
        ``run_until_drained`` of that seed (and therefore bit-exact vs its
        own sequential oracle for dyadic workloads).  Buffers are donated:
        rebind the result, the input handle dies.

        Read the result per replication with :meth:`replication`,
        :meth:`totals_replicated` and :meth:`in_flight_replicated`.
        """
        self.check_stats_bound(max_epochs)
        self.dispatches += 1
        return self._drain_rep_sm(state, jnp.int32(max_epochs))

    # -- inspection -------------------------------------------------------------

    def replication(self, state: EngineState, r: int) -> EngineState:
        """Slice replication ``r`` out of a stacked state — the result has
        the classic single-simulation layout, so every scalar inspection
        helper (:meth:`totals`, :meth:`in_flight`, ...) applies to it."""
        return jax.tree.map(lambda l: l[r], state)

    def totals_replicated(self, state: EngineState) -> list[dict[str, int]]:
        """Per-replication Stats totals of a stacked state, in seed order."""
        sums = {k: np.asarray(l).reshape(l.shape[0], -1).sum(axis=1)
                for k, l in state.stats._asdict().items()}
        return [{k: int(v[r]) for k, v in sums.items()}
                for r in range(state.epoch.shape[0])]

    def in_flight_replicated(self, state: EngineState) -> np.ndarray:
        """Per-replication in-flight event counts, i64[R]."""
        R = state.epoch.shape[0]
        cal = np.asarray(state.cal.cnt).reshape(R, -1).sum(axis=1)
        fb = np.asarray(state.fb.events.valid).reshape(R, -1).sum(axis=1)
        return (cal + fb).astype(np.int64)

    def totals(self, state: EngineState) -> dict[str, int]:
        st = jax.tree.map(lambda l: int(np.sum(np.asarray(l))), state.stats)
        return st._asdict()

    def in_flight(self, state: EngineState) -> int:
        cal = int(np.sum(np.asarray(state.cal.cnt)))
        fb = int(np.sum(np.asarray(state.fb.events.valid)))
        return cal + fb

    def occupancy(self, state: EngineState) -> dict[str, np.ndarray | int]:
        """Width-packing diagnostics for the *current* epoch's bucket.

        Per device: live event total (``events``), max per-object batch depth
        (``max_depth``), the dense rounds grid each device would execute
        (``padded_lanes = max_depth × n_local_max`` — every device pays its
        own grid, in lockstep until the collective), and the events actually
        present (``packed_lanes``, what ``batch_impl='packed'`` processes up
        to per-round tile rounding).  The padded-row tax is the gap.
        """
        M = self.placement.n_local_max
        depth = np.asarray(
            bucket_occupancy(state.cal, state.epoch[0])).reshape(self.D, M)
        events = depth.sum(axis=1)
        max_depth = depth.max(axis=1, initial=0)
        return {"events": events, "max_depth": max_depth,
                "padded_lanes": max_depth * M, "packed_lanes": events,
                "n_local_max": M}

    def boundaries_of(self, state: EngineState) -> np.ndarray:
        """The live placement boundaries, i64[D+1] (they move under
        ``placement='adaptive'``; rows of ``state.bounds`` are identical)."""
        return np.asarray(state.bounds)[0].astype(np.int64)

    def global_row_of(self, state: EngineState) -> tuple[np.ndarray, np.ndarray]:
        """(gid, live) per padded row, each [D * n_local_max].

        ``gid[r]`` is the global object id row ``r`` backs; ``live[r]`` is
        False for pad rows (which never hold events or meaningful state).
        """
        b = self.boundaries_of(state)
        M = self.placement.n_local_max
        d = np.arange(self.D * M) // M
        i = np.arange(self.D * M) % M
        gid = b[d] + i
        live = i < (b[d + 1] - b[d])
        return np.where(live, gid, 0), live

    def global_object_state(self, state: EngineState) -> dict[str, np.ndarray]:
        """Per-object state re-assembled in global id order, leading dim
        ``n_objects`` — the padded per-device layout undone."""
        gid, live = self.global_row_of(state)
        order = np.nonzero(live)[0]  # contiguous ranges → already gid-sorted
        assert np.array_equal(gid[order], np.arange(self.model.n_objects))
        return {k: np.asarray(v)[order] for k, v in state.obj.items()}
