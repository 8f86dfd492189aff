"""EngineConfig: the stage-selection + capacity record of the pipeline.

Everything an engine build needs to know that isn't the model or the mesh.
Stage names (``scheduler``, ``route``) are registry keys resolved by
:mod:`repro.core.pipeline.base`; unknown names and degenerate capacities fail
at *construction* time.  The one check that needs the device count —
``route_cap >= n_devices`` for a2a, without which the per-pair sub-buffers
would be zero-sized and every event would silently spill to fallback — lives
in :meth:`EngineConfig.validate` and is invoked by the engine (and the a2a
router) as soon as the mesh is known.

Bit-exactness contract: **no field of this record is allowed to change
simulation semantics.**  Every legal configuration — any scheduler, batch
implementation, router, stealing, placement, epoch length or capacity —
must drive the engine to the sequential oracle's drained state bit-for-bit
(the conformance SWEEP is the cross-product proof).  Capacities bound
*buffers*, never behavior: overflow is counted in ``Stats`` and the
affected events recirculate; nothing is silently dropped or reordered.
"""
from __future__ import annotations

import dataclasses

from .names import PLACEMENTS


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """The engine's complete configuration surface, one knob per field.

    Units, defaults and valid ranges (validated in ``__post_init__`` /
    :meth:`validate` — degenerate values fail at construction, never
    mid-run):

    ======================  =============================================
    field                   units · default · valid range
    ======================  =============================================
    ``lookahead``           simulated-time units; required; > 0.  The
                            model's conservative bound L — every emitted
                            event satisfies ``ts_out >= ts_in + L``.
    ``epoch_len``           simulated-time units; default ``lookahead``;
                            (0, lookahead].  Window width of one epoch;
                            smaller = more, emptier epochs.
    ``n_buckets``           count; default 8; >= 1 and > the maximum
                            epochs-ahead any model emission can land
                            (``ceil((L + max_draw) / epoch_len)``) or
                            inserts overflow (counted).
    ``bucket_cap``          events per (object, bucket); default 128;
                            >= 1.  Depth of one calendar cell — size for
                            the hottest object's per-epoch batch.
    ``route_cap``           events per device per epoch; default 4096;
                            >= 1; for a2a also >= n_devices and divisible
                            by it (per-pair sub-buffer = route_cap / D).
    ``fallback_cap``        events per device; default 4096; >= 1.
                            Park-list for events the exchange couldn't
                            carry; they retry next epoch.
    ``route``               registry name; default ``"allgather"``;
                            {allgather, a2a} (+ user-registered).
    ``scheduler``           registry name; default ``"batch"``; {batch,
                            ltf} ∪ user-registered, excluding the internal
                            batch-family names (selected via batch_impl).
    ``batch_impl``          default ``"rounds"``; {rounds, packed, model};
                            only with ``scheduler="batch"``.  A *schedule*
                            choice: identical bits by contract.
    ``pack_tile``           rows; default 64; >= 1 (clamped to the local
                            row count).  packed's vmap tile width —
                            schedule-only, any value yields identical bits.
    ``steal``               bool; default False.  Epoch-granular object
                            loans; requires the batch scheduler family
                            with batch_impl in {rounds, packed}.
    ``steal_cap``           loans per donor per epoch; default 4; >= 1
                            when stealing (0 would silently never steal).
    ``claim_cap``           loans per receiver per epoch; default 4;
                            >= 1 when stealing.
    ``placement``           default ``"equal"``; {equal, weighted,
                            adaptive} (paper §II-A/§II-C knapsacks).
    ``rebalance_every``     epochs; default 0; >= 1 iff adaptive (0 would
                            silently never fire; nonzero otherwise is
                            rejected as dead config).
    ``migrate_cap``         calendar/state rows per device per rebalance;
                            default 16; >= 2 when adaptive.  Boundary
                            shifts are clamped to ``migrate_cap // 2`` —
                            migration traffic is bounded by construction.
    ``placement_slack``     ratio; default 2.0; >= 1.0 when adaptive.
                            Static per-device row pad over the equal
                            split — headroom for boundaries to skew
                            without reallocation.
    ``opt_window``          epochs; default 0 (strictly conservative);
                            >= 0.  W > 0 speculates up to W epochs past
                            the safe horizon against a shadow copy and
                            rolls violated windows back on stragglers
                            (Time Warp lite — schedule-only, same bits).
                            Requires n_buckets >= W + 2.  Composes with
                            placement='adaptive' (windows are clamped to
                            stop short of rebalance firing epochs) and
                            with steal=True (which requires
                            opt_commit='global' — loans execute on the
                            borrower, so the verdict must be atomic).
    ``opt_stage_cap``       events per device; default 0 → route_cap;
                            >= 1 when speculating (0 otherwise).
                            Staging buffer for speculative emissions
                            that may not be published yet (remote dst,
                            or beyond the shadow window); overflow
                            aborts the window — counted as a rollback,
                            never as a drop.
    ``opt_commit``          default ``"device"``; {device, global}; only
                            with opt_window > 0.  Commit locality:
                            'device' rolls back only devices that
                            received a straggler (horizon-guarded, see
                            pipeline/speculate.py); 'global' is the
                            atomic all-or-nothing vote.  Schedule-only:
                            identical bits either way.
    ``opt_adaptive``        bool; default False; only with opt_window
                            > 0.  Host-side controller retunes the live
                            window between drain dispatches from the
                            observed rollbacks/spec_commits ratio
                            (opt_window becomes the cap).  Schedule-
                            only: any W sequence yields the same bits.
    ``inject_straggler_every``  windows; default 0 (off); only with
                            opt_window > 0.  Test-only determinism
                            harness: every n-th window is forced down
                            the rollback path on every device.  Only
                            the ``rollbacks`` activity meter (never a
                            clean counter) observes it.
    ``count_rounds``        bool; default False.  Tracing: carry the
                            scheduler's ``rounds`` in ``Stats`` (and
                            report them, with the ``lanes`` they ran, in
                            ``totals``).  Off, the epoch loop carries no
                            counter and compiles to the uninstrumented
                            program.  Observation only: same bits.
    ======================  =============================================
    """

    lookahead: float                 # model lookahead L
    epoch_len: float | None = None   # defaults to L; may be a fraction of it
    n_buckets: int = 8               # N — calendar epochs in flight
    bucket_cap: int = 128            # events per (object, bucket)
    route_cap: int = 4096            # outgoing events per device per epoch
    fallback_cap: int = 4096         # per-device fallback list capacity
    route: str = "allgather"         # Router registry key (allgather | a2a)
    scheduler: str = "batch"         # Scheduler registry key (batch | ltf | …)
    batch_impl: str = "rounds"       # rounds (vmap grid) | packed (width-
    #                                  packed tiles) | model (Pallas kernel)
    pack_tile: int = 64              # packed: vmap tile width (clamped to the
    #                                  local row count; schedule-only — any
    #                                  tile yields identical bits)
    steal: bool = False
    steal_cap: int = 4               # loans a donor may publish per epoch
    claim_cap: int = 4               # loans a receiver may claim per epoch
    placement: str = "equal"         # equal | weighted | adaptive (§II-A/C)
    rebalance_every: int = 0         # adaptive: epochs between rebalances
    migrate_cap: int = 16            # adaptive: max rows a device publishes
    #                                  per rebalance (boundary shift <= cap/2)
    placement_slack: float = 2.0     # adaptive: per-device row pad factor
    #                                  over the equal split (headroom for the
    #                                  boundaries to skew)
    opt_window: int = 0              # speculation window W (0 = conservative)
    opt_stage_cap: int = 0           # speculative-emission staging buffer
    #                                  (0 → route_cap when speculating)
    opt_commit: str = "device"       # commit locality: device (only violated
    #                                  devices roll back) | global (atomic)
    opt_adaptive: bool = False       # host-side live-W controller (W = cap)
    inject_straggler_every: int = 0  # test-only: force every n-th window to
    #                                  abort (0 = off; deterministic rollback
    #                                  coverage at any device count)
    count_rounds: bool = False       # tracing: carry the scheduler's rounds
    #                                  in Stats (off = no counter in the loop)

    def __post_init__(self):
        if self.lookahead <= 0:
            raise ValueError(f"lookahead must be > 0 (the conservative bound "
                             f"L), got {self.lookahead}")
        el = self.epoch_len if self.epoch_len is not None else self.lookahead
        if el <= 0:
            raise ValueError(f"epoch_len must be > 0, got {el}")
        if el > self.lookahead + 1e-9:
            raise ValueError("epoch_len must be <= lookahead (conservative)")
        object.__setattr__(self, "epoch_len", el)

        caps = ["n_buckets", "bucket_cap", "route_cap", "fallback_cap",
                "pack_tile"]
        if self.steal:
            caps += ["steal_cap", "claim_cap"]  # 0 would silently never steal
        for cap in caps:
            if getattr(self, cap) < 1:
                raise ValueError(f"{cap} must be >= 1, got {getattr(self, cap)}")
        if self.placement not in PLACEMENTS:
            raise ValueError(f"unknown placement {self.placement!r} "
                             f"(choose from {list(PLACEMENTS)})")
        if self.placement == "adaptive":
            if self.rebalance_every < 1:
                raise ValueError(
                    "placement='adaptive' needs rebalance_every >= 1 — with "
                    f"{self.rebalance_every} the rebalance stage would "
                    "silently never fire")
            if self.migrate_cap < 2:
                raise ValueError(
                    f"migrate_cap must be >= 2 (one row each way per "
                    f"rebalance), got {self.migrate_cap}")
            if self.placement_slack < 1.0:
                raise ValueError(
                    f"placement_slack must be >= 1.0, got "
                    f"{self.placement_slack}")
        elif self.rebalance_every:
            raise ValueError(
                f"rebalance_every={self.rebalance_every} only applies to "
                f"placement='adaptive' (got placement={self.placement!r}) — "
                "it would silently do nothing")

        if self.opt_window < 0:
            raise ValueError(
                f"opt_window must be >= 0, got {self.opt_window}")
        if self.opt_commit not in ("device", "global"):
            raise ValueError(
                f"unknown opt_commit {self.opt_commit!r} "
                "(choose from ['device', 'global'])")
        if self.opt_window > 0:
            if self.steal and self.opt_commit != "global":
                # a loaned batch executes on the borrower: a split verdict
                # could commit the borrower's staged loan emissions while
                # the aborting owner re-executes the loaned batch — the
                # same events delivered twice.  The atomic vote keeps loan
                # effects and their rollback in lockstep.
                raise ValueError(
                    "steal=True with opt_window > 0 requires "
                    "opt_commit='global' — loaned batches execute on the "
                    "borrower, so a per-device verdict could commit a "
                    "loan's emissions while its owner rolls back")
            if self.inject_straggler_every < 0:
                raise ValueError(
                    f"inject_straggler_every must be >= 0, got "
                    f"{self.inject_straggler_every}")
            if self.n_buckets < self.opt_window + 2:
                raise ValueError(
                    f"opt_window={self.opt_window} needs n_buckets >= "
                    f"{self.opt_window + 2} (got {self.n_buckets}) — the "
                    "shadow window plus the live epoch must fit the bucket "
                    "ring without wrapping onto itself")
            if self.opt_stage_cap == 0:
                object.__setattr__(self, "opt_stage_cap", self.route_cap)
            if self.opt_stage_cap < 1:
                raise ValueError(
                    f"opt_stage_cap must be >= 1 when speculating, got "
                    f"{self.opt_stage_cap}")
        else:
            # dead speculation knobs with W == 0 are rejected, not ignored:
            # a config that *looks* speculative but isn't would silently
            # change nothing.
            if self.opt_stage_cap:
                raise ValueError(
                    f"opt_stage_cap={self.opt_stage_cap} only applies with "
                    f"opt_window > 0 — it would silently do nothing")
            if self.opt_commit != "device":
                raise ValueError(
                    f"opt_commit={self.opt_commit!r} only applies with "
                    f"opt_window > 0 — it would silently do nothing")
            if self.opt_adaptive:
                raise ValueError(
                    "opt_adaptive=True only applies with opt_window > 0 — "
                    "the controller needs a window cap to tune under")
            if self.inject_straggler_every:
                raise ValueError(
                    f"inject_straggler_every={self.inject_straggler_every} "
                    "only applies with opt_window > 0 — there is no window "
                    "to abort")

        # stage-name validation against the registries (populated on package
        # import; imported lazily here so config stays cycle-free).
        from . import routers, schedulers  # noqa: F401  (registration import)
        from .base import BATCH_IMPLS, ROUTERS, SCHEDULERS
        if self.batch_impl not in BATCH_IMPLS:
            raise ValueError(f"unknown batch_impl {self.batch_impl!r} "
                             f"(choose from {sorted(BATCH_IMPLS)})")
        if self.route not in ROUTERS:
            raise ValueError(f"unknown route {self.route!r} "
                             f"(choose from {sorted(ROUTERS)})")
        internal = set(BATCH_IMPLS.values()) - {"batch"}
        known = sorted(set(SCHEDULERS) - internal | {"batch"})
        if self.scheduler in internal:
            # internal registry names — selecting one directly would let
            # scheduler and batch_impl disagree about what executes.
            raise ValueError(
                f"scheduler {self.scheduler!r} is internal; use "
                f"scheduler='batch' with batch_impl="
                f"{self.scheduler.split('-', 1)[1]!r}")
        if self.scheduler != "batch" and self.scheduler not in SCHEDULERS:
            raise ValueError(f"unknown scheduler {self.scheduler!r} "
                             f"(choose from {known})")
        if self.batch_impl != "rounds" and self.scheduler != "batch":
            raise ValueError(
                f"batch_impl={self.batch_impl!r} requires scheduler='batch' "
                f"— with scheduler={self.scheduler!r} it would silently "
                "never take effect")
        if self.steal and (self.scheduler != "batch"
                           or self.batch_impl == "model"):
            # loaned batches are concatenated onto the local extract and run
            # through the rounds-family scheduler (dense or width-packed);
            # a model-specific whole-batch kernel can't ingest the augmented
            # arrays, and silently ignoring another scheduler would change
            # semantics with no Stats counter set.
            raise ValueError(
                f"steal=True only supports scheduler='batch' with "
                f"batch_impl in ('rounds', 'packed') (got "
                f"scheduler={self.scheduler!r}, "
                f"batch_impl={self.batch_impl!r})")

    def validate(self, n_devices: int) -> None:
        """Device-count-dependent fail-fast checks (engine construction)."""
        if self.route == "a2a":
            if self.route_cap < n_devices:
                raise ValueError(
                    f"route_cap={self.route_cap} must be >= n_devices="
                    f"{n_devices} for a2a routing — the per-pair sub-buffer "
                    "(route_cap // n_devices) would be empty and every event "
                    "would spill to fallback instead of being exchanged")
            if self.route_cap % n_devices:
                raise ValueError(
                    f"route_cap={self.route_cap} must be divisible by mesh "
                    f"size {n_devices} for a2a")
