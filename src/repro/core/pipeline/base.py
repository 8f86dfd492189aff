"""Stage interfaces and registries for the engine pipeline.

The PARSIR epoch step is architecturally a fixed pipeline

    extract → steal → process → rebalance → route → deliver

and this module defines the narrow interfaces of its pluggable stages:

  * :class:`Scheduler` — how a device's per-epoch event batch is executed
    (PARSIR batch rounds, width-packed tiles, lowest-timestamp-first, or a
    model-provided whole-batch kernel);
  * :class:`Router` — how emitted events reach their owners (`allgather`
    broadcast or pairwise `a2a` exchange);
  * :class:`StealPolicy` — whether/how epoch-granular object loans rebalance
    load before processing;
  * :class:`RebalancePolicy` — whether/how the placement boundaries move at
    epoch boundaries (object + calendar-row migration).

Implementations are small registered classes (``@register_scheduler("ltf")``
…); :class:`~repro.core.pipeline.config.EngineConfig` selects them by name and
:func:`repro.core.pipeline.step.make_step` only wires them together.  Shared
engine types (``Stats``, ``EngineState``, epoch arithmetic) live here too so
every stage module can import them without cycles.

Bit-exactness contract: a stage implementation chooses *how* — an execution
schedule, an exchange topology, a load split — never *what*.  Every
registered implementation of every stage must leave the simulation's
semantics untouched: the same processed-event multiset and (for dyadic
workloads) bit-identical object state as the sequential oracle, for every
composition of stages.  The differential conformance harness
(:mod:`repro.testing.conformance`) sweeps the registry cross-product to
enforce exactly this; register a new stage and the sweep inherits it.
"""
from __future__ import annotations

import abc
import math
from typing import TYPE_CHECKING, Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from ..api import SimModel
from ..calendar import Calendar, Fallback
from ..events import EventBatch
from ..placement import Placement
from .names import BATCH_IMPLS  # noqa: F401  (re-export; names.py is jax-free)

if TYPE_CHECKING:  # pragma: no cover - import cycle guard, typing only
    from .config import EngineConfig

#: mesh axis name of the worker dimension (one program instance per device).
AXIS = "workers"


class Stats(NamedTuple):
    processed: jax.Array             # events processed on this device
    cal_overflow: jax.Array          # bucket-capacity overflows (must be 0)
    fb_overflow: jax.Array           # fallback-capacity overflows (must be 0)
    route_overflow: jax.Array        # route-capacity overflows (must be 0)
    late_events: jax.Array           # causality violations (must be 0)
    lookahead_violations: jax.Array  # model emitted ts < ts_in + L (must be 0)
    stolen: jax.Array                # loaned batches processed on this device
    oob_events: jax.Array            # emitted dst outside [0, n_objects) (must be 0)
    rebalances: jax.Array            # adaptive-placement rebalance firings
    migrated: jax.Array              # object rows received via rebalance migration
    rollbacks: jax.Array             # speculation windows aborted (straggler hit)
    speculated: jax.Array            # events processed past the safe horizon
    #                                  and committed (never counts aborted work)
    spec_commits: jax.Array          # speculation windows committed
    rounds: jax.Array | None = None  # serial steps the scheduler ran
    #                                  (aborted windows too); carried only
    #                                  under EngineConfig.count_rounds, else
    #                                  None: no leaf, no work in the loop


def stats_dtype() -> jnp.dtype:
    """Counter dtype for the in-carry Stats ledger.

    int64 when the runtime allows it (``JAX_ENABLE_X64=1``) — wide enough for
    any campaign; int32 otherwise (the JAX default truncates int64 silently),
    in which case the engine *fails fast* before any dispatch whose
    worst-case per-counter increment could overflow
    (:meth:`repro.core.engine.ParsirEngine` checks the bound).
    """
    return jnp.int64 if jax.config.jax_enable_x64 else jnp.int32


def zero_stats(count_rounds: bool = False) -> Stats:
    z = jnp.zeros((1,), stats_dtype())
    return Stats(*(z,) * (len(Stats._fields) - 1),
                 rounds=z if count_rounds else None)


def tally(count: jax.Array | None, n: jax.Array) -> jax.Array | None:
    """``count + n`` for a counter the state may not carry (``None``)."""
    return None if count is None else count + n


class EngineState(NamedTuple):
    cal: Calendar
    fb: Fallback
    obj: Any
    epoch: jax.Array   # i32 [1] per device (identical everywhere)
    stats: Stats
    bounds: jax.Array  # i32 [1, n_devices + 1] per device (identical everywhere)
    load: jax.Array    # i32 [n_local_max] per-object processed counts since
    #                    the last rebalance (measured placement weights)


def epoch_of(ts: jax.Array, epoch_len: float) -> jax.Array:
    return jnp.floor(ts * jnp.float32(1.0 / epoch_len)
                     if math.log2(1.0 / epoch_len).is_integer()
                     else ts / jnp.float32(epoch_len)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# stage interfaces
# ---------------------------------------------------------------------------

#: a scheduler's result: (updated object pytree, flat emitted EventBatch,
#: lookahead-violation count, rounds).  ``rounds`` counts the serial steps
#: the schedule ran (vmap rounds, packed tiles, kernel grid steps, single
#: events) — the scheduler's work, beside the events it committed.
ProcessResult = tuple[Any, EventBatch, jax.Array, jax.Array]


class Scheduler(abc.ABC):
    """Per-epoch batch execution strategy (pipeline stage 3, paper §II-A).

    Contract: a scheduler is a *schedule*, never a semantics change.  It
    must process each object's epoch batch in timestamp order (intra-object
    causality) and call the model's ``process_event`` with exactly the
    extracted (ts, seed, payload) values — so any scheduler, at any tile
    width or round order, produces bit-identical object state and the
    identical emitted-event multiset.
    """

    name: str

    def validate(self, model: SimModel, cfg: "EngineConfig") -> None:
        """Fail fast at engine construction if the model/config can't run."""

    @abc.abstractmethod
    def process(self, model: SimModel, cfg: "EngineConfig", obj: Any,
                ts_s: jax.Array, seed_s: jax.Array, pay_s: jax.Array,
                cnt_b: jax.Array) -> ProcessResult:
        """Apply every object's sorted epoch batch; return emitted events.

        Inputs are the per-object [n_local, cap] arrays of
        :func:`repro.core.calendar.extract_sorted`; ``cfg`` carries the
        execution knobs a scheduler may consult (``lookahead``,
        ``pack_tile``, …).  The returned EventBatch is flat with ``valid``
        masks honored downstream — a scheduler may emit 0..``model.max_out``
        events per processed event.  The last result is the ``rounds`` it
        ran (an i32 scalar, see :data:`ProcessResult`).
        """

    def lanes_per_round(self, cfg: "EngineConfig", n_rows: int) -> int | None:
        """Event slots one round executes, occupied or not, on ``n_rows``
        local rows (static), or ``None`` where a round has no fixed width.
        ``rounds`` times this is the scheduler's ``lanes``."""
        return None


class Router(abc.ABC):
    """Event exchange strategy (pipeline stage 5, paper §II-B).

    Contract: routing moves events, never invents, drops or reorders them.
    Events that don't fit the route buffer must be handed back (the caller
    parks them in the fallback list) and any true capacity loss *counted* —
    the conformance harness asserts the counters stay zero and the pending
    multiset matches the oracle under either topology.

    ``replicated`` declares the exchange's output topology so per-event
    counters downstream can be reduced correctly: True means every device
    sees the *same* routed batch (allgather broadcast — count each event
    once globally, e.g. on device 0), False means each device sees a
    *distinct* slice (pairwise a2a — every device counts its own events).
    Getting this wrong silently over- or under-counts delivery-side
    ``oob_events``.
    """

    name: str
    #: True if exchange() presents an identical batch on every device
    #: (broadcast); False if each device receives a distinct slice.
    replicated: bool = True

    def validate(self, cfg: "EngineConfig", placement: Placement) -> None:
        """Fail fast at engine construction on bad capacity/topology."""

    @abc.abstractmethod
    def select_send(self, prod: EventBatch, eligible: jax.Array,
                    placement: Placement, cfg: "EngineConfig"
                    ) -> tuple[EventBatch, jax.Array, jax.Array]:
        """Pick which eligible produced events ride this epoch's exchange.

        Returns (route buffer, sent-mask over ``prod``, overflow count).
        Unsent valid events are the caller's to park in the fallback buffer.
        """

    @abc.abstractmethod
    def exchange(self, buf: EventBatch, placement: Placement,
                 cfg: "EngineConfig") -> EventBatch:
        """Run the collective; return the events visible to this device."""

    def sender_ids(self, placement: Placement, cfg: "EngineConfig"
                   ) -> jax.Array:
        """Source device of each slot in an :meth:`exchange` output batch.

        A static i32 vector matching the exchange output's slot count —
        both built-in exchanges pack by source positionally, so provenance
        is recoverable without widening the event record.  The speculation
        stage uses it to filter speculative arrivals by the *sender's*
        commit verdict (``opt_commit='device'``); a custom router must
        override this to compose with per-device commit.
        """
        raise NotImplementedError(
            f"router {self.name!r} does not expose sender identity; "
            "override sender_ids() to compose with opt_commit='device'")


class StealPolicy(abc.ABC):
    """Load-balancing strategy (pipeline stage 2, paper §II-A)."""

    name: str

    @abc.abstractmethod
    def process(self, model: SimModel, scheduler: Scheduler,
                cfg: "EngineConfig", placement: Placement, dev: jax.Array,
                obj: Any, ts_s: jax.Array, seed_s: jax.Array,
                pay_s: jax.Array, cnt_b: jax.Array
                ) -> tuple[Any, EventBatch, jax.Array, jax.Array, jax.Array,
                           jax.Array]:
        """Run stage 2+3 (rebalance, then process).

        Returns (obj, flat emitted EventBatch, lookahead violations,
        stolen-batch count, processed-event count, scheduler rounds).
        """


class RebalancePolicy(abc.ABC):
    """Placement-rebalancing strategy (epoch-boundary stage, paper §II-C).

    Where :class:`StealPolicy` loans an object's *current-epoch batch* and
    returns it (ownership never moves), a rebalance policy moves *ownership*:
    it recomputes the contiguous placement boundaries from measured load and
    migrates object state + calendar rows to the new owners.  It runs between
    the process and route stages, so the epoch's freshly emitted events are
    routed against the NEW boundaries, and fallback entries (which carry
    global dst) re-route themselves through the existing routers on the next
    epochs — no fallback migration is needed.
    """

    name: str

    @abc.abstractmethod
    def rebalance(self, cfg: "EngineConfig", placement: Placement,
                  dev: jax.Array, cur: jax.Array, bounds: jax.Array,
                  load: jax.Array, cal: Calendar, obj: Any
                  ) -> tuple[jax.Array, jax.Array, Calendar, Any,
                             jax.Array, jax.Array]:
        """Maybe move the boundaries and migrate rows.

        ``bounds`` is the live i32[n_devices+1] boundaries vector, ``load``
        the per-local-row processed counts accumulated since the last firing
        (this epoch included).  Returns (bounds, load, cal, obj,
        n_rows_received, fired ∈ {0, 1}); non-firing epochs return everything
        unchanged.
        """


# ---------------------------------------------------------------------------
# registries
# ---------------------------------------------------------------------------

SCHEDULERS: dict[str, Scheduler] = {}
ROUTERS: dict[str, Router] = {}
STEAL_POLICIES: dict[str, StealPolicy] = {}
REBALANCERS: dict[str, RebalancePolicy] = {}


def _register(registry: dict, kind: str, name: str) -> Callable:
    def deco(cls):
        if name in registry:
            raise ValueError(f"{kind} {name!r} already registered")
        cls.name = name
        registry[name] = cls()
        return cls
    return deco


def register_scheduler(name: str):
    """Class decorator: register a :class:`Scheduler` under ``name``."""
    return _register(SCHEDULERS, "scheduler", name)


def register_router(name: str):
    """Class decorator: register a :class:`Router` under ``name``."""
    return _register(ROUTERS, "router", name)


def register_steal_policy(name: str):
    """Class decorator: register a :class:`StealPolicy` under ``name``."""
    return _register(STEAL_POLICIES, "steal policy", name)


def register_rebalancer(name: str):
    """Class decorator: register a :class:`RebalancePolicy` under ``name``."""
    return _register(REBALANCERS, "rebalancer", name)


def resolve_scheduler(cfg: "EngineConfig") -> Scheduler:
    """EngineConfig → Scheduler.

    The PARSIR ``batch`` scheduler is further split by ``batch_impl``
    (``rounds`` = vmap loop, ``packed`` = width-packed tiles, ``model`` = the
    model's whole-batch kernel), preserving the historical config surface;
    any other name (``ltf``, or a user-registered scheduler) is looked up
    directly.
    """
    if cfg.scheduler == "batch":
        return SCHEDULERS[BATCH_IMPLS[cfg.batch_impl]]
    return SCHEDULERS[cfg.scheduler]


def resolve_router(name: str) -> Router:
    return ROUTERS[name]


def resolve_steal(cfg: "EngineConfig", n_devices: int) -> StealPolicy:
    if cfg.steal and n_devices > 1:
        return STEAL_POLICIES["loan"]
    return STEAL_POLICIES["none"]


def resolve_rebalance(cfg: "EngineConfig") -> RebalancePolicy:
    if cfg.placement == "adaptive":
        return REBALANCERS["adaptive"]
    return REBALANCERS["none"]
