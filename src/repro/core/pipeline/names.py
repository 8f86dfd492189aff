"""Selectable stage names, importable without JAX.

Single source of truth for the *user-facing* choice sets of the pipeline
(`EngineConfig` fields, CLI ``choices=``).  The live registries in
:mod:`repro.core.pipeline.base` are populated by importing the stage modules
— which import JAX — so anything that must enumerate the choices in a
dependency-free context (the CI docs job, :mod:`repro.testing.docs_check`'s
CLI cross-check) reads this module instead.  ``tests/test_pipeline.py``
asserts these constants match the populated registries, so they cannot
silently drift.

This module must stay stdlib-only (no jax, no numpy): docs_check loads it
by file path in an environment with nothing installed.
"""
from __future__ import annotations

#: the ``scheduler='batch'`` family, split by ``EngineConfig.batch_impl``
#: (keys = selectable batch_impl values, values = internal registry names).
BATCH_IMPLS: dict[str, str] = {"rounds": "batch", "model": "batch-model",
                               "packed": "batch-packed"}

#: directly selectable ``EngineConfig.scheduler`` names (the internal
#: batch-family registry names are reached via ``batch_impl``, never named).
SELECTABLE_SCHEDULERS: tuple[str, ...] = ("batch", "ltf")

#: ``EngineConfig.route`` registry keys.
ROUTES: tuple[str, ...] = ("allgather", "a2a")

#: ``EngineConfig.placement`` values (paper §II-A/§II-C knapsacks).
PLACEMENTS: tuple[str, ...] = ("equal", "weighted", "adaptive")

#: ``EngineConfig`` fields of the bounded-optimism speculation stage
#: (Time Warp lite).  Every knob here must be exposed as a ``--opt-*`` CLI
#: flag by the simulate driver — :mod:`repro.testing.docs_check` derives the
#: required flag names from this tuple, so a new speculation knob that never
#: reaches the CLI fails the docs job.
#: (``inject_straggler_every`` is deliberately absent: it is a test-only
#: determinism harness, not a user-facing speculation knob.)
SPECULATION_KNOBS: tuple[str, ...] = ("opt_window", "opt_stage_cap",
                                      "opt_commit", "opt_adaptive")

#: ``jax.named_scope`` names of the epoch step's stages.  Every device op of
#: a step carries one in its ``op_name`` metadata, so a profiler trace (or
#: the compiled HLO) attributes device time to a stage; a fused op takes its
#: root op's scope.  The conservative step (``pipeline/step.py``) opens the
#: first six; the speculative step (``pipeline/speculate.py``) opens them
#: for its safe section and sub-epochs plus the last four for its own parts.
EXTRACT = "parsir.extract"        # drain + sort the epoch's calendar bucket
PROCESS = "parsir.process"        # steal policy + scheduler
REBALANCE = "parsir.rebalance"    # adaptive placement: boundaries, migration
ROUTE = "parsir.route"            # producer triage, select_send, fallback
EXCHANGE = "parsir.exchange"      # the router's collective
DELIVER = "parsir.deliver"        # owner-side calendar/fallback insertion
SHADOW = "parsir.shadow"          # speculation: snapshot of window buckets
VERDICT = "parsir.verdict"        # speculation: stragglers, the vote
COMMIT = "parsir.commit"          # speculation: keep the window
RESTORE = "parsir.restore"        # speculation: roll the window back
STAGE_SCOPES: tuple[str, ...] = (EXTRACT, PROCESS, REBALANCE, ROUTE, EXCHANGE,
                                 DELIVER, SHADOW, VERDICT, COMMIT, RESTORE)
