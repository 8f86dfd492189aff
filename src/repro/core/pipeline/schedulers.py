"""Scheduler stage implementations (paper §II-A).

``batch``        — PARSIR's per-object batch rounds: round r applies the r-th
                   (ts, seed)-ordered event of every object in parallel
                   (vmap), keeping each object's state register/VMEM-hot
                   across its whole batch.
``batch-packed`` — the same schedule width-packed: the occupied slots of the
                   epoch slice are compacted round-major into a dense work
                   list (:mod:`repro.core.pipeline.packing`) and processed in
                   fixed-size vmap tiles with a per-tile state gather /
                   scatter-back.  Same bits, different schedule: epoch cost
                   scales with the events actually present instead of
                   ``max batch depth × padded row width``.
``batch-model``  — same schedule, but the whole per-object batch goes through
                   the model's own ``process_batch`` kernel (e.g. the Pallas
                   event-apply kernel) instead of the vmap rounds loop.
``ltf``          — strict lowest-timestamp-first interleaving across objects
                   (ROOT-Sim/USE-style), one event at a time — same results,
                   no batch locality.  The Fig-5 analogue comparison point.

Schedulers receive the live :class:`~repro.core.pipeline.config.EngineConfig`
(``process(model, cfg, obj, …)``) so implementation knobs — ``lookahead``,
the packer's ``pack_tile`` — stay on the config instead of leaking into the
stage interface one positional argument at a time.

All schedulers honor the generalized emission contract: each processed event
may emit 0..``model.max_out`` events; emitted ``valid`` masks flow through
unchanged (an absorbing model simply emits an all-invalid row).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..api import SimModel
from ..events import EventBatch
from .base import Scheduler, register_scheduler
from .packing import effective_tile, pack_slice


def process_batch_rounds(model: SimModel, obj: Any, ts_s, seed_s, pay_s,
                         cnt_b, lookahead: float):
    """Round r applies the r-th (ts,seed)-ordered event of every object.

    A plain function (not just a method) because the loan-stealing policy
    reuses it for the claimed-batch augmented processing pass.  Runs
    ``max(cnt_b)`` rounds of ``n_rows`` lanes each, live or not.
    """
    n_rows, C = ts_s.shape
    mo = model.max_out
    out0 = EventBatch(
        dst=jnp.zeros((C, n_rows, mo), jnp.int32),
        ts=jnp.full((C, n_rows, mo), jnp.inf, jnp.float32),
        seed=jnp.zeros((C, n_rows, mo), jnp.uint32),
        payload=jnp.zeros((C, n_rows, mo), jnp.float32),
        valid=jnp.zeros((C, n_rows, mo), bool),
    )

    def body(r, carry):
        obj, out, lv = carry
        ets = jax.lax.dynamic_index_in_dim(ts_s, r, axis=1, keepdims=False)
        eseed = jax.lax.dynamic_index_in_dim(seed_s, r, axis=1, keepdims=False)
        epay = jax.lax.dynamic_index_in_dim(pay_s, r, axis=1, keepdims=False)
        m = r < cnt_b
        new_obj, emitted = jax.vmap(model.process_event)(obj, ets, eseed, epay)

        def sel(n, o):
            mm = m.reshape(m.shape + (1,) * (n.ndim - 1))
            return jnp.where(mm, n, o)

        obj = jax.tree.map(sel, new_obj, obj)
        ev_valid = emitted.valid & m[:, None]
        lv = lv + jnp.sum((ev_valid
                           & (emitted.ts < ets[:, None] + jnp.float32(lookahead))
                           ).astype(jnp.int32))
        out = EventBatch(
            dst=out.dst.at[r].set(emitted.dst),
            ts=out.ts.at[r].set(jnp.where(ev_valid, emitted.ts, jnp.inf)),
            seed=out.seed.at[r].set(emitted.seed),
            payload=out.payload.at[r].set(emitted.payload),
            valid=out.valid.at[r].set(ev_valid),
        )
        return obj, out, lv

    # `initial=0` handles the zero-rows slice uniformly — jnp.max on an empty
    # array would raise at trace time, and a Python shape branch here used to
    # leave the n_rows == 0 path untested.
    max_r = jnp.max(cnt_b, initial=0).astype(jnp.int32)
    obj, out, lv = jax.lax.fori_loop(
        0, max_r, body, (obj, out0, jnp.int32(0)))
    flat = EventBatch(*(x.reshape(-1) for x in out))
    return obj, flat, lv, max_r


def process_batch_packed(model: SimModel, obj: Any, ts_s, seed_s, pay_s,
                         cnt_b, lookahead: float, tile: int):
    """Width-packed batch rounds: dense tiles over the occupied slots.

    The slice is packed round-major (see :mod:`.packing`): tiles never span a
    round boundary, so each tile holds at most one event per object and the
    per-tile gather → vmap(process_event) → scatter-back is conflict-free,
    while an object's rounds land in strictly increasing tiles (the scatter
    carries its state forward).  Identical per-event inputs in identical
    intra-object order ⇒ bit-identical results to ``batch``.  Runs
    ``n_tiles`` rounds of ``tile`` lanes each.
    """
    n_rows, C = ts_s.shape
    mo = model.max_out
    packed = pack_slice(ts_s, seed_s, pay_s, cnt_b, tile)
    k_pad, T = packed.ts.shape[0], packed.tile
    out0 = EventBatch(
        dst=jnp.zeros((k_pad, mo), jnp.int32),
        ts=jnp.full((k_pad, mo), jnp.inf, jnp.float32),
        seed=jnp.zeros((k_pad, mo), jnp.uint32),
        payload=jnp.zeros((k_pad, mo), jnp.float32),
        valid=jnp.zeros((k_pad, mo), bool),
    )
    if k_pad == 0:
        zero = jnp.int32(0)
        return obj, EventBatch(*(x.reshape(-1) for x in out0)), zero, zero

    def body(t, carry):
        obj, out, lv = carry
        start = t * T
        sl = lambda a: jax.lax.dynamic_slice(a, (start,), (T,))
        rows, vvalid = sl(packed.row), sl(packed.valid)
        vts, vseed, vpay = sl(packed.ts), sl(packed.seed), sl(packed.payload)

        st = jax.tree.map(lambda l: l[jnp.clip(rows, 0, n_rows - 1)], obj)
        new_st, emitted = jax.vmap(model.process_event)(st, vts, vseed, vpay)

        # dead slots scatter to the n_rows sentinel and drop.
        scat_rows = jnp.where(vvalid, rows, n_rows)
        obj = jax.tree.map(
            lambda l, n: l.at[scat_rows].set(n, mode="drop"), obj, new_st)

        ev_valid = emitted.valid & vvalid[:, None]
        lv = lv + jnp.sum((ev_valid
                           & (emitted.ts < vts[:, None] + jnp.float32(lookahead))
                           ).astype(jnp.int32))
        upd = lambda dst, src: jax.lax.dynamic_update_slice(dst, src,
                                                            (start, 0))
        out = EventBatch(
            dst=upd(out.dst, emitted.dst),
            ts=upd(out.ts, jnp.where(ev_valid, emitted.ts, jnp.inf)),
            seed=upd(out.seed, emitted.seed),
            payload=upd(out.payload, emitted.payload),
            valid=upd(out.valid, ev_valid),
        )
        return obj, out, lv

    obj, out, lv = jax.lax.fori_loop(
        0, packed.n_tiles, body, (obj, out0, jnp.int32(0)))
    flat = EventBatch(*(x.reshape(-1) for x in out))
    return obj, flat, lv, packed.n_tiles


@register_scheduler("batch")
class BatchRoundsScheduler(Scheduler):
    """PARSIR per-object batch processing via the vmap rounds loop."""

    def process(self, model, cfg, obj, ts_s, seed_s, pay_s, cnt_b):
        return process_batch_rounds(model, obj, ts_s, seed_s, pay_s, cnt_b,
                                    cfg.lookahead)

    def lanes_per_round(self, cfg, n_rows):
        return n_rows


@register_scheduler("batch-packed")
class PackedBatchScheduler(Scheduler):
    """Width-packed batch rounds (``batch_impl='packed'``): process only the
    occupied event slots, in ``pack_tile``-wide vmap tiles."""

    def process(self, model, cfg, obj, ts_s, seed_s, pay_s, cnt_b):
        return process_batch_packed(model, obj, ts_s, seed_s, pay_s, cnt_b,
                                    cfg.lookahead, cfg.pack_tile)

    def lanes_per_round(self, cfg, n_rows):
        return effective_tile(cfg.pack_tile, n_rows)


@register_scheduler("batch-model")
class ModelKernelScheduler(Scheduler):
    """Whole per-object batches through the model's own kernel
    (``batch_impl='model'``, e.g. Pallas event-apply).  The model's
    ``process_batch`` reports its kernel's grid steps as the rounds."""

    def validate(self, model, cfg):
        if not hasattr(model, "process_batch"):
            raise ValueError("batch_impl='model' needs model.process_batch")

    def process(self, model, cfg, obj, ts_s, seed_s, pay_s, cnt_b):
        return model.process_batch(obj, ts_s, seed_s, pay_s, cnt_b,
                                   cfg.lookahead)


@register_scheduler("ltf")
class LtfScheduler(Scheduler):
    """Strict lowest-timestamp-first interleaving across objects."""

    def process(self, model, cfg, obj, ts_s, seed_s, pay_s, cnt_b):
        lookahead = cfg.lookahead
        n_rows, C = ts_s.shape
        mo = model.max_out
        rows = jnp.broadcast_to(jnp.arange(n_rows, dtype=jnp.int32)[:, None],
                                (n_rows, C)).reshape(-1)
        live = (jnp.arange(C, dtype=jnp.int32)[None, :]
                < cnt_b[:, None]).reshape(-1)
        ts_f = jnp.where(live, ts_s.reshape(-1), jnp.inf)
        seed_f, pay_f = seed_s.reshape(-1), pay_s.reshape(-1)

        p1 = jnp.argsort(seed_f, stable=True)
        p2 = jnp.argsort(ts_f[p1], stable=True)
        order = p1[p2]
        ts_f, seed_f, pay_f = ts_f[order], seed_f[order], pay_f[order]
        rows, live = rows[order], live[order]

        K = n_rows * C
        out0 = EventBatch(
            dst=jnp.zeros((K, mo), jnp.int32),
            ts=jnp.full((K, mo), jnp.inf, jnp.float32),
            seed=jnp.zeros((K, mo), jnp.uint32),
            payload=jnp.zeros((K, mo), jnp.float32),
            valid=jnp.zeros((K, mo), bool),
        )

        def body(i, carry):
            obj, out, lv = carry
            row = rows[i]
            st = jax.tree.map(lambda l: l[row], obj)
            new_st, emitted = model.process_event(st, ts_f[i], seed_f[i],
                                                  pay_f[i])
            obj = jax.tree.map(lambda l, n: l.at[row].set(n), obj, new_st)
            lv = lv + jnp.sum((emitted.valid
                               & (emitted.ts < ts_f[i] + jnp.float32(lookahead))
                               ).astype(jnp.int32))
            out = EventBatch(
                dst=out.dst.at[i].set(emitted.dst),
                ts=out.ts.at[i].set(jnp.where(emitted.valid, emitted.ts,
                                              jnp.inf)),
                seed=out.seed.at[i].set(emitted.seed),
                payload=out.payload.at[i].set(emitted.payload),
                valid=out.valid.at[i].set(emitted.valid),
            )
            return obj, out, lv

        total = jnp.sum(cnt_b)
        obj, out, lv = jax.lax.fori_loop(0, total, body,
                                         (obj, out0, jnp.int32(0)))
        flat = EventBatch(*(x.reshape(-1) for x in out))
        # one event a round: ``sum(cnt_b)`` rounds.
        return obj, flat, lv, total

    def lanes_per_round(self, cfg, n_rows):
        return 1
