"""The calendar multi-queue (paper §II-B), as dense device-resident rings.

Per device we keep, for its local objects, a calendar of ``n_buckets`` epoch
buckets with a static per-bucket capacity:

    ts/seed/payload : [n_local, n_buckets, cap]     (compact: slots [0, cnt) live)
    cnt             : [n_local, n_buckets]

Buckets are reused circularly exactly as in the paper: bucket ``e % n_buckets``
holds epoch ``e``; once epoch ``e`` is drained the bucket is cleared and becomes
epoch ``e + n_buckets``.

Insertion is the paper's "per-bucket spinlock" path made *structurally*
conflict-free: incoming events are sorted by (object, bucket), ranks inside each
group are computed with prefix sums, and every event lands at
``cnt[obj, bucket] + rank`` — a lock-free scatter (the TPU replacement for RMW
spinlocks: slot assignment by scan instead of contention).

Extraction in the *current* epoch needs no synchronization at all, mirroring the
paper's lock-free fast path: the SPMD owner is the only reader/writer, and the
lookahead guarantees nobody inserts into the live bucket.

Overflow (bucket capacity exceeded) is counted and returned — never silent; the
conservative engine treats a nonzero count as a hard error at the driver level.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .events import EventBatch, concat_batches, take_first


class Calendar(NamedTuple):
    ts: jax.Array       # f32 [n_local, n_buckets, cap]
    seed: jax.Array     # u32 [n_local, n_buckets, cap]
    payload: jax.Array  # f32 [n_local, n_buckets, cap]
    cnt: jax.Array      # i32 [n_local, n_buckets]

    @property
    def n_local(self) -> int:
        return self.ts.shape[0]

    @property
    def n_buckets(self) -> int:
        return self.ts.shape[1]

    @property
    def cap(self) -> int:
        return self.ts.shape[2]


def make_calendar(n_local: int, n_buckets: int, cap: int) -> Calendar:
    return Calendar(
        ts=jnp.full((n_local, n_buckets, cap), jnp.inf, jnp.float32),
        seed=jnp.zeros((n_local, n_buckets, cap), jnp.uint32),
        payload=jnp.zeros((n_local, n_buckets, cap), jnp.float32),
        cnt=jnp.zeros((n_local, n_buckets), jnp.int32),
    )


def group_ranks(key: jax.Array, valid: jax.Array, sentinel: int):
    """Sort events by group key; return (order, sorted_key, rank-in-group).

    rank[i] = position of sorted element i inside its contiguous key group —
    the prefix-sum replacement for fetch_and_add slot assignment.  Shared with
    the width-packer (:mod:`repro.core.pipeline.packing`), whose unpack path
    is the same group-and-rank scatter keyed by object row.
    """
    k = jnp.where(valid, key, sentinel)
    order = jnp.argsort(k, stable=True)
    ks = k[order]
    idx = jnp.arange(k.shape[0], dtype=jnp.int32)
    is_start = jnp.concatenate([jnp.ones((1,), bool), ks[1:] != ks[:-1]])
    start_idx = jax.lax.associative_scan(jnp.maximum, jnp.where(is_start, idx, 0))
    rank = idx - start_idx
    return order, ks, rank


def insert(cal: Calendar, local_idx: jax.Array, epoch: jax.Array,
           ts: jax.Array, seed: jax.Array, payload: jax.Array,
           valid: jax.Array):
    """Insert a flat batch of events destined to local objects.

    epoch must already be within the calendar horizon (caller splits fallback).
    Returns (calendar, n_overflow).
    """
    n_local, n_buckets, cap = cal.ts.shape
    bucket = (epoch % n_buckets).astype(jnp.int32)
    key = local_idx * n_buckets + bucket
    sentinel = n_local * n_buckets
    order, ks, rank = group_ranks(key, valid, sentinel)

    ts_s = ts[order]
    seed_s = seed[order]
    pay_s = payload[order]
    valid_s = ks < sentinel

    base = cal.cnt.reshape(-1)[jnp.where(valid_s, ks, 0)]
    slot = base + rank
    ok = valid_s & (slot < cap)
    n_overflow = jnp.sum((valid_s & ~ok).astype(jnp.int32))

    flat = jnp.where(ok, ks * cap + slot, n_local * n_buckets * cap)
    new_ts = cal.ts.reshape(-1).at[flat].set(ts_s, mode="drop").reshape(cal.ts.shape)
    new_seed = cal.seed.reshape(-1).at[flat].set(seed_s, mode="drop").reshape(cal.seed.shape)
    new_pay = cal.payload.reshape(-1).at[flat].set(pay_s, mode="drop").reshape(cal.payload.shape)

    cnt_flat = cal.cnt.reshape(-1).at[jnp.where(ok, ks, sentinel)].add(
        jnp.ones_like(ks, jnp.int32), mode="drop")
    new_cnt = cnt_flat.reshape(cal.cnt.shape)
    return Calendar(new_ts, new_seed, new_pay, new_cnt), n_overflow


def extract_sorted(cal: Calendar, epoch: jax.Array):
    """Drain the bucket for ``epoch``: per-object events sorted by (ts, seed).

    Returns (calendar-with-cleared-bucket, ts, seed, payload, cnt_b) where the
    event arrays are [n_local, cap] with invalid slots at ts=+inf.  This is the
    paper's lock-free current-epoch extraction — plus the batch ordering that
    per-object causality requires.
    """
    n_local, n_buckets, cap = cal.ts.shape
    b = (epoch % n_buckets).astype(jnp.int32)
    ts = jax.lax.dynamic_index_in_dim(cal.ts, b, axis=1, keepdims=False)
    seed = jax.lax.dynamic_index_in_dim(cal.seed, b, axis=1, keepdims=False)
    pay = jax.lax.dynamic_index_in_dim(cal.payload, b, axis=1, keepdims=False)
    cnt_b = jax.lax.dynamic_index_in_dim(cal.cnt, b, axis=1, keepdims=False)

    live = jnp.arange(cap, dtype=jnp.int32)[None, :] < cnt_b[:, None]
    ts = jnp.where(live, ts, jnp.inf)

    # lexicographic (ts, seed): two stable argsorts composed.
    p1 = jnp.argsort(seed, axis=1, stable=True)
    ts1 = jnp.take_along_axis(ts, p1, axis=1)
    p2 = jnp.argsort(ts1, axis=1, stable=True)
    order = jnp.take_along_axis(p1, p2, axis=1)

    ts = jnp.take_along_axis(ts, order, axis=1)
    seed = jnp.take_along_axis(seed, order, axis=1)
    pay = jnp.take_along_axis(pay, order, axis=1)

    # clear the bucket for reuse (epoch + n_buckets).
    new_cnt = jax.lax.dynamic_update_index_in_dim(
        cal.cnt, jnp.zeros((n_local,), jnp.int32), b, axis=1)
    new_ts = jax.lax.dynamic_update_index_in_dim(
        cal.ts, jnp.full((n_local, cap), jnp.inf, jnp.float32), b, axis=1)
    return cal._replace(ts=new_ts, cnt=new_cnt), ts, seed, pay, cnt_b


# ---------------------------------------------------------------------------
# bulk row movement (adaptive-placement migration, paper §II-C)
# ---------------------------------------------------------------------------

def take_rows(cal: Calendar, idx: jax.Array) -> Calendar:
    """Gather whole per-object calendar rows (all buckets, all slots).

    Bucket indices are absolute-epoch modulo ``n_buckets`` — identical on
    every device — so a row's bucket contents stay valid wherever the row
    lands.  This is the bulk-extract half of object migration: the rebalance
    stage ships rows wholesale instead of flattening events through the
    bounded route path (no capacity to overflow, nothing to drop).
    """
    return Calendar(cal.ts[idx], cal.seed[idx], cal.payload[idx],
                    cal.cnt[idx])


def put_rows(cal: Calendar, idx: jax.Array, rows: Calendar,
             mask: jax.Array) -> Calendar:
    """Scatter whole calendar rows into local slots where ``mask`` holds.

    The reinsert half of migration: receivers overwrite the slot wholesale
    (the migrated row replaces whatever the slot held — callers guarantee the
    slot was vacated).  Masked-off rows are dropped via an out-of-range index.
    """
    safe = jnp.where(mask, idx, cal.n_local)
    put = lambda dstf, srcf: dstf.at[safe].set(srcf, mode="drop")
    return Calendar(put(cal.ts, rows.ts), put(cal.seed, rows.seed),
                    put(cal.payload, rows.payload), put(cal.cnt, rows.cnt))


def clear_rows(cal: Calendar, dead: jax.Array) -> Calendar:
    """Deaden rows where ``dead`` holds: zero counts, +inf timestamps.

    Used after a rebalance shifts a device's range: slots no longer backing a
    live object must never contribute events (extraction and the pending-
    multiset readers both key off ``cnt``/``ts``).
    """
    cnt = jnp.where(dead[:, None], 0, cal.cnt)
    ts = jnp.where(dead[:, None, None], jnp.inf, cal.ts)
    return cal._replace(ts=ts, cnt=cnt)


def take_buckets(cal: Calendar, first_epoch, n: int) -> Calendar:
    """Snapshot ``n`` consecutive epoch buckets starting at ``first_epoch``.

    The shadow-copy half of the speculation stage (pipeline/speculate.py):
    the returned Calendar holds the window's buckets only — O(W) rows per
    object, not the whole ring — in window order (bucket axis index w holds
    epoch ``first_epoch + w``).  The complement of :func:`take_rows`: rows
    select objects, this selects *epochs*.
    """
    idx = (first_epoch + jnp.arange(n, dtype=jnp.int32)) % cal.n_buckets
    return Calendar(cal.ts[:, idx], cal.seed[:, idx], cal.payload[:, idx],
                    cal.cnt[:, idx])


def put_buckets(cal: Calendar, first_epoch, shadow: Calendar) -> Calendar:
    """Restore a :func:`take_buckets` snapshot wholesale (rollback).

    Every slot of the window's buckets is overwritten from the shadow —
    speculative insertions vanish, speculative extractions reappear — so the
    calendar is bit-restored to the snapshot point for those epochs.
    Buckets outside the window are untouched — the disjointness that makes
    the restore *local*: under per-device commit (``opt_commit='device'``)
    only violated devices run it, and a device's rollback can never disturb
    epochs (its own or anyone else's) outside its window.  Property-tested
    in tests/test_property.py: take ∘ damage ∘ put is the identity on the
    window, ring wrap-around included.
    """
    n = shadow.ts.shape[1]
    idx = (first_epoch + jnp.arange(n, dtype=jnp.int32)) % cal.n_buckets
    return Calendar(cal.ts.at[:, idx].set(shadow.ts),
                    cal.seed.at[:, idx].set(shadow.seed),
                    cal.payload.at[:, idx].set(shadow.payload),
                    cal.cnt.at[:, idx].set(shadow.cnt))


class Fallback(NamedTuple):
    """The per-thread TLS fallback list (paper §II-B) → per-device buffer.

    Events whose epoch lies beyond the calendar horizon (or that missed the
    route-capacity this epoch) park here with their *global* dst and are
    re-offered every epoch close, exactly like the paper drains TLS lists as
    the circular calendar advances.
    """

    events: EventBatch  # flat [cap]

    @property
    def cap(self) -> int:
        return self.events.capacity


def make_fallback(cap: int) -> Fallback:
    from .events import empty_batch
    return Fallback(empty_batch(cap))


def fallback_put(fb: Fallback, new: EventBatch):
    """Append valid events of ``new`` into free slots of the fallback buffer.

    Returns (fallback, n_overflow).  Live events stay in front, in order.
    """
    merged = concat_batches(fb.events, new)
    keep, spill = take_first(merged, merged.valid, fb.cap)
    return Fallback(keep), spill
