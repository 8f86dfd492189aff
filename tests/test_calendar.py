"""Unit tests for the calendar multi-queue (ring reuse, conflict-free insert,
sorted extraction, fallback list)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import calendar as cal_mod
from repro.core.calendar import (Calendar, Fallback, extract_sorted,
                                 fallback_put, insert, make_calendar,
                                 make_fallback)
from repro.core.events import EventBatch, empty_batch


def _flat_events(local_idx, epoch, ts, seed):
    k = len(local_idx)
    return (jnp.asarray(local_idx, jnp.int32), jnp.asarray(epoch, jnp.int32),
            jnp.asarray(ts, jnp.float32), jnp.asarray(seed, jnp.uint32),
            jnp.zeros((k,), jnp.float32), jnp.ones((k,), bool))


def test_insert_then_extract_sorted():
    cal = make_calendar(n_local=4, n_buckets=4, cap=8)
    li, ep, ts, seed, pay, valid = _flat_events(
        [2, 2, 2, 0], [1, 1, 1, 1], [1.9, 1.2, 1.5, 1.0], [7, 9, 8, 1])
    cal, ovf = insert(cal, li, ep, ts, seed, pay, valid)
    assert int(ovf) == 0
    assert int(cal.cnt[2, 1]) == 3 and int(cal.cnt[0, 1]) == 1

    cal, ts_s, seed_s, pay_s, cnt = extract_sorted(cal, jnp.int32(1))
    np.testing.assert_allclose(np.asarray(ts_s[2, :3]), [1.2, 1.5, 1.9])
    np.testing.assert_array_equal(np.asarray(seed_s[2, :3]), [9, 8, 7])
    assert int(cnt[2]) == 3
    # bucket cleared for ring reuse
    assert int(cal.cnt[2, 1]) == 0


def test_insert_same_ts_orders_by_seed():
    cal = make_calendar(n_local=1, n_buckets=2, cap=8)
    li, ep, ts, seed, pay, valid = _flat_events(
        [0, 0, 0], [0, 0, 0], [1.0, 1.0, 1.0], [30, 10, 20])
    cal, _ = insert(cal, li, ep, ts, seed, pay, valid)
    _, ts_s, seed_s, _, cnt = extract_sorted(cal, jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(seed_s[0, :3]), [10, 20, 30])


def test_bucket_overflow_is_counted_not_silent():
    cal = make_calendar(n_local=1, n_buckets=2, cap=2)
    li, ep, ts, seed, pay, valid = _flat_events(
        [0, 0, 0, 0], [0, 0, 0, 0], [1.0, 2.0, 3.0, 4.0], [1, 2, 3, 4])
    cal, ovf = insert(cal, li, ep, ts, seed, pay, valid)
    assert int(ovf) == 2
    assert int(cal.cnt[0, 0]) == 2


def test_ring_reuse_across_epochs():
    N, cap = 4, 4
    cal = make_calendar(n_local=1, n_buckets=N, cap=cap)
    # epoch 0 and epoch N land in the same bucket — but only after 0 is drained.
    li, ep, ts, seed, pay, valid = _flat_events([0], [0], [0.5], [1])
    cal, _ = insert(cal, li, ep, ts, seed, pay, valid)
    cal, ts_s, _, _, cnt = extract_sorted(cal, jnp.int32(0))
    assert int(cnt[0]) == 1
    li, ep, ts, seed, pay, valid = _flat_events([0], [N], [float(N) + 0.5], [2])
    cal, ovf = insert(cal, li, ep, ts, seed, pay, valid)
    assert int(ovf) == 0
    cal, ts_s, seed_s, _, cnt = extract_sorted(cal, jnp.int32(N))
    assert int(cnt[0]) == 1 and float(ts_s[0, 0]) == N + 0.5


def test_invalid_events_are_ignored():
    cal = make_calendar(n_local=2, n_buckets=2, cap=4)
    li = jnp.asarray([0, 1], jnp.int32)
    ep = jnp.asarray([0, 0], jnp.int32)
    ts = jnp.asarray([1.0, 1.0], jnp.float32)
    seed = jnp.asarray([1, 2], jnp.uint32)
    pay = jnp.zeros((2,), jnp.float32)
    valid = jnp.asarray([True, False])
    cal, ovf = insert(cal, li, ep, ts, seed, pay, valid)
    assert int(cal.cnt.sum()) == 1 and int(ovf) == 0


def test_fallback_put_compacts_and_counts_overflow():
    fb = make_fallback(4)
    new = empty_batch(6)
    new = EventBatch(
        dst=jnp.arange(6, dtype=jnp.int32),
        ts=jnp.full((6,), 2.0, jnp.float32),
        seed=jnp.arange(6, dtype=jnp.uint32),
        payload=jnp.zeros((6,), jnp.float32),
        valid=jnp.asarray([True, False, True, True, True, True]),
    )
    fb2, ovf = fallback_put(fb, new)
    assert int(jnp.sum(fb2.events.valid)) == 4
    assert int(ovf) == 1  # 5 valid events, capacity 4
    # stable order: dst 0,2,3,4 kept
    np.testing.assert_array_equal(np.asarray(fb2.events.dst[:4]), [0, 2, 3, 4])


def _extract_by_argsorts(cal, epoch):
    """Reference: the bucket ordered by two stable argsorts composed (seed,
    then ts) and applied with gathers; the bucket cleared as extract does."""
    n_local, n_buckets, cap = cal.ts.shape
    b = int(epoch) % n_buckets
    ts, seed, pay = cal.ts[:, b], cal.seed[:, b], cal.payload[:, b]
    cnt_b = cal.cnt[:, b]
    live = jnp.arange(cap, dtype=jnp.int32)[None, :] < cnt_b[:, None]
    ts = jnp.where(live, ts, jnp.inf)
    p1 = jnp.argsort(seed, axis=1, stable=True)
    p2 = jnp.argsort(jnp.take_along_axis(ts, p1, axis=1), axis=1, stable=True)
    order = jnp.take_along_axis(p1, p2, axis=1)
    take = lambda a: jnp.take_along_axis(a, order, axis=1)
    cleared = cal._replace(ts=cal.ts.at[:, b].set(jnp.inf),
                           cnt=cal.cnt.at[:, b].set(0))
    return cleared, take(ts), take(seed), take(pay), cnt_b


def _bucket_case(case, rng):
    """A calendar whose every slot holds something (stale past ``cnt``), and
    the epochs to drain from it in turn."""
    n_local, n_buckets, cap = (0 if case == "zero_rows" else 16), 4, 32
    shape = (n_local, n_buckets, cap)
    ts = rng.uniform(0.0, 8.0, shape).astype(np.float32)
    seed = rng.integers(0, 2**32, shape, dtype=np.uint32)
    pay = rng.standard_normal(shape).astype(np.float32)
    cnt = rng.integers(0, cap + 1, (n_local, n_buckets)).astype(np.int32)
    epochs = [5]
    if case == "ties":
        ts = rng.choice(np.float32([1.0, 1.5, 2.0]), shape)
        seed = rng.integers(0, 3, shape, dtype=np.uint32)
    elif case == "stale_dead":
        cnt = rng.integers(0, cap // 4, (n_local, n_buckets)).astype(np.int32)
        seed = rng.integers(0, 4, shape, dtype=np.uint32)
    elif case == "signed_zero":
        ts = rng.choice(np.float32([-0.0, 0.0, 1.0]), shape)
        seed = rng.integers(0, 2, shape, dtype=np.uint32)
    elif case == "full_and_empty":
        cnt = np.where(np.arange(n_local)[:, None] % 2 == 0, cap, 0)
        cnt = np.broadcast_to(cnt, (n_local, n_buckets)).astype(np.int32)
    elif case == "ring_wrap":
        epochs = [n_buckets - 1, n_buckets, 2 * n_buckets - 1]
    cal = Calendar(jnp.asarray(ts), jnp.asarray(seed), jnp.asarray(pay),
                   jnp.asarray(cnt))
    return cal, epochs


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint32) if a.dtype == np.float32 else a


@pytest.mark.parametrize("case", ["ties", "stale_dead", "signed_zero",
                                  "full_and_empty", "zero_rows", "ring_wrap"])
def test_extract_sorted_matches_two_argsorts_bit_for_bit(case):
    # One stable sort keyed (ts, seed) gives, in every slot (dead ones
    # included), the bits of two stable argsorts composed and gathered.
    cal, epochs = _bucket_case(case, np.random.default_rng(17))
    ref = cal
    for epoch in epochs:
        e = jnp.int32(epoch)
        cal, *got = jax.jit(extract_sorted)(cal, e)
        ref, *want = _extract_by_argsorts(ref, e)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(_bits(g), _bits(w))
        for g, w in zip(cal, ref):
            np.testing.assert_array_equal(_bits(g), _bits(w))
