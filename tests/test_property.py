"""Hypothesis property tests for the system invariants:

* calendar insert/extract conserves events and never reorders per object;
* the speculation shadow window (take_buckets / put_buckets) is a bit-exact
  restore: take ∘ damage ∘ put is the identity on the window's buckets —
  ring wrap-around included — and put never touches buckets outside it;
* the width-packer (batch_impl='packed') is an exact permutation: pack →
  unpack round-trips the (ts, seed, payload, cnt) slice bit-for-bit, the
  work list is stable by (round, row), and no vmap tile mixes rounds;
* the event-batch algebra (concat_batches / take_first) the
  route/deliver stages lean on preserves the valid-event multiset;
* the arena stack allocator keeps the free-region invariant and LIFO reuse;
* placement is a partition (every object owned by exactly one device);
* the loan planner never over-assigns receivers and is donor/receiver disjoint.
"""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="hypothesis not installed — property tests skipped, not collected")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import events as ev
from repro.core.calendar import (extract_sorted, insert, make_calendar,
                                 put_buckets, take_buckets)
from repro.core.pipeline.packing import pack_slice, unpack_slice
from repro.core.placement import equal_placement, weighted_placement
from repro.testing.fixtures import random_sorted_slice
from repro.core.stealing import plan_loans
from repro.phold import arena as ar

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")


@given(st.lists(st.tuples(st.integers(0, 7),          # local obj
                          st.integers(0, 3),          # epoch
                          st.floats(0.0, 3.75, width=32),
                          st.integers(0, 2**32 - 1)),
                min_size=1, max_size=40))
def test_calendar_conserves_and_orders(events):
    cal = make_calendar(n_local=8, n_buckets=4, cap=64)
    li = jnp.asarray([e[0] for e in events], jnp.int32)
    ep = jnp.asarray([e[1] for e in events], jnp.int32)
    ts = jnp.asarray([e[1] + (e[2] % 1.0) for e in events], jnp.float32)
    seed = jnp.asarray([e[3] for e in events], jnp.uint32)
    pay = jnp.zeros((len(events),), jnp.float32)
    valid = jnp.ones((len(events),), bool)
    cal, ovf = insert(cal, li, ep, ts, seed, pay, valid)
    assert int(ovf) == 0
    assert int(cal.cnt.sum()) == len(events)

    seen = 0
    for epoch in range(4):
        cal, ts_s, seed_s, _, cnt = extract_sorted(cal, jnp.int32(epoch))
        cnt = np.asarray(cnt)
        ts_np = np.asarray(ts_s)
        for o in range(8):
            k = int(cnt[o])
            if k:
                row = ts_np[o, :k]
                assert np.all(np.diff(row) >= 0), "per-object ts order violated"
            seen += k
    assert seen == len(events)


# --------------------------------------------------------------------------
# speculation shadow windows: take_buckets / put_buckets (speculate.py's
# rollback restore) — snapshot semantics on the circular bucket ring
# --------------------------------------------------------------------------

_cal_events = st.lists(st.tuples(st.integers(0, 7),           # local obj
                                 st.integers(0, 3),           # epoch
                                 st.floats(0.0, 3.75, width=32),
                                 st.integers(0, 2**32 - 1)),  # seed
                       min_size=0, max_size=40)


def _populated_cal(events):
    cal = make_calendar(n_local=8, n_buckets=4, cap=64)
    if not events:
        return cal
    cal, ovf = insert(
        cal,
        jnp.asarray([e[0] for e in events], jnp.int32),
        jnp.asarray([e[1] for e in events], jnp.int32),
        jnp.asarray([e[1] + (e[2] % 1.0) for e in events], jnp.float32),
        jnp.asarray([e[3] for e in events], jnp.uint32),
        jnp.asarray([e[2] for e in events], jnp.float32),
        jnp.ones((len(events),), bool))
    assert int(ovf) == 0
    return cal


@given(_cal_events, st.integers(0, 11), st.integers(1, 3), _cal_events)
def test_take_put_buckets_restores_window_bit_exact(events, e0, n, extra):
    # take ∘ damage ∘ put == identity: speculative insertions into the
    # window vanish, the speculative extraction of the safe epoch
    # reappears, every slot bit-for-bit.  first_epoch ranges well past the
    # ring size so windows regularly straddle the wrap edge.
    cal = _populated_cal(events)
    shadow = take_buckets(cal, jnp.int32(e0), n)
    # the snapshot is in WINDOW order: axis w holds epoch e0 + w, wherever
    # that epoch lives on the ring.
    cnt = np.asarray(cal.cnt)
    for w in range(n):
        np.testing.assert_array_equal(np.asarray(shadow.cnt)[:, w],
                                      cnt[:, (e0 + w) % 4])
    cal2 = cal
    if extra:
        # damage: insert events at window epochs only (a capacity overflow
        # here is fine — dropped-on-overflow is just less damage to undo)
        cal2, _ = insert(
            cal2,
            jnp.asarray([e[0] for e in extra], jnp.int32),
            jnp.asarray([e0 + e[1] % n for e in extra], jnp.int32),
            jnp.asarray([e0 + (e[2] % 1.0) for e in extra], jnp.float32),
            jnp.asarray([e[3] for e in extra], jnp.uint32),
            jnp.zeros((len(extra),), jnp.float32),
            jnp.ones((len(extra),), bool))
    # ...and a speculative extraction, which clears the first window bucket
    cal2, *_ = extract_sorted(cal2, jnp.int32(e0))
    cal3 = put_buckets(cal2, jnp.int32(e0), shadow)
    for la, lb in zip(cal3, cal):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


@given(_cal_events, st.integers(0, 11), st.integers(1, 2))
def test_put_buckets_leaves_untouched_buckets_alone(events, e0, n):
    # disjointness: a restore of window [e0, e0+n) must not disturb buckets
    # outside it — an insertion at epoch e0+n (a distinct ring bucket for
    # n < n_buckets) survives the rollback untouched.
    cal = _populated_cal(events)
    shadow = take_buckets(cal, jnp.int32(e0), n)
    out_ep = e0 + n
    cal2, ovf = insert(cal, jnp.asarray([0], jnp.int32),
                       jnp.asarray([out_ep], jnp.int32),
                       jnp.asarray([float(out_ep)], jnp.float32),
                       jnp.asarray([7], jnp.uint32),
                       jnp.zeros((1,), jnp.float32),
                       jnp.ones((1,), bool))
    assert int(ovf) == 0
    cal3 = put_buckets(cal2, jnp.int32(e0), shadow)
    ob = out_ep % 4
    assert int(cal3.cnt[0, ob]) == int(cal.cnt[0, ob]) + 1
    for w in range(n):
        b = (e0 + w) % 4
        for leaf3, leaf0 in zip(cal3, cal):
            np.testing.assert_array_equal(np.asarray(leaf3)[:, b],
                                          np.asarray(leaf0)[:, b])


def test_take_buckets_wraps_the_ring():
    # the deterministic wrap case: window [7, 8] on a 4-ring is buckets
    # [3, 0] — the snapshot must present them in window order regardless.
    cal = make_calendar(n_local=2, n_buckets=4, cap=8)
    cal, ovf = insert(cal, jnp.asarray([0, 1], jnp.int32),
                      jnp.asarray([7, 8], jnp.int32),
                      jnp.asarray([7.5, 8.5], jnp.float32),
                      jnp.asarray([1, 2], jnp.uint32),
                      jnp.zeros((2,), jnp.float32),
                      jnp.ones((2,), bool))
    assert int(ovf) == 0
    shadow = take_buckets(cal, jnp.int32(7), 2)
    assert int(shadow.cnt[0, 0]) == 1          # epoch 7 → window axis 0
    assert int(shadow.cnt[1, 1]) == 1          # epoch 8 → window axis 1
    assert float(shadow.ts[0, 0, 0]) == 7.5
    assert float(shadow.ts[1, 1, 0]) == 8.5


# --------------------------------------------------------------------------
# the width-packer: pack → unpack is an exact, order-preserving permutation
# --------------------------------------------------------------------------

_pack_case = st.tuples(
    st.lists(st.integers(0, 6), min_size=0, max_size=10),  # cnt per row
    st.integers(1, 12),                                    # tile width
    st.integers(0, 2**31 - 1),                             # value seed
)


def _pack_inputs(cnts, vseed, cap=6):
    ts, seed, pay, cnt, live = random_sorted_slice(cnts, vseed, cap)
    return ts, seed, pay, cnt, live, cap


@given(_pack_case)
def test_pack_unpack_roundtrips_slice_exactly(case):
    cnts, tile, vseed = case
    ts, seed, pay, cnt, live, cap = _pack_inputs(cnts, vseed)
    p = pack_slice(jnp.asarray(ts), jnp.asarray(seed), jnp.asarray(pay),
                   jnp.asarray(cnt), tile)
    uts, useed, upay, ucnt = unpack_slice(p, len(cnts), cap)
    np.testing.assert_array_equal(np.asarray(ucnt), cnt)
    # dead slots come back as the canonical layout (+inf ts), live slots
    # bit-for-bit — the whole slice, not just a multiset.
    np.testing.assert_array_equal(np.asarray(uts), ts)
    np.testing.assert_array_equal(np.asarray(useed)[live], seed[live])
    np.testing.assert_array_equal(np.asarray(upay)[live], pay[live])


@given(_pack_case)
def test_pack_preserves_multiset_and_per_object_order(case):
    cnts, tile, vseed = case
    ts, seed, pay, cnt, live, cap = _pack_inputs(cnts, vseed)
    p = pack_slice(jnp.asarray(ts), jnp.asarray(seed), jnp.asarray(pay),
                   jnp.asarray(cnt), tile)
    v = np.asarray(p.valid)
    assert int(v.sum()) == int(cnt.sum())
    rows, rnds = np.asarray(p.row)[v], np.asarray(p.rnd)[v]
    seeds = np.asarray(p.seed)[v]
    # multiset of (row, round, seed) is exactly the live slice slots.
    got = sorted(zip(rows.tolist(), rnds.tolist(), seeds.tolist()))
    r, c = np.nonzero(live)
    want = sorted(zip(r.tolist(), c.tolist(), seed[live].tolist()))
    assert got == want
    # work list is stable by (round, row) ⇒ strictly increasing key ⇒ an
    # object's rounds appear in order (intra-object causality).
    key = rnds.astype(np.int64) * (len(cnts) + 1) + rows
    assert np.all(np.diff(key) > 0)


@given(_pack_case)
def test_pack_tiles_never_mix_rounds(case):
    # the conflict-freedom invariant the scheduler's per-tile state
    # gather/scatter relies on: one round (⇒ distinct objects) per tile.
    cnts, tile, vseed = case
    ts, seed, pay, cnt, live, cap = _pack_inputs(cnts, vseed)
    p = pack_slice(jnp.asarray(ts), jnp.asarray(seed), jnp.asarray(pay),
                   jnp.asarray(cnt), tile)
    v = np.asarray(p.valid)
    k = np.nonzero(v)[0]
    assert k.size == 0 or k.max() < int(p.n_tiles) * p.tile
    rnds, rows = np.asarray(p.rnd)[v], np.asarray(p.row)[v]
    for t in np.unique(k // p.tile):
        in_tile = k // p.tile == t
        assert len(np.unique(rnds[in_tile])) == 1
        assert len(np.unique(rows[in_tile])) == in_tile.sum()


_batch_rows = st.lists(
    st.tuples(st.integers(0, 40),            # dst
              st.integers(0, 1023),          # ts grid point
              st.integers(0, 2**32 - 1),     # seed
              st.booleans(),                 # valid
              st.booleans()),                # mask
    min_size=1, max_size=48)


def _mk_batch(rows):
    return ev.EventBatch(
        dst=jnp.asarray([r[0] for r in rows], jnp.int32),
        ts=jnp.asarray([r[1] / 1024.0 for r in rows], jnp.float32),
        seed=jnp.asarray([r[2] for r in rows], jnp.uint32),
        payload=jnp.zeros((len(rows),), jnp.float32),
        valid=jnp.asarray([r[3] for r in rows]),
    )


def _valid_multiset(b):
    v = np.asarray(b.valid)
    return sorted(zip(np.asarray(b.dst)[v].tolist(),
                      np.asarray(b.ts)[v].tolist(),
                      np.asarray(b.seed)[v].tolist()))


@given(_batch_rows)
def test_compact_mask_preserves_valid_multiset(rows):
    b = _mk_batch(rows)
    mask = jnp.asarray([r[4] for r in rows]) & b.valid
    out, spill = ev.take_first(b, mask, b.capacity)
    assert _valid_multiset(out) == _valid_multiset(b._replace(valid=mask))
    v = np.asarray(out.valid)
    k = int(v.sum())
    assert int(spill) == 0 and np.all(v[:k]) and not np.any(v[k:])


@given(_batch_rows, _batch_rows)
def test_concat_batches_preserves_valid_multiset(rows_a, rows_b):
    a, b = _mk_batch(rows_a), _mk_batch(rows_b)
    assert _valid_multiset(ev.concat_batches(a, b)) == \
        sorted(_valid_multiset(a) + _valid_multiset(b))


@given(_batch_rows, st.integers(1, 64))
def test_truncate_partitions_valid_multiset(rows, cap):
    b = _mk_batch(rows)
    kept, spill = ev.take_first(b, b.valid, cap)
    n_spill = int(spill)
    assert n_spill == max(int(np.asarray(b.valid).sum()) - cap, 0)
    assert len(_valid_multiset(kept)) + n_spill == len(_valid_multiset(b))
    if n_spill == 0:
        assert _valid_multiset(kept) == _valid_multiset(b)


@given(st.lists(st.integers(0, 63), min_size=1, max_size=16, unique=True))
def test_arena_free_then_alloc_is_lifo(idxs):
    a = ar.arena_init(64)
    idx = jnp.asarray(idxs, jnp.int32)
    a = ar.free_k(a, idx)
    assert int(a.top) == 64 - len(idxs)
    a, got = ar.alloc_k(a, len(idxs))
    assert int(a.top) == 64
    # LIFO: allocation returns exactly the freed set
    assert sorted(np.asarray(got).tolist()) == sorted(idxs)
    # numpy mirror agrees element-for-element
    addr_np, top_np = ar.arena_init_np(64)
    addr_np, top_np = ar.free_k_np(addr_np, top_np, np.asarray(idxs))
    addr_np, top_np, got_np = ar.alloc_k_np(addr_np, top_np, len(idxs))
    np.testing.assert_array_equal(np.asarray(got), got_np)


@given(st.integers(1, 512), st.integers(1, 16))
def test_equal_placement_is_partition(n_objects, n_devices):
    p = equal_placement(n_objects, n_devices)
    counts = p.counts()
    assert counts.sum() == n_objects
    assert counts.max() - counts.min() <= 1
    owners = p.owner_np(np.arange(n_objects))
    assert owners.min() >= 0 and owners.max() < n_devices
    for d in range(n_devices):
        lo, hi = p.range_of(d)
        assert np.all(owners[lo:hi] == d)


@given(st.lists(st.one_of(st.just(0.0), st.floats(0.0, 100.0)),
                min_size=4, max_size=64),
       st.integers(1, 8),
       st.integers(0, 64))
def test_weighted_placement_partitions(weights, n_devices, n_zero_prefix):
    # zeros are legal weights — including an all-zero vector and a zero
    # prefix (idle leading objects), which used to collapse every cut onto
    # an edge device.
    weights = [0.0] * min(n_zero_prefix, len(weights) - 1) \
        + weights[min(n_zero_prefix, len(weights) - 1):]
    p = weighted_placement(weights, n_devices)
    assert p.counts().sum() == len(weights)
    assert np.all(p.counts() >= 0)
    # true pad, not papered over
    assert p.n_local_max == int(p.counts().max())
    # every object owned by exactly one device
    owners = p.owner_np(np.arange(len(weights)))
    assert owners.min() >= 0 and owners.max() < n_devices
    if sum(weights) <= 0:
        # degenerate mass → equal split, never everything-on-one-device
        np.testing.assert_array_equal(
            p.boundaries, equal_placement(len(weights), n_devices).boundaries)


@given(st.lists(st.integers(0, 100), min_size=2, max_size=8),
       st.integers(1, 4))
def test_loan_plan_respects_capacity_and_roles(loads, claim_cap):
    D = len(loads)
    steal_cap = 3
    loads_j = jnp.asarray(loads, jnp.int32)
    # every device publishes loans of weight 1..3
    w = jnp.asarray(np.random.default_rng(0).integers(1, 4, (D, steal_cap)),
                    jnp.int32)
    valid = jnp.ones((D, steal_cap), bool)
    plan = plan_loans(loads_j, w, valid, claim_cap)
    assignee = np.asarray(plan.assignee)
    claimed = np.asarray(plan.claimed)
    total = sum(loads)
    target = -(-total // D)
    deficit = np.maximum(0, target - np.asarray(loads))
    for r in range(D):
        got = claimed & (assignee == r)
        assert got.sum() <= claim_cap
        if deficit[r] == 0:
            assert got.sum() == 0, "zero-deficit device received loans"


@given(st.integers(0, 2**32 - 1), st.integers(0, 31))
def test_rng_jax_numpy_bit_identical(seed, k):
    a = int(ev.fold(jnp.uint32(seed), k))
    b = int(ev.fold_np(np.uint32(seed), k))
    assert a == b
    assert float(ev.dyadic10(jnp.uint32(seed))) == float(ev.dyadic10_np(np.uint32(seed)))
    assert float(ev.uniform24(jnp.uint32(seed))) == float(ev.uniform24_np(np.uint32(seed)))


@given(st.integers(0, 2**32 - 1), st.integers(0, 8))
def test_dyadic_scaled_closure(bits, shift):
    # dyadic closure of the scaled draw (wireless hot cells): the value sits
    # exactly on the 1/(1024·2^shift) grid, inside [0, 2^-shift), and the
    # JAX and numpy faces agree bit-for-bit.
    a = float(ev.dyadic_scaled(jnp.uint32(bits), shift))
    b = float(ev.dyadic_scaled_np(np.uint32(bits), shift))
    assert a == b
    grid = 1024 * (1 << shift)
    scaled = a * grid
    assert scaled == int(scaled), "left the dyadic grid"
    assert 0.0 <= a < 2.0 ** -shift
    # power-of-two scaling is exact: the scaled draw is literally the base
    # draw with a shifted exponent.
    assert a == float(ev.dyadic10_np(np.uint32(bits))) * 2.0 ** -shift


@given(st.lists(st.tuples(st.integers(0, 2**32 - 1), st.integers(0, 4)),
                min_size=1, max_size=64))
def test_dyadic_scaled_partial_sums_are_exact(draws):
    # the invariant workload timestamps rely on: partial sums on the
    # 1/(1024·2^shift) grid are exactly representable below 2**(14 - shift)
    # (the window shrinks with the refinement — f32 has 24 mantissa bits and
    # the grid uses 10 + shift of them), so f32 accumulation order can't
    # introduce drift between engine and oracle inside that window.
    import fractions
    total32 = np.float32(0.0)
    exact = fractions.Fraction(0)
    for bits, shift in draws:
        d = ev.dyadic_scaled_np(np.uint32(bits), shift)
        total32 = np.float32(total32 + d)
        exact += fractions.Fraction(int(np.uint32(bits) & np.uint32(1023)),
                                    1024 * (1 << shift))
    max_shift = max(s for _, s in draws)
    assert exact < 2 ** (14 - max_shift), "strategy left the exact window"
    assert float(total32) == float(exact)


@given(st.integers(0, 2**32 - 1), st.integers(0, 6),
       st.sampled_from(["dyadic", "uniform24"]))
def test_draw_scaled_jax_numpy_bit_identical(bits, shift, dist):
    # exponential is deliberately absent: log1p rounds differently in XLA
    # and numpy, which is exactly why bit-exact conformance requires the
    # dyadic (or pure power-of-two uniform24) grids.
    a = float(ev.draw_scaled(jnp.uint32(bits), dist, shift))
    b = float(ev.draw_scaled_np(np.uint32(bits), dist, shift))
    assert a == b
