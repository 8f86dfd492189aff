"""The pipeline decomposition contract (repro/core/pipeline):

* stage registries carry the built-ins; unknown stage names fail at
  EngineConfig *construction* time, not deep inside a trace;
* a user-registered Scheduler is selectable by name and round-trips the
  whole engine (identical results to the built-in it wraps);
* the a2a capacity validation fails fast instead of silently spilling every
  event to fallback (route_cap // D == 0 regression);
* the width-packer (batch_impl='packed'): deterministic edge cases
  (all-empty / single-row / full-width slices, zero local rows) plus the
  engine-level "same bits, different schedule" equivalence vs the dense
  rounds loop — the hypothesis round-trip properties live in
  test_property.py;
* event-batch helpers (concat_batches / take_first) preserve the
  valid-event multiset — the algebra `route` and `deliver` stages lean on
  (property-style over seeded random batches, no hypothesis dependency) —
  and take_first selects what a plain numpy loop selects.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EngineConfig, ParsirEngine
from repro.core.events import (EventBatch, concat_batches, empty_batch,
                               take_first)
from repro.core.pipeline import (ROUTERS, SCHEDULERS, STEAL_POLICIES,
                                 Scheduler, pack_slice, register_scheduler,
                                 resolve_scheduler, unpack_slice)
from repro.core.pipeline.schedulers import (process_batch_packed,
                                            process_batch_rounds)
from repro.testing.fixtures import random_sorted_slice
from repro.workloads.registry import get_workload


# ---------------------------------------------------------------------------
# registries + construction-time validation
# ---------------------------------------------------------------------------

def test_builtin_stages_registered():
    assert {"batch", "batch-packed", "batch-model", "ltf"} <= set(SCHEDULERS)
    assert {"allgather", "a2a"} <= set(ROUTERS)
    assert {"none", "loan"} <= set(STEAL_POLICIES)


def test_stage_name_truth_sets_track_registries():
    # repro.core.pipeline.names is the jax-free single source the CLI driver
    # and the stdlib-only docs checker consume — every declared name must
    # resolve in the live registries (registries may additionally hold
    # user-registered stages, so these are subset checks), and the internal
    # batch-family scheduler names must stay out of the selectable set.
    from repro.core.pipeline import base, names
    assert base.BATCH_IMPLS is names.BATCH_IMPLS
    assert set(names.ROUTES) <= set(ROUTERS)
    assert {"allgather", "a2a"} <= set(names.ROUTES)
    assert set(names.BATCH_IMPLS) == {"rounds", "packed", "model"}
    assert set(names.BATCH_IMPLS.values()) <= set(SCHEDULERS)
    internal = set(names.BATCH_IMPLS.values()) - {"batch"}
    assert not internal & set(names.SELECTABLE_SCHEDULERS)
    for s in names.SELECTABLE_SCHEDULERS:
        assert s in SCHEDULERS, s
    for p in names.PLACEMENTS:  # every declared placement is constructible
        kw = dict(rebalance_every=4) if p == "adaptive" else {}
        EngineConfig(lookahead=0.5, placement=p, **kw)


@pytest.mark.parametrize("bad_kw", [dict(route="bogus"),
                                    dict(scheduler="bogus"),
                                    dict(batch_impl="bogus"),
                                    dict(route_cap=0),
                                    dict(n_buckets=0),
                                    dict(pack_tile=0),
                                    dict(steal=True, steal_cap=0),
                                    dict(steal=True, claim_cap=0),
                                    dict(epoch_len=0.0),
                                    dict(epoch_len=-1.0)])
def test_unknown_or_degenerate_config_fails_at_construction(bad_kw):
    with pytest.raises(ValueError):
        EngineConfig(lookahead=0.5, **bad_kw)


@pytest.mark.parametrize("la", [0.0, -2.0])
def test_nonpositive_lookahead_fails_at_construction(la):
    with pytest.raises(ValueError, match="lookahead"):
        EngineConfig(lookahead=la)


def test_a2a_route_cap_validation_fails_fast():
    # pair_cap = route_cap // D == 0 used to silently drop every event into
    # overflow; now the engine-side validation refuses the config outright.
    cfg = EngineConfig(lookahead=0.5, route="a2a", route_cap=2)
    with pytest.raises(ValueError, match="route_cap"):
        cfg.validate(n_devices=4)
    # divisible-and-large-enough passes
    EngineConfig(lookahead=0.5, route="a2a", route_cap=8).validate(4)


def test_resolve_scheduler_batch_impl_split():
    assert resolve_scheduler(EngineConfig(lookahead=0.5)).name == "batch"
    assert resolve_scheduler(
        EngineConfig(lookahead=0.5, batch_impl="model")).name == "batch-model"
    assert resolve_scheduler(
        EngineConfig(lookahead=0.5,
                     batch_impl="packed")).name == "batch-packed"
    assert resolve_scheduler(
        EngineConfig(lookahead=0.5, scheduler="ltf")).name == "ltf"


def test_model_kernel_scheduler_requires_process_batch():
    model = get_workload("cluster", n_nodes=8, n_rings=2)  # no process_batch
    cfg = EngineConfig(lookahead=0.5, batch_impl="model", n_buckets=8,
                       bucket_cap=32, route_cap=128, fallback_cap=128)
    with pytest.raises(ValueError, match="process_batch"):
        ParsirEngine(model, cfg)


def test_custom_registered_scheduler_runs_end_to_end():
    # registering a Scheduler class and selecting it by EngineConfig name is
    # the whole extension story — prove it round-trips the engine with
    # results identical to the built-in it delegates to.
    if "test-echo" not in SCHEDULERS:
        @register_scheduler("test-echo")
        class EchoScheduler(Scheduler):
            def process(self, model, cfg, obj, ts_s, seed_s, pay_s, cnt_b):
                return process_batch_rounds(model, obj, ts_s, seed_s, pay_s,
                                            cnt_b, cfg.lookahead)

    model = get_workload("phold", n_objects=16, initial_events=4,
                         state_nodes=64, realloc_fraction=0.02,
                         lookahead=0.5, dist="dyadic")
    kw = dict(lookahead=0.5, n_buckets=8, bucket_cap=64, route_cap=512,
              fallback_cap=512)
    eng_a = ParsirEngine(model, EngineConfig(**kw))
    eng_b = ParsirEngine(model, EngineConfig(scheduler="test-echo", **kw))
    tot_a = eng_a.totals(eng_a.run(eng_a.init(), 12))
    tot_b = eng_b.totals(eng_b.run(eng_b.init(), 12))
    assert tot_a == tot_b
    assert tot_a["processed"] > 0


def test_inconsistent_stage_combinations_fail_at_construction():
    # loan stealing processes through the rounds-family schedulers; pairing
    # it with another scheduler/impl must refuse (device-independently, at
    # config construction) rather than silently ignore the setting.
    for bad in (dict(steal=True, scheduler="ltf"),
                dict(steal=True, batch_impl="model")):
        with pytest.raises(ValueError, match="steal"):
            EngineConfig(lookahead=0.5, **bad)
    # ...but the width-packed impl ingests loan-augmented rows fine.
    EngineConfig(lookahead=0.5, steal=True, batch_impl="packed")
    # a non-rounds batch_impl under a non-batch scheduler would silently
    # never take effect.
    for impl in ("model", "packed"):
        with pytest.raises(ValueError, match="batch_impl"):
            EngineConfig(lookahead=0.5, scheduler="ltf", batch_impl=impl)
    # the internal registry names are not directly selectable.
    for internal in ("batch-model", "batch-packed"):
        with pytest.raises(ValueError, match="internal"):
            EngineConfig(lookahead=0.5, scheduler=internal)


def test_duplicate_registration_rejected():
    with pytest.raises(ValueError, match="already registered"):
        @register_scheduler("batch")
        class Clash(Scheduler):  # pragma: no cover - never instantiated
            def process(self, *a):
                ...


# ---------------------------------------------------------------------------
# the width-packer (batch_impl='packed'): edge cases + engine equivalence
# ---------------------------------------------------------------------------

def _slice_of(cnts, cap, seed=0):
    ts, seed_a, pay, cnt, _ = random_sorted_slice(cnts, seed, cap)
    return (jnp.asarray(ts), jnp.asarray(seed_a), jnp.asarray(pay),
            jnp.asarray(cnt))


@pytest.mark.parametrize("cnts,cap,tile", [
    ([0, 0, 0, 0], 6, 2),          # all-empty: zero tiles, nothing live
    ([5], 5, 3),                   # single row, full depth
    ([4] * 6, 4, 4),               # full width: every slot occupied
    ([0, 7, 0, 1, 3], 7, 2),       # ragged
])
def test_pack_unpack_edge_cases(cnts, cap, tile):
    ts, seed, pay, cnt = _slice_of(cnts, cap)
    p = pack_slice(ts, seed, pay, cnt, tile)
    total = int(np.sum(cnts))
    assert int(np.asarray(p.valid).sum()) == total
    if total == 0:
        assert int(p.n_tiles) == 0
    # no tile mixes rounds (the conflict-freedom invariant).
    v = np.asarray(p.valid)
    k = np.nonzero(v)[0]
    rr = np.asarray(p.rnd)[v]
    for t in np.unique(k // p.tile):
        assert len(np.unique(rr[k // p.tile == t])) == 1
    uts, useed, upay, ucnt = unpack_slice(p, len(cnts), cap)
    np.testing.assert_array_equal(np.asarray(ucnt), np.asarray(cnt))
    np.testing.assert_array_equal(np.asarray(uts), np.asarray(ts))
    live = np.arange(cap)[None, :] < np.asarray(cnt)[:, None]
    np.testing.assert_array_equal(np.asarray(useed)[live],
                                  np.asarray(seed)[live])
    np.testing.assert_array_equal(np.asarray(upay)[live],
                                  np.asarray(pay)[live])


def _tiny_phold():
    return get_workload("phold", n_objects=16, initial_events=4,
                        state_nodes=64, realloc_fraction=0.02,
                        lookahead=0.5, dist="dyadic")


@pytest.mark.parametrize("n_rows", [0, 3])
@pytest.mark.parametrize("impl", ["rounds", "packed"])
def test_schedulers_handle_empty_and_tiny_slices(n_rows, impl):
    # n_rows == 0 is the previously-untested local-slice edge: a device that
    # currently owns no objects must process cleanly and emit nothing.
    model = _tiny_phold()
    obj = model.init_object_state(np.arange(n_rows))
    cap = 4
    ts = jnp.full((n_rows, cap), jnp.inf, jnp.float32)
    seed = jnp.zeros((n_rows, cap), jnp.uint32)
    pay = jnp.zeros((n_rows, cap), jnp.float32)
    cnt = jnp.zeros((n_rows,), jnp.int32)
    if impl == "rounds":
        obj2, flat, lv, rounds = process_batch_rounds(model, obj, ts, seed,
                                                      pay, cnt, 0.5)
    else:
        obj2, flat, lv, rounds = process_batch_packed(model, obj, ts, seed,
                                                      pay, cnt, 0.5, tile=2)
    assert int(lv) == 0
    assert int(rounds) == 0
    assert int(flat.valid.sum()) == 0
    for a, b in zip(jax.tree.leaves(obj), jax.tree.leaves(obj2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("pack_tile", [1, 4, 64])
def test_packed_engine_bit_exact_vs_batch(pack_tile):
    # "same bits, different schedule": any tile width must reproduce the
    # dense rounds loop exactly — totals and final object state.
    model = _tiny_phold()
    kw = dict(lookahead=0.5, n_buckets=8, bucket_cap=64, route_cap=512,
              fallback_cap=512)
    a = ParsirEngine(model, EngineConfig(**kw))
    b = ParsirEngine(model, EngineConfig(batch_impl="packed",
                                         pack_tile=pack_tile, **kw))
    sa, sb = a.run(a.init(), 16), b.run(b.init(), 16)
    assert a.totals(sa) == b.totals(sb)
    assert a.totals(sa)["processed"] > 0
    oa, ob = a.global_object_state(sa), b.global_object_state(sb)
    for k in oa:
        np.testing.assert_array_equal(oa[k], ob[k], err_msg=k)


# ---------------------------------------------------------------------------
# event-batch algebra: valid-multiset preservation (property-style)
# ---------------------------------------------------------------------------

def _rand_batch(rng, n):
    return EventBatch(
        dst=jnp.asarray(rng.integers(0, 50, n), jnp.int32),
        ts=jnp.asarray(rng.integers(0, 1024, n) / 1024.0, jnp.float32),
        seed=jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint32)),
        payload=jnp.asarray(rng.integers(0, 7, n), jnp.float32),
        valid=jnp.asarray(rng.random(n) < 0.6),
    )


def _multiset(b: EventBatch):
    v = np.asarray(b.valid)
    return sorted(zip(np.asarray(b.dst)[v].tolist(),
                      np.asarray(b.ts)[v].tolist(),
                      np.asarray(b.seed)[v].tolist(),
                      np.asarray(b.payload)[v].tolist()))


@pytest.mark.parametrize("trial", range(8))
def test_event_batch_algebra_preserves_valid_multiset(trial):
    # deterministic always-running counterpart of the hypothesis properties
    # in test_property.py (which skip when hypothesis isn't installed).
    rng = np.random.default_rng(100 + trial)
    a = _rand_batch(rng, int(rng.integers(1, 48)))
    b = _rand_batch(rng, int(rng.integers(1, 48)))

    # concat is multiset union
    cat = concat_batches(a, b)
    assert _multiset(cat) == sorted(_multiset(a) + _multiset(b))

    # take_first keeps exactly the selected sub-multiset, front-compacted
    # in stable order (the engine always selects a subset: send ⊆ valid).
    mask = jnp.asarray(rng.random(cat.capacity) < 0.5) & cat.valid
    sel, spill = take_first(cat, mask, cat.capacity)
    assert _multiset(sel) == _multiset(cat._replace(valid=mask))
    v = np.asarray(sel.valid)
    k = int(v.sum())
    assert int(spill) == 0 and np.all(v[:k]) and not np.any(v[k:])
    np.testing.assert_array_equal(np.asarray(sel.dst)[:k],
                                  np.asarray(cat.dst)[np.asarray(mask)])

    # a smaller cap partitions the multiset: kept + countable drops —
    # exactly how the route/fallback stages account overflow.
    cap = int(rng.integers(1, cat.capacity + 1))
    kept, spill = take_first(cat, cat.valid, cap)
    total = len(_multiset(cat))
    assert int(spill) == max(total - cap, 0)
    assert len(_multiset(kept)) + int(spill) == total
    if cap >= total:
        assert _multiset(kept) == _multiset(cat)


def _take_first_np(b: EventBatch, mask, cap):
    """Plain numpy reference: the first ``cap`` selected events in order,
    empty fill after them, and how many were selected."""
    idx = np.flatnonzero(np.asarray(mask))[:cap]
    out = [np.array(f) for f in empty_batch(cap)]
    for field, x in zip(out, b):
        field[:len(idx)] = np.asarray(x)[idx]
    return EventBatch(*out), int(np.asarray(mask).sum())


_CAP = 16


def _take_first_mask(case, rng, size):
    if case == "empty":
        return np.zeros(size, bool)
    if case == "all":
        return np.ones(size, bool)
    if case in ("cap", "cap+1"):
        m = np.zeros(size, bool)
        m[rng.choice(size, _CAP + (case == "cap+1"), replace=False)] = True
        return m
    if case == "last":
        return np.arange(size) == size - 1
    return rng.random(size) < 0.02            # about 2% density


@pytest.mark.parametrize("case", ["empty", "all", "cap", "cap+1", "last",
                                  "sparse", "vmapped"])
def test_take_first_matches_numpy_reference(case):
    # the route buffer and the fallback keep select this way: the first cap
    # selected events in batch order, empty fill after them, and the count
    # of selected events that did not fit.
    rng = np.random.default_rng(7)
    size, rows = 2048, 3 if case == "vmapped" else 1
    batches = [_rand_batch(rng, size) for _ in range(rows)]
    masks = [jnp.asarray(_take_first_mask(
        "sparse" if case == "vmapped" else case, rng, size)) for _ in batches]
    # every selected event is valid; about half the others are valid too
    batches = [b._replace(valid=m | jnp.asarray(rng.random(size) < 0.5))
               for b, m in zip(batches, masks)]
    if case == "vmapped":
        stack = EventBatch(*(jnp.stack(f) for f in zip(*batches)))
        out, spill = jax.vmap(lambda b, m: take_first(b, m, _CAP))(
            stack, jnp.stack(masks))
        got = [(EventBatch(*(f[r] for f in out)), spill[r])
               for r in range(rows)]
    else:
        got = [take_first(batches[0], masks[0], _CAP)]
    for (out, spill), b, m in zip(got, batches, masks):
        want, want_n = _take_first_np(b, m, _CAP)
        assert int(spill) == max(want_n - _CAP, 0)
        for x, y in zip(out, want):
            assert x.shape == (_CAP,) and x.dtype == y.dtype
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
