"""Per-kernel validation: Pallas (interpreted on the CPU) vs pure-jnp ref.py
oracles, swept over shapes and dtypes."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


# -- event_apply ---------------------------------------------------------------

@pytest.mark.parametrize("n,S,C", [(2, 128, 4), (4, 256, 8), (1, 512, 16),
                                   (8, 160, 5)])
def test_event_apply_matches_ref_bitexact(n, S, C):
    LANES = 6
    K, KR = max(1, S // 32), 3
    payload = jnp.asarray(RNG.random((n, LANES, S), np.float32))
    addresses = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (n, S))
    top = jnp.full((n,), S, jnp.int32)
    ts = jnp.asarray(np.sort(RNG.random((n, C)).astype(np.float32), axis=1))
    seed = jnp.asarray(RNG.integers(0, 2**32, (n, C), dtype=np.uint32))
    cnt = jnp.asarray(RNG.integers(0, C + 1, (n,), dtype=np.int32))
    kw = dict(n_objects=64, lookahead=0.5, K=K, KR=KR, dist="dyadic")
    got = ops.event_apply(payload, addresses, top, ts, seed, cnt, **kw)
    want = ref.event_apply_ref(payload, addresses, top, ts, seed, cnt, **kw)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("dist", ["dyadic", "uniform24", "exponential"])
def test_event_apply_distributions(dist):
    n, LANES, S, C = 2, 6, 128, 4
    payload = jnp.asarray(RNG.random((n, LANES, S), np.float32))
    addresses = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (n, S))
    top = jnp.full((n,), S, jnp.int32)
    ts = jnp.asarray(np.sort(RNG.random((n, C)).astype(np.float32), axis=1))
    seed = jnp.asarray(RNG.integers(0, 2**32, (n, C), dtype=np.uint32))
    cnt = jnp.full((n,), C, jnp.int32)
    kw = dict(n_objects=16, lookahead=0.25, K=4, KR=2, dist=dist)
    got = ops.event_apply(payload, addresses, top, ts, seed, cnt, **kw)
    want = ref.event_apply_ref(payload, addresses, top, ts, seed, cnt, **kw)
    np.testing.assert_allclose(np.asarray(got[4]), np.asarray(want[4]),
                               rtol=1e-6)  # emitted ts
    np.testing.assert_array_equal(np.asarray(got[3]), np.asarray(want[3]))


# -- flash attention -----------------------------------------------------------

@pytest.mark.parametrize("shape", [
    (1, 4, 2, 128, 128, 64),   # GQA group 2
    (2, 8, 2, 256, 256, 64),   # GQA group 4
    (1, 2, 2, 64, 64, 32),     # MHA
    (1, 4, 1, 96, 96, 32),     # ragged (padding path)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(shape, dtype):
    B, Hq, Hkv, Tq, Tk, D = shape
    q = jnp.asarray(RNG.standard_normal((B, Hq, Tq, D)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, Hkv, Tk, D)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, Hkv, Tk, D)), dtype)
    got = ops.mha(q, k, v, causal=True, bq=64, bk=64, use_pallas=True)
    want = ops.mha(q, k, v, causal=True, use_pallas=False)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


def test_flash_attention_noncausal():
    q = jnp.asarray(RNG.standard_normal((1, 2, 128, 32)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 2, 128, 32)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 2, 128, 32)), jnp.float32)
    got = ops.mha(q, k, v, causal=False, bq=64, bk=64, use_pallas=True)
    want = ops.mha(q, k, v, causal=False, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# -- SSD ------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(1, 64, 2, 32, 16), (2, 160, 4, 64, 32),
                                   (1, 96, 1, 16, 8)])
def test_ssd_matches_sequential_ref(shape):
    b, T, H, P, N = shape
    x = jnp.asarray(RNG.standard_normal((b, T, H, P)), jnp.float32) * 0.5
    dt = jnp.asarray(RNG.random((b, T, H)), jnp.float32) * 0.2
    A = -jnp.asarray(RNG.random((H,)), jnp.float32)
    B = jnp.asarray(RNG.standard_normal((b, T, N)), jnp.float32) * 0.3
    C = jnp.asarray(RNG.standard_normal((b, T, N)), jnp.float32) * 0.3
    got = ops.ssd(x, dt, A, B, C, chunk=32, use_pallas=True)
    want = ops.ssd(x, dt, A, B, C, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_ssd_bf16():
    b, T, H, P, N = 1, 64, 2, 32, 16
    x = jnp.asarray(RNG.standard_normal((b, T, H, P)), jnp.bfloat16) * 0.5
    dt = jnp.asarray(RNG.random((b, T, H)), jnp.float32) * 0.2
    A = -jnp.asarray(RNG.random((H,)), jnp.float32)
    B = jnp.asarray(RNG.standard_normal((b, T, N)), jnp.float32) * 0.3
    C = jnp.asarray(RNG.standard_normal((b, T, N)), jnp.float32) * 0.3
    got = ops.ssd(x, dt, A, B, C, chunk=32, use_pallas=True)
    want = ops.ssd(x, dt, A, B, C, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=5e-2)


# -- kernel-in-engine integration ------------------------------------------------

def test_engine_with_pallas_batch_impl_matches_oracle():
    from repro.core.engine import EngineConfig, ParsirEngine
    from repro.core.ref_engine import run_sequential
    from repro.phold.model import Phold, PholdParams

    p = PholdParams(n_objects=8, initial_events=4, state_nodes=64,
                    realloc_fraction=0.02, lookahead=0.5, dist="dyadic")
    model = Phold(p)
    cfg = EngineConfig(lookahead=0.5, n_buckets=8, bucket_cap=32,
                       route_cap=256, fallback_cap=256, batch_impl="model")
    eng = ParsirEngine(model, cfg)
    st = eng.run(eng.init(), 12)
    tot = eng.totals(st)
    ref_run = run_sequential(model, 12, 0.5)
    assert tot["processed"] == ref_run.total_processed
    assert tot["late_events"] == 0 and tot["cal_overflow"] == 0
    pay = np.asarray(st.obj["payload"])
    ref_pay = np.stack([s["payload"] for s in ref_run.obj_state])
    np.testing.assert_array_equal(pay, ref_pay)
