"""Kernel micro-benchmarks: Pallas vs jnp reference.

On a CPU the Pallas kernels run in the interpreter, so those timings are
correctness-path timings, not TPU performance."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref


def _time(fn, *args, reps=3):
    out = fn(*args)
    jax.tree.leaves(out)[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.tree.leaves(out)[0].block_until_ready()
    return (time.perf_counter() - t0) / reps


def run(rows):
    rng = np.random.default_rng(0)

    # event_apply
    n, LANES, S, C = 32, 6, 512, 16
    payload = jnp.asarray(rng.random((n, LANES, S), np.float32))
    addresses = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (n, S))
    top = jnp.full((n,), S, jnp.int32)
    ts = jnp.asarray(np.sort(rng.random((n, C)).astype(np.float32), axis=1))
    seed = jnp.asarray(rng.integers(0, 2**32, (n, C), dtype=np.uint32))
    cnt = jnp.full((n,), C, jnp.int32)
    kw = dict(n_objects=64, lookahead=0.5, K=S // 32, KR=3, dist="dyadic")
    for impl, fn in (("pallas", ops.event_apply),
                     ("jnp_ref", ref.event_apply_ref)):
        f = jax.jit(lambda *a, fn=fn: fn(*a, **kw))
        dt = _time(f, payload, addresses, top, ts, seed, cnt)
        rows.append({"name": f"kernel_event_apply_{impl}",
                     "us_per_call": 1e6 * dt,
                     "derived": f"events={n*C} shape=({n},{LANES},{S})x{C}"})

    # flash attention
    q = jnp.asarray(rng.standard_normal((1, 4, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 256, 64)), jnp.float32)
    for impl, flag in (("pallas_interp", True), ("jnp_ref", False)):
        f = jax.jit(lambda a, b, c: ops.mha(a, b, c, causal=True, bq=128,
                                            bk=128, use_pallas=flag))
        dt = _time(f, q, k, v)
        rows.append({"name": f"kernel_flash_attn_{impl}",
                     "us_per_call": 1e6 * dt,
                     "derived": "shape=B1 Hq4 Hkv2 T256 D64"})

    # ssd
    x = jnp.asarray(rng.standard_normal((1, 256, 4, 64)), jnp.float32) * 0.5
    dtt = jnp.asarray(rng.random((1, 256, 4)), jnp.float32) * 0.2
    A = -jnp.asarray(rng.random((4,)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((1, 256, 32)), jnp.float32) * 0.3
    Cm = jnp.asarray(rng.standard_normal((1, 256, 32)), jnp.float32) * 0.3
    for impl, flag in (("pallas_interp", True), ("seq_ref", False)):
        f = jax.jit(lambda *a: ops.ssd(*a, chunk=64, use_pallas=flag))
        dt = _time(f, x, dtt, A, B, Cm)
        rows.append({"name": f"kernel_ssd_{impl}",
                     "us_per_call": 1e6 * dt,
                     "derived": "shape=B1 T256 H4 P64 N32"})
    return rows
