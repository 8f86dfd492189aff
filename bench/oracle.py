"""The plain reference: a sequential discrete-event loop in numpy.

Events are taken one at a time in global ``(ts, seed)`` order from a heap,
the classic single-threaded DES loop.  The workload handlers it runs live in
``bench/reference/<workload>.py``.  Nothing here imports the program: the
counter-based RNG below is a copy of the one the program documents
(splitmix32), so a change to the program cannot move the yardstick.

``rnd`` puts a lower precision in: it is applied to every emitted event's
timestamp and payload and to every float leaf of the object that handled the
event.  The benchmark's runs never pass it; the control does (bfloat16).
"""
from __future__ import annotations

import heapq
from dataclasses import dataclass
from typing import Callable

import numpy as np

U32 = np.uint32
M32 = 0xFFFFFFFF


def mix_int(z: int) -> int:
    """splitmix32 finaliser on one value, in Python integers (the fast path
    of the per-event loop)."""
    z = (z + 0x9E3779B9) & M32
    z = ((z ^ (z >> 16)) * 0x85EBCA6B) & M32
    z = ((z ^ (z >> 13)) * 0xC2B2AE35) & M32
    return z ^ (z >> 16)


def fold_int(seed: int, k: int) -> int:
    """Stream ``k`` of one seed, in Python integers."""
    return mix_int(seed ^ ((k * 0x632BE59B) & M32))


def dyadic_int(bits: int) -> float:
    """One uint32 -> a value on the 1/1024 grid in [0, 1), exact in f32."""
    return (bits & 1023) / 1024.0


def f32(x: float) -> float:
    """Round a Python float to the nearest f32 value."""
    return float(np.float32(x))


def mix(z):
    """splitmix32 finaliser on uint32 arrays."""
    with np.errstate(over="ignore"):
        z = (np.asarray(z).astype(U32) + U32(0x9E3779B9)).astype(U32)
        z = ((z ^ (z >> U32(16))) * U32(0x85EBCA6B)).astype(U32)
        z = ((z ^ (z >> U32(13))) * U32(0xC2B2AE35)).astype(U32)
        return (z ^ (z >> U32(16))).astype(U32)


def fold(seed, k: int):
    """Stream ``k`` of a seed."""
    c = U32((k * 0x632BE59B) & 0xFFFFFFFF)
    return mix(np.asarray(seed).astype(U32) ^ c)


def dyadic10(bits):
    """uint32 -> f32 in [0, 1) on the 1/1024 grid."""
    return (np.asarray(bits).astype(U32) & U32(1023)).astype(np.float32) \
        * np.float32(1.0 / 1024.0)


def seed_salt(seed: int):
    """A replication seed's salt for the bootstrap stream (odd Weyl step)."""
    with np.errstate(over="ignore"):
        return U32(U32(seed) * U32(0x9E3779B9))


def bootstrap_seeds(n_per_object: np.ndarray, init_c, seed: int):
    """(object id, bootstrap seed) of ``n_per_object[o]`` streams per object."""
    c = U32(init_c) ^ seed_salt(seed)
    o = np.repeat(np.arange(len(n_per_object), dtype=U32), n_per_object)
    m = np.concatenate([np.arange(n, dtype=U32) for n in n_per_object])
    with np.errstate(over="ignore"):
        s0 = mix(mix(o ^ c) + m * U32(0x9E3779B9))
    return o, s0


def to_bfloat16(x):
    """Round f32 values to the nearest bfloat16 (ties to even), kept as f32;
    a Python float gives a Python float, an array an array."""
    a = np.array(x, np.float32, ndmin=1)
    b = a.view(U32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    out = b.astype(U32).view(np.float32).reshape(np.shape(x))
    return float(out) if isinstance(x, float) else out


@dataclass
class Result:
    """What one reference simulation leaves at its horizon."""

    committed: int                 # events processed before the horizon
    pending: np.ndarray            # (dst, seed) u64 [n, 2], sorted
    state: dict[str, np.ndarray]   # object state, leading dim n_objects


def sorted_records(dst, seed) -> np.ndarray:
    rec = np.stack([np.asarray(dst, np.uint64).reshape(-1),
                    np.asarray(seed, np.uint64).reshape(-1)], axis=1)
    return rec[np.lexsort((rec[:, 1], rec[:, 0]))]


def run(model, horizon: float, seed: int,
        rnd: Callable | None = None) -> Result:
    """Simulate replication ``seed`` of ``model`` up to ``horizon``
    (exclusive) and return what is left there."""
    horizon = float(np.float32(horizon))
    keep = rnd or (lambda v: v)
    states = model.init_state()
    init = model.initial_events(seed)
    # heap entries (ts, seed, dst, payload): Python floats that hold f32
    # values and Python ints, ordered by (ts, seed) as the program orders.
    heap = [(keep(float(t)), int(s), int(d), keep(float(p)))
            for d, t, s, p in zip(init["dst"], init["ts"], init["seed"],
                                  init["payload"])]
    heapq.heapify(heap)
    committed = 0
    while heap and heap[0][0] < horizon:
        ts, s, dst, pay = heapq.heappop(heap)
        committed += 1
        st = states[dst]
        for d, t, s2, p in model.process(st, ts, s, pay):
            heapq.heappush(heap, (keep(t), s2, d, keep(p)))
        if rnd is not None:
            for k, v in st.items():
                if np.asarray(v).dtype == np.float32:
                    st[k] = rnd(v)
    pending = sorted_records([d for _, _, d, _ in heap],
                             [s for _, s, _, _ in heap])
    state = {k: np.stack([np.asarray(st[k]) for st in states])
             for k in states[0]}
    return Result(committed, pending, state)
