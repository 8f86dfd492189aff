"""PHOLD, as the plain reference runs it (PARSIR paper, section IV-A).

Each object keeps a node arena ``payload[S, lanes]`` and a stack allocator
(``addresses``, ``top``: free nodes are ``addresses[top:]``).  An event
halves-and-shifts a contiguous window of ``S/32`` nodes, frees and
reallocates ``ceil(P*S)`` nodes, and emits one event to a uniform (or, with
``hot_objects``/``hot_prob``, skewed) destination at ``ts + L + draw``.
Only the ``dyadic`` draw is supported: it keeps every value exact in f32.
"""
from __future__ import annotations

import math

import numpy as np

from bench.oracle import (bootstrap_seeds, dyadic10, dyadic_int, f32, fold,
                          fold_int, mix)

INIT_C = 0xA511E9B3


class Model:
    max_out = 1

    def __init__(self, n_objects=1024, initial_events=10, state_nodes=4000,
                 realloc_fraction=0.001, lookahead=0.5, mean_increment=1.0,
                 dist="dyadic", lanes=6, hot_objects=0, hot_prob=0):
        if dist != "dyadic":
            raise ValueError(f"the reference is exact only for dist='dyadic', "
                             f"got {dist!r}")
        self.n_objects, self.m = n_objects, initial_events
        self.S, self.lanes, self.L = state_nodes, lanes, f32(lookahead)
        self.K = max(1, state_nodes // 32)
        self.KR = max(1, int(math.ceil(realloc_fraction * state_nodes)))
        self.hot_objects, self.hot_prob = hot_objects, hot_prob

    def init_state(self) -> list[dict]:
        out = []
        for g in np.arange(self.n_objects, dtype=np.uint32):
            base = dyadic10(fold(mix(g ^ np.uint32(INIT_C)), 7))
            out.append({"payload": np.full((self.S, self.lanes), base,
                                           np.float32),
                        "addresses": np.arange(self.S, dtype=np.int32),
                        "top": np.int32(self.S)})
        return out

    def initial_events(self, seed: int) -> dict[str, np.ndarray]:
        o, s0 = bootstrap_seeds(np.full(self.n_objects, self.m), INIT_C, seed)
        return {"dst": o.astype(np.int32), "ts": dyadic10(fold(s0, 2)),
                "seed": s0, "payload": dyadic10(fold(s0, 4))}

    def process(self, st: dict, ts: float, seed: int, payload: float
                ) -> list[tuple]:
        S, K, KR = self.S, self.K, self.KR
        start = fold_int(seed, 0) % (S - K + 1)
        win = st["payload"][start:start + K]        # a view: written in place
        win *= np.float32(0.5)
        win += np.float32(dyadic_int(fold_int(seed, 5)))
        # free the first KR touched nodes (last freed at the lowest slot),
        # then allocate KR from the top of the stack.
        top = int(st["top"]) - KR
        addr = st["addresses"]
        addr[top:top + KR] = np.arange(start + KR - 1, start - 1, -1)
        st["top"] = np.int32(top + KR)
        st["payload"][addr[top:top + KR]] = np.float32(
            dyadic_int(fold_int(seed, 6)))

        dst = fold_int(seed, 1) % self.n_objects
        if self.hot_objects and self.hot_prob:
            if (fold_int(seed, 8) & 255) < self.hot_prob:
                dst = fold_int(seed, 9) % self.hot_objects
        ts_out = f32(f32(ts + self.L) + dyadic_int(fold_int(seed, 2)))
        return [(dst, ts_out, fold_int(seed, 3), dyadic_int(fold_int(seed, 4)))]
