"""PHOLD benchmark model (paper §IV-A, Table II).

State of each object = linked lists of chunks in the paper's extended PHOLD;
here: a node arena ``payload[S, LANES]`` plus the stack allocator of
:mod:`repro.phold.arena` (addresses/top — the paper's Fig 1 layout).  An event

  * touches ``S/32`` of the nodes (read + write, mimicking the busy-channel
    scans of [28] in the paper),
  * reallocates a fraction ``P`` of the state via free/alloc pairs through the
    stack allocator (the paper's malloc/free interception path),
  * emits exactly one new event with a uniformly random destination and a
    timestamp increment ``lookahead + draw(dist)`` — so global event population
    is conserved at ``O*M``, as in classic PHOLD.

Every implementation exists twice: in JAX (engine) and in numpy
(sequential-oracle mirror, same op order).  With ``dist='dyadic'`` all floats
are exact dyadics and the two agree bit-for-bit (see core/events.py).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..core import events as ev
from ..core.api import EmittedEvents, SimModel
from . import arena as ar

_INIT_C = np.uint32(0xA511E9B3)


@dataclasses.dataclass(frozen=True)
class PholdParams:
    n_objects: int = 1024          # O
    initial_events: int = 10       # M
    state_nodes: int = 4000        # S (list nodes per object)
    realloc_fraction: float = 0.001  # P
    lookahead: float = 0.5         # L (simulation-time units)
    mean_increment: float = 1.0    # TA scale for the draw
    dist: str = "dyadic"           # dyadic | uniform24 | exponential
    lanes: int = 6                 # payload lanes per node (~32B chunks)
    # non-uniform routing (paper §IV-A: "uniform or non-uniform distribution"):
    # with probability hot_prob/256 the new event targets one of the first
    # hot_objects ids — a skewed workload that exercises work stealing.
    hot_objects: int = 0
    hot_prob: int = 0              # out of 256
    # replication seed: salts the bootstrap event stream only (seed=0 is the
    # historical stream); see SimModel.initial_events.
    seed: int = 0

    @property
    def touch(self) -> int:
        return max(1, self.state_nodes // 32)

    @property
    def realloc_k(self) -> int:
        return max(1, int(math.ceil(self.realloc_fraction * self.state_nodes)))


def _draw(bits, params: PholdParams):
    return ev.draw(bits, params.dist, params.mean_increment)


def _draw_np(bits, params: PholdParams):
    return ev.draw_np(bits, params.dist, params.mean_increment)


class Phold(SimModel):
    max_out = 1

    def __init__(self, params: PholdParams):
        self.params = params

    @property
    def n_objects(self) -> int:
        return self.params.n_objects

    # -- state ---------------------------------------------------------------

    def init_object_state(self, global_ids: np.ndarray) -> Any:
        n = len(global_ids)
        S, LN = self.params.state_nodes, self.params.lanes
        # initial payload from the object id — deterministic, device-agnostic.
        g = np.asarray(global_ids, np.uint32)
        base = ev.dyadic10_np(ev.fold_np(ev._mix_np(g ^ _INIT_C), 7))  # [n]
        payload = np.broadcast_to(base[:, None, None], (n, S, LN)).astype(np.float32)
        return {
            "payload": jnp.asarray(payload),
            "addresses": jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (n, S)),
            "top": jnp.full((n,), S, jnp.int32),
        }

    def object_weights(self) -> np.ndarray | None:
        """Expected steady-state event share per object (placement hint).

        With non-uniform routing, every emission lands on one of the first
        ``hot_objects`` ids with probability ``hot_prob/256`` — so in steady
        state that mass concentrates there regardless of where events start.
        Uniform routing carries no skew: return None (equal split).
        """
        p = self.params
        if not (p.hot_objects and p.hot_prob):
            return None
        h = p.hot_prob / 256.0
        w = np.full(p.n_objects, (1.0 - h) / p.n_objects, np.float64)
        w[:p.hot_objects] += h / p.hot_objects
        return w

    def initial_events(self, seed: int | None = None) -> dict[str, np.ndarray]:
        p = self.params
        c = _INIT_C ^ ev.seed_salt_np(p.seed if seed is None else seed)
        o = np.repeat(np.arange(p.n_objects, dtype=np.uint32), p.initial_events)
        m = np.tile(np.arange(p.initial_events, dtype=np.uint32), p.n_objects)
        with np.errstate(over="ignore"):
            s0 = ev._mix_np(ev._mix_np(o ^ c) + m * np.uint32(0x9E3779B9))
        ts0 = _draw_np(ev.fold_np(s0, 2), p).astype(np.float32)
        return {
            "dst": o.astype(np.int32),
            "ts": ts0,
            "seed": s0,
            "payload": ev.dyadic10_np(ev.fold_np(s0, 4)).astype(np.float32),
        }

    # -- ProcessEvent (JAX) ----------------------------------------------------

    def process_event(self, state, ts, seed, payload):
        p = self.params
        S, K, KR = p.state_nodes, p.touch, p.realloc_k
        seed = seed.astype(jnp.uint32)

        # contiguous touch window [start, start + K) (no wraparound).  Both
        # payload writes are masked dense updates over the object's [S, LANES]
        # rows (node iota compare + select), as in the Pallas event_apply
        # kernel: vmapped over every object of a round, an indexed
        # .at[idx].set would become one chip scatter of n_objects x K rows.
        start = (ev.fold(seed, 0) % jnp.uint32(S - K + 1)).astype(jnp.int32)
        idx = start + jnp.arange(K, dtype=jnp.int32)
        del payload  # PHOLD's handler keys everything off the event seed
        delta = ev.dyadic10(ev.fold(seed, 5))
        node = jnp.arange(S, dtype=jnp.int32)[:, None]     # [S, 1]
        win = (node >= start) & (node < start + K)
        state_payload = jnp.where(
            win, state["payload"] * jnp.float32(0.5) + delta, state["payload"])

        a = ar.Arena(state["addresses"], state["top"])
        a = ar.free_k(a, idx[:KR])
        a, got = ar.alloc_k(a, KR)
        initval = ev.dyadic10(ev.fold(seed, 6))
        # after the window, so a reallocated node inside it ends as initval.
        hit = jnp.any(node == got[None, :], axis=1, keepdims=True)
        state_payload = jnp.where(hit, jnp.float32(0.0) + initval,
                                  state_payload)

        dst = (ev.fold(seed, 1) % jnp.uint32(p.n_objects)).astype(jnp.int32)
        if p.hot_objects and p.hot_prob:
            hot = ((ev.fold(seed, 8) & jnp.uint32(255))
                   < jnp.uint32(p.hot_prob))
            hot_dst = (ev.fold(seed, 9) % jnp.uint32(p.hot_objects)
                       ).astype(jnp.int32)
            dst = jnp.where(hot, hot_dst, dst)
        ts_out = ts + jnp.float32(p.lookahead) + _draw(ev.fold(seed, 2), p)
        out = EmittedEvents(
            dst=dst[None],
            ts=ts_out[None],
            seed=ev.fold(seed, 3)[None],
            payload=ev.dyadic10(ev.fold(seed, 4))[None],
            valid=jnp.ones((1,), bool),
        )
        new_state = {"payload": state_payload, "addresses": a.addresses, "top": a.top}
        return new_state, out

    # -- whole-batch ProcessEvent via the Pallas kernel ------------------------

    def process_batch(self, state, ts_s, seed_s, pay_s, cnt_b, lookahead):
        """Apply each object's sorted epoch batch in one kernel call
        (kernels/event_apply.py — the VMEM-hot analogue of the paper's
        cache-hot batch execution).  Drop-in for the engine's rounds loop;
        Mosaic-compiled on a TPU, interpreted on the CPU.  Its rounds are
        the kernel's grid steps, one per ``OBJ_BLOCK`` objects."""
        from ..core.events import EventBatch
        from ..kernels import ops
        from ..kernels.event_apply import OBJ_BLOCK
        p = self.params
        payload = jnp.swapaxes(state["payload"], 1, 2)   # [n,S,LN] → [n,LN,S]
        (pay2, addr2, top2, odst, ots, oseed, opay, ovalid) = ops.event_apply(
            payload, state["addresses"], state["top"], ts_s, seed_s, cnt_b,
            n_objects=p.n_objects, lookahead=p.lookahead, K=p.touch,
            KR=p.realloc_k, dist=p.dist, mean=p.mean_increment,
            hot_objects=p.hot_objects, hot_prob=p.hot_prob)
        new_state = {"payload": jnp.swapaxes(pay2, 1, 2),
                     "addresses": addr2, "top": top2}
        valid = ovalid.astype(bool)
        out = EventBatch(dst=odst.reshape(-1), ts=ots.reshape(-1),
                         seed=oseed.reshape(-1), payload=opay.reshape(-1),
                         valid=valid.reshape(-1))
        lv = jnp.sum((valid & (ots < ts_s + jnp.float32(lookahead))
                      ).astype(jnp.int32))
        grid = jnp.int32(-(-ts_s.shape[0] // OBJ_BLOCK))
        return new_state, out, lv, grid

    # -- numpy mirror (sequential oracle) --------------------------------------

    def process_event_np(self, st: dict, ts, seed, payload):
        p = self.params
        S, K, KR = p.state_nodes, p.touch, p.realloc_k
        seed = np.uint32(seed)

        start = np.int32(ev.fold_np(seed, 0) % np.uint32(S - K + 1))
        idx = start + np.arange(K, dtype=np.int32)
        delta = ev.dyadic10_np(ev.fold_np(seed, 5))
        st["payload"][idx] = st["payload"][idx] * np.float32(0.5) + delta

        st["addresses"], st["top"] = ar.free_k_np(st["addresses"], st["top"], idx[:KR])
        st["addresses"], st["top"], got = ar.alloc_k_np(st["addresses"], st["top"], KR)
        st["payload"][got] = ev.dyadic10_np(ev.fold_np(seed, 6))

        dst = np.int32(ev.fold_np(seed, 1) % np.uint32(p.n_objects))
        if p.hot_objects and p.hot_prob:
            if (ev.fold_np(seed, 8) & np.uint32(255)) < np.uint32(p.hot_prob):
                dst = np.int32(ev.fold_np(seed, 9) % np.uint32(p.hot_objects))
        ts_out = np.float32(np.float32(ts) + np.float32(p.lookahead)
                            + _draw_np(ev.fold_np(seed, 2), p))
        return {
            "dst": dst,
            "ts": ts_out,
            "seed": ev.fold_np(seed, 3),
            "payload": ev.dyadic10_np(ev.fold_np(seed, 4)),
        }

    def init_object_state_np(self, global_ids: np.ndarray) -> list[dict]:
        S, LN = self.params.state_nodes, self.params.lanes
        out = []
        for g in np.asarray(global_ids, np.uint32):
            base = ev.dyadic10_np(ev.fold_np(ev._mix_np(g ^ _INIT_C), 7))
            addresses, top = ar.arena_init_np(S)
            out.append({
                "payload": np.full((S, LN), base, np.float32),
                "addresses": addresses,
                "top": top,
            })
        return out
