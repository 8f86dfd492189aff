"""jit'd public wrappers for the Pallas kernels (+ layout/padding adapters).

:func:`event_apply` — the simulator's kernel — derives its mode from the
platform (:func:`interpret_mode`): the Pallas interpreter on ``cpu``, Mosaic
on ``tpu``, an error anywhere else, so it never runs interpreted on a chip.
``ref.py`` holds the pure-jnp oracles the kernels are tested against.  The
attention and SSD ops are seed LLM scaffolding and keep their explicit
``use_pallas``/``interpret`` switches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .event_apply import LANE, OBJ_BLOCK, build_event_apply
from .flash_attention import flash_attention
from .ssd_scan import ssd_scan


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter on this platform:
    ``cpu`` interprets, ``tpu`` compiles with Mosaic, anything else fails."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernel path for platform {platform!r}")


# -- event apply -------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _event_apply_cached(S, LANES, C, K, KR, n_objects, lookahead, dist, mean,
                        interpret, hot_objects, hot_prob):
    call = build_event_apply(S=S, LANES=LANES, C=C, K=K, KR=KR,
                             n_objects=n_objects, lookahead=lookahead,
                             dist=dist, mean=mean, interpret=interpret,
                             hot_objects=hot_objects, hot_prob=hot_prob)
    return jax.jit(call)


def event_apply(payload, addresses, top, ts, seed, cnt, *, n_objects: int,
                lookahead: float, K: int, KR: int, dist: str = "dyadic",
                mean: float = 1.0, hot_objects: int = 0, hot_prob: int = 0):
    """Batched per-object event application.  payload: [n, LANES, S].

    Pads objects to the kernel's 8-object block (pad rows have ``cnt`` 0)
    and events to whole 128-lane tiles, then slices the result back; the
    outputs match :func:`repro.kernels.ref.event_apply_ref` bit for bit."""
    n, LANES, S = payload.shape
    C = ts.shape[1]
    pn, pc = -n % OBJ_BLOCK, -C % LANE
    if pn:
        rows = lambda x, v=0: jnp.pad(x, ((0, pn),) + ((0, 0),) * (x.ndim - 1),
                                      constant_values=v)
        payload, addresses, top, cnt = (rows(payload), rows(addresses),
                                        rows(top), rows(cnt))
        ts, seed = rows(ts, jnp.inf), rows(seed)
    if pc:
        ts = jnp.pad(ts, ((0, 0), (0, pc)), constant_values=jnp.inf)
        seed = jnp.pad(seed, ((0, 0), (0, pc)))
    fn = _event_apply_cached(S, LANES, C + pc, K, KR, n_objects, lookahead,
                             dist, mean, interpret_mode(), hot_objects,
                             hot_prob)
    pay, addr, *emitted = fn(payload, addresses, top, ts, seed, cnt)
    emitted = [e[:n, :C] for e in emitted]
    return (pay[:n], addr[:n], top[:n], *emitted)


# -- attention ----------------------------------------------------------------

def mha(q, k, v, *, causal: bool = True, bq: int = 128, bk: int = 128,
        interpret: bool = True, use_pallas: bool = True):
    """GQA attention.  q: [B,Hq,Tq,D]; k,v: [B,Hkv,Tk,D]."""
    if not use_pallas:
        return ref.attention_ref(q, k, v, causal=causal)
    B, Hq, Tq, D = q.shape
    Tk = k.shape[2]
    bq_, bk_ = min(bq, max(8, Tq)), min(bk, max(8, Tk))
    pq = (-Tq) % bq_
    pk = (-Tk) % bk_
    if pk and not causal:
        raise ValueError("non-causal attention requires Tk % bk == 0")
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    out = flash_attention(q, k, v, causal=causal, bq=bq_, bk=bk_,
                          interpret=interpret)
    return out[:, :, :Tq, :]


# -- SSD ----------------------------------------------------------------------

def ssd(x, dt, A, B, C, *, chunk: int = 128, interpret: bool = True,
        use_pallas: bool = True):
    """Mamba-2 SSD.  x: [b,T,H,P]; dt: [b,T,H]; A: [H]; B,C: [b,T,N]."""
    if not use_pallas:
        return ref.ssd_ref(x, dt, A, B, C)
    b, T, H, P = x.shape
    ch = min(chunk, T) if T % min(chunk, T) == 0 else chunk
    pad = (-T) % ch
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))  # dt=0 → identity update
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    y = ssd_scan(x, dt, A, B, C, chunk=ch, interpret=interpret)
    return y[:, :T]
