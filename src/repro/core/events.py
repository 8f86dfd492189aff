"""Event records and counter-based RNG for deterministic PDES.

PARSIR's event buffers are C structs in shared memory.  Here an event batch is a
struct-of-arrays pytree with a fixed capacity and a validity mask (JAX needs
static shapes; capacity overflow is *accounted*, never silent).

The RNG is a counter-based splitmix32 implemented identically in JAX (uint32
lax ops) and numpy (``events_np``), so the set of events generated by a model is
a pure function of the initial seeds — independent of processing order, device
count, or engine variant.  This is what makes the parallel engine bit-comparable
to the sequential oracle.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class EventBatch(NamedTuple):
    """A fixed-capacity batch of events (struct of arrays).

    dst:     global destination object id (i32)
    ts:      timestamp (f32)
    seed:    per-event RNG counter / tie-break (u32)
    payload: one f32 payload lane (models carrying data between objects)
    valid:   mask (bool)
    """

    dst: jax.Array
    ts: jax.Array
    seed: jax.Array
    payload: jax.Array
    valid: jax.Array

    @property
    def capacity(self) -> int:
        return self.dst.shape[-1]

    def count(self) -> jax.Array:
        return jnp.sum(self.valid.astype(jnp.int32), axis=-1)


def empty_batch(cap: int, *lead: int) -> EventBatch:
    shape = tuple(lead) + (cap,)
    return EventBatch(
        dst=jnp.zeros(shape, jnp.int32),
        ts=jnp.full(shape, jnp.inf, jnp.float32),
        seed=jnp.zeros(shape, jnp.uint32),
        payload=jnp.zeros(shape, jnp.float32),
        valid=jnp.zeros(shape, bool),
    )


def concat_batches(a: EventBatch, b: EventBatch) -> EventBatch:
    return EventBatch(*(jnp.concatenate([x, y], axis=-1) for x, y in zip(a, b)))


#: slots per row of :func:`take_first`'s two-level search: one vector
#: register's lanes.
SELECT_ROW = 128


def take_first(batch: EventBatch, mask: jax.Array, cap: int
               ) -> tuple[EventBatch, jax.Array]:
    """The first ``cap`` events that ``mask`` selects, in batch order, and
    how many selected events did not fit (the overflow past ``cap``).

    ``mask`` selects valid events only (``mask`` implies ``batch.valid``).
    Output slot ``j`` holds the ``(j+1)``-th selected event, found in two
    levels over rows of :data:`SELECT_ROW` slots: the prefix sum of the row
    counts gives its row (slot ``j`` against every row's end), and the
    prefix sum inside that row its place.  Nothing is sorted; each field is
    gathered as ``cap`` rows and the event picked out of its row by a
    one-hot sum of its bits, which is exact.  Slots past the count hold
    :func:`empty_batch`'s fill.  One-dimensional; vmap it over leading axes.
    """
    pad = -mask.shape[-1] % SELECT_ROW
    rows = lambda x: jnp.pad(x, (0, pad)).reshape(-1, SELECT_ROW)
    in_row = jnp.cumsum(rows(mask.astype(jnp.int32)), axis=1)
    count = in_row[:, -1]
    ends = jnp.cumsum(count)
    n = ends[-1]
    j = jnp.arange(cap, dtype=jnp.int32)
    before = ends[None, :] <= j[:, None]          # rows wholly before slot j
    r = jnp.minimum(jnp.sum(before, axis=1), count.shape[0] - 1)
    k = j - jnp.sum(jnp.where(before, count[None, :], 0), axis=1)
    at = jnp.minimum(jnp.sum(in_row[r] <= k[:, None], axis=1), SELECT_ROW - 1)
    hit = jnp.arange(SELECT_ROW, dtype=jnp.int32)[None, :] == at[:, None]
    ok = j < n

    def take(x, fill):
        bits = x.view(jnp.uint32) if x.dtype == jnp.float32 else x
        v = jnp.sum(jnp.where(hit, rows(bits)[r], 0), axis=1, dtype=bits.dtype)
        return jnp.where(ok, v.view(x.dtype), fill)

    out = EventBatch(*(take(x, f) for x, f in zip(batch[:4],
                                                   empty_batch(cap)[:4])), ok)
    return out, n - jnp.minimum(n, cap)


# ---------------------------------------------------------------------------
# splitmix32: counter-based RNG, bit-identical in JAX and numpy.
# ---------------------------------------------------------------------------

def _mix(z):
    # JAX version (z: uint32 array)
    z = (z + jnp.uint32(0x9E3779B9)).astype(jnp.uint32)
    z = (z ^ (z >> jnp.uint32(16))) * jnp.uint32(0x85EBCA6B)
    z = (z ^ (z >> jnp.uint32(13))) * jnp.uint32(0xC2B2AE35)
    return z ^ (z >> jnp.uint32(16))


def fold(seed, k: int):
    """Derive stream k from a seed (JAX)."""
    c = np.uint32((k * 0x632BE59B) & 0xFFFFFFFF)
    return _mix(seed.astype(jnp.uint32) ^ jnp.uint32(c))


def uniform24(bits):
    """uint32 → f32 uniform in [0, 1) with 24-bit resolution (exact dyadic)."""
    return (bits >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def dyadic10(bits):
    """uint32 → f32 in [0,1) on a 1/1024 grid — every value and every partial
    timestamp sum below 2**14 is exactly representable in f32, so numpy and XLA
    (with or without FMA contraction) agree bit-for-bit."""
    return (bits & jnp.uint32(1023)).astype(jnp.float32) * jnp.float32(1.0 / 1024.0)


def draw(bits, dist: str, mean: float = 1.0):
    """Shared increment draw used by every workload model (JAX).

    ``dyadic`` ignores ``mean`` — it exists for bit-exact oracle comparison and
    must stay on the 1/1024 grid."""
    if dist == "dyadic":
        return dyadic10(bits)
    if dist == "uniform24":
        return uniform24(bits) * jnp.float32(mean)
    if dist == "exponential":
        return -jnp.log1p(-uniform24(bits)) * jnp.float32(mean)
    raise ValueError(dist)


def dyadic_scaled(bits, shift: int):
    """uint32 → f32 in [0, 2**-shift) on the 1/(1024·2**shift) grid.

    Scaling by a power of two only changes the f32 exponent, so the result
    is still an exact dyadic — the grid *refines*, it never leaves.  The
    finer grid needs ``10 + shift`` fractional mantissa bits, so partial
    sums on it stay bit-exact below ``2**(14 - shift)`` (not dyadic10's
    2**14 — the exactness window shrinks with the refinement; keep shift
    small and horizons short enough that timestamps stay under it).
    Workloads use this for rate skew (e.g. a hot cell draws its
    inter-arrival gap on a 2**shift-finer grid ⇒ 2**shift-ish higher rate)
    without giving up oracle bit-exactness."""
    return dyadic10(bits) * jnp.float32(2.0 ** -shift)


def draw_scaled(bits, dist: str, shift: int, mean: float = 1.0):
    """:func:`draw` scaled by ``2**-shift`` (exact for every dist — power-of-
    two scaling never rounds in IEEE-754; for ``dyadic`` this is exactly
    :func:`dyadic_scaled`)."""
    if dist == "dyadic":
        return dyadic_scaled(bits, shift)
    return draw(bits, dist, mean) * jnp.float32(2.0 ** -shift)


def ring_neighbor(gid, go_right, n: int):
    """Neighbor on a ring of ``n`` objects, wrapping at both edges.

    Polymorphic over jnp/np int inputs (one definition serves both mirror
    faces — integer arithmetic is exact everywhere): ``(gid + 1) % n`` to
    the right, ``(gid + n - 1) % n`` to the left — the ``+ n - 1`` form
    keeps the operand non-negative so ``%`` behaves identically in jnp and
    np.  Shared by the ring-topology workloads (epidemic patches, wireless
    cells).
    """
    step = jnp.where(go_right, 1, n - 1) if hasattr(gid, "aval") \
        else np.int32(1 if go_right else n - 1)
    return (gid + step) % n


# numpy mirrors ---------------------------------------------------------------

def seed_salt_np(seed: int) -> np.uint32:
    """Replication-seed salt for a workload's initial-event stream.

    Every workload derives its bootstrap seeds from a fixed per-workload
    constant (``_INIT_C`` et al.); replications differ *only* by XORing this
    salt into that constant before the splitmix fold.  The odd-multiplier
    Weyl step is a bijection on uint32 (distinct seeds → distinct salts) and
    maps ``seed=0`` to 0, so the unsalted historical stream — the one pinned
    by the golden digests — is exactly the seed-0 stream.
    """
    with np.errstate(over="ignore"):
        return np.uint32(np.uint32(seed) * np.uint32(0x9E3779B9))


def _mix_np(z: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        z = (z.astype(np.uint32) + np.uint32(0x9E3779B9)).astype(np.uint32)
        z = ((z ^ (z >> np.uint32(16))) * np.uint32(0x85EBCA6B)).astype(np.uint32)
        z = ((z ^ (z >> np.uint32(13))) * np.uint32(0xC2B2AE35)).astype(np.uint32)
        return (z ^ (z >> np.uint32(16))).astype(np.uint32)


def fold_np(seed, k: int):
    c = np.uint32((k * 0x632BE59B) & 0xFFFFFFFF)
    with np.errstate(over="ignore"):
        return _mix_np(np.uint32(seed) ^ c)


def uniform24_np(bits):
    return np.float32(np.uint32(bits) >> np.uint32(8)) * np.float32(1.0 / (1 << 24))


def dyadic10_np(bits):
    return np.float32(np.uint32(bits) & np.uint32(1023)) * np.float32(1.0 / 1024.0)


def draw_np(bits, dist: str, mean: float = 1.0):
    """numpy mirror of :func:`draw` — identical op order for bit-exactness."""
    if dist == "dyadic":
        return dyadic10_np(bits)
    if dist == "uniform24":
        return uniform24_np(bits) * np.float32(mean)
    if dist == "exponential":
        u = uniform24_np(bits)
        return np.float32(-np.log1p(-u)) * np.float32(mean)
    raise ValueError(dist)


def dyadic_scaled_np(bits, shift: int):
    """numpy mirror of :func:`dyadic_scaled` — identical op order."""
    return np.float32(dyadic10_np(bits) * np.float32(2.0 ** -shift))


def draw_scaled_np(bits, dist: str, shift: int, mean: float = 1.0):
    """numpy mirror of :func:`draw_scaled` — identical op order."""
    if dist == "dyadic":
        return dyadic_scaled_np(bits, shift)
    return np.float32(draw_np(bits, dist, mean) * np.float32(2.0 ** -shift))
