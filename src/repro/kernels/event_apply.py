"""Pallas TPU kernel: per-object batched event application (PHOLD hot loop).

This is the paper's core locality idea (§II-A) mapped to the TPU memory
hierarchy: PARSIR keeps a simulation object cache-hot while a worker thread
processes the object's whole epoch batch; here the object's state tile is
loaded **once** into VMEM, every event of its batch is applied in timestamp
order by an in-kernel loop, and the state is written back **once**.  HBM
traffic per epoch drops from O(events x touched-state) to O(state), which is
exactly the paper's cache-miss argument restated for HBM<->VMEM.

Layout (what Mosaic accepts on a v5e):
  * one grid step owns a block of ``OBJ_BLOCK`` = 8 objects, so every 2-D
    block's sublane dim is 8; the per-event arrays arrive lane-padded to a
    multiple of 128 (:func:`repro.kernels.ops.event_apply` pads both);
  * node payloads are ``[LANES, S]`` per object — the long node axis is the
    lane (minor) dimension and the block spans it whole;
  * ``cnt``/``top`` are scalar-prefetch operands (SMEM), and each event's
    seed is read as a scalar from an SMEM copy of the seed block, so the
    per-event draws (touch-window start, deltas) are scalar-unit work;
  * the data-dependent, unaligned touch window and the arena's free/alloc
    stores are masked full-width updates (lane iota compare + select) of the
    VMEM-resident rows, never dynamic lane slices;
  * emission does not depend on the state, so it is computed for the whole
    ``[8, C]`` event block at once and masked by ``lane < cnt``.

Grid: one program instance per object block; instances are independent.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.events import fold

#: objects per grid step — the f32 sublane tile.
OBJ_BLOCK = 8
#: the lane tile: per-event arrays are padded to a multiple of it.
LANE = 128


def _dyadic10(bits):
    # via int32: the value fits in 10 bits, and Mosaic converts signed ints.
    return ((bits & jnp.uint32(1023)).astype(jnp.int32).astype(jnp.float32)
            * jnp.float32(1.0 / 1024.0))


def _uniform24(bits):
    return ((bits >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)
            * jnp.float32(1.0 / (1 << 24)))


def _draw(bits, dist: str, mean: float):
    if dist == "dyadic":
        return _dyadic10(bits)
    if dist == "uniform24":
        return _uniform24(bits) * jnp.float32(mean)
    if dist == "exponential":
        return -jnp.log1p(-_uniform24(bits)) * jnp.float32(mean)
    raise ValueError(dist)


def _kernel(cnt_ref, top_ref, seed_smem, ts_ref, seed_ref, payload_in,
            addr_in, payload_out, addr_out, odst, ots, oseed, opay, ovalid,
            *, S, K, KR, n_objects, lookahead, dist, mean, hot_objects,
            hot_prob):
    base = pl.program_id(0) * OBJ_BLOCK
    C = ts_ref.shape[1]

    # -- emission: a pure function of (ts, seed), all 8 x C events at once.
    lane_c = jax.lax.broadcasted_iota(jnp.int32, (OBJ_BLOCK, C), 1)
    cnt_rows = jnp.concatenate(
        [jnp.full((1, C), cnt_ref[base + j], jnp.int32)
         for j in range(OBJ_BLOCK)], axis=0)
    valid = lane_c < cnt_rows
    seed = seed_ref[...]
    dst = (fold(seed, 1) % jnp.uint32(n_objects)).astype(jnp.int32)
    if hot_objects and hot_prob:
        hot = (fold(seed, 8) & jnp.uint32(255)) < jnp.uint32(hot_prob)
        hot_dst = (fold(seed, 9) % jnp.uint32(hot_objects)).astype(jnp.int32)
        dst = jnp.where(hot, hot_dst, dst)
    odst[...] = jnp.where(valid, dst, 0)
    ots[...] = jnp.where(valid, ts_ref[...] + jnp.float32(lookahead)
                         + _draw(fold(seed, 2), dist, mean), jnp.inf)
    oseed[...] = jnp.where(valid, fold(seed, 3), jnp.uint32(0))
    opay[...] = jnp.where(valid, _dyadic10(fold(seed, 4)), jnp.float32(0))
    ovalid[...] = valid.astype(jnp.int32)

    # -- state: each object's rows stay in registers/VMEM across its batch.
    LANES = payload_in.shape[1]
    node = jax.lax.broadcasted_iota(jnp.int32, (LANES, S), 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
    for j in range(OBJ_BLOCK):
        top = top_ref[base + j]

        def apply(r, carry, j=j, top=top):
            pay, addr = carry
            s = seed_smem[j, r]
            start = (fold(s, 0) % jnp.uint32(S - K + 1)).astype(jnp.int32)
            # touch: the window [start, start + K) is halved plus a delta.
            win = (node >= start) & (node < start + K)
            pay = jnp.where(win, pay * jnp.float32(0.5)
                            + _dyadic10(fold(s, 5)), pay)
            # arena: free the KR first touched nodes onto the stack at
            # [top - KR, top) in reverse order, then alloc them back (LIFO)
            # and re-initialise them — top is unchanged.
            freed = (slot >= top - KR) & (slot < top)
            addr = jnp.where(freed, start + top - 1 - slot, addr)
            got = (node >= start) & (node < start + KR)
            pay = jnp.where(got, _dyadic10(fold(s, 6)), pay)
            return pay, addr

        pay, addr = jax.lax.fori_loop(
            0, cnt_ref[base + j], apply,
            (payload_in[j], addr_in[j:j + 1, :]))
        payload_out[j] = pay
        addr_out[j:j + 1, :] = addr


def build_event_apply(*, S: int, LANES: int, C: int, K: int, KR: int,
                      n_objects: int, lookahead: float, dist: str,
                      mean: float, interpret: bool, hot_objects: int = 0,
                      hot_prob: int = 0):
    """Build the pallas_call for fixed static geometry.

    The returned ``call(payload, addresses, top, ts, seed, cnt)`` takes
    ``n`` a multiple of :data:`OBJ_BLOCK` and ``C`` a multiple of
    :data:`LANE`; it returns ``(payload, addresses, dst, ts, seed, payload,
    valid)`` — the updated state (``top`` is unchanged by construction) and
    the ``[n, C]`` emission rows.  ``interpret`` has no default: callers
    derive it from the platform (:func:`repro.kernels.ops.interpret_mode`).
    """
    if C % LANE:
        raise ValueError(f"C={C} must be a multiple of {LANE}")
    kern = functools.partial(_kernel, S=S, K=K, KR=KR, n_objects=n_objects,
                             lookahead=lookahead, dist=dist, mean=mean,
                             hot_objects=hot_objects, hot_prob=hot_prob)
    B = OBJ_BLOCK
    row = lambda i, cnt, top: (i, 0)
    row3 = lambda i, cnt, top: (i, 0, 0)
    ev_block = pl.BlockSpec((B, C), row)

    def call(payload, addresses, top, ts, seed, cnt):
        n = payload.shape[0]
        if n % B:
            raise ValueError(f"n={n} must be a multiple of {B}")
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // B,),
            in_specs=[pl.BlockSpec((B, C), row, memory_space=pltpu.SMEM),
                      ev_block, ev_block,
                      pl.BlockSpec((B, LANES, S), row3),
                      pl.BlockSpec((B, S), row)],
            out_specs=[pl.BlockSpec((B, LANES, S), row3),
                       pl.BlockSpec((B, S), row)] + [ev_block] * 5)
        out_shape = [
            jax.ShapeDtypeStruct((n, LANES, S), jnp.float32),
            jax.ShapeDtypeStruct((n, S), jnp.int32),
            jax.ShapeDtypeStruct((n, C), jnp.int32),
            jax.ShapeDtypeStruct((n, C), jnp.float32),
            jax.ShapeDtypeStruct((n, C), jnp.uint32),
            jax.ShapeDtypeStruct((n, C), jnp.float32),
            jax.ShapeDtypeStruct((n, C), jnp.int32),
        ]
        # operand indices count the two scalar-prefetch operands: payload
        # and addresses (operands 5, 6) are updated in place.
        return pl.pallas_call(
            kern, grid_spec=grid_spec, out_shape=out_shape,
            input_output_aliases={5: 0, 6: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=interpret,
        )(cnt, top, seed, ts, seed, payload, addresses)

    return call
