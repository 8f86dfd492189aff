"""Router stage implementations (paper §II-B).

``allgather`` — every device broadcasts its route buffer to everyone and each
                owner filters at delivery: the direct SPMD transliteration of
                PARSIR's shared-memory "any thread enqueues anywhere".
``a2a``       — the optimized pairwise exchange: per-destination-device
                sub-buffers of ``route_cap // D`` events through
                ``all_to_all``, D× less traffic than the broadcast.

Both degrade to an identity exchange on a single device; a2a additionally
falls back to global (first-come) selection there, since per-pair sub-buffers
only exist with a real exchange.  Selection never drops events silently:
whatever misses the route capacity is counted *and* handed back to the
caller's fallback buffer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..events import EventBatch, take_first
from .base import AXIS, Router, register_router


def _select_send_global(prod: EventBatch, eligible, cfg):
    """First-come selection: the first route_cap eligible events are sent."""
    rank = jnp.cumsum(eligible.astype(jnp.int32)) - 1
    send = eligible & (rank < cfg.route_cap)
    buf, ovf = take_first(prod, eligible, cfg.route_cap)
    return buf, send, ovf


@register_router("allgather")
class AllGatherRouter(Router):
    """Broadcast exchange — every device sees every route buffer."""

    replicated = True   # exchange() output is identical on every device

    def select_send(self, prod, eligible, placement, cfg):
        return _select_send_global(prod, eligible, cfg)

    def exchange(self, buf, placement, cfg):
        if placement.n_devices == 1:
            return buf
        g = jax.tree.map(lambda x: jax.lax.all_gather(x, AXIS), buf)
        return EventBatch(*(x.reshape(-1) for x in g))

    def sender_ids(self, placement, cfg):
        # broadcast layout: D stacked route buffers, route_cap slots each.
        D = placement.n_devices
        if D == 1:
            return jnp.zeros((cfg.route_cap,), jnp.int32)
        return jnp.repeat(jnp.arange(D, dtype=jnp.int32), cfg.route_cap)


@register_router("a2a")
class AllToAllRouter(Router):
    """Pairwise exchange with per-destination-device sub-buffers."""

    replicated = False  # each device receives a distinct routed slice

    def validate(self, cfg, placement):
        cfg.validate(placement.n_devices)

    def select_send(self, prod, eligible, placement, cfg):
        D = placement.n_devices
        if D == 1:
            return _select_send_global(prod, eligible, cfg)
        pair_cap = cfg.route_cap // D
        owner = placement.owner(prod.dst)
        key = jnp.where(eligible, owner, D)
        order = jnp.argsort(key, stable=True)
        ks = key[order]
        idx = jnp.arange(ks.shape[0], dtype=jnp.int32)
        is_start = jnp.concatenate([jnp.ones((1,), bool), ks[1:] != ks[:-1]])
        start_idx = jax.lax.associative_scan(jnp.maximum,
                                             jnp.where(is_start, idx, 0))
        rank = idx - start_idx
        ok = (ks < D) & (rank < pair_cap)
        ovf = jnp.sum(((ks < D) & ~ok).astype(jnp.int32))

        slot = jnp.where(ok, ks * pair_cap + rank, D * pair_cap)

        def put(field, fill, dtype):
            out = jnp.full((D * pair_cap,), fill, dtype)
            return out.at[slot].set(field[order], mode="drop")

        valid = jnp.zeros((D * pair_cap,), bool).at[slot].set(True,
                                                              mode="drop")
        buf = EventBatch(
            dst=put(prod.dst, 0, jnp.int32),
            ts=put(prod.ts, jnp.inf, jnp.float32),
            seed=put(prod.seed, 0, jnp.uint32),
            payload=put(prod.payload, 0.0, jnp.float32),
            valid=valid,
        )
        # sent mask back in original event order
        send = jnp.zeros_like(eligible).at[order].set(ok)
        return buf, send, ovf

    def exchange(self, buf, placement, cfg):
        D = placement.n_devices
        if D == 1:
            return buf
        pair_cap = cfg.route_cap // D
        shaped = jax.tree.map(lambda x: x.reshape(D, pair_cap), buf)
        recv = jax.tree.map(
            lambda x: jax.lax.all_to_all(x, AXIS, split_axis=0, concat_axis=0,
                                         tiled=True), shaped)
        return EventBatch(*(x.reshape(-1) for x in recv))

    def sender_ids(self, placement, cfg):
        # after all_to_all, dim 0 of the [D, pair_cap] view is the source.
        D = placement.n_devices
        if D == 1:
            return jnp.zeros((cfg.route_cap,), jnp.int32)
        return jnp.repeat(jnp.arange(D, dtype=jnp.int32), cfg.route_cap // D)
