"""The per-object stack allocator (paper §II-C, Fig 1), vectorized.

PARSIR's allocator keeps, per object and chunk size, an ``addresses`` array of
deliverable chunk pointers and a ``top_elem`` cursor::

    alloc:  return addresses[top_elem++]
    free:   addresses[--top_elem] = addr

i.e. free chunks live at ``addresses[top : count)``.  We keep that discipline
verbatim over *indices* into a preallocated node arena (placement-by-sharding
replaces mmap+mbind — the arena array lives in the owning device's HBM by
construction).  All functions below operate on a single
object and are vmapped by the model; ``k`` is static.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Arena(NamedTuple):
    addresses: jax.Array  # i32 [n_nodes] — stack of free-chunk indices at [top:]
    top: jax.Array        # i32 scalar


def arena_init(n_nodes: int) -> Arena:
    """All nodes allocated: empty free region (top == count)."""
    return Arena(jnp.arange(n_nodes, dtype=jnp.int32),
                 jnp.asarray(n_nodes, jnp.int32))


def free_k(a: Arena, idxs: jax.Array) -> Arena:
    """Release k chunks: addresses[--top] = addr, vectorized."""
    k = idxs.shape[0]
    top2 = a.top - k
    pos = top2 + jnp.arange(k, dtype=jnp.int32)
    # paper order: successive frees push downward → last freed at lowest slot.
    return Arena(a.addresses.at[pos].set(idxs[::-1], mode="drop"), top2)


def alloc_k(a: Arena, k: int) -> tuple[Arena, jax.Array]:
    """Allocate k chunks: return addresses[top++], vectorized (LIFO)."""
    pos = a.top + jnp.arange(k, dtype=jnp.int32)
    vals = a.addresses[jnp.clip(pos, 0, a.addresses.shape[0] - 1)]
    return Arena(a.addresses, a.top + k), vals


# numpy mirror (sequential oracle) -------------------------------------------

def arena_init_np(n_nodes: int):
    return np.arange(n_nodes, dtype=np.int32), np.int32(n_nodes)


def free_k_np(addresses, top, idxs):
    k = len(idxs)
    top2 = top - k
    addresses[top2:top2 + k] = np.asarray(idxs, np.int32)[::-1]
    return addresses, np.int32(top2)


def alloc_k_np(addresses, top, k):
    vals = addresses[top:top + k].copy()
    return addresses, np.int32(top + k), vals
