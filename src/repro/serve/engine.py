"""Serving: prefill + one-token decode step factories and cache shardings.

Cache sharding rule: the batch-sized dim shards over
("pod","data"); the largest remaining dim divisible by the "model" axis shards
over "model" — for GQA KV caches that is the sequence dim (context-parallel
cache) or the kv-head dim, for MLA the latent sequence, for SSM states the
feature dims.  This keeps every decode shape within per-device HBM.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def cache_shardings(cache_shape: Any, mesh: Mesh, batch_size: int):
    bnames = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bsize = int(np.prod([mesh.shape[n] for n in bnames])) if bnames else 1
    msize = mesh.shape.get("model", 1)

    def spec_for(leaf):
        spec = [None] * len(leaf.shape)
        bdim = None
        for i, s in enumerate(leaf.shape):
            if s == batch_size and bnames and s % bsize == 0:
                spec[i] = bnames
                bdim = i
                break
        if "model" in mesh.axis_names and msize > 1:
            cands = [(s, i) for i, s in enumerate(leaf.shape)
                     if i != bdim and s % msize == 0 and s >= msize]
            if cands:
                _, mdim = max(cands)
                spec[mdim] = "model"
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(spec_for, cache_shape)


def make_decode_step(model):
    def decode_step(params, tokens, caches, cur_len):
        return model.decode_step(params, tokens, caches, cur_len)
    return decode_step


def make_prefill(model):
    def prefill(params, batch, caches):
        return model.prefill(params, batch, caches)
    return prefill


class ServeSession:
    """Minimal batched serving loop (greedy), used by examples/serve_lm.py."""

    def __init__(self, model, params, batch_size: int, max_len: int,
                 dtype=jnp.bfloat16):
        self.model, self.params = model, params
        self.caches = model.init_cache(batch_size, max_len, dtype)
        self._decode = jax.jit(make_decode_step(model))
        self._prefill = jax.jit(make_prefill(model))
        self.cur_len = 0

    def prefill(self, batch):
        logits, self.caches = self._prefill(self.params, batch, self.caches)
        first = next(iter(batch.values()))
        self.cur_len = int(first.shape[1])
        return np.asarray(jnp.argmax(logits[:, -1], axis=-1))

    def decode(self, tokens_np, n_steps: int):
        toks = jnp.asarray(tokens_np, jnp.int32)[:, None]
        out = []
        for _ in range(n_steps):
            logits, self.caches = self._decode(self.params, toks, self.caches,
                                               jnp.int32(self.cur_len))
            toks = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            out.append(np.asarray(toks[:, 0]))
            self.cur_len += 1
        return np.stack(out, axis=1)
