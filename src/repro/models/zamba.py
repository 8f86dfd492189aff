"""Zamba2 hybrid (arXiv:2411.15242): Mamba-2 backbone + one weight-SHARED
attention block invoked every ``attn_every`` layers on concat(hidden, embed0).

The shared block attends at width 2*d_model (32 heads x 128 = 4096 for the
1.2B config) and projects back to d_model.  Each *invocation* gets its own KV
cache slot (weights shared, caches not) — allocated per invocation, not per
layer, so the long-context cache is ~6x smaller than a naive per-layer layout.
LoRA-per-invocation adapters from the paper are omitted.
Layers are a python loop (heterogeneous structure; 38 small blocks keep the
HLO manageable without scan).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .layers import (dense_init, dt_of, embed, init_embed, init_norm, norm,
                     rope, sdpa, unembed, _attn_masked_decode)
from .mamba2 import init_mamba_block, mamba_apply


def init_shared_attn(cfg, key):
    da = 2 * cfg.d_model
    hd = cfg.hd
    Hq, Hkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 7)
    return {
        "ln1": init_norm(da, cfg.norm),
        "wq": dense_init(ks[0], (da, Hq * hd)),
        "wk": dense_init(ks[1], (da, Hkv * hd)),
        "wv": dense_init(ks[2], (da, Hkv * hd)),
        "wo": dense_init(ks[3], (Hq * hd, da), scale=1.0 / math.sqrt(Hq * hd)),
        "ln2": init_norm(da, cfg.norm),
        "wg": dense_init(ks[4], (da, cfg.d_ff)),
        "wu": dense_init(ks[5], (da, cfg.d_ff)),
        "wd": dense_init(ks[6], (cfg.d_ff, da), scale=1.0 / math.sqrt(cfg.d_ff)),
        "wproj": dense_init(jax.random.fold_in(key, 9), (da, cfg.d_model),
                            scale=1.0 / math.sqrt(da)),
    }


def shared_attn_apply(cfg, p, h, e0, positions, cache=None, cur_len=None):
    """h: hidden [B,T,d]; e0: initial embeddings [B,T,d]."""
    B, T, d = h.shape
    hd, Hq, Hkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    cdt = dt_of(cfg)
    xa = jnp.concatenate([h, e0], axis=-1)               # [B,T,2d]
    y = norm(p["ln1"], xa, cfg.norm, cfg.norm_eps)
    q = (y @ p["wq"].astype(cdt)).reshape(B, T, Hq, hd)
    k = (y @ p["wk"].astype(cdt)).reshape(B, T, Hkv, hd)
    v = (y @ p["wv"].astype(cdt)).reshape(B, T, Hkv, hd)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    if cache is None:
        o = sdpa(cfg, q, k, v, causal=True)
        new_cache = None
    else:
        ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, cur_len, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, cur_len, 0, 0))
        if getattr(cfg, "decode_attn", "gather") == "sp":
            from .layers import _attn_decode_sp
            o = _attn_decode_sp(cfg, q, ck.astype(cdt), cv.astype(cdt),
                                cur_len + T)
        else:
            o = _attn_masked_decode(q, ck.astype(cdt), cv.astype(cdt),
                                    cur_len + T)
        new_cache = {"k": ck, "v": cv}
    xa = xa + (o.reshape(B, T, Hq * hd) @ p["wo"].astype(cdt))
    y = norm(p["ln2"], xa, cfg.norm, cfg.norm_eps)
    ff = jax.nn.silu(y @ p["wg"].astype(cdt)) * (y @ p["wu"].astype(cdt))
    xa = xa + ff @ p["wd"].astype(cdt)
    return h + xa @ p["wproj"].astype(cdt), new_cache


class Zamba:
    def __init__(self, cfg):
        self.cfg = cfg
        every = cfg.attn_every or 6
        self.attn_at = [i for i in range(cfg.n_layers) if i % every == 0]

    def init(self, key):
        cfg = self.cfg
        params = {"embed": init_embed(cfg, key),
                  "final_norm": init_norm(cfg.d_model, cfg.norm),
                  "shared_attn": init_shared_attn(cfg, jax.random.fold_in(key, 3))}
        keys = jax.random.split(jax.random.fold_in(key, 5), cfg.n_layers)
        params["blocks"] = [init_mamba_block(cfg, k) for k in keys]
        from .layers import cast_params
        return cast_params(cfg, params)

    def _run(self, params, x, positions, mamba_states, attn_caches, cur_len,
             decode):
        cfg = self.cfg
        e0 = x
        new_m, new_a = [], []
        inv = 0
        for i, bp in enumerate(params["blocks"]):
            if i in self.attn_at:
                cache = None if attn_caches is None else attn_caches[inv]
                x, nc = shared_attn_apply(cfg, params["shared_attn"], x, e0,
                                          positions, cache, cur_len)
                new_a.append(nc)
                inv += 1
            st = None if mamba_states is None else mamba_states[i]
            x, ns = mamba_apply(cfg, bp, x, st, decode)
            new_m.append(ns)
        x = norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
        return x, new_m, new_a

    def loss(self, params, batch):
        cfg = self.cfg
        x = embed(cfg, params["embed"], batch["tokens"])
        T = x.shape[1]
        positions = jnp.arange(T, dtype=jnp.int32)[None, :]
        x, _, _ = self._run(params, x, positions, None, None, None, False)
        logits = unembed(cfg, params["embed"], x)
        lp = jax.nn.log_softmax(logits, axis=-1)
        tgt = batch["tokens"][:, 1:]
        sel = jnp.take_along_axis(lp[:, :-1], tgt[..., None], axis=-1)[..., 0]
        return -jnp.mean(sel)

    def init_cache(self, batch_size: int, max_len: int, dtype=jnp.bfloat16):
        cfg = self.cfg
        B = batch_size
        W, C = cfg.ssm_conv, cfg.d_inner + 2 * cfg.ssm_state
        H, N, P = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim
        mamba = [{"conv": jnp.zeros((B, W - 1, C), dtype),
                  "h": jnp.zeros((B, H, N, P), jnp.float32)}
                 for _ in range(cfg.n_layers)]
        attn = [{"k": jnp.zeros((B, max_len, cfg.n_kv_heads, cfg.hd), dtype),
                 "v": jnp.zeros((B, max_len, cfg.n_kv_heads, cfg.hd), dtype)}
                for _ in self.attn_at]
        return {"mamba": mamba, "attn": attn}

    def prefill(self, params, batch, caches):
        cfg = self.cfg
        x = embed(cfg, params["embed"], batch["tokens"])
        T = x.shape[1]
        positions = jnp.arange(T, dtype=jnp.int32)[None, :]
        x, new_m, new_a = self._run(params, x, positions, caches["mamba"],
                                    caches["attn"], jnp.int32(0), False)
        logits = unembed(cfg, params["embed"], x[:, -1:])
        return logits, {"mamba": new_m, "attn": new_a}

    def decode_step(self, params, tokens, caches, cur_len):
        cfg = self.cfg
        x = embed(cfg, params["embed"], tokens)
        positions = cur_len + jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]
        x, new_m, new_a = self._run(params, x, positions, caches["mamba"],
                                    caches["attn"], cur_len, True)
        logits = unembed(cfg, params["embed"], x)
        return logits, {"mamba": new_m, "attn": new_a}
