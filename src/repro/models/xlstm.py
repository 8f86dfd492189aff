"""xLSTM (arXiv:2405.04517): mLSTM + sLSTM blocks, 7:1 interleave.

TPU adaptation notes:
  * mLSTM's matrix memory is computed in *chunkwise-parallel* form — the same
    matmul-rich reorganization as Mamba-2's SSD — instead of a per-step scan:
    per chunk, intra-chunk gated attention + inter-chunk state passing.  This
    is the MXU-friendly form; the per-step recurrence is used only for decode.
  * sLSTM is inherently sequential (recurrent R matrices); it runs as a
    lax.scan over time with small per-head state — acceptable because only 1
    in 8 blocks is sLSTM and its state is O(d).
  * exponential gating is realized in the stabilized log-domain for the decay
    (cumulative log-sigmoid forget gates); input gates use sigmoid (stabilized
    variant) — recorded as a simplification.

States are O(1) in sequence length → this family runs the long_500k shape.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .layers import dense_init, dt_of, embed, init_embed, init_norm, norm, unembed


# -- chunkwise gated linear attention (the mLSTM core) ---------------------------

def gated_chunk(q, k, v, logf, ig, *, chunk: int, state=None,
                compute_bf16: bool = False):
    """q,k: [B,T,H,dk]; v: [B,T,H,dv]; logf, ig: [B,T,H] (logf<=0, ig>=0).

    y_t = q_t · S_t,   S_t = exp(logf_t)·S_{t-1} + ig_t·k_t v_t^T
    Returns (y [B,T,H,dv], final_state [B,H,dk,dv])."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    Q = min(chunk, T)
    assert T % Q == 0
    nc = T // Q
    scale = 1.0 / math.sqrt(dk)

    cdt = jnp.bfloat16 if compute_bf16 else jnp.float32
    qc = q.astype(cdt).reshape(B, nc, Q, H, dk)
    kc = k.astype(cdt).reshape(B, nc, Q, H, dk)
    vc = v.astype(cdt).reshape(B, nc, Q, H, dv)
    fc = logf.astype(jnp.float32).reshape(B, nc, Q, H)
    ic = ig.astype(jnp.float32).reshape(B, nc, Q, H)

    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)

    def per_chunk(S, inp):
        qb, kb, vb, fb, ib = inp            # [B,Q,H,*]
        L = jnp.cumsum(fb.astype(jnp.float32), axis=1)  # [B,Q,H] inclusive
        # intra-chunk: decay exp(L_i - L_j) for i >= j
        dmat = jnp.exp(L[:, :, None, :] - L[:, None, :, :])       # [B,Q,Q,H]
        dmat = jnp.where((ii >= jj)[None, :, :, None], dmat, 0.0)
        att = jnp.einsum("bihd,bjhd->bijh", qb, kb,
                         preferred_element_type=jnp.float32) * scale
        g = (att * dmat * ib[:, None, :, :]).astype(qb.dtype)
        y = jnp.einsum("bijh,bjhv->bihv", g, vb,
                       preferred_element_type=jnp.float32)
        # inter-chunk: inherited state decayed to position i
        y = y + jnp.einsum("bihd,bih,bhdv->bihv", qb, jnp.exp(L).astype(qb.dtype),
                           S, preferred_element_type=jnp.float32) * scale
        # state update
        w = (jnp.exp(L[:, -1:, :] - L) * ib).astype(qb.dtype)     # [B,Q,H]
        S = S * jnp.exp(L[:, -1, :])[:, :, None, None] \
            + jnp.einsum("bjh,bjhd,bjhv->bhdv", w, kb, vb,
                         preferred_element_type=jnp.float32)
        return S, y

    S0 = (jnp.zeros((B, H, dk, dv), jnp.float32) if state is None
          else state.astype(jnp.float32))
    S, ys = jax.lax.scan(per_chunk, S0,
                         (qc.transpose(1, 0, 2, 3, 4),
                          kc.transpose(1, 0, 2, 3, 4),
                          vc.transpose(1, 0, 2, 3, 4),
                          fc.transpose(1, 0, 2, 3),
                          ic.transpose(1, 0, 2, 3)))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(B, T, H, dv)
    return y.astype(q.dtype), S


def gated_step(q, k, v, logf, ig, state, scale):
    """Single-token recurrence (decode).  q,k,v: [B,1,H,d*]."""
    S = state * jnp.exp(logf[:, 0])[..., None, None] \
        + jnp.einsum("bh,bhd,bhv->bhdv", ig[:, 0], k[:, 0].astype(jnp.float32),
                     v[:, 0].astype(jnp.float32))
    y = jnp.einsum("bhd,bhdv->bhv", q[:, 0].astype(jnp.float32), S) * scale
    return y[:, None].astype(q.dtype), S


# -- blocks -----------------------------------------------------------------------

def init_mlstm_block(cfg, key):
    d = cfg.d_model
    di = 2 * d
    H = cfg.n_heads
    dh = di // H
    ks = jax.random.split(key, 7)
    return {
        "ln": init_norm(d, cfg.norm),
        "wup": dense_init(ks[0], (d, 2 * di)),          # x_in, z gate
        "wq": dense_init(ks[1], (di, di)),
        "wk": dense_init(ks[2], (di, di)),
        "wv": dense_init(ks[3], (di, di)),
        "wif": dense_init(ks[4], (di, 2 * H), scale=0.02),
        "out_norm": init_norm(di, "rms"),
        "wdown": dense_init(ks[5], (di, d), scale=1.0 / math.sqrt(di)),
    }


def mlstm_apply(cfg, p, x, state=None, decode=False):
    B, T, d = x.shape
    di = 2 * d
    H = cfg.n_heads
    dh = di // H
    cdt = dt_of(cfg)
    h = norm(p["ln"], x, cfg.norm, cfg.norm_eps)
    up = h @ p["wup"].astype(cdt)
    xin, z = up[..., :di], up[..., di:]
    q = (xin @ p["wq"].astype(cdt)).reshape(B, T, H, dh)
    k = (xin @ p["wk"].astype(cdt)).reshape(B, T, H, dh)
    v = (xin @ p["wv"].astype(cdt)).reshape(B, T, H, dh)
    gates = xin @ p["wif"].astype(cdt)
    ig = jax.nn.sigmoid(gates[..., :H].astype(jnp.float32))
    logf = jax.nn.log_sigmoid(gates[..., H:].astype(jnp.float32))

    if decode:
        y, S = gated_step(q, k, v, logf, ig, state, 1.0 / math.sqrt(dh))
    else:
        y, S = gated_chunk(q, k, v, logf, ig, chunk=cfg.mlstm_chunk,
                           state=state,
                           compute_bf16=getattr(cfg, "mlstm_bf16", False))
    y = y.reshape(B, T, di)
    y = norm(p["out_norm"], y, "rms", cfg.norm_eps)
    y = y * jax.nn.silu(z)
    return x + y @ p["wdown"].astype(cdt), S


def init_slstm_block(cfg, key):
    d = cfg.d_model
    H = cfg.n_heads
    dh = d // H
    ks = jax.random.split(key, 3)
    return {
        "ln": init_norm(d, cfg.norm),
        "wx": dense_init(ks[0], (d, 4 * d)),            # z, i, f, o
        "r": dense_init(ks[1], (H, dh, 4 * dh), scale=1.0 / math.sqrt(dh)),
        "wout": dense_init(ks[2], (d, d), scale=1.0 / math.sqrt(d)),
    }


def slstm_apply(cfg, p, x, state=None, decode=False):
    """Sequential sLSTM with per-head recurrence.  state: (c, n, h) [B,H,dh]."""
    B, T, d = x.shape
    H = cfg.n_heads
    dh = d // H
    cdt = dt_of(cfg)
    inp = norm(p["ln"], x, cfg.norm, cfg.norm_eps)
    pre = (inp @ p["wx"].astype(cdt)).reshape(B, T, H, 4 * dh).astype(jnp.float32)
    r = p["r"]

    if state is None:
        c0 = jnp.zeros((B, H, dh), jnp.float32)
        n0 = jnp.full((B, H, dh), 1e-6, jnp.float32)
        h0 = jnp.zeros((B, H, dh), jnp.float32)
    else:
        c0, n0, h0 = state

    def step(carry, xt):
        c, n, h = carry
        g = xt + jnp.einsum("bhd,hdk->bhk", h, r)
        z, i, f, o = jnp.split(g, 4, axis=-1)
        z, i, f, o = jnp.tanh(z), jax.nn.sigmoid(i), jax.nn.sigmoid(f), \
            jax.nn.sigmoid(o)
        c = f * c + i * z
        n = f * n + i
        h = o * c / jnp.maximum(n, 1e-6)
        return (c, n, h), h

    (c, n, h), hs = jax.lax.scan(step, (c0, n0, h0), pre.transpose(1, 0, 2, 3))
    y = hs.transpose(1, 0, 2, 3).reshape(B, T, d).astype(cdt)
    return x + y @ p["wout"].astype(cdt), (c, n, h)


# -- full model ---------------------------------------------------------------------

class XLSTM:
    def __init__(self, cfg):
        self.cfg = cfg

    def _kinds(self):
        cfg = self.cfg
        k = cfg.slstm_every or 8
        return ["s" if (i % k == k - 1) else "m" for i in range(cfg.n_layers)]

    def init(self, key):
        cfg = self.cfg
        params = {"embed": init_embed(cfg, key),
                  "final_norm": init_norm(cfg.d_model, cfg.norm)}
        keys = jax.random.split(jax.random.fold_in(key, 7), cfg.n_layers)
        blocks = []
        for kind, k in zip(self._kinds(), keys):
            blocks.append(init_mlstm_block(cfg, k) if kind == "m"
                          else init_slstm_block(cfg, k))
        params["blocks"] = blocks
        from .layers import cast_params
        return cast_params(cfg, params)

    def _run(self, params, x, states, decode):
        cfg = self.cfg
        new_states = []
        for kind, bp, st in zip(self._kinds(), params["blocks"], states):
            if kind == "m":
                x, s = mlstm_apply(cfg, bp, x, st, decode)
            else:
                x, s = slstm_apply(cfg, bp, x, st, decode)
            new_states.append(s)
        x = norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
        return x, new_states

    def loss(self, params, batch):
        cfg = self.cfg
        x = embed(cfg, params["embed"], batch["tokens"])
        x, _ = self._run(params, x, [None] * cfg.n_layers, decode=False)
        logits = unembed(cfg, params["embed"], x)
        lp = jax.nn.log_softmax(logits, axis=-1)
        tgt = batch["tokens"][:, 1:]
        sel = jnp.take_along_axis(lp[:, :-1], tgt[..., None], axis=-1)[..., 0]
        return -jnp.mean(sel)

    def init_cache(self, batch_size: int, max_len: int, dtype=jnp.bfloat16):
        # recurrent: O(1) state per block — max_len is irrelevant (the point
        # of running long_500k on this family).
        cfg = self.cfg
        B = batch_size
        d = cfg.d_model
        H = cfg.n_heads
        dhm = (2 * d) // H
        dhs = d // H
        states = []
        for kind in self._kinds():
            if kind == "m":
                states.append(jnp.zeros((B, H, dhm, dhm), jnp.float32))
            else:
                states.append((jnp.zeros((B, H, dhs), jnp.float32),
                               jnp.full((B, H, dhs), 1e-6, jnp.float32),
                               jnp.zeros((B, H, dhs), jnp.float32)))
        return states

    def prefill(self, params, batch, caches):
        cfg = self.cfg
        x = embed(cfg, params["embed"], batch["tokens"])
        x, states = self._run(params, x, caches, decode=False)
        logits = unembed(cfg, params["embed"], x[:, -1:])
        return logits, states

    def decode_step(self, params, tokens, caches, cur_len):
        cfg = self.cfg
        x = embed(cfg, params["embed"], tokens)
        x, states = self._run(params, x, caches, decode=True)
        logits = unembed(cfg, params["embed"], x)
        return logits, states
