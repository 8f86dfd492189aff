"""StealPolicy stage implementations (paper §II-A).

``none`` — stage 2 is a no-op: the scheduler processes exactly the local
           extract.
``loan`` — epoch-granular batch loans: overloaded devices publish their
           hottest objects' (state + current-epoch batch); a deterministic
           plan computed replicated from the gathered load vector assigns
           each loan to an underloaded receiver; receivers process loans
           alongside their own batches and return the updated state.
           Ownership (calendars, future insertions) never moves.

The combinatorial loan math (donor selection, replicated planning) stays in
:mod:`repro.core.stealing`; this module is the pipeline-facing policy that
wires it around the processing stage.  The loan path processes through the
rounds-family schedulers (dense ``batch`` rounds or the width-packed tiles)
— loaned batches are concatenated onto the local extract as extra rows,
which a model-specific whole-batch kernel can't ingest (EngineConfig fails
fast on that combination).

Composition with speculation (``opt_window > 0``, pipeline/speculate.py):
loans run inside speculative sub-epochs too, but ONLY under the global
all-or-nothing vote (``opt_commit='global'``).  A loaned batch executes on
the *borrower*: its staged emissions sit in the borrower's staging buffer
and would commit with the borrower's verdict, while a straggler at the
*owner* re-executes the same batch after rollback — a per-device verdict
could deliver those emissions twice.  The global vote makes every window
atomic across devices, so the loan's emissions exist exactly once whichever
branch runs.  EngineConfig rejects ``steal=True`` with
``opt_commit='device'`` fail-fast.  The ``all_gather``s below are legal
inside the speculation stage's ``lax.cond`` because the window predicate is
replicated — every device takes the same branch in the same iteration.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import stealing as steal_mod
from .base import AXIS, StealPolicy, register_steal_policy


@register_steal_policy("none")
class NoSteal(StealPolicy):
    """Process the local extract as-is (also used whenever D == 1)."""

    def process(self, model, scheduler, cfg, placement, dev, obj, ts_s,
                seed_s, pay_s, cnt_b):
        obj, out_flat, lv, rounds = scheduler.process(
            model, cfg, obj, ts_s, seed_s, pay_s, cnt_b)
        return obj, out_flat, lv, jnp.int32(0), jnp.sum(cnt_b), rounds


@register_steal_policy("loan")
class LoanSteal(StealPolicy):
    """Publish loans, claim, process augmented batches, return state."""

    def process(self, model, scheduler, cfg, placement, dev, obj, ts_s,
                seed_s, pay_s, cnt_b):
        # loans ride the rounds-family schedulers (see module docstring);
        # EngineConfig fails fast if steal=True is combined with a scheduler
        # that can't ingest the loan-augmented rows.
        D = placement.n_devices
        boundaries = jnp.asarray(placement.boundaries, jnp.int32)

        load = jnp.sum(cnt_b)
        loads = jax.lax.all_gather(load, AXIS)                     # [D]
        total = jnp.sum(loads)
        target = (total + D - 1) // D

        top_idx, top_w, loan_valid = steal_mod.select_loans(
            cnt_b, load, target, cfg.steal_cap)

        pub = {
            "state": steal_mod.gather_rows(obj, top_idx),
            "ts": ts_s[top_idx], "seed": seed_s[top_idx],
            "pay": pay_s[top_idx],
            "cnt": top_w, "gid": top_idx + boundaries[dev],
            "valid": loan_valid,
        }
        pub_g = jax.tree.map(lambda x: jax.lax.all_gather(x, AXIS), pub)

        plan = steal_mod.plan_loans(loads, pub_g["cnt"], pub_g["valid"],
                                    cfg.claim_cap)

        # donor side: claimed loans are processed remotely — zero them here.
        own_claimed = plan.claimed.reshape(D, cfg.steal_cap)[dev]
        cnt_b = cnt_b.at[top_idx].set(
            jnp.where(own_claimed & loan_valid, 0, cnt_b[top_idx]))

        # receiver side: compact my claims into claim_cap rows.
        claim_mask = plan.claimed & (plan.assignee == dev)         # [D*sc]
        corder = jnp.argsort(~claim_mask, stable=True)[:cfg.claim_cap]
        cvalid = claim_mask[corder]
        flat = lambda l: l.reshape((D * cfg.steal_cap,) + l.shape[2:])
        cl_state = jax.tree.map(lambda l: flat(l)[corder], pub_g["state"])
        cl_ts = flat(pub_g["ts"])[corder]
        cl_seed = flat(pub_g["seed"])[corder]
        cl_pay = flat(pub_g["pay"])[corder]
        cl_cnt = jnp.where(cvalid, flat(pub_g["cnt"])[corder], 0)
        cl_gid = flat(pub_g["gid"])[corder]

        n_local = cnt_b.shape[0]
        obj_aug = jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=0),
                               obj, cl_state)
        ts_aug = jnp.concatenate([ts_s, cl_ts], axis=0)
        seed_aug = jnp.concatenate([seed_s, cl_seed], axis=0)
        pay_aug = jnp.concatenate([pay_s, cl_pay], axis=0)
        cnt_aug = jnp.concatenate([cnt_b, cl_cnt], axis=0)

        # the augmented pass is the device's only pass: its rounds cover
        # the local rows and the claimed loans together.
        obj_aug, out_flat, lv, rounds = scheduler.process(
            model, cfg, obj_aug, ts_aug, seed_aug, pay_aug, cnt_aug)
        obj = jax.tree.map(lambda l: l[:n_local], obj_aug)
        ret_state = jax.tree.map(lambda l: l[n_local:], obj_aug)

        ret = {"state": ret_state, "gid": cl_gid, "valid": cvalid}
        ret_g = jax.tree.map(lambda x: jax.lax.all_gather(x, AXIS), ret)
        rgid = ret_g["gid"].reshape(-1)
        rvalid = ret_g["valid"].reshape(-1)
        rmine = rvalid & (placement.owner(rgid) == dev)
        lidx = jnp.clip(rgid - boundaries[dev], 0, n_local - 1)
        rstate = jax.tree.map(lambda l: l.reshape((-1,) + l.shape[2:]),
                              ret_g["state"])
        obj = steal_mod.scatter_rows(obj, lidx, rstate, rmine)

        proc_count = jnp.sum(cnt_b) + jnp.sum(cl_cnt)
        return (obj, out_flat, lv, jnp.sum(cvalid.astype(jnp.int32)),
                proc_count, rounds)
