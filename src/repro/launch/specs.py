"""ShapeDtypeStruct stand-ins for every (arch x shape) dry-run cell —
weak-type-correct, shardable, zero device allocation."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import SHAPES, ShapeConfig
from ..configs.registry import FULL_ATTENTION_ONLY, get_config
from ..data.synthetic import batch_spec
from ..models.registry import build_model


def cell_is_skipped(arch: str, shape_name: str) -> str | None:
    """Returns a skip reason or None."""
    if shape_name == "long_500k" and arch in FULL_ATTENTION_ONLY:
        return ("pure full-attention arch: 524k-token quadratic prefill is "
                "not representable without sub-quadratic attention")
    return None


def input_specs(arch: str, shape_name: str, overrides: dict | None = None):
    """Returns a dict describing what to lower for this cell:

    kind=train:   {params, opt_state, batch}
    kind=prefill: {params, batch, caches}
    kind=decode:  {params, tokens, caches, cur_len}

    overrides: ModelConfig field=value replacements (hillclimb variants);
    keys prefixed "train." are handled by the caller.
    """
    import dataclasses
    cfg = get_config(arch)
    model_over = {k: v for k, v in (overrides or {}).items()
                  if not k.startswith("train.") and not k.startswith("_")}
    if model_over:
        cfg = dataclasses.replace(cfg, **model_over)
    shape: ShapeConfig = SHAPES[shape_name]
    model = build_model(cfg)
    key_spec = jax.eval_shape(lambda: jax.random.key(0))
    params = jax.eval_shape(model.init, key_spec)

    if shape.kind == "train":
        from ..train import optimizer as opt
        batch = batch_spec(cfg, shape.global_batch, shape.seq_len)
        opt_state = jax.eval_shape(opt.init, params)
        return {"kind": "train", "cfg": cfg, "model": model, "params": params,
                "opt_state": opt_state, "batch": batch}

    if shape.kind == "prefill":
        caches = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len))
        batch = batch_spec(cfg, shape.global_batch, shape.seq_len)
        return {"kind": "prefill", "cfg": cfg, "model": model,
                "params": params, "batch": batch, "caches": caches}

    # decode: one new token against a cache of seq_len
    caches = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, shape.seq_len))
    if cfg.frontend == "audio":
        tokens = jax.ShapeDtypeStruct((shape.global_batch, 1, cfg.d_model),
                                      jnp.dtype(cfg.dtype))
    else:
        tokens = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    return {"kind": "decode", "cfg": cfg, "model": model, "params": params,
            "tokens": tokens, "caches": caches}
