"""Serving layer: cache shardings helper + ServeSession end-to-end."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.data.synthetic import make_batch
from repro.models.registry import build_model
from repro.serve.engine import ServeSession, cache_shardings


def test_serve_session_greedy_decode_is_deterministic():
    cfg = get_config("granite-3-2b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = make_batch(cfg, 2, 16)

    s1 = ServeSession(model, params, 2, max_len=32, dtype=np.float32)
    f1 = s1.prefill(batch)
    o1 = s1.decode(f1, 6)

    s2 = ServeSession(model, params, 2, max_len=32, dtype=np.float32)
    f2 = s2.prefill(batch)
    o2 = s2.decode(f2, 6)
    np.testing.assert_array_equal(o1, o2)
    assert o1.shape == (2, 6)


def test_cache_shardings_pick_batch_and_model_dims():
    # production-mesh geometry without devices (AbstractMesh)
    from jax.sharding import AbstractMesh
    mesh = AbstractMesh((16, 16), ("data", "model"))
    cache = {"k": jax.ShapeDtypeStruct((32, 128, 4, 64), jnp.bfloat16),
             "h": jax.ShapeDtypeStruct((32, 16, 64), jnp.float32)}
    sh = cache_shardings(cache, mesh, batch_size=32)

    def norm(e):
        return tuple(e) if isinstance(e, tuple) else (e,)

    # batch dim (size 32, divisible by data=16) shards over data
    assert norm(sh["k"].spec[0]) == ("data",)
    assert norm(sh["h"].spec[0]) == ("data",)
    # the largest divisible non-batch dim (seq=128) gets "model"
    assert sh["k"].spec[1] == "model"
    # h: largest divisible dim is 64 (dim 2); 16 would also divide
    assert sh["h"].spec[2] == "model"
