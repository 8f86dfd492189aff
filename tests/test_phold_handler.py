"""PHOLD's JAX handler against its numpy mirror, one event at a time.

``Phold.process_event`` writes the touch window and the reallocated nodes as
masked dense updates; ``process_event_np`` writes them by index.  Both must
leave the same state bits and emit the same event, unbatched and under the
rounds scheduler's ``jax.vmap``, wherever the window and the reallocated
nodes fall.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import events as ev
from repro.phold import arena as ar
from repro.phold.model import Phold, PholdParams

# K = 8 touched nodes, KR = 6 reallocated: small, and KR > 1.
PARAMS = PholdParams(n_objects=16, state_nodes=256, realloc_fraction=0.02)
S, K, KR = PARAMS.state_nodes, PARAMS.touch, PARAMS.realloc_k


def _seed_with_start(start: int) -> np.uint32:
    """The first seed whose touch window begins at ``start``."""
    seeds = np.arange(1 << 16, dtype=np.uint32)
    hit = ev.fold_np(seeds, 0) % np.uint32(S - K + 1) == start
    return seeds[np.argmax(hit)]


def _object_state(g: int, rng, permuted: bool) -> dict:
    st = Phold(PARAMS).init_object_state_np(np.array([g]))[0]
    # a payload with distinct dyadic bits per node and lane
    st["payload"] = (rng.integers(0, 1024, st["payload"].shape)
                     .astype(np.float32) / np.float32(1024))
    if permuted:
        st["addresses"] = rng.permutation(S).astype(np.int32)
        st["top"] = np.int32(S - 4 * KR)   # a free region [top, S) to pop
    return st


CASES = {
    # (window start, permuted free stack whose pops bypass the frees)
    "window-at-0": (0, False),
    "window-at-S-K": (S - K, False),
    "got-outside-window": (S // 2, True),
    "got-inside-window": (S // 3, False),
}


@pytest.mark.parametrize("batched", [False, True], ids=["single", "vmap"])
@pytest.mark.parametrize("case", list(CASES))
def test_process_event_matches_numpy_mirror(case, batched, monkeypatch):
    start, permuted = CASES[case]
    if permuted:
        # frees leave the stack alone, so alloc pops nodes of the permuted
        # free region rather than the window's nodes it just pushed.
        monkeypatch.setattr(ar, "free_k", lambda a, idxs: a)
        monkeypatch.setattr(ar, "free_k_np", lambda a, top, idxs: (a, top))
    model = Phold(PARAMS)
    rng = np.random.default_rng(len(case))
    n = 5 if batched else 1
    seeds = [_seed_with_start(start)] + [
        np.uint32(s) for s in rng.integers(0, 2**32, n - 1, dtype=np.uint64)]
    states = [_object_state(g, rng, permuted) for g in range(n)]
    ts = rng.integers(0, 4096, n).astype(np.float32) / np.float32(64)

    if permuted:
        got = states[0]["addresses"][states[0]["top"]:][:KR]
        assert not np.any((got >= start) & (got < start + K))
    else:
        assert int(ev.fold_np(seeds[0], 0) % np.uint32(S - K + 1)) == start

    # JAX first: the mirror writes its states in place.
    stack = {k: jnp.asarray(np.stack([s[k] for s in states]))
             for k in states[0]}
    args = (jnp.asarray(ts), jnp.asarray(np.array(seeds, np.uint32)),
            jnp.zeros(n, jnp.float32))
    if batched:
        new, out = jax.jit(jax.vmap(model.process_event))(stack, *args)
    else:
        one = jax.tree.map(lambda x: x[0], (stack, *args))
        new, out = jax.jit(model.process_event)(*one)
        new, out = jax.tree.map(lambda x: x[None], (new, out))

    for i in range(n):
        want = model.process_event_np(states[i], ts[i], seeds[i], 0.0)
        for k in ("payload", "addresses", "top"):
            np.testing.assert_array_equal(
                np.asarray(new[k][i]).view(np.uint32 if k == "payload"
                                           else np.int32),
                np.asarray(states[i][k]).view(np.uint32 if k == "payload"
                                              else np.int32), err_msg=k)
        for k in ("dst", "ts", "seed", "payload"):
            got_k = np.asarray(getattr(out, k)[i, 0])
            assert got_k.tobytes() == np.asarray(want[k]).astype(
                got_k.dtype).tobytes(), k
        assert bool(out.valid[i, 0])


def test_rounds_handler_lowers_without_payload_scatter():
    # the CPU twin of tests/test_tpu_compile.py's Table II guard: the
    # vmapped handler indexes no row of the payload.
    from test_tpu_compile import payload_index_ops
    model = Phold(PARAMS)
    n = 8
    st = jax.eval_shape(lambda: jax.tree.map(
        jnp.asarray, model.init_object_state(np.arange(n))))
    f32 = jax.ShapeDtypeStruct((n,), jnp.float32)
    u32 = jax.ShapeDtypeStruct((n,), jnp.uint32)
    hlo = jax.jit(jax.vmap(model.process_event)).lower(
        st, f32, u32, f32).as_text(dialect="hlo")
    assert payload_index_ops(hlo, n * S * PARAMS.lanes) == []
    # the parser does see indexed ops: the allocator's stack keeps its own.
    assert payload_index_ops(hlo, n * S)
