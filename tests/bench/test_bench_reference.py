"""The benchmark's plain reference (``bench/oracle.py`` and
``bench/reference/``) against the program's own numpy oracle, and the control
that ``correct`` must reject."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import check, oracle  # noqa: E402
from bench.reference import phold as ref_phold  # noqa: E402

PHOLD = dict(n_objects=16, initial_events=4, state_nodes=64,
             realloc_fraction=0.02, lookahead=0.5, dist="dyadic")
# a stopped simulation's in-flight multiset, read back empty: the comparison
# then holds the stand-in's in-flight events against the reference's.
NONE_IN_FLIGHT = np.zeros((0, 2), np.uint64)
CASES = [
    ("phold", PHOLD, ref_phold, 24, 0),
    ("phold", dict(PHOLD, hot_objects=4, hot_prob=96), ref_phold, 24,
     4_000_000_000),
]


@pytest.mark.parametrize("workload,kw,mod,n_epochs,seed", CASES)
def test_reference_matches_program_oracle(workload, kw, mod, n_epochs, seed):
    from repro.core.ref_engine import run_sequential
    from repro.workloads.registry import get_workload

    want = run_sequential(get_workload(workload, **kw), n_epochs, 0.5,
                          seed=seed)
    got = oracle.run(mod.Model(**kw),
                     np.float32(n_epochs) * np.float32(0.5), seed)
    assert got.committed == want.total_processed > 0
    np.testing.assert_array_equal(got.pending, want.pending_sorted())
    for k, leaf in got.state.items():
        ref = np.stack([np.asarray(s[k]) for s in want.obj_state])
        assert leaf.dtype == ref.dtype, k
        np.testing.assert_array_equal(leaf, ref, err_msg=k)


def test_bfloat16_rounding():
    import ml_dtypes

    x = np.array([1.0, 1.00390625, 1.005859375, 3.0e-3, -2.5, 1e30,
                  7.1234e-5], np.float32)
    want = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    np.testing.assert_array_equal(oracle.to_bfloat16(x), want)
    assert oracle.to_bfloat16(1.005859375) == 1.0078125


@pytest.mark.parametrize("kw,n_epochs", [
    (PHOLD, 24), (dict(PHOLD, initial_events=8), 16)])
def test_control_fails_the_comparison(kw, n_epochs):
    """The reference at bfloat16 in the program's place reads above every
    limit of 0 on at least one number, for every seed tried."""
    model = check.reference_model("phold", kw)
    for seed in (1, 2, 3):
        sims = [check.Sim(seed, n_epochs, 0, {}, NONE_IN_FLIGHT)]
        gaps = check.compare(sims, model, 0.5, rnd=oracle.to_bfloat16,
                             stand_in=model)
        assert any(gaps[k] > check.LIMITS[k] for k in gaps), gaps


def test_sound_stand_in_passes():
    model = check.reference_model("phold", PHOLD)
    gaps = check.compare([check.Sim(5, 24, 0, {}, NONE_IN_FLIGHT)], model, 0.5,
                         stand_in=model)
    assert gaps == {"committed_gap": 0, "in_flight_gap": 0, "state_gap": 0}
