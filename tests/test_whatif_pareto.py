"""``pareto_flags``: the sort-based pass against the all-pairs definition.

The oracle is the quadratic loop the pass replaced, kept here verbatim in
its comparisons: point ``j`` dominates ``i`` iff it is at least as good in
both coordinates and strictly better in one. The flags must equal it
element for element, as Python ``bool``s, on ties, duplicates, NaN, +-inf
and signed zeros.
"""
import math
import tempfile

import numpy as np
import pytest

import repro.obs as obs
from repro.cluster import generate_cluster
from repro.telemetry import TelemetryStore
from repro.whatif import default_policy_grid, pareto_flags, run_sweep
from repro.whatif import search as search_mod
from repro.whatif.search import find_knee

INF, NAN = math.inf, math.nan


def oracle_flags(saved, penalty):
    flags = []
    for i, (s_i, p_i) in enumerate(zip(saved, penalty)):
        dominated = any(
            (s_j >= s_i and p_j <= p_i) and (s_j > s_i or p_j < p_i)
            for j, (s_j, p_j) in enumerate(zip(saved, penalty))
            if j != i)
        flags.append(not dominated)
    return flags


def assert_flags_match(saved, penalty):
    got = pareto_flags(saved, penalty)
    assert isinstance(got, list)
    assert all(type(f) is bool for f in got)
    assert got == oracle_flags(saved, penalty)


@pytest.mark.parametrize("n,seed", [(0, 0), (1, 0), (2, 0), (2, 1), (3, 0),
                                    (4, 0), (7, 0), (16, 0), (16, 1),
                                    (33, 0), (60, 0), (60, 1), (60, 2)])
def test_random_integer_grids_match_all_pairs(n, seed):
    """Small integer ranges, so many equal savings and equal penalties."""
    rng = np.random.default_rng(seed)
    saved = rng.integers(0, 6, n).astype(float).tolist()
    penalty = rng.integers(0, 6, n).astype(float).tolist()
    assert_flags_match(saved, penalty)


CASES = {
    "empty": ([], []),
    "exact_duplicates": ([1.0, 1.0, 0.0, 0.0, 2.0, 2.0],
                         [1.0, 1.0, 2.0, 2.0, 3.0, 3.0]),
    "duplicates_dominated": ([1.0, 1.0, 2.0], [1.0, 1.0, 0.5]),
    "nan_saving": ([1.0, NAN, 2.0, 0.5], [1.0, 0.0, 2.0, 3.0]),
    "nan_penalty": ([1.0, 9.0, 2.0, 0.5], [1.0, NAN, 2.0, 3.0]),
    "nan_both": ([NAN, 1.0, 1.0], [NAN, 1.0, 2.0]),
    "all_nan": ([NAN, NAN], [NAN, NAN]),
    "pos_inf_saving": ([INF, 1.0, INF], [5.0, 0.0, 6.0]),
    "neg_inf_saving": ([-INF, 1.0, -INF], [0.0, 2.0, 3.0]),
    "pos_inf_penalty": ([5.0, 1.0, 5.0], [INF, 0.0, INF]),
    "neg_inf_penalty": ([0.0, 1.0, 0.0], [-INF, 2.0, -INF]),
    "neg_inf_saving_first_group_alone": ([-INF], [0.0]),
    "neg_inf_saving_first_group": ([-INF, -INF, -INF], [0.0, 0.0, 1.0]),
    "neg_inf_saving_beside_finite": ([-INF, 3.0, -INF], [0.0, 0.0, 1.0]),
    "inf_both_coordinates": ([INF, -INF, INF, 0.0], [INF, -INF, -INF, 0.0]),
    "signed_zeros": ([-0.0, 0.0, 1.0, 0.0], [0.0, -0.0, 1.0, 1.0]),
    "signed_zero_penalty_groups": ([1.0, 2.0, 2.0], [-0.0, 0.0, -0.0]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_edge_cases_match_all_pairs(name):
    saved, penalty = CASES[name]
    assert_flags_match(saved, penalty)


def test_duplicates_are_both_kept_and_nan_never_dominated():
    assert pareto_flags([1.0, 1.0], [2.0, 2.0]) == [True, True]
    assert pareto_flags([NAN, 5.0], [9.0, 0.0]) == [True, True]
    assert pareto_flags([0.0, 5.0, NAN], [9.0, 0.0, 0.0]) == \
        [False, True, True]


def _monotone_front(n, seed):
    """A front where more saving costs more penalty, in shuffled order."""
    perm = np.random.default_rng(seed).permutation(n)
    return (perm.astype(float) * 1.5).tolist(), (perm.astype(float) ** 2).tolist()


@pytest.mark.parametrize("dominated_point", [False, True])
def test_large_strictly_monotone_front(dominated_point):
    n = 10_000
    saved, penalty = _monotone_front(n, seed=7)
    expect = [True] * n
    if dominated_point:
        # (1.5 * 5000, 5000 ** 2) dominates it; it dominates nothing
        saved.insert(1234, 1.5 * 5000 - 0.25)
        penalty.insert(1234, 5000.0 ** 2 + 0.5)
        expect.insert(1234, False)
    got = pareto_flags(saved, penalty)
    assert all(type(f) is bool for f in got)
    assert got == expect


@pytest.mark.parametrize("n", [0, 3])
def test_one_span_per_call(n):
    prev = obs.enabled()
    obs.enable()
    obs.reset()
    try:
        pareto_flags([1.0] * n, [1.0] * n)
        spans = [s for s in obs.spans() if s.name == "whatif.pareto"]
    finally:
        obs.enable() if prev else obs.disable()
        obs.reset()
    assert [s.attrs for s in spans] == [{"n": n}]


@pytest.fixture(scope="module")
def jax_frontier():
    pytest.importorskip("jax")
    with tempfile.TemporaryDirectory() as d:
        store = TelemetryStore(d)
        generate_cluster(n_devices=8, horizon_s=2700, seed=3,
                         store=store, shard_s=900)
        yield run_sweep(store, default_policy_grid(), backend="jax",
                        min_job_duration_s=0.0)


def test_default_jax_sweep_flags_match_all_pairs(jax_frontier):
    outcomes = jax_frontier.outcomes
    assert len(outcomes) == 200
    expect = oracle_flags([o.energy_saved_j for o in outcomes],
                          [o.penalty_s for o in outcomes])
    assert [o.pareto for o in outcomes] == expect
    assert all(type(o.pareto) is bool for o in outcomes)


def test_find_knee_matches_all_pairs_front(jax_frontier, monkeypatch):
    knee = find_knee(jax_frontier.outcomes)
    monkeypatch.setattr(search_mod, "pareto_flags", oracle_flags)
    assert find_knee(jax_frontier.outcomes) == knee
