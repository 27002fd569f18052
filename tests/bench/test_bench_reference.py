"""The plain reference, its control and the faults that ``correct`` must
catch.

* The reference agrees with the program's NumPy oracle on a small fleet:
  every count exactly, every energy and penalty within 1e-9 relative, the
  Pareto flags and the knee exactly.
* The control, the reference computed in float32 in the program's place,
  reads ``correct`` false.
* A run with the timed path broken underneath reads ``correct`` false:
  an answer altered where it is produced, half of the streams left out.
"""
import dataclasses
import pathlib

import numpy as np
import pytest

from bench_copy import run_cell, small_copy

from bench import reference as R
from bench.harness import Compare, check_frontier, load_cell, passed


@pytest.fixture(scope="module")
def fleet_store(tmp_path_factory):
    from repro.cluster import generate_cluster
    from repro.telemetry import TelemetryStore

    root = tmp_path_factory.mktemp("fleet") / "store"
    store = TelemetryStore(root, shard_format="npy_dir")
    generate_cluster(n_devices=6, horizon_s=5400, seed=11, store=store,
                     shard_s=1800)
    return store


SEM = {"dt_s": 1.0, "min_interval_s": 5.0, "min_job_duration_s": 0.0,
       "activity_pct": 5.0, "link_gbs": 1.0}


def test_reference_agrees_with_the_numpy_oracle(fleet_store):
    from repro.whatif import default_policy_grid, run_sweep
    from repro.whatif.search import find_knee, search_frontier

    fleet = R.Fleet(R.read_store(fleet_store.root), SEM)
    grid = default_policy_grid()
    front = run_sweep(fleet_store, grid, compact=False,
                      min_job_duration_s=0.0)
    res = search_frontier(fleet_store, min_job_duration_s=0.0,
                          max_evals=60, max_rounds=2)
    for outcomes, specs, knee in (
            (front.outcomes, [p.describe() for p in grid],
             find_knee(front.outcomes).params),
            (res.frontier.outcomes, [o.params for o in res.frontier.outcomes],
             res.knee.params)):
        cmp = Compare()
        check_frontier(cmp, fleet, specs, outcomes, knee, "oracle")
        assert cmp.mismatches == 0, cmp.notes
        assert cmp.rel_err < 1e-12, cmp.worst
    assert any(o.name == "parking+downscale" for o in res.frontier.outcomes)


def test_float32_control_is_refused(fleet_store):
    from repro.whatif import default_policy_grid, run_sweep
    from repro.whatif.search import find_knee

    grid = default_policy_grid()
    front = run_sweep(fleet_store, grid, min_job_duration_s=0.0)
    cols = R.read_store(fleet_store.root)
    cmp = Compare()
    check_frontier(cmp, R.Fleet(cols, SEM), [p.describe() for p in grid],
                   front.outcomes, find_knee(front.outcomes).params,
                   "control", control=R.Fleet(cols, SEM, dtype=np.float32))
    assert cmp.rel_err > 1e-6
    assert not passed({"rel_err": {"value": cmp.rel_err, "limit": 1e-9}})


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return small_copy(tmp_path_factory.mktemp("bench"))


def test_control_readings_on_a_cell(copy):
    from bench import control

    cell = load_cell(copy, "acad192.grid200", 5, 0.5, False)
    try:
        rows = control.readings(cell, [5])
    finally:
        import repro.obs as obs
        obs.disable()
        obs.reset()
    # the program reads far under the limit (1e-9), the control far over
    assert rows[0]["program"]["rel_err"] < 1e-12
    assert rows[0]["control"]["rel_err"] > 1e-7


def _alter_one_answer(monkeypatch):
    from repro.whatif import backend

    real = backend.replay_ir_outcomes

    def altered(*args, **kwargs):
        outs, n_rows, n_runs = real(*args, **kwargs)
        i = len(outs) // 2
        outs[i] = dataclasses.replace(outs[i],
                                      penalty_s=outs[i].penalty_s * 1.000001
                                      + 1e-6)
        return outs, n_rows, n_runs

    monkeypatch.setattr(backend, "replay_ir_outcomes", altered)


def _drop_half_the_streams(monkeypatch):
    from repro.whatif.ir import RunIR

    real = RunIR.select
    monkeypatch.setattr(RunIR, "select",
                        lambda self, hosts=None: real(self, hosts)[::2])


@pytest.mark.parametrize("cell, fault", [
    ("acad192.grid200", _alter_one_answer),
    ("acad192.search", _alter_one_answer),
    ("acad192.grid200", _drop_half_the_streams),
    ("acad192.search", _drop_half_the_streams),
])
def test_broken_timed_path_is_not_correct(copy, monkeypatch, cell, fault):
    fault(monkeypatch)
    rc, res, err = run_cell(copy, "--workload", cell, "--seed", "3",
                            "--seconds", "1.2")
    assert rc == 0, err
    assert res["correct"] is False, err
