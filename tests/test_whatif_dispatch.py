"""The replay dispatcher's ladder, pinned end to end.

For every combination of ``backend``, ``batched`` and ``compact`` the
sweep decides which configs replay on the IR (NumPy ``compact`` or the
``jax`` backend) and which on the row path (``row_batched`` or
``row_serial``), and counts every step down. These tests pin the counter
deltas of each decision and check that every path gives the row-exact
answer: times and counts equal, energies and penalties within 1e-9.
"""
import tempfile

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import repro.obs as obs
from repro.cluster import generate_cluster
from repro.core.controller import ControllerConfig, DownscaleMode
from repro.core.imbalance import PoolConfig, PoolPolicy
from repro.telemetry import TelemetryStore
from repro.telemetry.records import TelemetryFrame
from repro.whatif import (CompositePolicy, DownscalePolicy, NoOpPolicy,
                          ParkingPolicy, PowerCapPolicy, default_policy_grid,
                          run_sweep)
from repro.whatif import backend as B

EXACT_FIELDS = ("name", "params", "n_jobs", "wake_events",
                "downscale_events", "throttled_time_s")
FLOAT_FIELDS = ("baseline_energy_j", "counterfactual_energy_j",
                "energy_saved_j", "saved_fraction", "penalty_s",
                "penalty_fraction", "exec_idle_energy_fraction_baseline",
                "exec_idle_energy_fraction_cf")
PATHS = ("jax", "compact", "row_batched", "row_serial")
REASONS = ("ir_unsupported", "device_oom")
KW = {"min_job_duration_s": 0.0}


def family_grid():
    """Every IR-capable family, including the parking+downscale composite."""
    park = ParkingPolicy(pool=PoolConfig(n_devices=4,
                                         policy=PoolPolicy.CONSOLIDATED,
                                         n_active=2),
                         resume_latency_s=12.0)
    return default_policy_grid(dense=False) + [
        CompositePolicy((park, DownscalePolicy())),
        CompositePolicy((park, DownscalePolicy(config=ControllerConfig(
            threshold_x_s=3.0, cooldown_y_s=9.0,
            mode=DownscaleMode.SM_AND_MEM)))),
    ]


def foreign_grid():
    """The family grid with one config the IR cannot host (a foreign
    low-activity threshold) in its middle, so outcomes must come back
    merged in input order."""
    grid = family_grid()
    mid = len(grid) // 2
    return grid[:mid] + [DownscalePolicy(config=ControllerConfig(
        activity_threshold=0.03))] + grid[mid:]


GRIDS = {"family": family_grid, "foreign": foreign_grid}


@pytest.fixture(scope="module")
def store():
    with tempfile.TemporaryDirectory() as d:
        s = TelemetryStore(d, shard_format="npy_dir")
        generate_cluster(n_devices=6, horizon_s=1500, seed=7, store=s,
                         shard_s=500)
        yield TelemetryStore(d)


@pytest.fixture(scope="module")
def irregular_store():
    """Two policies over one stream with a sampling gap: the IR refuses
    the store, so every config replays on the row path."""
    frame = TelemetryFrame.from_rows([
        {"timestamp": float(t), "job_id": 1, "program_resident": 1,
         "power": 100.0, "sm": 50.0, "hostname": 0, "device_id": 0,
         "platform": 0}
        for t in (0.0, 1.0, 2.0, 5.0, 6.0)])      # gap at t=3,4
    with tempfile.TemporaryDirectory() as d:
        s = TelemetryStore(d)
        s.write_shard(frame, host="h0")
        yield s


@pytest.fixture(scope="module")
def references(store):
    """Row-exact frontiers (``compact=False``), one per grid."""
    return {name: run_sweep(store, make(), compact=False, **KW)
            for name, make in GRIDS.items()}


@pytest.fixture()
def counted():
    prev = obs.enabled()
    obs.enable()
    yield
    if not prev:
        obs.disable()


def _read() -> dict:
    reg = obs.REGISTRY
    out = {p: reg.total("repro_replay_configs_total", path=p) for p in PATHS}
    out["row_fallback"] = reg.total("repro_replay_row_fallback_configs_total")
    for r in REASONS:
        out[r] = reg.total("repro_fallbacks_total", reason=r)
    out["fallbacks"] = reg.total("repro_fallbacks_total")
    return out


def _delta(before: dict) -> dict:
    after = _read()
    return {k: after[k] - before[k] for k in after}


def _expected(**nonzero) -> dict:
    out = dict.fromkeys(PATHS + ("row_fallback",) + REASONS + ("fallbacks",),
                        0.0)
    out.update({k: float(v) for k, v in nonzero.items()})
    out["fallbacks"] = sum(out[r] for r in REASONS)
    return out


def _assert_same_answers(ref, got):
    assert got.n_rows == ref.n_rows
    assert len(got.outcomes) == len(ref.outcomes)
    for a, b in zip(ref.outcomes, got.outcomes):
        for f in EXACT_FIELDS:
            assert getattr(a, f) == getattr(b, f), (a.name, a.params, f)
        for f in FLOAT_FIELDS:
            assert np.isclose(getattr(a, f), getattr(b, f),
                              rtol=1e-9, atol=1e-9), (a.name, a.params, f)
        for f in ("per_job_saved_fraction", "per_job_penalty_s"):
            assert len(getattr(a, f)) == len(getattr(b, f))
            np.testing.assert_allclose(getattr(a, f), getattr(b, f),
                                       rtol=1e-9, atol=1e-9)


@pytest.mark.parametrize("grid", sorted(GRIDS))
@pytest.mark.parametrize("compact", [None, True, False])
@pytest.mark.parametrize("batched", [True, False])
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_ladder_paths_and_counters(store, references, counted, backend,
                                   batched, compact, grid):
    """``compact=None`` means the IR on jax and follows ``batched`` on
    NumPy; the IR's configs go to the backend's IR path, the rest (the
    foreign threshold) to the row path of ``batched``, counted as a row
    fallback; ``compact=False`` replays everything on rows."""
    configs = GRIDS[grid]()
    n_rest = 1 if grid == "foreign" else 0
    row = "row_batched" if batched else "row_serial"
    use_ir = compact if compact is not None else (backend == "jax"
                                                  or batched)
    before = _read()
    got = run_sweep(store, configs, batched=batched, compact=compact,
                    backend=backend, **KW)
    if use_ir:
        ir_path = "jax" if backend == "jax" else "compact"
        want = _expected(**{ir_path: len(configs) - n_rest},
                         row_fallback=n_rest)
        want[row] += n_rest
        assert got.n_runs > 0
    else:
        want = _expected(**{row: len(configs)})
        assert got.n_runs == 0
    assert _delta(before) == want
    _assert_same_answers(references[grid], got)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_irregular_store_steps_down_once(irregular_store, counted, backend):
    """A store the IR cannot hold is one ``compact -> row`` step down,
    counted once, whichever backend asked."""
    configs = [NoOpPolicy(), PowerCapPolicy(cap_fraction=0.5)]
    kw = {"min_job_duration_s": 0.0, "min_interval_s": 1.0}
    ref = run_sweep(irregular_store, configs, compact=False, **kw)
    before = _read()
    got = run_sweep(irregular_store, configs, backend=backend, **kw)
    assert _delta(before) == _expected(row_batched=2, ir_unsupported=1)
    assert got.n_runs == 0 and got.n_rows == 5
    _assert_same_answers(ref, got)


def test_device_oom_serial_rows_when_compact_unset(store, references,
                                                   counted, monkeypatch):
    """After a device out of memory the decision is made again as NumPy
    with the same arguments: ``batched=False, compact=None`` then replays
    every config on the serial row path."""
    def out_of_memory(*args, **kwargs):
        raise B.DeviceError("RESOURCE_EXHAUSTED: Out of memory allocating")
    monkeypatch.setattr(B, "replay_ir_outcomes", out_of_memory)
    configs = foreign_grid()
    before = _read()
    got = run_sweep(store, configs, batched=False, backend="jax", **KW)
    assert _delta(before) == _expected(row_serial=len(configs),
                                       device_oom=1)
    assert got.n_runs == 0
    _assert_same_answers(references["foreign"], got)
