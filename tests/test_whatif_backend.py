"""JAX replay backend (ISSUE 6): NumPy-oracle equivalence, pack_ir
padding/bucketing properties, mesh-shape invariance, and the integrator
port.

The backend contract under test: **time and count metrics are
bit-identical** to the NumPy run-level replay (integer sample sums and
identical Algorithm-1 decision sequences), **energies and penalties agree
to <= 1e-9 relative** (float summation order differs), and results are
independent of padding bucket layout and of the config-axis mesh shape.
"""
import contextlib
import tempfile

import numpy as np
import pytest
from _hyp import given, settings, st

jax = pytest.importorskip("jax")

import repro.obs as obs
from repro.cluster import generate_cluster
from repro.core.controller import ControllerConfig, DownscaleMode
from repro.core.energy import integrate_runs
from repro.core.imbalance import PoolConfig, PoolPolicy
from repro.core.states import DEFAULT_CLASSIFIER
from repro.telemetry import TelemetryStore
from repro.telemetry.records import TelemetryFrame
from repro.whatif import (CompositePolicy, DownscalePolicy, IRConfig,
                          NoOpPolicy, ParkingPolicy, PowerCapPolicy,
                          build_ir, default_policy_grid, evaluate, get_ir,
                          run_sweep, search_frontier)
from repro.whatif import backend as B
from repro.whatif.ir import ir_config_for
from repro.whatif.policies import DownscaleBatch, _run_downscale
from repro.whatif.replay import _resolve_platform
from repro.whatif.sweep import resolve_backend

EXACT_FIELDS = ("name", "params", "n_jobs", "wake_events",
                "downscale_events", "throttled_time_s")
FLOAT_FIELDS = ("baseline_energy_j", "counterfactual_energy_j",
                "energy_saved_j", "saved_fraction", "penalty_s",
                "penalty_fraction", "exec_idle_energy_fraction_baseline",
                "exec_idle_energy_fraction_cf")


def assert_outcomes_equivalent(ref, cmp_, exact_energies=False):
    assert len(ref) == len(cmp_)
    for a, b in zip(ref, cmp_):
        for f in EXACT_FIELDS:
            assert getattr(a, f) == getattr(b, f), (a.name, a.params, f)
        for f in FLOAT_FIELDS:
            if exact_energies:
                assert getattr(a, f) == getattr(b, f), (a.name, a.params, f)
            else:
                assert np.isclose(getattr(a, f), getattr(b, f),
                                  rtol=1e-9, atol=1e-9), (a.name, a.params, f)
        for f in ("per_job_saved_fraction", "per_job_penalty_s"):
            if exact_energies:
                assert getattr(a, f) == getattr(b, f), (a.name, a.params, f)
            else:
                np.testing.assert_allclose(getattr(a, f), getattr(b, f),
                                           rtol=1e-9, atol=1e-9)


@contextlib.contextmanager
def jax_path_counted():
    """Run the body with obs on, then assert that the jax path replayed
    it: ``repro_replay_configs_total{path="jax"}`` grew and no ``jax ->
    numpy`` fallback was counted — equal answers from a silent NumPy run
    cannot pass."""
    prev = obs.enabled()
    obs.enable()
    jax_before = obs.REGISTRY.total("repro_replay_configs_total", path="jax")
    fb_before = obs.REGISTRY.total("repro_fallbacks_total", **{"from": "jax"})
    try:
        yield
        assert obs.REGISTRY.total("repro_replay_configs_total",
                              path="jax") > jax_before
        assert obs.REGISTRY.total("repro_fallbacks_total",
                              **{"from": "jax"}) == fb_before
    finally:
        if not prev:
            obs.disable()


@pytest.fixture(scope="module")
def store_dir():
    with tempfile.TemporaryDirectory() as d:
        store = TelemetryStore(d, shard_format="npy_dir")
        generate_cluster(n_devices=6, horizon_s=1500, seed=7, store=store,
                         shard_s=500)
        yield d


def _store(store_dir):
    return TelemetryStore(store_dir)


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


# --------------------------------------------------------------------------- #
# backend selection
# --------------------------------------------------------------------------- #
def test_resolve_backend():
    assert resolve_backend("numpy") == "numpy"
    assert resolve_backend("jax") == "jax"
    assert resolve_backend("auto") == "jax"      # jax is importable here
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend("tpu")


# --------------------------------------------------------------------------- #
# oracle equivalence: full family set, >= 2 mesh shapes
# --------------------------------------------------------------------------- #
def test_jax_matches_oracle_full_families_and_mesh_shapes(store_dir):
    store = _store(store_dir)
    grid = family_grid()
    ref = evaluate(grid, store, compact=True, min_job_duration_s=0.0)
    for dist in (None, B.config_mesh(1), B.config_mesh(4)):
        with jax_path_counted():
            out = evaluate(grid, store, backend="jax", dist=dist,
                           min_job_duration_s=0.0)
        assert_outcomes_equivalent(ref, out)


def test_jax_matches_oracle_interval_and_duration_variants(store_dir):
    store = _store(store_dir)
    grid = family_grid()
    for mjd, mis in ((300.0, 5.0), (0.0, 1.0), (0.0, 10.0)):
        ref = evaluate(grid, store, compact=True, min_job_duration_s=mjd,
                       min_interval_s=mis)
        with jax_path_counted():
            out = evaluate(grid, store, backend="jax",
                           min_job_duration_s=mjd, min_interval_s=mis)
        assert_outcomes_equivalent(ref, out)


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=3, deadline=None)
def test_jax_matches_oracle_random_grid_and_chunking(seed):
    """Random family mixes — including configs the IR cannot host, which
    the jax path must route through the NumPy row fallback — over random
    shard chunkings. run_sweep comparison also covers Pareto flags."""
    rng = np.random.default_rng(seed % 100000)
    grid = [NoOpPolicy()]
    for _ in range(int(rng.integers(1, 4))):
        grid.append(DownscalePolicy(config=ControllerConfig(
            threshold_x_s=float(rng.uniform(0.5, 8.0)),
            cooldown_y_s=float(rng.uniform(1.0, 10.0)),
            interval_eps_s=float(rng.choice([0.5, 1.0, 2.0])),
            mode=rng.choice([DownscaleMode.SM_ONLY,
                             DownscaleMode.SM_AND_MEM]))))
    n_dev = int(rng.choice([2, 4]))
    grid.append(ParkingPolicy(
        pool=PoolConfig(n_devices=n_dev, policy=PoolPolicy.CONSOLIDATED,
                        n_active=int(rng.integers(1, n_dev))),
        resume_latency_s=float(rng.uniform(2.0, 40.0))))
    for _ in range(int(rng.integers(1, 3))):
        grid.append(PowerCapPolicy(
            cap_fraction=float(rng.uniform(0.3, 0.9))))
    grid.append(CompositePolicy((
        ParkingPolicy(pool=PoolConfig(n_devices=4,
                                      policy=PoolPolicy.CONSOLIDATED,
                                      n_active=2),
                      resume_latency_s=float(rng.uniform(2.0, 30.0))),
        DownscalePolicy(config=ControllerConfig(
            threshold_x_s=float(rng.uniform(0.5, 8.0)))),
    )))
    if rng.random() < 0.5:
        # foreign low-activity threshold: IR-unsupported, row fallback
        grid.append(DownscalePolicy(config=ControllerConfig(
            activity_threshold=0.03)))
    order = rng.permutation(len(grid))
    grid = [grid[i] for i in order]
    shard_s = int(rng.choice([300, 700, 1500]))
    with tempfile.TemporaryDirectory() as d:
        store = TelemetryStore(d)
        generate_cluster(n_devices=4, horizon_s=1200,
                         seed=int(rng.integers(0, 100)),
                         store=store, shard_s=shard_s)
        ref = run_sweep(store, grid, min_job_duration_s=300.0)
        with jax_path_counted():
            cmp_ = run_sweep(store, grid, min_job_duration_s=300.0,
                             backend="jax")
        assert cmp_.n_rows == ref.n_rows and cmp_.n_runs == ref.n_runs
        assert_outcomes_equivalent(ref.outcomes, cmp_.outcomes)
        assert [o.pareto for o in ref.outcomes] == \
            [o.pareto for o in cmp_.outcomes]


def test_search_jax_matches_numpy_trajectory(store_dir):
    store = _store(store_dir)
    ref = search_frontier(store, min_job_duration_s=0.0)
    with jax_path_counted():
        out = search_frontier(store, min_job_duration_s=0.0, backend="jax")
    assert out.n_evals == ref.n_evals
    assert out.knee.params == ref.knee.params
    assert np.isclose(out.knee.saved_fraction, ref.knee.saved_fraction,
                      rtol=1e-9, atol=1e-12)


# --------------------------------------------------------------------------- #
# pack_ir properties: round-trip, padding isolation, retrace bounds
# --------------------------------------------------------------------------- #
def test_pack_ir_roundtrip_bit_identical(store_dir):
    from repro.core.power_model import ClockLevel

    store = _store(store_dir)
    ir = get_ir(store, ir_config_for([DownscalePolicy()]))
    min_samples = 5
    packed = B.pack_ir(ir, min_samples, min_job_duration_s=0.0)
    assert packed.n_streams == len(ir.select(None))
    views = packed.unpack()
    for s, plat, v in zip(packed.streams, packed.platforms, views):
        off, low_flags = s.controller_runs()
        low_j = np.flatnonzero(low_flags)
        np.testing.assert_array_equal(v["lr_s0"], off[low_j])
        np.testing.assert_array_equal(v["lr_len"],
                                      off[low_j + 1] - off[low_j])
        np.testing.assert_array_equal(
            v["lr_busy"],
            s.ts_first + s.dt_s * off[low_j + 1].astype(np.float64))
        np.testing.assert_array_equal(v["cum_res"], s.cum_resident())
        for j, (sm, mem) in enumerate(((ClockLevel.MIN, ClockLevel.MAX),
                                       (ClockLevel.MIN, ClockLevel.MIN))):
            delta = plat.exec_idle_w - plat.residency_floor_w(sm, mem)
            ce, ca = s.downscale_cums(float(delta), plat.deep_idle_w,
                                      min_samples)
            np.testing.assert_array_equal(v["ds_cum"][2 * j], ce)
            np.testing.assert_array_equal(v["ds_cum"][2 * j + 1], ca)
        cap = s.cap_buckets(min_samples)
        for st_key in (0, 1, 2):
            sp, top = v["cap_buckets"][st_key]
            np.testing.assert_array_equal(sp, cap[st_key][0])
            np.testing.assert_array_equal(top, cap[st_key][1])
        sp, top = v["cap_buckets"]["penalty"]
        np.testing.assert_array_equal(sp, cap["penalty"][0])
        np.testing.assert_array_equal(top, cap["penalty"][2])
        pk = s.parking_counterfactual(min_samples)
        np.testing.assert_array_equal(v["pk_state"], pk["cf_state"])
        np.testing.assert_array_equal(
            v["pk_energy"],
            pk["keep_sum"] + pk["idle_len"] * plat.deep_idle_w)
        np.testing.assert_array_equal(v["pk_len"], s.length)
        assert v["ts_first"] == s.ts_first
    # the pack is cached on the IR: same key, same object
    assert B.pack_ir(ir, min_samples, min_job_duration_s=0.0) is packed


def test_pack_ir_padding_never_leaks(store_dir):
    """Forcing every stream into one giant padding bucket (pad_floor
    crank) must leave outcomes EXACTLY identical — fired padding lanes
    would shift energies, counts, or CDFs."""
    store = _store(store_dir)
    grid = family_grid()
    ir = get_ir(store, ir_config_for(grid))
    ref, _, _ = B.replay_ir_outcomes(ir, grid, min_job_duration_s=0.0)
    big, _, _ = B.replay_ir_outcomes(ir, grid, min_job_duration_s=0.0,
                                     pad_floor=2048)
    packed_small = B.pack_ir(ir, 5, min_job_duration_s=0.0)
    packed_big = B.pack_ir(ir, 5, min_job_duration_s=0.0, pad_floor=2048)
    assert len(packed_big.buckets) <= len(packed_small.buckets)
    assert len(packed_big.buckets) == 1
    assert_outcomes_equivalent(ref, big, exact_energies=True)


def test_pack_ir_retrace_counts(store_dir):
    """Retraces stay bounded by the number of distinct padding buckets,
    and a repeat replay compiles nothing new."""
    store = _store(store_dir)
    grid = family_grid()
    ir = get_ir(store, ir_config_for(grid))
    before = dict(B.TRACE_COUNTS)
    B.replay_ir_outcomes(ir, grid, min_job_duration_s=0.0)
    packed = B.pack_ir(ir, 5, min_job_duration_s=0.0)
    after_first = dict(B.TRACE_COUNTS)
    n_buckets = len(packed.buckets)
    for name in ("downscale", "powercap", "integrate"):
        delta = after_first.get(name, 0) - before.get(name, 0)
        assert 0 <= delta <= n_buckets, (name, delta, n_buckets)
    B.replay_ir_outcomes(ir, grid, min_job_duration_s=0.0)
    assert dict(B.TRACE_COUNTS) == after_first


# --------------------------------------------------------------------------- #
# integrator port
# --------------------------------------------------------------------------- #
@given(st.integers(0, 2**31 - 1))
@settings(max_examples=5, deadline=None)
def test_jax_integrate_runs_matches_numpy(seed):
    rng = np.random.default_rng(seed % 100000)
    n_runs, n_cfg = 150, 4
    states = rng.choice([0, 1, 2], size=n_runs).astype(np.int32)
    lengths = rng.integers(1, 12, size=n_runs)
    energy = rng.normal(100, 30, (n_cfg, n_runs)) * lengths
    min_samples = int(rng.integers(0, 8))
    ref = integrate_runs(states, energy, lengths, min_samples, dt_s=1.0)
    out = B.jax_integrate_runs(states, energy, lengths, min_samples,
                               dt_s=1.0)
    assert len(ref) == len(out)
    for a, b in zip(ref, out):
        assert a.time_s == b.time_s                 # bit-identical
        for k in a.energy_j:
            assert np.isclose(a.energy_j[k], b.energy_j[k],
                              rtol=1e-9, atol=1e-9)


# --------------------------------------------------------------------------- #
# backend misuse is loud
# --------------------------------------------------------------------------- #
def test_backend_validation_errors(store_dir):
    from repro.core.states import ClassifierConfig

    store = _store(store_dir)
    grid = [DownscalePolicy()]
    ir = get_ir(store, ir_config_for(grid))
    with pytest.raises(ValueError, match="classifier"):
        B.replay_ir_outcomes(
            ir, grid,
            classifier=ClassifierConfig(activity_threshold_pct=10.0))
    with pytest.raises(ValueError, match="dt_s"):
        B.replay_ir_outcomes(ir, grid, dt_s=2.0)
    park = ParkingPolicy(pool=PoolConfig(n_devices=2,
                                         policy=PoolPolicy.CONSOLIDATED,
                                         n_active=1))
    with pytest.raises(ValueError):
        # downscale-then-parking composite is not IR-capable
        B.replay_ir_outcomes(ir, [CompositePolicy((DownscalePolicy(),
                                                   park))])


def test_jax_errors_raise_and_only_device_oom_falls_back(store_dir,
                                                        monkeypatch):
    """``backend="jax"`` never runs NumPy unseen: a compile or programming
    error raises through ``evaluate``; only a device out of memory steps
    down to the NumPy compact kernel, counted as a fallback."""
    store = _store(store_dir)
    grid = family_grid()
    ref = evaluate(grid, store, compact=True, min_job_duration_s=0.0)

    def broken(*args, **kwargs):
        raise TypeError("kernel bug")
    monkeypatch.setattr(B, "replay_ir_outcomes", broken)
    with pytest.raises(TypeError, match="kernel bug"):
        evaluate(grid, store, backend="jax", min_job_duration_s=0.0)

    def out_of_memory(*args, **kwargs):
        raise B.DeviceError("RESOURCE_EXHAUSTED: Out of memory allocating")
    monkeypatch.setattr(B, "replay_ir_outcomes", out_of_memory)
    prev = obs.enabled()
    obs.enable()
    try:
        before = obs.REGISTRY.total("repro_fallbacks_total",
                                reason="device_oom")
        out = evaluate(grid, store, backend="jax", min_job_duration_s=0.0)
        assert obs.REGISTRY.total("repro_fallbacks_total",
                              reason="device_oom") == before + 1
    finally:
        if not prev:
            obs.disable()
    assert_outcomes_equivalent(ref, out, exact_energies=True)


def test_pool_tasks_never_import_jax(store_dir, monkeypatch):
    """One process per chip: every process-pool task (row sweep, IR build,
    NumPy run replay, analysis) runs in a worker that never imports JAX,
    so no worker can reach the jax backend or claim the device."""
    from _pool_probe import probe
    from repro.telemetry import analyze_store
    from repro.telemetry import pipeline
    from repro.whatif import replay_ir

    store = _store(store_dir)
    grid = family_grid()
    seen = []
    real = pipeline.run_supervised

    def probed(fn, tasks, **kwargs):
        out = real(probe, [(fn, *t) for t in tasks], **kwargs)
        seen.extend(jax_loaded for _, jax_loaded in out)
        return [result for result, _ in out]
    monkeypatch.setattr(pipeline, "run_supervised", probed)
    with tempfile.TemporaryDirectory() as d:
        fresh = TelemetryStore(d, shard_format="npy_dir")
        generate_cluster(n_devices=8, horizon_s=1200, seed=2, store=fresh,
                         shard_s=600)
        run_sweep(fresh, grid, workers=2, compact=False,
                  min_job_duration_s=0.0)
        ir = build_ir(fresh, ir_config_for(grid), workers=2)
    replay_ir(ir, grid, workers=2, min_job_duration_s=0.0)
    analyze_store(store, workers=2, compact=False)
    assert len(seen) >= 8 and not any(seen)


# --------------------------------------------------------------------------- #
# cooldown-suppression pass: decision sequences pinned (satellite #2)
# --------------------------------------------------------------------------- #
def _cooldown_frame():
    """Six cycles of [10 low-activity samples][3 busy samples]: short busy
    gaps make every later low run cooldown-risky for large-Y configs."""
    rows = []
    t = 0.0
    for _ in range(6):
        for sm, n in ((1.0, 10), (95.0, 3)):
            for _ in range(n):
                rows.append({"timestamp": t, "job_id": 1,
                             "program_resident": 1,
                             "power": 300.0 if sm > 50 else 80.0, "sm": sm,
                             "hostname": 0, "device_id": 0, "platform": 0})
                t += 1.0
    return TelemetryFrame.from_rows(rows)


def _naive_decisions(stream, dt_s, y, trig):
    """Transparent per-(run, config) sequential reference for the fire
    sequence: full-window searchsorted, no risky screen, no hoisting."""
    off, low_flags = stream.controller_runs()
    low_j = np.flatnonzero(low_flags)
    s0s = off[low_j]
    e0s = off[low_j + 1]
    lens = e0s - s0s
    ts = stream.ts()
    busy_after = stream.ts_first + dt_s * e0s.astype(np.float64)
    n_cfg = y.shape[0]
    fires = np.zeros((low_j.size, n_cfg), dtype=bool)
    last_busy = np.full(n_cfg, -np.inf)
    for k in range(low_j.size):
        for c in range(n_cfg):
            i = max(int(trig[c]), int(np.searchsorted(
                ts[s0s[k]:e0s[k]], last_busy[c] + y[c], side="left")))
            if lens[k] > trig[c] and i < lens[k]:
                fires[k, c] = True
                last_busy[c] = busy_after[k]
    return fires


def test_downscale_cooldown_decisions_pinned():
    grid = [DownscalePolicy(config=ControllerConfig(
        threshold_x_s=x, cooldown_y_s=y))
        for x, y in ((2.0, 1.0), (2.0, 10.0), (6.0, 10.0), (2.0, 20.0))]
    batch = DownscaleBatch(tuple(grid))
    with tempfile.TemporaryDirectory() as d:
        store = TelemetryStore(d)
        store.write_shard(_cooldown_frame(), host="h0")
        ir = build_ir(store, IRConfig())
        s = list(ir.streams.values())[0]
        plat = _resolve_platform(None, {}, s.platform_id)
        n_down, n_rest, throttled, _, _ = _run_downscale(
            s, plat, 1, 1.0, batch._eps, batch._x, batch._y, batch._trig,
            batch._delta(plat))
        fires = _naive_decisions(s, 1.0, batch._y, batch._trig)
        np.testing.assert_array_equal(n_down,
                                      fires.sum(axis=0).astype(np.int64))
        # pinned sequences: (x=2,y=1) fires every run untouched; (x=2,y=10)
        # and (x=6,y=10) fire every run but cooldown delays the trigger
        # index (visible as fewer throttled samples); (x=2,y=20)'s cooldown
        # overshoots the whole next run, so every other run is suppressed
        np.testing.assert_array_equal(n_down, [6, 6, 6, 3])
        np.testing.assert_array_equal(n_rest, [6, 6, 6, 3])
        np.testing.assert_array_equal(
            fires[:, 3], [True, False, True, False, True, False])
        assert throttled[1] < throttled[0]
        assert throttled[2] < throttled[1]
        # and the jax backend reproduces the same decision sequence
        out, _, _ = B.replay_ir_outcomes(ir, grid, min_job_duration_s=0.0,
                                         min_interval_s=1.0)
        np.testing.assert_array_equal(
            [o.downscale_events for o in out], n_down)
        np.testing.assert_array_equal(
            [int(o.throttled_time_s) for o in out], throttled)
