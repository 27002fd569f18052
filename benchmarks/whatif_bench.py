"""What-if engine benchmarks: sweep throughput and closed-loop search.

``bench_whatif_sweep`` tracks the batched config-axis sweep and the
run-level-IR compact sweep ("compact once, replay many");
``bench_whatif_search`` tracks :func:`repro.whatif.search_frontier` against
the dense 200-config sweep (configs evaluated to reach the knee, configs/s,
knee-match tolerance), its IR fast path, and the warm-started re-search.
Both run in ``--quick`` CI mode on every PR, exercising the compact AND the
row-exact sweep paths.

Generates the 96-group bench corpus (64 devices x 3 h, the fleet_bench
deployment) straight into a shard store, then sweeps the legacy 48-config
policy grid three ways — per-policy reference (serial), config-axis batched
(serial), batched process-pool — plus the dense 200-config default grid
through the batched row path and through the run-level IR (build timed
separately; replays hit the in-memory/sidecar cache, which is the
steady-state of repeat sweeps).

Acceptance: the row-path sweeps stream shard-by-shard (peak memory ~ one
shard; the compact path instead holds the run tables + power column — see
the memory note in :mod:`repro.whatif.ir`), the
batched path is bit-identical to the per-policy reference AND to itself
under ``workers=2``, the compact path matches the batched path exactly on
time/count metrics and to <= 1e-9 relative on energies/penalties, the no-op
config anchors the frontier at zero saving / zero penalty, and on the
48-config x 691k-row corpus ``configs_per_s_batched / configs_per_s_serial
>= 5`` (PR 3 baseline) while the dense compact sweep reaches ``>= 3x`` the
dense batched throughput (``compact_speedup_target_3x``).
``configs_per_s_batched_dense`` carries a one-sided regression floor
(``mode="min"``) instead of an informational null target.

The jax replay backend (:mod:`repro.whatif.backend`) adds
``configs_per_s_compact_dense_jax`` (floored at the committed NumPy
compact baseline, ``mode="min"``, with the measuring device count in the
``devices`` column), a ``jax_matches_numpy_oracle`` exactness gate that
runs in ``--quick`` CI too, and — full mode only — a 10^4-config grid
replayed end-to-end (``configs_per_s_compact_jax_10k``).

The observability layer (:mod:`repro.obs`) adds its acceptance gates:
``obs_overhead_le_5pct`` (obs-on vs obs-off dense compact sweep, min-of-5),
``obs_bit_identical`` (frontier dicts equal either way),
``obs_prom_lint_errors`` (the exposition parses), ``obs_distinct_metrics``
(>= 15 ``repro_*`` families when the whole run is instrumented via
``run.py --obs``), the span-derived jax stage split
(``jax_kernel_stage_s`` / ``jax_assembly_stage_s`` — the vectorized
host-assembly evidence), and ``jax_mesh_matches_single_device`` when >1
device is visible (the CI lane forces 4).

Run:  PYTHONPATH=src python -m benchmarks.run --only whatif \
          [--json BENCH_whatif_sweep.json] [--quick]

``--quick`` (CI) shrinks the corpus and drops the timing targets; the
correctness targets (bit-identity, compact equivalence, frontier anchoring)
still validate.
"""
from __future__ import annotations

import math
import tempfile
import time

import numpy as np

from benchmarks import common
from benchmarks.common import Bench

#: same deployment as fleet_bench, emitted chunked: 96 analyzable groups.
#: One shard per device stream (npy_dir): shard reads cost one open per
#: column instead of a deflate pass, so the timings measure the replay
#: engines, not decompression.
N_DEVICES = 64
HORIZON_S = 3 * 3600
SEED = 3
SHARD_S = HORIZON_S

#: min-of-N timing — container timing noise is multi-second, so single-shot
#: ratios are unstable; the minimum is the standard de-noised estimate
REPS_BATCHED = 3
REPS_SERIAL = 2

#: min-of-N reps for the obs-overhead pair (off vs on): the <= 5% gate
#: compares two sub-second timings, so it needs more de-noising than the
#: throughput rows
REPS_OBS = 5

#: one-sided throughput floor for the dense batched row path (configs/s on
#: the full corpus; committed baseline ~29, floor at ~1/3 to absorb
#: container noise without letting a real regression through)
DENSE_BATCHED_FLOOR = 10.0

#: --quick (CI): tiny store, timing targets disabled. The horizon must
#: clear the jobs' deep-idle setup phase (~24% of duration) so policies
#: actually have execution-idle time to mitigate.
QUICK_N_DEVICES = 8
QUICK_HORIZON_S = 2700
QUICK_SHARD_S = 900

#: one-sided floor for the jax-backend dense compact sweep: the committed
#: NumPy ``configs_per_s_compact_dense`` baseline. The acceptance target
#: is >= 5x this; flooring at 1x lets CI absorb container noise while
#: still catching a backend that regresses below the path it replaces.
JAX_DENSE_FLOOR = 500.9266642388074


def _timed(fn, reps):
    """(min wall seconds over ``reps`` runs, last result)."""
    best = math.inf
    result = None
    for _ in range(reps):
        t0 = time.perf_counter()
        result = fn()
        best = min(best, time.perf_counter() - t0)
    return best, result


def _frontiers_equivalent(ref, cmp_, rtol=1e-9, atol=1e-9) -> bool:
    """The compact-path contract: every time/count metric bit-identical to
    the row path, every energy/penalty metric within ``rtol`` relative."""
    if len(ref.outcomes) != len(cmp_.outcomes) or ref.n_rows != cmp_.n_rows:
        return False
    exact = ("name", "params", "n_jobs", "wake_events", "downscale_events",
             "throttled_time_s", "pareto")
    close = ("baseline_energy_j", "counterfactual_energy_j", "penalty_s",
             "saved_fraction", "penalty_fraction")
    for a, b in zip(ref.outcomes, cmp_.outcomes):
        if any(getattr(a, f) != getattr(b, f) for f in exact):
            return False
        if not all(np.isclose(getattr(a, f), getattr(b, f),
                              rtol=rtol, atol=atol) for f in close):
            return False
        if not np.allclose(a.per_job_saved_fraction,
                           b.per_job_saved_fraction, rtol=rtol, atol=atol):
            return False
        if not np.allclose(a.per_job_penalty_s, b.per_job_penalty_s,
                           rtol=rtol, atol=atol):
            return False
    return True


def _grid_10k():
    """A dense per-platform 10^4-config grid (the arXiv 2004.08177-style
    deadline-sweep scale): 1 no-op + 2048 Algorithm-1 downscale (32 X x
    32 Y x 2 modes) + 50 consolidation pools + 7901 power caps."""
    from repro.core.controller import ControllerConfig, DownscaleMode
    from repro.core.imbalance import PoolConfig, PoolPolicy
    from repro.whatif import (DownscalePolicy, NoOpPolicy, ParkingPolicy,
                              PowerCapPolicy)
    grid = [NoOpPolicy()]
    for x in np.linspace(0.5, 16.0, 32):
        for y in np.linspace(1.0, 12.0, 32):
            for mode in (DownscaleMode.SM_ONLY, DownscaleMode.SM_AND_MEM):
                grid.append(DownscalePolicy(config=ControllerConfig(
                    threshold_x_s=round(float(x), 4),
                    cooldown_y_s=round(float(y), 4), mode=mode)))
    for n_devices in (4, 8):
        for k in range(1, n_devices):
            for resume_s in (2.0, 5.0, 10.0, 30.0, 60.0):
                grid.append(ParkingPolicy(
                    pool=PoolConfig(n_devices=n_devices,
                                    policy=PoolPolicy.CONSOLIDATED,
                                    n_active=k),
                    resume_latency_s=resume_s))
    for frac in np.linspace(0.2, 0.99, 10_000 - len(grid)):
        grid.append(PowerCapPolicy(cap_fraction=round(float(frac), 6)))
    return grid


def bench_whatif_sweep() -> Bench:
    from repro.cluster import generate_cluster
    from repro.telemetry import TelemetryStore
    from repro.whatif import (default_policy_grid, frontier_to_dict, get_ir,
                              ir_config_for, run_sweep)

    quick = common.QUICK
    n_devices = QUICK_N_DEVICES if quick else N_DEVICES
    horizon_s = QUICK_HORIZON_S if quick else HORIZON_S
    shard_s = QUICK_SHARD_S if quick else SHARD_S
    reps_b = 1 if quick else REPS_BATCHED
    reps_s = 1 if quick else REPS_SERIAL

    b = Bench("whatif_sweep")
    grid = default_policy_grid(dense=False)
    dense_grid = default_policy_grid()
    with tempfile.TemporaryDirectory() as d:
        store = TelemetryStore(d, shard_format="npy_dir")
        generate_cluster(n_devices=n_devices, horizon_s=horizon_s, seed=SEED,
                         store=store, shard_s=shard_s)
        rows = store.total_rows

        t_serial, serial = _timed(
            lambda: run_sweep(store, grid, workers=1, min_job_duration_s=0.0,
                              batched=False), reps_s)
        t_batched, batched = _timed(
            lambda: run_sweep(store, grid, workers=1, min_job_duration_s=0.0,
                              batched=True, compact=False), reps_b)
        t_pooled, pooled = _timed(
            lambda: run_sweep(store, grid, workers=2, min_job_duration_s=0.0,
                              batched=True, compact=False), 1)
        t_dense, dense_row = _timed(
            lambda: run_sweep(store, dense_grid, workers=1,
                              min_job_duration_s=0.0, batched=True,
                              compact=False), reps_b)

        # run-level IR: one O(rows) build (timed cold), then compact sweeps
        # replay O(runs) per config against the cached IR — the steady
        # state of "compact once, replay many"
        t_ir_build, ir = _timed(
            lambda: get_ir(store, ir_config_for(dense_grid)), 1)
        t_compact, compact = _timed(
            lambda: run_sweep(store, dense_grid, workers=1,
                              min_job_duration_s=0.0, compact=True), reps_b)

        # jax backend: warm-up pays compilation + pack, then the timed
        # replays measure the steady state — same protocol as the compact
        # rows above (the IR cache is already warm)
        try:
            import jax as _jax

            import repro.whatif.backend  # noqa: F401
            n_jax_devices = len(_jax.devices())
        except ImportError:
            n_jax_devices = 0
        if n_jax_devices:
            def jax_sweep(pols):
                return run_sweep(store, pols, workers=1,
                                 min_job_duration_s=0.0, backend="jax")
            jax_sweep(dense_grid)
            t_jax, jax_front = _timed(lambda: jax_sweep(dense_grid), reps_b)
            if not quick:
                grid_10k = _grid_10k()
                jax_sweep(grid_10k)
                t_10k, front_10k = _timed(lambda: jax_sweep(grid_10k), 1)

        # ---- observability contract: overhead, bit-identity, exposition.
        # Save/restore the enabled flag (run.py --obs may have turned obs
        # on globally) and never reset the registry — it may hold the
        # whole run's metrics.
        import repro.obs as obs

        def compact_sweep():
            return run_sweep(store, dense_grid, workers=1,
                             min_job_duration_s=0.0, compact=True)

        reps_obs = 1 if quick else REPS_OBS
        prev_obs = obs.enabled()
        obs.disable()
        t_obs_off, front_obs_off = _timed(compact_sweep, reps_obs)
        obs.enable()
        t_obs_on, front_obs_on = _timed(compact_sweep, reps_obs)

        # per-stage split of the jax replay (kernels vs host assembly),
        # from the spans of one obs-on sweep — the vectorized-assembly
        # before/after evidence rides in the bench JSON
        jax_kernel_s = jax_assembly_s = 0.0
        if n_jax_devices:
            n0 = len(obs.spans())
            jax_sweep(grid_10k if not quick else dense_grid)
            totals = obs.stage_totals(obs.spans()[n0:])
            jax_kernel_s = totals.get("backend.kernels",
                                      {}).get("total_s", 0.0)
            jax_assembly_s = totals.get("backend.assembly",
                                        {}).get("total_s", 0.0)
            # config-mesh lane: shard the config axis over every visible
            # device; must match the single-device sweep under the oracle
            # contract (counts exact, energies <= 1e-9) and record the
            # device count in the gauge CI asserts on
            mesh_matches = 0.0
            t_mesh = 0.0
            if n_jax_devices > 1:
                from repro.whatif.backend import config_mesh

                # shared IR handle: the same RunIR every consumer in this
                # bench replays (analyze/sweep/search all accept ir=), so
                # the mesh row times the sharded kernels, not acquisition
                def mesh_sweep():
                    return run_sweep(store, dense_grid, workers=1,
                                     min_job_duration_s=0.0, backend="jax",
                                     dist=config_mesh(), ir=ir)
                mesh_front = mesh_sweep()       # warm-up: compile + pack
                t_mesh, mesh_front = _timed(mesh_sweep, reps_b)
                mesh_matches = float(
                    _frontiers_equivalent(jax_front, mesh_front))

        obs_prom_errors = len(obs.lint_exposition(obs.render_prometheus()))
        n_obs_metrics = len([n for n in obs.REGISTRY.names()
                             if n.startswith("repro_")])
        if not prev_obs:
            obs.disable()

    n_cfg = len(grid)
    b.add("rows", float(rows))
    b.add("n_configs", float(n_cfg), (48.0, 0.01))
    b.add("n_groups", float(serial.n_jobs))
    if not quick:
        b.add("groups_target_96", float(serial.n_jobs >= 96), (1.0, 0.01))
    b.add("configs_per_s_serial", n_cfg / t_serial, seconds=t_serial)
    b.add("configs_per_s_batched", n_cfg / t_batched, seconds=t_batched)
    b.add("configs_per_s_workers2", n_cfg / t_pooled, seconds=t_pooled)
    b.add("row_configs_per_s_batched", rows * n_cfg / t_batched,
          seconds=t_batched)

    speedup = t_serial / t_batched
    b.add("batched_speedup_vs_serial", speedup)
    b.add("batched_speedup_target_5x", float(speedup >= 5.0),
          None if quick else (1.0, 0.01))

    b.add("batched_bit_identical",
          float(frontier_to_dict(batched) == frontier_to_dict(serial)),
          (1.0, 0.01))
    b.add("workers_bit_identical",
          float(frontier_to_dict(pooled) == frontier_to_dict(batched)),
          (1.0, 0.01))

    b.add("dense_grid_configs", float(len(dense_grid)), (200.0, 0.01))
    b.add("configs_per_s_batched_dense", len(dense_grid) / t_dense,
          None if quick else (DENSE_BATCHED_FLOOR, 0.0), mode="min",
          seconds=t_dense)

    # ---- run-level IR (compact) rows ----
    b.add("ir_build_s", t_ir_build, seconds=t_ir_build)
    b.add("ir_runs", float(ir.n_runs))
    b.add("compaction_ratio", ir.compaction_ratio)
    b.add("configs_per_s_compact_dense", len(dense_grid) / t_compact,
          seconds=t_compact)
    compact_speedup = t_dense / t_compact
    b.add("compact_speedup_vs_batched_dense", compact_speedup)
    b.add("compact_speedup_target_3x", float(compact_speedup >= 3.0),
          None if quick else (1.0, 0.01))
    b.add("compact_matches_reference",
          float(_frontiers_equivalent(dense_row, compact)), (1.0, 0.01))
    b.add("compact_reports_runs", float(compact.n_runs == ir.n_runs),
          (1.0, 0.01))

    # ---- jax backend (jit'd run-level evaluators) rows ----
    b.add("jax_devices", float(n_jax_devices))
    if n_jax_devices:
        b.add("configs_per_s_compact_dense_jax", len(dense_grid) / t_jax,
              None if quick else (JAX_DENSE_FLOOR, 0.0), mode="min",
              seconds=t_jax, devices=n_jax_devices)
        jax_speedup = t_compact / t_jax
        b.add("jax_speedup_vs_compact_dense", jax_speedup,
              devices=n_jax_devices)
        b.add("jax_speedup_target_5x", float(jax_speedup >= 5.0),
              None if quick else (1.0, 0.01))
        # the oracle gate runs in --quick too: exactness is corpus-size
        # independent, so CI always checks it even with timings disabled
        b.add("jax_matches_numpy_oracle",
              float(_frontiers_equivalent(compact, jax_front)), (1.0, 0.01))
        if not quick:
            b.add("grid10k_configs", float(len(grid_10k)), (10000.0, 0.01))
            b.add("configs_per_s_compact_jax_10k", len(grid_10k) / t_10k,
                  seconds=t_10k, devices=n_jax_devices)
            b.add("grid10k_pareto_set_size",
                  float(len(front_10k.pareto_set())))

    # ---- observability rows (tentpole acceptance gates) ----
    obs_overhead = t_obs_on / t_obs_off - 1.0
    b.add("obs_overhead_frac", obs_overhead,
          seconds=t_obs_on)
    b.add("obs_overhead_le_5pct", float(obs_overhead <= 0.05),
          None if quick else (1.0, 0.01))
    b.add("obs_bit_identical",
          float(frontier_to_dict(front_obs_on)
                == frontier_to_dict(front_obs_off)), (1.0, 0.01))
    b.add("obs_prom_lint_errors", float(obs_prom_errors), (0.0, 0.5))
    # the >= 15 gate needs the whole run instrumented (run.py --obs); a
    # bare bench only enables obs for the overhead window above, so the
    # count is informational there
    b.add("obs_distinct_metrics", float(n_obs_metrics),
          (15.0, 0.0) if prev_obs else None, mode="min")
    if n_jax_devices:
        b.add("jax_kernel_stage_s", jax_kernel_s, seconds=jax_kernel_s)
        b.add("jax_assembly_stage_s", jax_assembly_s,
              seconds=jax_assembly_s)
        if jax_kernel_s + jax_assembly_s > 0:
            b.add("jax_assembly_fraction",
                  jax_assembly_s / (jax_kernel_s + jax_assembly_s))
        if n_jax_devices > 1:
            b.add("jax_mesh_matches_single_device", mesh_matches,
                  (1.0, 0.01), devices=n_jax_devices)
            # multi-device timing over the shared IR handle: informational
            # (no target) — host-count CI runners make mesh timings too
            # noisy to gate, but the row closes the PR 7 follow-on and the
            # committed baseline records the device count for
            # like-for-like comparison
            b.add("configs_per_s_compact_dense_jax_mesh",
                  len(dense_grid) / t_mesh, seconds=t_mesh,
                  devices=n_jax_devices)

    noop = next(o for o in serial.outcomes if o.name == "noop")
    anchored = noop.energy_saved_j == 0.0 and noop.penalty_s == 0.0
    b.add("noop_anchors_frontier", float(anchored), (1.0, 0.01))
    b.add("pareto_set_size", float(len(serial.pareto_set())))
    best = max(serial.outcomes, key=lambda o: o.energy_saved_j)
    b.add("best_saved_fraction", best.saved_fraction)
    return b


def bench_whatif_search() -> Bench:
    """Closed-loop Pareto search vs the dense fixed-grid sweep.

    Same corpus as :func:`bench_whatif_sweep` (64 devices x 3 h, 691k
    rows). Acceptance (full mode): :func:`repro.whatif.search_frontier`
    over the composite-free default families reaches a Pareto front whose
    knee matches the dense 200-config sweep's — knee ``saved_fraction``
    within 0.01 absolute and knee ``penalty_s`` within 5% relative (the
    documented tolerance) — while evaluating <= 50% of the dense grid, and
    the search terminates by knee convergence, not budget exhaustion. The
    compact (run-IR) search must cut wall-clock >= 2x against the row-path
    search at an unchanged knee, and a warm start from the cold search's
    frontier must reach the knee in no more evaluations than the cold
    start. ``--quick`` (CI) shrinks the corpus and keeps only the
    structural targets: on a tiny fleet the trade-off front is sparse
    enough that the two knee constructions may legitimately pick different
    elbows.
    """
    from repro.cluster import generate_cluster
    from repro.telemetry import TelemetryStore
    from repro.whatif import (PenaltyBudget, default_families,
                              default_policy_grid, find_knee, get_ir,
                              ir_config_for, run_sweep, search_frontier)

    quick = common.QUICK
    n_devices = QUICK_N_DEVICES if quick else N_DEVICES
    horizon_s = QUICK_HORIZON_S if quick else HORIZON_S
    shard_s = QUICK_SHARD_S if quick else SHARD_S

    def evals_to_knee(res) -> float:
        """Configs evaluated up to the round the final knee first appeared."""
        return float(next(
            (r.n_evals_total for r in res.history
             if r.knee_params == res.knee.params), res.n_evals))

    b = Bench("whatif_search")
    with tempfile.TemporaryDirectory() as d:
        store = TelemetryStore(d, shard_format="npy_dir")
        generate_cluster(n_devices=n_devices, horizon_s=horizon_s, seed=SEED,
                         store=store, shard_s=shard_s)
        rows = store.total_rows

        # pay the IR build explicitly (the default grid and the search
        # families share the default thresholds, hence one IR) so every
        # timed stage below measures warm compact replay, independent of
        # stage order
        t_ir_build, _ = _timed(
            lambda: get_ir(store, ir_config_for(default_policy_grid())), 1)
        t_dense, dense = _timed(
            lambda: run_sweep(store, min_job_duration_s=0.0), 1)
        t_row_search, res_row = _timed(
            lambda: search_frontier(store,
                                    families=default_families(
                                        composites=False),
                                    min_job_duration_s=0.0,
                                    compact=False), 1)
        t_search, res = _timed(
            lambda: search_frontier(store,
                                    families=default_families(
                                        composites=False),
                                    min_job_duration_s=0.0), 1)
        t_comp, res_comp = _timed(
            lambda: search_frontier(store,
                                    budget=PenaltyBudget(
                                        max_penalty_fraction=0.01),
                                    min_job_duration_s=0.0), 1)
        t_warm, res_warm = _timed(
            lambda: search_frontier(store,
                                    families=default_families(
                                        composites=False),
                                    min_job_duration_s=0.0,
                                    init_frontier=res.frontier), 1)

    n_dense = len(dense.outcomes)
    b.add("rows", float(rows))
    b.add("dense_configs", float(n_dense), (200.0, 0.01))
    b.add("ir_build_s", t_ir_build, seconds=t_ir_build)
    b.add("dense_sweep_s", t_dense, seconds=t_dense)
    b.add("search_s", t_search, seconds=t_search)
    b.add("search_evals", float(res.n_evals))
    b.add("search_rounds", float(res.n_rounds))
    b.add("search_configs_per_s", res.n_evals / t_search, seconds=t_search)
    b.add("evals_fraction_of_dense", res.n_evals / n_dense)
    b.add("evals_le_half_dense", float(res.n_evals <= n_dense // 2),
          (1.0, 0.01))
    b.add("search_converged", float(res.converged), (1.0, 0.01))

    # compact (run-IR) search: build once, replay every round against runs
    b.add("search_row_path_s", t_row_search, seconds=t_row_search)
    search_speedup = t_row_search / t_search
    b.add("search_speedup_compact", search_speedup)
    b.add("search_speedup_target_2x", float(search_speedup >= 2.0),
          None if quick else (1.0, 0.01))
    b.add("search_knee_unchanged_compact",
          float(res.knee.params == res_row.knee.params
                and res.n_evals == res_row.n_evals), (1.0, 0.01))

    b.add("evals_to_knee", evals_to_knee(res))

    # warm start from the cold search's frontier (ROADMAP: week-over-week
    # re-search starts at last week's knee)
    b.add("warm_evals_to_knee", evals_to_knee(res_warm), seconds=t_warm)
    b.add("warm_start_no_more_evals_to_knee",
          float(evals_to_knee(res_warm) <= evals_to_knee(res)),
          None if quick else (1.0, 0.01))

    knee_dense = find_knee(list(dense.outcomes))
    b.add("knee_saved_fraction_dense", knee_dense.saved_fraction)
    b.add("knee_saved_fraction_search", res.knee.saved_fraction)
    b.add("knee_penalty_s_dense", knee_dense.penalty_s)
    b.add("knee_penalty_s_search", res.knee.penalty_s)
    saved_ok = abs(res.knee.saved_fraction
                   - knee_dense.saved_fraction) <= 0.01
    pen_ok = (abs(res.knee.penalty_s - knee_dense.penalty_s)
              <= 0.05 * abs(knee_dense.penalty_s))
    b.add("knee_saved_match_0p01", float(saved_ok),
          None if quick else (1.0, 0.01))
    b.add("knee_penalty_match_5pct", float(pen_ok),
          None if quick else (1.0, 0.01))

    # composite-enabled search under an operator budget (1% of active time)
    b.add("composite_search_evals", float(res_comp.n_evals), seconds=t_comp)
    n_comp_front = sum(1 for o in res_comp.frontier.pareto_set()
                       if o.params.get("policy") == "composite")
    b.add("composite_configs_on_front", float(n_comp_front))
    if res_comp.best is not None:
        b.add("budget_best_saved_fraction", res_comp.best.saved_fraction)
        b.add("budget_best_penalty_fraction", res_comp.best.penalty_fraction)
        b.add("budget_respected",
              float(res_comp.best.penalty_fraction <= 0.01), (1.0, 0.01))
    return b
