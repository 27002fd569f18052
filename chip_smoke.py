"""Bring-up smoke of the what-if replay on a TPU.

Drives the replay's main path once, through the entry points a user calls
(store -> run-level IR -> jax replay -> closed-loop search -> live tick),
on the fleet the what-if benches define: 64 devices x 3 h at 1 Hz from
seed 3, 691,200 rows. Every phase is checked against the NumPy oracle:
times and counts bit-identical, energies and penalties within 1e-9
relative. The obs counters must show the jax path replaying every
IR-capable config, no fallback, and the power-cap program must hold the
compiled Pallas kernel (``tpu_custom_call``).

    python chip_smoke.py              # one chip, every phase
    python chip_smoke.py --chips 4    # only the config mesh: the 10^4-config
                                      # grid on four chips against one

Progress and timings go on earlier lines. The last line of stdout is one
JSON object naming the device. The script stops with a non-zero exit, and
prints no result, at the first failed check, and when JAX finds no TPU.
One process drives the chip; nothing here starts another.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402

import repro.obs as obs  # noqa: E402
from repro.cluster import generate_cluster  # noqa: E402
from repro.kernels import run_replay  # noqa: E402
from repro.telemetry import TelemetryStore, analyze_store  # noqa: E402
from repro.whatif import (default_policy_grid, evaluate, get_ir,  # noqa: E402
                          ir_config_for, run_sweep, search_frontier)
from repro.whatif import backend as B  # noqa: E402

#: the what-if benches' fleet (benchmarks/whatif_bench.py)
N_DEVICES = 64
HORIZON_S = 3 * 3600
SEED = 3
#: every 39th config of the 10^4 grid (257 configs, every family) is
#: replayed again by the NumPy oracle
ORACLE_STRIDE = 39
#: the live cell's fleet (benchmarks/live_bench.py)
LIVE_STREAMS = 10_000
RTOL = 1e-9

EXACT_FIELDS = ("name", "params", "n_jobs", "wake_events",
                "downscale_events", "throttled_time_s")
CLOSE_FIELDS = ("baseline_energy_j", "counterfactual_energy_j",
                "energy_saved_j", "saved_fraction", "penalty_s",
                "penalty_fraction", "exec_idle_energy_fraction_baseline",
                "exec_idle_energy_fraction_cf")


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


# --------------------------------------------------------------------------- #
# oracle comparison and path accounting
# --------------------------------------------------------------------------- #
def compare_outcomes(ref, out, what: str) -> float:
    """Hold ``out`` to the NumPy contract against ``ref`` (an energy or a
    penalty may be off by 1e-9 relative plus 1e-9 absolute, as in the
    repo's tests); return the largest error seen as a share of what it was
    allowed."""
    check(len(ref) == len(out), f"{what}: {len(out)} outcomes, "
                                f"oracle has {len(ref)}")
    worst = 0.0
    for a, b in zip(ref, out):
        for f in EXACT_FIELDS:
            check(getattr(a, f) == getattr(b, f),
                  f"{what}: {f} differs for {a.params}: "
                  f"{getattr(a, f)!r} != {getattr(b, f)!r}")
        pairs = [(getattr(a, f), getattr(b, f)) for f in CLOSE_FIELDS]
        for f in ("per_job_saved_fraction", "per_job_penalty_s"):
            check(len(getattr(a, f)) == len(getattr(b, f)),
                  f"{what}: {f} length differs for {a.params}")
            pairs.extend(zip(getattr(a, f), getattr(b, f)))
        for x, y in pairs:
            check(math.isfinite(y), f"{what}: non-finite value for {a.params}")
            allowed = RTOL * abs(x) + RTOL
            check(abs(x - y) <= allowed,
                  f"{what}: {x!r} vs {y!r} for {a.params}")
            worst = max(worst, abs(x - y) / allowed)
    return worst


def jax_path(fn, what: str):
    """Run ``fn`` (a ``backend="jax"`` call) and check that the jax path
    replayed every IR-capable config: ``repro_replay_configs_total
    {path="jax"}`` plus the configs the IR cannot host equals every config
    the call evaluated, and the jax count grew."""
    names = ("repro_replay_family_configs_total",
             "repro_replay_row_fallback_configs_total")
    before = [obs.REGISTRY.total(n) for n in names]
    jax_before = obs.REGISTRY.total("repro_replay_configs_total", path="jax")
    out, seconds = timed(fn)
    total, row = (obs.REGISTRY.total(n) - b for n, b in zip(names, before))
    on_jax = obs.REGISTRY.total("repro_replay_configs_total", path="jax") \
        - jax_before
    check(on_jax > 0 and on_jax + row == total,
          f"{what}: jax path counted {on_jax:g} of {total:g} configs "
          f"({row:g} on the row path)")
    return out, seconds, int(on_jax)


@contextlib.contextmanager
def first_call(name: str, dist=None):
    """Record the first call of the backend's ``name`` program (its
    arguments and outputs), so the program actually run can be lowered
    again and inspected."""
    fn = B._get_fn(name, dist)
    key = next(k for k, v in B._FN_CACHE.items() if v is fn)
    seen: dict = {}

    def recorder(*args):
        out = fn(*args)
        seen.setdefault("call", (args, out))
        return out

    B._FN_CACHE[key] = recorder
    try:
        yield seen
    finally:
        B._FN_CACHE[key] = fn


def compiled_text(name: str, seen: dict, dist=None) -> str:
    check("call" in seen, f"the {name} program never ran")
    args, _ = seen["call"]
    with jax.enable_x64():
        return B._get_fn(name, dist).lower(*args).compile().as_text()


# --------------------------------------------------------------------------- #
# phases
# --------------------------------------------------------------------------- #
def make_store(root, n_devices: int, horizon_s: int, seed: int):
    store = TelemetryStore(root, shard_format="npy_dir")
    generate_cluster(n_devices=n_devices, horizon_s=horizon_s, seed=seed,
                     store=store, shard_s=horizon_s)
    return store


def phase_analyze(store) -> dict:
    fa, secs = timed(lambda: analyze_store(store, min_job_duration_s=0.0))
    t_frac = fa.in_execution_time_fraction
    e_frac = fa.in_execution_energy_fraction
    check(0.0 < t_frac < 1.0 and 0.0 < e_frac < 1.0,
          f"analysis fractions out of range: {t_frac}, {e_frac}")
    log(f"analyze: {store.total_rows} rows in {secs:.3f} s; exec-idle "
        f"time fraction {t_frac!r}, energy fraction {e_frac!r}")
    return {"seconds": secs, "time_fraction": t_frac,
            "energy_fraction": e_frac}


def phase_ir(store, grid):
    ir, secs = timed(lambda: get_ir(store, ir_config_for(grid)))
    log(f"ir: {ir.n_runs} runs over {len(ir.select(None))} streams "
        f"in {secs:.3f} s")
    return ir, secs


def phase_sweep(store, ir, grid, on_tpu: bool) -> dict:
    """The dense grid on the jax backend (first call, then steady state)
    against the NumPy compact oracle; on the chip, the power-cap program
    that ran must hold the compiled Pallas kernel."""
    def jax_sweep():
        return run_sweep(store, grid, ir=ir, backend="jax",
                         min_job_duration_s=0.0)
    with first_call("powercap") as seen:
        _, first_s, n_jax = jax_path(jax_sweep, "sweep")
    front, steady_s, _ = jax_path(jax_sweep, "sweep")
    ref, numpy_s = timed(lambda: run_sweep(store, grid, ir=ir, compact=True,
                                           min_job_duration_s=0.0))
    err = compare_outcomes(ref.outcomes, front.outcomes, "sweep")
    if on_tpu:
        check("tpu_custom_call" in compiled_text("powercap", seen),
              "the power-cap program holds no compiled Pallas kernel")
    log(f"sweep {len(grid)} configs: jax first {first_s:.3f} s, steady "
        f"{steady_s:.3f} s, numpy compact {numpy_s:.3f} s; {n_jax} on the "
        f"jax path; worst error {err:.3e} of the tolerance"
        + ("; power-cap program holds tpu_custom_call" if on_tpu else ""))
    return {"first_s": first_s, "steady_s": steady_s, "numpy_s": numpy_s,
            "tolerance_used": err}


def phase_large_grid(store, ir, grid, stride: int = ORACLE_STRIDE) -> dict:
    def jax_sweep():
        return run_sweep(store, grid, ir=ir, backend="jax",
                         min_job_duration_s=0.0)
    _, first_s, _ = jax_path(jax_sweep, "large grid")
    obs.clear_spans()
    front, steady_s, n_jax = jax_path(jax_sweep, "large grid")
    # host-clock seconds of the steady call's spans, largest first
    stages = sorted(obs.stage_totals().items(),
                    key=lambda kv: -kv[1]["total_s"])
    idx = list(range(0, len(grid), stride))
    ref = evaluate([grid[i] for i in idx], store, ir=ir, compact=True,
                   min_job_duration_s=0.0)
    err = compare_outcomes(ref, [front.outcomes[i] for i in idx],
                           "large grid")
    log(f"grid {len(grid)} configs: jax first {first_s:.3f} s, steady "
        f"{steady_s:.3f} s; {n_jax} on the jax path; {len(idx)} checked "
        f"against numpy, worst error {err:.3e} of the tolerance")
    log("grid steady spans: " + ", ".join(
        f"{name} {agg['total_s']:.3f} s" for name, agg in stages))
    return {"first_s": first_s, "steady_s": steady_s, "checked": len(idx),
            "tolerance_used": err}


def phase_search(store, ir) -> dict:
    res, jax_s, n_jax = jax_path(
        lambda: search_frontier(store, ir=ir, backend="jax",
                                min_job_duration_s=0.0), "search")
    ref, numpy_s = timed(lambda: search_frontier(store, ir=ir,
                                                 min_job_duration_s=0.0))
    check(res.knee.params == ref.knee.params,
          f"search knee {res.knee.params} != numpy knee {ref.knee.params}")
    check(res.n_evals == ref.n_evals,
          f"search evaluated {res.n_evals}, numpy {ref.n_evals}")
    log(f"search: {res.n_evals} evals in {jax_s:.3f} s on jax "
        f"({n_jax} on the jax path), {numpy_s:.3f} s on numpy; knee "
        f"{res.knee.params}")
    return {"jax_s": jax_s, "numpy_s": numpy_s, "n_evals": res.n_evals}


def phase_live(root, n_streams: int, ticks: int = 2) -> dict:
    from benchmarks.live_bench import _fast_search_kwargs
    from repro.live import LiveConfig, LiveController, SyntheticProducer

    root = pathlib.Path(root)
    store = TelemetryStore(root / "store")
    dt_s = 5.0
    prod = SyntheticProducer(store, n_streams=n_streams, window_s=60,
                             dt_s=dt_s)
    # the search replays at the producer's cadence: at any other dt_s the
    # run-level IR refuses the store and every config takes the row path
    cfg = LiveConfig(backend="jax", max_evals=24,
                     search_kwargs={**_fast_search_kwargs(), "dt_s": dt_s})
    ctrl = LiveController(store, root / "ckpt.json", cfg,
                          publish_path=root / "knee.json")
    secs = []
    for _ in range(ticks):
        prod.step()
        r, s, _ = jax_path(ctrl.tick, "live tick")
        check(r.result == "refreshed" and r.rung == "warm_jax",
              f"live tick: result {r.result}, rung {r.rung}, "
              f"error {r.error}")
        secs.append(s)
    log(f"live: {n_streams} streams, tick seconds "
        + ", ".join(f"{s:.3f}" for s in secs) + " on rung warm_jax")
    return {"tick_s": secs}


def phase_mesh(store, ir, grid, n_chips: int) -> dict:
    """The config axis sharded over ``n_chips`` devices against the same
    grid on one: the NumPy contract between the two, the device gauge,
    and outputs that span every device of the mesh."""
    dist = B.config_mesh(n_chips)

    def sweep(d):
        return run_sweep(store, grid, ir=ir, backend="jax", dist=d,
                         min_job_duration_s=0.0)
    one, one_first_s, _ = jax_path(lambda: sweep(None), "one chip")
    _, one_s, _ = jax_path(lambda: sweep(None), "one chip")
    with first_call("downscale", dist) as ds_seen, \
            first_call("powercap", dist) as cap_seen:
        _, mesh_first_s, _ = jax_path(lambda: sweep(dist), "mesh")
    mesh, mesh_s, _ = jax_path(lambda: sweep(dist), "mesh")
    devices = obs.REGISTRY.total("repro_backend_devices")
    check(devices == n_chips, f"repro_backend_devices is {devices:g}")
    for name, seen in (("downscale", ds_seen), ("powercap", cap_seen)):
        check("call" in seen, f"the sharded {name} program never ran")
        for arr in jax.tree.leaves(seen["call"][1]):
            spans = len(arr.sharding.device_set)
            check(spans == n_chips,
                  f"a {name} output spans {spans} devices, not {n_chips}")
    err = compare_outcomes(one.outcomes, mesh.outcomes, "mesh")
    identical = all(a == b for a, b in zip(one.outcomes, mesh.outcomes))
    log(f"mesh {n_chips} chips, {len(grid)} configs: one chip first "
        f"{one_first_s:.3f} s steady {one_s:.3f} s; mesh first "
        f"{mesh_first_s:.3f} s steady {mesh_s:.3f} s; outputs span "
        f"{n_chips} devices; against one chip, worst error {err:.3e} of "
        f"the tolerance, bit-identical {identical}")
    return {"one_s": one_s, "mesh_s": mesh_s, "tolerance_used": err,
            "bit_identical": identical}


# --------------------------------------------------------------------------- #
# entry point
# --------------------------------------------------------------------------- #
def tpu_device(n_chips: int):
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX runs on {devs[0].platform}")
    check(len(devs) >= n_chips, f"{n_chips} chips asked, {len(devs)} found")
    check("REPRO_PALLAS_INTERPRET" not in os.environ,
          "REPRO_PALLAS_INTERPRET is set; Pallas must compile on the chip")
    check(not run_replay.default_interpret(),
          "Pallas would run in interpret mode on the TPU")
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the config-mesh phase on four chips")
    args = ap.parse_args(argv)
    try:
        devs = tpu_device(args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1

    from benchmarks.whatif_bench import _grid_10k
    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache(ROOT)
    log(f"device: {devs[0].device_kind} x {len(devs)}; jax {jax.__version__}"
        f"; compile cache {cache}")
    obs.enable()
    dense, large = default_policy_grid(), _grid_10k()
    t0 = time.perf_counter()
    try:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as d:
            store, gen_s = timed(lambda: make_store(
                pathlib.Path(d) / "fleet", N_DEVICES, HORIZON_S, SEED))
            log(f"store: {store.total_rows} rows generated in {gen_s:.3f} s")
            ir, _ = phase_ir(store, dense)
            if args.chips > 1:
                phase_mesh(store, ir, large, args.chips)
            else:
                phase_analyze(store)
                phase_sweep(store, ir, dense, on_tpu=True)
                phase_large_grid(store, ir, large)
                phase_search(store, ir)
                phase_live(pathlib.Path(d) / "live", LIVE_STREAMS)
        fallbacks = obs.REGISTRY.total("repro_fallbacks_total")
        check(fallbacks == 0, f"{fallbacks:g} fallbacks counted")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.3f} s; "
        f"0 fallbacks")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
