"""Chip benchmark of the what-if replay: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Looks the cell up in ``BENCHMARK.json``, finds its configuration, traffic
mix, driver and metric readers by name under ``bench/``, builds the inputs
from ``--seed``, warms every shape the window will use (all counted as
``setup_s``), then measures for ``--seconds``. With ``--trace 1`` the
window runs under the JAX profiler and the run reports the cell's
per-layer metrics instead of its end-to-end ones.

After the window the answers the window produced are compared with the
plain reference (``bench/reference.py``); the numbers compared are printed
beside their limits as the last lines of standard error, and under
``checks`` in the result. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
and with ``--trace 1`` ``breakdown``.

The run needs the chips the cell asks for: without a TPU, or with fewer
chips, it exits non-zero and prints no result. JAX's persistent
compilation cache lives at ``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.harness import (BenchError, checks, load_cell,  # noqa: E402
                           load_module, passed)
from bench.work import peaks  # noqa: E402

CACHE_DIR = ".jax_cache"
#: the TPU runtime's logs, which it would otherwise write to /tmp/tpu_logs
LOG_DIR = ".tpu_logs"
#: per-program device time is reported for these jitted programs, and the
#: kernel time of the Pallas cap scan
PROGRAMS = ("_downscale_kernel", "_powercap_kernel", "_integrate_runs_kernel")
KERNELS = ("cap_bucket_scan",)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def devices_for(chips: int, require_tpu: bool, root: pathlib.Path = ROOT):
    os.environ.setdefault("TPU_LOG_DIR", str(root / LOG_DIR))
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips, "
                         f"{len(devs)} found")
    return devs


def enable_compile_cache(root: pathlib.Path) -> str:
    import jax

    path = str(root / CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak(devs) -> int:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def jit_traces() -> float:
    import repro.obs as obs

    return obs.REGISTRY.total("repro_backend_jit_traces_total")


class Profiler:
    """The JAX profiler around the window, without the Python tracer."""

    def __init__(self, directory: pathlib.Path):
        self.dir = directory

    def __enter__(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        from bench import trace
        self.sync_wall = time.time()
        with jax.profiler.TraceAnnotation(trace.SYNC):
            pass
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()
        return False


def per_layer(cell, res, trace_summary, spans) -> dict:
    rec = {"spans": spans, "counts": res["counts"], "trace": trace_summary}
    out = {}
    for m in cell.per_layer():
        reader = load_module(cell.file("metrics", f"{m['name']}.py"),
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(rec)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None, root: pathlib.Path = ROOT,
         require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(root, args.workload, args.seed, args.seconds,
                         bool(args.trace))
        devs = devices_for(cell.workload["chips"], require_tpu, root)
        if require_tpu:
            peaks(devs[0].device_kind)
        return run(cell, devs, root)
    except (BenchError, KeyError) as e:
        log(f"bench: {e}")
        return 2


def run(cell, devs, root) -> int:
    import repro.obs as obs

    cache = enable_compile_cache(root)
    obs.enable()
    driver = cell.module("drivers", cell.traffic["kind"])
    with tempfile.TemporaryDirectory(prefix="bench_") as work:
        work = pathlib.Path(work)
        st = driver.setup(cell, work)
        setup_s = time.perf_counter() - T_START
        log(f"setup: {setup_s:.3f} s (generate {st.generate_s:.3f} s, "
            f"ir {st.ir_s:.3f} s, warm {st.warm_s:.3f} s); "
            f"{devs[0].device_kind} x {len(devs)}; cache {cache}")
        stages = sorted(obs.stage_totals().items(),
                        key=lambda kv: -kv[1]["total_s"])[:6]
        log("setup spans: " + ", ".join(
            f"{k} {v['total_s']:.3f} s" for k, v in stages))
        obs.clear_spans()
        traces0 = jit_traces()
        trace_summary = None
        if cell.trace:
            with Profiler(work / "trace") as prof:
                res = driver.window(cell, st, cell.seconds)
        else:
            res = driver.window(cell, st, cell.seconds)
        compiles = jit_traces() - traces0
        spans = obs.spans()
        peak = memory_peak(devs)
        log(f"window: {res['window_s']:.3f} s, {res['counts']}, "
            f"{compiles:g} jit traces; {res.get('notes', {})}")
        if cell.trace:
            from bench import trace
            trace_summary = trace.reduce(
                trace.find_xplane(work / "trace"), res["window_s"],
                spans=spans, sync_wall=prof.sync_wall, kernels=KERNELS)
            progs = {k: v for k, v in trace_summary["programs"].items()
                     if k in PROGRAMS}
            log("trace: " + json.dumps(
                {"busy_s": trace_summary["busy_s"],
                 "window_s": trace_summary["window_s"],
                 "program_device_s": progs,
                 "kernel_device_s": trace_summary["kernels"]}))
            work_ = getattr(st, "cap_work", None)
            if work_:
                hbm = peaks(devs[0].device_kind)["hbm_bytes_per_s"] \
                    if devs[0].platform == "tpu" else None
                log(f"cap_bucket_scan work per sweep: {work_['compares']} "
                    f"word-pair compares, {work_['bytes']} bytes"
                    + (f" ({1e3 * work_['bytes'] / hbm:.6f} ms at the HBM "
                       f"peak)" if hbm else ""))
        t_check = time.perf_counter()
        cmp = driver.check(cell, st)
    tol = cell.config["guarantees"]["energies_penalties_rel"]
    chk = checks(cmp, st.path, tol)
    log(f"check: {cmp.compared} outcomes compared in "
        f"{time.perf_counter() - t_check:.3f} s; worst {cmp.worst}")
    for note in cmp.notes:
        log(f"check: {note}")
    for name, c in chk.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    if cell.trace:
        device["busy_s"] = trace_summary["busy_s"]
        device["window_s"] = trace_summary["window_s"]
        metrics = per_layer(cell, res, trace_summary, spans)
    else:
        values = {**res["metrics"], "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end()}
    out = {"correct": passed(chk), "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    if cell.trace:
        out["breakdown"] = {"device_ops": trace_summary["device_ops"],
                            "idle_gaps": trace_summary["idle_gaps"]}
    out["checks"] = chk
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
