"""Benchmark entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus target/ok columns when a
paper number exists) and a per-bench validation summary. The §Roofline bench
reads the dry-run reports if present (reports/dryrun/*.json).

Run:  PYTHONPATH=src python -m benchmarks.run [--only fig5,fig10]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys


def bench_roofline():
    """Summarize dry-run roofline cells (§Roofline) if reports exist."""
    from benchmarks.common import Bench
    b = Bench("roofline")
    report_dir = pathlib.Path("reports/dryrun")
    if not report_dir.exists():
        return b
    cells = sorted(report_dir.glob("*.json"))
    n_ok = n_skip = n_err = 0
    for path in cells:
        r = json.loads(path.read_text())
        if r["status"] == "ok":
            n_ok += 1
            rf = r["roofline"]
            cell = f"{r['arch']}_{r['shape']}_{r['mesh']}"
            b.add(f"{cell}_bound_s", rf.get("roofline_bound_s", 0.0))
            b.add(f"{cell}_useful_fraction", rf["useful_fraction"])
        elif r["status"] == "skipped":
            n_skip += 1
        else:
            n_err += 1
    b.add("cells_ok", float(n_ok))
    b.add("cells_skipped", float(n_skip))
    b.add("cells_error", float(n_err), (0.0, 0.5))
    return b


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench-name substrings")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the result rows as JSON (committed "
                         "baselines, e.g. BENCH_fleet_analyze.json)")
    ap.add_argument("--obs", default=None, metavar="DIR",
                    help="enable the repro.obs observability layer for the "
                         "run and write DIR/metrics.prom (Prometheus text "
                         "exposition) + DIR/spans.jsonl (span trace); the "
                         "per-stage breakdown is attached to --json output "
                         "and a stage tree is printed to stderr")
    ap.add_argument("--quick", action="store_true",
                    help="CI mode for the throughput benches (fleet, "
                         "whatif, kernels): tiny corpora, timing targets "
                         "disabled, correctness targets kept, jax pinned "
                         "to CPU. Paper-figure benches ignore it (their "
                         "targets are paper numbers that only hold at full "
                         "corpus size) — combine with "
                         "--only fleet,whatif,kernels for a fast CI pass")
    args = ap.parse_args()

    if args.quick:
        import os

        from benchmarks import common
        common.QUICK = True
        # hermetic CI: pin jax to the host CPU before anything imports it,
        # so the quick jax-backend rows behave identically on machines
        # with and without accelerators
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    else:
        from repro.compile_cache import enable_compile_cache
        enable_compile_cache(pathlib.Path(__file__).resolve().parent.parent)

    import repro.obs as obs
    if args.obs:
        obs.enable()
        # zero-register the degradation ladder, the incremental-IR and the
        # live-controller families so a fault-free / append-free / tickless
        # exposition still carries them (CI lints on presence)
        obs.init_degradation_metrics()
        obs.init_ir_append_metrics()
        obs.init_live_metrics()

    from benchmarks.fleet_bench import bench_fleet_analyze
    from benchmarks.kernels_bench import bench_kernels
    from benchmarks.live_bench import bench_live_controller
    from benchmarks.paper_benches import ALL_BENCHES
    from benchmarks.whatif_bench import bench_whatif_search, bench_whatif_sweep
    benches = list(ALL_BENCHES) + [bench_roofline, bench_fleet_analyze,
                                   bench_whatif_sweep, bench_whatif_search,
                                   bench_live_controller, bench_kernels]
    if args.only:
        keys = args.only.split(",")
        benches = [fn for fn in benches
                   if any(k in fn.__name__ for k in keys)]

    print("name,us_per_call,derived,devices,target,ok")
    summaries = []
    all_rows = []
    all_ok = True
    for fn in benches:
        # no-op span when --obs is absent (obs stays disabled)
        with obs.span("bench." + fn.__name__):
            bench = fn()
        for row in bench.rows:
            target = "" if row.target is None else f"{row.target:.6g}"
            ok = "" if row.ok is None else str(row.ok)
            print(f"{row.csv()},{target},{ok}", flush=True)
            all_rows.append({"name": row.name, "us_per_call": row.us_per_call,
                             "derived": row.derived, "devices": row.devices,
                             "target": row.target, "ok": row.ok})
        summaries.append(bench.summary())
        if any(r.ok is False for r in bench.rows):
            all_ok = False

    payload = {"rows": all_rows, "all_ok": all_ok}
    if args.obs:
        obs_dir = pathlib.Path(args.obs)
        obs.write_textfile(obs_dir / "metrics.prom")
        obs.dump_spans_jsonl(obs_dir / "spans.jsonl")
        payload["stages"] = obs.stage_breakdown()
        print("\n== stage tree ==", file=sys.stderr)
        print(obs.stage_report(min_dur_s=1e-3), file=sys.stderr)
        # degradation ladder: quarantines / retries / fallbacks / coverage —
        # all zero (or 1.0 coverage) on a healthy run, by construction
        fam_names = {name for name, _, _ in obs.DEGRADATION_FAMILIES}
        print("\n== degradation ladder ==", file=sys.stderr)
        for line in obs.render_prometheus().splitlines():
            if line.startswith("#"):
                continue
            sample_name = line.split("{")[0].split(" ")[0]
            if sample_name in fam_names:
                print("  " + line, file=sys.stderr)

    if args.json:
        pathlib.Path(args.json).write_text(
            json.dumps(payload, indent=1) + "\n")

    print("\n== validation summary ==", file=sys.stderr)
    for s in summaries:
        print("  " + s, file=sys.stderr)
    print(f"overall: {'ALL TARGETS HIT' if all_ok else 'SOME TARGETS MISSED'}",
          file=sys.stderr)


if __name__ == "__main__":
    main()
