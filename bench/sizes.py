"""How the acad cells' costs grow with the fleet: the measurement that
sizes a configuration's ``n_devices``.

    python3 bench/sizes.py --config acad64_3h --devices 64 128 256 [--out F]

For each fleet size, in one process on the chip: the simulation, the
seed's draw and the store's write, the IR build, the pinned 10^4 grid's
``evaluate`` (first call, which compiles, and a steady one, with its
``backend.kernels`` and ``backend.assembly`` spans), the 200-config
sweep and the default search (first and steady), and the plain
reference over both grids and the search's configs. At the first size it
also splits a steady 10^4 sweep into ``run_sweep`` and ``find_knee``, and
times one Pareto pass alone. Prints one JSON line per size. The
benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import copy
import json
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.harness import (BenchError, free_device, load_json,  # noqa: E402
                           semantics, timed)
from bench.run import devices_for, log, memory_peak  # noqa: E402


def _spans(names) -> dict:
    import repro.obs as obs

    tot = obs.stage_totals()
    return {n: tot[n]["total_s"] for n in names if n in tot}


def measure(config: dict, n: int, seed: int, work: pathlib.Path,
            split: bool, devs) -> dict:
    import repro.obs as obs
    from bench import reference as R
    from bench.generators import cluster
    from bench.grids import grid10k, grid200
    from bench.policies import build
    from repro.whatif import evaluate, get_ir, ir_config_for, run_sweep
    from repro.whatif.search import find_knee, search_frontier
    from repro.whatif.sweep import pareto_flags

    conf = copy.deepcopy(config)
    conf["fleet"]["n_devices"] = n
    replay = dict(conf["replay"])
    row = {"n_devices": n}
    frame, row["simulate_s"] = timed(lambda: cluster.simulate(conf))
    row["rows"] = len(frame)
    store, row["write_s"] = timed(
        lambda: cluster.write(conf, frame, seed, work / f"s{n}"))
    del frame
    big = [build(s) for s in grid10k.specs()]
    small = [build(s) for s in grid200.specs()]
    ir, row["ir_s"] = timed(lambda: get_ir(
        store, ir_config_for(big, dt_s=replay["dt_s"])))
    row["streams"] = len(ir.select())

    def ev():
        return evaluate(big, store, ir=ir, backend="jax", **replay)
    _, row["grid10k_evaluate_first_s"] = timed(ev)
    obs.clear_spans()
    _, row["grid10k_evaluate_s"] = timed(ev)
    row["grid10k_spans"] = _spans(("backend.kernels", "backend.assembly",
                                   "backend.pack"))
    if split:
        front, row["grid10k_run_sweep_s"] = timed(lambda: run_sweep(
            store, big, ir=ir, backend="jax", **replay))
        _, row["grid10k_find_knee_s"] = timed(lambda: find_knee(
            front.outcomes))
        _, row["grid10k_pareto_flags_s"] = timed(lambda: pareto_flags(
            [o.energy_saved_j for o in front.outcomes],
            [o.penalty_s for o in front.outcomes]))

    def sweep200():
        f = run_sweep(store, small, ir=ir, backend="jax", **replay)
        return find_knee(f.outcomes)
    _, row["grid200_first_s"] = timed(sweep200)
    row["grid200_s"] = min(timed(sweep200)[1] for _ in range(5))

    def search():
        return search_frontier(store, ir=ir, backend="jax", **replay)
    res, row["search_first_s"] = timed(search)
    row["search_s"] = min(timed(search)[1] for _ in range(2))
    row["memory_peak_bytes"] = memory_peak(devs)
    search_specs = [o.params for o in res.frontier.outcomes]
    root = store.root
    del store, ir, res
    free_device()

    fleet, row["ref_read_s"] = timed(
        lambda: R.Fleet(R.read_store(root), semantics(conf)))
    for name, specs in (("grid10k", grid10k.specs()),
                        ("grid200", grid200.specs()),
                        ("search", search_specs)):
        _, row[f"ref_{name}_s"] = timed(lambda: R.outcomes(fleet, specs))
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--devices", type=int, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    try:
        devs = devices_for(1, True)
    except BenchError as e:
        log(f"sizes: {e}")
        return 2
    import repro.obs as obs

    obs.enable()
    config = load_json(ROOT / "bench" / "configs" / f"{args.config}.json")
    for i, n in enumerate(args.devices):
        with tempfile.TemporaryDirectory(prefix="bench_sizes_") as work:
            row = measure(config, n, args.seed, pathlib.Path(work), i == 0,
                          devs)
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
